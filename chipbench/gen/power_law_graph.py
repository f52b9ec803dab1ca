"""A directed graph with power-law out-degrees and power-law column
popularity, at an exact node and edge count, in vectorised numpy.

Parameters (the ``structure`` block of a configuration file):

    nodes              rows and columns of the adjacency matrix
    edges              nonzeros after duplicates and self-loops are gone
    out_degree_shape   Lomax shape a of the out-degree draw: tail
                       exponent a + 1 of the degree distribution
    out_degree_cap     the largest out-degree a row may have
    in_rank_exponent   s in P(column of popularity rank r) ~ r^-s:
                       in-degree tail exponent 1 + 1/s

Out-degrees are Lomax draws scaled to the edge count, floored, capped,
and the remainder spread one edge at a time over random rows below the
cap.  Each row then draws its columns from the popularity law (ranks
mapped to node ids by a random permutation); draws that repeat an edge
the row has or point at the row itself are dropped and drawn again
until every row holds its degree.  The structure seed fixes everything.
"""
from __future__ import annotations

import numpy as np


def _out_degrees(rng, n: int, edges: int, shape: float, cap: int):
    cap = min(int(cap), n - 1)
    if edges > n * cap:
        raise ValueError(f"{edges} edges do not fit {n} rows of at most "
                         f"{cap}")
    raw = (1.0 - rng.random(n)) ** (-1.0 / shape) - 1.0
    deg = np.minimum(np.floor(raw * (edges / raw.sum())), cap).astype(
        np.int64)
    while (short := edges - int(deg.sum())) > 0:
        room = np.flatnonzero(deg < cap)
        deg[rng.choice(room, size=min(short, room.size), replace=False)] += 1
    return deg


def _popular_columns(rng, count: int, n: int, s: float, col_of_rank):
    """``count`` columns drawn by the continuous inverse CDF of r^-s on
    [1, n + 1), floored to a rank."""
    u = rng.random(count)
    if s == 1.0:
        r = np.exp(u * np.log(n + 1.0))
    else:
        e = 1.0 - s
        r = (u * ((n + 1.0) ** e - 1.0) + 1.0) ** (1.0 / e)
    return col_of_rank[np.minimum(r.astype(np.int64), n) - 1]


def _known(sorted_parts, keys):
    """Whether each key is already in one of the sorted key arrays."""
    seen = np.zeros(keys.size, bool)
    for part in sorted_parts:
        if part.size == 0:
            continue
        i = np.minimum(np.searchsorted(part, keys), part.size - 1)
        seen |= part[i] == keys
    return seen


def generate(params: dict, seed: int):
    """Returns ``(row_ptr int64 (n+1,), cols int32 (edges,), (n, n))``
    with columns sorted within each row."""
    n, edges = int(params["nodes"]), int(params["edges"])
    rng = np.random.default_rng(seed)
    deg = _out_degrees(rng, n, edges, float(params["out_degree_shape"]),
                       int(params["out_degree_cap"]))
    col_of_rank = rng.permutation(n).astype(np.int64)
    parts, need = [], deg
    while need.any():
        rows = np.repeat(np.arange(n, dtype=np.int64), need)
        cols = _popular_columns(rng, rows.size, n,
                                float(params["in_rank_exponent"]),
                                col_of_rank)
        keys = np.unique((rows * n + cols)[cols != rows])
        if parts:
            keys = keys[~_known(parts, keys)]
        parts.append(keys)
        need = need - np.bincount(keys // n, minlength=n)
    keys = np.sort(np.concatenate(parts)) if len(parts) > 1 else parts[0]
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=row_ptr[1:])
    return row_ptr, (keys % n).astype(np.int32), (n, n)
