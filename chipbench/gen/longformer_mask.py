"""The attention pattern of Longformer (Beltagy et al., arXiv:2004.05150):
a two-sided sliding window plus global tokens, as a CSR structure.

Parameters (the ``structure`` block of a configuration file):

    seq_len            queries and keys
    attention_window   the published two-sided window: query i sees keys
                       i - w .. i + w with w = attention_window // 2
    global_tokens      the first ``global_tokens`` positions attend to
                       every key, and every query attends to them

Columns are sorted within each row.  The structure takes no seed.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int = 0):
    S = int(params["seq_len"])
    w = int(params["attention_window"]) // 2
    g = min(int(params["global_tokens"]), S)
    i = np.arange(S, dtype=np.int64)
    lo = np.maximum(i - w, g)               # window part past the globals
    hi = np.minimum(i + w, S - 1)
    win = np.maximum(hi - lo + 1, 0)
    # a global row sees every key; any other row its globals + window
    length = np.where(i < g, S, g + win)
    row_ptr = np.zeros(S + 1, np.int64)
    np.cumsum(length, out=row_ptr[1:])
    # position of each entry within its row, then its column
    pos = np.arange(row_ptr[-1], dtype=np.int64) - np.repeat(row_ptr[:-1],
                                                             length)
    row = np.repeat(i, length)
    cols = np.where((row < g) | (pos < g), pos, lo[row] + pos - g)
    return row_ptr, cols.astype(np.int32), (S, S)
