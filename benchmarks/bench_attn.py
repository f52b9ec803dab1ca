"""CI smoke cells for the fused sparse-attention sandwich (SDDMM →
in-register segment softmax → S·V through the descriptor stream,
DESIGN.md §13).

The fixture is the longformer mask the ``"sattn"`` model slot
actually builds (causal window + global columns,
``models/sparse_attention.py``), small enough for interpret-mode CPU:
the resident and ``_dma``-staged cells.

Cell naming follows benchmarks/common.py: the staging axis is the
``_dma`` bench-name suffix.  Dispatches per call come from
``DISPATCH_COUNTS["attn_fused"]`` — the Table IV one-launch-per-chip
invariant extended to attention — and each staged cell additionally
asserts it really took the DMA lowering.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

try:
    from .common import bench_record, time_fn
except ImportError:          # plain-script run: python benchmarks/...
    import pathlib
    import sys
    _ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(_ROOT / "src"))   # repro package
    sys.path.insert(0, str(_ROOT))           # benchmarks package
    from benchmarks.common import bench_record, time_fn

from repro.core import CSRMatrix, compile_sparse_attention
from repro.core.jit_cache import JitCache
from repro.kernels import ops
from repro.models.sparse_attention import sparse_attention_mask


def _qkv(a: CSRMatrix, dh: int, dv: int, seed: int):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((a.m, dh)), jnp.float32),
            jnp.asarray(rng.standard_normal((a.n, dh)), jnp.float32),
            jnp.asarray(rng.standard_normal((a.n, dv)), jnp.float32))


def _timed_cell(bench, strategy, backend, a, q, k, v, *, staging=None):
    """One attention smoke cell: compile, time, count launches."""
    c = compile_sparse_attention(a, q.shape[1], v.shape[1],
                                 strategy=strategy, backend=backend,
                                 interpret=True, staging=staging,
                                 cache=JitCache())
    vals = jnp.asarray(a.vals)
    ops.reset_dispatch_counts()
    # min-of-7 at warmup 2, like the SpMM cells: the gate compares at
    # 2x and the min filters interpret-mode scheduler spikes
    warmup, iters = 2, 7
    us = time_fn(c, vals, q, k, v, warmup=warmup, iters=iters,
                 stat="min")
    calls = warmup + iters
    if staging == "dma":
        assert ops.DISPATCH_COUNTS["attn_fused_dma"] > 0, \
            f"{bench}: staged cell fell back to the resident lowering"
    dispatches = ops.DISPATCH_COUNTS["attn_fused"] / calls
    return bench_record(bench, strategy, backend, 0, us / 1e3, dispatches)


def smoke_records() -> list:
    """CI bench-smoke cells (schema: benchmarks/common.py) for the
    fused attention hot path: wall per call + pallas launches per call
    on the resident AND DMA-staged lowerings."""
    a = sparse_attention_mask(96, 12, num_global=4)
    q, k, v = _qkv(a, 16, 16, seed=3)
    return [_timed_cell("attn_fused", "nnz_split", "pallas_bcsr",
                        a, q, k, v),
            _timed_cell("attn_fused_dma", "nnz_split", "pallas_bcsr",
                        a, q, k, v, staging="dma")]


if __name__ == "__main__":
    for r in smoke_records():
        print(f"{r['bench']}/{r['strategy']}/{r['backend']}"
              f"/c{r['n_chips']}: {r['wall_ms']:.3f}ms "
              f"{r['dispatches']:.0f} dispatch/call", flush=True)
