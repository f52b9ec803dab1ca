"""jit'd wrappers around the Pallas kernels.

``interpret`` resolves to False (native Mosaic kernels) on a TPU
backend and to True elsewhere: off the chip the kernels run in the
Pallas interpreter, which checks results, not speed.

Every wrapper records its launches in ``DISPATCH_COUNTS``, a plain host
counter incremented each time the wrapper's Python body runs: once per
call when it is called eagerly, and once per trace when a jitted caller
traces it (the caller's cached executions then count nothing).  The
fused-path tests use it to assert the Table IV invariant: one dispatch
per (matrix, d) instance, regardless of segment count — and on the
sharded path ``n_chips`` per forward (``shard_map`` traces the body
once and SPMD-replicates it; the wrapper counts all C).  A staged
stream whose descriptor tables exceed one call's SMEM is issued as
several calls, and each counts.
"""
from __future__ import annotations

import collections

import jax

from .attn_fused import attn_fused, attn_fused_sharded, attn_fused_staged
from .spmm_bcsr_fused import (spmm_bcsr_fused, spmm_bcsr_fused_sharded,
                              spmm_bcsr_fused_staged)
from .staging import chip_windows, num_calls

# name -> number of pallas_call dispatches issued (host-side; jit tracing
# reuses the compiled kernel but each op wrapper call is one dispatch)
DISPATCH_COUNTS: "collections.Counter[str]" = collections.Counter()

# The registry of every dispatch-count key any kernel entry point may
# increment.  tools/lint_invariants.py statically cross-checks the two
# directions: every ``DISPATCH_COUNTS[...] += `` site in src/ uses a
# literal key registered here, and every key here has at least one
# increment site — so a new kernel wrapper cannot ship an accounting
# key the Table IV tests (and the smoke-bench cells) don't know about,
# and a renamed wrapper cannot leave a stale key behind.
DISPATCH_KEYS = frozenset({
    # per-pallas_call invariant keys (one per plan, n_chips when sharded)
    "bcsr_fused", "attn_fused",
    # lowering-variant keys: WHICH path served a forward
    "bcsr_fused_dma", "bcsr_fused_sharded", "bcsr_fused_xshard",
    "attn_fused_dma", "attn_fused_sharded",
})

# kind -> accumulated host seconds spent building plans/packings (the
# paper's Table IV JIT-cost side, measurable per phase: "plan" covers
# build/merge/tag, "pack" the descriptor-table packing, "tune" the
# autotuner's search loop, "verify" the static plan verifier — §15's
# honest-cost cell; exactly 0.0 under validate="off").  Reset together
# with DISPATCH_COUNTS.
BUILD_SECONDS: "collections.Counter[str]" = collections.Counter()


def record_build_seconds(kind: str, seconds: float) -> None:
    """Accumulate host-side build cost under ``kind`` (see
    :data:`BUILD_SECONDS`)."""
    BUILD_SECONDS[kind] += float(seconds)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` around one stage of a call, for the
    JAX profiler to record when it traces: ``with span("spmm.kernel"):``.
    Names are fixed strings (``spmm.*``, ``attn.*``, ``spmm_batched.*``)
    so nothing is formatted per call; with the profiler off a span
    costs about a microsecond."""
    return jax.profiler.TraceAnnotation(name)


# fused-dispatch operand staging modes (DESIGN.md §7.7):
#   resident  streams scalar-prefetched into SMEM, X in VMEM — the
#             interpret-mode default, the bit-identity oracle, and on a
#             chip only for instances that fit its fast memories
#   dma       double-buffered per-trip window DMA from HBM — the
#             TPU default
STAGING_MODES = ("resident", "dma")


def reset_dispatch_counts() -> None:
    DISPATCH_COUNTS.clear()
    BUILD_SECONDS.clear()


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret=None) -> bool:
    """The effective interpret flag — resolved ONCE so jit-cache keys and
    kernel launches agree (a plan built for interpret=True must never be
    served to an interpret=False caller, and vice versa)."""
    return default_interpret() if interpret is None else bool(interpret)


def resolve_staging(staging=None, interpret=None) -> str:
    """The effective staging mode — resolved ONCE, same contract as
    :func:`resolve_interpret`: ``None``/``"auto"`` picks ``"dma"`` on a
    real TPU backend and ``"resident"`` under interpret mode (the
    emulated DMA engine is an oracle, not a win), and the resolved
    string is part of every jit-cache key that touches it."""
    if staging in (None, "auto"):
        return "resident" if resolve_interpret(interpret) else "dma"
    if staging not in STAGING_MODES:
        raise ValueError(
            f"staging must be 'auto' or one of {STAGING_MODES}, "
            f"got {staging!r}")
    return staging


def _resolve_op_staging(staging, interpret, span: int, cspan: int) -> str:
    """Wrapper-level resolution: the staged kernels need the planner's
    DMA windows, so a caller without them (a direct kernel-layer call
    that never built a workspace) must not be auto-routed onto the
    staged path with zero-size scratch — auto falls back to resident,
    and an EXPLICIT ``"dma"`` request without windows is an error."""
    if span > 0 and cspan > 0:
        return resolve_staging(staging, interpret)
    if staging == "dma":
        raise ValueError(
            "staging='dma' needs the workspace DMA windows "
            f"(span/cspan > 0, got span={span}, cspan={cspan}) — build "
            "them via build_fused_workspace / build_sharded_workspace")
    if staging not in (None, "auto", *STAGING_MODES):
        raise ValueError(
            f"staging must be 'auto' or one of {STAGING_MODES}, "
            f"got {staging!r}")
    return "resident"


def _launches(staging: str, num_blocks: int, mw: int) -> int:
    """``pallas_call`` launches one fused forward issues per chip: one,
    or for a staged stream longer than one call's SMEM tables, one per
    call (``staging.issue_in_calls``)."""
    return num_calls(num_blocks, mw) if staging == "dma" else 1


def attn_fused_op(blk_tag, blk_off, blk_coff, blk_L, cols_flat,
                  vals_flat, q_ws, k, v, cont=None, *, bm: int = 8,
                  bk: int = 8,
                  mw: int = 1, vpu: bool = True, interpret=None,
                  staging=None, span: int = 0, cspan: int = 0):
    """ONE dispatch for the whole sparse-attention sandwich (SDDMM →
    masked softmax → SpMM, DESIGN.md §13); staged launches also count
    under ``attn_fused_dma`` — the same accounting shape as the SpMM
    wrappers so the Table IV invariant tests extend unchanged."""
    interpret = resolve_interpret(interpret)
    staging = _resolve_op_staging(staging, interpret, span, cspan)
    DISPATCH_COUNTS["attn_fused"] += 1
    if staging == "dma":
        DISPATCH_COUNTS["attn_fused_dma"] += 1
        return attn_fused_staged(blk_tag, blk_off, blk_coff, blk_L,
                                 cols_flat, vals_flat, q_ws, k, v, cont,
                                 span=span, cspan=cspan, bm=bm, bk=bk,
                                 mw=mw, vpu=vpu, interpret=interpret)
    return attn_fused(blk_tag, blk_off, blk_coff, blk_L, cols_flat,
                      vals_flat, q_ws, k, v, cont, bm=bm, bk=bk, mw=mw,
                      vpu=vpu, interpret=interpret)


def attn_fused_sharded_op(blk_tag, blk_off, blk_coff, blk_L, cols_flat,
                          vals_flat, q_ws, k, v, cont=None, *, mesh,
                          bm: int = 8,
                          bk: int = 8, mw: int = 1, vpu: bool = True,
                          interpret=None, staging=None, span=0, cspan=0):
    """One fused attention dispatch per chip: counts ``mesh.size``
    pallas_calls under ``attn_fused`` plus one ``attn_fused_sharded``
    wrapper call, ``mesh.size`` under ``attn_fused_dma`` when staged —
    K/V are replicated, so there is no ``_xshard`` variant here."""
    interpret = resolve_interpret(interpret)
    span = chip_windows(span, mesh.size)
    cspan = chip_windows(cspan, mesh.size)
    staging = _resolve_op_staging(staging, interpret, min(span),
                                  min(cspan))
    DISPATCH_COUNTS["attn_fused"] += mesh.size
    DISPATCH_COUNTS["attn_fused_sharded"] += 1
    if staging == "dma":
        DISPATCH_COUNTS["attn_fused_dma"] += mesh.size
    else:
        span = cspan = (0,) * mesh.size   # resident ignores the windows
    return attn_fused_sharded(blk_tag, blk_off, blk_coff, blk_L,
                              cols_flat, vals_flat, q_ws, k, v, cont,
                              mesh=mesh, bm=bm, bk=bk, mw=mw, vpu=vpu,
                              interpret=interpret, staging=staging,
                              span=span, cspan=cspan)


def spmm_bcsr_fused_op(blk_tag, blk_off, blk_coff, blk_L, cols_flat,
                       vals_flat, x, cont=None, *, bm: int = 8, bk: int = 8,
                       mw: int = 1, interpret=None, staging=None,
                       span: int = 0, cspan: int = 0):
    """ONE dispatch for a whole mixed VPU/MXU plan (Table IV invariant,
    now covering the MXU block-rows as well); staged launches also
    count under ``bcsr_fused_dma``."""
    interpret = resolve_interpret(interpret)
    staging = _resolve_op_staging(staging, interpret, span, cspan)
    n = _launches(staging, blk_off.shape[0], mw)
    DISPATCH_COUNTS["bcsr_fused"] += n
    if staging == "dma":
        DISPATCH_COUNTS["bcsr_fused_dma"] += n
        return spmm_bcsr_fused_staged(blk_tag, blk_off, blk_coff, blk_L,
                                      cols_flat, vals_flat, x, cont,
                                      span=span, cspan=cspan, bm=bm, bk=bk,
                                      mw=mw, interpret=interpret)
    return spmm_bcsr_fused(blk_tag, blk_off, blk_coff, blk_L, cols_flat,
                           vals_flat, x, cont, bm=bm, bk=bk, mw=mw,
                           interpret=interpret)


def spmm_bcsr_fused_sharded_op(blk_tag, blk_off, blk_coff, blk_L,
                               cols_flat, vals_flat, x, cont=None, *, mesh,
                               bm: int = 8, bk: int = 8, mw: int = 1,
                               interpret=None,
                               staging=None, span=0, cspan=0,
                               x_sharding: str = "replicated",
                               x_send=None, x_recv=None):
    """One mixed fused dispatch per chip: counts ``mesh.size``
    pallas_calls under the ``bcsr_fused`` key plus one
    ``bcsr_fused_sharded`` wrapper call, ``mesh.size`` under
    ``bcsr_fused_dma`` when staged, and ``mesh.size`` under
    ``bcsr_fused_xshard`` when X is row-sharded (the fetch-table
    exchange path; ``span``/``cspan`` accept per-chip tuples)."""
    interpret = resolve_interpret(interpret)
    span = chip_windows(span, mesh.size)
    cspan = chip_windows(cspan, mesh.size)
    staging = _resolve_op_staging(staging, interpret, min(span),
                                  min(cspan))
    n = mesh.size * _launches(staging, blk_off.shape[1], mw)
    DISPATCH_COUNTS["bcsr_fused"] += n
    DISPATCH_COUNTS["bcsr_fused_sharded"] += 1
    if x_sharding == "rows":
        DISPATCH_COUNTS["bcsr_fused_xshard"] += n
    if staging == "dma":
        DISPATCH_COUNTS["bcsr_fused_dma"] += n
    else:
        span = cspan = (0,) * mesh.size   # resident ignores the windows
    return spmm_bcsr_fused_sharded(blk_tag, blk_off, blk_coff, blk_L,
                                   cols_flat, vals_flat, x, cont, mesh=mesh,
                                   bm=bm, bk=bk, mw=mw,
                                   interpret=interpret,
                                   staging=staging, span=span, cspan=cspan,
                                   x_sharding=x_sharding, x_send=x_send,
                                   x_recv=x_recv)
