"""Runtime-feedback autotuner for the fused SpMM dispatch.

The paper's JIT thesis is that the *instance* should pick the code
shape; the plan pipeline (DESIGN.md §7.9) already exposes the knobs —
``strategy`` (row/nnz/merge split), ``bm``/``bk`` tiling, ``mxu_gain``
tagging, the CGCM ``merge_threshold`` and the operand ``staging`` mode.
This module closes the loop in two stages (DESIGN.md §11):

  predict  rank every candidate :class:`TuneConfig` with the analytic
           roofline terms (the device row of ``repro.platform`` +
           ``analysis.memmodel.spmm_hbm_traffic`` on the candidate's
           OWN packed workspace) plus a per-grid-step launch overhead —
           the term CGCM merging shrinks.  Host-only, no compilation.
  measure  compile the top-K predicted candidates through
           ``compile_spmm`` (same jit cache — the search warms it) and
           time real forwards; the measurement hook is injectable so
           tests run on a deterministic fake timer.

The winning config is memoized in the :class:`~repro.core.jit_cache.
JitCache` under a ``("spmm_tune", ...)`` key, so the search cost
amortizes across recompiles exactly like the paper's Table IV codegen
cost — the second ``autotune=True`` compile is a cache hit and runs no
search at all.  Search wall-time is surfaced through
``kernels.ops.BUILD_SECONDS["tune"]``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .csr import CSRMatrix
from .jit_cache import GLOBAL_CACHE, JitCache, mesh_fingerprint
from .plan import build_workspace
from ..analysis.memmodel import spmm_hbm_traffic
from ..platform import current_spec, resident_fits

# amortized per-grid-step launch/descriptor overhead (s).  The absolute
# value only has to be the right order of magnitude: it breaks ties
# between plans whose streamed bytes are close, in favor of fewer
# merged trips — exactly the skew CGCM targets.
TRIP_OVERHEAD_S = 2e-6

STRATEGIES = ("row_split", "nnz_split", "merge_split")


@dataclasses.dataclass(frozen=True)
class TuneConfig:
    """One point of the search space — the per-instance knobs the
    dispatch stack bakes into its jit-cache keys."""
    strategy: str = "nnz_split"
    bm: int = 8
    bk: int = 8
    mxu_gain: float = 4.0
    merge_threshold: int = 0
    staging: str = "resident"

    def compile_kwargs(self) -> dict:
        return {"strategy": self.strategy, "bm": self.bm, "bk": self.bk,
                "mxu_gain": self.mxu_gain,
                "merge_threshold": self.merge_threshold,
                "staging": self.staging}


@dataclasses.dataclass
class TuneResult:
    """The memoized outcome of one search: the winner plus the full
    ranking (predicted seconds for every candidate, measured seconds
    for the finalists) for introspection and the bench tables."""
    config: TuneConfig
    predicted_s: dict           # TuneConfig -> predicted seconds
    measured_s: dict            # TuneConfig -> measured seconds (top-K)
    tune_seconds: float = 0.0

    @property
    def best_measured_s(self) -> float:
        return self.measured_s[self.config]


def default_candidates(*, bm: int = 8, bk: int = 8,
                       mxu_gain: float = 4.0,
                       staging: str = "resident",
                       merge_thresholds: Sequence[int] = (0, 8, 32)
                       ) -> List[TuneConfig]:
    """The default grid: every strategy × CGCM threshold at the caller's
    tiling/staging.  Callers with wider budgets pass their own list
    (any ``TuneConfig`` field may vary — bm/bk/mxu_gain/staging
    included); the default keeps the measured stage to a handful of
    compiles so autotuning stays cheaper than one training step."""
    return [TuneConfig(strategy=s, bm=bm, bk=bk, mxu_gain=mxu_gain,
                       merge_threshold=t, staging=staging)
            for s in STRATEGIES for t in merge_thresholds]


def predict_seconds(a: CSRMatrix, d: int, cfg: TuneConfig, *,
                    native: bool = False) -> float:
    """Analytic forward-time estimate for one candidate: the roofline
    max of compute and HBM terms on the candidate's own packed
    workspace, plus the per-trip launch overhead.  Host-only.  With
    ``native`` (a compiled, not interpreted, run) a resident candidate
    whose buffers exceed the chip's fast memories predicts ``inf``:
    it is not offered."""
    ws = build_workspace(
        a.row_ptr, a.col_indices, a.shape, d, strategy=cfg.strategy,
        row_block=cfg.bm, bk=cfg.bk, mxu_gain=cfg.mxu_gain,
        merge_threshold=cfg.merge_threshold)
    d_pad = max(-(-d // 128) * 128, 128)
    if native and cfg.staging == "resident" and not resident_fits(
            ws.num_blocks, ws.gather_flat.size, ws.cols_flat.size,
            4 * a.shape[1] * min(d_pad, 512)):
        return float("inf")
    traffic = spmm_hbm_traffic(
        slots=int(ws.gather_flat.shape[0]),
        cols_entries=int(ws.cols_flat.shape[0]),
        padded_nnz=int(ws.gather_flat.shape[0]),
        ws_rows=ws.ws_rows, d_pad=d_pad)
    spec = current_spec()
    compute_s = 2.0 * a.nnz * d / spec.peak_bf16_flops
    memory_s = sum(traffic.values()) / spec.hbm_bytes_per_s
    return max(compute_s, memory_s) + ws.num_trips * TRIP_OVERHEAD_S


def spmm_tune_key(a: CSRMatrix, d: int, *, backend: str, interpret: bool,
                  x_sharding: str, mesh,
                  candidates: Sequence[TuneConfig],
                  top_k: int = 3) -> Tuple:
    """The memoization key for one search — factored out so the batched
    knob resolver (DESIGN.md §14.3) can *peek* a member's winner with
    exactly the key its solo warmup used.

    ``top_k`` is part of the search's identity, not a pass-through
    detail: it sets which predicted candidates get MEASURED, so two
    searches over the same candidate list with different ``top_k`` can
    crown different winners (a mispredicted-but-fast config only wins
    if the measurement stage reaches it)."""
    return ("spmm_tune", a.fingerprint, d, backend, interpret, x_sharding,
            mesh_fingerprint(mesh),
            tuple(dataclasses.astuple(c) for c in candidates),
            max(int(top_k), 1))


def lookup_tune_result(a: CSRMatrix, d: int, *, backend: str,
                       interpret: bool, x_sharding: str = "replicated",
                       mesh=None,
                       candidates: Sequence[TuneConfig],
                       top_k: int = 3,
                       cache: JitCache = GLOBAL_CACHE
                       ) -> Optional[TuneResult]:
    """The memoized :class:`TuneResult` for one instance, or ``None``
    when its search has not run (or was evicted).  Never builds and
    never touches cache stats/recency — safe to call on the dispatch
    path."""
    key = spmm_tune_key(a, d, backend=backend, interpret=interpret,
                        x_sharding=x_sharding, mesh=mesh,
                        candidates=list(candidates), top_k=top_k)
    return cache.peek(key)


def resolve_batch_config(results: Sequence[Optional[TuneResult]],
                         fallback: TuneConfig) -> TuneConfig:
    """One static configuration for a batched dispatch from the
    members' memoized solo winners (DESIGN.md §14.3).

    The batched artifact needs ONE knob set, so per-member winners are
    folded: ``strategy``/``bm``/``bk``/``mxu_gain``/``staging`` by
    majority vote (ties broken toward the fallback, then toward the
    earliest member — deterministic for a given batch composition) and
    ``merge_threshold`` by *min* — the conservative CGCM bound, since
    the packer already coerces the batch to the minimum member width
    and a low threshold never merges more than a high one would.
    Members with no memoized result (search not run yet, or evicted)
    vote for the fallback.
    """
    votes = [r.config if r is not None else fallback for r in results]
    if not votes:
        return fallback

    def _majority(field: str):
        tally: dict = {}
        order: list = []
        for v in votes:
            val = getattr(v, field)
            if val not in tally:
                order.append(val)
            tally[val] = tally.get(val, 0) + 1
        best = max(tally.values())
        tied = [val for val in order if tally[val] == best]
        fb = getattr(fallback, field)
        return fb if fb in tied else tied[0]

    return TuneConfig(
        strategy=_majority("strategy"), bm=_majority("bm"),
        bk=_majority("bk"), mxu_gain=_majority("mxu_gain"),
        merge_threshold=min(v.merge_threshold for v in votes),
        staging=_majority("staging"))


def _wall_time_measure(compiled, vals, x, *, repeats: int = 3) -> float:
    """Default measurement hook: min-of-N blocked wall time after one
    warmup forward (which also pays tracing/compilation, keeping it out
    of the timed region)."""
    jax.block_until_ready(compiled(vals, x))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(vals, x))
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_spmm(a: CSRMatrix, d: int, *, backend: str = "auto",
                  bm: int = 8, bk: int = 8, mxu_gain: float = 4.0,
                  interpret: Optional[bool] = None,
                  mesh=None, n_chips: Optional[int] = None,
                  staging: Optional[str] = None,
                  x_sharding: Optional[str] = None,
                  validate: Optional[str] = None,
                  candidates: Optional[Sequence[TuneConfig]] = None,
                  measure: Optional[Callable] = None, top_k: int = 3,
                  cache_priority: float = 0.0,
                  cache: JitCache = GLOBAL_CACHE):
    """Search the plan space for this instance and return the winning
    compiled artifact (``compile_spmm`` of the winner — a jit-cache hit
    when the search already ran).  ``measure(compiled, vals, x) ->
    seconds`` is injectable for deterministic tests."""
    compiled, _ = autotune_spmm_with_result(
        a, d, backend=backend, bm=bm, bk=bk, mxu_gain=mxu_gain,
        interpret=interpret, mesh=mesh, n_chips=n_chips, staging=staging,
        x_sharding=x_sharding, validate=validate, candidates=candidates,
        measure=measure,
        top_k=top_k, cache_priority=cache_priority, cache=cache)
    return compiled


def autotune_spmm_with_result(
        a: CSRMatrix, d: int, *, backend: str = "auto", bm: int = 8,
        bk: int = 8, mxu_gain: float = 4.0,
        interpret: Optional[bool] = None, mesh=None,
        n_chips: Optional[int] = None, staging: Optional[str] = None,
        x_sharding: Optional[str] = None,
        validate: Optional[str] = None,
        candidates: Optional[Sequence[TuneConfig]] = None,
        measure: Optional[Callable] = None, top_k: int = 3,
        cache_priority: float = 0.0,
        cache: JitCache = GLOBAL_CACHE) -> Tuple[object, TuneResult]:
    """:func:`autotune_spmm` plus the full :class:`TuneResult` (the
    bench tables report the per-candidate rankings)."""
    from .spmm import (_resolve_backend, _resolve_staging_for,
                       _resolve_x_sharding_for, compile_spmm,
                       resolve_chip_mesh)
    from ..analysis.verify import resolve_validate
    from ..kernels.ops import record_build_seconds, resolve_interpret

    backend = _resolve_backend(
        backend, sharded=mesh is not None or n_chips is not None)
    if backend != "pallas_bcsr":
        raise ValueError(
            f"autotune searches the fused plan space (pallas_bcsr); "
            f"backend={backend!r} has nothing to tune")
    interpret = resolve_interpret(interpret)
    # validate never joins the tune key: verification cannot change a
    # search's winner (it only gates compilation), so fragmenting the
    # memoized TuneResult on it would re-run identical searches
    validate = resolve_validate(validate, interpret)
    staging_r = _resolve_staging_for(backend, staging, interpret)
    mesh = resolve_chip_mesh(mesh, n_chips)
    x_sharding = _resolve_x_sharding_for(backend, x_sharding, interpret,
                                         mesh)
    if candidates is None:
        candidates = default_candidates(bm=bm, bk=bk, mxu_gain=mxu_gain,
                                        staging=staging_r)
    candidates = list(candidates)
    if not candidates:
        raise ValueError("autotune needs at least one candidate config")
    measure = measure or _wall_time_measure

    key = spmm_tune_key(a, d, backend=backend, interpret=interpret,
                        x_sharding=x_sharding, mesh=mesh,
                        candidates=candidates, top_k=top_k)

    def _search() -> TuneResult:
        t0 = time.perf_counter()
        predicted = {c: predict_seconds(a, d, c, native=not interpret)
                     for c in candidates}
        ranked = sorted((c for c in candidates
                         if predicted[c] < float("inf")),
                        key=lambda c: predicted[c])
        if not ranked:
            raise ValueError("no candidate fits the chip's fast memories")
        finalists = ranked[:max(int(top_k), 1)]
        vals = jnp.asarray(a.vals)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((a.shape[1], d)), jnp.float32)
        measured = {}
        for c in finalists:
            compiled_c = compile_spmm(
                a, d, backend=backend, interpret=interpret, mesh=mesh,
                x_sharding=x_sharding, validate=validate, cache=cache,
                **c.compile_kwargs())
            measured[c] = float(measure(compiled_c, vals, x))
        # stable tie-break: measured time, then predicted rank — a
        # constant fake timer degenerates to the predicted order
        winner = min(finalists,
                     key=lambda c: (measured[c], predicted[c]))
        res = TuneResult(config=winner, predicted_s=predicted,
                         measured_s=measured,
                         tune_seconds=time.perf_counter() - t0)
        record_build_seconds("tune", res.tune_seconds)
        return res

    result: TuneResult = cache.get_or_build(key, _search,
                                            priority=cache_priority)
    compiled = compile_spmm(
        a, d, backend=backend, interpret=interpret, mesh=mesh,
        x_sharding=x_sharding, validate=validate,
        cache_priority=cache_priority,
        cache=cache, **result.config.compile_kwargs())
    return compiled, result
