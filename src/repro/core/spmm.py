"""Public JIT-SpMM API: Y = A·X specialized to the runtime instance.

``compile_spmm`` is the paper's "JIT code generator": given the concrete
structure of A and the runtime-known d, it builds (or fetches from the
jit cache) a ``CompiledSpmm`` — plan + device constants + differentiable
callable.  ``spmm`` is the one-shot convenience wrapper.

Backends:
  pallas_bcsr  the fused dispatch: each bm-aligned row-block is tagged
               VPU (the paper's CCM ELL gather+FMA) or MXU ((bm x bk)
               block matmuls) at plan time (``build_mixed_plan``;
               ``mxu_gain`` tunes the tagging, and ``mxu_gain=0`` tags
               every block VPU), and the whole plan is ONE pallas_call
               via a descriptor table + one inverse-permutation gather
               (validated in interpret mode on CPU; native on TPU).
               With ``mesh`` / ``n_chips`` the rows are partitioned
               across chips at block-row boundaries
               (``partition_rows_for_chips``) and each chip runs its
               shard as one pallas_call under shard_map.
  ref          pure-jnp gather/segment-sum (jit-friendly; used inside
               the model stack and the 512-device dry-run)
  dense        densified matmul (tiny tests only)
  auto         pallas_bcsr on a TPU or when sharding is asked for, ref
               otherwise

The fused backend takes a ``staging`` knob (DESIGN.md §7.7):
``"resident"`` (whole flat slot buffer + X panel in VMEM — the
interpret-mode default and bit-identity oracle) or ``"dma"``
(double-buffered per-block slot-panel DMA, the TPU default), resolved
once and baked into the jit-cache key like ``interpret``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from . import ccm
from .csr import CSRMatrix
from .jit_cache import GLOBAL_CACHE, JitCache, mesh_fingerprint
from .plan import (MXU_TAG, PANEL_EDGES, SPARSE_ATTN_MIXED_EINSUM,
                   VPU_TAG, BatchedFusedWorkspace, MixedPlan,
                   ShardedFusedWorkspace, build_batched_workspace,
                   build_einsum_workspace, build_fused_workspace,
                   build_mixed_plan, build_sharded_workspace,
                   choose_merge_width, choose_panel_edge, panel_stats,
                   sharded_workspace_row_maps, workspace_row_map)
from ..analysis.verify import (PlanVerificationError, check_workspace,
                               resolve_validate)
from ..kernels import ops as kops
from ..kernels.ops import resolve_interpret, resolve_staging, span
from ..platform import LANE, STAGE_TILE, resident_fits

__all__ = [
    "BACKENDS", "X_SHARDING_MODES",
    "CompiledSpmm", "CompiledBatchedSpmm", "CompiledSparseAttention",
    "PlanVerificationError", "chip_mesh", "resolve_chip_mesh",
    "compile_spmm", "compile_batched_spmm", "compile_sparse_attention",
    "spmm", "sparse_attention",
]

# "pallas_bcsr" is the fused descriptor-table dispatch, the one backend
# with mesh/n_chips sharding and the staging/x_sharding knobs
BACKENDS = ("pallas_bcsr", "ref", "dense", "auto")

# X placement on the sharded fused path (DESIGN.md §7.8):
#   replicated  every chip holds all of X (the PR 2 layout) — n·d_pad
#               is bounded by ONE chip's HBM
#   rows        X rows are split into bk-row panels owned contiguously
#               by chips; each chip fetches exactly the panels its
#               descriptor stream touches via the planner's exact-panel
#               exchange — instance size scales with the mesh
X_SHARDING_MODES = ("replicated", "rows")

# nonzeros per step of the gradient's SDDMM: bounds its gathered
# (chunk, d) operands to a few hundred MB at d <= 256
_SDDMM_CHUNK = 1 << 18


def _resolve_x_sharding_for(backend: str, x_sharding, interpret: bool,
                            mesh) -> str:
    """The effective X placement — resolved ONCE, same contract as the
    staging knob: ``None``/``"auto"`` picks ``"rows"`` on a real multi-
    chip mesh (the scale default) and ``"replicated"`` under interpret
    mode or single-chip/unsharded dispatch; the resolved string joins
    every jit-cache key that touches it (including the transpose
    artifact).  ``"rows"`` without a mesh, or any non-replicated value
    on a non-fused backend, is an error — the knob only exists where
    the fetch-table machinery does."""
    if backend == "pallas_bcsr":
        if x_sharding in (None, "auto"):
            if mesh is not None and mesh.size > 1 and not interpret:
                return "rows"
            return "replicated"
        if x_sharding not in X_SHARDING_MODES:
            raise ValueError(
                f"x_sharding must be 'auto' or one of {X_SHARDING_MODES}, "
                f"got {x_sharding!r}")
        if x_sharding == "rows" and mesh is None:
            raise ValueError(
                "x_sharding='rows' shards X over the chip mesh — pass "
                "mesh= or n_chips= (unsharded dispatch has no chips to "
                "own X panels)")
        return x_sharding
    if x_sharding not in (None, "auto", "replicated"):
        raise ValueError(
            f"x_sharding is a fused-dispatch knob (pallas_bcsr); "
            f"backend={backend!r} has no sharded lowering")
    return "replicated"


def _resolve_staging_for(backend: str, staging, interpret: bool) -> str:
    """Per-backend staging resolution: the knob only exists on the fused
    dispatch, so non-fused backends pin ``"resident"`` (and reject an
    explicit ``"dma"`` the way single-device backends reject a mesh) —
    keeping ref/dense cache keys independent of a knob they ignore."""
    if backend == "pallas_bcsr":
        return resolve_staging(staging, interpret)
    if staging not in (None, "auto", "resident"):
        raise ValueError(
            f"staging is a fused-dispatch knob (pallas_bcsr); "
            f"backend={backend!r} has no staged lowering")
    return "resident"


def _resolve_backend(backend: str, *, sharded: bool = False) -> str:
    """The effective backend: ``"auto"`` is the fused dispatch on a TPU,
    and off it wherever sharding is asked for (mesh/n_chips is a
    fused-path feature, run in interpret mode on CPU); otherwise the
    jnp ``ref`` backend.  A name outside :data:`BACKENDS` is an error
    here, before anything is built."""
    if backend not in BACKENDS:
        raise ValueError(
            f"backend must be one of {BACKENDS}, got {backend!r}")
    if backend != "auto":
        return backend
    if sharded or jax.default_backend() == "tpu":
        return "pallas_bcsr"
    return "ref"


def chip_mesh(n_chips: int) -> Mesh:
    """1-D ``("chips",)`` mesh over the first ``n_chips`` local devices —
    the data mesh the sharded fused path partitions rows over."""
    devs = jax.devices()
    if not 1 <= n_chips <= len(devs):
        raise ValueError(
            f"n_chips={n_chips} but {len(devs)} device(s) available")
    return Mesh(np.asarray(devs[:n_chips]), ("chips",))


def resolve_chip_mesh(mesh: Optional[Mesh],
                      n_chips: Optional[int]) -> Optional[Mesh]:
    """Normalize the two spellings of "shard over C chips" to a concrete
    1-D mesh (or None = unsharded), so cache keys and compiled artifacts
    agree whichever the caller used."""
    if mesh is None and n_chips is None:
        return None
    if mesh is not None:
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"sharded spmm needs a 1-D mesh, got axes {mesh.axis_names}")
        if n_chips is not None and n_chips != mesh.size:
            raise ValueError(f"n_chips={n_chips} != mesh size {mesh.size}")
        return mesh
    return chip_mesh(n_chips)


def _record_build(plan_seconds: float, pack_seconds: float) -> None:
    """Surface host-side plan/pack cost through the dispatch-count
    plumbing (the Table IV JIT-cost side — ``bench_codegen_overhead``
    reads these to show the amortization story for the tuned path)."""
    from ..kernels.ops import record_build_seconds
    record_build_seconds("plan", plan_seconds)
    record_build_seconds("pack", pack_seconds)


def _verify_workspace_timed(ws, *, level: str, context: str,
                            **kwargs) -> None:
    """Run the static verifier (DESIGN.md §15) over a freshly packed
    workspace BEFORE any device constants are built, raising
    :class:`PlanVerificationError` on a malformed plan.  The host cost
    lands in ``BUILD_SECONDS["verify"]`` next to plan/pack, so the
    codegen bench can show ``validate="off"`` contributes exactly 0.0
    to the dispatch path."""
    if level == "off":
        return
    from ..kernels.ops import record_build_seconds
    t0 = time.perf_counter()
    try:
        check_workspace(ws, level=level, context=context, **kwargs)
    finally:
        record_build_seconds("verify", time.perf_counter() - t0)


@dataclasses.dataclass
class _FusedConsts:
    """Device-resident fused-plan constants: ONE descriptor table + flat
    slot arrays for all segments, so the forward pass is a single
    pallas_call plus one inverse-permutation gather (no per-segment
    dispatch loop, no scatters)."""
    blk_tag: jax.Array       # (B,) int32 — VPU/MXU tag
    blk_off: jax.Array       # (B,) int32 — first slot per row-block
    blk_coff: jax.Array      # (B,) int32 into cols_flat
    blk_L: jax.Array         # (B,) int32 — loop trips per row-block
    cols_flat: jax.Array     # (Sc,) int32 — X row / block-column stream
    vals: "_SlotValues"      # how the (S,) slot-value stream is staged
    inv_perm: jax.Array      # (m,) int32 — output row -> workspace row
    num_blocks: int
    max_span: int = 0        # staged-DMA slot window (DESIGN.md §7.7)
    max_cspan: int = 0       # staged-DMA cols window
    merge_width: int = 1     # CGCM width (DESIGN.md §7.9)
    cont: Optional[jax.Array] = None   # (B//W,) int32 piece trips
    vpu_steps: int = 0       # loop steps of the VPU descriptors per call

    @property
    def gather_flat(self) -> jax.Array:
        """``concat(vals,[0])`` indices of the element-gathered prefix
        of the slot stream: the whole stream only when ``vals.lanes``
        is None (a plan without MXU panels); see ``vals.slots``."""
        return self.vals.gather


def _require_resident_fit(staging: str, interpret: bool, ws,
                          operand_bytes: int, context: str) -> None:
    """The resident lowering is the interpret oracle and the small-
    instance path: compiled for a chip, its buffers must fit the fast
    memories (``repro.platform.resident_fits``)."""
    if staging != "resident" or interpret:
        return
    slots, cols = ws.gather_flat.shape[-1], ws.cols_flat.shape[-1]
    if not resident_fits(ws.num_blocks, slots, cols, operand_bytes):
        raise ValueError(
            f"{context}: staging='resident' keeps {slots} slots and "
            f"{cols} column entries in SMEM and {operand_bytes} operand "
            f"bytes in VMEM, more than this chip holds; use "
            f"staging='dma' (the default on a TPU)")


def _tiles(a: np.ndarray, fill) -> np.ndarray:
    """A flat stream (or a per-chip stack of them) padded to whole
    tiles: the kernels view streams as (rows, LANE) arrays."""
    pad = -a.shape[-1] % STAGE_TILE
    return np.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)],
                  constant_values=fill)


def _put(a: np.ndarray, sharding=None) -> jax.Array:
    return jax.device_put(a.astype(np.int32) if a.dtype == np.int64 else a,
                          sharding)


def _stream(a: np.ndarray, fill, sharding=None) -> jax.Array:
    """Device copy of a stream padded to whole tiles."""
    return _put(_tiles(a, fill), sharding)


# lax.gather of single elements of a 1-D operand at (..., 1) indices
_TAKE = lax.GatherDimensionNumbers(offset_dims=(), collapsed_slice_dims=(0,),
                                   start_index_map=(0,))


@dataclasses.dataclass
class _SlotValues:
    """How a call stages the kernel's slot-value stream, which equals
    ``concat(vals, [0])[gather_flat]`` bit for bit (DESIGN.md §7.7).

    A plan with MXU panels stores each ``(bm, bk)`` value panel
    lane-padded to ``(bm, LANE)``: lanes ``bk..LANE-1`` name the zero
    sentinel.  Each member's stream (the plan's, a chip's, a request's)
    is viewed as ``(rows, LANE)``: the slots before the first row after
    which no row has a live lane past ``bk`` are one element gather,
    the rows from there to the last row with a live lane gather their
    first ``bk`` lanes alone and are padded back to ``LANE`` with zeros,
    and the all-sentinel rows after them are zeros.  A plan without MXU
    panels is one element gather of the whole stream."""
    gather: jax.Array                 # ([M,] P) int32 element-gathered
    lanes: Optional[jax.Array] = None  # ([M,] R, bk, 1) int32 live lanes
    zero_rows: int = 0                # all-sentinel rows after ``lanes``
    shape: tuple = ()                 # the stream's shape

    @property
    def gather_elems(self) -> int:
        """Elements gathered per call."""
        return self.gather.size + (0 if self.lanes is None
                                   else self.lanes.size)

    @property
    def slots(self) -> int:
        return int(np.prod(self.shape))

    def stage(self, vals) -> jax.Array:
        vals_ext = jnp.concatenate(
            [vals.astype(jnp.float32), jnp.zeros((1,), jnp.float32)])
        if self.lanes is None:
            return vals_ext[self.gather]
        tiles = _lane_tiles(vals_ext, self.lanes, self.zero_rows)
        if self.gather.shape[-1]:
            tiles = jnp.concatenate([vals_ext[self.gather], tiles], axis=-1)
        return tiles.reshape(self.shape)


@functools.partial(jax.jit, static_argnums=2)
def _lane_tiles(vals_ext, lanes, zero_rows: int) -> jax.Array:
    """``([M,] R, bk, 1)`` live lanes of lane-padded rows gathered from
    ``vals_ext``, padded back to ``LANE`` lanes and by ``zero_rows``
    rows of zeros, flat per member: one program, so the pad and the
    flattening need no pass of their own."""
    # in range by construction: no index normalisation
    live = lax.gather(vals_ext, lanes, _TAKE, slice_sizes=(1,),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    lead, bk = live.shape[:-2], live.shape[-1]
    tiles = jnp.pad(live, [(0, 0)] * len(lead)
                    + [(0, zero_rows), (0, LANE - bk)])
    return tiles.reshape(*lead, -1)


def _slot_values(ws, nnz: int, members: int = 1,
                 sharding=None) -> _SlotValues:
    """The device side of :class:`_SlotValues` for a workspace whose
    ``gather_flat`` is one stream, ``members`` equal-width streams laid
    end to end (the request axis), or a per-chip ``(C, S)`` stack; the
    split comes from the host stream itself."""
    g = _tiles(ws.gather_flat, nnz)
    if not np.any(ws.blk_tag == MXU_TAG):
        return _SlotValues(gather=_put(g, sharding), shape=g.shape)
    lead = g.shape[:-1] or ((members,) if members > 1 else ())
    rows = g.reshape(*lead, -1, LANE)
    n_rows = rows.shape[-2]
    # per row, over every member: a live lane past bk / any live lane
    wide = (rows[..., ws.bk:] != nnz).any(-1).reshape(-1, n_rows).any(0)
    live = (rows != nnz).any(-1).reshape(-1, n_rows).any(0)
    p = int(np.flatnonzero(wide)[-1]) + 1 if wide.any() else 0
    e = max(int(np.flatnonzero(live)[-1]) + 1 if live.any() else 0, p)
    return _SlotValues(
        gather=_put(rows[..., :p, :].reshape(*lead, -1), sharding),
        lanes=_put(rows[..., p:e, :ws.bk, None], sharding),
        zero_rows=n_rows - e, shape=g.shape)


def _vpu_steps(ws) -> int:
    """Loop steps a workspace's VPU descriptors run per call, over every
    chip or request it stacks: the sum of their ``blk_L``."""
    return int(np.asarray(ws.blk_L, np.int64)[ws.blk_tag == VPU_TAG].sum())


class _SlotValueCounts:
    """``vals_gather_elems``, ``vals_slots`` and ``vpu_steps`` of an
    artifact: the elements its slot-value staging gathers per call, the
    slots of the stream it stages and the loop steps its VPU trips run,
    fixed when it is built; None off the fused backends."""

    def _tables(self):
        """The artifact's device constants; None off the fused
        backends."""
        return self._sharded or self._fused

    def _staged(self) -> Optional[_SlotValues]:
        fw = self._tables()
        return None if fw is None else fw.vals

    @property
    def vals_gather_elems(self) -> Optional[int]:
        sv = self._staged()
        return None if sv is None else sv.gather_elems

    @property
    def vals_slots(self) -> Optional[int]:
        sv = self._staged()
        return None if sv is None else sv.slots

    @property
    def vpu_steps(self) -> Optional[int]:
        fw = self._tables()
        return None if fw is None else fw.vpu_steps


def _fused_consts(ws, nnz: int, members: int = 1) -> "_FusedConsts":
    """Device constants of a solo or request-batched workspace; ``nnz``
    is the gather stream's zero-slot sentinel, ``members`` the number
    of requests laid end to end in its streams."""
    return _FusedConsts(
        blk_off=jnp.asarray(ws.blk_off), blk_L=jnp.asarray(ws.blk_L),
        cols_flat=_stream(ws.cols_flat, 0),
        vals=_slot_values(ws, nnz, members),
        inv_perm=jnp.asarray(ws.inv_perm), num_blocks=ws.num_blocks,
        blk_tag=jnp.asarray(ws.blk_tag), blk_coff=jnp.asarray(ws.blk_coff),
        max_span=ws.max_span, max_cspan=ws.max_cspan,
        merge_width=ws.merge_width, cont=jnp.asarray(ws.blk_cont),
        vpu_steps=_vpu_steps(ws))


@dataclasses.dataclass
class _ShardedConsts:
    """Device-resident multi-chip fused constants: stacked per-chip
    descriptor tables (leading axis = chips), the GLOBAL inverse
    permutation into the flattened (n_chips * ws_rows) workspace, and
    the mesh the shard_map dispatch runs over."""
    blk_tag: jax.Array       # (C, B) int32 — VPU/MXU tag
    blk_off: jax.Array       # (C, B) int32
    blk_coff: jax.Array      # (C, B) int32 into cols_flat
    blk_L: jax.Array         # (C, B) int32
    cols_flat: jax.Array     # (C, Sc) int32
    vals: _SlotValues        # how the (C, S) slot-value stream is staged
    inv_perm: jax.Array      # (m,) int32 into flattened workspace rows
    ws_rows: int             # per-chip workspace rows
    num_blocks: int          # common per-chip block count B
    n_chips: int
    mesh: Mesh
    max_span: int = 0        # cross-chip max staged-DMA slot window
    max_cspan: int = 0       # cross-chip max staged-DMA cols window
    chip_span: tuple = ()    # (C,) per-chip staged-DMA slot windows
    chip_cspan: tuple = ()   # (C,) per-chip staged-DMA cols windows
    # cross-chip X fetch schedule (x_sharding="rows"; DESIGN.md §7.8).
    # Only the send/recv tables reach the dispatch; the fetch table
    # stays host-side on ShardedFusedWorkspace for introspection.
    x_sharding: str = "replicated"
    x_panels: int = 0
    x_own_panels: int = 0
    x_send: Optional[jax.Array] = None    # (C, C, T2) int32 local panels
    x_recv: Optional[jax.Array] = None    # (C, T) int32 into (C*T2,)
    merge_width: int = 1     # CGCM width, global across chips (§7.9)
    cont: Optional[jax.Array] = None      # (C, B//W) int32 piece trips
    vpu_steps: int = 0       # loop steps of the VPU descriptors per call


def _sharded_consts(sw: ShardedFusedWorkspace, mesh: Mesh
                    ) -> _ShardedConsts:
    """Device constants of a chip-stacked workspace, each chip's rows
    placed on that chip."""
    from ..distributed.sharding import chip_row_sharding
    on_chips = chip_row_sharding(mesh)

    def put(a):
        return None if a is None else jax.device_put(a, on_chips)

    return _ShardedConsts(
        blk_off=put(sw.blk_off), blk_L=put(sw.blk_L),
        cols_flat=_stream(sw.cols_flat, 0, on_chips),
        vals=_slot_values(sw, sw.nnz, sharding=on_chips),
        inv_perm=jnp.asarray(sw.inv_perm), ws_rows=sw.ws_rows,
        num_blocks=sw.num_blocks, n_chips=sw.n_chips, mesh=mesh,
        blk_tag=put(sw.blk_tag), blk_coff=put(sw.blk_coff),
        max_span=sw.max_span, max_cspan=sw.max_cspan,
        chip_span=tuple(int(s) for s in sw.chip_span),
        chip_cspan=tuple(int(s) for s in sw.chip_cspan),
        x_sharding=sw.x_sharding, x_panels=sw.x_panels,
        x_own_panels=sw.x_own_panels, x_send=put(sw.x_send),
        x_recv=put(sw.x_recv), merge_width=sw.merge_width,
        cont=put(sw.blk_cont), vpu_steps=_vpu_steps(sw))


class CompiledSpmm(_SlotValueCounts):
    """The "jit-function": structure-specialized, value-generic,
    differentiable SpMM."""

    def __init__(self, a: CSRMatrix, d: int, *, strategy: str,
                 backend: str, bm: int = 8, interpret: Optional[bool] = None,
                 mesh: Optional[Mesh] = None, n_chips: Optional[int] = None,
                 bk: int = 8, mxu_gain: float = 4.0,
                 staging: Optional[str] = None,
                 x_sharding: Optional[str] = None,
                 merge_threshold: int = 0,
                 validate: Optional[str] = None,
                 cache: JitCache = GLOBAL_CACHE):
        self.backend = _resolve_backend(
            backend, sharded=mesh is not None or n_chips is not None)
        self.strategy = strategy
        self.bm = bm
        self.bk = bk
        self.mxu_gain = mxu_gain
        self.merge_threshold = int(merge_threshold)
        # resolved ONCE: the effective flag is part of the compiled
        # artifact's identity (and of every jit-cache key touching it)
        self.interpret = resolve_interpret(interpret)
        self.validate = resolve_validate(validate, self.interpret)
        self.staging = _resolve_staging_for(self.backend, staging,
                                            self.interpret)
        self.mesh = resolve_chip_mesh(mesh, n_chips)
        self.x_sharding = _resolve_x_sharding_for(
            self.backend, x_sharding, self.interpret, self.mesh)
        self.n_chips = None if self.mesh is None else int(self.mesh.size)
        fused = self.backend == "pallas_bcsr"
        if self.mesh is not None and not fused:
            raise ValueError(
                f"mesh/n_chips sharding is a fused-dispatch feature "
                f"(pallas_bcsr); backend={self.backend!r} is "
                f"single-device")
        self.cache = cache
        self.d = d
        self.shape = a.shape
        # host structure retained for gradients / transpose
        self._row_ptr = a.row_ptr
        self._col_indices = a.col_indices
        self._fingerprint = a.fingerprint
        self._nnz = a.nnz
        # the MXU trip slices (bk, dt) X panels per block-column, so X
        # rows are padded up to the block-column grid
        self._x_rows_pad = -(-a.shape[1] // bk) * bk

        self.mixed_plan: Optional[MixedPlan] = None
        self._fused: Optional[_FusedConsts] = None
        self._sharded: Optional[_ShardedConsts] = None
        if fused and self.mesh is not None:
            # the sharded workspace re-plans every chip range itself, so
            # packing a global plan here would duplicate O(padded_nnz)
            # host work; only the d tiling is needed from this level
            self.d_tiling = ccm.plan_d_tiles(d, rows_in_flight=bm)
            sw: ShardedFusedWorkspace = build_sharded_workspace(
                a.row_ptr, a.col_indices, a.shape, d,
                n_chips=self.n_chips, strategy=strategy, row_block=bm,
                fingerprint=a.fingerprint, bk=bk, mxu_gain=mxu_gain,
                x_sharding=self.x_sharding,
                merge_threshold=self.merge_threshold)
            self.sharded_workspace = sw
            _verify_workspace_timed(
                sw, level=self.validate, n_cols=a.shape[1],
                context=f"compile_spmm[{self.backend}/sharded]")
            _require_resident_fit(
                self.staging, self.interpret, sw,
                4 * self._x_rows_pad * self.d_tiling.dt, "compile_spmm")
            self._sharded = _sharded_consts(sw, self.mesh)
            _record_build(
                sum(p.plan_seconds for p in sw.shard_plans),
                sw.pack_seconds)
        elif fused:
            self.mixed_plan = build_mixed_plan(
                a.row_ptr, a.col_indices, a.shape, d, strategy=strategy,
                row_block=bm, bk=bk, mxu_gain=mxu_gain,
                fingerprint=a.fingerprint)
            self.d_tiling = self.mixed_plan.d_tiling
            # merge stage: the CGCM width is a plan-time decision from
            # the instance's row lengths (DESIGN.md §7.9); 1 = no merge
            mw = choose_merge_width(a.row_ptr, row_block=bm,
                                    merge_threshold=self.merge_threshold)
            ws = build_fused_workspace(self.mixed_plan, merge_width=mw)
            _verify_workspace_timed(
                ws, level=self.validate, n_cols=a.shape[1],
                context=f"compile_spmm[{self.backend}]")
            _require_resident_fit(
                self.staging, self.interpret, ws,
                4 * self._x_rows_pad * self.d_tiling.dt, "compile_spmm")
            self._fused = _fused_consts(ws, a.nnz)
            _record_build(self.mixed_plan.plan_seconds, ws.pack_seconds)
        elif self.backend == "ref":
            self._cols = jnp.asarray(a.col_indices)

        self._erows: Optional[jax.Array] = None
        if self.backend in ("ref", "dense"):
            # the row expansion is pure structure — precompute it so the
            # serving path never repeats the host-side np.repeat
            self._expanded_rows()

        self._transpose: Optional[CompiledSpmm] = None
        self._t_order: Optional[jax.Array] = None

        fwd = self._forward

        @jax.custom_vjp
        def _apply(vals, x):
            return fwd(vals, x)

        def _apply_fwd(vals, x):
            return fwd(vals, x), (vals, x)

        def _apply_bwd(res, dy):
            vals, x = res
            with span("spmm.sddmm"):
                dvals = self._sddmm(dy, x).astype(vals.dtype)
            with span("spmm.transpose"):
                dx = self._transpose_apply(vals, dy).astype(x.dtype)
            return dvals, dx

        _apply.defvjp(_apply_fwd, _apply_bwd)
        self._apply = _apply

    def _expanded_rows(self) -> jax.Array:
        """(nnz,) int32 row id per nonzero — shared by the ref/dense
        forward paths and the sddmm gradient (built once, cached)."""
        if self._erows is None:
            self._erows = jnp.asarray(
                np.repeat(np.arange(self.shape[0]),
                          np.diff(self._row_ptr)).astype(np.int32))
        return self._erows

    def _x_row_strips(self, x_pad):
        """Stack the dense operand into the (C, P, bk, d_pad) owned-
        panel strips the x-sharded dispatch consumes: rows padded to
        whole bk-row panels, panels padded to a rectangular per-chip
        strip.  The strips are pinned to the chip mesh either way —
        ``device_put`` for eager callers, a GSPMD sharding constraint
        under a trace — so when the CALLER supplies an already
        row-sharded X (the at-scale entry point, see DESIGN.md §7.8),
        the pad/reshape partitions instead of replicating and no chip
        ever materializes a full X; steady-state per-chip residency is
        then the owned strip plus the touched-panel working set."""
        from ..distributed.sharding import chip_row_sharding
        sw = self._sharded
        n_rows = sw.x_panels * self.bk
        if x_pad.shape[0] < n_rows:
            x_pad = jnp.pad(x_pad, ((0, n_rows - x_pad.shape[0]), (0, 0)))
        strips = x_pad.reshape(sw.x_panels, self.bk, x_pad.shape[1])
        tot = sw.n_chips * sw.x_own_panels
        if sw.x_panels < tot:
            strips = jnp.pad(
                strips, ((0, tot - sw.x_panels), (0, 0), (0, 0)))
        strips = strips.reshape(sw.n_chips, sw.x_own_panels, self.bk,
                                x_pad.shape[1])
        if isinstance(strips, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(
                strips, chip_row_sharding(sw.mesh))
        return jax.device_put(strips, chip_row_sharding(sw.mesh))

    # -- forward -----------------------------------------------------------
    def _forward(self, vals, x):
        m, n = self.shape
        d = x.shape[1]
        assert d == self.d, (d, self.d)
        backend = self.backend
        if backend == "dense":
            dense = jnp.zeros((m, n), vals.dtype)
            dense = dense.at[self._expanded_rows(),
                             self._col_indices].set(vals)
            return dense.astype(jnp.float32) @ x.astype(jnp.float32)
        if backend == "ref":
            prod = (vals[:, None].astype(jnp.float32)
                    * x[self._cols].astype(jnp.float32))
            return jax.ops.segment_sum(prod, self._expanded_rows(),
                                       num_segments=m,
                                       indices_are_sorted=True)
        sharded = self._sharded is not None
        fw = self._sharded if sharded else self._fused
        if fw.num_blocks == 0:
            return jnp.zeros((m, d), jnp.float32)
        # one dispatch (per chip) for the whole plan, whatever the
        # segment count, between the slot-value gather and one
        # inverse-permutation gather that recovers row order
        with span("spmm.stage_vals"):
            vals_flat = fw.vals.stage(vals)
        with span("spmm.stage_operands"):
            x_pad = ccm.pad_cols(x, self.d_tiling.d_pad)
            if x_pad.shape[0] < self._x_rows_pad:
                x_pad = jnp.pad(
                    x_pad,
                    ((0, self._x_rows_pad - x_pad.shape[0]), (0, 0)))
            if sharded and fw.x_sharding == "rows":
                x_pad = self._x_row_strips(x_pad)
        with span("spmm.kernel"):
            y_ws = self._kernel(fw, vals_flat, x_pad)
        with span("spmm.unpermute"):
            if sharded:
                # the GLOBAL inv_perm indexes the flattened
                # (n_chips * ws_rows) workspace
                y_ws = y_ws.reshape(fw.n_chips * fw.ws_rows, -1)
            return y_ws[fw.inv_perm, :d]

    def _kernel(self, fw, vals_flat, x_pad):
        """The fused dispatch of the plan's constants ``fw``: one
        pallas_call, or one per chip under shard_map."""
        tables = (fw.blk_tag, fw.blk_off, fw.blk_coff, fw.blk_L,
                  fw.cols_flat, vals_flat, x_pad, fw.cont)
        knobs = dict(bm=self.bm, bk=self.bk, mw=fw.merge_width,
                     interpret=self.interpret, staging=self.staging)
        if self._sharded is not None:
            return kops.spmm_bcsr_fused_sharded_op(
                *tables, mesh=fw.mesh, span=fw.chip_span,
                cspan=fw.chip_cspan, x_sharding=fw.x_sharding,
                x_send=fw.x_send, x_recv=fw.x_recv, **knobs)
        return kops.spmm_bcsr_fused_op(*tables, span=fw.max_span,
                                       cspan=fw.max_cspan, **knobs)

    # -- gradients ----------------------------------------------------------
    def _sddmm(self, dy, x):
        """dvals[e] = <dy[row_e], x[col_e]>, in chunks of
        ``_SDDMM_CHUNK`` nonzeros: the gathered (nnz, d) operands of a
        graph at scale would not fit the chip's HBM at once."""
        rows, cols = self._expanded_rows(), jnp.asarray(self._col_indices)
        nnz = cols.shape[0]
        n_chunks = max(-(-nnz // _SDDMM_CHUNK), 1)
        size = max(-(-nnz // n_chunks), 1)     # one padded entry if empty
        pad = n_chunks * size - nnz

        def chunk(rc):
            r, c = rc
            return jnp.sum(dy[r].astype(jnp.float32)
                           * x[c].astype(jnp.float32), axis=-1)

        out = jax.lax.map(chunk, (jnp.pad(rows, (0, pad)).reshape(-1, size),
                                  jnp.pad(cols, (0, pad)).reshape(-1, size)))
        return out.reshape(-1)[:nnz]

    def _transpose_apply(self, vals, dy):
        if self._transpose is None:
            a = CSRMatrix(self.shape, self._row_ptr, self._col_indices,
                          np.zeros(self._nnz, np.float32))
            t_struct, order = a.transpose_structure()
            key = ("spmmT", self._fingerprint, self.d, self.strategy,
                   self.backend, self.bm, self.bk, self.mxu_gain,
                   self.interpret, self.staging, self.x_sharding,
                   self.merge_threshold, self.validate,
                   mesh_fingerprint(self.mesh))
            self._transpose = self.cache.get_or_build(
                key, lambda: CompiledSpmm(
                    t_struct, self.d, strategy=self.strategy,
                    backend=self.backend, bm=self.bm, bk=self.bk,
                    mxu_gain=self.mxu_gain, interpret=self.interpret,
                    staging=self.staging, x_sharding=self.x_sharding,
                    merge_threshold=self.merge_threshold,
                    validate=self.validate,
                    mesh=self.mesh, cache=self.cache))
            self._t_order = jnp.asarray(order.astype(np.int32))
        vals_t = vals[self._t_order]
        return self._transpose._forward(vals_t, dy)

    def __call__(self, vals, x):
        with span("spmm.call"):
            return self._apply(vals, x)


def compile_spmm(a: CSRMatrix, d: int, *, strategy: str = "nnz_split",
                 backend: str = "auto", bm: int = 8,
                 interpret: Optional[bool] = None,
                 mesh: Optional[Mesh] = None, n_chips: Optional[int] = None,
                 bk: int = 8, mxu_gain: float = 4.0,
                 staging: Optional[str] = None,
                 x_sharding: Optional[str] = None,
                 merge_threshold: int = 0,
                 validate: Optional[str] = None, autotune: bool = False,
                 measure=None, candidates=None, top_k: int = 3,
                 cache_priority: float = 0.0,
                 cache: JitCache = GLOBAL_CACHE) -> CompiledSpmm:
    """Build (or fetch) the structure-specialized SpMM artifact.

    ``backend`` is one of :data:`BACKENDS`; ``"auto"`` resolves to the
    fused ``"pallas_bcsr"`` on a TPU or when sharding is asked for, and
    to the jnp ``"ref"`` otherwise.  Any other name is a ``ValueError``.

    ``mesh`` / ``n_chips`` (the fused backend) shard the fused plan
    across a 1-D device mesh: rows are partitioned by the same strategy
    at the chip level, at block-row boundaries, and each chip runs its
    range as one pallas_call under shard_map.  The resolved mesh is
    part of the cache key — same normalization as ``interpret``.
    ``bk`` / ``mxu_gain`` parameterize the plan (block width,
    VPU-vs-MXU tagging; ``mxu_gain=0`` tags every block VPU) and are
    part of the specialization identity as well.

    ``staging`` selects the fused kernels' operand staging (DESIGN.md
    §7.7): ``"resident"`` keeps the flat slot buffer and X panel in
    VMEM, ``"dma"`` double-buffers per-block slot panels and per-trip
    X panels from HBM.  ``"auto"``/``None``
    resolves to ``"dma"`` on a real TPU and ``"resident"`` under
    interpret mode; the resolved mode is part of the cache key and the
    two lowerings are bit-identical.

    ``x_sharding`` selects X placement on the sharded path (DESIGN.md
    §7.8): ``"replicated"`` keeps all of X on every chip, ``"rows"``
    splits X into bk-row panels owned by chips and fetches exactly the
    panels each chip's plan touches (exact-panel exchange).
    ``"auto"``/``None`` resolves to ``"rows"`` on a real multi-chip
    mesh and ``"replicated"`` otherwise; the resolved mode is part of
    the cache key and the two placements are bit-identical.

    ``merge_threshold`` drives the CGCM merge stage (DESIGN.md §7.9):
    0 disables merging (the legacy layout, byte-identical), a positive
    value lets ``choose_merge_width`` coalesce up to ``MAX_MERGE_WIDTH``
    short block-rows per descriptor trip when the instance's typical
    trip count times the merged width stays under it.  Output is
    bit-identical either way; only grid-step count and DMA windows
    change.  ``autotune=True`` instead searches strategy × merge ×
    staging per instance (``core.autotune``, memoized in the same
    cache) — the explicit knobs then serve as the search's fallback
    configuration, and ``measure`` / ``candidates`` / ``top_k`` pass
    through to the search (deterministic tests inject a fake timer).

    ``cache_priority`` is the artifact's SLA eviction score (DESIGN.md
    §14.4): the serving tier maps a tenant's deadline hint onto it so a
    capacity-bounded cache sheds cold tenants' artifacts before those a
    tight-SLA tenant would have to rebuild on its critical path.

    ``validate`` runs the static plan verifier (DESIGN.md §15) over the
    packed workspace before any device constants are built:
    ``"off"`` / ``"cheap"`` / ``"full"``, with ``"auto"``/``None``
    resolving to ``"full"`` under interpret mode (every test verifies
    every workspace it builds) and ``"off"`` on a real TPU backend (the
    zero-cost production setting).  A malformed plan raises
    :class:`~repro.analysis.verify.PlanVerificationError` naming the
    violated invariants instead of computing silently wrong numerics."""
    if autotune:
        from .autotune import autotune_spmm
        return autotune_spmm(a, d, backend=backend, bm=bm, bk=bk,
                             mxu_gain=mxu_gain, interpret=interpret,
                             mesh=mesh, n_chips=n_chips, staging=staging,
                             x_sharding=x_sharding, validate=validate,
                             measure=measure,
                             candidates=candidates, top_k=top_k,
                             cache_priority=cache_priority,
                             cache=cache)
    backend = _resolve_backend(
        backend, sharded=mesh is not None or n_chips is not None)
    interpret = resolve_interpret(interpret)
    staging = _resolve_staging_for(backend, staging, interpret)
    mesh = resolve_chip_mesh(mesh, n_chips)
    x_sharding = _resolve_x_sharding_for(backend, x_sharding, interpret,
                                         mesh)
    merge_threshold = int(merge_threshold)
    validate = resolve_validate(validate, interpret)
    key = ("spmm", a.fingerprint, d, strategy, backend, bm, bk, mxu_gain,
           interpret, staging, x_sharding, merge_threshold, validate,
           mesh_fingerprint(mesh))
    return cache.get_or_build(
        key, lambda: CompiledSpmm(a, d, strategy=strategy, backend=backend,
                                  bm=bm, bk=bk, mxu_gain=mxu_gain,
                                  interpret=interpret, staging=staging,
                                  x_sharding=x_sharding,
                                  merge_threshold=merge_threshold,
                                  validate=validate,
                                  mesh=mesh, cache=cache),
        priority=cache_priority)


class CompiledBatchedSpmm(_SlotValueCounts):
    """Request-axis batched jit-function for the serving tier
    (DESIGN.md §12): R structure-specialized instances stacked
    block-diagonally (:func:`build_batched_workspace`) into ONE fused
    dispatch through the ordinary single-chip kernels.

    Bit-identical to dispatching each request alone with the same
    knobs: slot padding, d-bucket padding, and the common CGCM width
    all leave per-lane accumulation order untouched.  Forward-only —
    the endpoint never differentiates through a served batch; training
    gradients stay on :class:`CompiledSpmm`.
    """

    def __init__(self, structures, d: int, *,
                 strategy: str = "nnz_split", backend: str = "auto",
                 bm: int = 8, bk: int = 8, mxu_gain: float = 4.0,
                 interpret: Optional[bool] = None,
                 staging: Optional[str] = None,
                 merge_threshold=0,
                 validate: Optional[str] = None):
        # sharded=True resolution: batching stacks descriptor tables, so
        # "auto" must land on the fused backend even on CPU (interpret)
        self.backend = _resolve_backend(backend, sharded=True)
        if self.backend != "pallas_bcsr":
            raise ValueError(
                f"batched dispatch stacks descriptor tables — the fused "
                f"backend is required (pallas_bcsr), got "
                f"{self.backend!r}")
        self.strategy = strategy
        self.bm = bm
        self.bk = bk
        self.mxu_gain = mxu_gain
        # scalar = one CGCM threshold for every member; a sequence
        # carries each member's own tuned threshold into the common-
        # width fold (DESIGN.md §14.3)
        self.merge_threshold = _normalize_batch_merge_threshold(
            merge_threshold, len(structures))
        self.interpret = resolve_interpret(interpret)
        self.validate = resolve_validate(validate, self.interpret)
        self.staging = _resolve_staging_for(self.backend, staging,
                                            self.interpret)
        self.d = int(d)
        self.shapes = [tuple(int(v) for v in a.shape) for a in structures]
        self.d_tiling = ccm.plan_d_tiles(d, rows_in_flight=bm)
        bw: BatchedFusedWorkspace = build_batched_workspace(
            [(a.row_ptr, a.col_indices, a.shape) for a in structures],
            d, strategy=strategy, row_block=bm, bk=bk, mxu_gain=mxu_gain,
            merge_threshold=self.merge_threshold,
            fingerprint="+".join(a.fingerprint[:8] for a in structures))
        self.batched_workspace = bw
        _verify_workspace_timed(
            bw, level=self.validate,
            context=f"compile_batched_spmm[{self.backend}]")
        _require_resident_fit(
            self.staging, self.interpret, bw,
            4 * bw.n_requests * bw.x_rows_pad * self.d_tiling.dt,
            "compile_batched_spmm")
        self._consts = _fused_consts(bw, bw.nnz, bw.n_requests)
        _record_build(sum(p.plan_seconds for p in bw.request_plans),
                      bw.pack_seconds)
        self._row_splits = [int(v) for v in bw.row_splits]
        # the serving path calls the SAME artifact repeatedly — trace
        # once here instead of per request (shapes are fixed by the
        # artifact, so this never retraces after warmup)
        self._jit_forward = jax.jit(self._forward)

    def _tables(self) -> "_FusedConsts":
        return self._consts

    @property
    def n_requests(self) -> int:
        return len(self.shapes)

    def stack_inputs(self, xs) -> np.ndarray:
        """Host-side bucket padding: per-request ``(n_r, d_r <= d)``
        operands -> ONE zero-filled ``(R * x_rows_pad, d)`` stacked
        array (request r's rows at ``[r * x_rows_pad, ...)``)."""
        bw = self.batched_workspace
        out = np.zeros((bw.n_requests * bw.x_rows_pad, self.d),
                       np.float32)
        for r, x in enumerate(xs):
            x = np.asarray(x, np.float32)
            out[r * bw.x_rows_pad:r * bw.x_rows_pad + x.shape[0],
                :x.shape[1]] = x
        return out

    def _forward(self, vals, x):
        fw = self._consts
        x_pad = ccm.pad_cols(x, self.d_tiling.d_pad)
        vals_flat = fw.vals.stage(vals)
        y_ws = kops.spmm_bcsr_fused_op(
            fw.blk_tag, fw.blk_off, fw.blk_coff, fw.blk_L, fw.cols_flat,
            vals_flat, x_pad, fw.cont, bm=self.bm, bk=self.bk,
            mw=fw.merge_width, interpret=self.interpret,
            staging=self.staging, span=fw.max_span, cspan=fw.max_cspan)
        # one inverse-permutation gather un-interleaves ALL requests
        return y_ws[fw.inv_perm]

    def __call__(self, vals, xs):
        """``vals``: per-request value vectors (or one pre-concatenated
        array); ``xs``: per-request operands (or the pre-stacked array
        from :meth:`stack_inputs`).  Returns per-request ``(m_r, d)``
        outputs in request order.  The forward is one jitted program,
        so the call has one span and no stage spans: a span inside a
        trace would time the tracing."""
        with span("spmm_batched.call"):
            if isinstance(vals, (list, tuple)):
                vals = jnp.concatenate(
                    [jnp.asarray(v, jnp.float32).ravel() for v in vals])
            if isinstance(xs, (list, tuple)):
                xs = jnp.asarray(self.stack_inputs(xs))
            y = self._jit_forward(vals, xs)
            rs = self._row_splits
            return [y[rs[r]:rs[r + 1], :self.d]
                    for r in range(self.n_requests)]


def _normalize_batch_merge_threshold(merge_threshold, n_requests: int):
    """Scalar -> int; per-member sequence -> tuple of ints, collapsed
    back to the scalar when every member agrees so a uniform tuple and
    the plain scalar share one cache key (and one artifact)."""
    if np.ndim(merge_threshold) == 0:
        return int(merge_threshold)
    ts = tuple(int(t) for t in merge_threshold)
    if len(ts) != n_requests:
        raise ValueError(
            f"per-request merge_threshold needs {n_requests} entries, "
            f"got {len(ts)}")
    if len(set(ts)) == 1:
        return ts[0]
    return ts


def compile_batched_spmm(structures, d: int, *,
                         strategy: str = "nnz_split",
                         backend: str = "auto", bm: int = 8, bk: int = 8,
                         mxu_gain: float = 4.0,
                         interpret: Optional[bool] = None,
                         staging: Optional[str] = None,
                         merge_threshold=0,
                         validate: Optional[str] = None,
                         cache_priority: float = 0.0,
                         cache: JitCache = GLOBAL_CACHE
                         ) -> CompiledBatchedSpmm:
    """Build (or fetch) the batched multi-tenant artifact (DESIGN.md
    §12): the cache key is the ORDERED tuple of member fingerprints
    plus every knob a solo key carries — so a serving endpoint that
    sees the same batch composition twice pays plan/pack exactly once,
    the Table IV amortization applied across tenants.

    ``merge_threshold`` may be one scalar or a per-member sequence (the
    batched-autotune resolver hands each member its own tuned CGCM
    threshold, DESIGN.md §14.3).  ``cache_priority`` is the artifact's
    SLA eviction score (DESIGN.md §14.4)."""
    structures = tuple(structures)
    backend = _resolve_backend(backend, sharded=True)
    interpret = resolve_interpret(interpret)
    staging = _resolve_staging_for(backend, staging, interpret)
    merge_threshold = _normalize_batch_merge_threshold(
        merge_threshold, len(structures))
    validate = resolve_validate(validate, interpret)
    key = ("spmm_batch", tuple(a.fingerprint for a in structures), d,
           strategy, backend, bm, bk, mxu_gain, interpret, staging,
           merge_threshold, validate)
    return cache.get_or_build(
        key, lambda: CompiledBatchedSpmm(
            structures, d, strategy=strategy, backend=backend, bm=bm,
            bk=bk, mxu_gain=mxu_gain, interpret=interpret,
            staging=staging, merge_threshold=merge_threshold,
            validate=validate),
        priority=cache_priority)


def spmm(a: CSRMatrix, x, *, strategy: str = "nnz_split",
         backend: str = "auto", bm: int = 8,
         interpret: Optional[bool] = None,
         mesh: Optional[Mesh] = None, n_chips: Optional[int] = None,
         bk: int = 8, mxu_gain: float = 4.0,
         staging: Optional[str] = None,
         x_sharding: Optional[str] = None,
         merge_threshold: int = 0, autotune: bool = False,
         measure=None, candidates=None, top_k: int = 3,
         validate: Optional[str] = None,
         cache: JitCache = GLOBAL_CACHE) -> jax.Array:
    """Y = A·X, specialized to A's structure and x's column count."""
    compiled = compile_spmm(a, x.shape[1], strategy=strategy,
                            backend=backend, bm=bm, interpret=interpret,
                            mesh=mesh, n_chips=n_chips, bk=bk,
                            mxu_gain=mxu_gain, staging=staging,
                            x_sharding=x_sharding,
                            merge_threshold=merge_threshold,
                            autotune=autotune, measure=measure,
                            candidates=candidates, top_k=top_k,
                            validate=validate, cache=cache)
    return compiled(jnp.asarray(a.vals), x)


def _attention_panel(a: CSRMatrix, backend: str, bm: Optional[int],
                     bk: Optional[int], mxu_gain: float,
                     mesh: Optional[Mesh]) -> tuple:
    """The ``(bm, bk)`` MXU panel of an attention artifact: as the
    caller gave it (a missing one is 8), or, where neither is given, a
    square panel derived from the mask on the fused backend
    (:func:`~repro.core.plan.choose_panel_edge`, DESIGN.md §13.4).  The
    ``ref`` backend has no MXU trip and keeps 8x8."""
    if bm is not None or bk is not None:
        return (PANEL_EDGES[0] if bm is None else int(bm),
                PANEL_EDGES[0] if bk is None else int(bk))
    if backend != "pallas_bcsr":
        return PANEL_EDGES[0], PANEL_EDGES[0]
    b = choose_panel_edge(a.row_ptr, a.col_indices, a.shape,
                          mxu_gain=mxu_gain,
                          chips=1 if mesh is None else mesh.size)
    return b, b


def _runs_vpu_trips(ws) -> bool:
    """Whether a (solo or chip-stacked) workspace holds a VPU trip
    that runs; an inert pad (``L == 0``) runs the same under either
    trip body."""
    return bool(np.any((ws.blk_tag == VPU_TAG) & (ws.blk_L > 0)))


class CompiledSparseAttention(_SlotValueCounts):
    """Structure-specialized sparse attention: out = softmax(mask ⊙
    (Q·Kᵀ)) · V, lowered as ONE fused pallas_call (per chip) through
    the same descriptor stream as SpMM (DESIGN.md §13).

    Where neither ``bm`` nor ``bk`` is given, the MXU panel is derived
    from the mask (:func:`_attention_panel`); ``bm``/``bk`` hold the
    resolved panel, and ``mxu_panels`` / ``panel_fill`` what one call
    multiplies.

    ``a`` is the (m queries × n keys) mask pattern; its values are the
    mask weights ``w`` (1.0 for a plain binary mask), giving
    ``p ∝ w · exp(z)`` — softmax over the present entries.  Weights
    must be non-negative: ``w <= 0`` entries are treated as absent by
    the running max, and the cross-trip clamp rescale is only exact
    under that contract.  The plan
    pipeline is the sparse-einsum composition
    (:func:`~repro.core.plan.build_einsum_workspace`): the descriptor
    stream, slot packing, CGCM merging and sharding stages are exactly
    SpMM's; only the per-trip body (SDDMM score → running softmax →
    S·V) and the workspace-ordered Q gather
    (:func:`~repro.core.plan.workspace_row_map`) differ.  ``S`` never
    materializes in HBM.

    Gradients run through ``jax.custom_vjp``: the forward is the fused
    kernel, the backward differentiates the pure-jnp reference (the
    same math, recomputed — the descriptor stream is forward-only
    today).  K/V are replicated on the sharded path (attention rows
    read arbitrary key columns), so ``x_sharding`` has no "rows" mode
    here.
    """

    def __init__(self, a: CSRMatrix, dh: int, dv: Optional[int] = None,
                 *, strategy: str = "nnz_split", backend: str = "auto",
                 bm: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 mesh: Optional[Mesh] = None,
                 n_chips: Optional[int] = None, bk: Optional[int] = None,
                 mxu_gain: float = 4.0, staging: Optional[str] = None,
                 merge_threshold: int = 0,
                 sm_scale: Optional[float] = None,
                 validate: Optional[str] = None,
                 cache: JitCache = GLOBAL_CACHE):
        self.backend = _resolve_backend(
            backend, sharded=mesh is not None or n_chips is not None)
        if self.backend == "dense":
            raise ValueError(
                "sparse attention has no dense backend — use ref as the "
                "oracle")
        self.strategy = strategy
        self.mxu_gain = mxu_gain
        self.merge_threshold = int(merge_threshold)
        self.interpret = resolve_interpret(interpret)
        self.validate = resolve_validate(validate, self.interpret)
        self.staging = _resolve_staging_for(self.backend, staging,
                                            self.interpret)
        self.mesh = resolve_chip_mesh(mesh, n_chips)
        self.n_chips = None if self.mesh is None else int(self.mesh.size)
        fused = self.backend == "pallas_bcsr"
        if self.mesh is not None and not fused:
            raise ValueError(
                f"mesh/n_chips sharding is a fused-dispatch feature "
                f"(pallas_bcsr); backend={self.backend!r} is "
                f"single-device")
        bm, bk = _attention_panel(a, self.backend, bm, bk, mxu_gain,
                                  self.mesh)
        self.bm, self.bk = bm, bk
        self.cache = cache
        self.dh = int(dh)
        self.dv = int(dh) if dv is None else int(dv)
        self.sm_scale = (float(dh) ** -0.5 if sm_scale is None
                         else float(sm_scale))
        self.shape = a.shape
        self._row_ptr = a.row_ptr
        self._col_indices = a.col_indices
        self._fingerprint = a.fingerprint
        self._nnz = a.nnz
        # value-dim tiling drives the kernel grid's second axis; the
        # head dim is only lane-padded (scores reduce over it whole)
        self.d_tiling = ccm.plan_d_tiles(self.dv, rows_in_flight=bm)
        self._dh_pad = ccm.plan_d_tiles(self.dh).d_pad
        # both branches slice K/V rows — the MXU branch by (bk,) panels
        self._kv_rows_pad = -(-a.shape[1] // bk) * bk

        self._fused: Optional[_FusedConsts] = None
        self._sharded: Optional[_ShardedConsts] = None
        self._row_map: Optional[jax.Array] = None   # ws slot -> Q row
        self._panels = None                          # PanelStats
        self._vpu = True      # whether the stream holds VPU trips
        if fused:
            self._panels = panel_stats(
                a.row_ptr, a.col_indices, a.shape, [(bm, bk)],
                mxu_gain=mxu_gain)[0]
        if fused and self.mesh is not None:
            sw: ShardedFusedWorkspace = build_sharded_workspace(
                a.row_ptr, a.col_indices, a.shape, self.dv,
                n_chips=self.n_chips, strategy=strategy, row_block=bm,
                fingerprint=a.fingerprint, bk=bk, mxu_gain=mxu_gain,
                x_sharding="replicated",
                merge_threshold=self.merge_threshold)
            self.sharded_workspace = sw
            _require_resident_fit(
                self.staging, self.interpret, sw, self._kv_bytes(),
                "compile_sparse_attention")
            row_maps = sharded_workspace_row_maps(sw)
            _verify_workspace_timed(
                sw, level=self.validate, n_cols=a.shape[1],
                spec=SPARSE_ATTN_MIXED_EINSUM, vals=np.asarray(a.vals),
                row_map=row_maps,
                context=f"compile_sparse_attention[{self.backend}"
                        f"/sharded]")
            self._sharded = _sharded_consts(sw, self.mesh)
            self._vpu = _runs_vpu_trips(sw)
            self._row_map = jnp.asarray(row_maps)
            _record_build(
                sum(p.plan_seconds for p in sw.shard_plans),
                sw.pack_seconds)
        elif fused:
            ws = build_einsum_workspace(
                SPARSE_ATTN_MIXED_EINSUM, a.row_ptr, a.col_indices,
                a.shape, self.dv, strategy=strategy, row_block=bm, bk=bk,
                mxu_gain=mxu_gain, merge_threshold=self.merge_threshold,
                fingerprint=a.fingerprint)
            self.workspace = ws
            _require_resident_fit(
                self.staging, self.interpret, ws, self._kv_bytes(),
                "compile_sparse_attention")
            # verify the SAME forward map the Q gather will ship (the
            # perm_roundtrip invariant guards the staged constant, not
            # a re-derivation)
            row_map = workspace_row_map(
                ws.inv_perm, ws.ws_rows, ws.blk_cont,
                ws.merge_width * ws.row_block)
            _verify_workspace_timed(
                ws, level=self.validate, n_cols=a.shape[1],
                spec=SPARSE_ATTN_MIXED_EINSUM, vals=np.asarray(a.vals),
                row_map=row_map,
                context=f"compile_sparse_attention[{self.backend}]")
            self._fused = _fused_consts(ws, a.nnz)
            self._vpu = _runs_vpu_trips(ws)
            self._row_map = jnp.asarray(row_map)
            _record_build(0.0, ws.pack_seconds)

        self._erows: Optional[np.ndarray] = None

        fwd = self._forward
        ref = self._ref_forward

        @jax.custom_vjp
        def _apply(vals, q, k, v):
            return fwd(vals, q, k, v)

        def _apply_fwd(vals, q, k, v):
            return fwd(vals, q, k, v), (vals, q, k, v)

        def _apply_bwd(res, dy):
            _, vjp = jax.vjp(ref, *res)
            return vjp(dy)

        _apply.defvjp(_apply_fwd, _apply_bwd)
        self._apply = _apply

    @property
    def mxu_panels(self) -> Optional[int]:
        """MXU panels of ``bm x bk`` one call multiplies; None off the
        fused backend."""
        return None if self._panels is None else self._panels.panels

    @property
    def panel_fill(self) -> Optional[float]:
        """Nonzeros over the slots of those panels (0 without any);
        None off the fused backend."""
        return None if self._panels is None else self._panels.fill

    def _kv_bytes(self) -> int:
        """VMEM bytes of the resident K and V panels."""
        return 4 * self._kv_rows_pad * (self._dh_pad + self.d_tiling.dt)

    def _expanded_rows(self) -> np.ndarray:
        # host numpy on purpose: _ref_forward may first run inside a
        # caller's trace (the model layers call artifacts under scan),
        # and a jnp constant cached on self there would leak the trace
        if self._erows is None:
            self._erows = np.repeat(
                np.arange(self.shape[0]),
                np.diff(self._row_ptr)).astype(np.int32)
        return self._erows

    def _ref_forward(self, vals, q, k, v):
        """Pure-jnp oracle (and the backward's recompute): the same
        ``p ∝ w · exp(z)`` semantics in segment ops, with the identical
        NaN-free clamp — ``w > 0`` entries never clamp (the segment max
        dominates), ``w == 0`` entries are killed before they can
        overflow."""
        m, _ = self.shape
        rows = self._expanded_rows()
        cols = jnp.asarray(self._col_indices)
        w = vals.astype(jnp.float32)
        z = jnp.sum(q[rows].astype(jnp.float32)
                    * k[cols].astype(jnp.float32),
                    axis=-1) * self.sm_scale
        zm = jnp.where(w > 0, z, -1e30)
        zmax = jax.ops.segment_max(zm, rows, num_segments=m)
        zmax = jnp.where(jnp.isfinite(zmax), zmax, 0.0)  # empty rows
        p = w * jnp.exp(jnp.minimum(z - zmax[rows], 0.0))
        denom = jax.ops.segment_sum(p, rows, num_segments=m)
        out = jax.ops.segment_sum(
            p[:, None] * v[cols].astype(jnp.float32), rows,
            num_segments=m)
        return out / jnp.where(denom > 0, denom, 1.0)[:, None]

    def _operands(self, q, k, v):
        """Stage the dense operands for the kernel: scale folded into
        Q, lane padding on both widths, K/V rows padded to the
        block-column grid, and Q extended by the zero row the sentinel
        gather relies on."""
        q_pad = ccm.pad_cols(q.astype(jnp.float32) * self.sm_scale,
                             self._dh_pad)
        q_ext = jnp.concatenate(
            [q_pad, jnp.zeros((1, self._dh_pad), jnp.float32)])
        k_pad = ccm.pad_cols(k.astype(jnp.float32), self._dh_pad)
        v_pad = ccm.pad_cols(v.astype(jnp.float32),
                             self.d_tiling.d_pad)
        if k_pad.shape[0] < self._kv_rows_pad:
            grow = self._kv_rows_pad - k_pad.shape[0]
            k_pad = jnp.pad(k_pad, ((0, grow), (0, 0)))
            v_pad = jnp.pad(v_pad, ((0, grow), (0, 0)))
        return q_ext, k_pad, v_pad

    # -- forward -----------------------------------------------------------
    def _forward(self, vals, q, k, v):
        m, n = self.shape
        assert q.shape == (m, self.dh), (q.shape, m, self.dh)
        assert k.shape == (n, self.dh), (k.shape, n, self.dh)
        assert v.shape == (n, self.dv), (v.shape, n, self.dv)
        if self.backend == "ref":
            return self._ref_forward(vals, q, k, v)
        sharded = self._sharded is not None
        fw = self._sharded if sharded else self._fused
        if fw.num_blocks == 0:
            return jnp.zeros((m, self.dv), jnp.float32)
        with span("attn.stage_vals"):
            vals_flat = fw.vals.stage(vals)
        with span("attn.stage_operands"):
            q_ext, k_pad, v_pad = self._operands(q, k, v)
            q_ws = q_ext[self._row_map]   # ([C,] ws_rows, dh_pad)
        with span("attn.kernel"):
            tables = (fw.blk_tag, fw.blk_off, fw.blk_coff, fw.blk_L,
                      fw.cols_flat, vals_flat, q_ws, k_pad, v_pad, fw.cont)
            knobs = dict(bm=self.bm, bk=self.bk, mw=fw.merge_width,
                         vpu=self._vpu, interpret=self.interpret,
                         staging=self.staging)
            if sharded:
                y_ws = kops.attn_fused_sharded_op(
                    *tables, mesh=fw.mesh, span=fw.chip_span,
                    cspan=fw.chip_cspan, **knobs)
            else:
                y_ws = kops.attn_fused_op(*tables, span=fw.max_span,
                                          cspan=fw.max_cspan, **knobs)
        with span("attn.unpermute"):
            if sharded:
                y_ws = y_ws.reshape(fw.n_chips * fw.ws_rows, -1)
            return y_ws[fw.inv_perm, :self.dv]

    def __call__(self, vals, q, k, v):
        with span("attn.call"):
            return self._apply(vals, q, k, v)


def compile_sparse_attention(a: CSRMatrix, dh: int,
                             dv: Optional[int] = None, *,
                             strategy: str = "nnz_split",
                             backend: str = "auto",
                             bm: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             mesh: Optional[Mesh] = None,
                             n_chips: Optional[int] = None,
                             bk: Optional[int] = None,
                             mxu_gain: float = 4.0,
                             staging: Optional[str] = None,
                             merge_threshold: int = 0,
                             sm_scale: Optional[float] = None,
                             validate: Optional[str] = None,
                             cache: JitCache = GLOBAL_CACHE
                             ) -> CompiledSparseAttention:
    """Build (or fetch) the structure-specialized sparse-attention
    artifact (DESIGN.md §13) — keyed like ``compile_spmm``, under the
    ``"attn"`` family: the mask fingerprint, BOTH runtime widths
    (head dim and value dim), the softmax scale, and every resolved
    knob join the cache key, so a pattern served at a new head size is
    a new artifact while repeated (B, H) instances of one layer hit.

    ``bm``/``bk`` pin the MXU panel; where neither is given it is
    derived from the mask (a square panel of 8 to 128 on the fused
    backend, DESIGN.md §13.4), and the resolved panel joins the key."""
    backend = _resolve_backend(
        backend, sharded=mesh is not None or n_chips is not None)
    interpret = resolve_interpret(interpret)
    staging = _resolve_staging_for(backend, staging, interpret)
    mesh = resolve_chip_mesh(mesh, n_chips)
    bm, bk = _attention_panel(a, backend, bm, bk, mxu_gain, mesh)
    merge_threshold = int(merge_threshold)
    dv = int(dh) if dv is None else int(dv)
    sm_scale = float(dh) ** -0.5 if sm_scale is None else float(sm_scale)
    validate = resolve_validate(validate, interpret)
    key = ("attn", a.fingerprint, int(dh), dv, strategy, backend, bm,
           bk, mxu_gain, interpret, staging, merge_threshold, sm_scale,
           validate, mesh_fingerprint(mesh))
    return cache.get_or_build(
        key, lambda: CompiledSparseAttention(
            a, dh, dv, strategy=strategy, backend=backend, bm=bm,
            bk=bk, mxu_gain=mxu_gain, interpret=interpret,
            staging=staging, merge_threshold=merge_threshold,
            sm_scale=sm_scale, validate=validate, mesh=mesh,
            cache=cache))


def sparse_attention(a: CSRMatrix, q, k, v, *,
                     strategy: str = "nnz_split", backend: str = "auto",
                     bm: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     mesh: Optional[Mesh] = None,
                     n_chips: Optional[int] = None,
                     bk: Optional[int] = None,
                     mxu_gain: float = 4.0,
                     staging: Optional[str] = None,
                     merge_threshold: int = 0,
                     sm_scale: Optional[float] = None,
                     validate: Optional[str] = None,
                     cache: JitCache = GLOBAL_CACHE) -> jax.Array:
    """One-shot convenience: softmax(mask ⊙ (Q·Kᵀ)) · V specialized to
    the mask's structure and the runtime head/value widths; the MXU
    panel is derived from the mask where neither ``bm`` nor ``bk`` is
    given, as in :func:`compile_sparse_attention`."""
    compiled = compile_sparse_attention(
        a, q.shape[1], v.shape[1], strategy=strategy, backend=backend,
        bm=bm, interpret=interpret, mesh=mesh, n_chips=n_chips, bk=bk,
        mxu_gain=mxu_gain, staging=staging,
        merge_threshold=merge_threshold, sm_scale=sm_scale,
        validate=validate, cache=cache)
    return compiled(jnp.asarray(a.vals), q, k, v)
