# Pallas TPU kernels for the paper's compute hot-spots, each with a
# pure-jnp oracle in ref.py (validated in interpret mode on CPU):
#   spmm_bcsr_fused         — THE fused SpMM dispatch: every block-row of
#                             the plan, VPU (ELL gather+FMA) or MXU ((bm x
#                             bk) block matmul) by its descriptor tag, in
#                             one pallas_call; _staged stages every stream
#                             through HBM->SMEM/VMEM window rings, _sharded
#                             runs it once per chip under shard_map
#   spmm_ell_fused          — the same kernel on a pure-ELL table (every
#                             tag VPU), keeping the ELL entry points
#   staging                 — the (rows, LANE) stream view, window DMAs,
#                             per-chip windows and multi-call issue the
#                             fused kernels share
#   attn_fused              — the sparse-attention sandwich: SDDMM ->
#                             in-register segment softmax -> S·V through
#                             the SAME descriptor stream, one dispatch,
#                             S never in HBM (DESIGN.md §13)
#   spmm_ell_segment        — single-segment micro-oracle retained from
#                             the per-segment era (paper Listing 2 CCM/VPU
#                             port); production traffic uses the fused path
#   spmm_bcsr               — pre-fusion MXU micro-oracle (global-Kmax
#                             padding); kernel-level regression sweeps only
#   sddmm                   — backward twin (dA.vals = <dY[row], X[col]>)
# ops.py wraps each kernel with the resolved interpret flag and the
# DISPATCH_COUNTS host counter the Table IV invariant tests read.
from . import ops, ref
from .attn_fused import attn_fused, attn_fused_sharded, attn_fused_staged
from .spmm_csr import spmm_ell_segment
from .spmm_ell_fused import (spmm_ell_fused, spmm_ell_fused_sharded,
                             spmm_ell_fused_staged)
from .spmm_bcsr import spmm_bcsr
from .spmm_bcsr_fused import (spmm_bcsr_fused, spmm_bcsr_fused_sharded,
                              spmm_bcsr_fused_staged)
from .sddmm import sddmm, sddmm_csr

__all__ = ["ops", "ref", "attn_fused", "attn_fused_sharded",
           "attn_fused_staged", "spmm_ell_segment", "spmm_ell_fused",
           "spmm_ell_fused_sharded", "spmm_ell_fused_staged",
           "spmm_bcsr", "spmm_bcsr_fused", "spmm_bcsr_fused_sharded",
           "spmm_bcsr_fused_staged", "sddmm", "sddmm_csr"]
