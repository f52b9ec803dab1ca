# Pallas TPU kernels for the paper's compute hot-spots, each with a
# pure-jnp oracle in ref.py (validated in interpret mode on CPU):
#   spmm_bcsr_fused         — THE fused SpMM dispatch: every block-row of
#                             the plan, VPU (ELL gather+FMA) or MXU ((bm x
#                             bk) block matmul) by its descriptor tag, in
#                             one pallas_call; _staged stages every stream
#                             through HBM->SMEM/VMEM window rings, _sharded
#                             runs it once per chip under shard_map
#   staging                 — the (rows, LANE) stream view, window DMAs,
#                             per-chip windows and multi-call issue the
#                             fused kernels share
#   attn_fused              — the sparse-attention sandwich: SDDMM ->
#                             in-register segment softmax -> S·V through
#                             the SAME descriptor stream, one dispatch,
#                             S never in HBM (DESIGN.md §13)
# ops.py wraps each kernel with the resolved interpret flag and the
# DISPATCH_COUNTS host counter the Table IV invariant tests read.
from . import ops, ref
from .attn_fused import attn_fused, attn_fused_sharded, attn_fused_staged
from .spmm_bcsr_fused import (spmm_bcsr_fused, spmm_bcsr_fused_sharded,
                              spmm_bcsr_fused_staged)

__all__ = ["ops", "ref", "attn_fused", "attn_fused_sharded",
           "attn_fused_staged", "spmm_bcsr_fused", "spmm_bcsr_fused_sharded",
           "spmm_bcsr_fused_staged"]
