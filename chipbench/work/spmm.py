"""Least work of an SpMM step, counted from the problem's own shapes.

Forward ``Y = A X`` (A is m x n with nnz nonzeros, X is n x d, float32):
    flops  2 nnz d
    bytes  values and columns (4 + 4 per nonzero), row_ptr (4 (m + 1)),
           one read of X, one write of Y
Training adds ``dX = A^T dY`` and ``dvals = SDDMM(dY, X)``:
    flops  2 nnz d each
    bytes  a read of dY, a write of dX, a write of dvals

Nothing here reads the program's plan: padded slots, descriptors or
compiler estimates do not count, so any correct implementation reads
the same work.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def count(structure, config: dict, traffic: dict) -> dict:
    row_ptr, cols, (m, n) = structure
    nnz, d = int(cols.shape[0]), int(config["width"])
    flops = 2 * nnz * d
    nbytes = nnz * (F32 + I32) + I32 * (m + 1) + F32 * (n * d + m * d)
    if traffic.get("grad"):
        flops *= 3
        nbytes += F32 * (m * d + n * d + nnz)
    return {"flops": flops, "bytes": nbytes}
