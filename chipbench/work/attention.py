"""Least work of one layer's sparse self-attention, counted from the
problem's own shapes: per head, ``O = softmax(mask * (Q K^T) / sqrt(dh)) V``
over the mask's nnz entries (Q, K, V, O are S x dh float32).

    flops  4 nnz dh per head: 2 for the scores, 2 for P V
    bytes  Q, K, V and O of every head, plus the mask once per step:
           its columns and weights (4 + 4 per entry) and row_ptr

The softmax's exponentials are not counted.  Nothing here reads the
program's plan.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def count(structure, config: dict, traffic: dict) -> dict:
    row_ptr, cols, (S, _) = structure
    nnz = int(cols.shape[0])
    heads, dh = int(config["num_attention_heads"]), int(config["head_dim"])
    flops = heads * 4 * nnz * dh
    nbytes = (heads * 4 * S * dh * F32 + nnz * (F32 + I32)
              + I32 * (S + 1))
    return {"flops": flops, "bytes": nbytes}
