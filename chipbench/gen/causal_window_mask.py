"""A causal sliding window plus global key columns, as a CSR structure:
the mask of a decoder's sliding-window attention layer (Mistral-style,
``sliding_window`` in a Hugging Face config).

Parameters (the ``structure`` block of a configuration file):

    seq_len        queries and keys
    window         query i sees keys j with j <= i and i - j < window
    global_tokens  and also the keys j < global_tokens (0: none)

Columns are sorted within each row: the globals left of the window,
then the window.  The structure takes no seed.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int = 0):
    S = int(params["seq_len"])
    w = int(params["window"])
    g = min(int(params["global_tokens"]), S)
    i = np.arange(S, dtype=np.int64)
    lo = np.maximum(i - w + 1, 0)           # the window's first key
    n_glob = np.minimum(lo, g)              # globals left of the window
    length = n_glob + i - lo + 1
    row_ptr = np.zeros(S + 1, np.int64)
    np.cumsum(length, out=row_ptr[1:])
    row = np.repeat(i, length)
    pos = np.arange(row_ptr[-1], dtype=np.int64) - row_ptr[row]
    cols = np.where(pos < n_glob[row], pos, lo[row] + pos - n_glob[row])
    return row_ptr, cols.astype(np.int32), (S, S)
