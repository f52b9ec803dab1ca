"""Least work of one prefill of a sliding/full-attention MoE decoder
(Mellum2's shape), counted from the problem's own shapes, with T = B S
tokens, per layer:

    flops  projections   2 T D (H + 2 KV) hd  +  2 T H hd D
           sliding       4 nnz hd per head over the mask's nonzeros
                         (2 for the scores, 2 for P V)
           full          4 hd S (S + 1) / 2 per head and prompt: causal
           router        2 T D E over all E router outputs
           experts       3 x 2 D F per routed row, at the T k n / E rows
                         the held experts see when routing is even
    and the head 2 T D V over the sliced vocabulary.
    bytes  every weight read once (float32), the ids read, the logits
           and the K/V caches (cache_len rows per layer) written.

Norms, RoPE, softmax exponentials and the routing's sort are not
counted.  Nothing here reads the program's plan.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def count(structure, config: dict, traffic: dict) -> dict:
    row_ptr, cols, (S, _) = structure
    nnz = int(cols.shape[0])
    B = int(config["batch"])
    D, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    KV, hd = int(config["num_key_value_heads"]), int(config["head_dim"])
    F, k = int(config["moe_intermediate_size"]), int(
        config["num_experts_per_tok"])
    E, n = int(config["router_experts"]), int(config["num_experts"])
    V, L = int(config["vocab_size"]), int(config["num_hidden_layers"])
    types = config["layer_types"][:L]
    T = B * S
    proj = 2 * T * D * (H + 2 * KV) * hd + 2 * T * H * hd * D
    attend = {"sliding_attention": B * H * 4 * nnz * hd,
              "full_attention": B * H * 4 * hd * S * (S + 1) // 2}
    router = 2 * T * D * E
    experts = (T * k * n // E) * 3 * 2 * D * F
    flops = sum(proj + attend[t] + router + experts for t in types) \
        + 2 * T * D * V
    layer_weights = D * (H + 2 * KV) * hd + H * hd * D + D * E \
        + n * 3 * D * F + 2 * D
    weights = L * layer_weights + 2 * V * D + D
    cache = L * 2 * B * int(traffic["cache_len"]) * KV * hd
    nbytes = F32 * (weights + T * V + cache) + I32 * T
    return {"flops": flops, "bytes": nbytes}
