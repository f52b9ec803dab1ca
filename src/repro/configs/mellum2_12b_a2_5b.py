"""mellum2-12b-a2.5b [moe] — JetBrains' code-completion MoE: every MLP
sparse (64 experts, top-8, renormalised, no shared expert), and a
period of three sliding-window layers and one full-attention layer.
The sliding layers are "sattn" slots (causal window 1024, no global
columns) lowered through the fused sparse-attention artifact; the full
ones are dense "attn" slots with YaRN RoPE.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]"""
from .base import ArchConfig, YarnRope, register

CONFIG = register(ArchConfig(
    name="mellum2-12b-a2.5b", family="moe",
    num_layers=28, d_model=2304, num_heads=32, num_kv_heads=4,
    head_dim=128, d_ff=896, vocab_size=98304,
    pattern=("sattn", "sattn", "sattn", "attn"),
    sparse_attn_window=1024, sparse_attn_global=0,
    rope_theta=5e5,
    rope_yarn=YarnRope(factor=16.0, original_max_position=8192,
                       beta_fast=32.0, beta_slow=1.0,
                       attention_factor=1.2772588722239782),
    moe=True, num_experts=64, top_k=8, capacity_factor=None,
    norm_eps=1e-6,
    notes="d_ff is moe_intermediate_size (every layer is MoE, so the "
          "dense intermediate_size 7168 is unused); no qk-norm and no "
          "attention bias (the config has neither); dropless routing; "
          "the multi-token-prediction head is not modelled",
))
