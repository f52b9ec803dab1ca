"""A model's serving prefill through the program's own entry:
``jax.jit(transformer.prefill)`` of one prompt, building its KV cache,
with the configuration's file turned into the program's ``ArchConfig``.

For Mellum2: the sliding-window layers are ``sattn`` slots, which the
program attends through the fused sparse-attention artifact; the full
layers are dense ``attn`` slots with YaRN RoPE; every MLP is the
dropless expert layer over the experts this chip holds.

Weights and token ids are made on the device in one jitted call from
the seed: every matrix N(0, 0.02^2), norms 1, ids uniform over the
(sliced) vocabulary.  ``inputs`` holds them in the benchmark's own
layout, which ``reference/model_prefill.py`` reads; the program gets
the same arrays re-keyed into its parameter tree (no copies), and the
first output, a host copy, goes into ``inputs`` too.  The
products run at matrix precision ``highest``: the configuration states
float32, which a TPU's default one-pass bfloat16 product is not.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02
KINDS = {"sliding_attention": "sattn", "full_attention": "attn"}


def arch_config(config: dict):
    """The program's ``ArchConfig`` of a configuration file: one period
    of ``layer_types`` (the cut depth), the router's published width,
    the block of experts held here, and dropless routing."""
    from repro.configs.base import ArchConfig, YarnRope
    L = int(config["num_hidden_layers"])
    rope = config["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    structure = config["structure"]
    # what the program's configuration cannot state otherwise
    if not (config["norm_topk_prob"] and not config["attention_bias"]
            and not config["tie_word_embeddings"]
            and full["rope_type"] == "yarn"
            and sliding["rope_type"] == "default"
            and full["rope_theta"] == sliding["rope_theta"]
            and structure["window"] == config["sliding_window"]):
        raise ValueError(f"{config['name']}: not a Mellum2-shaped config")
    return ArchConfig(
        name=config["name"], family="moe", num_layers=L,
        d_model=int(config["hidden_size"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        d_ff=int(config["moe_intermediate_size"]),
        vocab_size=int(config["vocab_size"]),
        pattern=tuple(KINDS[t] for t in config["layer_types"][:L]),
        sparse_attn_window=int(config["sliding_window"]),
        sparse_attn_global=int(structure["global_tokens"]),
        rope_theta=float(sliding["rope_theta"]),
        rope_yarn=YarnRope(
            factor=float(full["factor"]),
            original_max_position=int(
                full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        moe=True, num_experts=int(config["router_experts"]),
        top_k=int(config["num_experts_per_tok"]), capacity_factor=None,
        experts_held=(int(config["first_expert_held"]),
                      int(config["num_experts"])),
        norm_eps=float(config["rms_norm_eps"]), dtype=config["dtype"])


@dataclasses.dataclass(frozen=True)
class Shapes:
    B: int
    S: int
    D: int
    H: int
    KV: int
    hd: int
    F: int
    E: int          # router outputs
    n: int          # experts held
    V: int
    slots: int      # layers (one period: each slot once)


def shapes(config: dict, S: int) -> Shapes:
    return Shapes(
        B=int(config["batch"]), S=S, D=int(config["hidden_size"]),
        H=int(config["num_attention_heads"]),
        KV=int(config["num_key_value_heads"]), hd=int(config["head_dim"]),
        F=int(config["moe_intermediate_size"]),
        E=int(config["router_experts"]), n=int(config["num_experts"]),
        V=int(config["vocab_size"]), slots=int(config["num_hidden_layers"]))


@functools.partial(jax.jit, static_argnums=1)
def operands(key, s: Shapes) -> dict:
    """``{"tokens", "embed", "final_norm", "lm_head", "slots": [per
    layer {"attn_ln", "wq", "wk", "wv", "wo", "moe_ln", "router",
    "w_gate", "w_up", "w_down"}]}``, each layer's arrays with a leading
    period axis of 1."""
    keys = iter(jax.random.split(key, 3 + 8 * s.slots))

    def w(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * STD

    slots = [{"attn_ln": jnp.ones((1, s.D), jnp.float32),
              "wq": w(1, s.D, s.H, s.hd), "wk": w(1, s.D, s.KV, s.hd),
              "wv": w(1, s.D, s.KV, s.hd), "wo": w(1, s.H, s.hd, s.D),
              "moe_ln": jnp.ones((1, s.D), jnp.float32),
              "router": w(1, s.D, s.E), "w_gate": w(1, s.n, s.D, s.F),
              "w_up": w(1, s.n, s.D, s.F), "w_down": w(1, s.n, s.F, s.D)}
             for _ in range(s.slots)]
    return {"tokens": jax.random.randint(next(keys), (s.B, s.S), 0, s.V,
                                         jnp.int32),
            "embed": w(s.V, s.D), "final_norm": jnp.ones((s.D,), jnp.float32),
            "lm_head": w(s.D, s.V), "slots": slots}


def program_params(cfg, inputs: dict) -> dict:
    """The program's parameter tree over the same arrays."""
    period = {}
    for i, (kind, s) in enumerate(zip(cfg.pattern, inputs["slots"])):
        period[f"slot{i}"] = {
            kind: {"ln": s["attn_ln"], "wq": s["wq"], "wk": s["wk"],
                   "wv": s["wv"], "wo": s["wo"]},
            "ffn_moe": {"ln": s["moe_ln"], "router": s["router"],
                        "w_gate": s["w_gate"], "w_up": s["w_up"],
                        "w_down": s["w_down"]}}
    return {"embed": inputs["embed"], "final_norm": inputs["final_norm"],
            "lm_head": inputs["lm_head"], "period": period}


@dataclasses.dataclass
class Step:
    call: object        # () -> {"logits": (B * S, V) float32}
    inputs: dict        # weights and ids, for the reference


def build(structure, config: dict, traffic: dict, key) -> Step:
    from repro.kernels import ops
    from repro.models import transformer
    _, _, (S, _) = structure
    cfg = arch_config(config)
    inputs = operands(key, shapes(config, S))
    params = program_params(cfg, inputs)
    tokens = inputs["tokens"]
    cache_len = int(traffic["cache_len"])

    @jax.jit
    def prefill(params, tokens):
        # the caches are outputs, so the program builds them
        logits, caches = transformer.prefill(cfg, params, tokens,
                                             cache_len)
        return logits.reshape(-1, logits.shape[-1]), caches

    def step():
        with ops.span("model.prefill"), \
                jax.default_matmul_precision("highest"):
            logits, _ = prefill(params, tokens)
        if "program_logits" not in inputs:
            # the first (warm-up) output, for the reference to resolve
            # routing ties as the program did (reference/model_prefill)
            inputs["program_logits"] = np.asarray(logits)
        return {"logits": logits}
    return Step(call=step, inputs=inputs)
