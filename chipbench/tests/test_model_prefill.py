"""The ``model_prefill`` step kind on the CPU at a small Mellum2-shaped
configuration: a sound run is correct, planted faults and the bfloat16
control are not, the new readers read what they should and nothing
where their reading is absent, and the mask generator is the model's
own mask."""
import json
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run as R

WORKLOAD = "mellum2.prefill_4k"
CONFIG = json.loads((R.BENCH_DIR / "configs" /
                     "mellum2-12b-a2.5b-ep8.json").read_text())
TRAFFIC = json.loads((R.BENCH_DIR / "traffic" /
                      "model_prefill_4k.json").read_text())
# one period of Mellum's layer types, 16 router outputs, 4 held of them
SMALL = dict(CONFIG, name="mellum-small", hidden_size=64,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             moe_intermediate_size=32, router_experts=16, num_experts=4,
             first_expert_held=4, num_experts_per_tok=4, vocab_size=256,
             sliding_window=16,
             structure=dict(CONFIG["structure"], seq_len=64, window=16))
READERS = ("sattn_kernel_ms", "model_glue_ms", "model_step_mfu",
           "sattn_plan_s")

sys.path.insert(0, str(R.ROOT / "src"))


def small_bench(root):
    bench = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    (root / "small.json").write_text(json.dumps(SMALL))
    bench["configs"] = [{"name": "small", "file": "small.json"}]
    bench["workloads"] = [{"name": WORKLOAD, "config": "small",
                           "traffic": "model_prefill_4k", "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    return bench


def run_small(root, seed=2**33 + 9, fault=None):
    return R.run(WORKLOAD, seed, 0.2, False, bench=small_bench(root),
                 root=root, cache_dir=root / "cache", require_chip=False,
                 device_kind="TPU v5 lite", fault=fault)


def test_sound_run_is_correct(tmp_path):
    res = run_small(tmp_path)
    assert res["correct"] is True and res["attempted"] >= 1
    assert set(res["metrics"]) == {"step_ms", "setup_s"}  # no HBM on a CPU
    assert 0 < res["checks"]["logits"]["value"] <= \
        res["checks"]["logits"]["limit"]


@pytest.mark.parametrize("fault", ["alter", "half"])
def test_planted_fault_is_not_correct(tmp_path, fault):
    assert run_small(tmp_path, seed=11, fault=fault)["correct"] is False


def test_a_dropped_experts_contribution_is_not_correct(tmp_path,
                                                       monkeypatch):
    """The program leaves out one held expert's part of every MLP."""
    from repro.models import moe
    moe_ffn = moe.moe_ffn

    def dropping(p, x, **kw):
        p = dict(p, w_down=p["w_down"].at[0].set(0.0))
        return moe_ffn(p, x, **kw)
    monkeypatch.setattr(moe, "moe_ffn", dropping)
    assert run_small(tmp_path, seed=12)["correct"] is False


def test_control_fails_the_limit():
    gen = R.load_module("gen", SMALL["structure"]["generator"])
    structure = gen.generate(SMALL["structure"])
    steps = R.load_module("steps", "model_prefill")
    ref_mod = R.load_module("reference", "model_prefill")
    worst = 0.0
    for seed in (1, 2, 3):
        inputs = steps.build(structure, SMALL, TRAFFIC,
                             R.seed_key(seed)).inputs
        ref = ref_mod.compute(structure, SMALL, TRAFFIC, inputs,
                              "reference")
        ctrl = ref_mod.compute(structure, SMALL, TRAFFIC, inputs, "control")
        worst = max(worst, R.scaled_error(ctrl["logits"], ref["logits"]))
    assert worst > TRAFFIC["limits"]["logits"]


def test_mask_generator_is_the_models_mask():
    from repro.models.sparse_attention import sparse_attention_mask
    s = CONFIG["structure"]
    row_ptr, cols, shape = R.load_module("gen", s["generator"]).generate(s)
    a = sparse_attention_mask(s["seq_len"], s["window"], s["global_tokens"])
    assert shape == a.shape == (4096, 4096)
    np.testing.assert_array_equal(row_ptr, a.row_ptr)
    np.testing.assert_array_equal(cols, a.col_indices)
    assert cols.size == 3_670_528


@pytest.mark.parametrize("S,window,g", [(40, 7, 0), (64, 16, 5), (9, 30, 2)])
def test_mask_generator_matches_the_pattern(S, window, g):
    gen = R.load_module("gen", "causal_window_mask")
    row_ptr, cols, _ = gen.generate(dict(seq_len=S, window=window,
                                         global_tokens=g))
    got = [cols[row_ptr[i]:row_ptr[i + 1]].tolist() for i in range(S)]
    assert got == [[j for j in range(i + 1) if i - j < window or j < g]
                   for i in range(S)]


def test_config_file_is_the_registered_model_cut():
    """The file's widths are the program's registered Mellum2; the step
    turns it into that configuration with the three cuts."""
    import dataclasses
    from repro.configs import get_config
    steps = R.load_module("steps", "model_prefill")
    cfg = steps.arch_config(CONFIG)
    full = get_config("mellum2-12b-a2.5b")
    assert cfg == dataclasses.replace(
        full, name=CONFIG["name"], num_layers=4, vocab_size=12288,
        experts_held=(0, 8), dtype="float32", notes="")
    assert CONFIG["published"] == {"num_hidden_layers": 28,
                                   "num_experts": 64, "vocab_size": 98304}
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]


def test_work_counts_the_cut_model():
    work = R.load_module("work", "model_prefill")
    gen = R.load_module("gen", "causal_window_mask")
    w = work.count(gen.generate(CONFIG["structure"]), CONFIG, TRAFFIC)
    T, D, hd, S = 4096, 2304, 128, 4096
    proj = 2 * T * D * 40 * hd + 2 * T * 32 * hd * D
    sliding = 32 * 4 * 3_670_528 * hd
    full = 32 * 4 * hd * S * (S + 1) // 2
    moe = 2 * T * D * 64 + (T * 8 * 8 // 64) * 6 * D * 896
    assert w["flops"] == 4 * (proj + moe) + 3 * sliding + full \
        + 2 * T * D * 12288
    weights = 4 * (D * 40 * hd + 32 * hd * D + D * 64 + 8 * 3 * D * 896
                   + 2 * D) + 2 * 12288 * D + D
    assert w["bytes"] == 4 * (weights + T * 12288 + 4 * 2 * S * 4 * hd) \
        + 4 * T
    peaks = R.peaks_for("TPU v5 lite")
    # compute-bound, about 7.4 ms at the bf16 peak
    assert w["flops"] / peaks["flops_per_s"] == pytest.approx(7.4e-3,
                                                              rel=0.02)


def _readings(trace, build_seconds=None):
    return R.Readings(steps=4, window_s=20.0, setup_s=50.0, peak_bytes=0,
                      build_seconds=build_seconds or {}, least_s=0.01,
                      trace=trace)


def _reduced(device_ops, busy_s=16.0, kernel_s=12.0):
    trace_reduce = R.load_module(".", "trace_reduce")
    return trace_reduce.Reduced(
        window_s=20.0, busy_s=busy_s, kernel_s=kernel_s,
        glue_s=busy_s - kernel_s, device_ops=device_ops, idle_gaps=[])


def test_readers_on_a_recorded_reading():
    r = _readings(_reduced([["attn_fused_staged [kernel]", 10.0],
                            ["fusion", 3.0], ["attn_fused [kernel]", 2.0]]),
                  {"sattn_mask": 3.5, "pack": 1.0})
    read = {name: R.load_module("metrics", name).read(r) for name in READERS}
    assert read == {"sattn_kernel_ms": 3000.0, "model_glue_ms": 1000.0,
                    "model_step_mfu": 0.2, "sattn_plan_s": 3.5}


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_where_their_reading_is_absent(name):
    reader = R.load_module("metrics", name)
    # untraced, and no sattn build (a parent without the model path)
    assert reader.read(_readings(None)) is None
    if name == "sattn_kernel_ms":
        # traced, but no fused attention kernel ran
        spmm_only = _reduced([["bcsr_fused_staged [kernel]", 9.0]])
        assert reader.read(_readings(spmm_only)) is None


def _small_inputs(seed):
    gen = R.load_module("gen", SMALL["structure"]["generator"])
    structure = gen.generate(SMALL["structure"])
    steps = R.load_module("steps", "model_prefill")
    return structure, steps.build(structure, SMALL, TRAFFIC,
                                  R.seed_key(seed)).inputs


def test_routing_ties_are_resolved_as_the_program_did(monkeypatch):
    """A token whose k-th and (k+1)-th router logits tie within the
    reference's margin may go either way; any other choice is wrong."""
    ref_mod = R.load_module("reference", "model_prefill")
    monkeypatch.setattr(ref_mod, "TIE_LOGIT", 0.05)   # ties at this size
    structure, inputs = _small_inputs(21)
    row = np.asarray(inputs["tokens"])[0]
    limit = TRAFFIC["limits"]["logits"]
    flips = np.zeros((4, row.size), bool)
    plain, near = ref_mod._forward(SMALL, inputs, row, flips, False)

    def program_flipped_at(layer, tok):
        f = flips.copy()
        f[layer, tok] = True
        out = np.asarray(ref_mod._forward(SMALL, inputs, row, f, False)[0])
        return out if ref_mod._row_gaps(out, np.asarray(plain))[tok] \
            > limit else None

    def check(program):
        got = ref_mod.compute(structure, SMALL, TRAFFIC,
                              dict(inputs, program_logits=program),
                              "reference")["logits"]
        return R.scaled_error(jnp.asarray(program), got)

    tied = [p for p in zip(*np.nonzero(near))
            if program_flipped_at(*p) is not None]
    untied = [p for p in zip(*np.nonzero(~near))
              if program_flipped_at(*p) is not None]
    assert tied and untied
    assert check(program_flipped_at(*tied[0])) == 0.0
    assert check(program_flipped_at(*untied[0])) > limit
    # no program output: the reference's own routing
    got = ref_mod.compute(structure, SMALL, TRAFFIC, inputs,
                          "reference")["logits"]
    assert R.scaled_error(plain, got) == 0.0
