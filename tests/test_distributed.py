"""Sharding rules + multi-device execution (subprocess: needs its own
XLA device count, which must be set before jax initializes)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.analysis.roofline import parse_collective_bytes

ROOT = Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_param_sharding_rules_resolve():
    out = _run("""
        import jax, json
        from repro.configs import get_config, reduced
        from repro.models.model import Model
        from repro.distributed.sharding import param_shardings
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = get_config("mixtral-8x7b")
        model = Model(cfg)
        sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        sh = param_shardings(sds, mesh)
        flat = jax.tree_util.tree_flatten_with_path(sh)[0]
        report = {}
        for path, s in flat:
            key = "/".join(str(getattr(k, "key", k)) for k in path)
            report[key] = str(s.spec)
        print(json.dumps(report))
    """)
    spec = json.loads(out.strip().splitlines()[-1])
    # experts E=8 divisible by model=4 -> EP on the stacked dim 1
    moe_gate = [v for k, v in spec.items() if "ffn_moe" in k
                and k.endswith("w_gate")][0]
    assert "'model'" in moe_gate
    # attention heads 32 % 4 == 0 -> tp on heads (stacked dim 2)
    wq = [v for k, v in spec.items() if k.endswith("wq")][0]
    assert "'model'" in wq and "'data'" in wq
    # norms replicated
    ln = [v for k, v in spec.items() if k.endswith("final_norm")][0]
    assert "'" not in ln          # replicated (no named axes)


def test_train_step_runs_on_2x4_mesh_and_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.launch.train import run_training
        cfg = reduced(get_config("qwen2.5-32b"))
        _, l_multi = run_training(cfg, steps=4, global_batch=4, seq_len=32,
                                  data_parallel=2, model_parallel=4,
                                  log_every=100)
        _, l_single = run_training(cfg, steps=4, global_batch=4, seq_len=32,
                                   data_parallel=1, model_parallel=1,
                                   log_every=100)
        print("LOSSES", l_multi, l_single)
        assert np.allclose(l_multi, l_single, rtol=5e-3, atol=5e-3), \
            (l_multi, l_single)
    """)
    assert "LOSSES" in out


def test_distributed_spmm_row_partition():
    """Chip-level SpMM: shard_map row partitions reproduce the full
    product (DESIGN.md §7.6)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import random_csr, partition_rows_for_chips
        from repro.kernels.ref import spmm_dense_ref

        mesh = jax.make_mesh((8,), ("chips",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        a = random_csr(64, 40, density=0.2, family="powerlaw", seed=3)
        x = jnp.asarray(np.random.default_rng(0).standard_normal((40, 16)),
                        jnp.float32)
        # nnz-balanced row partition, then pad each chip's rows equally
        bounds = partition_rows_for_chips(a.row_ptr, 8, "nnz_split")
        dense = np.asarray(a.to_dense())
        rows_per = int(max(np.diff(bounds)))
        a_pad = np.zeros((8, rows_per, 40), np.float32)
        for c in range(8):
            r0, r1 = bounds[c], bounds[c + 1]
            a_pad[c, : r1 - r0] = dense[r0:r1]

        def chip_fn(a_local, x_full):
            return (a_local[0] @ x_full)[None]

        y_sh = jax.shard_map(chip_fn, mesh=mesh,
                             in_specs=(P("chips", None, None), P(None, None)),
                             out_specs=P("chips", None, None))(
            jnp.asarray(a_pad), x)
        y = np.concatenate([np.asarray(y_sh[c, : bounds[c+1]-bounds[c]])
                            for c in range(8)])
        want = np.asarray(spmm_dense_ref(a.to_dense(), x))
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
        print("SPMM_SHARD_OK")
    """)
    assert "SPMM_SHARD_OK" in out


def test_collective_parser_on_synthetic_hlo():
    hlo = """
  %ag = bf16[16,1024]{1,0} all-gather(%p0), replica_groups={}
  %ar.1 = f32[256]{0} all-reduce(%x), to_apply=%add
  %rs = f32[64,32]{1,0} reduce-scatter(%y), dimensions={0}
  %cp = u32[8]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %done = f32[999]{0} all-reduce-done(%ar.1)
"""
    got = parse_collective_bytes(hlo)
    assert got["all-gather"] == 16 * 1024 * 2
    assert got["all-reduce"] == 256 * 4
    assert got["reduce-scatter"] == 64 * 32 * 4
    assert got["collective-permute"] == 8 * 4


def test_compressed_psum_wire_collective():
    """int8-wire all-reduce over 8 participants matches the f32 sum to
    quantization tolerance (the DCN-axis compression lever)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.collectives import compressed_psum
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
        got = compressed_psum(x, mesh, axis="data")
        want = x * 8.0           # every participant contributes x
        err = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(x))) / 127.0
        assert err <= 8 * scale * 0.51 + 1e-6, (err, scale)
        print("COMPRESSED_PSUM_OK", err)
    """)
    assert "COMPRESSED_PSUM_OK" in out
