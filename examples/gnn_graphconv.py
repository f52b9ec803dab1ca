"""Graph convolution with JIT-planned SpMM — the paper's own application
domain (GNNs; §I).  Trains a 2-layer GCN on a synthetic community graph
for node classification; the neighborhood aggregation A_hat·H is our
spmm with the structure planned once and cached across all steps.

  PYTHONPATH=src python examples/gnn_graphconv.py
  # multi-chip aggregation (sharded fused pallas_bcsr under shard_map):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/gnn_graphconv.py --n-chips 8
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CSRMatrix, compile_spmm
from repro.core.jit_cache import JitCache

ap = argparse.ArgumentParser()
ap.add_argument("--n-chips", type=int, default=0,
                help="shard the A_hat aggregation across this many chips "
                     "via the fused pallas_bcsr path (0 = ref backend)")
ap.add_argument("--x-sharding", default="auto",
                choices=["auto", "replicated", "rows"],
                help="feature-matrix placement on the chip mesh: "
                     "replicated per chip, or rows = each chip fetches "
                     "exactly the H panels its rows touch (exact-panel "
                     "exchange; bit-identical either way)")
ap.add_argument("--autotune", action="store_true",
                help="search strategy x CGCM merge x staging per "
                     "aggregation instance (docs/DESIGN.md §11) instead "
                     "of the fixed nnz_split plan; the winner is "
                     "memoized, so only the first compile searches "
                     "(needs a fused backend, i.e. --n-chips >= 1)")
args = ap.parse_args()

# -- synthetic 2-community graph -------------------------------------------
rng = np.random.default_rng(0)
N, D_IN, D_H, CLASSES = 256, 16, 32, 2
labels = (np.arange(N) >= N // 2).astype(np.int32)
p_in, p_out = 0.08, 0.005
rows, cols = [], []
for i in range(N):
    for j in range(i + 1, N):
        p = p_in if labels[i] == labels[j] else p_out
        if rng.random() < p:
            rows += [i, j]
            cols += [j, i]
rows = np.array(rows + list(range(N)))          # + self loops
cols = np.array(cols + list(range(N)))
deg = np.bincount(rows, minlength=N).astype(np.float64)
vals = 1.0 / np.sqrt(deg[rows] * deg[cols])     # sym-normalized A_hat
a_hat = CSRMatrix.from_coo((N, N), rows, cols, vals.astype(np.float32))
print(f"graph: {N} nodes, {a_hat.nnz} edges (incl self-loops)")

# features: noisy community indicator
feats = rng.standard_normal((N, D_IN)).astype(np.float32)
feats[:, 0] += labels * 2.0
X = jnp.asarray(feats)
y = jnp.asarray(labels)

# the JIT-planned aggregation operators (structure planned ONCE).  With
# --n-chips the same plan is row-partitioned across a 1-D device mesh and
# each chip runs its range as one fused pallas_call under shard_map.
cache = JitCache()
if args.n_chips:
    n_chips = min(args.n_chips, len(jax.devices()))
    if n_chips < args.n_chips:
        print(f"clamping --n-chips {args.n_chips} -> {n_chips} "
              f"(devices present)")
    agg_kw = dict(backend="pallas_bcsr", interpret=None, n_chips=n_chips,
                  x_sharding=args.x_sharding)
elif args.autotune:
    # the search needs the fused backend (interpret-mode on CPU,
    # native on TPU)
    agg_kw = dict(backend="pallas_bcsr", interpret=None)
else:
    agg_kw = dict(backend="ref")
if args.autotune:
    agg_kw["autotune"] = True          # DESIGN.md §11: per-instance
    agg_kw.pop("strategy", None)       # search picks the strategy
else:
    agg_kw["strategy"] = "nnz_split"
agg_h = compile_spmm(a_hat, D_H, cache=cache, **agg_kw)
agg_out = compile_spmm(a_hat, CLASSES, cache=cache, **agg_kw)
print(f"aggregation backend: {agg_h.backend}"
      + (f" sharded over {agg_h.n_chips} chip(s), "
         f"x_sharding={agg_h.x_sharding}" if agg_h.n_chips else "")
      + (f", autotuned: strategy={agg_h.strategy} "
         f"merge_threshold={agg_h.merge_threshold}"
         if args.autotune else ""))
a_vals = jnp.asarray(a_hat.vals)

def init(rng_key):
    k1, k2 = jax.random.split(rng_key)
    return {"w1": jax.random.normal(k1, (D_IN, D_H)) * 0.2,
            "w2": jax.random.normal(k2, (D_H, CLASSES)) * 0.2}

def forward(params, x):
    h = jax.nn.relu(agg_h(a_vals, x @ params["w1"]))    # A_hat (X W1)
    return agg_out(a_vals, h @ params["w2"])            # A_hat (H W2)

def loss_fn(params, x, yy):
    logits = forward(params, x)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, yy[:, None], 1))

@jax.jit
def step(params, x, yy):
    loss, g = jax.value_and_grad(loss_fn)(params, x, yy)
    params = jax.tree.map(lambda p, gg: p - 0.5 * gg, params, g)
    return params, loss

params = init(jax.random.PRNGKey(0))
for epoch in range(60):
    params, loss = step(params, X, y)
    if epoch % 10 == 0:
        acc = float(jnp.mean(jnp.argmax(forward(params, X), -1) == y))
        print(f"epoch {epoch:3d} loss {float(loss):.4f} acc {acc:.3f}")
acc = float(jnp.mean(jnp.argmax(forward(params, X), -1) == y))
print(f"final accuracy: {acc:.3f} (plan cached: {cache.stats()})")
assert acc > 0.9, "GCN should separate the two communities"
