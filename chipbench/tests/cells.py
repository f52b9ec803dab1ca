"""Small configurations for driving the harness on the CPU: the same
cells, traffic and readers as BENCHMARK.json, at sizes a test holds."""
import json
from pathlib import Path

from chipbench import run as R

GRAPH = {"name": "graph", "width": 16, "structure": {
    "generator": "power_law_graph", "seed": 3, "nodes": 3000,
    "edges": 40000, "out_degree_shape": 1.5, "out_degree_cap": 900,
    "in_rank_exponent": 0.5}}
MASK = {"name": "mask", "num_attention_heads": 2, "head_dim": 64,
        "structure": {"generator": "longformer_mask", "seq_len": 512,
                      "attention_window": 128, "global_tokens": 16}}
# every traffic mix of the benchmark, each on a small configuration
CELLS = {"pokec.spmm_fwd": ("graph", "spmm_fwd"),
         "longformer.attn_fwd": ("mask", "attn_fwd"),
         "pokec.spmm_train": ("graph", "spmm_train")}
WORKLOADS = tuple(CELLS)


def small_bench(root: Path) -> dict:
    """BENCHMARK.json's metrics over the cells above, with the small
    configurations written under ``root``."""
    bench = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = []
    for cfg in (GRAPH, MASK):
        path = root / f"{cfg['name']}.json"
        path.write_text(json.dumps(cfg))
        bench["configs"].append({"name": cfg["name"], "file": path.name})
    bench["workloads"] = [{"name": name, "config": c, "traffic": t,
                           "chips": 1} for name, (c, t) in CELLS.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    return bench


def run_small(root: Path, workload: str, seed: int = 2**33 + 5,
              trace: bool = False, fault: str = None):
    return R.run(workload, seed, 0.2, trace, bench=small_bench(root),
                 root=root, cache_dir=root / "cache", require_chip=False,
                 device_kind="TPU v5 lite", fault=fault)
