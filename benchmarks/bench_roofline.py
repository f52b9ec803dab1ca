"""Roofline table from the dry-run artifacts (§Roofline source).

Merges the probe-extrapolated compute/collective terms from
artifacts/dryrun/*.json with the analytic HBM-traffic model
(analysis/memmodel.py); emits one row per (arch x shape x mesh) cell.
Run after the dry-run sweep; also used by tools/make_experiments.py to
regenerate EXPERIMENTS.md tables.
"""
from __future__ import annotations

import glob
import json
from pathlib import Path

from repro.analysis import memmodel
from repro.analysis.roofline import TARGET
from repro.configs import SHAPES, get_config

from .common import csv_row

ARTIFACTS = Path("artifacts/dryrun")


def cell_summary(rec: dict) -> dict:
    cfg = get_config(rec["arch"])
    shape = SHAPES[rec["shape"]]
    multi = rec["mesh"] != "pod16x16"
    chips = rec["chips"]
    ext = rec["cost_extrapolated_per_chip"]
    rf = rec["roofline"]
    compute_s = ext["flops"] / TARGET.peak_bf16_flops
    coll_s = sum(ext["collectives"].values()) / TARGET.ici_link_bytes_per_s
    mem_s = memmodel.memory_seconds(cfg, shape, multi_pod=multi,
                                    remat=rec.get("remat", "full"))
    mem_upper_s = ext["bytes"] / TARGET.hbm_bytes_per_s
    terms = {"compute": compute_s, "memory": mem_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    lb = max(terms.values())
    ideal = rf["model_flops"] / chips / TARGET.peak_bf16_flops
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "chips": chips,
        "compute_s": compute_s, "memory_s": mem_s,
        "memory_upper_s": mem_upper_s, "collective_s": coll_s,
        "bottleneck": bottleneck,
        "model_flops": rf["model_flops"],
        "hlo_flops_fleet": ext["flops"] * chips,
        "useful_flops_ratio": rf["model_flops"] / (ext["flops"] * chips),
        "roofline_fraction": (ideal / lb) if lb > 0 else None,
        "step_lower_bound_s": lb,
    }


def load_cells(tag: str = ""):
    cells = []
    for f in sorted(glob.glob(str(ARTIFACTS / "*.json"))):
        rec = json.loads(Path(f).read_text())
        if rec.get("tag", "") != tag:
            continue
        if rec["status"] != "ok":
            cells.append(rec)
            continue
        cells.append({**rec, "summary": cell_summary(rec)})
    return cells


def run() -> list:
    rows = []
    for rec in load_cells():
        cell = f"{rec['arch']}.{rec['shape']}.{rec['mesh']}"
        if rec["status"] == "skip":
            rows.append(csv_row(f"roofline_{cell}", 0.0,
                                f"SKIP:{rec['reason'][:60]}"))
            continue
        if rec["status"] != "ok":
            rows.append(csv_row(f"roofline_{cell}", 0.0,
                                f"ERROR:{rec.get('error','')[:60]}"))
            continue
        s = rec["summary"]
        rows.append(csv_row(
            f"roofline_{cell}", s["step_lower_bound_s"] * 1e6,
            f"bneck={s['bottleneck']};compute_s={s['compute_s']:.3f};"
            f"memory_s={s['memory_s']:.3f};coll_s={s['collective_s']:.3f};"
            f"useful={s['useful_flops_ratio']:.3f};"
            f"roofline_frac={s['roofline_fraction']:.4f}"))
    return rows
