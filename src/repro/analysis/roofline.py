"""Three-term roofline from compiled dry-run artifacts.

  compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
  memory term     = HLO_bytes / (chips x HBM_bw)
  collective term = collective_bytes / (chips x link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``;
collective_bytes is parsed from the partitioned HLO text
(``compiled.as_text()``): we sum the result-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instruction.  Sizes in the partitioned module are
per-participant, so we multiply by the number of chips to get fleet
totals, then divide back per the roofline formulas (the per-chip terms
are what matter).

Hardware rates come from the TPU v5e row of ``repro.platform`` (the
dry-run's target chip, named as such): peak bf16 FLOP/s and HBM
bandwidth per chip, and one ICI link's bandwidth.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from ..platform import DEVICE_SPECS, INTERPRET_DEVICE_KIND

TARGET = DEVICE_SPECS[INTERPRET_DEVICE_KIND]

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# "bf16[16,4096]{1,0}" or tuple "(f32[2], f32[2])"
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(", re.M)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes (per participant) in the module.
    `-done` ops are skipped so async pairs aren't double counted."""
    out = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        # skip the -done half of async pairs
        if "-done(" in m.group(0):
            continue
        b = _shape_bytes(shape_str)
        if "-start(" in m.group(0):
            b //= 2            # tuple carries (operand, result): count one
        out[kind] += b
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float                 # total FLOPs (fleet)
    hbm_bytes: float             # total bytes accessed (fleet)
    collective_bytes: float      # total collective bytes (fleet)
    chips: int
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bottleneck: str = ""
    model_flops: Optional[float] = None

    def finalize(self):
        self.compute_s = self.flops / (self.chips * TARGET.peak_bf16_flops)
        self.memory_s = self.hbm_bytes / (self.chips
                                          * TARGET.hbm_bytes_per_s)
        self.collective_s = self.collective_bytes / (
            self.chips * TARGET.ici_link_bytes_per_s)
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.bottleneck = max(terms, key=terms.get)
        return self

    @property
    def step_time_lower_bound(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> Optional[float]:
        if self.model_flops is None or self.flops == 0:
            return None
        return self.model_flops / self.flops

    @property
    def roofline_fraction(self) -> Optional[float]:
        """MODEL_FLOPS-time / achievable step time — the score."""
        if self.model_flops is None:
            return None
        ideal = self.model_flops / (self.chips * TARGET.peak_bf16_flops)
        lb = self.step_time_lower_bound
        return ideal / lb if lb > 0 else None

    def to_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes, "chips": self.chips,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "step_time_lower_bound_s": self.step_time_lower_bound,
        }


def analyze(cost: dict, collective_per_chip: Dict[str, int], chips: int,
            model_flops: Optional[float] = None,
            per_device_cost: bool = True) -> RooflineTerms:
    """cost: compiled.cost_analysis() dict (per-participant program);
    collective bytes are per participant -> scale both to fleet."""
    scale = chips if per_device_cost else 1
    flops = float(cost.get("flops", 0.0)) * scale
    hbm = float(cost.get("bytes accessed", 0.0)) * scale
    coll = float(sum(collective_per_chip.values())) * scale
    return RooflineTerms(flops=flops, hbm_bytes=hbm,
                         collective_bytes=coll, chips=chips,
                         model_flops=model_flops).finalize()


def model_flops_for_cell(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (train, dense) / 6·N_active·D (MoE); forward-
    only steps (prefill/decode) use 2·N·D (noted in EXPERIMENTS.md)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active * tokens
    # decode: one new token per sequence
    return 2.0 * n_active * shape.global_batch
