"""The accelerator the program plans for, as one table keyed by
``device_kind``.

Every hardware number the planner, the autotuner's predictor and the
roofline read lives here, so a second chip generation is one new row.

Sources for the ``TPU v5 lite`` (v5e) row:

* peaks — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
  16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect;
* fast memories — the limits the TPU compiler reports when it compiles
  for a described ``v5e:2x2`` topology: 1 MiB of SMEM ("Used 1.00M of
  1.00M smem"), 128 MiB of VMEM, of which a kernel may use 16 MiB
  unless it raises ``vmem_limit_bytes``.

A TPU whose ``device_kind`` has no row is an error, never a default.
Under interpret mode (no TPU backend) plans are laid out for the v5e
row, named as such, so a CPU run packs exactly what the chip would run.
"""
from __future__ import annotations

import dataclasses
import os
from pathlib import Path

import jax

# vector-register geometry: f32 vregs are (SUBLANE, LANE); one such tile
# is the unit Mosaic addresses at a dynamic offset
LANE = 128
SUBLANE = 8
STAGE_TILE = SUBLANE * LANE


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    device_kind: str
    peak_bf16_flops: float     # FLOP/s per chip
    hbm_bytes_per_s: float     # per chip
    hbm_bytes: int             # per chip
    ici_link_bytes_per_s: float  # chip-to-chip, one of the chip's links
    smem_bytes: int            # scalar memory per core
    vmem_bytes: int            # vector memory per core
    vmem_scoped_bytes: int     # default per-kernel VMEM limit


DEVICE_SPECS = {
    "TPU v5 lite": DeviceSpec(
        device_kind="TPU v5 lite", peak_bf16_flops=197e12,
        hbm_bytes_per_s=819e9, hbm_bytes=16 * 10**9,
        ici_link_bytes_per_s=1600e9 / 8 / 4,  # 4 links share 1,600 Gbit/s
        smem_bytes=1 << 20,
        vmem_bytes=128 << 20, vmem_scoped_bytes=16 << 20),
}

# the row interpret-mode plans and predictions use
INTERPRET_DEVICE_KIND = "TPU v5 lite"


def device_spec(device_kind: str) -> DeviceSpec:
    try:
        return DEVICE_SPECS[device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware row for device_kind {device_kind!r}; add one to "
            f"repro.platform.DEVICE_SPECS (known: "
            f"{sorted(DEVICE_SPECS)})") from None


def current_spec() -> DeviceSpec:
    """The row of the chip this process plans for: the first TPU's
    ``device_kind`` on a TPU backend, the v5e row otherwise."""
    if jax.default_backend() == "tpu":
        return device_spec(jax.devices()[0].device_kind)
    return DEVICE_SPECS[INTERPRET_DEVICE_KIND]


@dataclasses.dataclass(frozen=True)
class StageLimits:
    """What one staged ``pallas_call`` may hold in SMEM.

    ``window``  slots one merged trip may stage: the cols ring (int32)
                and the VPU value ring (f32) are two ``(2, window)``
                SMEM buffers, a quarter of SMEM together.  The planner
                splits any block whose panel is wider.
    ``descs``   descriptors per call: the four scalar-prefetched
                ``(descs,)`` int32 tables take another quarter.  A
                longer stream is issued as a sequence of calls.
    """
    window: int
    descs: int


def stage_limits(spec: DeviceSpec = None) -> StageLimits:
    spec = current_spec() if spec is None else spec
    quarter_words = spec.smem_bytes // 4 // 4
    return StageLimits(window=quarter_words // 4, descs=quarter_words // 4)


def resident_fits(num_blocks: int, slots: int, cols: int,
                  vmem_operand_bytes: int,
                  spec: DeviceSpec = None) -> bool:
    """Whether the resident lowering fits the chip: its descriptor
    tables and both streams are scalar-prefetched into SMEM, and its
    VMEM operands (the value panel stream and X, or K/V) are double-
    buffered blocks.  Half of each memory is left to the compiler."""
    spec = current_spec() if spec is None else spec
    smem = 4 * (5 * num_blocks + slots + cols)
    vmem = 2 * (4 * slots + vmem_operand_bytes)
    return (smem <= spec.smem_bytes // 2
            and vmem <= spec.vmem_scoped_bytes // 2)


# the checkout's own compile cache: a fixed path, because the path is
# part of the cache key and a directory that moves never hits
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory
    before the first compile; the entry points call this.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and that
    directory stands; otherwise the cache lives in ``.jax_cache`` at
    the root of the checkout.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
