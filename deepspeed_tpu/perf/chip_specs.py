"""Per-chip peak specs for the roofline model.

Public-datasheet numbers (per chip, bf16 dense peak; HBM and ICI are
aggregate per-chip bandwidths). These feed :mod:`deepspeed_tpu.perf.roofline`
to turn HLO-level facts into predicted step times — the specs are the only
chip-dependent piece of the perf-gate subsystem, so a new chip generation is
one table row, not a new gate.
"""

from dataclasses import dataclass
from typing import Dict, Tuple


@dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_bf16_flops: float      # dense bf16 FLOP/s per chip
    hbm_bytes_per_s: float      # HBM bandwidth per chip
    hbm_bytes: float            # HBM capacity per chip
    ici_bytes_per_s: float      # aggregate inter-chip interconnect bandwidth
    notes: str = ""
    device_kinds: Tuple[str, ...] = ()  # jax ``device_kind`` strings of this chip


CHIP_SPECS: Dict[str, ChipSpec] = {
    # the deployment target (BASELINE.json: v5e-1 ZeRO-3 Llama SFT)
    "v5e": ChipSpec("v5e", peak_bf16_flops=197e12, hbm_bytes_per_s=819e9,
                    hbm_bytes=16 * 2**30, ici_bytes_per_s=2 * 200e9 / 2,
                    notes="v5litepod; 1600 Gbps ICI aggregate (200 GB/s, counted one-way)",
                    device_kinds=("TPU v5 lite", "TPU v5e")),
    "v5p": ChipSpec("v5p", peak_bf16_flops=459e12, hbm_bytes_per_s=2765e9,
                    hbm_bytes=95 * 2**30, ici_bytes_per_s=600e9,
                    device_kinds=("TPU v5", "TPU v5p")),
    "v4": ChipSpec("v4", peak_bf16_flops=275e12, hbm_bytes_per_s=1228e9,
                   hbm_bytes=32 * 2**30, ici_bytes_per_s=300e9,
                   device_kinds=("TPU v4", )),
    "v6e": ChipSpec("v6e", peak_bf16_flops=918e12, hbm_bytes_per_s=1640e9,
                    hbm_bytes=32 * 2**30, ici_bytes_per_s=448e9,
                    notes="trillium", device_kinds=("TPU v6 lite", "TPU v6e")),
    # CPU smoke entry so roofline math is exercisable in tests without
    # pretending the numbers mean anything about a TPU
    "cpu-host": ChipSpec("cpu-host", peak_bf16_flops=1e12, hbm_bytes_per_s=100e9,
                         hbm_bytes=64 * 2**30, ici_bytes_per_s=10e9,
                         notes="placeholder host spec for tests"),
}

DEFAULT_CHIP = "v5e"


def get_chip_spec(name: str = DEFAULT_CHIP) -> ChipSpec:
    try:
        return CHIP_SPECS[name]
    except KeyError:
        raise KeyError(f"unknown chip {name!r}; known: {sorted(CHIP_SPECS)}") from None


def chip_spec_for_device_kind(device_kind: str) -> ChipSpec:
    """The table row of the chip whose devices report ``device_kind`` (what
    ``jax.devices()[0].device_kind`` says; a v5e says ``TPU v5 lite``). A kind
    the table does not list is an error, never a default: a peak assumed for
    an unknown device turns every utilization computed from it into fiction."""
    for spec in CHIP_SPECS.values():
        if device_kind in spec.device_kinds:
            return spec
    known = sorted(k for s in CHIP_SPECS.values() for k in s.device_kinds)
    raise KeyError(f"no peak specs for device kind {device_kind!r}; known: {known}")
