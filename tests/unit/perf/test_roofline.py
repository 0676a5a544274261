"""Roofline math: pure, no jax."""

import pytest

from deepspeed_tpu.perf.chip_specs import CHIP_SPECS, ChipSpec, get_chip_spec
from deepspeed_tpu.perf.hlo_stats import HloStats
from deepspeed_tpu.perf.roofline import predict

SPEC = ChipSpec("test", peak_bf16_flops=100e12, hbm_bytes_per_s=1e12,
                hbm_bytes=16 * 2**30, ici_bytes_per_s=100e9)


def test_compute_bound():
    st = HloStats(flops=100e12, bytes_accessed=1e9, collective_bytes_total=0)
    p = predict(st, SPEC)
    assert p.bound == "compute"
    assert p.step_s == pytest.approx(1.0)
    assert p.mfu_bound == pytest.approx(1.0)
    assert p.arithmetic_intensity == pytest.approx(100e12 / 1e9)


def test_memory_bound_caps_mfu():
    st = HloStats(flops=1e12, bytes_accessed=1e12, collective_bytes_total=0)
    p = predict(st, SPEC)
    assert p.bound == "memory"
    assert p.step_s == pytest.approx(1.0)
    assert p.mfu_bound == pytest.approx(0.01)


def test_collective_bound():
    st = HloStats(flops=1e9, bytes_accessed=1e9, collective_bytes_total=100e9)
    p = predict(st, SPEC)
    assert p.bound == "collective"
    assert p.step_s == pytest.approx(1.0)


def test_analytic_flops_discount_recompute_in_mfu():
    # HLO flops double the analytic model's (remat recompute): MFU halves
    st = HloStats(flops=100e12, bytes_accessed=1.0, analytic_flops=50e12)
    p = predict(st, SPEC)
    assert p.mfu_bound == pytest.approx(0.5)


def test_fits_hbm_flag():
    small = HloStats(flops=1.0, bytes_accessed=1.0, peak_bytes=2**30)
    big = HloStats(flops=1.0, bytes_accessed=1.0, peak_bytes=32 * 2**30)
    assert predict(small, SPEC).fits_hbm
    assert not predict(big, SPEC).fits_hbm


def test_empty_program():
    p = predict(HloStats(), SPEC)
    assert p.bound == "none" and p.step_s == 0.0 and p.mfu_bound == 0.0


def test_chip_table_lookup_and_default():
    assert get_chip_spec().name == "v5e"
    assert get_chip_spec("v5e").peak_bf16_flops == pytest.approx(197e12)
    with pytest.raises(KeyError):
        get_chip_spec("v99")
    assert set(CHIP_SPECS) >= {"v5e", "v5p", "v4", "v6e"}


def test_prediction_serializes():
    d = predict(HloStats(flops=1e12, bytes_accessed=1e9), SPEC).to_dict()
    assert d["chip"] == "test" and "step_s" in d and "mfu_bound" in d
