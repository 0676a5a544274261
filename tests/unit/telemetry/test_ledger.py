"""Cost-attribution ledger units: PriceBook pricing, RequestCost accrual,
TenantRollup bounding, CostLedger conservation, and the predicted-vs-observed
PerfObservedLedger (compile amnesty, baseline freeze, drift detection)."""

import pytest

from deepspeed_tpu.perf.observed import PerfObservedLedger, _bucket
from deepspeed_tpu.telemetry import MetricsRegistry
from deepspeed_tpu.telemetry.ledger import (OTHER_TENANT, PHASES, CostLedger,
                                            PriceBook, RequestCost,
                                            TenantRollup)


class _Req:
    """The slice of Request the ledger touches."""

    def __init__(self, tenant=None):
        self.tenant = tenant
        self.cost = None


# ---------------------------------------------------------------- PriceBook --
def test_pricebook_fallback_and_analytic():
    fallback = PriceBook()
    assert fallback.source == "fallback"
    assert fallback.flops(10) == 10 * fallback.flops_per_token

    class Cfg:
        hidden_size = 64
        num_layers = 2
        vocab_size = 256
        intermediate_size = 128

    book = PriceBook.from_model_config(Cfg())
    assert book.source == "analytic"
    params = 2 * (4 * 64 * 64 + 3 * 64 * 128) + 256 * 64
    assert book.flops_per_token == 2.0 * params
    assert book.bytes_per_token == 2.0 * params  # bf16


def test_pricebook_bad_config_falls_back():
    assert PriceBook.from_model_config(None).source == "fallback"
    assert PriceBook.from_model_config(object()).source == "fallback"


# -------------------------------------------------------------- RequestCost --
def test_request_cost_docs_and_compact_row():
    cost = RequestCost(PriceBook())
    cost.tokens["prefill"] = 100
    cost.tokens["decode"] = 20
    cost.device_seconds = 0.25
    cost.kv_block_seconds["device"] = 3.0
    cost.wire_bytes["handoff"] = 512
    doc = cost.to_dict()
    assert doc["tokens"]["billed"] == 120
    assert doc["flops"] == PriceBook().flops(120)
    row = cost.compact_row()
    assert row == {"billed_tokens": 120, "device_ms": 250.0,
                   "kv_block_s": 3.0, "wire_bytes": 512}


# ------------------------------------------------------------- TenantRollup --
def test_tenant_rollup_bounds_and_conserves():
    rollup = TenantRollup(max_tenants=2)
    for tenant in ("a", "b", "c", "d"):
        cost = RequestCost(PriceBook())
        cost.tokens["decode"] = 10
        bucket = rollup.fold(tenant, cost)
        assert bucket == (tenant if tenant in ("a", "b") else OTHER_TENANT)
    doc = rollup.doc()
    assert set(doc) == {"a", "b", OTHER_TENANT}
    # overflow folds, never drops: the sum over rows is all 4 requests
    assert sum(row["tokens"]["billed"] for row in doc.values()) == 40
    assert sum(row["requests"] for row in doc.values()) == 4


# --------------------------------------------------------------- CostLedger --
def test_charge_dispatch_amortizes_by_token_share():
    reg = MetricsRegistry()
    ledger = CostLedger(reg, PriceBook())
    a, b = _Req("a"), _Req("b")
    ledger.begin(a)
    ledger.begin(b)
    # one dispatch, 30 + 10 fed tokens: wall time splits 3:1
    ledger.charge_dispatch([(a.cost, "prefill", 30), (b.cost, "prefill", 10)],
                           seconds=0.4, amnesty_s=0.04)
    assert a.cost.device_seconds == pytest.approx(0.3)
    assert b.cost.device_seconds == pytest.approx(0.1)
    assert a.cost.amnesty_seconds == pytest.approx(0.03)
    # the aggregate got the SAME dispatch exactly once
    assert ledger.totals.device_seconds == pytest.approx(0.4)
    assert ledger.totals.dispatches == 1
    assert ledger.totals.tokens["prefill"] == 40


def test_kv_touch_accrues_piecewise_constant():
    reg = MetricsRegistry()
    ledger = CostLedger(reg, PriceBook())
    req = _Req()
    ledger.begin(req)
    ledger.touch_kv(req.cost, blocks=4, tier="device", now_s=10.0)
    # 2s at 4 device blocks, then the occupancy moves to 2 host blocks
    ledger.touch_kv(req.cost, blocks=2, tier="host", now_s=12.0)
    ledger.finalize(req, now_s=15.0)  # closes the 3s host segment
    assert req.cost.kv_block_seconds["device"] == pytest.approx(8.0)
    assert req.cost.kv_block_seconds["host"] == pytest.approx(6.0)
    assert ledger.totals.kv_block_seconds == req.cost.kv_block_seconds


def test_conservation_per_tenant_sums_match_aggregate():
    """The conservation gate's core: after every request finalizes, the sum
    over tenant rows equals the aggregate exactly on the integer fields."""
    reg = MetricsRegistry()
    ledger = CostLedger(reg, PriceBook(), max_tenants=2)
    reqs = [_Req(t) for t in ("a", "b", "c", "a", None)]
    for i, req in enumerate(reqs):
        ledger.begin(req)
        ledger.charge_dispatch([(req.cost, "prefill", 7 + i)], seconds=0.01)
        ledger.charge_dispatch([(req.cost, "decode", 3)], seconds=0.002)
        ledger.charge_wire(req.cost, "handoff", 100 + i)
        ledger.charge_spec(req.cost, drafted=4, accepted=2)
        ledger.finalize(req, now_s=float(i))
    rows = ledger.usage_doc()["tenants"].values()
    totals = ledger.usage_doc()["totals"]
    for field in ("billed",):
        assert sum(r["tokens"][field] for r in rows) == totals["tokens"][field]
    for phase in PHASES:
        assert sum(r["tokens"][phase] for r in rows) == totals["tokens"][phase]
    assert sum(r["requests"] for r in rows) == totals["requests"] == 5
    assert sum(r["wire_bytes"].get("handoff", 0) for r in rows) \
        == totals["wire_bytes"]["handoff"]
    assert sum(r["speculative"]["accepted"] for r in rows) \
        == totals["speculative"]["accepted"] == 10
    # a and b claimed the 2 tenant slots; c and the unlabeled request (its
    # default-tenant identity arrived after the cap) folded into <other>
    assert set(ledger.usage_doc()["tenants"]) == {"a", "b", OTHER_TENANT}


def test_tenant_metric_top_k_overflow():
    reg = MetricsRegistry()
    ledger = CostLedger(reg, PriceBook(), max_tenants=16,
                        tenant_metric_top_k=2)
    for tenant in ("a", "b", "c", "d"):
        req = _Req(tenant)
        ledger.begin(req)
        ledger.charge_dispatch([(req.cost, "decode", 5)], seconds=0.001)
        ledger.finalize(req, now_s=0.0)
    # the rollup keeps all 4 rows, the metric families only top-K + <other>
    assert set(ledger.usage_doc()["tenants"]) == {"a", "b", "c", "d"}
    labeled = {t for t in ledger._tenant_m}
    assert labeled == {"a", "b", OTHER_TENANT}


# ------------------------------------------------------- PerfObservedLedger --
def test_bucket_is_next_power_of_two():
    assert [_bucket(n) for n in (1, 2, 3, 8, 9, 100)] == [1, 2, 4, 8, 16, 128]


def test_program_mapping():
    pf = PerfObservedLedger.program_for
    assert pf("decode_loop", 4, 4) == "paged_decode_step"
    assert pf("verify_tree", 1, 16) == "spec_tree_verify"
    assert pf("put", 2, 50) == "prefix_suffix_prefill"
    assert pf("put", 4, 4) == "paged_decode_step"  # all-single-token feeds


def test_compile_amnesty_then_ratio_gauge():
    reg = MetricsRegistry()
    perf = PerfObservedLedger(reg, PriceBook(), baseline_dispatches=2)
    # first sight of (program, bucket): the whole wall time is amnesty
    assert perf.observe("decode_loop", 4, 4, 0.5) == 0.5
    assert perf.observe("decode_loop", 4, 4, 0.01) == 0.0
    doc = perf.doc()
    (row,) = doc["programs"]
    assert row["program"] == "paged_decode_step" and row["bucket"] == 4
    assert row["dispatches"] == 1  # the amnestied dispatch is excluded
    assert row["ratio"] == pytest.approx(0.01 / row["predicted_s"])


def test_drift_event_after_consecutive_over_baseline():
    reg = MetricsRegistry()
    perf = PerfObservedLedger(reg, PriceBook(), drift_factor=4.0,
                              drift_consecutive=3, baseline_dispatches=2)
    perf.observe("decode_loop", 4, 4, 1.0)  # amnesty
    for _ in range(2):                      # freeze baseline at 0.01s
        perf.observe("decode_loop", 4, 4, 0.01)
    # two slow dispatches: under drift_consecutive, no event yet
    for _ in range(2):
        perf.observe("decode_loop", 4, 4, 0.01 * 10)
    assert perf.doc()["programs"][0]["drift_events"] == 0
    perf.observe("decode_loop", 4, 4, 0.01 * 10)  # third consecutive
    assert perf.doc()["programs"][0]["drift_events"] == 1
    counter = reg.counter("perf_drift_events_total",
                          labels={"program": "paged_decode_step"})
    assert counter.value == 1
    # a fast dispatch resets the run: no spurious second event
    perf.observe("decode_loop", 4, 4, 0.01)
    perf.observe("decode_loop", 4, 4, 0.01 * 10)
    assert counter.value == 1


def test_explicit_predictions_override_roofline():
    reg = MetricsRegistry()
    perf = PerfObservedLedger(reg, PriceBook(), baseline_dispatches=1)
    perf.load_predictions({"paged_decode_step": 0.02})
    perf.observe("decode_loop", 4, 4, 1.0)  # amnesty
    perf.observe("decode_loop", 4, 4, 0.04)
    (row,) = perf.doc()["programs"]
    assert row["predicted_s"] == 0.02
    assert row["ratio"] == pytest.approx(2.0)
