"""Fair-share admission (ISSUE tentpole b): per-tenant rate EWMAs, the
deficit-weighted over-share verdict with hysteresis, the brownout-gated
admission 429 and queue-shed paths, and the flood gate — a one-tenant flood
cannot starve a well-behaved tenant's interactive deadline goodput.

Policy math (FairSharePolicy, validate_tenant) is tested engine-free;
scheduler behavior drives ``step()`` manually (``start=False``) like
test_overload.py. The flood gate runs real engine work on a warmed engine
with deadlines derived from a measured baseline, so it is rate-calibrated
rather than wall-clock-guessed.
"""

import time

import numpy as np
import pytest

from deepspeed_tpu.serving import (AdmissionRejected, RequestState,
                                   ServingConfig, ServingScheduler)
from deepspeed_tpu.serving.config import OverloadConfig
from deepspeed_tpu.serving.overload import FairSharePolicy, validate_tenant

MAX_STEPS = 400


def _run_until(sched, pred, max_steps=MAX_STEPS):
    for _ in range(max_steps):
        if pred():
            return
        sched.step()
    raise AssertionError(f"predicate not reached in {max_steps} steps")


def _prompt(n=9, vocab=64):
    return (np.arange(n) % vocab).tolist()


def _force_stage(sched, minimum=1):
    """Deterministically drive the brownout controller past ``minimum``, then
    pin it there: per-tick pressure sampling must not decay the stage while a
    test exercises the pressure-gated fair-share paths."""
    for _ in range(30):
        sched._brownout.update(1.0)
    assert sched._brownout.stage >= minimum
    sched._brownout.update = lambda pressure: sched._brownout.stage


# ---------------------------------------------------------------------------
# policy primitives (engine-free)
# ---------------------------------------------------------------------------
def test_validate_tenant_normalizes_and_rejects():
    assert validate_tenant(None) is None
    assert validate_tenant("") is None
    assert validate_tenant("   ") is None  # whitespace-only = unlabeled
    assert validate_tenant("  acme \t") == "acme"
    with pytest.raises(ValueError, match="longer"):
        validate_tenant("x" * 65)
    for bad in ("a\nb", "a\rb", "a\x00b"):
        with pytest.raises(ValueError, match="control"):
            validate_tenant(bad)


def test_lone_tenant_owns_share_one_and_is_never_over():
    """The policy is inert until there is someone to be unfair to."""
    fs = FairSharePolicy(alpha=1.0)
    fs.observe("only", 10_000, now=0.0)
    fs.observe("only", 10_000, now=1.0)
    assert fs.configured_share("only") == 1.0
    assert fs.measured_share("only") == 1.0
    assert not fs.over_share("only")  # measured <= 1.0 < over_factor * 1.0


def test_observe_ignores_zero_tokens_and_non_advancing_clock():
    fs = FairSharePolicy(alpha=1.0)
    fs.observe("a", 100, now=0.0)  # anchor only: no interval yet
    assert fs.measured_share("a") == 0.0
    fs.observe("a", 100, now=1.0)
    rate = fs.doc()["tenants"]["a"]["rate_tokens_per_s"]
    assert rate == pytest.approx(100.0)
    fs.observe("a", 0, now=2.0)    # zero tokens: dropped entirely
    fs.observe("a", 50, now=0.5)   # behind the last observation: dt <= 0
    assert fs.doc()["tenants"]["a"]["rate_tokens_per_s"] == pytest.approx(rate)


def test_over_share_enters_and_clears_with_hysteresis():
    fs = FairSharePolicy(alpha=1.0, over_factor=1.25, hysteresis=0.25)
    for t in ("hog", "meek"):
        fs.observe(t, 1, now=0.0)  # anchors
    # equal default shares (0.5 each); hog takes ~99% of the measured rate
    fs.observe("hog", 9_900, now=1.0)
    fs.observe("meek", 100, now=1.0)
    assert not fs.over_share("meek")
    assert fs.over_share("hog")  # 0.99 > 1.25 * 0.5
    # hysteresis holds the flag in the dead band: 0.55 is under the 0.625
    # enter threshold but above the (1.25 - 0.25) * 0.5 = 0.5 clear threshold
    fs.observe("hog", 5_500, now=2.0)
    fs.observe("meek", 4_500, now=2.0)
    assert fs.measured_share("hog") == pytest.approx(0.55)
    assert fs.over_share("hog")
    # a fresh policy at the same measured split would NOT flag — the flag is
    # state, not a pure function of the rates
    fresh = FairSharePolicy(alpha=1.0, over_factor=1.25, hysteresis=0.25)
    for t, tok in (("hog", 5_500), ("meek", 4_500)):
        fresh.observe(t, 1, now=0.0)
        fresh.observe(t, tok, now=1.0)
    assert not fresh.over_share("hog")
    # falling below the clear threshold releases the original flag
    fs.observe("hog", 1_000, now=3.0)
    fs.observe("meek", 9_000, now=3.0)
    assert not fs.over_share("hog")


def test_explicit_shares_weight_the_entitlement():
    fs = FairSharePolicy(shares={"gold": 3.0, "bronze": 1.0}, alpha=1.0)
    for t in ("gold", "bronze"):
        fs.observe(t, 1, now=0.0)
        fs.observe(t, 5_000, now=1.0)  # equal measured rates
    assert fs.configured_share("gold") == pytest.approx(0.75)
    assert fs.configured_share("bronze") == pytest.approx(0.25)
    # at a 50/50 measured split, bronze is past 1.25 x 0.25, gold is under
    assert fs.deficit("bronze") == pytest.approx(0.25)
    assert fs.deficit("gold") == pytest.approx(-0.25)
    assert fs.over_share("bronze") and not fs.over_share("gold")
    # a tenant the map does not list gets weight 1.0, never zero entitlement
    fs.note("walkin")
    assert fs.configured_share("walkin") == pytest.approx(1.0 / 5.0)


def test_doc_shape():
    fs = FairSharePolicy(alpha=1.0, over_factor=1.5, hysteresis=0.1)
    fs.note("a")
    doc = fs.doc()
    assert doc["over_factor"] == 1.5 and doc["sheds"] == 0
    row = doc["tenants"]["a"]
    assert row["rate_tokens_per_s"] is None
    assert row["configured_share"] == 1.0 and not row["over_share"]


def test_over_factor_must_exceed_one():
    with pytest.raises(ValueError, match="over_factor"):
        FairSharePolicy(over_factor=1.0)


# ---------------------------------------------------------------------------
# scheduler gates (manual stepping)
# ---------------------------------------------------------------------------
def _fs_config(queue_capacity=64, **overload_kw):
    overload_kw.setdefault("fair_share_enabled", True)
    overload_kw.setdefault("fair_share_alpha", 1.0)
    return ServingConfig(queue_capacity=queue_capacity,
                         overload=OverloadConfig(**overload_kw))


def _make_over_share(sched, hog="hog", meek="meek"):
    """Synthetically establish hog as over-share: feed the policy's EWMAs
    directly (the deterministic stand-in for hog's executed batches)."""
    fs = sched._fair_share
    fs.note(meek)
    fs.observe(hog, 1, now=0.0)
    fs.observe(hog, 10_000, now=1.0)
    assert fs.over_share(hog)


def test_admission_429_for_over_share_tenant_under_pressure(make_engine):
    engine = make_engine()
    sched = ServingScheduler(engine, _fs_config(), start=False)
    try:
        _make_over_share(sched)
        # stage 0: no pressure, the gate is inert even for an over-share tenant
        r0 = sched.submit(_prompt(), max_new_tokens=2, tenant="hog")
        _run_until(sched, lambda: r0.state is RequestState.DONE)
        _force_stage(sched, minimum=1)
        with pytest.raises(AdmissionRejected) as exc:
            sched.submit(_prompt(), max_new_tokens=2, tenant="hog")
        assert exc.value.retry_after_s is not None
        assert exc.value.retry_after_s >= \
            sched._config.overload.retry_after_floor_s
        assert sched.stats()["counters"]["fair_share_shed"] == 1
        # the well-behaved tenant is admitted and completes under the same
        # pressure — that is the entire point of the policy
        good = sched.submit(_prompt(7), max_new_tokens=2, tenant="meek")
        _run_until(sched, lambda: good.state is RequestState.DONE)
        # the shed shows in the usage doc's fair-share posture
        fair = sched.usage()["fair_share"]
        assert fair["sheds"] == 1
        assert fair["tenants"]["hog"]["over_share"]
    finally:
        sched.stop(drain=False)


def test_unlabeled_requests_bill_to_the_default_tenant(make_engine):
    engine = make_engine()
    sched = ServingScheduler(engine, _fs_config(), start=False)
    try:
        req = sched.submit(_prompt(), max_new_tokens=2)
        assert req.tenant == sched._config.cost.default_tenant == "default"
        _run_until(sched, lambda: req.state is RequestState.DONE)
        assert "default" in sched.usage()["fair_share"]["tenants"]
    finally:
        sched.stop(drain=False)


def test_fair_share_disabled_is_the_control_arm(make_engine):
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    try:
        assert sched._fair_share is None  # default off
        _force_stage(sched, minimum=1)
        # no fair-share gate: any tenant is admitted under pressure
        req = sched.submit(_prompt(), max_new_tokens=2, tenant="hog")
        assert req.shed_reason is None
        _run_until(sched, lambda: req.state is RequestState.DONE)
        assert "fair_share" not in sched.usage()
    finally:
        sched.stop(drain=False)


def test_queue_shed_takes_over_share_tenants_first(make_engine):
    engine = make_engine(max_tracked_sequences=1)
    # admission control off so requests QUEUE; the stage->shed path (not the
    # submit() gate) must be what rejects them — stage is still 0 at submit
    cfg = _fs_config(admission_control=False)
    sched = ServingScheduler(engine, cfg, start=False)
    try:
        hog1 = sched.submit(_prompt(), max_new_tokens=4, tenant="hog")
        hog2 = sched.submit(_prompt(5), max_new_tokens=4, tenant="hog")
        meek = sched.submit(_prompt(7), max_new_tokens=4, tenant="meek")
        _make_over_share(sched)
        _force_stage(sched, minimum=1)
        sched._shed_queued(now=time.monotonic())
        for r in (hog1, hog2):
            assert r.state is RequestState.FAILED
            assert "fair-share" in r.shed_reason
            assert r.retry_after_s is not None and r.retry_after_s > 0
            assert r.tokens == [] and r._fed == 0  # zero engine work consumed
        assert meek.shed_reason is None
        assert sched.stats()["counters"]["fair_share_shed"] == 2
        assert sched._fair_share.sheds == 2
        _run_until(sched, lambda: meek.state is RequestState.DONE)
    finally:
        sched.stop(drain=False)


def test_fair_share_shed_is_work_conserving(make_engine):
    """Shedding only happens when an under-share tenant is waiting behind the
    over-share work: with the queue holding ONLY the flagged tenant's
    requests, dropping them frees capacity for nobody — nothing is shed, and
    the work completes once pressure-independent admission reaches it."""
    engine = make_engine(max_tracked_sequences=1)
    sched = ServingScheduler(engine, _fs_config(admission_control=False),
                             start=False)
    try:
        hog1 = sched.submit(_prompt(), max_new_tokens=2, tenant="hog")
        hog2 = sched.submit(_prompt(5), max_new_tokens=2, tenant="hog")
        _make_over_share(sched)
        _force_stage(sched, minimum=1)
        sched._shed_queued(now=time.monotonic())
        assert hog1.shed_reason is None and hog2.shed_reason is None
        assert sched.stats()["counters"]["fair_share_shed"] == 0
        _run_until(sched, lambda: hog1.state is RequestState.DONE
                   and hog2.state is RequestState.DONE)
    finally:
        sched.stop(drain=False)


# ---------------------------------------------------------------------------
# the flood gate: real engine work, rate-calibrated deadlines
# ---------------------------------------------------------------------------
N_GOOD = 3
GOOD_TOKENS = 6
FLOOD_TOKENS = 32          # per flood request; the COUNT adapts to the rate
PROMPT_TOKENS = 9


def _flood_config(fair_share_on):
    # FIFO admission models the realistic arrival order (same priority
    # class); admission control off so the flood actually queues — the
    # policy under test is fair-share, not deadline feasibility
    # room for the largest flood the gate sizes (400) and the good requests
    # behind it: a faster scheduler measures a higher rate and sizes more
    return _fs_config(queue_capacity=512,
                      fair_share_enabled=fair_share_on,
                      priority_ordering=False,
                      admission_control=False)


def _warm_engine(sched):
    """Pay every XLA compile (prefill bucket + decode batch 1 and 2) before
    any clock starts: compile time must bias neither the measured baseline
    nor a deadline."""
    warm = [sched.submit(_prompt(), max_new_tokens=2, tenant="warmup")
            for _ in range(2)]
    _run_until(sched, lambda: all(r.state is RequestState.DONE for r in warm))


def _measure_baseline(make_engine):
    """The well-behaved tenant alone on a warmed engine: the good workload's
    wall time AND the sustained flood-shaped token rate — everything else in
    the gate is calibrated off these. Two measurements because they differ by
    an order of magnitude: the good run is tiny (scheduler-overhead-bound),
    while the flood drains at the engine's sustained batch-decode rate."""
    engine = make_engine(max_tracked_sequences=2)
    sched = ServingScheduler(engine, _flood_config(True), start=False)
    try:
        _warm_engine(sched)
        # two identical passes, timing only the second: the first flushes any
        # batch-shape compile _warm_engine missed (e.g. the lone-sequence
        # decode tail), which would otherwise inflate the measured wall ~10x
        # and mis-size every deadline derived from it
        for _ in range(2):
            t0 = time.monotonic()
            good = [sched.submit(_prompt(), max_new_tokens=GOOD_TOKENS,
                                 tenant="good") for _ in range(N_GOOD)]
            _run_until(sched, lambda: all(r.finished for r in good))
            wall_good = time.monotonic() - t0
        assert all(r.state is RequestState.DONE for r in good)
        # sustained rate over >= 4 flood-sized requests (a long enough window
        # that per-dispatch jitter and burst effects average out)
        t0 = time.monotonic()
        cal = [sched.submit(_prompt(), max_new_tokens=FLOOD_TOKENS,
                            tenant="good") for _ in range(4)]
        _run_until(sched, lambda: all(r.finished for r in cal),
                   max_steps=4000)
        rate = 4 * (PROMPT_TOKENS + FLOOD_TOKENS) / (time.monotonic() - t0)
        return max(wall_good, 1e-3), rate
    finally:
        sched.stop(drain=False)


def _run_flood_arm(make_engine, fair_share_on, deadline_s, flood_n):
    """Deadline goodput is judged by the TEST's clock, not in-scheduler
    deadlines: the good requests carry none, so neither the deadline-
    feasibility walk nor the timeout path can touch them — what separates
    the arms is fair-share alone."""
    engine = make_engine(max_tracked_sequences=2)
    sched = ServingScheduler(engine, _flood_config(fair_share_on), start=False)
    try:
        _warm_engine(sched)
        _force_stage(sched, minimum=1)  # sustained pressure for the whole arm
        flood = []
        for _ in range(flood_n):
            try:
                flood.append(sched.submit(_prompt(), tenant="flood",
                                          max_new_tokens=FLOOD_TOKENS))
            except AdmissionRejected as exc:
                # a 429 at submit is a valid fair-share outcome — but never
                # without the backoff contract
                assert exc.retry_after_s is not None and exc.retry_after_s > 0
        good = [sched.submit(_prompt(), max_new_tokens=GOOD_TOKENS,
                             tenant="good") for _ in range(N_GOOD)]
        cutoff = time.monotonic() + deadline_s
        while time.monotonic() < cutoff \
                and not all(r.finished for r in good):
            sched.step()
        goodput = sum(1 for r in good if r.state is RequestState.DONE)
        # the Retry-After contract holds on EVERY fair-share shed
        for r in flood:
            if r.shed_reason is not None:
                assert "fair-share" in r.shed_reason
                assert r.retry_after_s is not None and r.retry_after_s > 0
        sheds = sum(1 for r in flood if r.shed_reason is not None)
        return goodput, sheds
    finally:
        sched.stop(drain=False)


def test_flood_cannot_starve_well_behaved_tenant(make_engine):
    """The acceptance gate: tenant ``flood`` dumps ~2.5 deadlines' worth of
    work ahead of tenant ``good``'s interactive requests. With fair-share on,
    good keeps >= 90% of its no-flood deadline goodput (the flood is shed);
    the off control collapses to zero — the difference IS the policy."""
    wall_good, rate = _measure_baseline(make_engine)
    flood_work = PROMPT_TOKENS + FLOOD_TOKENS
    # the deadline covers (with ~8x slack) the un-sheddable in-flight flood
    # (2 tracked sequences) plus the good workload itself — generous because
    # the fair arm also pays a per-tick shed walk over the whole queued
    # flood, and suite-load CPU noise halves the calibrated rate; the flood
    # COUNT then scales to ~2.5 deadlines of drain time so the FIFO control
    # arm cannot finish it before the cutoff however fast the machine is
    deadline_s = max(2.5, 4.0 * wall_good,
                     8.0 * (2 * flood_work + N_GOOD * 15) / rate)
    flood_n = int(min(400, max(24, 2.5 * deadline_s * rate / flood_work)))

    goodput_fair, sheds = _run_flood_arm(
        make_engine, True, deadline_s, flood_n)
    goodput_ctrl, _ = _run_flood_arm(
        make_engine, False, deadline_s, flood_n)

    baseline_goodput = N_GOOD  # the baseline run completed every request
    assert goodput_fair >= 0.9 * baseline_goodput, (
        f"fair-share arm: {goodput_fair}/{baseline_goodput} good-tenant "
        f"requests made the {deadline_s:.2f}s deadline under a "
        f"{flood_n}-request flood")
    assert sheds > 0, "the flood was never shed — the gate proved nothing"
    assert goodput_ctrl < 0.5 * baseline_goodput, (
        f"control arm (fair-share off) did not collapse "
        f"({goodput_ctrl}/{baseline_goodput}): the flood sizing is too small "
        f"to starve anyone, so the fair-share arm passes vacuously")
