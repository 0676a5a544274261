"""The load generator: its traffic reproduces from a seed, and its latencies
are counted from when a request was due."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmark import loadloop
from benchmark.traffic_kinds import _draw

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OPEN = {"rate_per_s": 20.0, "temperature": 0.7,
        "prompt": {"dist": "lognormal", "median": 50, "sigma": 0.7, "min": 4, "max": 200},
        "output": {"dist": "lognormal", "median": 20, "sigma": 0.6, "min": 2, "max": 64}}
CLOSED = {"clients": 3, "requests_per_client": 5, "temperature": 0.0,
          "prompt": {"dist": "uniform", "min": 8, "max": 16},
          "output": {"dist": "uniform", "min": 2, "max": 6}}
PACKED = {"document": {"dist": "lognormal", "median": 30, "sigma": 1.0, "min": 2, "max": 500},
          "eos_token_id": 2}


def _kind(name):
    return importlib.import_module(f"benchmark.traffic_kinds.{name}")


def _dump(requests):
    return json.dumps([(r.index, r.due_s, r.prompt.tolist(), r.max_new_tokens, r.temperature,
                        r.seed, r.client) for r in requests]).encode()


def _make(kind, seed):
    if kind == "open_poisson":
        return _dump(_kind(kind).Traffic(OPEN, seed, 5.0, 1.0, 1000).initial())
    if kind == "closed_clients":
        t = _kind(kind).Traffic(CLOSED, seed, 5.0, 1.0, 1000)
        sent = t.initial()
        # replies end in an order that is NOT the order of sending
        for r in list(reversed(sent)) + sent:
            nxt = t.on_finish(r, 1.0)
            if nxt is not None:
                sent.append(nxt)
        return _dump(sorted(sent, key=lambda r: (r.client, r.index)))
    ids, labels = _kind(kind).Traffic(PACKED, seed, 4, 128, 1000).batch(3)
    return ids.tobytes() + labels.tobytes()


@pytest.mark.parametrize("kind", ["open_poisson", "closed_clients", "packed_documents"])
def test_traffic_reproduces_from_a_seed_and_differs_across_seeds(kind):
    assert _make(kind, 7) == _make(kind, 7)
    assert _make(kind, 7) != _make(kind, 8)


def test_every_seed_offers_the_same_work_in_another_order():
    a = _kind("open_poisson").Traffic(OPEN, 1, 20.0, 2.0, 1000).initial()
    b = _kind("open_poisson").Traffic(OPEN, 2, 20.0, 2.0, 1000).initial()
    assert abs(len(a) - len(b)) <= 2  # the last arrivals may fall either side of the end
    n = int(round(OPEN["rate_per_s"] * 22.0))
    for spec in (OPEN["prompt"], OPEN["output"]):
        x = np.sort(_draw.lengths(spec, n, np.random.default_rng(1)))
        y = np.sort(_draw.lengths(spec, n, np.random.default_rng(2)))
        assert (x == y).all()
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert abs(np.median(x) - spec["median"]) <= 0.05 * spec["median"]
    assert [r.due_s for r in a] != [r.due_s for r in b]
    gaps = _draw.exponential_gaps(20.0, 4000, np.random.default_rng(0))
    assert abs(gaps.mean() - 1 / 20.0) < 0.01 / 20.0


def test_packed_documents_are_full_sequences_with_shifted_labels():
    t = _kind("packed_documents").Traffic(PACKED, 0, 4, 128, 1000)
    ids, labels = t.batch(0)
    assert ids.shape == labels.shape == (4, 128) and ids.dtype == np.int32
    flat = np.concatenate([ids.reshape(-1), labels.reshape(-1)[-1:]])
    assert (flat[1:] == labels.reshape(-1)).all()
    assert (ids == 2).sum() >= 3  # documents end inside the sequences
    assert ((ids >= 2) & (ids < 1000)).all()


class FakeClock:
    """A clock that only ``sleep`` and the system under test move."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-4)


class FakeSystem:
    """Answers every request ``service_s`` after it was SENT with all its tokens
    at once; ``submit`` of request ``stall_index`` blocks for ``stall_s``."""

    def __init__(self, clock, service_s=0.010, stall_index=None, stall_s=0.0, refuse=()):
        self.clock, self.service_s = clock, service_s
        self.stall_index, self.stall_s, self.refuse = stall_index, stall_s, set(refuse)

    def submit(self, req):
        if req.index in self.refuse:
            raise RuntimeError("queue full")
        if req.index == self.stall_index:
            self.clock.t += self.stall_s
        return {"ready": self.clock() + self.service_s, "n": req.max_new_tokens, "given": False}

    def poll(self, handle):
        if handle["given"] or self.clock() < handle["ready"]:
            return 0, handle["given"]
        handle["given"] = True
        return handle["n"], True

    def outcome(self, handle):
        return True, "DONE"

    def sample(self):
        return {"kv_blocks_used": 1}


class Schedule:

    def __init__(self, dues, n_tokens=4):
        self.requests = [loadloop.Request(index=i, due_s=d, prompt=np.zeros(8, np.int32),
                                          max_new_tokens=n_tokens) for i, d in enumerate(dues)]

    def initial(self):
        return list(self.requests)

    def on_finish(self, request, now_s):
        return None


def _run(system, clock, dues, **kw):
    return loadloop.run(system, Schedule(dues), seconds=1.0, lead_in_s=0.2, drain_s=1.0,
                        clock=clock, sleep=clock.sleep, **kw)


def test_latency_is_counted_from_due_when_the_generator_is_stalled():
    clock = FakeClock()
    dues = [0.1 * i for i in range(10)]
    system = FakeSystem(clock, stall_index=2, stall_s=0.35)
    requests, samples, t0 = _run(system, clock, dues)
    judged = loadloop.measured(requests, 1.0)
    assert len(judged) == 10 and not any(loadloop.failed(r) for r in judged)
    by_index = {r.index: r for r in judged}
    # requests 3, 4 and 5 were due during the stall: they were SENT late, and a
    # clock started at the send would hide it. From due, they carry the stall.
    for i, waited in ((3, 0.25), (4, 0.15), (5, 0.05)):
        r = by_index[i]
        assert r.sent_s - r.due_s == pytest.approx(waited, abs=0.01)
        assert (r.first_s - r.sent_s) == pytest.approx(0.010, abs=0.005)
        assert (r.first_s - r.due_s) == pytest.approx(waited + 0.010, abs=0.01)
    ttft = loadloop.ttft_values_ms(judged)
    assert max(ttft) == pytest.approx(360, abs=15)  # request 2 itself: 350 ms in submit
    assert loadloop.percentile(loadloop.late_values_ms(requests), 99) > 200
    assert samples and all(0 <= t < 1.0 for t, _ in samples)


def test_a_refused_or_silent_request_counts_as_the_largest_latency():
    clock = FakeClock()
    requests, _, _ = _run(FakeSystem(clock, refuse={4}), clock, [0.1 * i for i in range(10)])
    judged = loadloop.measured(requests, 1.0)
    assert sum(loadloop.failed(r) for r in judged) == 1
    ttft = loadloop.ttft_values_ms(judged)
    assert len(ttft) == 10 and sorted(ttft)[-1] == sorted(ttft)[-2]
    assert loadloop.slo_met_pct(judged, 1e9, 1e9) == pytest.approx(90.0)


def test_lead_in_requests_are_not_judged_but_their_tokens_count_where_they_are_served():
    clock = FakeClock()
    system = FakeSystem(clock, service_s=0.15)
    requests, _, _ = _run(system, clock, [-0.1, 0.5, 0.95])
    assert [r.index for r in loadloop.measured(requests, 1.0)] == [1, 2]
    # request 0 was sent in the lead-in and its tokens arrived at +0.05: inside the
    # window, prompt and all. Request 2's arrived at 1.10: outside it.
    assert loadloop.served_tokens_per_s(requests, 1.0) == pytest.approx(2 * (8 + 4))
    assert [r.window_tokens for r in requests] == [12, 12, 0]
    assert loadloop.tpot_values_ms(requests) == [0.0, 0.0, 0.0]  # all tokens in one burst
