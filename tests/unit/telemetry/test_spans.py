"""SpanRecorder: ring bound, Chrome-trace export, timer wrapping."""

import json
import time

import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import SpanRecorder, TelemetryConfig, TracingTimers
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer


def test_ring_buffer_bound_and_drop_count():
    rec = SpanRecorder(max_spans=4)
    for i in range(10):
        rec.record(f"s{i}", ts_us=i, dur_us=1)
    assert len(rec) == 4
    assert rec.dropped == 6
    names = [e["name"] for e in rec.chrome_trace()["traceEvents"]]
    assert names == ["s6", "s7", "s8", "s9"]


def test_ring_overflow_increments_spans_dropped_total(tmp_path):
    """ISSUE satellite: ring overflow is VISIBLE — the session's recorder
    feeds ``spans_dropped_total``, the ``/trace`` doc carries the drop count,
    and flight dumps record it too."""
    session = telemetry.configure(TelemetryConfig(
        enabled=True, max_spans=4,
        flight_recorder={"enabled": True, "dir": str(tmp_path),
                         "watchdog_enabled": False}))
    try:
        rec = telemetry.get_span_recorder()
        for i in range(10):
            rec.record(f"s{i}", ts_us=i, dur_us=1)
        counter = telemetry.get_registry().counter("spans_dropped_total")
        assert counter.value == 6
        assert rec.chrome_trace()["spansDropped"] == 6
        path = telemetry.get_flight_recorder().dump("api")
        with open(path) as f:
            assert json.load(f)["spans_dropped"] == 6
        # export_since surfaces the same count for the fleet collector
        assert rec.export_since(0)["dropped"] == 6
    finally:
        session.close()
    # a bare recorder (no session) stays registry-free: no counter, no crash
    bare = SpanRecorder(max_spans=2)
    for i in range(5):
        bare.record(f"b{i}", ts_us=i)
    assert bare.dropped == 3


def test_export_since_filters_by_timestamp():
    rec = SpanRecorder()
    rec.record("old", ts_us=100, dur_us=1)
    rec.record("new", ts_us=5000, dur_us=1)
    doc = rec.export_since(1000)
    assert [s["name"] for s in doc["spans"]] == ["new"]
    assert doc["pid"] > 0 and doc["now_us"] > 0 and doc["dropped"] == 0


def test_span_context_manager_measures():
    rec = SpanRecorder()
    with rec.span("work", cat="test", args={"k": 1}):
        time.sleep(0.01)
    (ev, ) = rec.chrome_trace()["traceEvents"]
    assert ev["name"] == "work" and ev["cat"] == "test"
    assert ev["ph"] == "X" and ev["dur"] >= 9000
    assert ev["args"] == {"k": 1}


def test_chrome_trace_export_is_loadable(tmp_path):
    rec = SpanRecorder()
    # recorded out of order on purpose: export must sort by ts
    rec.record("late", ts_us=500, dur_us=10)
    rec.record("early", ts_us=100, dur_us=10)
    rec.record("mid", ts_us=300, dur_us=10)
    path = rec.export_chrome_trace(str(tmp_path / "trace.json"))

    with open(path) as f:
        trace = json.load(f)  # must be valid JSON
    evs = trace["traceEvents"]
    assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)
    assert all(e["ph"] == "X" for e in evs)  # complete events: no B/E pairing to break
    assert all(isinstance(e["dur"], int) and e["dur"] >= 0 for e in evs)


def test_tracing_timers_wrap_wall_clock_timers():
    rec = SpanRecorder()
    timers = TracingTimers(SynchronizedWallClockTimer(), rec)
    t = timers("fwd")
    t.start()
    time.sleep(0.005)
    t.stop()
    t.start()
    t.stop()
    evs = rec.chrome_trace()["traceEvents"]
    assert [e["name"] for e in evs] == ["fwd", "fwd"]
    assert evs[0]["cat"] == "engine" and evs[0]["dur"] >= 4000
    # the inner timer still accumulates (the engine's log() path keeps working)
    assert timers("fwd").elapsed(reset=False) > 0
    assert "fwd" in timers.get_timers()


# ------------------------------------------- live spans in the profiler's trace --
class _RecordingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps what was
    constructed, entered and exited."""
    log = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs
        _RecordingAnnotation.log.append(("init", name, kwargs))

    def __enter__(self):
        _RecordingAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _RecordingAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax
    _RecordingAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _RecordingAnnotation)
    return _RecordingAnnotation.log


def test_live_span_is_also_a_trace_annotation(annotations):
    rec = SpanRecorder()
    args = {"tick": 7, "kind": "put", "uids": [1, 2]}
    with rec.span("tick", cat="sched", args=args):
        assert annotations[-1] == ("enter", "dstpu.sched.tick")
        args["seqs"] = 2  # filled in by the block: the ring keeps it, the annotation is gone
    assert annotations == [("init", "dstpu.sched.tick", {"tick": 7, "kind": "put"}),
                           ("enter", "dstpu.sched.tick"), ("exit", "dstpu.sched.tick")]
    (span, ) = rec.tail(1)
    assert span["name"] == "tick" and span["cat"] == "sched"
    assert span["args"] == {"tick": 7, "kind": "put", "uids": [1, 2], "seqs": 2}


def test_nested_live_spans_nest_their_annotations(annotations):
    rec = SpanRecorder()
    with rec.span("tick", cat="sched"):
        with rec.span("emit", cat="sched"):
            pass
    assert [a[:2] for a in annotations if a[0] != "init"] == [
        ("enter", "dstpu.sched.tick"), ("enter", "dstpu.sched.emit"),
        ("exit", "dstpu.sched.emit"), ("exit", "dstpu.sched.tick")]
    inner, outer = rec.tail(2)
    assert (inner["name"], outer["name"]) == ("emit", "tick")
    assert outer["ts_us"] <= inner["ts_us"]
    assert inner["ts_us"] + inner["dur_us"] <= outer["ts_us"] + outer["dur_us"]


def test_record_writes_the_ring_only(annotations):
    rec = SpanRecorder()
    rec.record("queued", cat="serving", ts_us=1, dur_us=2)
    assert annotations == [] and len(rec) == 1


def test_live_span_with_telemetry_off_is_the_shared_null_context(annotations):
    from deepspeed_tpu.telemetry import NULL_SPAN, live_span
    assert live_span(None, "tick", "sched", None) is NULL_SPAN
    with live_span(None, "tick", "sched"):
        with live_span(None, "emit", "sched"):  # re-entrant
            pass
    assert annotations == []
    rec = SpanRecorder()
    with live_span(rec, "tick", "sched"):
        pass
    assert len(rec) == 1 and annotations[0][1] == "dstpu.sched.tick"


def test_scheduler_tick_and_engine_put_touch_nothing_with_telemetry_off(annotations, monkeypatch):
    """The off path: one ``None`` check per phase. No recorder call, no
    registry call, no annotation constructed, by a scheduler tick or by an
    engine ``put`` / ``decode_loop``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler

    calls = []
    for method in ("span", "record"):
        monkeypatch.setattr(SpanRecorder, method,
                            lambda self, *a, _m=method, **k: calls.append(_m))
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = {"model": LlamaModel(cfg).init(jax.random.PRNGKey(0),
                                            jnp.zeros((1, 8), jnp.int32))["params"]}
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=32),
                               max_context=128)
    engine = build_engine(params, cfg,
                          RaggedInferenceEngineConfig(state_manager=mgr, kv_block_size=16))
    try:
        np.asarray(engine.put([0], [np.arange(5, dtype=np.int32)]))
        engine.decode_loop([0], [np.array([3], np.int32)], 2)
        engine.flush(0)
        sched = ServingScheduler(engine, ServingConfig(decode_chunk=2), start=False)
        greedy = sched.submit([1, 2, 3], max_new_tokens=4)
        sampled = sched.submit([1, 2, 3], max_new_tokens=3, temperature=0.8, seed=3)
        for _ in range(50):
            sched.step()
            if greedy.finished and sampled.finished:
                break
        assert greedy.finished and sampled.finished
        assert not sched.step()  # an idle poll
        sched.stop(drain=False)
    finally:
        engine.close()
    assert calls == [] and annotations == []
    assert telemetry.get_registry().api_calls == 0
