"""Flight recorder: dump contents, SIGUSR1/API/HTTP triggers, and the
heartbeat watchdog (stall detection + serving_stalled metric)."""

import json
import os
import signal
import time
import urllib.error
import urllib.request

import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry.flight_recorder import SERVING_SCHEDULER_CHANNEL


def _session(tmp_path, **fr_kw):
    fr = {"enabled": True, "dir": str(tmp_path / "flight"),
          "watchdog_enabled": False, "signal_enabled": False}
    fr.update(fr_kw)
    return telemetry.configure(telemetry.TelemetryConfig(
        enabled=True, flight_recorder=fr))


def test_dump_contains_spans_events_metrics_and_state(tmp_path):
    session = _session(tmp_path)
    reg = telemetry.get_registry()
    reg.counter("serving_completions_total", "done").inc(3)
    reg.event("train_step", step=7, loss=0.5)
    session.spans.record("put", cat="inference", ts_us=1, dur_us=2,
                         trace_id="abc123", span_id=9)
    recorder = telemetry.get_flight_recorder()
    recorder.register_provider("custom", lambda: {"answer": 42})
    recorder.register_provider("broken", lambda: 1 / 0)

    path = recorder.dump("api")
    with open(path) as f:
        doc = json.load(f)  # must be parseable JSON
    assert doc["meta"]["trigger"] == "api" and doc["meta"]["pid"] == os.getpid()
    span = next(s for s in doc["spans"] if s["name"] == "put")
    assert span["trace_id"] == "abc123" and span["span_id"] == 9
    assert any(e["event"] == "train_step" and e["step"] == 7 for e in doc["events"])
    assert doc["metrics"]["serving_completions_total"][0][1] == 3
    assert doc["state"]["custom"] == {"answer": 42}
    assert "provider raised" in doc["state"]["broken"]["error"]
    # the dump itself is metered
    assert reg.snapshot()["flight_recorder_dumps_total"] == [({"trigger": "api"}, 1.0)]


def test_sigusr1_triggers_a_dump_and_close_restores_handler(tmp_path):
    prev = signal.getsignal(signal.SIGUSR1)
    session = _session(tmp_path, signal_enabled=True)
    os.kill(os.getpid(), signal.SIGUSR1)
    # the handler hands the dump to a worker thread (inline dumping could
    # deadlock on the recorder lock) — poll briefly, for the file: the worker
    # makes the directory first and moves the finished dump into it last
    deadline = time.monotonic() + 5.0
    flight, dumps = tmp_path / "flight", []
    while time.monotonic() < deadline and not dumps:
        time.sleep(0.01)
        dumps = [n for n in os.listdir(flight) if n.endswith(".json")] if flight.exists() else []
    assert len(dumps) == 1 and "sigusr1" in dumps[0]
    session.close()
    assert signal.getsignal(signal.SIGUSR1) == prev


def test_displaced_recorder_close_keeps_newer_handler(tmp_path):
    """Closing an older recorder must not stomp a newer recorder's live
    SIGUSR1 handler with its own (possibly SIG_DFL) predecessor — that would
    turn the runbook's `kill -USR1` dump into process termination."""
    from deepspeed_tpu.telemetry.config import FlightRecorderConfig
    from deepspeed_tpu.telemetry.flight_recorder import FlightRecorder
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    prev = signal.getsignal(signal.SIGUSR1)
    a = FlightRecorder(FlightRecorderConfig(
        enabled=True, dir=str(tmp_path / "a"), signal_enabled=True,
        watchdog_enabled=False), MetricsRegistry()).install()
    b = FlightRecorder(FlightRecorderConfig(
        enabled=True, dir=str(tmp_path / "b"), signal_enabled=True,
        watchdog_enabled=False), MetricsRegistry()).install()
    try:
        a.close()  # out of order: B's handler is live and must stay
        assert signal.getsignal(signal.SIGUSR1) == b._on_signal
    finally:
        b.close()
        signal.signal(signal.SIGUSR1, prev)


def test_http_flight_route_dumps(tmp_path):
    session = telemetry.configure(telemetry.TelemetryConfig(
        enabled=True, http={"enabled": True},
        flight_recorder={"enabled": True, "dir": str(tmp_path / "flight"),
                         "watchdog_enabled": False, "signal_enabled": False}))
    with urllib.request.urlopen(session.server.url + "/flight", timeout=5) as resp:
        doc = json.loads(resp.read())
    assert os.path.exists(doc["path"])
    assert doc["dump"]["meta"]["trigger"] == "http"


def test_flight_route_404_without_recorder():
    session = telemetry.configure(telemetry.TelemetryConfig(
        enabled=True, http={"enabled": True}))
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(session.server.url + "/flight", timeout=5)
    assert err.value.code == 404


def test_watchdog_detects_a_stalled_heartbeat(tmp_path):
    session = _session(tmp_path, watchdog_enabled=True,
                       watchdog_stall_s=0.1, watchdog_poll_s=0.02)
    recorder = telemetry.get_flight_recorder()
    recorder.register_provider(SERVING_SCHEDULER_CHANNEL,
                               lambda: {"queue_depth": 5})
    recorder.watch_heartbeat(SERVING_SCHEDULER_CHANNEL)
    # beat for a while: no dump while the loop makes progress
    for _ in range(5):
        recorder.heartbeat(SERVING_SCHEDULER_CHANNEL)
        time.sleep(0.02)
    assert not os.path.exists(tmp_path / "flight")
    # ...then stop beating: exactly ONE dump per stall episode + the metric
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not os.path.exists(tmp_path / "flight"):
        time.sleep(0.02)
    time.sleep(0.1)  # would double-dump here if episodes weren't latched
    dumps = os.listdir(tmp_path / "flight")
    assert len(dumps) == 1 and "watchdog" in dumps[0]
    with open(tmp_path / "flight" / dumps[0]) as f:
        doc = json.load(f)
    assert doc["state"][SERVING_SCHEDULER_CHANNEL] == {"queue_depth": 5}
    assert doc["heartbeats_age_s"][SERVING_SCHEDULER_CHANNEL] > 0.1
    snap = telemetry.get_registry().snapshot()
    assert snap["serving_stalled_total"] == [({}, 1.0)]
    # a resumed heartbeat re-arms the episode
    recorder.heartbeat(SERVING_SCHEDULER_CHANNEL)
    time.sleep(0.1)
    recorder.unwatch_heartbeat(SERVING_SCHEDULER_CHANNEL)
    session.close()


def test_watchdog_grants_compile_grace_to_busy_loops(tmp_path):
    """A loop blocked inside a watched jit call (a long first-bucket XLA
    compile) is busy, not wedged: no stall until the hard budget expires."""
    import threading

    from deepspeed_tpu.telemetry import compile_watch

    session = _session(tmp_path, watchdog_enabled=True,
                       watchdog_stall_s=0.05, watchdog_poll_s=0.01,
                       watchdog_hard_stall_s=0.6)
    recorder = telemetry.get_flight_recorder()
    recorder.watch_heartbeat("c")

    watch = compile_watch.get()
    release = time.monotonic() + 0.3

    def slow(x):  # holds the wrapped call open well past the soft stall
        while time.monotonic() < release:
            time.sleep(0.01)
        return x

    wrapped = watch.wrap("test", "slow", slow)
    thread = threading.Thread(target=wrapped, args=(1, ))
    thread.start()
    time.sleep(0.2)  # soft stall long exceeded, but the call is in flight
    assert not os.path.exists(tmp_path / "flight")
    thread.join()
    # call over, heartbeat still stale: the stall now fires
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not os.path.exists(tmp_path / "flight"):
        time.sleep(0.02)
    assert os.path.exists(tmp_path / "flight")
    session.close()


def test_unwatched_channel_never_fires(tmp_path):
    session = _session(tmp_path, watchdog_enabled=True,
                       watchdog_stall_s=0.05, watchdog_poll_s=0.01)
    recorder = telemetry.get_flight_recorder()
    recorder.watch_heartbeat("c")
    recorder.unwatch_heartbeat("c")
    time.sleep(0.15)
    assert not os.path.exists(tmp_path / "flight")
    session.close()
