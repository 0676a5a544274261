"""The runtime watch: garbage collections and host stalls as spans, present
only under a telemetry session. Pure Python: no engine is built."""

import gc
import os
import re
import sys
import threading
import time

import pytest

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import runtime_watch
from deepspeed_tpu.telemetry.registry import MetricsRegistry
from deepspeed_tpu.telemetry.spans import SpanRecorder


def _ours():
    return [cb for cb in gc.callbacks
            if getattr(cb, "__self__", None).__class__ is runtime_watch.RuntimeWatch]


def _threads():
    return [t for t in threading.enumerate() if t.name == runtime_watch.THREAD_NAME]


def _rows(name, recorder=None):
    recorder = recorder or telemetry.get_span_recorder()
    return [s for s in recorder.export_since(0)["spans"]
            if s["cat"] == "runtime" and s["name"] == name]


def _full_collections(session):
    """The ring's generation-2 ``gc`` spans, once there is one: the pause is
    written by whoever takes it off the callback's deque, this thread or the
    watch's, which may be a moment behind (or waiting for a lock the test holds)."""
    deadline = time.monotonic() + 10
    while True:
        session.runtime_watch.flush()
        full = [s for s in _rows("gc") if s["args"]["generation"] == 2]
        if full or time.monotonic() > deadline:
            return full
        time.sleep(0.01)


def _session():
    return telemetry.configure(telemetry.TelemetryConfig(enabled=True))


class _FakeTime:
    """A clock that moves only while the watch waits; ``late_us[i]`` is added to
    the i-th wait, and the loop is told to stop after ``waits`` of them."""

    def __init__(self, waits, late_us=None, during_wait=None):
        self.now, self.calls, self.waits = 1_000_000, 0, waits
        self.late_us, self.during_wait = late_us or {}, during_wait or {}

    def clock(self):
        return self.now

    def wait(self, seconds):
        if self.calls >= self.waits:
            return True
        hook = self.during_wait.get(self.calls)
        if hook is not None:
            hook(self)
        self.now += int(seconds * 1e6) + self.late_us.get(self.calls, 0)
        self.calls += 1
        return False


def _fake_watch(fake):
    spans = SpanRecorder()
    return runtime_watch.RuntimeWatch(MetricsRegistry(), spans, clock_us=fake.clock,
                                      wait=fake.wait), spans


def _named(spans, name):
    return [s for s in spans.export_since(0)["spans"] if s["name"] == name]


def test_nothing_is_installed_without_a_session_and_nothing_is_left_after_one():
    assert not _ours() and not _threads()
    session = _session()
    assert len(_ours()) == 1 and len(_threads()) == 1
    assert _ours()[0].__self__ is session.runtime_watch
    session.close()
    assert not _ours() and not _threads()


def test_a_second_configure_leaves_one_callback_and_one_thread():
    first = _session()
    second = _session()
    assert len(_ours()) == 1 and len(_threads()) == 1
    assert _ours()[0].__self__ is second.runtime_watch is not first.runtime_watch
    first.close()  # displaced already: must not take the live watch down
    assert len(_ours()) == 1 and len(_threads()) == 1
    telemetry.shutdown()
    assert not _ours() and not _threads()


def test_no_call_site_asks_for_the_watch():
    """The off path is unchanged: outside ``telemetry/`` no module of the program
    names ``runtime_watch``, so no hot path gained a check."""
    root = os.path.dirname(deepspeed_tpu.__file__)
    naming = []
    for folder, _, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            if name.endswith(".py") and os.path.dirname(path) != os.path.join(root, "telemetry"):
                with open(path) as f:
                    if re.search(r"runtime_watch", f.read()):
                        naming.append(os.path.relpath(path, root))
    assert naming == []


def test_a_full_collection_is_one_gc_span_with_its_generation():
    session = _session()
    gc.collect()
    full = _full_collections(session)
    assert len(full) == 1
    assert set(full[0]["args"]) == {"generation", "collected", "uncollectable"}
    assert full[0]["dur_us"] > 0
    text = telemetry.get_registry().render_prometheus()
    assert re.search(r'runtime_gc_pause_seconds_count\{generation="2"\} 1', text)


@pytest.mark.parametrize("generation, pause_us, written", [
    (0, 200, False), (1, 999, False), (1, 1000, True), (0, 4000, True), (2, 50, True)])
def test_a_young_pass_under_a_millisecond_writes_nothing(generation, pause_us, written):
    fake = _FakeTime(0)
    watch, spans = _fake_watch(fake)
    info = {"generation": generation, "collected": 3, "uncollectable": 0}
    watch.on_gc("start", info)
    fake.now += pause_us
    watch.on_gc("stop", info)
    watch.flush()
    rows = _named(spans, "gc")
    assert len(rows) == int(written)
    if written:
        assert rows[0]["dur_us"] == pause_us and rows[0]["ts_us"] == 1_000_000
        assert rows[0]["args"] == info


def test_the_callback_takes_no_lock_of_the_ring_or_the_registry():
    """A collection can start inside a call that holds the recorder's or the
    registry's lock; the callback must not wait for either."""
    session = _session()
    with session.spans._lock, session.registry._lock:
        done = threading.Event()

        def collect():
            gc.collect()
            done.set()

        worker = threading.Thread(target=collect)
        worker.start()
        assert done.wait(10), "gc.collect() waited for a lock the callback wanted"
        worker.join()
    # the watch's thread may have taken the pause and be waiting for the ring's
    # lock, which this test held: the span is written as soon as it has it
    assert len(_full_collections(session)) == 1


def test_a_held_interpreter_is_one_stall_not_one_a_tick():
    interval = sys.getswitchinterval()
    session = _session()
    time.sleep(0.05)
    before = len(_rows("stall"))
    sys.setswitchinterval(1.0)  # a pure-Python loop now keeps the interpreter
    try:
        began = time.perf_counter()
        while time.perf_counter() - began < 0.05:
            pass
    finally:
        sys.setswitchinterval(interval)
    time.sleep(0.05)
    session.close()
    stalls = _rows("stall", session.spans)[before:]
    held = [s for s in stalls if s["dur_us"] >= 30_000]
    # one a stall, where the loop spans five of the watch's periods (a loaded
    # machine may add a stall of its own in these 150 ms)
    assert 1 <= len(held) <= 3, stalls
    assert held[0]["args"] == {"in_gc": 0}
    # from the instant it should have woken: inside the loop above
    assert held[0]["ts_us"] >= began * 1e6 - runtime_watch.PERIOD_S * 1e6
    text = telemetry.get_registry().render_prometheus()
    assert re.search(r"runtime_host_late_seconds_count [1-9]", text)


def test_alive_once_a_second_with_the_worst_lateness_of_the_second():
    def a_collection(fake):
        info = {"generation": 0, "collected": 0, "uncollectable": 0}
        watch.on_gc("start", info)
        fake.now += 300
        watch.on_gc("stop", info)

    # 2.5 s of periods; the 10th woke 3 ms late (under the threshold), one
    # short collection in the first second
    fake = _FakeTime(int(2.5 / runtime_watch.PERIOD_S), late_us={10: 3000},
                     during_wait={20: a_collection})
    watch, spans = _fake_watch(fake)
    watch.run()
    alive = _named(spans, "alive")
    assert [s["args"] for s in alive] == [{"max_late_us": 3000}, {"max_late_us": 0}]
    assert alive[0]["ts_us"] == 1_000_000 and alive[0]["dur_us"] >= 1_000_000
    assert alive[1]["ts_us"] == alive[0]["ts_us"] + alive[0]["dur_us"]
    assert not _named(spans, "stall") and not _named(spans, "gc")


def test_a_stall_says_whether_a_collection_lay_inside_it():
    def a_full_collection(fake):
        info = {"generation": 2, "collected": 9, "uncollectable": 0}
        fake.now += period + 1000  # past the instant the watch was due
        watch.on_gc("start", info)
        fake.now += 40_000
        watch.on_gc("stop", info)
        fake.now -= period + 41_000

    period = int(runtime_watch.PERIOD_S * 1e6)
    fake = _FakeTime(60, late_us={5: 46_000, 30: 20_000, 40: 10_000},
                     during_wait={5: a_full_collection})
    watch, spans = _fake_watch(fake)
    watch.run()
    stalls = _named(spans, "stall")
    # late by the threshold itself is no stall
    assert [(s["dur_us"], s["args"]) for s in stalls] == [(46_000, {"in_gc": 1}),
                                                          (20_000, {"in_gc": 0})]
    (pause, ) = _named(spans, "gc")
    first = stalls[0]
    assert first["ts_us"] < pause["ts_us"] < first["ts_us"] + first["dur_us"]
    assert first["ts_us"] == 1_000_000 + 5 * period + period  # when the sixth wake was due


def test_a_stall_sees_the_collection_whose_stop_it_interrupted():
    """The late thread takes the interpreter at the first bytecode of the
    callback's ``stop`` (seen on the chip: a 197 ms stall beside a 198 ms
    collection, ``in_gc`` 0): the collection is not in the deque yet."""
    def a_collection_still_stopping(fake):
        watch.on_gc("start", {"generation": 2, "collected": 0, "uncollectable": 0})

    fake = _FakeTime(4, late_us={1: 50_000}, during_wait={1: a_collection_still_stopping})
    watch, spans = _fake_watch(fake)
    watch.run()
    (stall, ) = _named(spans, "stall")
    assert stall["dur_us"] == 50_000 and stall["args"] == {"in_gc": 1}


def test_the_watch_writes_no_annotation_of_its_own(tmp_path):
    """A profiler names a thread's line for the process, so a reader that keys
    lines by name (``benchmark/host_phases.load_host``) reads every Python
    thread's ``dstpu.*`` events as the scheduler thread's: a collection inside
    ``inference.put`` must stay idle time of ``inference.put`` there. The ring's
    ``runtime.*`` rows are all there is."""
    import glob

    import jax
    from jax.profiler import ProfileData
    session = _session()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.live_span(session.spans, "put", "inference"):
            gc.collect()
            time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    assert len(_full_collections(session)) == 1
    session.close()
    (path, ) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    ours = {e.name for plane in ProfileData.from_file(path).planes for line in plane.lines
            for e in line.events if e.name.startswith("dstpu.")}
    assert ours == {"dstpu.inference.put"}
