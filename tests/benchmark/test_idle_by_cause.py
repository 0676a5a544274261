"""The device's idle share by cause (``readers/trace_idle_by_cause.py``) and what
the runtime watch saw over the window (``readers/span_runtime_watch.py``), on a
synthetic trace and ring: hand-made intervals, the form ``host_phases.load_json``
gives, and the rows the program's ring holds."""

import importlib
import json
import os
import types

import pytest

from benchmark import harness
from benchmark import host_phases as hp
from benchmark import trace_reduce as tr
from benchmark.readers import trace_idle_by_cause as by_cause

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1_000_000  # ns
RING_TO_TRACE_NS = 5_000 * MS  # the ring's clock reads 5 s less than the trace's
NEW = {"idle_in_gc_pct": ("trace_idle_by_cause", "device_trace", "%"),
       "idle_in_host_stall_pct": ("trace_idle_by_cause", "device_trace", "%"),
       "idle_waiting_pct": ("trace_idle_by_cause", "device_trace", "%"),
       "idle_unnamed_pct": ("trace_idle_by_cause", "device_trace", "%"),
       "gc_pause_ms_per_s": ("span_runtime_watch", "program_span", "ms/s"),
       "host_late_max_ms": ("span_runtime_watch", "program_span", "ms")}
RUNTIME_LAYER = "host runtime (telemetry/runtime_watch.py)"
SCHEDULER_LAYER = "scheduler (serving/scheduler.py)"


def _ev(start_ms, end_ms, phase, **stats):
    return hp.HostEvent(int(start_ms * MS), int(end_ms * MS), phase, stats)


def _row(name, start_ms, end_ms, cat="runtime", **args):
    """A ring row whose times are the trace's ``start_ms`` .. ``end_ms``."""
    ts_us = (int(start_ms * MS) - RING_TO_TRACE_NS) // 1000
    return {"name": name, "cat": cat, "ts_us": ts_us,
            "dur_us": int((end_ms - start_ms) * 1000), "args": args}


# a tick of a put (0..20 ms), 2 ms under nothing, no_work (22..30), a tick of a
# chunk that first waits for its commit time (30..70)
EVENTS = sorted([
    _ev(0, 20, "sched.tick", tick=1),
    _ev(0.5, 1.5, "sched.admit"), _ev(1.5, 3, "sched.build_batch"),
    _ev(3.5, 4.5, "inference.prepare"), _ev(4.5, 5.5, "inference.put"),
    _ev(5.5, 14, "sched.fetch"), _ev(14, 19.5, "sched.emit"),
    _ev(22, 30, "sched.no_work"),
    _ev(30, 70, "sched.tick", tick=2),
    _ev(30, 30.5, "sched.commit_wait"), _ev(30.5, 31, "sched.admit"),
    _ev(31, 32, "sched.build_batch"), _ev(32, 33, "inference.prepare"),
    _ev(33, 64, "inference.decode_loop", steps=4),
    _ev(64, 64, "sched.fetch"), _ev(64, 69, "sched.emit"),
], key=lambda e: (e.start, -e.end))
# the device: a sliver at 0 (the slice's first event), busy 6..14 and 34..64
OPS = [(0, int(0.2 * MS), "%copy.0 = bf16[8] copy()"),
       (6 * MS, 14 * MS, "%fusion.1 = bf16[8] fusion()"),
       (34 * MS, 64 * MS, "%fusion.2 = bf16[8] fusion()")]
TRACE = tr.Trace({0: OPS}, [])
WINDOW_MS, IDLE_MS = 64.0, 25.8
TICKS = [_row("tick", 0, 20, cat="sched", tick=1), _row("tick", 30, 70, cat="sched", tick=2)]
ALIVE = [_row("alive", -90, 400, max_late_us=140), _row("alive", 400, 650, max_late_us=2100)]
# a collection over inference.put (4.8..5.2), a stall over emit (15..17.5) whose
# end a second collection covers (16..18), a stall over no_work (24..26)
STALLS = [_row("gc", 4.8, 5.2, generation=1, collected=10, uncollectable=0),
          _row("stall", 15, 17.5, in_gc=1),
          _row("gc", 16, 18, generation=2, collected=90, uncollectable=0),
          _row("stall", 24, 26, in_gc=0)]
EXPECTED_MS = {"gc": 2.4, "host_stall": 3.0, "waiting": 6.5, "working": 10.6, "unnamed": 3.3}


def _run(rows, **more):
    # the window: one second from 100 ms before the slice, on the ring's clock
    return dict({"spans": rows, "t0": (-100 * MS - RING_TO_TRACE_NS) / 1e9, "seconds": 1.0}, **more)


def _env(trace=TRACE, events=EVENTS):
    log = []
    return {"trace": trace, "host_phases": (events, {}), "log": log.append, "logged": log,
            "trace_summary": tr.summarize(trace) if trace is not None and trace.devices[0] else None}


def _read(metric, run, env):
    with open(os.path.join(ROOT, "benchmark", "metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == NEW[metric][0]
    module = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return module.read(run, spec["params"], env)


def _merged(rows, name):
    return by_cause.on_trace_clock([r for r in rows if r["name"] == name], RING_TO_TRACE_NS)


def test_the_five_causes_add_up_to_the_idle_share_to_the_nanosecond():
    lo, hi = TRACE.window()
    ns = by_cause.by_cause(hp.innermost(EVENTS), _merged(STALLS, "gc"), _merged(STALLS, "stall"),
                           OPS, lo, hi)
    assert sum(ns.values()) == tr.total(tr.gaps(tr.busy(OPS), lo, hi)) == int(IDLE_MS * MS)
    assert ns == {cause: int(round(ms * MS)) for cause, ms in EXPECTED_MS.items()}
    # a quiet slice: everything goes by the scheduler's phase
    quiet = by_cause.by_cause(hp.innermost(EVENTS), [], [], OPS, lo, hi)
    assert quiet == {"gc": 0, "host_stall": 0, "waiting": int(8.5 * MS),
                     "working": int(14 * MS), "unnamed": int(3.3 * MS)}


def test_a_collection_goes_before_a_stall_and_both_before_the_schedulers_phase():
    lo, hi = TRACE.window()
    segments = hp.innermost(EVENTS)
    put_only = by_cause.by_cause(segments, _merged(STALLS[:1], "gc"), [], OPS, lo, hi)
    # 0.4 ms of the idle time under inference.put is the collection's, not "working"
    assert (put_only["gc"], put_only["working"]) == (int(0.4 * MS), int(13.6 * MS))
    # a stall alone takes its whole length; under a collection only what is left
    alone = by_cause.by_cause(segments, [], _merged(STALLS, "stall"), OPS, lo, hi)
    assert alone["host_stall"] == int(4.5 * MS)
    both = by_cause.by_cause(segments, _merged(STALLS, "gc"), _merged(STALLS, "stall"),
                             OPS, lo, hi)
    assert (both["gc"], both["host_stall"]) == (int(2.4 * MS), int(3.0 * MS))


def test_starved_is_waiting_and_a_new_phase_is_working():
    events = [_ev(0, 10, "sched.starved"), _ev(10, 30, "sched.tick", tick=1),
              _ev(12, 20, "sched.sweep")]
    ops = [(0, 1 * MS, "a"), (29 * MS, 30 * MS, "b")]
    ns = by_cause.by_cause(hp.innermost(events), [], [], ops, 0, 30 * MS)
    assert ns == {"gc": 0, "host_stall": 0, "waiting": 9 * MS, "working": 8 * MS,
                  "unnamed": 11 * MS}


@pytest.mark.parametrize("metric, cause", [
    ("idle_in_gc_pct", "gc"), ("idle_in_host_stall_pct", "host_stall"),
    ("idle_waiting_pct", "waiting"), ("idle_unnamed_pct", "unnamed")])
def test_each_metric_reads_its_cause_through_the_rings_ticks(metric, cause):
    env = _env()
    value = _read(metric, _run(TICKS + ALIVE + STALLS), env)
    assert value == pytest.approx(100 * EXPECTED_MS[cause] / WINDOW_MS, abs=1e-9)
    (line, ) = env["logged"]
    assert line.startswith("device idle by cause, % of the slice: gc 3.750, host_stall 4.688, "
                           "waiting 10.156, working 16.562, unnamed 5.156; together 40.312 "
                           "against the device's idle share 40.31")  # summarize's own sum
    assert sum(env["idle_by_cause"].values()) == pytest.approx(
        tr.summarize(TRACE)["idle_pct_by_chip"][0])


def test_the_offset_is_the_median_over_the_ticks_both_clocks_hold():
    late = dict(TICKS[1], ts_us=TICKS[1]["ts_us"] + 900)  # one ring span stamped late
    third = _row("tick", 80, 90, cat="sched", tick=3)
    events = EVENTS + [_ev(80, 90, "sched.tick", tick=3)]
    assert by_cause.ring_offset_ns({"spans": [TICKS[0], late, third]}, events) == RING_TO_TRACE_NS
    # no tick ties the clocks: the ring's spans are left out, the phases still read
    env = _env()
    assert by_cause.ring_offset_ns(_run(ALIVE + STALLS), EVENTS) is None
    assert _read("idle_in_gc_pct", _run(ALIVE + STALLS), env) == 0.0
    assert _read("idle_waiting_pct", _run(ALIVE + STALLS), env) == \
        pytest.approx(100 * 8.5 / WINDOW_MS)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_off_the_chip_every_new_metric_is_left_out(metric):
    run = _run(TICKS + ALIVE + STALLS)
    assert _read(metric, run, _env(trace=None)) is None
    assert _read(metric, run, _env(trace=tr.Trace({0: []}, []))) is None


def test_a_quiet_watched_run_reads_zero_and_an_unwatched_one_nothing():
    quiet = _run(TICKS + ALIVE)
    for metric in ("idle_in_gc_pct", "idle_in_host_stall_pct", "gc_pause_ms_per_s"):
        value = _read(metric, quiet, _env())
        assert value == 0.0 and value is not None
    assert _read("host_late_max_ms", quiet, _env()) == pytest.approx(2.1)
    # a program without the watch (the parent commit): its two causes and the
    # window's readings are absent, the scheduler's phases read as before
    parent = _run(TICKS)
    for metric in ("idle_in_gc_pct", "idle_in_host_stall_pct", "gc_pause_ms_per_s",
                   "host_late_max_ms"):
        assert _read(metric, parent, _env()) is None
    assert _read("idle_waiting_pct", parent, _env()) == pytest.approx(100 * 8.5 / WINDOW_MS)
    assert _read("idle_unnamed_pct", parent, _env()) == pytest.approx(100 * 3.3 / WINDOW_MS)
    # a run whose scheduler thread carries no annotation has no table at all
    assert _read("idle_unnamed_pct", quiet, _env(events=[])) is None


def test_the_windows_pauses_are_summed_over_its_seconds_and_the_latest_tick_is_kept():
    before = _row("gc", -400, -150, generation=2, collected=1, uncollectable=0)  # lead-in
    lead_in = _row("stall", -2600, -100.5, in_gc=0)
    after = _row("alive", 1000, 2000, max_late_us=90_000)
    env = _env()
    run = _run(TICKS + ALIVE + STALLS + [before, after, lead_in], seconds=0.8)
    # 0.4 + 2 ms of pauses that start inside a window of 0.8 s
    assert _read("gc_pause_ms_per_s", run, env) == pytest.approx(2.4 / 0.8)
    assert _read("host_late_max_ms", run, env) == pytest.approx(2.1)
    (line, ) = env["logged"]
    assert "2 collections of generation 2 or >= 1 ms, the longest 2.0 ms" in line
    assert "2 stalls, 1 of them over a collection, at [(0.11, 2.5), (0.12, 2.0)] (s into" in line
    assert "outside it (lead-in, drain) [(-2.5, 2499.5)]" in line  # a late generator's cause
    assert "a second: [140, 2100] us (the median second 1120.0 us)" in line


def test_the_seconds_of_the_profilers_own_start_and_stop_are_left_out_of_the_lateness():
    """The harness's profiler holds the interpreter ~40 ms while it starts, in
    every traced window: the measurement's stall, not the program's."""
    def second(i, late_us):
        return _row("alive", 1000 * i, 1000 * (i + 1), max_late_us=late_us)

    def clock(ms):  # the ring's clock, in seconds, at the trace's ``ms``
        return (ms * MS - RING_TO_TRACE_NS) / 1e9

    alive = [second(0, 3000), second(1, 42_000), second(2, 2600), second(3, 1900),
             second(4, 6000), second(5, 1700)]
    # due at 1.2 s of a window that begins with the first second, started by 1.25 s,
    # stopped at 4.3 s
    slice_ = types.SimpleNamespace(start_s=1.2, began=clock(1250), ended=clock(4300))
    run = dict(_run(TICKS + alive, trace_slice=slice_), t0=clock(0), seconds=6.0)
    env = _env()
    assert _read("host_late_max_ms", run, env) == pytest.approx(3.0)
    assert "left out for the profiler's own start and stop [42000, 6000] us" in env["logged"][0]
    # a stall of the program's own in another second is the reading
    run["spans"] = run["spans"] + [second(5.0, 105_000)]
    assert _read("host_late_max_ms", dict(run, seconds=7.0), _env()) == pytest.approx(105.0)
    # no profiler (the ring read by another tool): every second counts
    assert _read("host_late_max_ms", dict(run, trace_slice=None), _env()) == pytest.approx(105.0)
    # a start that spans two seconds leaves both out
    slice_.start_s, slice_.began = 0.95, clock(1050)
    run["spans"] = TICKS + alive
    assert _read("host_late_max_ms", run, _env()) == pytest.approx(2.6)


def test_each_new_metric_names_a_reader_that_exists_and_lists_every_serving_cell():
    bench = harness._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    serving = [w["name"] for w in bench["workloads"]
               if "tpot_p50_ms" in {m["name"] for m in harness.metrics_for(bench, w["name"], False)}]
    assert len(serving) >= 10  # every cell that reports ``tpot_p50_ms``, as the file has them
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(entries) and len(bench["per_layer"]) <= 128
    for name, (reader, source, unit) in NEW.items():
        entry = entries[name]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "readers", f"{reader}.py"))
        assert (entry["source"], entry["unit"], entry["better"], entry["moves"]) == \
            (source, unit, "lower", "tpot_p50_ms")
        assert set(entry["workloads"]) == set(serving)
        assert entry["layer"] == (SCHEDULER_LAYER if name in ("idle_waiting_pct",
                                                              "idle_unnamed_pct")
                                  else RUNTIME_LAYER)
    assert SCHEDULER_LAYER in {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
