"""What the runtime watch (``deepspeed_tpu/telemetry/runtime_watch.py``) saw over
the WHOLE window, from the ring (a 4 s slice of the trace sees one eleventh):

- ``params.what`` ``gc_pause_ms_per_s``: the summed durations of the
  ``runtime.gc`` spans that start inside the window (collections of generation 2,
  and any of at least 1 ms), in milliseconds a second of the window;
- ``host_late_max_ms``: the largest ``max_late_us`` of the window's
  ``runtime.alive`` spans: how late the watch's thread woke at worst, also below
  the threshold of a ``runtime.stall``. The seconds that hold the harness's own
  profiler starting or stopping (``TraceSlice``: a stall of ~40 ms in every
  traced window, which is the measurement's and not the program's) are left out.

0.0 is a reading. None only where the window holds no ``runtime.alive`` span (a
program without the watch), and, like ``span_phase``, off the chip."""

import statistics

from benchmark import host_phases, spans
from benchmark.readers import trace_idle_by_cause


def profiler_edges_us(run):
    """``[(from_us, to_us)]`` on the ring's clock: the profiler's start (from the
    instant the slice was due to the instant ``start_trace`` returned) and the
    instant its stop was called."""
    slice_ = run.get("trace_slice")
    if slice_ is None or slice_.began is None:
        return []
    return [((run["t0"] + slice_.start_s) * 1e6, slice_.began * 1e6),
            (slice_.ended * 1e6, slice_.ended * 1e6)]


def read(run, params, env):
    if not host_phases.on_chip(env):
        return None
    rows = trace_idle_by_cause.runtime_rows(run, env)
    alive, pauses = spans.in_window(rows["alive"], run), spans.in_window(rows["gc"], run)
    if not alive:
        return None
    edges = profiler_edges_us(run)
    program = [a for a in alive if not any(
        a["ts_us"] <= hi and lo <= a["ts_us"] + a["dur_us"] for lo, hi in edges)]
    if "runtime_watch_logged" not in env:
        env["runtime_watch_logged"] = True

        def at(rows):
            return [(round(s["ts_us"] / 1e6 - run["t0"], 2), round(s["dur_us"] / 1e3, 1))
                    for s in rows]

        stalls = spans.in_window(rows["stall"], run)
        env["log"](
            f"runtime watch over the window: {len(pauses)} collections of generation 2 or >= 1 "
            f"ms, the longest {max((p['dur_us'] for p in pauses), default=0) / 1e3:.1f} ms; "
            f"{len(stalls)} stalls, {sum(s['args']['in_gc'] for s in stalls)} of them over a "
            f"collection, at {at(stalls)} (s into the window, ms), outside it (lead-in, drain) "
            f"{at(s for s in rows['stall'] if s not in stalls)}; the watch woke late by at most, "
            f"a second: {sorted(a['args']['max_late_us'] for a in program)} us (the median "
            f"second {statistics.median([a['args']['max_late_us'] for a in program] or [0])} us), "
            f"left out for the profiler's own start and stop "
            f"{[a['args']['max_late_us'] for a in alive if a not in program]} us")
    if params["what"] == "gc_pause_ms_per_s":
        return sum(p["dur_us"] for p in pauses) / 1e3 / run["seconds"]
    return max(a["args"]["max_late_us"] for a in program) / 1e3 if program else None
