"""The device's idle time under a ``decode_loop`` chunk's call, split in two:
the round trip around the chunk's run on the device, and the gaps between
operations inside that run.

A chunk is one engine call that launches one program and fetches its tokens
(``dstpu.inference.decode_loop`` on the scheduler thread's line of the trace;
nothing is annotated inside it). The chunk's run is the longest program run
among the device operations under the call, operations closer than
``host_phases.RUN_GAP_NS`` being one run: a call may put a helper program of
a few microseconds on the device milliseconds before its own (a greedy chunk
builds ``PRNGKey(0)`` there every time), and the wait between the two is the
launch's, not a gap inside the run. Per chunk:

- round trip = the call's duration - the run's extent: launch (Python, argument
  handling, enqueue, the device's start) and the result's way back. A
  difference of two durations, so free of the offset between the host's and
  the device's clock;
- gaps inside = the run's extent - the time an operation ran in it.

``params.value``: ``round_trip_p50_ms`` (the median over the chunks wholly
inside the slice) or ``idle_in_run_pct`` (100 x the gaps inside all chunks' runs
/ the slice). A chunk that reaches over the slice's edge is clipped to it for
the share and the sums, and left out of the median. Logged once a run: the sums
against the ``inference.decode_loop`` row of ``trace_idle_in_phase``'s table
(round trips + gaps inside - the time of the operations outside the runs ARE
that row, both being the slice's idle time under those calls), and the call's
two parts on the host's clock, the span's ``launch_us`` and ``fetch_us``."""

import bisect

import numpy as np

from benchmark import host_phases, spans, trace_reduce
from benchmark.readers import trace_idle_in_phase

PHASE = "inference.decode_loop"


def split(calls, busy, lo, hi):
    """``calls``: ``[(start, end)]`` of the chunk calls; ``busy``: the device's
    merged busy intervals on the same clock. Returns ``{"round_trips": [ns of
    each whole chunk], "round_trip_ns", "gaps_ns", "outside_ns"}``, the last
    three summed over all chunks clipped to ``[lo, hi]`` (``outside_ns``: the
    time of operations under a call and outside its run)."""
    starts = [s for s, _ in busy]
    round_trips, round_trip_ns, gaps_ns, outside_ns = [], 0, 0, 0
    for start, end in calls:
        s, e = max(start, lo), min(end, hi)
        if e <= s:
            continue
        first = max(0, bisect.bisect_right(starts, s) - 1)
        under = trace_reduce.clip(busy[first:bisect.bisect_left(starts, e)], s, e)
        runs = []  # [start, end, time an operation ran]
        for a, b in under:
            if runs and a - runs[-1][1] < host_phases.RUN_GAP_NS:
                runs[-1][1], runs[-1][2] = b, runs[-1][2] + b - a
            else:
                runs.append([a, b, b - a])
        run_start, run_end, ran = max(runs, key=lambda r: r[1] - r[0], default=(0, 0, 0))
        extent = run_end - run_start
        round_trip_ns += (e - s) - extent
        gaps_ns += extent - ran
        outside_ns += trace_reduce.total(under) - ran
        if (s, e) == (start, end):
            round_trips.append((e - s) - extent)
    return {"round_trips": round_trips, "round_trip_ns": round_trip_ns, "gaps_ns": gaps_ns,
            "outside_ns": outside_ns}


def aligned_chip(run, env):
    """``host_phases.aligned_chip`` of the run's trace (the idlest chip's
    operations and the slice's window, both on the host's timeline), worked
    out once for this reader and ``trace_expert_roofline`` and kept in ``env``."""
    if "aligned_chip" not in env:
        events, _ = host_phases.of(run, env)
        env["aligned_chip"] = host_phases.aligned_chip(env["trace"], events)
    return env["aligned_chip"]


def table(run, env):
    """The split of the slice's chunks, worked out once and kept in ``env``."""
    if "chunk_idle" not in env:
        env["chunk_idle"] = None
        events, _ = host_phases.of(run, env)
        calls = [(e.start, e.end) for e in events if e.phase == PHASE]
        if calls:
            ops, lo, hi = aligned_chip(run, env)
            out = split(calls, trace_reduce.busy(ops), lo, hi)
            out["slice_ns"] = hi - lo
            env["chunk_idle"] = out
            _log(out, len(calls), run, env)
    return env["chunk_idle"]


def _log(out, n_calls, run, env):
    ms = 1e-6
    whole = out["round_trips"]
    together = 100.0 * (out["round_trip_ns"] + out["gaps_ns"] - out["outside_ns"]) / out["slice_ns"]
    line = (f"decode_loop chunks in the slice: {n_calls} ({len(whole)} whole); round trip p50 "
            f"{np.median(whole) * ms if whole else float('nan'):.3f} ms, summed "
            f"{out['round_trip_ns'] * ms:.1f} ms; gaps inside the runs {out['gaps_ns'] * ms:.1f} ms"
            f"; operations outside the runs {out['outside_ns'] * ms:.3f} ms; round trips + gaps - "
            f"those {together:.2f} % of the slice")
    by_phase = trace_idle_in_phase.table(run, env)
    if by_phase:
        line += f" against {PHASE}'s idle share {by_phase.get(PHASE, 0.0):.2f} %"
    rows = [s["args"] for s in spans.in_window(run.get("spans") or [], run)
            if s["name"] == "decode_loop" and s.get("cat") == "inference"
            and "launch_us" in (s.get("args") or {})]
    if rows:
        line += (f"; on the host's clock a call is launch p50 "
                 f"{np.median([a['launch_us'] for a in rows]) / 1e3:.3f} ms + fetch p50 "
                 f"{np.median([a['fetch_us'] for a in rows]) / 1e3:.3f} ms")
    env["log"](line)


def read(run, params, env):
    if not host_phases.on_chip(env):
        return None
    out = table(run, env)
    if out is None:
        return None
    if params["value"] == "idle_in_run_pct":
        return 100.0 * out["gaps_ns"] / out["slice_ns"]
    if params["value"] == "round_trip_p50_ms":
        return float(np.median(out["round_trips"])) / 1e6 if out["round_trips"] else None
    raise KeyError(f"trace_chunk_idle has no value {params['value']!r}")
