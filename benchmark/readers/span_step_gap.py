"""The scheduler's time between two engine steps, median (ms) over the window:
start of step i+1 minus end of step i (``spans.steps``: the per-request phase
spans), over the pairs with no ``no_work`` span (cat ``sched``) between them,
so that waiting for a request is not read as scheduler work. Read only beside
the chip's trace, as ``span_phase`` is."""

import bisect

import numpy as np

from benchmark import host_phases, spans


def read(run, params, env):
    if not host_phases.on_chip(env):
        return None
    rows = run.get("spans") or []
    steps = spans.in_window(spans.steps(rows), run)
    idle = sorted(s["ts_us"] for s in rows if s["name"] == "no_work" and s.get("cat") == "sched")
    gaps = []
    for a, b in zip(steps, steps[1:]):
        end = a["ts_us"] + a["dur_us"]
        i = bisect.bisect_left(idle, end)
        if b["ts_us"] >= end and not (i < len(idle) and idle[i] < b["ts_us"]):
            gaps.append((b["ts_us"] - end) / 1e3)
    return float(np.median(gaps)) if gaps else None
