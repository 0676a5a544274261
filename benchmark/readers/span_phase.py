"""A percentile (ms) over the program's tick-phase spans of one name that start
inside the window: of their durations, or with ``params.arg`` of that numeric
``args`` entry times ``params.scale`` (0.001 for a ``*_us`` entry).
``params``: ``name``, ``cat``, ``percentile``.

Read only beside the chip's trace (``host_phases.on_chip``): these times exist
to explain the device's idle share, and a run without device events (the CPU
rehearsal, whose printed metrics ``tests/benchmark/test_harness_rehearsal.py``
lists exactly) leaves them out."""

import numpy as np

from benchmark import host_phases, spans


def read(run, params, env):
    if not host_phases.on_chip(env):
        return None
    arg = params.get("arg")
    rows = [s for s in run.get("spans") or []
            if s["name"] == params["name"] and s.get("cat") == params["cat"]
            and (arg is None or arg in (s.get("args") or {}))]
    values = [s["dur_us"] / 1e3 if arg is None else s["args"][arg] * params.get("scale", 1.0)
              for s in spans.in_window(rows, run)]
    return float(np.percentile(values, params["percentile"])) if values else None
