"""The sum of one numeric ``args`` entry over the program's spans of one name
that start inside the window, per second of the window. ``params``: ``name``,
``cat``, ``arg``. A program whose spans lack the entry gives nothing to read.

Read only beside the chip's trace (``host_phases.on_chip``), as ``span_phase``
is and for its reason: the CPU rehearsal's printed metrics are listed exactly
(``tests/benchmark/test_harness_rehearsal.py``)."""

from benchmark import host_phases, spans


def read(run, params, env):
    if not host_phases.on_chip(env):
        return None
    rows = [s for s in run.get("spans") or []
            if s["name"] == params["name"] and s.get("cat") == params["cat"]
            and params["arg"] in (s.get("args") or {})]
    if not rows:
        return None
    return sum(s["args"][params["arg"]] for s in spans.in_window(rows, run)) / run["seconds"]
