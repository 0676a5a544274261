"""A ratio of sums over the program's spans of one name that start inside the
window: the ``numerator`` args summed, over the ``denominator`` args summed,
times ``scale`` (default 1). ``params``: ``name``, ``cat``, ``numerator`` and
``denominator`` (lists of ``args`` entries), ``scale``. A program whose spans
lack an entry, or a window whose denominator is 0, gives nothing to read.

Read only beside the chip's trace (``host_phases.on_chip``), as
``span_arg_per_second`` is and for its reason: the CPU rehearsal's printed
metrics are listed exactly (``tests/benchmark/test_harness_rehearsal.py``)."""

from benchmark import host_phases, spans


def read(run, params, env):
    if not host_phases.on_chip(env):
        return None
    wanted = list(params["numerator"]) + list(params["denominator"])
    rows = [s for s in run.get("spans") or []
            if s["name"] == params["name"] and s.get("cat") == params["cat"]
            and all(arg in (s.get("args") or {}) for arg in wanted)]
    rows = spans.in_window(rows, run)
    below = sum(s["args"][arg] for s in rows for arg in params["denominator"])
    if not below:
        return None
    above = sum(s["args"][arg] for s in rows for arg in params["numerator"])
    return params.get("scale", 1.0) * above / below
