"""Sequences in an engine step, mean over the window's steps (a ``decode_loop``
chunk counts as its K steps)."""

from benchmark import spans


def read(run, params, env):
    rows = spans.in_window(spans.steps(run.get("spans") or []), run)
    total = sum(len(s["members"]) * s["loop_steps"] for s in rows)
    count = sum(s["loop_steps"] for s in rows)
    return total / count if count else None
