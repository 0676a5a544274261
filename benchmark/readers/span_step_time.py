"""Host wall time of a blocked engine step, median over the window's steps of
one phase. ``params.phase``: ``decode`` (every member decodes; a ``decode_loop``
chunk counts as its K steps, each a K-th of the chunk), ``prefill`` (some
member is fed prompt tokens) or ``any``."""

import numpy as np

from benchmark import spans


def read(run, params, env):
    rows = spans.in_window(spans.steps(run.get("spans") or []), run)
    values = []
    for step in rows:
        has_prefill = any(phase == "prefill" for _, phase, _ in step["members"])
        if params["phase"] != "any" and (params["phase"] == "prefill") != has_prefill:
            continue
        values += [step["dur_us"] / step["loop_steps"] / 1e3] * step["loop_steps"]
    return float(np.median(values)) if values else None
