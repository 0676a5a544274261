"""A percentile of the durations (ms) of the program's spans of one name that
start inside the window. ``params``: ``name``, ``cat``, ``percentile``."""

import numpy as np

from benchmark import spans


def read(run, params, env):
    rows = [s for s in run.get("spans") or []
            if s["name"] == params["name"] and s.get("cat") == params["cat"]]
    values = [s["dur_us"] / 1e3 for s in spans.in_window(rows, run)]
    return float(np.percentile(values, params["percentile"])) if values else None
