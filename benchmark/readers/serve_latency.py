"""Serving latencies from the load loop's own timestamps.

``params``: ``what`` in ``ttft`` (due to first token), ``tpot`` (per request,
mean gap between its tokens), ``late`` (sent minus due, over every request
sent); ``percentile``."""

from benchmark import loadloop


def read(run, params, env):
    if run["mode"] != "serve":
        return None
    if params["what"] == "late":
        values = loadloop.late_values_ms(run["requests"])
    elif params["what"] == "ttft":
        values = loadloop.ttft_values_ms(run["judged"])
    else:
        values = loadloop.tpot_values_ms(run["judged"])
    if not values:
        return None
    return loadloop.percentile(values, params["percentile"])
