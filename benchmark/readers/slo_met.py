"""Share of the requests due in the window that met both of the traffic file's
limits (``slo``: ``ttft_ms``, ``tpot_ms``). A failed request misses."""

from benchmark import loadloop


def read(run, params, env):
    slo = env["traffic"].get("slo")
    if run["mode"] != "serve" or not slo:
        return None
    return loadloop.slo_met_pct(run["judged"], slo["ttft_ms"], slo["tpot_ms"])
