"""Largest share of the KV pool in use, from ``engine.free_blocks`` sampled by
the load loop every 100 ms of the window."""


def read(run, params, env):
    if run["mode"] != "serve" or not run["samples"]:
        return None
    used = max(g["kv_blocks_used"] for _, g in run["samples"])
    return 100.0 * used / run["kv_capacity_blocks"]
