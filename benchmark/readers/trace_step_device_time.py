"""Device time of an engine step, median (ms) over the slice's ticks: the time
some operation ran on the chip between the start of a tick's dispatch
(``dstpu.inference.put|decode_loop|verify|verify_tree``) and the end of its
``dstpu.sched.fetch``, over the steps the dispatch ran (``steps`` of a
``decode_loop``, else 1; a chunk counts as that many readings)."""

import numpy as np

from benchmark import host_phases, trace_reduce


def read(run, params, env):
    trace = env.get("trace")
    events, _ = host_phases.of(run, env)
    if trace is None or not trace.devices or not events:
        return None
    ops, _, _ = host_phases.aligned_chip(trace, events)
    busy = trace_reduce.busy(ops)
    values = []
    for tick in host_phases.ticks(events):
        ns = trace_reduce.total(trace_reduce.clip(busy, tick["dispatch_start"], tick["fetch_end"]))
        values += [ns / tick["loop_steps"] / 1e6] * tick["loop_steps"]
    if not values:
        return None
    env["log"](f"device time per step over {len(values)} steps of the slice: p10 "
               f"{np.percentile(values, 10):.3f} p50 {np.median(values):.3f} p90 "
               f"{np.percentile(values, 90):.3f} ms")
    return float(np.median(values))
