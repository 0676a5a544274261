"""Time inside collective operations (all-gather, reduce-scatter, all-reduce,
all-to-all, collective-permute) during which no other operation runs on that
chip, over the traced slice. The worst chip."""

from benchmark import trace_reduce


def read(run, params, env):
    trace = env["trace"]
    if trace is None or len(trace.devices) < 2:
        return None
    lo, hi = trace.window()
    if hi <= lo:
        return None
    return max(100.0 * trace_reduce.exposed_collective_ns(ops) / (hi - lo)
               for ops in trace.devices.values())
