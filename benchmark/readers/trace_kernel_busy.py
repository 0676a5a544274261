"""A kernel's share of the device's busy time: the summed durations of the
trace events whose name matches ``params.pattern`` over the summed durations of
all device operations (all chips together)."""

from benchmark import trace_reduce


def read(run, params, env):
    trace = env["trace"]
    if trace is None or not trace.devices:
        return None
    kernel = all_ops = 0
    for ops in trace.devices.values():
        kernel += sum(e - s for s, e, _ in trace_reduce.matching(ops, params["pattern"]))
        all_ops += trace_reduce.total(trace_reduce.busy(ops))
    if not kernel or not all_ops:
        return None
    return 100.0 * kernel / all_ops
