"""Flash attention's share of its roofline: the least time the chip could take
for the calls the trace shows, over the time they took.

Every call of a training step has the same shape (the chip's sequences x the
sequence length, from the run's model sizes), so each trace event matching
``params.kernels[i].pattern`` costs ``opcount.<params.kernels[i].cost>`` of that
shape; the bound is the larger of operations over peak FLOP/s and bytes over
peak bytes/s."""

from benchmark import opcount, trace_reduce


def read(run, params, env):
    trace, peaks = env["trace"], env["peaks"]
    if trace is None or peaks is None or run["mode"] != "train":
        return None
    m = run["model"]
    least = took = 0.0
    for kernel in params["kernels"]:
        flops, nbytes = opcount.KERNEL_COSTS[kernel["cost"]](
            m["sequences_per_chip"], m["seq_len"], m["n_heads"], m["n_kv_heads"], m["head_dim"])
        bound, _ = opcount.roofline_seconds(flops, nbytes, peaks)
        for ops in trace.devices.values():
            events = trace_reduce.matching(ops, kernel["pattern"])
            least += bound * len(events)
            took += sum(e - s for s, e, _ in events) / 1e9
    return 100.0 * least / took if took else None
