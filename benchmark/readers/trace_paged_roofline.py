"""The paged-attention kernel's share of its roofline over the traced slice.

The time it took: the summed durations of the trace events matching
``params.pattern``. The least it could take: for every engine step inside the
slice that the kernel serves (at most ``params.kernel_max_tokens`` tokens in
the batch: the program's own routing rule, inference/v2/modules/heuristics.py),
``opcount.paged_attention`` of the live contexts, once per layer; a
``decode_loop`` chunk is K such steps with the contexts growing by one. The
contexts are rebuilt from the program's step spans: a sequence's context is
what its earlier spans fed it."""

from collections import defaultdict

from benchmark import opcount, spans


def read(run, params, env):
    trace, peaks, slice_ = env["trace"], env["peaks"], run.get("trace_slice")
    if trace is None or peaks is None or slice_ is None or slice_.began is None:
        return None
    import re
    rx = re.compile(params["pattern"])
    took = sum(e - s for ops in trace.devices.values() for s, e, n in ops if rx.search(n)) / 1e9
    if not took:
        return None
    m = run["model"]
    lo, hi = slice_.began * 1e6, slice_.ended * 1e6
    context = defaultdict(int)
    least = 0.0
    for step in spans.steps(run.get("spans") or []):
        k = step["loop_steps"]
        fed = sum(n for _, _, n in step["members"]) if k == 1 else len(step["members"])
        if lo <= step["ts_us"] < hi and fed <= params["kernel_max_tokens"]:
            for j in range(k):
                queries = [[context[uid] + j + q + 1 for q in range(n if k == 1 else 1)]
                           for uid, _, n in step["members"]]
                flops, nbytes = opcount.paged_attention(queries, m["n_heads"], m["n_kv_heads"],
                                                        m["head_dim"], m["block_size"])
                least += m["n_layers"] * opcount.roofline_seconds(flops, nbytes, peaks)[0]
        for uid, _, n in step["members"]:
            context[uid] += n if k == 1 else k
    return 100.0 * least / took
