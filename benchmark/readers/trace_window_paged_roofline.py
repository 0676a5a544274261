"""The paged-attention kernel's share of its roofline under a sliding window,
for one of its two grids, over the traced slice.

The time it took: the summed durations of the trace events matching
``params.pattern``. The least it could take: for every engine step inside the
slice that this grid serves (``params.min_tokens`` <= tokens in the batch <=
``params.max_tokens``: the program's routing rule,
inference/v2/modules/heuristics.py), ``windowed_paged_attention`` of the live
contexts, once per layer; a ``decode_loop`` chunk is K such steps with the
contexts growing by one. Contexts are rebuilt from the program's step spans, as
``trace_paged_roofline`` does; the window is the configuration's
``sliding_window``.

``paged_attn_roofline`` prices the WHOLE context, which a kernel that reads only
the window would beat: a window cell reports these two instead."""

import re
from collections import defaultdict

from benchmark import opcount, spans


def windowed_paged_attention(query_contexts, window, n_heads, n_kv_heads, head_dim, block_size):
    """``opcount.paged_attention`` with every context clamped to the window:
    a query attends to ``min(context, window)`` keys, and a sequence's K and V
    blocks are those covering ``min(longest context, window + queries - 1)``
    positions (the first query's window through the last query). ``window`` 0
    is no window. Never more than the unclamped count, and equal to it while
    every context is inside the window."""
    flops = nbytes = 0
    for contexts in query_contexts:
        if not contexts:
            continue
        keys = [min(c, window) for c in contexts] if window else list(contexts)
        span = min(max(contexts), window + len(contexts) - 1) if window else max(contexts)
        flops += opcount.paged_attention([keys], n_heads, n_kv_heads, head_dim, block_size)[0]
        nbytes += opcount.paged_attention([[span] * len(contexts)], n_heads, n_kv_heads,
                                          head_dim, block_size)[1]
    return flops, nbytes


def read(run, params, env):
    trace, peaks, slice_ = env["trace"], env["peaks"], run.get("trace_slice")
    if trace is None or peaks is None or slice_ is None or slice_.began is None:
        return None
    rx = re.compile(params["pattern"])
    took = sum(e - s for ops in trace.devices.values() for s, e, n in ops if rx.search(n)) / 1e9
    if not took:
        return None
    m = run["model"]
    window = int(env["config"].get("sliding_window") or 0)
    fewest, most = params.get("min_tokens", 1), params.get("max_tokens", float("inf"))
    lo, hi = slice_.began * 1e6, slice_.ended * 1e6
    context = defaultdict(int)
    least = 0.0
    for step in spans.steps(run.get("spans") or []):
        k = step["loop_steps"]
        fed = sum(n for _, _, n in step["members"]) if k == 1 else len(step["members"])
        if lo <= step["ts_us"] < hi and fewest <= fed <= most:
            for j in range(k):
                queries = [[context[uid] + j + q + 1 for q in range(n if k == 1 else 1)]
                           for uid, _, n in step["members"]]
                flops, nbytes = windowed_paged_attention(queries, window, m["n_heads"],
                                                         m["n_kv_heads"], m["head_dim"],
                                                         m["block_size"])
                least += m["n_layers"] * opcount.roofline_seconds(flops, nbytes, peaks)[0]
        for uid, _, n in step["members"]:
            context[uid] += n if k == 1 else k
    return 100.0 * least / took
