"""The paged-attention kernel's share of its roofline in a model whose layers
see DIFFERENT spans (sliding-window and full layers side by side), for one of
the kernel's two grids, over the traced slice.

The time it took: the summed durations of the trace events matching
``params.pattern``, over every layer. The least it could take: for every engine
step inside the slice that this grid serves (``params.min_tokens`` <= tokens in
the batch <= ``params.max_tokens``: the program's routing rule,
inference/v2/modules/heuristics.py), ``windowed_paged_attention``
(``trace_window_paged_roofline``'s count: ``opcount.paged_attention`` clamped to a
window, 0 = unclamped) of the live contexts: once a LAYER, each under its own
window. A ``decode_loop`` chunk is K
such steps with the contexts growing by one. Contexts are rebuilt from the
program's step spans, as ``trace_paged_roofline`` does. The layers' windows are
the configuration's: the first ``num_hidden_layers`` entries of ``layer_types``,
a ``sliding_attention`` layer under ``sliding_window``, any other unclamped.

``paged_attn_roofline`` prices every layer whole (a window layer's kernel would
beat it) and ``paged_window_*_roofline`` clamps every layer (a full layer's
kernel could not reach it): a cell of such a model reports these two instead.
A configuration without ``layer_types`` gives nothing to read."""

import re
from collections import defaultdict

from benchmark import opcount, spans
from benchmark.readers.trace_window_paged_roofline import windowed_paged_attention


def layer_windows(config):
    """The window of each served layer, from a configuration file; None where
    the configuration does not say layer by layer."""
    kinds = config.get("layer_types")
    if not kinds:
        return None
    window = int(config.get("sliding_window") or 0)
    return [window if kind == "sliding_attention" else 0
            for kind in kinds[:config["num_hidden_layers"]]]


def mixed_least_seconds(query_contexts, windows, shape, peaks):
    """The least time the layers' calls could take together: the roofline time
    of one layer under each distinct window, times the layers that have it."""
    return sum(windows.count(w) * opcount.roofline_seconds(
        *windowed_paged_attention(query_contexts, w, *shape), peaks)[0] for w in set(windows))


def read(run, params, env):
    trace, peaks, slice_ = env["trace"], env["peaks"], run.get("trace_slice")
    if trace is None or peaks is None or slice_ is None or slice_.began is None:
        return None
    config = env["config"]
    windows = layer_windows(config)
    if windows is None:
        return None
    rx = re.compile(params["pattern"])
    took = sum(e - s for ops in trace.devices.values() for s, e, n in ops if rx.search(n)) / 1e9
    if not took:
        return None
    heads = config["num_attention_heads"]
    shape = (heads, config["num_key_value_heads"],
             config.get("head_dim") or config["hidden_size"] // heads,
             config["engine"]["kv_block_size"])
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
                least += mixed_least_seconds(queries, windows, shape, peaks)
        for uid, _, n in step["members"]:
            context[uid] += n if k == 1 else k
    return 100.0 * least / took
