"""A latent-attention kernel's share of its roofline, over the traced slice:
the two grids of ``latent_paged_attention`` and ``latent_index_scores``
(``deepspeed_tpu/ops/pallas/latent_attention.py``). The count is kept here.

The time it took: the summed durations of the trace events matching
``params.pattern``, over every layer. The least it could take: for every engine
step inside the slice that the grid serves (``params.min_tokens`` <= tokens in
the batch <= ``params.max_tokens``: the program's routing rule), once a layer,

- ``params.kind`` ``attention``: per query of context c (itself included),
  heads x min(c, index_topk) keys x (latent row + value width) x 2 flop: a
  key's logit is one dot product with its latent row (``kv_lora_rank`` +
  ``qk_rope_head_dim``) and its value the row's first ``kv_lora_rank`` lanes;
  per sequence the SELECTED rows read once a step (min(longest context,
  index_topk) rows of ``latent row x 2`` bytes: what the mask leaves out is
  work the kernel does and the count does not), plus the absorbed queries in
  and the latent outputs out;
- ``index``: per query of context c, ``index_n_heads x index_head_dim x 2``
  flop a key, and per sequence its index keys once a step (``index_head_dim x
  2`` bytes a key of the longest context), the queries and weights in and the
  float32 scores out; only the steps whose block-table bucket can hold more
  than ``index_topk`` keys (the others' programs score nothing).

A ``decode_loop`` chunk is K such steps with the contexts growing by one.
Contexts are rebuilt from the program's step spans, as
``trace_mixed_paged_roofline`` does. A configuration without ``kv_lora_rank``,
or a trace without the kernel, gives nothing to read."""

import re
from collections import defaultdict

from benchmark import opcount, spans


def latent_attention(query_contexts, heads, row, value, topk, dtype_bytes=2):
    flops = nbytes = 0
    for contexts in query_contexts:
        if not contexts:
            continue
        flops += sum(2 * heads * (row + value) * min(c, topk) for c in contexts)
        nbytes += min(max(contexts), topk) * row * dtype_bytes
        nbytes += len(contexts) * heads * (row + value) * dtype_bytes
    return flops, nbytes


def index_scores(query_contexts, index_heads, index_dim, dtype_bytes=2):
    flops = nbytes = 0
    for contexts in query_contexts:
        if not contexts:
            continue
        flops += sum(2 * index_heads * index_dim * c for c in contexts)
        nbytes += max(contexts) * index_dim * dtype_bytes
        nbytes += len(contexts) * (index_heads * index_dim * dtype_bytes + index_heads * 4)
        nbytes += sum(4 * c for c in contexts)
    return flops, nbytes


def table_floor(block, topk, max_context):
    """The program's smallest block-table bucket, in blocks: the smallest power
    of two (from 4) that holds ``index_topk`` keys, or the whole table's where
    that is at most four times as long (the served model's rule, copied)."""

    def bucket(keys):
        blocks = 4
        while blocks * block < keys:
            blocks *= 2
        return blocks

    floor, whole = bucket(topk), bucket(max_context)
    return whole if whole <= 4 * floor else floor


def table_bucket_keys(longest, block, floor):
    """Keys the step's block-table bucket can hold: the smallest power of two
    of blocks, from ``floor``, that holds the longest context."""
    blocks = floor
    while blocks * block < longest:
        blocks *= 2
    return blocks * block


def read(run, params, env):
    trace, peaks, slice_ = env["trace"], env["peaks"], run.get("trace_slice")
    if trace is None or peaks is None or slice_ is None or slice_.began is None:
        return None
    config = env["config"]
    if "kv_lora_rank" not in config:
        return None
    rx = re.compile(params["pattern"])
    took = sum(e - s for ops in trace.devices.values() for s, e, n in ops if rx.search(n)) / 1e9
    if not took:
        return None
    topk, block = config["index_topk"], config["engine"]["kv_block_size"]
    floor = table_floor(block, topk, config["engine"]["state_manager"]["max_context"])
    layers = config["num_hidden_layers"]
    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    fewest, most = params.get("min_tokens", 1), params.get("max_tokens", float("inf"))
    lo, hi = slice_.began * 1e6, slice_.ended * 1e6
    context = defaultdict(int)
    least = 0.0
    for step in spans.steps(run.get("spans") or []):
        k = step["loop_steps"]
        fed = sum(n for _, _, n in step["members"]) if k == 1 else len(step["members"])
        if lo <= step["ts_us"] < hi and fewest <= fed <= most:
            longest = max(context[uid] + (n if k == 1 else k) for uid, _, n in step["members"])
            selects = table_bucket_keys(longest, block, floor) > topk
            for j in range(k):
                queries = [[context[uid] + j + q + 1 for q in range(n if k == 1 else 1)]
                           for uid, _, n in step["members"]]
                if params["kind"] == "attention":
                    work = latent_attention(queries, config["num_attention_heads"], row,
                                            config["kv_lora_rank"], topk)
                elif selects:
                    work = index_scores(queries, config["index_n_heads"],
                                        config["index_head_dim"])
                else:
                    continue
                least += layers * opcount.roofline_seconds(*work, peaks)[0]
        for uid, _, n in step["members"]:
            context[uid] += n if k == 1 else k
    return 100.0 * least / took
