"""The paged-attention kernel's tile grid under a BLOCK mask, as a share of its
roofline over the traced slice: a model that generates by diffusion over blocks
of ``B`` positions (the configuration's ``assumed.block_length``) runs every
step on that grid — a prompt chunk, and each forward of B rows a sequence of a
block loop.

The time it took: the summed durations of the trace events matching
``params.pattern``, over every layer. The least it could take:
``opcount.paged_attention`` of every forward inside the slice, once a layer,
each row priced for the keys up to its block's END (``block_contexts``: what
the mask lets it see, its own block whole). Contexts are rebuilt from the
program's step spans: a ``prefill`` member fed n tokens grows by n; a step
whose members all ``decode`` is a block loop, and what it ran is what its
``inference.block_loop`` span (the one of the same ``tick``) says it
dispatched — ``blocks`` and ``forwards`` over its ``seqs``, whatever the
scheduler kept of them. A configuration without a block length, or a program
without such spans, gives nothing to read."""

import re
from collections import defaultdict

from benchmark import opcount


def block_contexts(first, rows, block):
    """The cached positions each of ``rows`` queries at positions ``first ..``
    attends to under a block mask: up to its block's end, itself included."""
    return [((first + r) | (block - 1)) + 1 for r in range(rows)]


def read(run, params, env):
    trace, peaks, slice_ = env.get("trace"), env.get("peaks"), run.get("trace_slice")
    config = env["config"]
    assumed = config.get("assumed") or {}
    block = int(assumed.get("block_length") or 0)
    if trace is None or peaks is None or slice_ is None or slice_.began is None or not block:
        return None
    rx = re.compile(params["pattern"])
    took = sum(e - s for ops in trace.devices.values() for s, e, n in ops if rx.search(n)) / 1e9
    if not took:
        return None
    heads = config["num_attention_heads"]
    shape = (heads, config["num_key_value_heads"],
             config.get("head_dim") or config["hidden_size"] // heads,
             config["engine"]["kv_block_size"])
    rows = run.get("spans") or []
    loops = {s["args"].get("tick"): s["args"] for s in rows
             if (s.get("cat"), s["name"]) == ("inference", "block_loop")}
    steps = defaultdict(list)
    for s in rows:
        if s.get("cat") == "serving" and s["name"] in ("prefill", "decode"):
            steps[s["ts_us"]].append(s)
    lo, hi = slice_.began * 1e6, slice_.ended * 1e6
    context = defaultdict(int)
    least = calls = 0.0
    for ts in sorted(steps):
        members = [(m["args"]["uid"], int(m["args"]["tokens"])) for m in steps[ts]]
        inside = lo <= ts < hi
        if all(m["name"] == "decode" for m in steps[ts]):
            tick = steps[ts][0]["args"].get("tick")
            loop = loops.get(tick) if tick is not None else None
            if loop is None:
                continue  # a step whose span the ring let go: not priced
            n_blocks, forwards = loop["blocks"] // loop["seqs"], loop["forwards"] // loop["blocks"]
            for b in range(n_blocks if inside else 0):
                queries = [block_contexts(context[uid] + b * block, block, block)
                           for uid, _ in members]
                least += forwards * opcount.roofline_seconds(
                    *opcount.paged_attention(queries, *shape), peaks)[0]
                calls += forwards
            for uid, _ in members:
                context[uid] += n_blocks * block
            continue
        if inside:
            queries = [block_contexts(context[uid], n, block) for uid, n in members]
            least += opcount.roofline_seconds(*opcount.paged_attention(queries, *shape), peaks)[0]
            calls += 1
        for uid, n in members:
            context[uid] += n
    if not calls:
        return None
    env["log"](f"tile grid under a block mask of {block}: {calls:.0f} forwards of the slice x "
               f"{config['num_hidden_layers']} layers, {took:.3f} s in /{params['pattern']}/ "
               f"events against {config['num_hidden_layers'] * least:.4f} s at the roofline")
    return 100.0 * config["num_hidden_layers"] * least / took
