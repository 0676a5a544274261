"""A latent-attention grid's share of its roofline where attention reads EVERY
causal row (no index of keys, no selection), over the traced slice: the two
grids of ``latent_paged_attention``
(``deepspeed_tpu/ops/pallas/latent_attention.py``) as a model with latent layers
among layers of another kind calls them. The count is kept here, and is of the
program's own span arguments: ``trace_latent_paged_roofline`` rebuilds the
contexts from the step spans, prices every layer of the model and bounds a
context by ``index_topk``, none of which holds for such a model.

The time it took: the summed durations of the trace events matching
``params.pattern``. The least it could take: :func:`latent_work` of the
dispatch spans that start inside the slice and that the grid ``params.grid``
serves: ``token``, every ``inference.decode_loop`` span and the
``inference.put`` spans whose ``attention`` is ``latent_token``; ``tiled``, the
``inference.put`` spans whose ``attention`` is ``latent_tiled``. A span says
``latent_rows`` (causal rows its queries attend to, over its steps and latent
layers) and ``latent_context_rows`` (rows of the pool it needs at all: a
sequence's context once a step a layer).

A configuration without ``kv_lora_rank``, spans without ``latent_rows`` (a
program that has none) or a trace without the kernel give nothing to read."""

import re

from benchmark import host_phases, opcount

LANES = 128
GRID_OF = {"latent_token": "token", "latent_tiled": "tiled"}


def latent_work(rows, context_rows, heads, width, value, dtype_bytes=2):
    """``(flop, bytes)`` of absorbed latent attention: a causal row costs each
    head one dot product with the row as cached (``width`` lanes, whole tiles)
    and one accumulation of its first ``value`` lanes, 2 flop a lane; the pool's
    rows are read once a sequence a step a layer, whatever the grid's tiles make
    of them (a decode row's ``context_rows`` are its ``rows``). The queries in
    and the outputs out are not counted: that lowers the reading."""
    return 2 * rows * heads * (width + value), context_rows * width * dtype_bytes


def read(run, params, env):
    trace, peaks, slice_ = env.get("trace"), env.get("peaks"), run.get("trace_slice")
    config = env["config"]
    if peaks is None or slice_ is None or slice_.began is None or "kv_lora_rank" not in config \
            or not host_phases.on_chip(env):
        return None
    rx = re.compile(params["pattern"])
    took = sum(e - s for ops in trace.devices.values() for s, e, n in ops if rx.search(n)) / 1e9
    lo, hi = slice_.began * 1e6, slice_.ended * 1e6
    mine = [s["args"] for s in run.get("spans") or []
            if s.get("cat") == "inference" and "latent_rows" in (s.get("args") or {})
            and lo <= s["ts_us"] < hi
            and (("token" if s["name"] == "decode_loop" else
                  GRID_OF.get(s["args"].get("attention"))) == params["grid"])]
    if not took or not mine:
        return None
    rows = sum(a["latent_rows"] for a in mine)
    context_rows = sum(a["latent_context_rows"] for a in mine)
    value = config["kv_lora_rank"]
    width = -(-(value + config["qk_rope_head_dim"]) // LANES) * LANES
    least, bound = opcount.roofline_seconds(
        *latent_work(rows, context_rows, config["num_attention_heads"], width, value), peaks)
    env["log"](f"latent attention on the {params['grid']} grid: {len(mine)} spans of the slice, "
               f"{rows} causal rows over {context_rows} rows of the pool, {took:.3f} s in "
               f"/{params['pattern']}/ events against {least:.3f} s at the roofline ({bound})")
    return 100.0 * least / took
