"""``trace_expert_roofline`` for a model that generates by diffusion over
blocks: the accepted reader, its time and its count (``expert_ffn``) unchanged,
on a run whose ``inference.block_loop`` spans are read as the chunk spans they
are. The accepted reader takes its counts from the spans it knows by name
(``CARRIERS``: ``sched.fetch``, ``inference.put``, ``inference.decode_loop``); a
block loop's span carries the same args for the same reason — ``moe_path``,
``moe_assignments`` and, written at the fetch, ``moe_banks``, over its ``steps``
forwards of the whole batch — under its own name, so it is handed over under
the chunk's. The ``put`` steps of such a cell (prompt chunks, grouped too) are
read as they always were.

A program without such spans (the parent's) gives the accepted reader's
answer: nothing, in a cell that runs no other grouped chunk."""

from benchmark.readers import trace_expert_roofline


def read(run, params, env):
    rows = [dict(s, name="decode_loop") if (s["name"], s.get("cat")) == ("block_loop", "inference")
            else s for s in run.get("spans") or []]
    return trace_expert_roofline.read(dict(run, spans=rows), params, env)
