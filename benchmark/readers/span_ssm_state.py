"""What the dispatch spans (``inference.put`` / ``inference.decode_loop``) that
start inside the window say of the per-sequence state group, by ``params.kind``:

- ``slots_peak_pct``: the largest share of the group's slots held as a step was
  dispatched, ``ssm_slots_live`` over ``ssm_slots_total`` (the pool's own
  count, from the program);
- ``rows_per_step``: rows through a Mamba-2 block a step: ``ssm_tokens`` over
  the steps (a ``decode_loop`` chunk is ``steps`` of them) and the model's
  Mamba-2 blocks (the configuration's pattern).

A program whose spans lack the entries gives nothing to read. Read only beside
the chip's trace, as ``span_arg_ratio`` is and for its reason."""

from benchmark import host_phases, spans


def read(run, params, env):
    if not host_phases.on_chip(env):
        return None
    rows = [s for s in run.get("spans") or []
            if s["name"] in ("put", "decode_loop") and s.get("cat") == "inference"
            and "ssm_slots_total" in (s.get("args") or {})]
    rows = [s["args"] for s in spans.in_window(rows, run)]
    if not rows:
        return None
    if params["kind"] == "slots_peak_pct":
        return 100.0 * max(a["ssm_slots_live"] / a["ssm_slots_total"] for a in rows)
    config = env["config"]
    blocks = config["hybrid_override_pattern"][:config["num_hidden_layers"]].count("M")
    steps = sum(int(a.get("steps", 1)) for a in rows)
    return sum(a["ssm_tokens"] for a in rows) / (steps * blocks)
