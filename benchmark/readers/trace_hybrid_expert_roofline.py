"""``trace_share_expert_roofline`` for a model whose blocks are ONE mixer each
and whose experts have no gate projection (``hybrid_override_pattern``, relu
squared: the Nemotron-H family): the grouped matmul's share of its roofline,
priced by what LANDED on this chip.

That reader counts the expert layers as ``num_hidden_layers`` less the leading
dense ones and prices three matrices an expert. Here the expert blocks are the
pattern's ``E``s and an expert is two matrices (``gated`` false); the rows the
kernel computes are the span's ``moe_assignments_local``, the banks at most
``deployment_share.experts_held``. The width is the configuration's published
``moe_intermediate_size`` (the device holds it in whole lane tiles: what the
padding costs is the program's, not the roofline's). The count, the carriers,
the clocks and the log line are ``trace_expert_roofline``'s. A configuration
without the pattern or the share, or spans without the local count, give
nothing to read."""

from benchmark.readers import trace_expert_roofline


def read(run, params, env):
    config = env["config"]
    share = config.get("deployment_share")
    if not share or "hybrid_override_pattern" not in config:
        return None
    rows = [dict(s, args=dict(s["args"], moe_assignments=s["args"]["moe_assignments_local"]))
            for s in run.get("spans") or []
            if "moe_assignments_local" in (s.get("args") or {})]
    if not rows:
        return None
    n = config["num_hidden_layers"]
    experts = config["hybrid_override_pattern"][:n].count("E")
    held = dict(config, num_experts=share["experts_held"], num_dense_layers=n - experts)
    return trace_expert_roofline.read(dict(run, spans=rows), dict(params, gated=False),
                                      dict(env, config=held))
