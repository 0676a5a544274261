"""``trace_expert_roofline`` for a layer that holds a SHARE of the experts it
routes over (a configuration with ``deployment_share``): the grouped matmul's
share of its roofline, priced by what LANDED on this chip.

That reader prices a step by its span's ``moe_assignments`` (every assignment
the router made) and bounds the banks by the configuration's ``num_experts``.
Here most assignments belong to other chips: the rows the kernel computes are
the span's ``moe_assignments_local`` (counted on the device, beside
``moe_banks``), the banks at most ``deployment_share.experts_held``, and the
expert layers ``num_hidden_layers - first_k_dense_replace`` (no such key: no
leading dense layer). The count, the carriers, the clocks and the log line are
that reader's, handed spans and a configuration that say so. A configuration
that holds every expert, one that counts its expert blocks by a
``hybrid_override_pattern`` (``trace_hybrid_expert_roofline`` reads those), or
spans without the local count (a program that has none), give nothing to read:
the rehearsals open every metric to every cell (``tests/benchmark/tiny.py``)."""

from benchmark.readers import trace_expert_roofline


def read(run, params, env):
    config = env["config"]
    share = config.get("deployment_share")
    if not share or "hybrid_override_pattern" in config:
        return None
    rows = [dict(s, args=dict(s["args"], moe_assignments=s["args"]["moe_assignments_local"]))
            for s in run.get("spans") or []
            if "moe_assignments_local" in (s.get("args") or {})]
    if not rows:
        return None
    held = dict(config, num_experts=share["experts_held"],
                num_dense_layers=config.get("first_k_dense_replace", 0))
    return trace_expert_roofline.read(dict(run, spans=rows), params, dict(env, config=held))
