"""``trace_hybrid_scope_busy`` for the Falcon-H1 family: the share (%) of the
device's busy time in operations whose scope path matches ``params.pattern``
(``params.invert``: does not match), the path ``trace_h1_ssm_roofline.attributed``'s
— the program's own scope, and for the compiler's own operations on a Mamba-2
mixer's arrays (a state or a piece of one, the convolution's kept rows, the
input projection's kernel) the scope of the array they make, by its shape from
this family's widths. The time so attributed and the largest of what stays
unscoped are logged once a run.

A configuration without ``mamba_d_state`` or a trace in which nothing matches
gives nothing to read."""

import re

from benchmark import host_phases, trace_reduce
from benchmark.readers import trace_h1_ssm_roofline


def _paths(trace, scopes, config, env):
    """Every chip's attributed operations, made once a run."""
    if "h1_scope_paths" not in env:
        given, left = {}, {}
        env["h1_scope_paths"] = {
            chip: trace_h1_ssm_roofline.attributed(ops, scopes, config, given)
            for chip, ops in trace.devices.items()}
        for ops in env["h1_scope_paths"].values():
            for s, e, scope, name in ops:
                if not host_phases.scope_parts(scope):
                    short = trace_reduce.short_name(name)
                    left[short] = left.get(short, 0.0) + (e - s) / 1e9
        top = sorted(left.items(), key=lambda kv: -kv[1])[:8]
        env["log"]("operations under no scope of the program's, given one by the array they make: "
                   + (", ".join(f"{k} {v:.3f} s" for k, v in sorted(given.items())) or "none")
                   + "; the largest left unscoped: "
                   + ", ".join(f"{k} {v:.3f} s" for k, v in top))
    return env["h1_scope_paths"]


def read(run, params, env):
    trace = env.get("trace")
    _, scopes = host_phases.of(run, env)
    config = trace_h1_ssm_roofline.hybrid_keys(env["config"])
    if trace is None or not trace.devices or not scopes or config is None:
        return None
    rx = re.compile(params["pattern"])
    matched = rest = busy = 0
    for chip, ops in _paths(trace, scopes, config, env).items():
        busy += trace_reduce.total(trace_reduce.busy(trace.devices[chip]))
        for s, e, scope, _ in ops:
            if rx.search(scope):
                matched += e - s
            else:
                rest += e - s
    if not matched or not busy:
        return None  # the program wrote no such scope: nothing to read
    return 100.0 * (rest if params.get("invert") else matched) / busy
