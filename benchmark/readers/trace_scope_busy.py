"""Share (%) of the device's busy time spent in operations whose named-scope
path matches ``params.pattern`` (with ``params.invert``: does not match;
nothing where no operation matches at all), all chips together. The path is what ``jax.named_scope`` wrote around the
operation (``host_phases.scopes_by_name``); a fusion has its root operation's.
Busy time is the union of the operations' intervals, as ``trace_kernel_busy``
has it. The whole table by top-level scope is logged once a run."""

import re

from benchmark import host_phases, trace_reduce


def _log_table(trace, scopes, env):
    if env.get("scope_table_logged"):
        return
    env["scope_table_logged"] = True
    busy_s = sum(trace_reduce.total(trace_reduce.busy(ops)) for ops in trace.devices.values()) / 1e9
    for depth in (1, 2):
        seconds = {}
        for ops in trace.devices.values():
            for scope, s in host_phases.scoped_seconds(ops, scopes, depth).items():
                seconds[scope] = seconds.get(scope, 0.0) + s
        top = sorted(seconds.items(), key=lambda kv: -kv[1])[:16]
        env["log"](f"device busy time by scope (depth {depth}), % of {busy_s:.3f}s busy: " +
                   ", ".join(f"{scope} {100 * s / busy_s:.2f}" for scope, s in top) +
                   f"; all scopes together {100 * sum(seconds.values()) / busy_s:.2f}")


def read(run, params, env):
    trace = env.get("trace")
    _, scopes = host_phases.of(run, env)
    if trace is None or not trace.devices or not scopes:
        return None
    _log_table(trace, scopes, env)
    rx = re.compile(params["pattern"])
    hit = {}  # by name: True / False, None for a container (a slice has few names, many events)
    matched = rest = busy = 0
    for ops in trace.devices.values():
        busy += trace_reduce.total(trace_reduce.busy(ops))
        for s, e, name in ops:
            if name not in hit:
                hit[name] = (None if trace_reduce.CONTAINERS.match(name)
                             else bool(rx.search(scopes.get(name, ""))))
            if hit[name]:
                matched += e - s
            elif hit[name] is not None:
                rest += e - s
    if not matched or not busy:
        return None  # the program wrote no such scope: nothing to read
    return 100.0 * (rest if params.get("invert") else matched) / busy
