"""Share (%) of the traced slice in which no operation ran on the chip AND the
scheduler thread's innermost ``dstpu.*`` annotation was one of
``params.phases`` (``sched.emit``, ``inference.prepare``, ...). Over all phases
and the unattributed rest the shares add up to the device's idle share; the
whole table is logged once a run."""

from benchmark import host_phases


def table(run, env):
    """``{phase: % of the slice idle in it}`` on the chip that idles most,
    worked out once and kept in ``env``."""
    if "idle_by_phase" not in env:
        trace = env.get("trace")
        events, _ = host_phases.of(run, env)
        shares = None
        if trace is not None and trace.devices and events:
            ops, lo, hi = host_phases.aligned_chip(trace, events, env["log"])
            by_phase = host_phases.idle_by_phase(host_phases.innermost(events), ops, lo, hi)
            shares = {phase: 100.0 * ns / (hi - lo) for phase, ns in by_phase.items()}
            env["log"]("device idle by scheduler phase, % of the slice: " + ", ".join(
                f"{phase} {share:.2f}" for phase, share in
                sorted(shares.items(), key=lambda kv: -kv[1])) +
                f"; together {sum(shares.values()):.2f}")
        env["idle_by_phase"] = shares
    return env["idle_by_phase"]


def read(run, params, env):
    shares = table(run, env)
    if shares is None:
        return None
    return sum(shares.get(phase, 0.0) for phase in params["phases"])
