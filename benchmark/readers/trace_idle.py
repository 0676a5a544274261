"""Share of the traced slice in which no operation ran on the chip: 1 - (union
of the chip's operation intervals) / (slice). The worst chip where there are
several."""


def read(run, params, env):
    summary = env.get("trace_summary")
    shares = [v for v in (summary or {}).get("idle_pct_by_chip", {}).values() if v is not None]
    return max(shares) if shares else None
