"""Share (%) of the traced slice in which no operation ran on the chip, by the
ONE cause each nanosecond of it goes to (``params.cause``), in this order:

- ``gc``: some thread was inside a collection (the ring's ``runtime.gc`` spans);
- ``host_stall``: the runtime watch's thread woke late by more than its
  threshold (the ring's ``runtime.stall`` spans: from the instant it should
  have woken to the instant it did);
- then by the scheduler thread's innermost ``dstpu.*`` annotation: ``waiting``
  (``sched.no_work``, ``sched.starved``, ``sched.commit_wait``), ``working`` (any
  other named phase below ``sched.tick``), ``unnamed`` (bare ``sched.tick``, or no
  annotation at all).

The five add up to the device's idle share on the chip that idles most, aligned
as ``host_phases.aligned_chip`` aligns it; the whole table is logged once a run
with its sum beside that share. ``working`` is not a metric of its own:
``idle_in_engine|emit|build_batch|admit_pct`` are its finer split.

The ``runtime.*`` spans are rows of the ring alone (the watch writes no
annotation: the accepted ``idle_in_*`` metrics read the scheduler thread's line
as they always did, a collection inside ``inference.put`` included), and are
moved onto the trace's clock by the scheduler's own ticks: every ``sched.tick`` is in the ring with the
number its annotation carries, and the median difference over the slice's ticks
is the offset (a slice without a tick has no scheduler phase to go by either).

``gc`` and ``host_stall`` read None where the run's ring has no ``runtime.alive``
span (a program without the watch): 0.0 means the watch ran and saw nothing."""

from benchmark import host_phases, trace_reduce

WAITING = ("sched.no_work", "sched.starved", "sched.commit_wait")
CAUSES = ("gc", "host_stall", "waiting", "working", "unnamed")
FROM_THE_WATCH = ("gc", "host_stall")


def ring_offset_ns(run, events):
    """What to add to a ring span's ``ts_us * 1000`` to land on the clock of
    the scheduler thread's ``events``: the median, over the ticks both hold, of
    (the annotation's start - the ring span's); None where they share no tick."""
    ring = {s["args"]["tick"]: s["ts_us"] for s in run.get("spans") or []
            if s.get("cat") == "sched" and s["name"] == "tick" and "tick" in (s.get("args") or {})}
    diffs = sorted(e.start - ring[e.stats["tick"]] * 1000 for e in events
                   if e.phase == host_phases.TICK and e.stats.get("tick") in ring)
    return diffs[len(diffs) // 2] if diffs else None


def runtime_rows(run, env):
    """``{name: [row]}`` of the ring's ``runtime.*`` spans, picked out of the
    run's (up to a million) rows once and kept in ``env`` for both readers."""
    if "runtime_rows" not in env:
        rows = {"gc": [], "stall": [], "alive": []}
        for s in run.get("spans") or []:
            if s.get("cat") == "runtime" and s["name"] in rows:
                rows[s["name"]].append(s)
        env["runtime_rows"] = rows
    return env["runtime_rows"]


def on_trace_clock(rows, offset_ns):
    """Ring rows as merged intervals on the trace's clock."""
    return trace_reduce.merge(
        (s["ts_us"] * 1000 + offset_ns, (s["ts_us"] + s["dur_us"]) * 1000 + offset_ns)
        for s in rows)


def _inside(a, b):
    """``(the part of merged a inside merged b, the rest of a)``."""
    rest = trace_reduce.subtract(a, b)
    return trace_reduce.subtract(a, rest), rest


def by_cause(segments, collections, stalls, ops, lo, hi):
    """Nanoseconds of ``[lo, hi]`` in which no operation of ``ops`` ran, by
    cause. ``segments``: the scheduler thread's innermost phases
    (``host_phases.innermost``); ``collections``, ``stalls``: merged intervals."""
    phases = {}
    for start, end, phase in segments:
        phases.setdefault(phase, []).append((start, end))
    waiting = trace_reduce.merge(iv for p in WAITING for iv in phases.pop(p, []))
    phases.pop(host_phases.TICK, None)
    working = trace_reduce.merge(iv for ivs in phases.values() for iv in ivs)
    out, rest = {}, trace_reduce.gaps(trace_reduce.busy(ops), lo, hi)
    for cause, intervals in (("gc", collections), ("host_stall", stalls), ("waiting", waiting),
                             ("working", working)):
        inside, rest = _inside(rest, intervals)
        out[cause] = trace_reduce.total(inside)
    out["unnamed"] = trace_reduce.total(rest)
    return out


def table(run, env):
    """``{cause: % of the slice idle by it}``, worked out once and kept in ``env``."""
    if "idle_by_cause" not in env:
        trace = env.get("trace")
        events = host_phases.of(run, env)[0] if host_phases.on_chip(env) else []
        shares = None
        if events:
            ops, lo, hi = host_phases.aligned_chip(trace, events)
            offset = ring_offset_ns(run, events)
            collections = stalls = []
            if offset is not None:
                rows = runtime_rows(run, env)
                collections = on_trace_clock(rows["gc"], offset)
                stalls = on_trace_clock(rows["stall"], offset)
            ns = by_cause(host_phases.innermost(events), collections, stalls, ops, lo, hi)
            shares = {cause: 100.0 * ns[cause] / (hi - lo) for cause in CAUSES}
            # what ``device_idle_pct`` reads, worked out by ``trace_reduce.summarize``
            by_chip = (env.get("trace_summary") or {}).get("idle_pct_by_chip") or {}
            idle = max((v for v in by_chip.values() if v is not None), default=float("nan"))
            env["log"]("device idle by cause, % of the slice: " + ", ".join(
                f"{cause} {shares[cause]:.3f}" for cause in CAUSES) +
                f"; together {sum(shares.values()):.3f} against the device's idle share "
                f"{idle:.3f} ({len(collections)} collections and {len(stalls)} stalls of the "
                f"ring on the trace's clock)")
        env["idle_by_cause"] = shares
    return env["idle_by_cause"]


def read(run, params, env):
    shares = table(run, env)
    cause = params["cause"]
    # a program with the watch says so once a second
    if shares is None or (cause in FROM_THE_WATCH and not runtime_rows(run, env)["alive"]):
        return None
    return shares[cause]
