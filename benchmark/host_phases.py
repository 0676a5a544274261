"""What the program was doing, from the profiler's trace alone: the scheduler
thread's phases (``dstpu.*`` host annotations) and the named scope of every
device operation. No second clock: the program's live spans are
``jax.profiler.TraceAnnotation``s, so they sit on the trace's clock beside
``XLA Ops`` and nothing is moved through ``bench.clock_sync``.

Host side. ``deepspeed_tpu.telemetry`` names a live span ``dstpu.<cat>.<name>``
and the scalar ``args`` known at entry become the event's stats (``tick``,
``steps``). Kept here: the events of the thread that ran the scheduler's ticks,
resolved to the INNERMOST phase at every instant
(``tick`` > ``admit`` / ``build_batch`` / ``prepare`` / dispatch / ``fetch`` /
``emit``; ``no_work`` between ticks). The profiler's host and device lines are
aligned only to a few milliseconds: ``device_offset_ns`` takes the rest out,
anchored at the fetch ends.

Device side. The TPU's trace carries an operation's scope path
(``jit(_forward_impl)/moe/experts/dot_general:``) in the ``tf_op`` stat of the
event's METADATA (``XEventMetadata.stats``). ``jax.profiler.ProfileData`` shows
an event's own stats only (offset, duration), and the HLO text that names the
event carries no ``op_name``; so ``scopes_by_name`` reads the ``.xplane.pb``
once more, as protobuf wire format, and only the metadata tables (the event
lines are skipped unparsed). A fusion has the scope of its root operation.

A slice cut by ``tools/trace_cut_phases.py`` (the test fixture's form) loads
through ``load_json`` into the same two structures.
"""

import re
from collections import defaultdict
from typing import NamedTuple

from benchmark import trace_reduce

PREFIX = "dstpu."
TICK = "sched.tick"
NO_WORK = "sched.no_work"
FETCH = "sched.fetch"
DISPATCH = ("inference.put", "inference.decode_loop", "inference.verify",
            "inference.verify_tree")


# ------------------------------------------------------------- host side -----
class HostEvent(NamedTuple):
    """One ``dstpu.*`` annotation; ``phase`` is its name without the prefix
    (``sched.emit``, ``inference.put``), times in nanoseconds."""
    start: int
    end: int
    phase: str
    stats: dict


def load_host(path):
    """``{thread: [HostEvent]}`` of the ``dstpu.*`` annotations in an
    ``.xplane.pb``, each thread's events in time order."""
    from jax.profiler import ProfileData
    threads = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    start = int(e.start_ns)
                    threads[line.name].append(HostEvent(
                        start, start + int(e.duration_ns), e.name[len(PREFIX):],
                        {k: v for k, v in e.stats}))
    return {t: sorted(evs, key=lambda e: (e.start, -e.end)) for t, evs in threads.items()}


def scheduler_thread(threads):
    """The events of the thread that ran the scheduler's ticks; without a
    tick anywhere (an engine driven directly), the thread with most events."""
    if not threads:
        return []
    with_ticks = [evs for evs in threads.values() if any(e.phase == TICK for e in evs)]
    return max(with_ticks or threads.values(), key=len)


def innermost(events):
    """Disjoint ``(start_ns, end_ns, phase)`` segments in time order: at every
    instant the phase of the innermost open event. ``events`` are one thread's,
    properly nested, sorted by ``(start, -end)``."""
    out, stack = [], []  # stack: [end, phase]; out segments may be empty and are dropped

    def emit(start, end, phase):
        if end > start:
            if out and out[-1][2] == phase and out[-1][1] == start:
                out[-1] = (out[-1][0], end, phase)
            else:
                out.append((start, end, phase))

    cursor = None
    for e in events:
        while stack and stack[-1][0] <= e.start:
            end, phase = stack.pop()
            emit(cursor, end, phase)
            cursor = end
        if stack:
            emit(cursor, e.start, stack[-1][1])
        cursor = e.start
        stack.append((min(e.end, stack[-1][0]) if stack else e.end, e.phase))
    while stack:
        end, phase = stack.pop()
        emit(cursor, end, phase)
        cursor = end
    return out


def ticks(events):
    """One row per ``tick`` that dispatched: ``{"tick", "start", "end",
    "dispatch_start", "fetch_end", "kind", "loop_steps"}``. ``loop_steps`` is
    the ``steps`` stat of a ``decode_loop`` dispatch, else 1; ``fetch_end`` is
    the end of the tick's last ``fetch`` (of its last dispatch where the engine
    call fetched itself)."""
    out, current = [], None
    for e in events:
        if e.phase == TICK:
            current = {"tick": e.stats.get("tick"), "start": e.start, "end": e.end,
                       "dispatch_start": None, "fetch_end": None, "kind": None,
                       "loop_steps": 1}
            out.append(current)
        elif current is not None and e.start < current["end"]:
            if e.phase in DISPATCH:
                if current["dispatch_start"] is None:
                    current["dispatch_start"] = e.start
                    current["kind"] = e.phase.split(".", 1)[1]
                    current["loop_steps"] = max(1, int(e.stats.get("steps", 1)))
                current["fetch_end"] = max(current["fetch_end"] or 0, e.end)
            elif e.phase == FETCH:
                current["fetch_end"] = max(current["fetch_end"] or 0, e.end)
    return [t for t in out if t["dispatch_start"] is not None]


RUN_GAP_NS = 500_000      # device operations closer than this are one program run
OFFSET_SEARCH_NS = 6_000_000


def device_offset_ns(tick_rows, ops):
    """How far the device's events have to be moved to sit on the host's
    timeline, from the trace itself. The profiler lines the two clocks up only
    to within a few milliseconds, differently in every session (a chip trace
    showed a program starting 1 ms BEFORE the call that dispatched it). The one
    instant the host knows on the device's timeline is the end of a fetch:
    ``np.asarray`` returns when the tick's last operation has finished. So the
    offset is the median, over the program runs of the slice, of (the fetch end
    nearest after-or-around the run's end) - (the run's end); 0 without ticks
    (a training run: nothing here depends on it). What the transfer and the
    wake-up take after the device is done (~0.1 ms) is read as device time."""
    import bisect
    ends = sorted(t["fetch_end"] for t in tick_rows)
    if not ends:
        return 0
    runs = []
    for s, e in trace_reduce.busy(ops):
        if runs and s - runs[-1][1] < RUN_GAP_NS:
            runs[-1] = (runs[-1][0], e)
        else:
            runs.append((s, e))
    diffs = []
    for _, run_end in runs:
        i = bisect.bisect_left(ends, run_end - OFFSET_SEARCH_NS)
        near = [f - run_end for f in ends[i:i + 3] if abs(f - run_end) <= OFFSET_SEARCH_NS]
        if near:
            diffs.append(min(near, key=abs))
    if not diffs:
        return 0
    diffs.sort()
    return diffs[len(diffs) // 2]


def shifted(ops, offset_ns):
    return [(s + offset_ns, e + offset_ns, n) for s, e, n in ops] if offset_ns else ops


def idle_by_phase(segments, ops, lo, hi):
    """Nanoseconds of ``[lo, hi]`` in which no operation of ``ops`` ran, by the
    phase the thread was in; time under no annotation is ``unattributed``."""
    idle = trace_reduce.gaps(trace_reduce.busy(ops), lo, hi)
    out = defaultdict(int)
    covered = 0
    for phase, spans_ in _by_phase(segments).items():
        ns = trace_reduce.total(_intersect(idle, spans_))
        if ns:
            out[phase] = ns
            covered += ns
    out["unattributed"] = trace_reduce.total(idle) - covered
    return dict(out)


def _by_phase(segments):
    out = defaultdict(list)
    for s, e, phase in segments:
        out[phase].append((s, e))
    return out


def _intersect(a, b):
    """The part of merged ``a`` inside merged ``b``."""
    return trace_reduce.subtract(a, trace_reduce.subtract(a, b))


# ----------------------------------------------------------- device side -----
def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane.pb")
        yield key >> 3, value


def _map_entry(buf):
    """A protobuf map entry's value message (field 2)."""
    for number, value in _fields(buf):
        if number == 2:
            return value
    return b""


SCOPE_STAT = "tf_op"


def scopes_by_name(path):
    """``{event name: scope path}`` over the device planes of an
    ``.xplane.pb``: XSpace.planes(1) -> XPlane{name(2), event_metadata(4),
    stat_metadata(5)}; XEventMetadata{name(2), stats(5)}; XStat{metadata_id(1),
    str_value(5), ref_value(7)}; XStatMetadata{id(1), name(2)}. The same HLO text
    in two programs is one operation kind under one scope."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                events.append(_map_entry(value))
            elif number == 5:
                meta = dict(_fields(_map_entry(value)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        for event in events:
            event_name, scope = "", None
            for number, value in _fields(event):
                if number == 2:
                    event_name = bytes(value).decode()
                elif number == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == SCOPE_STAT:
                        scope = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if scope is not None:
                out[event_name] = scope
    return out


# jit(...)/while/body/... : what JAX and XLA wrap a program's own scopes in
_WRAPPER = re.compile(r"^(jit|pjit|jvp|transpose|vmap|remat|checkpoint|custom_jvp|custom_vjp"
                      r"|shard_map|named)\(|^(while|body|cond|branch_\d+_fun|closed_call"
                      r"|core_call|custom_vjp_call|custom_jvp_call)$")


def scope_parts(scope):
    """``jit(_forward_impl)/moe/experts/dot_general:`` -> ``["moe", "experts"]``:
    the program's own scopes, without JAX's wrappers and the operation."""
    parts = scope.rstrip(":").split("/")[:-1]
    return [p for p in parts if p and not _WRAPPER.match(p)]


def scoped_seconds(ops, scopes, depth=1):
    """Seconds of device operations by the first ``depth`` scopes (``(none)``
    for an operation under no scope of the program's), containers left out."""
    out, row_of = defaultdict(float), {}  # a slice has ~1e5 events of ~1e3 names
    for s, e, name in ops:
        if name not in row_of:
            row_of[name] = (None if trace_reduce.CONTAINERS.match(name) else
                            "/".join(scope_parts(scopes.get(name, ""))[:depth]) or "(none)")
        if row_of[name] is not None:
            out[row_of[name]] += (e - s) / 1e9
    return dict(out)


# -------------------------------------------------------- what readers use ---
def load_json(path):
    """A slice cut by ``tools/trace_cut_phases.py``: ``{"devices": {chip: [[start,
    end, name, scope]]}, "host": [[start, end, phase, stats]]}`` ->
    ``(Trace, scheduler thread's events, scopes)``."""
    import json
    with open(path) as f:
        doc = json.load(f)
    trace = trace_reduce.Trace({int(c): [(s, e, n) for s, e, n, _ in ops]
                                for c, ops in doc["devices"].items()}, [])
    scopes = {n: scope for ops in doc["devices"].values() for _, _, n, scope in ops}
    events = [HostEvent(*row) for row in doc["host"]]
    return trace, sorted(events, key=lambda e: (e.start, -e.end)), scopes


def on_chip(env):
    """Whether the run has the chip's trace: some device plane with events."""
    trace = env.get("trace")
    return trace is not None and any(trace.devices.values())


def of(run, env):
    """``(scheduler thread's events, scopes)`` of a traced run, read once and
    kept in ``env`` for the readers that follow."""
    if "host_phases" not in env:
        path = run.get("trace_path")
        env["host_phases"] = ((scheduler_thread(load_host(path)), scopes_by_name(path))
                              if path and on_chip(env) else ([], {}))
    return env["host_phases"]


def aligned_chip(trace, events, log=None):
    """``(ops, lo, hi)`` of the chip with the largest idle share of the slice,
    its events moved onto the host's timeline (``device_offset_ns``); ``lo`` and
    ``hi`` are the slice's window (first to last device event), moved too."""
    lo, hi = trace.window()
    chip = min(trace.devices, key=lambda c: trace_reduce.total(trace_reduce.busy(trace.devices[c])))
    offset = device_offset_ns(ticks(events), trace.devices[chip])
    if log is not None:
        log(f"device events moved by {offset / 1e6:+.3f} ms onto the host's timeline "
            f"(each tick's last operation ends with its fetch)")
    return shifted(trace.devices[chip], offset), lo + offset, hi + offset
