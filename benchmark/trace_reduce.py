"""From the profiler's trace to numbers: device busy and idle time, time per
operation name, exposed collective time, and what the host was doing in the
longest idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. A TPU shows as one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event per executed HLO
operation (a Pallas kernel is one such event, named after the kernel), and the
host as ``/host:CPU`` with one line per thread, where
``jax.profiler.TraceAnnotation`` spans appear under their own names. All times
are nanoseconds on one clock.

Intervals are ``(start, end)`` pairs; ``merge`` makes them disjoint and sorted,
and every other function expects merged input where it says so.
"""

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
                        r"|collective-broadcast|ragged-all-to-all")
MAX_BREAKDOWN = 10
# operations that only hold others (their time is their body's, which the trace
# lists too): counted in the busy union, left out of the per-name ranking
CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\s]")


# ------------------------------------------------------------ intervals -----
def merge(intervals):
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(merged):
    return sum(end - start for start, end in merged)


def clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def subtract(a, b):
    """The part of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, cur = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def gaps(merged, lo, hi):
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    return subtract([(lo, hi)], clip(merged, lo, hi))


def overlap(a_start, a_end, b_start, b_end):
    return max(0, min(a_end, b_end) - max(a_start, b_start))


# ----------------------------------------------------------------- load -----
class Trace:
    """``devices``: ``{chip index: [(start_ns, end_ns, name)]}`` from each
    chip's ``XLA Ops`` line, in time order. ``host``: ``[(start_ns, end_ns,
    name, thread)]``."""

    def __init__(self, devices, host):
        self.devices, self.host = devices, host

    def window(self):
        """From the first to the last device event over all chips."""
        starts = [ops[0][0] for ops in self.devices.values() if ops]
        ends = [max(e for _, e, _ in ops) for ops in self.devices.values() if ops]
        return (min(starts), max(ends)) if starts else (0, 0)


def load(path, host_prefixes=("bench.", )):
    """Read an ``.xplane.pb``. Of the host's events only those whose name starts
    with one of ``host_prefixes`` are kept (the rest is the interpreter's)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                           for e in line.events]
                    devices[int(m.group(1))] = sorted(ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefixes):
                        host.append((int(e.start_ns), int(e.start_ns + e.duration_ns), e.name,
                                     line.name))
    return Trace(devices, sorted(host))


def load_json(path):
    """Read a slice cut by ``tools/trace_cut.py``: ``{"devices": {chip: [[start,
    end, name]]}, "host": [[start, end, name, thread]]}``."""
    import json
    with open(path) as f:
        doc = json.load(f)
    return Trace({int(c): [tuple(op) for op in ops] for c, ops in doc["devices"].items()},
                 [tuple(h) for h in doc["host"]])


# -------------------------------------------------------------- reduce ------
def busy(ops):
    return merge((s, e) for s, e, _ in ops)


def seconds_by_name(ops):
    out = defaultdict(float)
    for s, e, name in ops:
        out[name] += (e - s) / 1e9
    return dict(out)


def matching(ops, pattern):
    rx = re.compile(pattern)
    return [(s, e, n) for s, e, n in ops if rx.search(n)]


def exposed_collective_ns(ops):
    """Time inside collective operations during which no other operation runs
    on that chip."""
    coll = merge((s, e) for s, e, n in ops if COLLECTIVE.search(n))
    compute = merge((s, e) for s, e, n in ops if not COLLECTIVE.search(n))
    return total(subtract(coll, compute))


def attribute(gap, labelled):
    """The label of the host interval ``(start, end, label)`` that overlaps the
    gap most, or ``unattributed``."""
    best, best_ns = "unattributed", 0
    for s, e, label in labelled:
        ns = overlap(gap[0], gap[1], s, e)
        if ns > best_ns:
            best, best_ns = label, ns
    return best


def generic_name(name):
    """``fusion.123`` -> ``fusion``: the kind of operation, without its number."""
    return re.sub(r"[._]\d+$", "", name)


_HLO = re.compile(r"^%?([\w.\-]+) = (\(?[a-z]+\d*\[[\d,]*\])")
_PARAM = re.compile(r"%params__(\w+?)(?:__)?(?:\.\d+)?[,)\s]")


def short_name(name):
    """A row of the breakdown for one trace event. The TPU's trace names an
    operation by its whole HLO text (``%fusion.248 = bf16[8,16,28672]{...}
    fusion(..., %params__layers_0____block_sparse_moe____ExpertFFN_0____wi__.1),
    ...``); kept are the kind of operation, the type it produces and the model
    parameter it reads, with layer numbers folded so that the same operation of
    every layer is one row: ``fusion bf16[8,16,28672] <-
    layers_N.block_sparse_moe.ExpertFFN_0.wi``."""
    m = _HLO.match(name)
    if not m:
        return generic_name(name.lstrip("%"))[:120]
    out = f"{generic_name(m.group(1))} {m.group(2)}"
    p = _PARAM.search(name)
    if p:
        param = re.sub(r"layers_\d+", "layers_N", p.group(1).replace("____", "."))
        out += f" <- {param.strip('_')}"
    return out[:120]


def summarize(trace, labelled=None):
    """``busy_s`` (averaged over the chips), ``window_s``, per-chip idle share,
    and the breakdown the result line carries: the device operations with most
    time (summed over chips, under ``short_name`` of the names the trace
    prints) and the idle time by what the host was doing."""
    lo, hi = trace.window()
    window_s = (hi - lo) / 1e9
    labelled = labelled if labelled is not None else [(s, e, n) for s, e, n, _ in trace.host]
    per_chip_busy, by_name, by_label = {}, defaultdict(float), defaultdict(float)
    for chip, ops in trace.devices.items():
        merged = busy(ops)
        per_chip_busy[chip] = total(merged) / 1e9
        for name, secs in seconds_by_name(ops).items():
            if not CONTAINERS.match(name):
                by_name[short_name(name)] += secs
        for gap in gaps(merged, lo, hi):
            by_label[attribute(gap, labelled)] += (gap[1] - gap[0]) / 1e9
    n = max(1, len(per_chip_busy))
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:MAX_BREAKDOWN]
    idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:MAX_BREAKDOWN]
    return {"busy_s": sum(per_chip_busy.values()) / n, "window_s": window_s,
            "idle_pct_by_chip": {c: 100.0 * (1 - b / window_s) if window_s else None
                                 for c, b in per_chip_busy.items()},
            "breakdown": {"device_ops": [[k, v] for k, v in top],
                          "idle_gaps": [[k, v / n] for k, v in idle]}}
