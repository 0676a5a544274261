#!/usr/bin/env python3
"""Cut a short slice out of an ``.xplane.pb`` into the small JSON form that
``host_phases.load_json`` reads (the fixture of
``tests/benchmark/test_host_phases_and_scopes.py``): device operations WITH
their named scope, and the scheduler thread's ``dstpu.*`` annotations with
their stats. (``trace_cut.py``'s form carries neither.)

    python3 benchmark/tools/trace_cut_phases.py <file.xplane.pb> <out.json> [start_ms] [length_ms]

Times are kept in nanoseconds, moved so that the slice starts at 0; names are
cut to 200 characters (the TPU's are whole HLO instructions). Host events that
reach over the slice's edges are clipped to it."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import host_phases, trace_reduce  # noqa: E402


def main(path, out, start_ms=1000.0, length_ms=60.0):
    trace = trace_reduce.load(path)
    scopes = host_phases.scopes_by_name(path)
    events = host_phases.scheduler_thread(host_phases.load_host(path))
    lo = trace.window()[0] + int(start_ms * 1e6)
    hi = lo + int(length_ms * 1e6)
    doc = {"source": os.path.basename(path), "start_ms": start_ms, "length_ms": length_ms,
           "devices": {str(c): [[s - lo, e - lo, n[:200], scopes.get(n, "")]
                                for s, e, n in ops if s >= lo and e <= hi]
                       for c, ops in trace.devices.items()},
           "host": [[max(e.start, lo) - lo, min(e.end, hi) - lo, e.phase, e.stats]
                    for e in events if e.end > lo and e.start < hi]}
    with open(out, "w") as f:
        json.dump(doc, f, separators=(",", ":"))
    print(f"{out}: {sum(len(v) for v in doc['devices'].values())} device events, "
          f"{len(doc['host'])} host events, {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(float(a) for a in sys.argv[3:5]))
