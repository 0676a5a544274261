"""From the program's spans to engine steps.

Read here, with the reason each is sound (PERF.md, the verdict table):

- ``prefill`` / ``decode`` (cat ``serving``, one per request in a step): the
  scheduler takes their start before ``np.asarray(engine.put(...))`` /
  ``np.asarray(engine.decode_loop(...))`` and their end after it, so the duration
  is the host wall time of a BLOCKED step. All members of one step share one
  ``ts_us``. ``args``: ``uid``, ``tokens`` (fed for prefill; kept for a chunk).
- ``decode_loop`` (cat ``inference``): read for ``args.steps`` and its position
  in time only; its duration is a dispatch time and is not read.
- ``queued`` (cat ``serving``): submit to admission, per request.

Span times are ``time.perf_counter()`` in microseconds.
"""

from collections import defaultdict


def steps(span_rows):
    """Engine steps in time order: ``{"ts_us", "dur_us", "members": [(uid, phase,
    tokens)], "loop_steps"}``; ``loop_steps`` is K for a ``decode_loop`` chunk
    and 1 for a ``put``."""
    by_ts = defaultdict(list)
    for s in span_rows:
        if s.get("cat") == "serving" and s["name"] in ("prefill", "decode"):
            by_ts[s["ts_us"]].append(s)
    loops = sorted((s["ts_us"], int(s["args"]["steps"])) for s in span_rows
                   if s.get("cat") == "inference" and s["name"] == "decode_loop")
    out, li = [], 0
    for ts in sorted(by_ts):
        members = by_ts[ts]
        end = ts + max(m["dur_us"] for m in members)
        while li < len(loops) and loops[li][0] < ts:
            li += 1
        k = 1
        if li < len(loops) and loops[li][0] <= end:
            k = loops[li][1]
        out.append({"ts_us": ts, "dur_us": end - ts, "loop_steps": k,
                    "members": [(m["args"]["uid"], m["name"], int(m["args"]["tokens"]))
                                for m in members]})
    return out


def in_window(items, run, key="ts_us"):
    lo = run["t0"] * 1e6
    hi = lo + run["seconds"] * 1e6
    return [i for i in items if lo <= i[key] < hi]


SYNC_EVENT = "bench.clock_sync"
NO_WORK_GAP_US = 20_000


def host_intervals(run, trace):
    """What the host was doing, as ``(start_ns, end_ns, label)`` on the TRACE's
    clock, for labelling the device's idle gaps.

    The harness's own ``bench.*`` annotations are in the trace already. The
    scheduler thread has no annotations of its own yet, so its time is split by
    the program's step spans, moved onto the trace's clock through the one
    event whose start is known on both (``bench.clock_sync``): inside a step
    (dispatch, transfer of the result, waiting for the device), between two
    steps (sampling, pushing tokens, building the next batch), or with no step
    for 20 ms (nothing to run)."""
    labelled = [(s, e, name) for s, e, name, _ in trace.host if name != SYNC_EVENT]
    slice_ = run.get("trace_slice")
    sync = [s for s, _, name, _ in trace.host if name == SYNC_EVENT]
    rows = steps(run.get("spans") or [])
    if not rows or not sync or slice_ is None or slice_.sync_clock is None:
        return labelled
    offset_ns = sync[0] - slice_.sync_clock * 1e9
    # step intervals label the gaps of a serving run; the load loop's submit and
    # consume marks run all the time on another thread and would only hide them
    labelled = []
    prev_end = None
    for step in rows:
        start = step["ts_us"] * 1e3 + offset_ns
        end = start + step["dur_us"] * 1e3
        if prev_end is not None and start > prev_end:
            idle = (start - prev_end) > NO_WORK_GAP_US * 1e3
            labelled.append((prev_end, start, "scheduler: no step for 20 ms (nothing to run)"
                             if idle else "scheduler: between steps (sample, push, build batch)"))
        if step["loop_steps"] > 1:
            kind = "decode_loop chunk"
        elif any(phase == "prefill" for _, phase, _ in step["members"]):
            kind = "put with prefill"
        else:
            kind = "put, decode only"
        labelled.append((start, end, f"scheduler: inside engine step ({kind})"))
        prev_end = end
    return labelled
