#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, when the cell is defined. Not part of a
run: a run offers load at the rate frozen in the traffic file and never
searches.

    python3 benchmark/tools/sweep.py --workload <cell> --rates 2,3,4,5,6 [--seconds 20]

One process prepares the cell once (weights, check, warm-up) and then measures
one window per rate, in the order given, each through a scheduler of its own.
The knee is the highest rate at which the backlog (requests due and not yet
done) at the end of the window is no larger than at its middle (give or take
two) and at least 90 % of the requests due in the window's first half had
finished when the run ended. A last
window at a quarter of the knee gives the unloaded medians from which the
traffic file's limits are set (3 x, rounded). Prints one row per window and, as
the last line, the whole ladder as JSON."""

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, loadloop  # noqa: E402


def backlog(requests, t):
    return sum(1 for r in requests if r.due_s <= t and (r.done_s is None or r.done_s > t))


def rung(serve, ctx, prepared, traffic, rate, seconds):
    doc = copy.deepcopy(traffic)
    doc["params"]["rate_per_s"] = rate
    w = serve.measure(ctx, prepared, doc, seconds)
    judged = w["judged"]
    # a request due late in the window is cut by its end whatever the load: the
    # share that finished is taken over those due in its first half
    early = [r for r in judged if r.due_s < seconds / 2]
    finished = sum(1 for r in early if r.ok)
    ttft, tpot = loadloop.ttft_values_ms(judged), loadloop.tpot_values_ms(judged)
    return {"rate_per_s": rate, "due": len(judged),
            "finished_pct": 100.0 * finished / max(1, len(early)),
            "failed": sum(1 for r in judged if r.ok is False),
            "backlog_mid": backlog(w["requests"], seconds / 2),
            "backlog_end": backlog(w["requests"], seconds),
            "ttft_p50_ms": loadloop.percentile(ttft, 50), "ttft_p90_ms": loadloop.percentile(ttft, 90),
            "tpot_p50_ms": loadloop.percentile(tpot, 50),
            "tokens_per_s": loadloop.served_tokens_per_s(w["requests"], seconds),
            "late_p99_ms": loadloop.percentile(loadloop.late_values_ms(w["requests"]), 99),
            "builds": w["builds_in_window"]}


def sustained(row, slack=2):
    return row["backlog_end"] <= row["backlog_mid"] + slack and row["finished_pct"] >= 90.0


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True, help="comma-separated requests per second")
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    def log(message):
        print(f"[{time.perf_counter() - harness.T_START:7.1f}s] {message}", flush=True)

    started = harness.start(ROOT, args.workload, False, log)
    if isinstance(started, int):
        return started
    _, cell, config, traffic, devices = started
    ctx = harness.make_ctx(ROOT, args.workload, cell, config, traffic, args.seed, args.seconds,
                           False, log)
    serve = harness._load_module(ROOT, "runners", config["mode"])
    prepared = serve.prepare(ctx)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        rows.append(rung(serve, ctx, prepared, traffic, rate, args.seconds))
        log(json.dumps(rows[-1]))
    ok = [r["rate_per_s"] for r in rows if sustained(r)]
    knee = max(ok) if ok else None
    unloaded = None
    if knee is not None:
        unloaded = rung(serve, ctx, prepared, traffic, knee / 4, args.seconds)
        log(json.dumps(unloaded))
    prepared["engine"].close()
    print(json.dumps({"workload": args.workload, "device_kind": devices[0].device_kind,
                      "date": time.strftime("%Y-%m-%d"), "seconds": args.seconds,
                      "correct": prepared["correct"], "knee_per_s": knee,
                      "rate_at_0.8_knee": None if knee is None else round(0.8 * knee, 3),
                      "ladder": rows, "unloaded_at_quarter_knee": unloaded}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
