#!/usr/bin/env python3
"""Run one cell several times, each run a new process with another seed, as the
driver does, and print each metric's median and spread (the distance between
the quartiles over the median). Stays off JAX itself: a parent that touched it
would hold the chip.

    python3 benchmark/tools/repeat.py --workload <cell> --runs 6 [--first-seed 100]
        [--seconds <run_seconds>] [--trace 0] [--out chiprun_out/<cell>.jsonl]

With ``--out`` each result line is appended to that file and each run's whole
output is kept beside it as ``<out>.seed<n>.log``.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def quartile_spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4, method="inclusive")
    med = statistics.median(values)
    return med, (q3 - q1) / med if med else float("nan")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--first-seed", type=int, default=100)
    parser.add_argument("--seconds", type=float)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--out")
    args = parser.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    lines = []
    for i in range(args.runs):
        cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
               args.workload, "--seed", str(args.first_seed + i), "--seconds", str(seconds),
               "--trace", str(args.trace)]
        done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        if done.returncode != 0:
            print(done.stdout[-3000:], done.stderr[-3000:], sep="\n")
            return done.returncode
        line = json.loads(done.stdout.strip().splitlines()[-1])
        line["seed"] = args.first_seed + i
        lines.append(line)
        notes = [ln for ln in done.stdout.splitlines()
                 if "generator:" in ln or "set-up " in ln or "WRONG" in ln or "failed request" in ln]
        print(f"run {i} seed {line['seed']}: correct={line['correct']} failed={line['failed']}/"
              f"{line['attempted']} " + " ".join(f"{k}={v['value']:.4g}"
                                                  for k, v in line["metrics"].items()), flush=True)
        for n in notes:
            print("    " + n[:400], flush=True)
        if args.out:
            with open(os.path.join(ROOT, args.out), "a") as f:
                f.write(json.dumps(line) + "\n")
            with open(os.path.join(ROOT, f"{args.out}.seed{line['seed']}.log"), "w") as f:
                f.write(done.stdout)
    print(f"{args.workload}: {len(lines)} runs of {seconds}s")
    for name in lines[0]["metrics"]:
        values = [ln["metrics"][name]["value"] for ln in lines if name in ln["metrics"]]
        # the first run of a checkout compiles: its set-up is recorded apart
        if name == "setup_s" and len(values) > 2:
            print(f"  setup_s first run {values[0]:.1f}")
            values = values[1:]
        med, spread = quartile_spread(values) if len(values) > 1 else (values[0], float("nan"))
        print(f"  {name:24s} median {med:12.4f}  spread {100 * spread:6.2f} %  "
              f"[{min(values):.4f} .. {max(values):.4f}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
