"""From a cell's name to the one result line.

Driven by data: this file holds no cell, configuration, traffic or metric name.
``BENCHMARK.json`` names the cell's configuration and traffic and the metrics;
each is a file of its own, found by that name:

- ``configs/<config>.json``: the sizes as run, the engine's or job's settings,
  ``family`` (``models/<family>.py`` builds the program's config object and names
  the plain reference) and ``mode`` (``runners/<mode>.py``);
- ``traffic/<traffic>.json``: ``kind`` (``traffic_kinds/<kind>.py``, the one
  general generator of that kind) and its ``params``;
- ``metrics/<metric>.json``: ``reader`` (``readers/<reader>.py``) and its
  ``params``. A reader that finds nothing to read returns None and the metric
  is left out of the line.

A later PR adds a cell, a configuration, a traffic mix or a metric as new files
plus new entries in ``BENCHMARK.json`` and edits nothing here.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

T_START = time.perf_counter()  # set-up is counted from the import of this module


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _load_module(root, subdir, name):
    path = os.path.join(root, "benchmark", subdir, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{subdir[:-1] if subdir.endswith('s') else subdir} {name!r}: "
                                f"no file {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark.{subdir}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(root, workload):
    """The cell, its configuration and its traffic, from ``BENCHMARK.json`` and
    the files it names."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load_json(os.path.join(root, config_entry["file"]))
    traffic = _load_json(os.path.join(root, "benchmark", "traffic", f"{cell['traffic']}.json"))
    return bench, cell, config, traffic


def metrics_for(bench, workload, traced):
    wanted = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in wanted if workload in m.get("workloads", [workload])]


def read_metrics(root, entries, run, env, log):
    out = {}
    for entry in entries:
        spec = _load_json(os.path.join(root, "benchmark", "metrics", f"{entry['name']}.json"))
        reader = _load_module(root, "readers", spec["reader"])
        value = reader.read(run, spec.get("params", {}), env)
        if value is None:
            log(f"metric {entry['name']}: nothing to read, left out")
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def make_ctx(root, workload, cell, config, traffic, seed, seconds, trace, log):
    """What a runner is handed."""
    from benchmark import instruments
    state_dir = os.path.join(root, ".benchmark_state")
    os.makedirs(state_dir, exist_ok=True)
    trace_dir = os.path.join(state_dir, "trace", workload)
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {
        "workload": workload, "config": config, "traffic": traffic, "seed": seed,
        "seconds": seconds, "trace": bool(trace), "chips": cell["chips"], "log": log,
        "state_dir": state_dir, "trace_dir": trace_dir, "meter": instruments.CompileMeter(),
        "family": _load_module(root, "models", config["family"]),
        "traffic_kind": _load_module(root, "traffic_kinds", traffic["kind"]),
    }


def start(root, workload, rehearsal, log):
    """Resolve the cell, refuse to run off the chip, and turn the persistent
    compile cache on. Returns ``(bench, cell, config, traffic, devices)`` or an
    exit code."""
    bench, cell, config, traffic = resolve(root, workload)
    try:
        import deepspeed_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not here ({e}); nothing to measure", file=sys.stderr)
        return 3
    import jax
    devices = jax.devices()
    if not rehearsal:
        if devices[0].platform != "tpu":
            print(f"benchmark: jax.devices()[0].platform is {devices[0].platform!r}, not 'tpu'; "
                  f"this benchmark measures the chip and does not run anywhere else",
                  file=sys.stderr)
            return 1
        if len(devices) < cell["chips"]:
            print(f"benchmark: workload {workload!r} needs {cell['chips']} chips, JAX reports "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        from deepspeed_tpu.utils.jax_platform import enable_compile_cache
        cache_dir = enable_compile_cache()
        # every program goes to the cache, also the ones that compile in under a
        # second: a cell has dozens of them and each run is a new process
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        log(f"persistent compile cache at {cache_dir}")
    return bench, cell, config, traffic, devices[:cell["chips"]]


def run_cell(root, workload, seed, seconds, trace, rehearsal=False, out=sys.stdout):
    """Run one cell and print its result line as the last line of ``out``.
    ``rehearsal`` is the test-only entry: it runs wherever JAX runs, and every
    metric it prints is renamed ``cpu_rehearsal.<name>`` so that no number from
    it can be taken for a device metric."""

    def log(message):
        print(f"[{time.perf_counter() - T_START:7.1f}s] {message}", file=out, flush=True)

    started = start(root, workload, rehearsal, log)
    if isinstance(started, int):
        return started
    bench, cell, config, traffic, devices = started
    log(f"workload {workload}: config {cell['config']}, traffic {cell['traffic']}, "
        f"{len(devices)} x {devices[0].device_kind}, seed {seed}, {seconds}s, trace {trace}")

    from benchmark import instruments, opcount
    ctx = make_ctx(root, workload, cell, config, traffic, seed, seconds, trace, log)
    run = _load_module(root, "runners", config["mode"]).run(ctx)
    run["setup_s"] = run["t0"] - T_START  # t0: the clock's reading at time 0 of the window
    run["compile"] = ctx["meter"].snapshot()
    log(f"set-up {run['setup_s']:.1f}s; compile {run['compile']['seconds']:.1f}s over "
        f"{run['compile']['programs']} programs (persistent cache {run['compile']['hits']} hit, "
        f"{run['compile']['misses']} miss); built inside the window: {run['builds_in_window']}")

    env = {"root": root, "cell": cell, "traffic": traffic, "config": config, "log": log,
           "peaks": None if rehearsal else opcount.peaks_for(devices[0].device_kind),
           "devices": devices, "trace": None, "trace_summary": None}
    device = instruments.device_summary(devices)
    breakdown = None
    if trace and run.get("trace_path"):
        from benchmark import spans, trace_reduce
        env["trace"] = trace_reduce.load(run["trace_path"])
        summary = trace_reduce.summarize(env["trace"],
                                         labelled=spans.host_intervals(run, env["trace"]))
        env["trace_summary"] = summary
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        breakdown = summary["breakdown"]

    # the other run's view: the traced run also works out the end-to-end numbers,
    # and prints what tracing cost against the last untraced run of this cell
    e2e = read_metrics(root, metrics_for(bench, workload, False), run, env, log)
    last_path = os.path.join(ctx["state_dir"], f"{workload}.untraced.json")
    if not trace:
        with open(last_path, "w") as f:
            json.dump(e2e, f)
        metrics = e2e
    else:
        if os.path.exists(last_path):
            last = _load_json(last_path)
            cost = {k: f"{e2e[k]['value'] / last[k]['value'] - 1:+.1%}"
                    for k in e2e if k in last and last[k]["value"]}
            log(f"tracing overhead against the last untraced run of this cell: {cost}")
        log(f"end-to-end numbers under tracing (not the cell's result): "
            f"{ {k: round(v['value'], 3) for k, v in e2e.items()} }")
        metrics = read_metrics(root, metrics_for(bench, workload, True), run, env, log)
    if rehearsal:
        metrics = {f"cpu_rehearsal.{k}": v for k, v in metrics.items()}
    line = {"correct": run["correct"], "attempted": run["attempted"], "failed": run["failed"],
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if run.get("compared"):
        # each number that decided ``correct`` beside its limit: the line's last key,
        # and the last lines of the standard error
        line["compared"] = run["compared"]
        for name, (value, limit) in run["compared"].items():
            print(f"compared: {name} {value:.6g} limit {limit:.6g} "
                  f"{'ok' if value <= limit else 'OVER'}", file=sys.stderr, flush=True)
    print(json.dumps(line), file=out, flush=True)
    return 0


def main(argv, root):
    parser = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    return run_cell(root, args.workload, args.seed, args.seconds, args.trace)
