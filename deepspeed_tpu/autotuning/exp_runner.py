"""Autotuning experiment runner — ONE experiment in its own process.

Reference: ``deepspeed/autotuning/scheduler.py`` (``run_experiment:375`` — the
scheduler materializes an experiment directory with the candidate's
ds_config.json, launches the user script through the DeepSpeed launcher, and
harvests the metric file the run writes).

TPU formulation: the experiment directory holds ``exp.json``::

    {"config": <full engine config>, "model_factory": "pkg.mod:fn",
     "steps": N, "warmup": N}

``model_factory`` names an importable ``fn(config) -> (model, params,
batch_fn)`` — the subprocess equivalent of the in-process tuner's live
objects (the reference passes a user *script* for the same reason: live
models don't cross process boundaries). The runner builds the engine, times
``steps`` train batches, and writes ``results.json`` with either
``throughput_samples_per_sec`` or ``error``. A hard death (OOM kill, XLA
abort) leaves no results.json — the scheduler treats that as a failed
experiment and moves on, which is the whole point of process isolation.
"""

import importlib
import json
import os
import sys
import time


def load_model_factory(spec: str):
    """'pkg.mod:fn' → the callable."""
    mod, sep, fn = spec.partition(":")
    if not sep:
        raise ValueError(f"model_factory must be 'module:function', got {spec!r}")
    return getattr(importlib.import_module(mod), fn)


def run(exp_dir: str) -> int:
    with open(os.path.join(exp_dir, "exp.json")) as f:
        exp = json.load(f)
    result_path = os.path.join(exp_dir, "results.json")
    steps = int(exp.get("steps", 3))
    warmup = int(exp.get("warmup", 1))
    try:
        import deepspeed_tpu
        from deepspeed_tpu.utils import groups

        cfg = exp["config"]
        factory = load_model_factory(exp["model_factory"])
        model, params, batch_fn = factory(cfg)
        micro = cfg.get("train_micro_batch_size_per_gpu", 1)
        groups.initialize_mesh(force=True)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=cfg)
        batch = batch_fn(micro)
        for _ in range(warmup):
            float(engine.train_batch(batch=batch))
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = engine.train_batch(batch=batch)
        float(loss)  # host fetch = true barrier
        dt = (time.perf_counter() - t0) / steps
        out = {"throughput_samples_per_sec": engine.train_batch_size() / dt,
               "step_time_sec": dt, "loss_final": float(loss)}
        rc = 0
    except Exception as e:  # noqa: BLE001 — a failed candidate is data, not a crash
        out = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        rc = 1
    with open(result_path, "w") as f:
        json.dump(out, f)
    return rc


def profile(factory_spec: str, config_path: str) -> int:
    """Build the factory's model once and print its parameter count as one
    JSON line — the tuner's static profile, run out-of-process so a model
    too big for the tuner process can't kill it."""
    import numpy as np
    import jax

    with open(config_path) as f:
        cfg = json.load(f)
    _, params, _ = load_model_factory(factory_spec)(cfg)
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(json.dumps({"n_params": n}))
    return 0


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) == 3 and argv[0] == "--profile":
        return profile(argv[1], argv[2])
    if len(argv) != 1:
        print("usage: python -m deepspeed_tpu.autotuning.exp_runner <exp_dir>\n"
              "       python -m deepspeed_tpu.autotuning.exp_runner --profile "
              "<pkg.mod:fn> <config.json>", file=sys.stderr)
        return 2
    return run(argv[0])


if __name__ == "__main__":
    sys.exit(main())
