"""Quickstart: launcher-scheduled autotuning.

Every candidate runs as its own dstpu-launched process (crash isolation:
an OOM-killed candidate fails alone). The model crosses the process
boundary as an importable factory, 'pkg.mod:fn'.

Run:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/autotune.py
"""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.realpath(__file__))))

from deepspeed_tpu.autotuning import Autotuner


def main():
    results_dir = tempfile.mkdtemp()
    tuner = Autotuner(
        base_config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
            "autotuning": {
                "tuner_type": "gridsearch",
                "max_experiments": 4,
                # fn(config) -> (model, params, batch_fn); see
                # deepspeed_tpu/autotuning/model_factories.py to write your own
                "model_factory": "deepspeed_tpu.autotuning.model_factories:tiny_llama",
                "experiment_timeout": 600,
            },
        },
        space={"train_micro_batch_size_per_gpu": [2, 4],
               "zero_optimization.stage": [0, 2]},
        steps=2, warmup=1, results_dir=results_dir)
    best = tuner.tune()
    print("best:", best)
    with open(os.path.join(results_dir, "results.json")) as f:
        print(json.dumps(json.load(f), indent=2)[:600])
    print("OK")


if __name__ == "__main__":
    from deepspeed_tpu.utils.jax_platform import enable_compile_cache
    enable_compile_cache()
    main()
