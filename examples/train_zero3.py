"""Quickstart: ZeRO-3 training with bf16 compute and qwZ weight gathers.

Run (virtual 8-device CPU mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_zero3.py
On a TPU host, drop both variables — the real chips form the mesh.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.realpath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn

import deepspeed_tpu


class MLP(nn.Module):
    """A module whose apply(params, batch) returns the scalar loss."""

    @nn.compact
    def __call__(self, batch):
        x, y = batch
        h = nn.tanh(nn.Dense(256)(x))
        h = nn.tanh(nn.Dense(256)(h))
        return jnp.mean((nn.Dense(1)(h).squeeze(-1) - y) ** 2)


def batch_for_step(step, batch=32, dim=64):
    """Deterministic pure-function-of-step data: a resumed run replays the
    exact batches an uninterrupted run would see (the chaos-equivalence
    contract — real loaders checkpoint their cursor via client_state)."""
    rng = np.random.default_rng(1000 + step)
    x = rng.normal(size=(batch, dim)).astype(np.float32)
    y = (x[:, 0] * 0.5 - x[:, 1]).astype(np.float32)
    return x, y


def main_fault_tolerant():
    """DSTPU_CKPT_DIR mode: crash-consistent checkpoint per step, resume from
    the latest good tag, preemption-safe SIGTERM exit — and, under
    DSTPU_KILL_AT_STEP=N, a chaos SIGKILL after step N (first life only; the
    supervisor's DSTPU_RESTART_COUNT suppresses the replay). Run it under
    ``bin/dstpu_train`` and the killed-and-resumed run reaches a final
    loss/params numerically identical to an uninterrupted one."""
    import json

    ckdir = os.environ["DSTPU_CKPT_DIR"]
    total_steps = int(os.environ.get("DSTPU_TOTAL_STEPS", "8"))
    kill_at = os.environ.get("DSTPU_KILL_AT_STEP")
    if kill_at and "DSTPU_TRAIN_FAULTS" not in os.environ:
        os.environ["DSTPU_TRAIN_FAULTS"] = json.dumps(
            {"enabled": True, "kill_at_steps": [int(kill_at)]})

    model = MLP()
    params = model.init(jax.random.PRNGKey(0),
                        (jnp.asarray(batch_for_step(0)[0]),
                         jnp.asarray(batch_for_step(0)[1])))["params"]
    config = {
        "train_micro_batch_size_per_gpu": 32,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "checkpoint": {"keep_last_k": 3, "verify_arrays_on_load": True},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=config)
    engine.install_preemption_handler(save_dir=ckdir)
    path, _ = engine.load_checkpoint(ckdir)  # (None, None) on a fresh dir
    life = os.environ.get("DSTPU_RESTART_COUNT", "0")
    print(f"life {life}: {'resumed from ' + path if path else 'fresh start'} "
          f"at step {engine.global_steps}", flush=True)

    loss = None
    while engine.global_steps < total_steps:
        loss = engine.train_batch(batch=batch_for_step(engine.global_steps))
        engine.save_checkpoint(ckdir)

    if loss is None:  # resumed life found training already complete
        print(f"final step {engine.global_steps} (already complete)")
    else:
        print(f"final step {engine.global_steps} loss {float(loss):.10f}")
    out = os.environ.get("DSTPU_FINAL_PARAMS")
    if out:
        flat = jax.tree_util.tree_flatten_with_path(jax.device_get(engine.params))[0]
        np.savez(out, **{jax.tree_util.keystr(k): np.asarray(v) for k, v in flat})
    engine.destroy()
    print("OK")


def main():
    model = MLP()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 64)).astype(np.float32)
    y = (x[:, 0] * 0.5 - x[:, 1]).astype(np.float32)
    params = model.init(jax.random.PRNGKey(0), (jnp.asarray(x), jnp.asarray(y)))["params"]

    config = {
        "train_micro_batch_size_per_gpu": 32,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3,
                              "zero_quantized_weights": True,   # qwZ: s8 gathers
                              "stage3_param_persistence_threshold": 0},
    }
    # DSTPU_TELEMETRY_DIR=<dir>: unified telemetry — JSONL metrics stream +
    # Chrome trace (open telemetry.trace.json in chrome://tracing / Perfetto)
    tel_dir = os.environ.get("DSTPU_TELEMETRY_DIR")
    if tel_dir:
        config["telemetry"] = {"enabled": True,
                               "jsonl_path": os.path.join(tel_dir, "telemetry.jsonl"),
                               "trace_path": os.path.join(tel_dir, "telemetry.trace.json")}

    engine, optimizer, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=config)
    assert isinstance(optimizer, deepspeed_tpu.ZeROOptimizer)

    for step in range(20):
        loss = engine.train_batch(batch=(np.tile(x, (2, 1)), np.tile(y, 2)))
        if step % 5 == 0:
            print(f"step {step:3d}  loss {float(loss):.4f}  lr {engine.get_lr()[0]:.2e}")

    if tel_dir:
        # micro-loop steps so the trace carries fwd/bwd/step spans, plus one
        # profiled eager collective for a comm span + latency/bytes histograms
        for _ in range(2):
            loss = engine.forward((x, y))
            engine.backward(loss)
            engine.step()
        deepspeed_tpu.comm.all_reduce(np.ones((8, 32), np.float32))

    # checkpoint + RLHF-style surgery on the sharded master
    import tempfile
    ckdir = tempfile.mkdtemp()
    engine.save_checkpoint(ckdir, tag="demo")
    from deepspeed_tpu.utils import safe_get_full_fp32_param
    w = safe_get_full_fp32_param(engine, "Dense_0/kernel")
    print(f"checkpoint saved; Dense_0/kernel gathered shape {w.shape}")
    engine.destroy()  # flushes the telemetry trace/JSONL when enabled
    print("OK")


if __name__ == "__main__":
    from deepspeed_tpu.utils.jax_platform import enable_compile_cache
    enable_compile_cache()
    if os.environ.get("DSTPU_CKPT_DIR"):
        main_fault_tolerant()
    else:
        main()
