"""CPU rehearsal of ``chip_smoke.py``: the refusal off-TPU, and every phase at
tiny widths on the virtual mesh — paths, arguments and control flow. What only
the chip can show (the kernels, the widths, the memory) is the script's own job
on the chip; what the chip's compiler accepts is tests/unit/ops/test_tpu_compile.py."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    return chip_smoke


def test_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=240,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "tpu" in r.stderr
    assert '"ok"' not in r.stdout  # no result line, and no phase output either
    assert "phase" not in r.stdout


def test_phases_run_tiny_on_the_virtual_mesh(chip_smoke):
    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.models.mixtral import MixtralConfig

    meter = chip_smoke.CompileMeter()
    serve = chip_smoke.ServeSizes(kv_blocks=32, max_context=256, token_budget=64, new_tokens=6,
                                  decode_chunk=2, short_prompt=20, long_prompt=90,
                                  compare_steps=2, window=64, window_prompt=210)
    train = chip_smoke.TrainSizes(seq_len=128, steps=2,
                                  zero_optimization=(("stage", 3),
                                                     ("stage3_param_persistence_threshold", 0)))
    mixtral = MixtralConfig.tiny(num_hidden_layers=1, max_position_embeddings=256)
    llama = LlamaConfig.tiny(num_hidden_layers=1, max_position_embeddings=256,
                             use_flash_attention=True)
    four = jax.devices()[:4]

    windowed = LlamaConfig.tiny(num_hidden_layers=1, max_position_embeddings=256,
                                model_type="mistral", sliding_window=serve.window)
    chip_smoke.serve_phase(meter, 0, serve, config=mixtral, window_config=windowed)
    chip_smoke.train_phase(meter, 0, train, config=llama)
    chip_smoke.ep_serve_phase(meter, 0, serve, config=mixtral, devices=four)
    chip_smoke.zero3_phase(meter, 0, train, config=llama, devices=four)
    assert meter.programs > 0
