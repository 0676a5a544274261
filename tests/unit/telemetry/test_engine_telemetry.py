"""Training-engine telemetry end-to-end on the virtual CPU mesh, and the
disabled-by-default zero-overhead guarantee."""

import json
import os

import numpy as np

import deepspeed_tpu
from deepspeed_tpu import comm as dist
from deepspeed_tpu import telemetry

from ..simple_model import make_simple_model, random_batches


def _engine(tmp_path=None, telemetry_enabled=False):
    model, params = make_simple_model(hidden_dim=16, batch_size=8)
    config = {"train_micro_batch_size_per_gpu": 8,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}}
    if telemetry_enabled:
        config["telemetry"] = {"enabled": True,
                               "jsonl_path": str(tmp_path / "metrics.jsonl"),
                               "trace_path": str(tmp_path / "trace.json")}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                               config=config)
    return engine


def test_enabled_engine_emits_jsonl_and_chrome_trace(tmp_path):
    engine = _engine(tmp_path, telemetry_enabled=True)
    batches = random_batches(4, 8, 16)

    # micro-loop steps (fwd/bwd/step spans) + the fused path + one profiled
    # eager collective (comm span + histograms)
    for batch in batches[:3]:
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
    engine.train_batch(batch=batches[3])
    dist.all_reduce(np.ones((8, 4), np.float32))
    engine.destroy()  # flushes trace + jsonl

    events = [json.loads(line) for line in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    steps = [e for e in events if e["event"] == "train_step"]
    assert len(steps) == 4
    assert all("loss" in e and "lr" in e for e in steps)
    assert any("samples_per_sec" in e for e in steps[1:])
    assert all("grad_norm" in e and "skipped_steps" in e for e in steps)

    with open(tmp_path / "trace.json") as f:
        trace = json.load(f)  # valid JSON
    evs = trace["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"fwd_microstep", "bwd_microstep", "step_microstep",
            "train_batch", "all_reduce"} <= names
    assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)
    assert all(e["ph"] == "X" for e in evs)
    # "compile" spans: the compile watch records the jit builds inline — but
    # only when jax actually backend-compiles, so a warm persistent
    # compilation cache (JAX_COMPILATION_CACHE_DIR) legitimately omits them
    cats = {e["cat"] for e in evs}
    # "runtime": the runtime watch's collections, stalls and once-a-second marker
    assert {"engine", "comm"} <= cats <= {"engine", "comm", "compile", "runtime"}
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        assert "compile" in cats


def test_enabled_engine_populates_registry_gauges(tmp_path):
    engine = _engine(tmp_path, telemetry_enabled=True)
    for batch in random_batches(2, 8, 16):
        engine.train_batch(batch=batch)
    snap = telemetry.get_registry().snapshot()
    assert snap["train_global_steps"][0][1] == 2
    assert snap["train_samples_total"][0][1] == 2 * engine.train_batch_size()
    assert snap["train_loss"][0][1] > 0
    engine.destroy()
    assert telemetry.state.active is False


def test_disabled_hot_path_makes_zero_telemetry_calls():
    """ISSUE acceptance: disabled (the default), engine and comm hot paths
    execute zero telemetry calls beyond a boolean check — proven by the
    registry's own call counter."""
    probe = telemetry.MetricsRegistry()
    telemetry.state.registry = probe

    engine = _engine(telemetry_enabled=False)
    assert telemetry.state.active is False
    batches = random_batches(3, 8, 16)
    loss = engine.forward(batches[0])
    engine.backward(loss)
    engine.step()
    engine.train_batch(batch=batches[1])
    dist.all_reduce(np.ones((8, 4), np.float32))  # comms logger disabled too

    assert probe.api_calls == 0
    assert telemetry.state.spans is None
    # the default timers stayed no-op (no span wrapper, no wall-clock sync)
    from deepspeed_tpu.utils.timer import NoopTimer
    assert isinstance(engine.timers, NoopTimer)
