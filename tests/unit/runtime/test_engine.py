"""Engine + ZeRO stage tests.

Reference: ``tests/unit/runtime/zero/test_zero.py`` — the core correctness gate:
same model trained with the engine at every ZeRO stage must match a plain JAX/optax
reference run (the reference compares against torch baselines).
"""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.utils import groups

from ..simple_model import SimpleModel, make_simple_model, random_batches

HIDDEN = 16


def _reference_adam_run(params, model, batches, lr=0.01, steps=None):
    """Hand-rolled AdamW reference (bias-corrected, eps outside sqrt)."""
    import jax
    import jax.numpy as jnp

    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    t = 0

    def loss_fn(p, batch):
        return model.apply({"params": p}, batch)

    losses = []
    for batch in batches:
        t += 1
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        m = jax.tree.map(lambda mm, gg: 0.9 * mm + 0.1 * gg, m, g)
        v = jax.tree.map(lambda vv, gg: 0.999 * vv + 0.001 * gg * gg, v, g)
        bc1 = 1 - 0.9**t
        bc2 = 1 - 0.999**t
        params = jax.tree.map(lambda p, mm, vv: p - lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + 1e-8), params, m, v)
        losses.append(float(loss))
    return params, losses


def _engine_config(stage=0, micro=2, gas=1, extra=None):
    cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 0.01, "weight_decay": 0.0}},
        "zero_optimization": {"stage": stage},
    }
    if extra:
        cfg.update(extra)
    return cfg


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stage_matches_reference(stage):
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=16)
    batches = random_batches(5, 16, HIDDEN)

    engine, _, _, _ = deepspeed_tpu.initialize(model=model,
                                               model_parameters=params0,
                                               config=_engine_config(stage=stage, micro=2))
    for batch in batches:
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()

    ref_params, _ = _reference_adam_run(params0, model, batches)
    import jax
    got = jax.device_get(engine.params)
    want = jax.device_get(ref_params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_param_sharding_by_stage():
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN)

    cfg = _engine_config(stage=3, micro=1)
    cfg["zero_optimization"]["stage3_param_persistence_threshold"] = 0
    e3, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params0, config=cfg)
    import jax
    # stage-3: at least the big kernels must be sharded over the zero axes
    kernel = e3.params["Dense_0"]["kernel"]
    assert not kernel.sharding.is_fully_replicated

    groups.destroy_mesh()
    groups.initialize_mesh(force=True)
    e0, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params0,
                                           config=_engine_config(stage=0, micro=1))
    assert e0.params["Dense_0"]["kernel"].sharding.is_fully_replicated


def test_gradient_accumulation_equivalence():
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=16)
    batches = random_batches(4, 16, HIDDEN)

    # gas=2 over half-batches == gas=1 over full batches
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params0,
                                               config=_engine_config(stage=1, micro=1, gas=2))
    for batch in batches:
        x, y = batch
        for half in range(2):
            sl = slice(half * 8, (half + 1) * 8)
            loss = engine.forward((x[sl], y[sl]))
            engine.backward(loss)
            engine.step()
    assert engine.global_steps == len(batches)

    ref, _ = _reference_adam_run(params0, model, batches)
    import jax
    for g, w in zip(jax.tree.leaves(jax.device_get(engine.params)), jax.tree.leaves(jax.device_get(ref))):
        np.testing.assert_allclose(g, w, rtol=3e-3, atol=3e-4)


def test_train_batch_fast_path_matches_micro_loop():
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=16)
    batches = random_batches(3, 16, HIDDEN)

    e1, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params0,
                                           config=_engine_config(stage=2, micro=2, gas=1))
    for b in batches:
        e1.train_batch(batch=b)

    groups.destroy_mesh()
    groups.initialize_mesh(force=True)
    e2, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params0,
                                           config=_engine_config(stage=2, micro=2, gas=1))
    for b in batches:
        loss = e2.forward(b)
        e2.backward(loss)
        e2.step()

    import jax
    for a, b in zip(jax.tree.leaves(jax.device_get(e1.params)), jax.tree.leaves(jax.device_get(e2.params))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_bf16_runs_and_converges():
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=16)
    batches = random_batches(20, 16, HIDDEN, seed=7)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params0,
        config=_engine_config(stage=2, micro=2, extra={"bf16": {"enabled": True}}))
    losses = []
    for b in batches:
        losses.append(float(engine.train_batch(batch=b)))
    assert losses[-1] < losses[0]
    # every input of the step is placed before the first call: a state leaf
    # that comes back with another placement makes step 2 compile all over
    assert engine.lowerable_callables()["train_batch"]._cache_size() == 1


def test_fp16_dynamic_loss_scale_skips_on_overflow():
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=8)

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params0,
        config=_engine_config(stage=0, micro=1,
                              extra={"fp16": {"enabled": True, "initial_scale_power": 4, "hysteresis": 2}}))
    scale0 = engine.loss_scale
    assert scale0 == 2.0**4

    x = np.full((8, HIDDEN), 1e30, dtype=np.float32)  # force overflow in fp16 compute
    y = np.ones((8, ), dtype=np.float32)
    # first overflow: step skipped, hysteresis consumed, scale UNCHANGED (reference
    # DynamicLossScaler semantics with delayed_shift=2)
    loss = engine.forward((x, y))
    engine.backward(loss)
    engine.step()
    assert engine.get_skipped_steps() == 1
    assert engine.loss_scale == scale0

    # second overflow: hysteresis exhausted -> scale halves
    loss = engine.forward((x, y))
    engine.backward(loss)
    engine.step()
    assert engine.get_skipped_steps() == 2
    assert engine.loss_scale == scale0 / 2.0

    # healthy step does not skip and refills nothing prematurely
    bx = np.random.default_rng(0).normal(size=(8, HIDDEN)).astype(np.float32)
    loss = engine.forward((bx, y))
    engine.backward(loss)
    engine.step()
    assert engine.get_skipped_steps() == 2


def test_gradient_clipping_applied():
    import jax
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=8)
    clip = 1e-4
    lr = 0.5
    cfg = _engine_config(stage=0, micro=1, extra={"gradient_clipping": clip})
    cfg["optimizer"] = {"type": "SGD", "params": {"lr": lr}}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params0, config=cfg)
    b = random_batches(1, 8, HIDDEN)[0]
    loss = engine.forward(b)
    engine.backward(loss)
    engine.step()
    # reported norm is the pre-clip global norm (reference semantics) ...
    assert engine.get_global_grad_norm() > clip
    # ... but the applied update is clipped: ||delta|| = lr * clip for SGD
    delta = jax.tree.map(lambda a, b: a - b, jax.device_get(engine.params), jax.device_get(params0))
    delta_norm = float(np.sqrt(sum(np.sum(d**2) for d in jax.tree.leaves(delta))))
    assert delta_norm == pytest.approx(lr * clip, rel=1e-2)


def test_checkpoint_save_load_roundtrip(tmp_path):
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=8)
    batches = random_batches(3, 8, HIDDEN)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params0,
                                               config=_engine_config(stage=2, micro=1))
    for b in batches:
        engine.train_batch(batch=b)
    engine.save_checkpoint(str(tmp_path), client_state={"note": 7})

    groups.destroy_mesh()
    groups.initialize_mesh(force=True)
    e2, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params0,
                                           config=_engine_config(stage=2, micro=1))
    path, client = e2.load_checkpoint(str(tmp_path))
    assert path is not None
    assert client["note"] == 7
    assert e2.global_steps == engine.global_steps
    import jax
    for a, b in zip(jax.tree.leaves(jax.device_get(engine.params)), jax.tree.leaves(jax.device_get(e2.params))):
        np.testing.assert_allclose(a, b)


def test_checkpoint_reshard_across_stages(tmp_path):
    """Save at stage 3, load at stage 1 (the universal-checkpoint acceptance test,
    SURVEY.md §4: 'save at dp=4 / load at dp=2' analog)."""
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=8)
    e3, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params0,
                                           config=_engine_config(stage=3, micro=1))
    e3.train_batch(batch=random_batches(1, 8, HIDDEN)[0])
    e3.save_checkpoint(str(tmp_path))

    groups.destroy_mesh()
    groups.initialize_mesh(model_parallel_size=2, force=True)
    e1, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params0,
                                           config=_engine_config(stage=1, micro=1))
    path, _ = e1.load_checkpoint(str(tmp_path))
    assert path is not None
    import jax
    for a, b in zip(jax.tree.leaves(jax.device_get(e3.params)), jax.tree.leaves(jax.device_get(e1.params))):
        np.testing.assert_allclose(a, b)


def test_sgd_with_param_specs_none_state():
    """SGD momentum=0 has a None state slot; param_specs must not crash init
    (regression: _broadcast_param_specs returned P() for None subtrees)."""
    import jax
    from jax.sharding import PartitionSpec as P
    groups.initialize_mesh(model_parallel_size=2, force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=8)
    specs = jax.tree.map(lambda p: P(), params0)
    cfg = _engine_config(stage=1, micro=1)
    cfg["optimizer"] = {"type": "SGD", "params": {"lr": 0.1}}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params0,
                                               config=cfg, param_specs=specs)
    loss = engine.train_batch(batch=random_batches(1, 8, HIDDEN)[0])
    assert np.isfinite(float(loss))


def test_lr_scheduler_integration():
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=8)
    engine, _, _, sched = deepspeed_tpu.initialize(
        model=model, model_parameters=params0,
        config=_engine_config(stage=0, micro=1,
                              extra={"scheduler": {"type": "WarmupLR",
                                                   "params": {"warmup_max_lr": 0.1, "warmup_num_steps": 5,
                                                              "warmup_type": "linear"}}}))
    assert sched is not None
    lrs = []
    for b in random_batches(6, 8, HIDDEN):
        engine.train_batch(batch=b)
        lrs.append(engine.get_lr()[0])
    assert lrs[-1] == pytest.approx(0.1)


def test_fp16_overflow_does_not_advance_lr_schedule():
    """Reference _take_model_step (engine.py:2100-2106): overflow-skipped steps
    leave warmup/decay schedules untouched."""
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=8)
    engine, _, _, sched = deepspeed_tpu.initialize(
        model=model, model_parameters=params0,
        config=_engine_config(stage=0, micro=1, extra={
            "fp16": {"enabled": True, "initial_scale_power": 4, "hysteresis": 1},
            "scheduler": {"type": "WarmupLR",
                          "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 0.01,
                                     "warmup_num_steps": 10}},
        }))
    it0 = sched.last_batch_iteration

    x = np.full((8, HIDDEN), 1e30, dtype=np.float32)  # overflow in fp16 compute
    y = np.ones((8, ), dtype=np.float32)
    engine.backward(engine.forward((x, y)))
    engine.step()
    assert engine.get_skipped_steps() == 1
    assert sched.last_batch_iteration == it0  # schedule frozen on skipped step

    bx = np.random.default_rng(0).normal(size=(8, HIDDEN)).astype(np.float32)
    engine.backward(engine.forward((bx, y)))
    engine.step()
    assert sched.last_batch_iteration == it0 + 1  # healthy step advances


def test_eval_forward_deterministic_no_grads():
    """ADVICE: eval() forward is a plain loss pass — no cached grads, deterministic."""
    groups.initialize_mesh(force=True)
    model, params0 = make_simple_model(hidden_dim=HIDDEN, batch_size=8)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params0, config=_engine_config(stage=0, micro=1))
    bx = np.random.default_rng(0).normal(size=(8, HIDDEN)).astype(np.float32)
    y = np.ones((8, ), dtype=np.float32)
    engine.eval()
    l1 = float(engine.forward((bx, y)))
    l2 = float(engine.forward((bx, y)))
    assert l1 == l2
    assert engine._cached_grads is None
    engine.train()
    l3 = engine.forward((bx, y))
    assert engine._cached_grads is not None
    engine.backward(l3)
    engine.step()
