"""The engines' official lowering hooks (the perf gates' only entry points —
no reaching into compile-watch-wrapped jit caches).

Engine builds are consolidated (one training engine, one inference engine)
— tier-1 runs on a small CPU box and every deepspeed_tpu.initialize pays an
XLA compile."""

import numpy as np
import pytest

from deepspeed_tpu.perf.programs import (build_train_engine, build_v2_engine,
                                         train_batch_example)


# ------------------------------------------------------------ training side --
def test_train_engine_lowering_hooks_end_to_end():
    """One engine build covers: raw-jit exposure under an ACTIVE compile
    watch (the wrapped cache entry cannot lower; the hook's raw one can),
    lowering producing real StableHLO, and engine state staying untouched."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry.config import TelemetryConfig

    telemetry.shutdown()
    telemetry.state.registry = None
    try:
        telemetry.configure(TelemetryConfig(enabled=True))
        engine, cfg = build_train_engine()
        rng_before = engine._rng
        steps_before = engine.global_steps

        lowered = engine.lower_train_batch(batch=train_batch_example(cfg))
        assert lowered.as_text().startswith("module")

        # state must not advance: lowering is analysis, not a step
        assert engine.global_steps == steps_before
        assert (np.asarray(engine._rng) == np.asarray(rng_before)).all(), \
            "lowering must not consume training rng"

        wrapped = engine._compiled["train_batch"]
        raw = engine.lowerable_callables()["train_batch"]
        assert not hasattr(wrapped, "lower")  # the compile-watch wrapper
        assert hasattr(raw, "lower"), \
            "lowerable_callables must return raw jax.jit callables"
    finally:
        telemetry.shutdown()
        telemetry.state.registry = None


# ----------------------------------------------------------- inference side --
@pytest.fixture(scope="module")
def v2():
    from deepspeed_tpu.utils import groups
    engine, cfg = build_v2_engine()
    rng = np.random.default_rng(0)
    engine.put([0], [rng.integers(0, cfg.vocab_size, 24)])
    engine.decode_loop([0], [np.asarray([1], np.int32)], 4)
    yield engine, cfg
    groups.destroy_mesh()


def test_engine_v2_lowerable_callables_track_buckets(v2):
    engine, _ = v2
    fns = engine.lowerable_callables()
    assert len(fns["forward"]) == 1 and len(fns["decode_loop"]) == 1
    (bucket, fwd), = fns["forward"].items()
    assert len(bucket) == 3 and hasattr(fwd, "lower")
    (dkey, dec), = fns["decode_loop"].items()
    assert dkey[1] == 4 and dkey[2] is False and hasattr(dec, "lower")


def test_lower_forward_default_and_explicit_bucket(v2):
    engine, _ = v2
    small = engine.lower_forward()
    big = engine.lower_forward((64, 8, 8))
    assert small.as_text().startswith("module")
    # bigger token bucket => more embed rows => different (larger) program
    assert len(big.as_text()) != len(small.as_text())


def test_lowering_does_not_touch_compile_watch_bucket_telemetry(v2):
    """Analysis-only lowering must not feed the bucket-churn recompile
    indicator — only executed batches do (via RaggedBatchWrapper.finalize)."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry.config import TelemetryConfig

    engine, _ = v2
    telemetry.shutdown()
    telemetry.state.registry = None
    try:
        telemetry.configure(TelemetryConfig(enabled=True))
        watch = telemetry.compile_watch.get()
        assert watch is not None
        before = watch._bucket_switches.value
        buckets_before = dict(watch._recent_buckets)
        engine.lower_forward()
        engine.lower_forward((64, 8, 8))
        engine.lower_decode_loop(2)
        assert watch._bucket_switches.value == before
        assert dict(watch._recent_buckets) == buckets_before
    finally:
        telemetry.shutdown()
        telemetry.state.registry = None


def test_lower_decode_loop_matches_executed_program(v2):
    """The lowered decode program and the one decode_loop actually runs must
    be the same jit (same cache key, identical HLO)."""
    import jax
    import jax.numpy as jnp

    engine, _ = v2
    (dkey, raw), = engine.lowerable_callables()["decode_loop"].items()
    lowered = engine.lower_decode_loop(4, bucket=dkey[0])
    model = engine.model
    dev = model._synthetic_batch(dkey[0])
    again = raw.lower(model._params, model.state_manager.kv_cache.cache, dev)
    assert lowered.as_text() == again.as_text()
    assert model._program("decode_loop", dkey, run=False) is raw  # the cached jit, not a fresh one


@pytest.mark.parametrize("call", [
    lambda e: e.decode_loop([0], [np.asarray([1], np.int32)], 2, True, temperature=0.7),
    lambda e: e.dispatch_decode_loop([0], [np.asarray([1], np.int32)], 2, temperature=0.7),
    lambda e: e.lower_decode_loop(2, temperature=0.7),
    lambda e: e.model.lower_decode_loop(2, temperature=0.7),
], ids=["engine.decode_loop", "engine.dispatch_decode_loop", "engine.lower_decode_loop",
        "model.lower_decode_loop"])
def test_the_decode_loop_takes_no_temperature(v2, call):
    """The loop is greedy: a sampled request is drawn a step at a time
    (``put_draw``), and a temperature handed to the loop is an error, not a
    silent argmax."""
    engine, _ = v2
    seen = engine._state_manager.get_sequence(0).seen_tokens
    with pytest.raises(TypeError, match="temperature"):
        call(engine)
    assert engine._state_manager.get_sequence(0).seen_tokens == seen


def test_one_program_a_kind_and_key_built_once(v2):
    """The model's program cache: one jit a (kind, key), under the keys the
    lowering hooks have always shown, and a step that finds its program builds
    nothing."""
    from deepspeed_tpu.inference.v2.spec.tree import TokenTree
    engine, _ = v2
    model = engine.model
    engine.verify_tree([0], [TokenTree([1, 2, 3, 4], [-1, 0, 0, 1])], greedy=True)
    engine.compact_accepted(0, 4, [2])
    fns = engine.lowerable_callables()
    assert list(fns) == ["forward", "decode_loop", "verify", "compact", "block_forward",
                         "block_loop"]
    (bucket, forward), = fns["forward"].items()
    (loop_key, loop), = fns["decode_loop"].items()
    (verify_key, verify), = fns["verify"].items()
    (compact_key, compact), = fns["compact"].items()
    assert loop_key == (loop_key[0], 4, False) and len(loop_key[0]) == 3
    assert verify_key == ("verify", verify_key[1], True, True) and compact_key == ("compact", 2)
    ran = [at for at, (_, called) in model._programs.items() if called is not None]
    assert sorted(ran) == sorted((kind, key) for kind, keyed in fns.items() for key in keyed)
    # the same steps again, twice: every program found, none built, none traced
    # again (the forward's first call took the pool as it was made, every later
    # one as a program left it: two signatures, then no more). The counts are
    # held to what they were before: ``_compact_impl`` is a static function, and
    # jits of one function share their signatures across the engines of a process
    sizes = [[fn._cache_size() for fn in (forward, loop, verify, compact)]]
    for _ in range(2):
        engine.put([1], [np.arange(24, dtype=np.int32)])
        engine.flush(1)
        engine.decode_loop([0], [np.asarray([1], np.int32)], 4)
        engine.verify_tree([0], [TokenTree([1, 2, 3, 4], [-1, 0, 0, 1])], greedy=True)
        engine.compact_accepted(0, 4, [2])
        sizes.append([fn._cache_size() for fn in (forward, loop, verify, compact)])
    assert sizes[1] == sizes[2] and sizes[2][1:] == sizes[0][1:] and sizes[0][1:3] == [1, 1]
    again = engine.lowerable_callables()
    assert {kind: list(keyed) for kind, keyed in again.items()} == \
        {kind: list(keyed) for kind, keyed in fns.items()}
    assert all(again[kind][key] is fn for kind, keyed in fns.items() for key, fn in keyed.items())


def test_lowering_a_program_that_never_ran_leaves_the_compile_watch_alone(v2):
    """``lower_*`` of a bucket no step has taken: the jit is made (once) and
    lowers, the compile watch counts no cache entry, and
    ``lowerable_callables`` — the programs that RAN — does not list it."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry.config import TelemetryConfig

    engine, _ = v2
    telemetry.shutdown()
    telemetry.state.registry = None
    try:
        telemetry.configure(TelemetryConfig(enabled=True))
        watch = telemetry.compile_watch.get()
        ran = {kind: list(keyed) for kind, keyed in engine.lowerable_callables().items()}
        entries = {site: watch._metrics_for(site)[2].value
                   for site, *_ in engine.model._PROGRAM_KINDS.values()}
        lowered = [engine.lower_forward((32, 8, 8)), engine.lower_decode_loop(3, (16, 8, 8)),
                   engine.lower_verify((32, 8, 8), tree=True)]
        assert all(low.as_text().startswith("module") for low in lowered)
        assert {site: watch._metrics_for(site)[2].value
                for site, *_ in engine.model._PROGRAM_KINDS.values()} == entries
        assert {kind: list(keyed) for kind, keyed in engine.lowerable_callables().items()} == ran
        made = engine.model._program("forward", (32, 8, 8), run=False)
        engine.lower_forward((32, 8, 8))
        assert engine.model._program("forward", (32, 8, 8), run=False) is made
    finally:
        telemetry.shutdown()
        telemetry.state.registry = None
