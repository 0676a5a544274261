"""ServingScheduler hard paths: continuous admission, streaming, cancellation
mid-prefill, deadline expiry mid-decode, backpressure, KV-pressure eviction
with transparent restore, drain, and the engine.close() handshake.

Deterministic tests drive ``step()`` manually (``start=False``); integration
tests use the background thread.
"""

import threading
import time

import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.serving import (QueueFullError, RequestState, SchedulerStopped,
                                   ServingConfig, ServingScheduler)

MAX_STEPS = 400  # safety bound for manual stepping loops


def _run_until(sched, pred, max_steps=MAX_STEPS):
    for _ in range(max_steps):
        if pred():
            return
        sched.step()
    raise AssertionError(f"predicate not reached in {max_steps} steps")


def _reference_greedy(llama_setup, prompt, n):
    """Training-model greedy continuation — the ground truth the paged-KV
    serving path must reproduce exactly."""
    import jax.numpy as jnp
    _, model, params = llama_setup
    toks = list(prompt)
    out = []
    for _ in range(n):
        logits = np.asarray(model.apply({"params": params["model"]},
                                        jnp.asarray(toks, jnp.int32)[None])[0])
        out.append(int(np.argmax(logits[-1])))
        toks.append(out[-1])
    return out


# --------------------------------------------------------------- happy path --
def test_overlapping_requests_stream_per_request(llama_setup, make_engine):
    """Acceptance: a persistent scheduler accepts requests submitted at
    different times and streams tokens back per-request."""
    cfg, _, _ = llama_setup
    engine = make_engine()
    rng = np.random.default_rng(0)
    p1 = rng.integers(0, cfg.vocab_size, 13).tolist()
    p2 = rng.integers(0, cfg.vocab_size, 5).tolist()

    sched = ServingScheduler(engine, ServingConfig())
    try:
        r1 = sched.submit(p1, max_new_tokens=6)
        assert r1.stream.get(timeout=60) == r1.tokens[0]  # streamed live (real TTFT)
        r2 = sched.submit(p2, max_new_tokens=4)           # overlaps with r1 in flight
        out1, out2 = r1.result(timeout=60), r2.result(timeout=60)
    finally:
        sched.stop(drain=False)
    assert out1 == _reference_greedy(llama_setup, p1, 6)
    assert out2 == _reference_greedy(llama_setup, p2, 4)
    assert r1.ttft_s is not None and r1.ttft_s <= r1.e2e_s
    assert engine._state_manager.n_tracked_sequences == 0


# ------------------------------------------------------------- cancellation --
def test_cancel_mid_prefill_frees_kv_blocks(make_engine, llama_setup):
    cfg, _, _ = llama_setup
    engine = make_engine(max_ragged_batch_size=16)  # 40-token prompt = 3 chunks
    free0 = engine.free_blocks
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    req = sched.submit((np.arange(40) % cfg.vocab_size).tolist(), max_new_tokens=8)

    sched.step()  # admits + prefills exactly one 16-token chunk
    assert req.state is RequestState.PREFILL and req._fed == 16
    assert engine.free_blocks < free0  # KV blocks held mid-prefill

    req.cancel()
    sched.step()
    assert req.state is RequestState.CANCELLED
    assert engine.free_blocks == free0  # blocks verifiably returned to the pool
    assert engine._state_manager.n_tracked_sequences == 0
    assert req.result(timeout=1) == []  # cancelled before any token
    sched.stop(drain=False)


def test_deadline_expiry_during_decode_frees_kv(make_engine, llama_setup):
    cfg, _, _ = llama_setup
    engine = make_engine()
    free0 = engine.free_blocks
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    req = sched.submit((np.arange(9) % cfg.vocab_size).tolist(),
                       max_new_tokens=1000, deadline_s=3600.0)

    _run_until(sched, lambda: req.state is RequestState.DECODE and len(req.tokens) >= 2)
    produced = list(req.tokens)
    req.deadline = time.monotonic() - 1.0  # the clock runs out mid-decode
    sched.step()
    assert req.state is RequestState.TIMED_OUT
    assert engine.free_blocks == free0
    assert req.result(timeout=1) == produced  # partial output survives the cut
    assert sched.stats()["counters"]["timed_out"] == 1
    sched.stop(drain=False)


def test_queued_request_past_deadline_never_touches_engine(make_engine, llama_setup):
    cfg, _, _ = llama_setup
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    req = sched.submit([1, 2, 3], max_new_tokens=4, deadline_s=0.001)
    time.sleep(0.01)
    sched.step()
    assert req.state is RequestState.TIMED_OUT and req.uid is None
    sched.stop(drain=False)


# -------------------------------------------------------------- backpressure --
def test_backpressure_reject_mode(make_engine):
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig(queue_capacity=2), start=False)
    sched.submit([1], max_new_tokens=1)
    sched.submit([2], max_new_tokens=1)
    with pytest.raises(QueueFullError):
        sched.submit([3], max_new_tokens=1)
    assert sched.stats()["counters"]["rejected"] == 1
    sched.stop(drain=False)


def test_backpressure_block_mode_unblocks_on_admission(make_engine):
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig(queue_capacity=1,
                                                   backpressure="block"), start=False)
    sched.submit([1, 2], max_new_tokens=1)
    admitted = []

    def blocked_submit():
        admitted.append(sched.submit([3, 4], max_new_tokens=1))

    t = threading.Thread(target=blocked_submit)
    t.start()
    time.sleep(0.15)
    assert t.is_alive() and not admitted  # genuinely blocked on the full queue
    sched.step()  # admission drains the queue -> submitter wakes
    t.join(timeout=10)
    assert not t.is_alive() and len(admitted) == 1
    _run_until(sched, lambda: all(r.finished for r in admitted) and sched.n_active == 0)
    sched.stop(drain=False)


# -------------------------------------------------- KV pressure and eviction --
def test_kv_pressure_evicts_and_restores_transparently(llama_setup, make_engine):
    """Two 64-token sequences fill an 8-block pool exactly; decode beyond the
    block boundary forces evict/restore alternation — outputs must equal the
    unconstrained run and all blocks must return to the pool."""
    cfg, _, _ = llama_setup
    rng = np.random.default_rng(3)
    pa = rng.integers(0, cfg.vocab_size, 64).tolist()
    pb = rng.integers(0, cfg.vocab_size, 64).tolist()

    engine = make_engine(num_blocks=8, block_size=16, max_context=128)
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    ra = sched.submit(pa, max_new_tokens=3)
    rb = sched.submit(pb, max_new_tokens=3)
    _run_until(sched, lambda: ra.finished and rb.finished)
    assert ra.state is RequestState.DONE and rb.state is RequestState.DONE
    assert sched.stats()["counters"]["evictions"] >= 2  # both directions thrashed
    assert engine.free_blocks == 8
    sched.stop(drain=False)

    assert ra.result() == _reference_greedy(llama_setup, pa, 3)
    assert rb.result() == _reference_greedy(llama_setup, pb, 3)


def test_prefill_chunk_shrinks_under_kv_pressure(make_engine, llama_setup):
    """A prompt larger than the free pool's worth of one chunk still prefills
    (halving), it just takes more ticks."""
    cfg, _, _ = llama_setup
    engine = make_engine(num_blocks=4, block_size=16)  # 64-token pool
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    prompt = (np.arange(62) % cfg.vocab_size).tolist()
    req = sched.submit(prompt, max_new_tokens=2)
    _run_until(sched, lambda: req.finished)
    assert req.state is RequestState.DONE
    assert req.result() == _reference_greedy(llama_setup, prompt, 2)
    assert engine.free_blocks == 4
    sched.stop(drain=False)


def test_sampled_requests_are_reproducible_despite_cobatching(llama_setup, make_engine):
    """temperature>0 output depends only on (prompt, seed) — never on what
    else is in flight (each request owns a seeded host stream; the chunked
    device fast path is greedy-only)."""
    cfg, _, _ = llama_setup
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 9).tolist()
    other = rng.integers(0, cfg.vocab_size, 14).tolist()

    def run(with_companion):
        engine = make_engine()
        sched = ServingScheduler(engine, ServingConfig(decode_chunk=4), start=False)
        req = sched.submit(prompt, max_new_tokens=5, temperature=1.0, seed=42)
        if with_companion:
            sched.submit(other, max_new_tokens=5, temperature=0.7, seed=7)
        _run_until(sched, lambda: req.finished)
        out = req.result()
        sched.stop(drain=False)
        return out

    assert run(with_companion=False) == run(with_companion=True)


# ------------------------------------------------------- infeasible requests --
def test_permanently_infeasible_requests_fail_fast(make_engine):
    engine = make_engine(num_blocks=4, block_size=16)  # 64-token pool, 512 ctx
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    too_long_ctx = sched.submit([1] * 600, max_new_tokens=1)   # > max_context
    too_many_blocks = sched.submit([1] * 100, max_new_tokens=1)  # 7 blocks > 4
    sched.step()
    assert too_long_ctx.state is RequestState.FAILED
    assert "max_context" in too_long_ctx.error
    assert too_many_blocks.state is RequestState.FAILED
    assert "KV blocks" in too_many_blocks.error
    with pytest.raises(RuntimeError, match="max_context"):
        too_long_ctx.result(timeout=1)
    sched.stop(drain=False)


def test_generate_wrapper_joins_attached_scheduler(llama_setup, make_engine):
    """generate() on an engine that is already serving routes through the live
    scheduler (requests join the batch mix) and leaves it running."""
    from deepspeed_tpu.inference.v2.engine_factory import generate
    cfg, _, _ = llama_setup
    engine = make_engine()
    prompt = (np.arange(8) % cfg.vocab_size).tolist()
    sched = ServingScheduler(engine, ServingConfig())
    try:
        out = generate(engine, [prompt], max_new_tokens=4)
        assert out[0] == _reference_greedy(llama_setup, prompt, 4)
        assert engine.serving_scheduler is sched  # still attached and running
        assert sched.stats()["counters"]["completed"] == 1
    finally:
        sched.stop(drain=False)


def test_generate_wrapper_raises_on_infeasible_prompt(make_engine):
    from deepspeed_tpu.inference.v2.engine_factory import generate
    engine = make_engine(num_blocks=4, block_size=16)
    with pytest.raises(RuntimeError, match="KV blocks"):
        generate(engine, [[1] * 100], max_new_tokens=2)
    assert engine.serving_scheduler is None  # wrapper detached its scheduler


def test_generate_on_shared_scheduler_cancels_orphans_on_error(make_engine):
    """A submit failure mid-generate() (queue full on the shared scheduler)
    must cancel the already-submitted requests — nobody will consume them."""
    from deepspeed_tpu.inference.v2.engine_factory import generate
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig(queue_capacity=1), start=False)
    with pytest.raises(QueueFullError):
        generate(engine, [[1, 2], [3, 4], [5, 6]], max_new_tokens=4)
    sched.step()  # honors the cancel flags
    assert sched.n_active == 0 and sched.queue_depth == 0
    assert sched.stats()["counters"]["cancelled"] == 1
    sched.stop(drain=False)


def test_capacity_check_uses_pool_size_not_construction_free(make_engine, llama_setup):
    """A scheduler built while a warmup sequence holds blocks must still judge
    feasibility against the whole pool once that sequence is flushed."""
    cfg, _, _ = llama_setup
    engine = make_engine(num_blocks=8, block_size=16)
    engine.put([999], [(np.arange(90) % cfg.vocab_size)])  # warmup holds 6 of 8 blocks
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    engine.flush(999)
    req = sched.submit((np.arange(100) % cfg.vocab_size).tolist(), max_new_tokens=2)
    _run_until(sched, lambda: req.finished)
    assert req.state is RequestState.DONE  # 7 blocks: fits the 8-block pool
    sched.stop(drain=False)


def test_chunked_decode_never_streams_past_max_context(make_engine, llama_setup):
    """The decode-loop fast path always runs K steps; near max_context it must
    fall back to single steps so no token beyond the window reaches a client."""
    cfg, _, _ = llama_setup
    engine = make_engine(max_context=32)
    sched = ServingScheduler(engine, ServingConfig(decode_chunk=4), start=False)
    req = sched.submit((np.arange(29) % cfg.vocab_size).tolist(), max_new_tokens=100)
    _run_until(sched, lambda: req.finished)
    assert req.state is RequestState.DONE and req.finish_reason == "context"
    assert len(req.tokens) == 32 - 29 + 1  # up to the window edge, not one past
    sched.stop(drain=False)


def test_context_window_exhaustion_is_a_clean_length_cut(make_engine, llama_setup):
    cfg, _, _ = llama_setup
    engine = make_engine(max_context=32)
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    req = sched.submit((np.arange(30) % cfg.vocab_size).tolist(), max_new_tokens=100)
    _run_until(sched, lambda: req.finished)
    assert req.state is RequestState.DONE
    assert req.finish_reason == "context"
    assert len(req.tokens) >= 1
    assert engine._state_manager.n_tracked_sequences == 0
    sched.stop(drain=False)


# ------------------------------------------------------------ stop and drain --
def test_stop_drains_in_flight_requests(make_engine, llama_setup):
    cfg, _, _ = llama_setup
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig())
    reqs = [sched.submit((np.arange(5 + i) % cfg.vocab_size).tolist(), max_new_tokens=3)
            for i in range(3)]
    sched.stop(drain=True, timeout=120)
    assert all(r.state is RequestState.DONE for r in reqs)
    assert sched.stats()["counters"]["completed"] == 3
    assert engine._state_manager.n_tracked_sequences == 0
    with pytest.raises(SchedulerStopped):
        sched.submit([1], max_new_tokens=1)


def test_stop_without_drain_cancels_everything(make_engine):
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    reqs = [sched.submit([1, 2], max_new_tokens=5) for _ in range(2)]
    sched.stop(drain=False)
    assert all(r.state is RequestState.CANCELLED for r in reqs)
    assert all(r.stream.closed for r in reqs)


def test_one_scheduler_per_engine(make_engine):
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    with pytest.raises(RuntimeError, match="already has an attached"):
        ServingScheduler(engine, ServingConfig(), start=False)
    sched.stop(drain=False)
    # detached on stop: a new scheduler may attach
    ServingScheduler(engine, ServingConfig(), start=False).stop(drain=False)


def test_engine_close_stops_scheduler(llama_setup):
    """Satellite: close() must stop an attached scheduler and detach it."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)

    cfg, _, params = llama_setup
    mgr = DSStateManagerConfig(
        memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=16),
        max_context=256)
    engine = build_engine(params, cfg,
                          RaggedInferenceEngineConfig(state_manager=mgr, kv_block_size=16))
    sched = ServingScheduler(engine, ServingConfig())
    engine.close()
    assert engine.serving_scheduler is None and sched._stopped
    engine.close()  # idempotent


# ---------------------------------------------------- telemetry and heartbeat --
def test_serving_metrics_zero_cost_when_disabled(make_engine):
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    req = sched.submit([1, 2, 3], max_new_tokens=2)
    _run_until(sched, lambda: req.finished)
    sched.stop(drain=False)
    assert telemetry.get_registry().api_calls == 0  # not one registry touch


def test_serving_metrics_record_when_enabled(make_engine):
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    done = sched.submit([1, 2, 3, 4], max_new_tokens=3)
    _run_until(sched, lambda: done.finished)
    with pytest.raises(QueueFullError):
        # drop capacity so the reject counter fires too
        sched._config = sched._config.model_copy(update={"queue_capacity": 0})
        sched.submit([1], max_new_tokens=1)
    sched.stop(drain=False)

    snap = telemetry.get_registry().snapshot()
    assert snap["serving_completions_total"][0][1] == 1
    assert snap["serving_rejections_total"][0][1] == 1
    assert snap["serving_ttft_seconds_count"][0][1] == 1
    assert snap["serving_inter_token_seconds_count"][0][1] == 2  # 3 tokens -> 2 gaps
    assert snap["serving_e2e_latency_seconds_count"][0][1] == 1


def test_idle_heartbeat_runs_empty_batches(make_engine):
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig(heartbeat_enabled=True,
                                                   heartbeat_interval_s=0.0))
    try:
        deadline = time.monotonic() + 30
        while sched.stats()["counters"]["heartbeats"] < 2:
            assert time.monotonic() < deadline, "no heartbeat within 30s"
            time.sleep(0.01)
    finally:
        sched.stop(drain=False)


# ------------------------------------------------------- kill + readiness --
def test_kill_fails_everything_terminal_and_frees_kv(make_engine, llama_setup):
    """The abrupt-death disposition (fleet fault tolerance): every queued and
    in-flight request ends FAILED with the 'replica killed' marker, streams
    close, KV returns to the pool — what the router and the supervisor key
    their recovery on."""
    from deepspeed_tpu.serving.scheduler import KILLED_ERROR_PREFIX
    cfg, _, _ = llama_setup
    engine = make_engine()
    free0 = engine.free_blocks
    sched = ServingScheduler(engine, ServingConfig())
    active = sched.submit((np.arange(9) % cfg.vocab_size).tolist(),
                          max_new_tokens=500)
    deadline = time.monotonic() + 60
    while active.first_token_s is None:  # mid-decode, KV held
        assert time.monotonic() < deadline
        time.sleep(0.005)
    queued = sched.submit([1, 2, 3], max_new_tokens=5)
    sched.kill("injected fault")
    for req in (active, queued):
        assert req.state is RequestState.FAILED
        assert req.error.startswith(KILLED_ERROR_PREFIX)
        assert req.stream.closed
    assert engine._state_manager.n_tracked_sequences == 0
    assert engine.free_blocks == free0
    assert not sched.ready
    with pytest.raises(SchedulerStopped):
        sched.submit([1], max_new_tokens=1)
    sched.kill()            # idempotent
    sched.stop(drain=False)  # and stop() after kill() is a no-op


def test_ready_gates_on_the_loop_ticking(make_engine):
    engine = make_engine()
    sched = ServingScheduler(engine, ServingConfig())
    deadline = time.monotonic() + 30
    while not sched.ready:
        assert time.monotonic() < deadline, "scheduler never became ready"
        time.sleep(0.001)
    sched.stop(drain=False)
    assert not sched.ready  # a stopped scheduler is not dispatchable
    # a manually-driven scheduler (start=False) is ready by construction
    engine2 = make_engine()
    manual = ServingScheduler(engine2, ServingConfig(), start=False)
    assert manual.ready
    manual.stop(drain=False)
