"""A sliding-window model (Mistral) served past its window: attention on either
arm against the plain float32 reference, and the KV pool's rolling release.

Tiny sizes on the CPU: window 16 over 4-token blocks, contexts of 3-6 x the
window. The Pallas kernel runs in interpret mode (``use_paged_kernel=True``);
the XLA gather arm is what it is checked against, and both are held to
``benchmark/references/mistral.py`` (no cache, no kernel, one sequence)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import mistral as reference
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import MistralV2Model
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode, DSStateManagerConfig,
                                                               MemoryConfig)
from deepspeed_tpu.inference.v2.spec import TokenTree
from deepspeed_tpu.models import llama
from deepspeed_tpu.utils import groups

WINDOW, BLOCK = 16, 4
SIZES = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
             num_hidden_layers=2, rms_norm_eps=1e-5, vocab_size=256, max_position_embeddings=256,
             rope_theta=1e4, sliding_window=WINDOW, tie_word_embeddings=False)

# Everything is float32 here: weights, pool, kernel operands (HIGHEST on the
# MXU path), reference. What is left is the order of float32 sums, ~1e-6 of
# the logits' scale (~1): 1e-4 absolute is 100 x that. A bf16 pool where
# float32 is configured rounds every key and value by 2^-9 and moves the
# logits by ~1e-3: ``test_a_bf16_pool_would_fail_the_tolerance`` shows it does.
ATOL = 1e-4


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig(dtype=jnp.float32, remat=False, model_type="mistral", **SIZES)
    _, params = llama.init_params(cfg, rng=jax.random.PRNGKey(7))
    return cfg, params


def _engine(model, kernel, blocks=64, budget=64, seqs=8, max_context=128):
    groups.initialize_mesh(force=True)
    cfg, params = model
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=blocks),
                               max_context=max_context, max_ragged_batch_size=budget,
                               max_ragged_sequence_count=seqs)
    engine = build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=BLOCK, use_paged_kernel=kernel))
    assert isinstance(engine.model, MistralV2Model) and engine.model.attention_window == WINDOW
    return engine


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n).astype(np.int32)


def _reference_rows(model, ids, rows):
    return np.asarray(reference.forward_logits(model[1], SIZES, ids, rows=np.asarray(rows)))


def _feed(engine, uid, ids, chunks):
    """``put`` the ids in ``chunks``; the logits after each chunk."""
    out, at = [], 0
    for n in chunks:
        out.append(np.asarray(engine.put([uid], [ids[at:at + n]]))[0])
        at += n
    assert at == len(ids)
    return out


# 40 and 23 tokens are buckets of 64 (the tile grid; the first straddles the
# window's edge), 7 a bucket of 8 (the token grid), then single tokens
CHUNKS = [40, 23, 7, 1, 1, 1]


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "gather"])
def test_prefill_in_chunks_then_decode_matches_the_float32_reference(model, kernel):
    engine = _engine(model, kernel)
    assert engine.model.attention_arm(64) == ("paged_tiled" if kernel else "xla_gather")
    assert engine.model.attention_arm(8) == ("paged_token" if kernel else "xla_gather")
    ids = _ids(0, sum(CHUNKS) + 4)
    got = _feed(engine, 0, ids[:sum(CHUNKS)], CHUNKS)
    want = _reference_rows(model, ids, np.cumsum(CHUNKS) - 1)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=ATOL, rtol=0)
    assert engine.released_blocks > 0  # every comparison above crossed a release
    # the window is not a no-op at these lengths
    wide = np.asarray(reference.forward_logits(model[1], dict(SIZES, sliding_window=0), ids,
                                               rows=np.cumsum(CHUNKS) - 1))
    assert np.abs(wide[-1] - want[-1]).max() > 100 * ATOL

    # decode_loop: K greedy steps on the device, the pool's table fixed for all
    # of them; then one more put reads what the loop wrote
    fed = sum(CHUNKS)
    tokens = engine.decode_loop([0], [ids[fed:fed + 1]], 4)[0]
    history = np.concatenate([ids[:fed + 1], tokens[:3]])
    want = _reference_rows(model, history, np.arange(fed, fed + 4))
    for j in range(4):
        assert want[j].max() - want[j][tokens[j]] <= 2 * ATOL  # the reference's greedy token
    after = np.asarray(engine.put([0], [tokens[3:4]]))[0]
    np.testing.assert_allclose(
        after, _reference_rows(model, np.concatenate([history, tokens[3:]]), [fed + 4])[0],
        atol=ATOL, rtol=0)


def test_a_bf16_pool_would_fail_the_tolerance(model):
    engine = _engine(model, kernel=False)
    ids = _ids(0, 64)
    _feed(engine, 0, ids[:63], [40, 23])
    pool = engine._state_manager.kv_cache
    pool.set_cache(pool.cache.astype(jnp.bfloat16).astype(jnp.float32))
    got = np.asarray(engine.put([0], [ids[63:]]))[0]
    assert np.abs(got - _reference_rows(model, ids, [63])[0]).max() > 3 * ATOL


def _expected_live(seen):
    return -(-seen // BLOCK) - max(seen - WINDOW + 1, 0) // BLOCK


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "gather"])
def test_free_blocks_return_as_the_window_passes(model, kernel):
    engine = _engine(model, kernel, blocks=40, budget=32)
    capacity = engine.free_blocks
    ids = _ids(1, 104)
    seq = None
    seen = 0
    for n in [32, 32, 20, 1, 1, 1, 1, 1, 1, 1, 1, 8]:
        engine.put([0], [ids[seen:seen + n]])
        seen += n
        seq = engine._state_manager.get_sequence(0)
        assert seq.seen_tokens == seen
        assert seq.live_blocks == _expected_live(seen) == capacity - engine.free_blocks
        assert seq.cur_allocated_blocks == -(-seen // BLOCK)  # the table still spans the context
        assert list(seq.kv_blocks[:seq.released_blocks]) == [-1] * seq.released_blocks
        assert (seq.kv_blocks[seq.released_blocks:] >= 0).all()
        # however long the context: the window's blocks, one feed's, and the ends
        assert seq.live_blocks <= engine.model.max_live_blocks(seen) <= (WINDOW + 32 - 1) // BLOCK + 2
    assert engine.released_blocks == seq.released_blocks == (seen - WINDOW + 1) // BLOCK
    engine.decode_loop([0], [ids[seen:seen + 1]], 4)
    assert seq.live_blocks == _expected_live(seen + 4) == capacity - engine.free_blocks
    engine.flush(0)
    assert engine.free_blocks == capacity


def test_admission_counts_live_blocks_not_the_context(model):
    """A pool of 20 blocks (80 tokens) serves a 120-token context: a step needs
    the blocks of its own feed, and what the window passed is back by then."""
    engine = _engine(model, kernel=False, blocks=20, budget=32)
    ids = _ids(2, 121)
    got = _feed(engine, 0, ids[:120], [32, 32, 32, 24])
    np.testing.assert_allclose(got[-1], _reference_rows(model, ids[:120], [119])[0], atol=ATOL, rtol=0)
    seq = engine._state_manager.get_sequence(0)
    toks, blocks = engine.query(0, 8, engine.free_blocks)
    assert (toks, blocks) == (8, 2) and seq.live_blocks <= 13
    # max_context still bounds the positions
    assert engine.query(0, 9, engine.free_blocks)[0] == 8


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "gather"])
def test_a_released_block_overwritten_by_another_sequence_changes_nothing(model, kernel):
    ids_a, ids_b = _ids(3, 61), _ids(4, 40)
    alone = _engine(model, kernel, blocks=32)
    want = _feed(alone, 0, ids_a, [40, 20, 1])[-1]

    engine = _engine(model, kernel, blocks=32)
    engine.put([0], [ids_a[:40]])
    seq_a = engine._state_manager.get_sequence(0)
    ever = {int(b) for b in seq_a.live_kv_blocks}
    engine.put([0], [ids_a[40:60]])
    held = {int(b) for b in seq_a.live_kv_blocks}
    gone = sorted(ever - held)
    assert len(gone) == 4 and seq_a.released_blocks == 11
    pool = engine._state_manager.kv_cache
    before = np.asarray(pool.cache[:, :, gone])
    engine.put([1], [ids_b])  # takes what A gave back (the allocator hands it out first)
    assert not {int(b) for b in engine._state_manager.get_sequence(1).live_kv_blocks} & held
    after = np.asarray(pool.cache[:, :, gone])
    assert all(np.abs(after[:, :, i] - before[:, :, i]).max() > 0 for i in range(len(gone)))
    got = np.asarray(engine.put([0], [ids_a[60:]]))[0]
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "gather"])
def test_recompute_after_a_flush_feeds_the_whole_history_again(model, kernel):
    """Preempt and recompute past the window: the whole prompt and the tokens
    generated so far go in again (a layer's keys depend on positions a window
    further back per layer below it), in other chunks, releasing as it goes."""
    engine = _engine(model, kernel, blocks=40)
    capacity = engine.free_blocks
    ids = _ids(5, 80)
    _feed(engine, 0, ids[:70], [40, 30])
    engine.flush(0)
    assert engine.free_blocks == capacity
    got = _feed(engine, 0, ids, [64, 15, 1])[-1]
    np.testing.assert_allclose(got, _reference_rows(model, ids, [79])[0], atol=ATOL, rtol=0)
    # only the last window fed again is another model's answer
    other = _engine(model, kernel, blocks=40)
    short = _feed(other, 0, ids[80 - WINDOW:], [WINDOW])[-1]
    assert np.abs(short - got).max() > 100 * ATOL


def test_offload_and_restore_keep_the_holes(model):
    """The scheduler's relief under KV pressure: a sequence that has released
    blocks goes to the host with the blocks it holds and comes back to the
    same places in its table."""
    engine = _engine(model, kernel=False, blocks=32)
    ids = _ids(6, 71)
    want = _feed(_engine(model, kernel=False, blocks=32), 0, ids, [40, 30, 1])[-1]
    _feed(engine, 0, ids[:70], [40, 30])
    seq = engine._state_manager.get_sequence(0)
    released, live = seq.released_blocks, seq.live_blocks
    assert released > 0
    engine.offload_sequence(0)
    assert engine.free_blocks == 32 and engine._restore_cost(0, seq) == live
    _feed(engine, 1, _ids(7, 40), [40])
    got = np.asarray(engine.put([0], [ids[70:]]))[0]
    assert seq.released_blocks >= released and (seq.kv_blocks[seq.released_blocks:] >= 0).all()
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)


def test_what_needs_the_whole_block_table_refuses(model):
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler
    engine = _engine(model, kernel=False)
    for feature in ("prefix_cache", "kv_tiers"):
        with pytest.raises(ValueError, match="sliding-window model"):
            ServingScheduler(engine, ServingConfig(**{feature: {"enabled": True}}), start=False)
    scheduler = ServingScheduler(engine, ServingConfig(), start=False)
    try:
        for flag in ("handoff", "park"):
            with pytest.raises(ValueError, match="sliding-window model"):
                scheduler.submit(_ids(8, 8), max_new_tokens=2, **{flag: True})
    finally:
        scheduler.stop(drain=False)

    # inside the window a sequence still exports; past it, it says why not
    engine.put([0], [_ids(9, 12)])
    assert isinstance(engine.export_sequence(0), bytes)
    engine.put([0], [_ids(10, 30)])
    with pytest.raises(ValueError, match="released .* KV blocks"):
        engine.export_sequence(0)
    # a rollback that would look behind what was released
    with pytest.raises(ValueError, match="already released"):
        engine.rollback(0, 20)
    engine.rollback(0, 1)


def test_a_verify_step_releases_nothing_until_its_rollback_is_settled(model):
    engine = _engine(model, kernel=False)
    ids = _ids(11, 60)
    engine.put([0], [ids[:40]])
    seq = engine._state_manager.get_sequence(0)
    released = seq.released_blocks
    engine.verify_tree([0], [TokenTree.chain(ids[40:48])], greedy=True)
    assert seq.seen_tokens == 48 and seq.released_blocks == released
    # truncating through compact_accepted keeps rollback's guard
    with pytest.raises(ValueError, match="already released"):
        engine.compact_accepted(0, 30, [])
    assert engine.compact_accepted(0, 8, [1, 2]) == 5
    got = np.asarray(engine.put([0], [ids[43:44]]))[0]
    np.testing.assert_allclose(got, _reference_rows(model, ids[:44], [43])[0], atol=ATOL, rtol=0)
    assert seq.released_blocks == (44 - WINDOW + 1) // BLOCK


def test_on_a_tpu_a_window_model_takes_the_kernel_like_any_other(model, monkeypatch):
    from deepspeed_tpu.inference.v2.modules import heuristics
    engine = _engine(model, kernel=None)
    assert engine.model.attention_arm(256) == "xla_gather"  # the CPU's arm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert heuristics.attention_implementation(engine.model, engine._config, 256) == "paged_tiled"
    assert heuristics.attention_implementation(engine.model, engine._config, 32) == "paged_token"


def test_the_scheduler_serves_a_prompt_longer_than_the_pool(model):
    """Through ``ServingScheduler`` (chunked prefill, ``decode_loop`` chunks):
    the tokens are the plain engine loop's, the pool is whole again at the
    end, and the ``prepare`` spans account for every released block."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler

    prompt = _ids(12, 100)
    plain = _engine(model, kernel=False, blocks=24, budget=32)
    first = int(np.argmax(_feed(plain, 0, prompt, [32, 32, 32, 4])[-1]))
    want = [first] + [int(t) for t in plain.decode_loop([0], [np.asarray([first])], 11)[0]]

    session = telemetry.configure({"enabled": True, "compile_watch": False})
    try:
        engine = _engine(model, kernel=False, blocks=24, budget=32)  # 96 tokens of pool
        scheduler = ServingScheduler(engine, ServingConfig(decode_chunk=4))
        try:
            request = scheduler.submit(prompt, max_new_tokens=12)
            assert request.result(timeout=120) == want
        finally:
            scheduler.stop(drain=False)
        assert engine.free_blocks == 24
        prepares = [s for s in session.spans.export_since(0)["spans"]
                    if s["name"] == "prepare" and s.get("cat") == "inference"]
        puts = [s for s in session.spans.export_since(0)["spans"]
                if s["name"] == "put" and s.get("cat") == "inference"]
        assert puts and all(s["args"]["attention"] == "xla_gather" for s in puts)
        assert prepares[0]["args"]["released_blocks"] == 0
        # the release after the last step is reported by no later prepare
        reported = sum(s["args"]["released_blocks"] for s in prepares)
        assert 0 < reported <= engine.released_blocks <= reported + 2
        assert engine.released_blocks >= (100 - WINDOW + 1) // BLOCK
    finally:
        telemetry.shutdown()
        telemetry.state.registry = None
