"""Speculative-decoding mechanism units: the model-free drafter (n-gram
self-lookup + prefix-trie continuation mining), the engine's multi-token
verify feed (per-position logits, exact parity with sequential single-step
decode), and the write-then-truncate KV rollback.

The serving-layer integration (adaptive k, brownout, handoff, CPU perf
gates) lives in tests/unit/serving/test_speculative.py.
"""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.spec import PromptLookupDrafter, TokenTree


# ----------------------------------------------------------------- drafter --
def test_self_lookup_longest_ngram_most_recent_match():
    d = PromptLookupDrafter(min_ngram=1, max_ngram=3)
    # suffix [1,2,3] occurred at position 0; continuation follows it
    assert d.draft([1, 2, 3, 4, 5, 1, 2, 3], 4).tolist() == [4, 5, 1, 2]
    # two earlier [1,2] occurrences: the most recent one wins
    assert d.draft([1, 2, 9, 1, 2, 7, 1, 2], 3).tolist() == [7, 1, 2]


def test_self_lookup_no_pattern_returns_empty():
    d = PromptLookupDrafter()
    assert d.draft([7, 8, 9, 10], 4).size == 0
    assert d.draft([5], 4).size == 0          # too short for any n-gram
    assert d.draft([1, 2, 3, 1, 2, 3], 0).size == 0  # k=0 never proposes


def test_self_lookup_caps_at_k():
    d = PromptLookupDrafter()
    out = d.draft([1, 2, 3, 4, 5, 6, 1, 2], 2)
    assert out.tolist() == [3, 4]


def test_drafter_validates_ngram_bounds():
    with pytest.raises(ValueError):
        PromptLookupDrafter(min_ngram=3, max_ngram=2)
    with pytest.raises(ValueError):
        PromptLookupDrafter(min_ngram=0)


# -------------------------------------------------------------- trie mining --
@pytest.fixture
def trie():
    from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   KVCacheConfig,
                                                                   MemoryConfig)
    from deepspeed_tpu.inference.v2.ragged.prefix_cache import PrefixCache
    kv = BlockedKVCache(
        KVCacheConfig(block_size=4, cache_shape=(1, 1, 4), cache_dtype="float32"),
        MemoryConfig(mode=AllocationMode.ALLOCATE, size=32))
    return PrefixCache(kv), kv


def test_trie_lookup_continuation_mid_and_at_block_boundary(trie):
    pc, kv = trie
    hist = np.arange(100, 114, dtype=np.int32)  # 3 full blocks of 4 committed
    pc.publish(hist, kv.reserve(3), committed_tokens=12)
    # mid-block tail: [100..105] extends the indexed path
    assert pc.lookup_continuation(np.arange(100, 106), 5).tolist() == \
        [106, 107, 108, 109, 110]
    # exactly at a block boundary
    assert pc.lookup_continuation(np.arange(100, 108), 3).tolist() == [108, 109, 110]
    # past the committed region: nothing to mine
    assert pc.lookup_continuation(np.arange(100, 112), 3).size == 0


def test_trie_lookup_divergent_history_is_empty(trie):
    pc, kv = trie
    pc.publish(np.arange(100, 112, dtype=np.int32), kv.reserve(3),
               committed_tokens=12)
    assert pc.lookup_continuation([100, 101, 102, 103, 999], 4).size == 0
    assert pc.lookup_continuation([55, 56, 57, 58, 59], 4).size == 0


def test_trie_lookup_takes_no_references_and_leaves_lru_untouched(trie):
    pc, kv = trie
    blocks = kv.reserve(2)
    pc.publish(np.arange(8, dtype=np.int32), blocks, committed_tokens=8)
    touches = {n.digest: n.last_touch for n in pc._by_digest.values()}
    refs = {int(b): kv.ref_count(int(b)) for b in blocks}
    assert pc.lookup_continuation(np.arange(5), 3).tolist() == [5, 6, 7]
    assert {n.digest: n.last_touch for n in pc._by_digest.values()} == touches
    assert {int(b): kv.ref_count(int(b)) for b in blocks} == refs


def test_drafter_prefers_trie_over_self_lookup(trie):
    pc, kv = trie
    # the history's own repetition would propose 2 again; the trie knows the
    # published continuation is 50
    hist = np.asarray([1, 2, 3, 1, 2, 3, 1, 2], np.int32)
    pc.publish(np.asarray([1, 2, 3, 1, 2, 3, 1, 2, 50, 60, 70, 80], np.int32),
               kv.reserve(3), committed_tokens=12)
    d = PromptLookupDrafter(prefix_cache=pc)
    assert d.draft(hist, 2).tolist() == [50, 60]


# -------------------------------------------------- descriptor rollback unit --
def test_sequence_descriptor_rollback_bounds():
    from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import \
        DSSequenceDescriptor
    seq = DSSequenceDescriptor(0)
    seq.pre_forward(5)
    with pytest.raises(RuntimeError):  # in-flight tokens: not rollbackable
        seq.rollback(1)
    seq.post_forward()
    seq.rollback(2)
    assert seq.seen_tokens == 3
    with pytest.raises(ValueError):
        seq.rollback(4)  # more than committed
    with pytest.raises(ValueError):
        seq.rollback(-1)
    seq.rollback(0)
    assert seq.seen_tokens == 3


# ------------------------------------------------------------- engine verify --
@pytest.fixture(scope="module")
def spec_engine_setup():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    model = LlamaModel(cfg)
    params = {"model": model.init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))["params"]}

    def make():
        mgr = DSStateManagerConfig(
            memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=64),
            max_context=512)
        return build_engine(params, cfg,
                            RaggedInferenceEngineConfig(state_manager=mgr,
                                                        kv_block_size=16))
    return cfg, make


def _greedy_reference(engine, prompt, n):
    logits = engine.put([0], [prompt])
    out = [int(np.argmax(np.asarray(logits)[0]))]
    while len(out) < n:
        logits = engine.put([0], [[out[-1]]])
        out.append(int(np.argmax(np.asarray(logits)[0])))
    return out


def test_verify_fully_accepted_feed_matches_sequential_decode(spec_engine_setup):
    """One verify pass over [x0, d1..dk] with oracle drafts emits exactly the
    sequential greedy continuation — k+1 tokens per dispatch — and
    seen_tokens lands where sequential decode would put it."""
    cfg, make = spec_engine_setup
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    ref = _greedy_reference(make(), prompt, 9)

    engine = make()
    logits = engine.put([0], [prompt])
    out = [int(np.argmax(np.asarray(logits)[0]))]
    seq = engine._state_manager.get_sequence(0)
    k = 3
    while len(out) < 9:
        drafts = ref[len(out):len(out) + k]
        feed = np.asarray([out[-1]] + drafts, np.int32)
        seen0 = seq.seen_tokens
        rows = engine.verify_tree([0], [TokenTree.chain(feed)])[0]["rows"]
        assert rows.shape == (feed.size, cfg.vocab_size)
        emitted = [int(np.argmax(rows[j])) for j in range(feed.size)]
        # oracle drafts: every position verifies, k+1 tokens emitted
        engine.rollback(0, 0)
        assert seq.seen_tokens == seen0 + feed.size
        out.extend(emitted)
    assert out[:9] == ref


def test_verify_rejection_rolls_back_and_continues_exactly(spec_engine_setup):
    cfg, make = spec_engine_setup
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    ref = _greedy_reference(make(), prompt, 3)

    engine = make()
    logits = engine.put([0], [prompt])
    t1 = int(np.argmax(np.asarray(logits)[0]))
    assert t1 == ref[0]
    # garbage drafts: only the next-input position survives
    bad = np.asarray([t1, (ref[1] + 1) % cfg.vocab_size, 7, 9], np.int32)
    rows = engine.verify_tree([0], [TokenTree.chain(bad)])[0]["rows"]
    emitted = int(np.argmax(rows[0]))
    engine.rollback(0, bad.size - 1)  # truncate the 3 rejected positions
    seq = engine._state_manager.get_sequence(0)
    assert seq.seen_tokens == prompt.size + 1
    assert emitted == ref[1]
    # single-step decode over the rolled-back positions stays bit-identical:
    # the stale KV is overwritten by the correct token's write
    logits = engine.put([0], [[emitted]])
    assert int(np.argmax(np.asarray(logits)[0])) == ref[2]


def test_verify_batches_multiple_sequences_with_ragged_widths(spec_engine_setup):
    cfg, make = spec_engine_setup
    rng = np.random.default_rng(1)
    engine = make()
    p0 = rng.integers(0, cfg.vocab_size, 20)
    p1 = rng.integers(0, cfg.vocab_size, 12)
    logits = np.asarray(engine.put([0, 1], [p0, p1]))
    n0, n1 = (int(np.argmax(logits[0])), int(np.argmax(logits[1])))
    rows = [out["rows"] for out in engine.verify_tree(
        [0, 1], [TokenTree.chain([n0, 1, 2]), TokenTree.chain([n1])])]
    assert rows[0].shape == (3, cfg.vocab_size)
    assert rows[1].shape == (1, cfg.vocab_size)
    s0 = engine._state_manager.get_sequence(0)
    s1 = engine._state_manager.get_sequence(1)
    assert s0.seen_tokens == p0.size + 3
    assert s1.seen_tokens == p1.size + 1


def test_decode_loop_multi_token_feed_contract(spec_engine_setup):
    """decode_loop takes ONE next-input token per sequence: a feed of several
    is a verify step (``verify_tree``), and says so."""
    cfg, make = spec_engine_setup
    engine = make()
    engine.put([0], [np.random.default_rng(0).integers(0, cfg.vocab_size, 24)])
    seq = engine._state_manager.get_sequence(0)
    seen = seq.seen_tokens
    for feed, steps in (([1, 2], 1), ([1, 2], 2), ([], 1)):
        with pytest.raises(ValueError, match="exactly one next-input token"):
            engine.decode_loop([0], [np.asarray(feed, np.int32)], steps)
    assert seq.seen_tokens == seen  # refused before anything was fed
    engine.flush(0)


def test_engine_rollback_validates_uid(spec_engine_setup):
    _, make = spec_engine_setup
    engine = make()
    with pytest.raises(ValueError, match="unknown uid"):
        engine.rollback(404, 1)
    engine.rollback(404, 0)  # 0 is a no-op even for unknown uids
