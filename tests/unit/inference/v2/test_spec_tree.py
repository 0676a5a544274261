"""Token-tree verification units: the TokenTree container, the one verify
step (chains through the causal program, a branching batch under the
tree-attention mask — ancestor-only visibility), ragged multi-sequence tree
packing, the device-argmax greedy verify path, and the accepted-path KV
compaction (re-pack + rollback with exact pool balance).

The serving-layer integration (learned drafter, auto arbitration, bitwise
spec-on/off identity through the scheduler) lives in
tests/unit/serving/test_speculative.py and test_spec_learned.py.
"""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.spec import TokenTree


# --------------------------------------------------------------- container --
def test_token_tree_chain_and_validation():
    t = TokenTree.chain([5, 6, 7])
    assert t.size == 3 and t.is_chain and t.max_depth == 2
    assert t.parents.tolist() == [-1, 0, 1]
    assert t.depths.tolist() == [0, 1, 2]

    # branching: root -> {a, b}, a -> c
    t = TokenTree([1, 2, 3, 4], [-1, 0, 0, 1])
    assert not t.is_chain and t.max_depth == 2
    assert t.children(0) == [1, 2] and t.children(1) == [3]
    assert t.child_with_token(0, 3) == 2
    assert t.child_with_token(0, 9) is None

    with pytest.raises(ValueError, match="root"):
        TokenTree([1, 2], [0, 0])
    with pytest.raises(ValueError, match="topological"):
        TokenTree([1, 2, 3], [-1, 2, 0])
    with pytest.raises(ValueError):
        TokenTree([], [])
    with pytest.raises(ValueError, match="depths"):
        TokenTree([1, 2], [-1, 0], depths=[0, 2])


# ----------------------------------------------------------------- fixture --
@pytest.fixture(scope="module")
def tree_engine_setup():
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

    def make(blocks=64):
        mgr = DSStateManagerConfig(
            memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=blocks),
            max_context=512)
        return build_engine(params, cfg,
                            RaggedInferenceEngineConfig(state_manager=mgr,
                                                        kv_block_size=16))
    return cfg, make


def _prefill_argmax(engine, prompt):
    logits = engine.put([0], [prompt])
    return int(np.argmax(np.asarray(logits)[0]))


# The test model is float32 end to end (weights, KV pool, logits up to ~4).
# Two DIFFERENT jitted programs — ``put`` and a verify program, the causal
# verify and the tree one, one bucket and the next — agree to the order of
# their float32 sums, not bitwise: XLA picks fusions and reduction orders per
# program. The largest difference these tests read here is 2.5e-6 (about five
# float32 steps at that scale); 2e-5 is 8 x that, and a wrong mask, position
# or KV slot moves a logit by ~1e-1.
ATOL = 2e-5


def _assert_same_scores(got, want):
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    np.testing.assert_array_equal(np.argmax(got, -1), np.argmax(want, -1))


def _verify_keys(engine):
    return sorted(engine.lowerable_callables()["verify"])


# ------------------------------------------------- one step, two programs --
def test_chain_through_verify_tree_scores_as_token_by_token_put(tree_engine_setup):
    """A chain through verify_tree scores every fed position as ``put`` does
    when fed the same tokens one at a time, and runs the CAUSAL program: no
    tree metadata is packed for a batch of chains."""
    cfg, make = tree_engine_setup
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)

    ref = make()
    t1 = _prefill_argmax(ref, prompt)
    feed = np.asarray([t1, 3, 9, 4], np.int32)
    ref_rows = np.stack([np.asarray(ref.put([0], [[t]]))[0] for t in feed])

    eng = make()
    assert _prefill_argmax(eng, prompt) == t1
    out = eng.verify_tree([0], [TokenTree.chain(feed)])[0]
    assert out["rows"].shape == (4, cfg.vocab_size)
    assert out["hidden"].shape == (4, cfg.hidden_size)
    _assert_same_scores(out["rows"], ref_rows)
    assert eng._state_manager.get_sequence(0).seen_tokens == prompt.size + 4
    assert _verify_keys(eng) == [("verify", (8, 8, 4), False, False)]
    # the hidden states are fetched for a caller that reads them
    assert eng.verify_tree([0], [TokenTree.chain([5])], hidden=False)[0]["hidden"] is None


def test_the_trees_shape_picks_the_program(tree_engine_setup):
    """Chains (a root alone is one) take the causal program, whose attention
    is ``put``'s; one branching tree puts its whole batch under the ancestor
    mask. The key names both facts, so the two never share a program."""
    cfg, make = tree_engine_setup
    eng = make()
    t1 = _prefill_argmax(eng, np.random.default_rng(8).integers(0, cfg.vocab_size, 12))
    eng.verify_tree([0], [TokenTree.chain([t1])], greedy=True)
    eng.verify_tree([0], [TokenTree.chain([1, 2, 3])], greedy=True)
    assert _verify_keys(eng) == [("verify", (8, 8, 4), False, True)]
    eng.verify_tree([0], [TokenTree([4, 5, 6], [-1, 0, 0])], greedy=True)
    assert _verify_keys(eng) == [("verify", (8, 8, 4), False, True),
                                 ("verify", (8, 8, 4), True, True)]
    causal, tree = (eng.lower_verify(tree=t, greedy=True).as_text() for t in (False, True))
    assert "while" not in causal and "while" in tree  # the ancestor walk is the tree's alone


def test_tree_greedy_ids_match_logits_argmax(tree_engine_setup):
    cfg, make = tree_engine_setup
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, 16)
    tree = TokenTree([0, 1, 2, 3, 4], [-1, 0, 0, 1, 2])

    e1 = make()
    t1 = _prefill_argmax(e1, prompt)
    tree.tokens[0] = t1
    rows = e1.verify_tree([0], [tree])[0]["rows"]

    e2 = make()
    assert _prefill_argmax(e2, prompt) == t1
    out = e2.verify_tree([0], [tree], greedy=True)[0]
    assert out["rows"] is None
    assert out["ids"].dtype == np.int32 and out["ids"].shape == (5,)
    np.testing.assert_array_equal(out["ids"], np.argmax(rows, axis=-1))


# --------------------------------------------------- ancestor-only masking --
def test_sibling_branches_are_mutually_invisible(tree_engine_setup):
    """Each branch of a tree scores as if it were fed ALONE as a chain (the
    causal program): node logits depend on the ancestor path only, never on
    sibling branches sharing the ragged feed."""
    cfg, make = tree_engine_setup
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, 20)

    # root -> {a-branch: 7 -> 11, b-branch: 3 -> 5}
    eng = make()
    t1 = _prefill_argmax(eng, prompt)
    tree = TokenTree([t1, 7, 11, 3, 5], [-1, 0, 1, 0, 3])
    rows = eng.verify_tree([0], [tree])[0]["rows"]

    for chain_nodes in ([0, 1, 2], [0, 3, 4]):
        ref = make()
        assert _prefill_argmax(ref, prompt) == t1
        chain = TokenTree.chain(tree.tokens[chain_nodes])
        ref_rows = ref.verify_tree([0], [chain])[0]["rows"]
        _assert_same_scores(rows[chain_nodes], ref_rows)


def test_ragged_multi_sequence_tree_packing(tree_engine_setup):
    """One dispatch carries a wide tree, a narrow tree, and a chain across
    three sequences; every sequence scores as if verified alone (the chain
    alone takes the causal program, the trees alone a smaller bucket)."""
    cfg, make = tree_engine_setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (20, 12, 9)]

    eng = make()
    logits = np.asarray(eng.put([0, 1, 2], prompts))
    nxt = [int(np.argmax(logits[i])) for i in range(3)]
    trees = [TokenTree([nxt[0], 7, 11, 3, 5], [-1, 0, 1, 0, 3]),
             TokenTree([nxt[1], 2, 4], [-1, 0, 0]),
             TokenTree.chain([nxt[2], 8])]
    outs = eng.verify_tree([0, 1, 2], trees)

    for i, (prompt, tree) in enumerate(zip(prompts, trees)):
        solo = make()
        lg = solo.put([0], [prompt])
        assert int(np.argmax(np.asarray(lg)[0])) == nxt[i]
        ref = solo.verify_tree([0], [tree])[0]
        _assert_same_scores(outs[i]["rows"], ref["rows"])
        np.testing.assert_allclose(outs[i]["hidden"], ref["hidden"], atol=ATOL, rtol=0)
        assert eng._state_manager.get_sequence(i).seen_tokens == \
            prompt.size + tree.size


# ------------------------------------------------------------- compaction --
def test_compact_accepted_repacks_branch_and_decode_continues_exactly(tree_engine_setup):
    """Accept the SECOND branch of a tree (nodes at non-contiguous slots):
    compact_accepted must gather the accepted KV to contiguous slots and
    truncate the rest, so subsequent decode continues as a run that fed the
    accepted tokens linearly does."""
    cfg, make = tree_engine_setup
    prompt = np.random.default_rng(4).integers(0, cfg.vocab_size, 24)

    # reference: feed [t1, a, b] linearly, then greedy-decode 4 tokens
    ref = make()
    t1 = _prefill_argmax(ref, prompt)
    a, b = 3, 5
    ref_rows = ref.verify_tree([0], [TokenTree.chain([t1, a, b])])[0]["rows"]
    ref_out, ref_logits = [int(np.argmax(ref_rows[-1]))], []
    for _ in range(3):
        ref_logits.append(np.asarray(ref.put([0], [[ref_out[-1]]]))[0])
        ref_out.append(int(np.argmax(ref_logits[-1])))

    # tree run: the accepted path 0 -> 3 -> 4 sits AFTER a rejected branch
    eng = make()
    assert _prefill_argmax(eng, prompt) == t1
    tree = TokenTree([t1, 7, 11, a, b], [-1, 0, 1, 0, 3])
    out = eng.verify_tree([0], [tree])[0]
    _assert_same_scores(out["rows"][[0, 3, 4]], ref_rows)
    rejected = eng.compact_accepted(0, tree.size, [3, 4])
    assert rejected == 2
    seq = eng._state_manager.get_sequence(0)
    assert seq.seen_tokens == prompt.size + 3  # t1, a, b committed
    tree_out, tree_logits = [int(np.argmax(out["rows"][4]))], []
    for _ in range(3):
        tree_logits.append(np.asarray(eng.put([0], [[tree_out[-1]]]))[0])
        tree_out.append(int(np.argmax(tree_logits[-1])))
    assert tree_out == ref_out
    # the same ``put`` program over KV the compaction moved: what is left is
    # the tree program's rounding of the moved keys and values
    np.testing.assert_allclose(tree_logits, ref_logits, atol=ATOL, rtol=0)


def test_compact_accepted_chain_path_skips_device_copy(tree_engine_setup):
    """A chain-shaped acceptance (path[j] == j+1) needs no KV movement: no
    compact program is compiled, only the rollback runs."""
    cfg, make = tree_engine_setup
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, 16)
    eng = make()
    t1 = _prefill_argmax(eng, prompt)
    tree = TokenTree([t1, 1, 2, 3], [-1, 0, 1, 2])
    eng.verify_tree([0], [tree])
    before = list(eng.lowerable_callables()["compact"])
    assert eng.compact_accepted(0, tree.size, [1, 2]) == 1
    assert list(eng.lowerable_callables()["compact"]) == before  # contiguous path: pure rollback
    assert eng._state_manager.get_sequence(0).seen_tokens == prompt.size + 3


def test_compact_accepted_validates_path(tree_engine_setup):
    cfg, make = tree_engine_setup
    eng = make()
    prompt = np.random.default_rng(6).integers(0, cfg.vocab_size, 8)
    t1 = _prefill_argmax(eng, prompt)
    eng.verify_tree([0], [TokenTree([t1, 1, 2], [-1, 0, 0])])
    with pytest.raises(ValueError, match="ascending"):
        eng.compact_accepted(0, 3, [2, 1])
    with pytest.raises(ValueError, match="ascending"):
        eng.compact_accepted(0, 3, [0])  # root is not part of the path
    with pytest.raises(ValueError, match="unknown uid"):
        eng.compact_accepted(404, 3, [])
    assert eng.compact_accepted(0, 3, []) == 2  # nothing accepted


def test_tree_rollback_soak_pool_balance(tree_engine_setup):
    """PR-10-style soak: interleaved tree verifies, compactions and flushes
    over several sequences never leak KV blocks — the pool balances exactly
    once every sequence is flushed."""
    cfg, make = tree_engine_setup
    eng = make()
    kv = eng._state_manager.kv_cache
    total = kv.num_blocks
    rng = np.random.default_rng(7)
    for round_ in range(6):
        uids = [10 + round_ * 3 + i for i in range(3)]
        prompts = [rng.integers(0, cfg.vocab_size, int(rng.integers(5, 40)))
                   for _ in uids]
        logits = np.asarray(eng.put(uids, prompts))
        trees = []
        for i in range(len(uids)):
            t1 = int(np.argmax(logits[i]))
            trees.append(TokenTree([t1, 7, 11, 3, 5], [-1, 0, 1, 0, 3]))
        eng.verify_tree(uids, trees)
        for i, uid in enumerate(uids):
            n_accept = int(rng.integers(0, 3))
            path = [[], [3], [3, 4]][n_accept]
            eng.compact_accepted(uid, trees[i].size, path)
            seq = eng._state_manager.get_sequence(uid)
            assert seq.seen_tokens == prompts[i].size + 1 + n_accept
        for uid in uids:
            eng.flush(uid)
        assert eng.free_blocks == total
    assert eng._state_manager.n_tracked_sequences == 0


def test_ragged_wrapper_rejects_malformed_tree_metadata(tree_engine_setup):
    from deepspeed_tpu.inference.v2.ragged.manager_configs import \
        DSStateManagerConfig
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
        RaggedBatchWrapper
    from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import \
        DSSequenceDescriptor
    w = RaggedBatchWrapper(DSStateManagerConfig())
    seq = DSSequenceDescriptor(0)
    with pytest.raises(ValueError, match="align"):
        w.insert_sequence(seq, [1, 2, 3], tree=([-1, 0], [0, 1]))
    with pytest.raises(ValueError, match="root"):
        w.insert_sequence(seq, [1, 2], tree=([0, 0], [1, 1]))
    with pytest.raises(ValueError, match="topological"):
        w.insert_sequence(seq, [1, 2, 3], tree=([-1, 2, 0], [0, 1, 1]))
    # a valid tree packs tree_meta into the device batch
    w.insert_sequence(seq, [1, 2, 3], tree=([-1, 0, 0], [0, 1, 1]))
    batch = w.finalize()
    assert batch["tree_meta"].shape[0] == 2
    assert batch["tree_meta"][0, :3].tolist() == [-1, 0, 0]
    assert batch["tree_meta"][1, :3].tolist() == [0, 1, 1]
