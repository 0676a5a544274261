"""FastGen engine end-to-end tests.

Reference coverage model: ``tests/unit/inference/v2/`` (ragged machinery +
module-level + model tests). The acceptance test from VERDICT item 3: prefill +
decode mixed-length sequences and match the training model's logits.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine, generate
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode, DSStateManagerConfig,
                                                               MemoryConfig)
from deepspeed_tpu.inference.v2.scheduling_utils import SchedulingError, SchedulingResult
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel


def _f32_tiny(**kw):
    return LlamaConfig.tiny(dtype=jnp.float32, **kw)


def _engine_config(num_blocks=64, block_size=16, **kw):
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=num_blocks),
                               max_context=512, **kw)
    return RaggedInferenceEngineConfig(state_manager=mgr, kv_block_size=block_size)


@pytest.fixture(scope="module")
def llama_setup():
    cfg = _f32_tiny()
    model = LlamaModel(cfg)
    rng = jax.random.PRNGKey(0)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = {"model": model.init(rng, ids)["params"]}
    return cfg, model, params


def _reference_logits(model, params, token_ids):
    """Training-model logits for a full sequence [S] -> [S, V]."""
    return np.asarray(model.apply({"params": params["model"]}, jnp.asarray(token_ids)[None])[0],
                      np.float32)


def test_prefill_matches_training_logits(llama_setup):
    cfg, model, params = llama_setup
    engine = build_engine(params, cfg, _engine_config())
    rng = np.random.default_rng(0)
    seqs = {0: rng.integers(0, cfg.vocab_size, 17), 1: rng.integers(0, cfg.vocab_size, 5),
            2: rng.integers(0, cfg.vocab_size, 33)}

    logits = np.asarray(engine.put(list(seqs), list(seqs.values())))
    assert logits.shape == (3, cfg.vocab_size)
    for i, (uid, toks) in enumerate(seqs.items()):
        ref = _reference_logits(model, params, toks)[-1]
        np.testing.assert_allclose(logits[i], ref, rtol=2e-4, atol=2e-4)


def test_decode_matches_training_logits(llama_setup):
    """Mixed prefill + several decode steps: paged-KV logits == full-context logits."""
    cfg, model, params = llama_setup
    engine = build_engine(params, cfg, _engine_config())
    rng = np.random.default_rng(1)
    ctx = {0: list(rng.integers(0, cfg.vocab_size, 9)), 1: list(rng.integers(0, cfg.vocab_size, 21))}

    out = engine.put(list(ctx), [np.asarray(v) for v in ctx.values()])
    for step in range(4):
        nxt = {u: int(np.argmax(np.asarray(out)[i])) for i, u in enumerate(ctx)}
        for u in ctx:
            ctx[u].append(nxt[u])
        out = engine.put(list(ctx), [np.asarray([nxt[u]]) for u in ctx])
        for i, u in enumerate(ctx):
            ref = _reference_logits(model, params, ctx[u])[-1]
            np.testing.assert_allclose(np.asarray(out)[i], ref, rtol=2e-4, atol=2e-4,
                                       err_msg=f"uid {u} step {step}")


def test_generate_greedy_matches_reference(llama_setup):
    cfg, model, params = llama_setup
    engine = build_engine(params, cfg, _engine_config())
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (4, 11)]

    outs = generate(engine, prompts, max_new_tokens=5, temperature=0.0)

    for prompt, out in zip(prompts, outs):
        toks = list(prompt)
        for expected in out:
            ref = _reference_logits(model, params, toks)[-1]
            assert int(np.argmax(ref)) == expected
            toks.append(expected)


def test_scheduling_limits(llama_setup):
    cfg, _, params = llama_setup
    engine = build_engine(params, cfg, _engine_config(num_blocks=4, block_size=16,
                                                      max_ragged_batch_size=32,
                                                      max_ragged_sequence_count=2))
    # KV budget: 80 tokens needs 5 blocks, only 4 exist
    assert engine.can_schedule([0], [80]) == SchedulingResult.KVCacheLimitExceeded
    # sequence-count budget
    assert engine.can_schedule([0, 1, 2], [1, 1, 1]) == SchedulingResult.BatchSequenceLimitExceeded
    # batch token budget (fits KV, exceeds ragged batch size)
    assert engine.can_schedule([0, 1], [32, 16]) == SchedulingResult.BatchTokenLimitExceeded
    assert engine.can_schedule([0], [16]) == SchedulingResult.Success
    with pytest.raises(SchedulingError):
        engine.put([0], [np.arange(64) % cfg.vocab_size])


def test_empty_run_changes_nothing_a_sequence_can_see(llama_setup):
    """An idle replica's lock-step forward: the engine's own batch with no
    sequence in it, through the smallest bucket's program. The sequences'
    state and what they read next are as without it."""
    cfg, _, params = llama_setup
    prompt = np.arange(9) % cfg.vocab_size
    idle, busy = (build_engine(params, cfg, _engine_config()) for _ in range(2))
    for engine in (idle, busy):
        engine.put([0], [prompt])
    idle.empty_run()
    idle.empty_run()
    assert sorted(idle.lowerable_callables()["forward"]) == [(8, 8, 4), (16, 8, 4)]
    assert idle.free_blocks == busy.free_blocks
    assert idle._state_manager.get_sequence(0).seen_tokens == 9
    np.testing.assert_array_equal(np.asarray(idle.put([0], [np.array([3])])),
                                  np.asarray(busy.put([0], [np.array([3])])))


def test_flush_recycles_blocks(llama_setup):
    cfg, _, params = llama_setup
    engine = build_engine(params, cfg, _engine_config(num_blocks=8, block_size=16))
    free0 = engine.free_blocks
    engine.put([7], [np.arange(40) % cfg.vocab_size])
    assert engine.free_blocks == free0 - 3  # ceil(40/16)
    # query: known sequence needs 1 more block for 10 tokens (40+10 -> 4 blocks)
    toks, blocks = engine.query(7, 10, engine.free_blocks)
    assert (toks, blocks) == (10, 1)
    engine.flush(7)
    assert engine.free_blocks == free0
    assert engine._state_manager.get_sequence(7) is None


def test_serialize_roundtrip(llama_setup, tmp_path):
    """serialize → build_engine_from_ds_checkpoint is a REAL round-trip
    (reference engine_factory.py:29): the rebuilt engine serves identical
    logits, and build_hf_engine auto-detects the DS checkpoint (ref :84)."""
    from deepspeed_tpu.inference.v2.engine_factory import (build_engine_from_ds_checkpoint,
                                                           build_hf_engine)

    cfg, _, params = llama_setup
    engine = build_engine(params, cfg, _engine_config())
    engine.serialize(str(tmp_path))
    data = np.load(tmp_path / "params_rank0.npz")
    flat = jax.tree.leaves(params)
    assert len(data.files) == len(flat)

    prompt = np.arange(17) % cfg.vocab_size
    want = np.asarray(engine.put([0], [prompt]))
    rebuilt = build_engine_from_ds_checkpoint(str(tmp_path), _engine_config())
    got = np.asarray(rebuilt.put([0], [prompt]))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(rebuilt._model._params), flat):
        assert a.dtype == b.dtype and a.shape == b.shape
    via_hf = build_hf_engine(str(tmp_path), _engine_config())  # auto-detect
    np.testing.assert_allclose(np.asarray(via_hf.put([0], [prompt])), want,
                               rtol=1e-5, atol=1e-5)
    # no pickle anywhere in the checkpoint dir (config is JSON; a checkpoint
    # must never be an arbitrary-code-execution vector)
    import os
    assert not any(f.endswith(".pkl") for f in os.listdir(tmp_path))


def test_serialize_roundtrip_bf16(llama_setup, tmp_path):
    """bf16 params exercise the uint-view storage branch: dtypes and logits
    must survive the round-trip."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_factory import build_engine_from_ds_checkpoint

    cfg, _, params = llama_setup
    bf16_params = jax.tree.map(lambda l: l.astype(jnp.bfloat16)
                               if jnp.issubdtype(l.dtype, jnp.floating) else l, params)
    engine = build_engine(bf16_params, cfg, _engine_config())
    engine.serialize(str(tmp_path))
    prompt = np.arange(11) % cfg.vocab_size
    want = np.asarray(engine.put([0], [prompt]))
    rebuilt = build_engine_from_ds_checkpoint(str(tmp_path), _engine_config())
    for a, b in zip(jax.tree.leaves(rebuilt._model._params),
                    jax.tree.leaves(bf16_params)):
        assert a.dtype == b.dtype, (a.dtype, b.dtype)
    got = np.asarray(rebuilt.put([0], [prompt]))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_serialize_rejects_unroundtrippable_trees(llama_setup, tmp_path):
    """Trees the path encoding cannot reconstruct (list nodes, '/' in keys)
    must be rejected at SAVE time, not corrupted at load time; and the loader
    refuses config classes outside the package."""
    import json
    import pytest as _pytest
    from deepspeed_tpu.inference.v2.engine_factory import build_engine_from_ds_checkpoint

    cfg, _, params = llama_setup
    eng = build_engine(params, cfg, _engine_config())
    good_params = eng._model._params
    try:
        eng._model._params = {"weird/key": np.ones((4, 4), np.float32)}
        with _pytest.raises(ValueError, match="'/'-free"):
            eng.serialize(str(tmp_path / "bad1"))
        eng._model._params = {"layers": [np.ones((4, 4), np.float32)]}
        with _pytest.raises(ValueError, match="string-keyed"):
            eng.serialize(str(tmp_path / "bad2"))
    finally:
        eng._model._params = good_params

    eng.serialize(str(tmp_path / "ok"))
    doc = json.loads((tmp_path / "ok" / "ds_model_config.json").read_text())
    doc["config_class"] = "os.path.join"
    (tmp_path / "ok" / "ds_model_config.json").write_text(json.dumps(doc))
    with _pytest.raises(ValueError, match="refusing to import"):
        build_engine_from_ds_checkpoint(str(tmp_path / "ok"))


def test_decode_loop_matches_host_loop(llama_setup):
    """Device-side scan decode (engine.decode_loop) generates EXACTLY the same
    greedy tokens as the host loop of put()+argmax, and leaves the sequence
    state (seen_tokens, blocks) identical."""
    cfg, model, params = llama_setup
    rng = np.random.default_rng(7)
    prompts = {0: rng.integers(0, cfg.vocab_size, 23), 1: rng.integers(0, cfg.vocab_size, 9)}
    N = 6

    # host loop
    eng_a = build_engine(params, cfg, _engine_config())
    logits = np.asarray(eng_a.put(list(prompts), list(prompts.values())))
    cur = np.argmax(logits, -1).astype(np.int32)
    host_tokens = []
    for _ in range(N):
        logits = np.asarray(eng_a.put(list(prompts), [np.array([c]) for c in cur]))
        cur = np.argmax(logits, -1).astype(np.int32)
        host_tokens.append(cur)
    host_tokens = np.stack(host_tokens, axis=1)  # [n_seqs, N]

    # device loop
    eng_b = build_engine(params, cfg, _engine_config())
    logits = np.asarray(eng_b.put(list(prompts), list(prompts.values())))
    first = np.argmax(logits, -1).astype(np.int32)
    dev_tokens = eng_b.decode_loop(list(prompts), [np.array([c]) for c in first], N)
    assert dev_tokens.shape == (2, N)
    np.testing.assert_array_equal(dev_tokens, host_tokens)

    for uid in prompts:
        sa = eng_a._state_manager.get_sequence(uid)
        sb = eng_b._state_manager.get_sequence(uid)
        assert sa.seen_tokens == sb.seen_tokens
        assert sa.cur_allocated_blocks == sb.cur_allocated_blocks


def test_decode_loop_validation(llama_setup):
    cfg, model, params = llama_setup
    engine = build_engine(params, cfg, _engine_config())
    engine.put([0], [np.arange(5) % cfg.vocab_size])
    # a multi-token entry is a speculative verify feed (verify_tree's): the
    # on-device scan takes single-token entries only
    with pytest.raises(ValueError, match="exactly one next-input token"):
        engine.decode_loop([0], [np.array([1, 2])], 4)
    with pytest.raises(ValueError, match="n_steps"):
        engine.decode_loop([0], [np.array([1])], 0)
    # block-budget check: n_steps beyond free blocks must be rejected up front
    with pytest.raises(SchedulingError):
        engine.decode_loop([0], [np.array([1])], 10_000)


def test_decode_loop_token_budget_is_per_step(llama_setup):
    """Admission: n_steps counts against the KV-block budget, NOT the ragged
    token budget — each scan step carries one token per sequence (regression:
    n_seqs*n_steps was charged against max_ragged_batch_size)."""
    cfg, model, params = llama_setup
    engine = build_engine(params, cfg, _engine_config(max_ragged_batch_size=64))
    prompt = np.arange(40) % cfg.vocab_size  # fits the 64-token ragged budget
    first = int(np.argmax(np.asarray(engine.put([0], [prompt]))[0]))
    toks = engine.decode_loop([0], [np.array([first])], 70)  # 70 > 64 and KV fits
    assert toks.shape == (1, 70)


def test_decode_loop_rejects_past_max_context(llama_setup):
    """n_steps beyond the per-sequence table cap (max_context) must be a
    SchedulingError up front — never an allocate-then-extend crash that leaks
    pool blocks (regression)."""
    cfg, model, params = llama_setup
    engine = build_engine(params, cfg, _engine_config(num_blocks=64))  # max_context=512
    engine.put([0], [np.arange(30) % cfg.vocab_size])
    free_before = engine.free_blocks
    with pytest.raises(SchedulingError):
        engine.decode_loop([0], [np.array([1])], 500)  # 530 > 512 cap
    assert engine.free_blocks == free_before  # nothing leaked


def _chunk_check_reference(engine, uids, n_steps):
    """The admission check ``dispatch_decode_loop`` carried inline until PR 46,
    kept as the reference ``can_schedule(..., steps=n_steps)`` is held to."""
    from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import PlaceholderSequenceDescriptor
    limits, manager = engine._config.state_manager, engine._state_manager
    if len(uids) > limits.max_ragged_sequence_count:
        return SchedulingResult.BatchSequenceLimitExceeded
    if len(uids) > limits.max_ragged_batch_size:
        return SchedulingResult.BatchTokenLimitExceeded
    free_blocks, cur_seqs = manager.free_blocks, manager.n_tracked_sequences
    for uid in uids:
        seq_desc = manager.get_sequence(uid)
        if seq_desc is None:
            cur_seqs += 1
            seq_desc = PlaceholderSequenceDescriptor()
        restore = seq_desc.live_blocks if manager.is_offloaded(uid) else 0
        sched_len, sched_blocks = engine.model.get_kv_requirements(
            seq_desc, n_steps, free_blocks - restore)
        if sched_len != n_steps:
            return SchedulingResult.KVCacheLimitExceeded
        free_blocks -= sched_blocks + restore
    if cur_seqs > limits.max_tracked_sequences:
        return SchedulingResult.EngineSequenceLimitExceeded
    return SchedulingResult.Success


@pytest.mark.parametrize("uids, n_steps, offload, expected", [
    ([0, 1, 2, 3, 4], 2, False, "BatchSequenceLimitExceeded"),
    # four sequences are four tokens a scan step, over a budget of three: said
    # before any sequence is looked at, though their KV would not fit either
    ([0, 1, 2, 3], 1000, False, "BatchTokenLimitExceeded"),
    ([0], 5 * 16, False, "Success"),            # 8 blocks, 3 held by uid 10: 5 free
    ([0], 5 * 16 + 1, False, "KVCacheLimitExceeded"),
    ([0, 10], 2 * 16, False, "Success"),        # uid 10 has 40 tokens in 3 blocks: 2 + 2 new
    ([0, 10], 2 * 16 + 9, False, "KVCacheLimitExceeded"),
    ([0, 1, 2], 1, False, "EngineSequenceLimitExceeded"),   # 1 tracked + 3 new > 3
    ([10], 88, True, "Success"),                # 8 free, 3 to restore: 40 + 88 fill 8 blocks
    ([10], 89, True, "KVCacheLimitExceeded"),   # its stale table would count 3 + 8 blocks
], ids=["sequences", "tokens-by-count", "kv-fits", "kv-over-k-steps", "kv-two-fit",
        "kv-two-over", "tracked", "restore-cost-fits", "restore-cost-over"])
def test_can_schedule_takes_the_chunk_case(llama_setup, uids, n_steps, offload, expected):
    """``can_schedule(uids, lengths, steps=k)`` answers what the chunk's own
    check answered: the token budget by the sequence count, the KV budget over
    ``k`` steps a sequence; and ``dispatch_decode_loop`` raises that answer
    with nothing changed."""
    cfg, _, params = llama_setup
    engine = build_engine(params, cfg, _engine_config(
        num_blocks=8, max_ragged_batch_size=3, max_ragged_sequence_count=4,
        max_tracked_sequences=3))
    seq = engine._state_manager.get_or_create_sequence(10)  # tracked, 40 tokens committed
    engine.model.maybe_allocate_kv(seq, 40)
    seq.pre_forward(40)
    seq.post_forward()
    if offload:
        engine.offload_sequence(10)
    free, tracked = engine.free_blocks, engine._state_manager.n_tracked_sequences
    got = engine.can_schedule(uids, [1] * len(uids), steps=n_steps)
    assert got == _chunk_check_reference(engine, uids, n_steps) == getattr(SchedulingResult, expected)
    if got != SchedulingResult.Success:
        with pytest.raises(SchedulingError) as said:
            engine.dispatch_decode_loop(uids, [np.array([1])] * len(uids), n_steps)
        assert said.value.status == got
        assert (engine.free_blocks, engine._state_manager.n_tracked_sequences) == (free, tracked)
        assert engine.is_offloaded(10) == offload
    engine.close()


def test_generate_chunked_matches_stepwise(llama_setup):
    """decode_chunk>1 (device-loop chunks) must reproduce the step-by-step
    greedy generation exactly, including eos cut-off and multi-prompt
    continuous batching."""
    cfg, model, params = llama_setup
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (19, 7, 31)]

    def run(chunk, eos=None):
        eng = build_engine(params, cfg, _engine_config())
        return generate(eng, prompts, max_new_tokens=10, eos_token_id=eos,
                        decode_chunk=chunk)

    np.testing.assert_equal(run(4), run(1))
    # eos: pick a token the stepwise run actually emits, then compare cut-offs
    ref = run(1)
    eos = ref[0][3]
    np.testing.assert_equal(run(4, eos=eos), run(1, eos=eos))


def test_kv_cache_dtype_follows_any_f32_representation(llama_setup):
    """An fp32 model config expressed as np.float32 / np.dtype('float32')
    (not the jnp scalar type) must still get an fp32 KV cache — the silent
    bf16 default only applies to genuinely low-precision/unknown dtypes."""
    import dataclasses
    cfg, model, params = llama_setup
    for rep in (np.float32, np.dtype("float32"), jnp.float32):
        c = dataclasses.replace(cfg, dtype=rep)
        eng = build_engine(params, c, _engine_config())
        assert eng._model.kv_cache_config().cache_dtype == "float32", rep
    bf = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    eng = build_engine(params, bf, _engine_config())
    assert eng._model.kv_cache_config().cache_dtype == "bfloat16"
