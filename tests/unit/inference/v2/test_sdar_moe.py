"""SDAR served through ``build_engine`` (PR 50): generation by diffusion over
blocks against the plain float32 reference — prefill in whole blocks, every
denoise forward and the commit for prompts of all four residues; the block
loop's ids AND unmasking order equal to ``generate``'s; what a denoise forward
leaves alone and what a commit leaves in the pool; through ``ServingScheduler``
exactly ``max_new_tokens`` tokens, alone and eight at a time; every refusal by
name. On the XLA arm and on the paged kernel's tile grid in interpret mode."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import sdar_moe as reference
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.engine_v2 import BlockChunk
from deepspeed_tpu.inference.v2.model_implementations import registry
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig, MemoryConfig)
from deepspeed_tpu.inference.v2.ragged.kv_cache import CACHE_OPERATIONS
from deepspeed_tpu.models import sdar_moe as sm
from deepspeed_tpu.serving import ServingConfig, ServingScheduler
from deepspeed_tpu.utils import groups

BLOCK, B = 16, 4
TOL = 1e-4
LENGTHS = (40, 41, 22, 27)  # 0, 1, 2 and 3 mod 4


def sizes_of(cfg):
    """The configuration-file view of a program config, as the reference reads it."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def engine_of(cfg, params, kernel=False, blocks=96, seqs=8, **overrides):
    groups.initialize_mesh(force=True)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                          size=blocks),
                               max_context=256, max_ragged_batch_size=64,
                               max_ragged_sequence_count=seqs)
    # capacity 4 = experts / top-k: dropless wherever the capacity path is taken, as the cell's 16
    return build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=BLOCK, use_paged_kernel=kernel,
        expert_parallel={"capacity_factor": 4.0}, **overrides))


@pytest.fixture(scope="module")
def model():
    """Three layers, top-2 of 8; the embedding at a twentieth of its seeded size, so that the
    attention and expert branches, not the mask token's embedding, decide the tokens."""
    cfg = sm.SdarMoeConfig.tiny(dtype=jnp.float32)
    params = sm.init_params(cfg, rng=jax.random.PRNGKey(3))[1]
    params["embed_tokens"]["embedding"] = params["embed_tokens"]["embedding"] * 0.05
    return cfg, params


def _prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 256, n).astype(np.int32) for n in LENGTHS]


def _prefill(engine, prompts, share=16):
    """The prompts' whole blocks, together, in shares of ``share`` tokens."""
    whole = [p.size // B * B for p in prompts]
    fed = [0] * len(prompts)
    while any(f < w for f, w in zip(fed, whole)):
        batch = [(u, prompts[u][fed[u]:min(fed[u] + share, whole[u])])
                 for u in range(len(prompts)) if fed[u] < whole[u]]
        engine.put([u for u, _ in batch], [t for _, t in batch])
        for u, t in batch:
            fed[u] += t.size
    return whole


def _first_blocks(prompts, whole):
    blocks = [np.concatenate([p[w:], np.zeros(B - (p.size - w), np.int32)])
              for p, w in zip(prompts, whole)]
    return blocks, [np.arange(B) >= p.size - w for p, w in zip(prompts, whole)]


def _want(cfg, params, committed, block, flags):
    ids = np.concatenate([committed, block])
    return np.asarray(reference.forward_logits(
        params, sizes_of(cfg), ids, rows=np.arange(committed.size, ids.size),
        flags=np.concatenate([np.zeros(committed.size, bool), flags])))


def _pool_rows(engine, uid, n):
    """The first ``n`` positions of ``uid``'s K and V in every layer, by position."""
    seq = engine._state_manager.get_sequence(uid)
    cache = np.asarray(engine._state_manager.kv_cache.cache)  # [L, 2, NB, KVH, bs, D]
    rows = cache[:, :, seq.kv_blocks]  # [L, 2, blocks, KVH, bs, D]
    L, two, nb, KVH, bs, D = rows.shape
    return rows.transpose(0, 1, 3, 2, 4, 5).reshape(L, two, KVH, nb * bs, D)[:, :, :, :n]


# ---------------------------------------------------------- (a) the engine --
def _walk(engine, cfg, uids, n_blocks):
    """``n_blocks`` all-masked blocks a sequence, a forward at a time:
    ``block_forward`` x ``denoising_steps``, the reference's rule on its
    logits, then the commit ``put``. ``(ids, steps)`` as a loop hands them."""
    ids, steps = [], []
    for _ in range(n_blocks):
        blocks = [np.zeros(B, np.int32) for _ in uids]
        masked = [np.ones(B, bool) for _ in uids]
        taken = [np.full(B, -1, np.int8) for _ in uids]
        for step in range(cfg.denoising_steps):
            logits = np.asarray(engine.block_forward(uids, blocks, masked))
            for i in range(len(uids)):
                x0, conf = reference.confidence(logits[i])
                for j in reference.most_confident(conf, masked[i], B // cfg.denoising_steps):
                    blocks[i][j], masked[i][j], taken[i][j] = int(x0[j]), False, step
        engine.put(uids, blocks)  # the commit
        ids.append(np.stack(blocks))
        steps.append(np.stack(taken))
    return np.concatenate(ids, axis=1), np.concatenate(steps, axis=1)


@pytest.mark.parametrize("kernel, n_blocks", [(False, 1), (False, 2), (False, 3), (True, 2),
                                              (True, 3)],
                         ids=["xla-arm-1-block", "xla-arm-2-blocks", "xla-arm-3-blocks",
                              "tile-grid-interpret-2-blocks", "tile-grid-interpret-3-blocks"])
def test_every_forward_of_two_blocks_is_the_references_and_the_loop_is_generates(model, kernel,
                                                                                  n_blocks):
    """Prefill of whole blocks together; then a block a prompt, a forward at a
    time: every denoise forward's four rows of logits are the reference's of
    the whole sequence as it then stands, ``seen_tokens`` and the committed K/V
    stay as they were, the commit ``put`` moves both; then the block loop of
    ``n_blocks`` blocks (contexts of 24 to 44 tokens; a block's commit fused
    with the next block's first forward, the last block's alone), whose ids and
    steps are ``generate``'s AND the forward-at-a-time walk's, and whose pool
    is the walk's."""
    cfg, params = model
    engine = engine_of(cfg, params, kernel)
    served = engine.model
    assert registry.model_cls_for(cfg) is type(served)
    assert "sdar_moe" in registry.supported_model_types()
    assert served.attention_block == B and served.attention_arm(8) == (
        "paged_tiled" if kernel else "xla_gather")
    assert (served.min_sequence_bucket, served.min_token_bucket, served.min_table_bucket) == \
        (8, 64, 16)
    prompts = _prompts()
    uids = list(range(len(prompts)))
    whole = _prefill(engine, prompts)
    assert [engine._state_manager.get_sequence(u).seen_tokens for u in uids] == whole
    committed = [p[:w] for p, w in zip(prompts, whole)]
    blocks, masked = _first_blocks(prompts, whole)
    assert [int(m.sum()) for m in masked] == [4, 3, 2, 1]
    before = [_pool_rows(engine, u, whole[u]).copy() for u in uids]
    for step in range(cfg.denoising_steps):
        logits = np.asarray(engine.block_forward(uids, blocks, masked))
        assert logits.shape == (4, B, cfg.vocab_size) and logits.dtype == np.float32
        for u in uids:
            want = _want(cfg, params, committed[u], blocks[u], masked[u])
            assert np.abs(want - logits[u]).max() < TOL, (u, step)
            x0, conf = reference.confidence(logits[u])
            for j in reference.most_confident(conf, masked[u], 1):
                blocks[u][j], masked[u][j] = int(x0[j]), False
        # nothing counts yet: the sequence is where it was, and so is its committed K/V
        assert [engine._state_manager.get_sequence(u).seen_tokens for u in uids] == whole
        for u in uids:
            assert np.array_equal(_pool_rows(engine, u, whole[u]), before[u])
    engine.put(uids, blocks)  # the commit
    assert [engine._state_manager.get_sequence(u).seen_tokens for u in uids] == \
        [w + B for w in whole]
    committed = [np.concatenate([c, b]) for c, b in zip(committed, blocks)]

    # the pool after the commit is what a block-masked prefill of the same tokens leaves
    fresh = engine_of(cfg, params, kernel)
    for u in uids:
        fresh.put([u], [committed[u][:32]])
        fresh.put([u], [committed[u][32:]]) if committed[u].size > 32 else None
    for u in uids:
        assert np.abs(_pool_rows(engine, u, committed[u].size)
                      - _pool_rows(fresh, u, committed[u].size)).max() < TOL, u
    fresh.close()

    # the block loop against generate, from the state the forwards left
    chunk = engine.dispatch_block_loop(uids, [np.zeros(B, np.int32)] * 4, [np.ones(B, bool)] * 4,
                                       n_blocks)
    assert isinstance(chunk, BlockChunk)
    ids = chunk.fetch()
    assert ids.shape == chunk.steps.shape == (4, n_blocks * B) and chunk.steps.dtype == np.int8
    for u in uids:
        want_ids, want_steps = reference.generate(params, sizes_of(cfg), committed[u], n_blocks * B)
        assert ids[u].tolist() == want_ids.tolist(), u
        assert chunk.steps[u].tolist() == want_steps.tolist(), u
        assert sorted(chunk.steps[u][:B]) == [0, 1, 2, 3]
    # what the rows were chosen on: the reference's confidence of each masked row behind each
    # denoise forward, -1 where the row had its token; the row taken is the largest's
    conf = chunk.confidences
    assert conf.shape == (4, n_blocks, cfg.denoising_steps, B) and conf.dtype == np.float32
    for u in uids[::3]:
        for b in range(n_blocks):
            block, steps = ids[u][b * B:(b + 1) * B], chunk.steps[u][b * B:(b + 1) * B]
            for step in range(cfg.denoising_steps):
                flags = steps >= step
                logits = _want(cfg, params, np.concatenate([committed[u], ids[u][:b * B]]),
                               np.where(flags, 0, block), flags)
                want = np.where(flags, reference.confidence(logits)[1], -1.0)
                assert np.abs(conf[u, b, step] - want).max() < TOL, (u, b, step)
                assert int(np.argmax(conf[u, b, step])) == int(np.flatnonzero(steps == step)[0])
    assert [engine._state_manager.get_sequence(u).seen_tokens for u in uids] == \
        [w + (1 + n_blocks) * B for w in whole]
    with pytest.raises(ValueError, match="hands no ids on"):
        chunk.ids
    programs = engine.lowerable_callables()
    assert {key[1:] for key in programs["forward"]} == {(8, 16)}  # one sequence, one table bucket
    assert list(programs["block_loop"]) == [((64, 8, 16), n_blocks)]
    assert list(programs["block_forward"]) == [(64, 8, 16)] and not programs["decode_loop"]

    # the same blocks a forward at a time on twins of the sequences, each block committed by a
    # put of its own: the loop's ids, its steps, and the K/V its commits (fused and alone) left
    twins = [u + len(uids) for u in uids]
    for u, twin in zip(uids, twins):
        for at in range(0, committed[u].size, 32):
            engine.put([twin], [committed[u][at:at + 32]])
    walk_ids, walk_steps = _walk(engine, cfg, twins, n_blocks)
    assert walk_ids.tolist() == ids.tolist() and walk_steps.tolist() == chunk.steps.tolist()
    for u, twin in zip(uids, twins):
        n = committed[u].size + n_blocks * B
        assert engine._state_manager.get_sequence(twin).seen_tokens == n
        assert np.abs(_pool_rows(engine, u, n) - _pool_rows(engine, twin, n)).max() < TOL, u
    engine.close()


@pytest.mark.parametrize("kernel, n_blocks", [(False, 1), (False, 2), (False, 3), (True, 1)],
                         ids=["xla-arm-1-block", "xla-arm-2-blocks", "xla-arm-3-blocks",
                              "tile-grid-interpret-1-block"])
def test_the_loop_from_a_part_given_first_block_is_generates(model, kernel, n_blocks):
    """Straight from the prefill (contexts of 0 to 40 tokens): each prompt's
    ``len % 4`` rows are given (step -1, its own ids), the rest take their
    tokens in ``generate``'s order; a sequence whose first block is fully given
    changes nothing in it."""
    cfg, params = model
    engine = engine_of(cfg, params, kernel)
    prompts = _prompts() + [np.random.default_rng(5).integers(0, 256, 3).astype(np.int32)]
    uids = list(range(len(prompts)))
    whole = _prefill(engine, prompts)
    assert whole[-1] == 0  # a prompt shorter than a block has nothing to prefill
    blocks, masked = _first_blocks(prompts, whole)
    ids, steps = engine.block_loop(uids, blocks, masked, n_blocks)
    for u, p in enumerate(prompts):
        k = p.size - whole[u]
        want_ids, want_steps = reference.generate(params, sizes_of(cfg), p, n_blocks * B - k)
        assert ids[u][:k].tolist() == p[whole[u]:].tolist() and (steps[u][:k] == -1).all()
        assert ids[u][k:].tolist() == want_ids.tolist(), u
        assert steps[u][k:].tolist() == want_steps.tolist(), u
    # the generated tokens vary: the comparison above is not of one repeated id
    assert len({int(t) for row in ids for t in row}) > 6
    # the loop's COMMITS: the pool holds what a block-masked prefill of the same tokens leaves
    # (a skipped commit leaves the last denoise forward's K/V, a row of it the mask token's)
    fresh = engine_of(cfg, params, kernel)
    for u, p in enumerate(prompts):
        full = np.concatenate([p[:whole[u]], ids[u]]).astype(np.int32)
        for at in range(0, full.size, 32):
            fresh.put([u], [full[at:at + 32]])
        assert engine._state_manager.get_sequence(u).seen_tokens == full.size
        assert np.abs(_pool_rows(engine, u, full.size) - _pool_rows(fresh, u, full.size)).max() \
            < TOL, u
    fresh.close()
    engine.close()


@pytest.mark.parametrize("n_blocks", [1, 2, 3], ids=lambda n: f"{n}-blocks-a-loop")
def test_the_spans_count_the_tile_grids_few_row_passes(model, n_blocks):
    """On the tile grid under a telemetry session: a block loop's span counts
    the program's forwards (``steps``: ``n_blocks * denoising_steps + 1``, of
    which ``fused_commits`` carry two blocks a sequence) beside ``forwards`` /
    ``blocks``, which stay forwards of B rows a sequence; its passes are
    ``tiled_passes()`` of what the program feeds the kernel, every one the
    few-row arm's (a fused forward's two blocks are two entries, two passes of
    one block); a prompt chunk's ``put`` counts its pass as a many-row one;
    without a block mask (``batch_counts`` of a causal model at the same
    positions) the arm is the one-token passes'."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.ops.pallas.paged_attention import tiled_passes
    cfg, params = model
    engine = engine_of(cfg, params, kernel=True)
    prompts = _prompts()
    uids = list(range(len(prompts)))
    whole = _prefill(engine, prompts)
    blocks, masked = _first_blocks(prompts, whole)
    session = telemetry.configure({"enabled": True, "compile_watch": False})
    try:
        engine.put([9], [np.arange(24, dtype=np.int32)])  # a prompt chunk of six blocks
        engine.dispatch_block_loop(uids, blocks, masked, n_blocks).fetch()
        spans = [s for s in session.spans.export_since(0)["spans"] if s["cat"] == "inference"]
    finally:
        telemetry.shutdown()
    layers, n_denoise = cfg.num_hidden_layers, cfg.denoising_steps
    (loop, ) = [s["args"] for s in spans if s["name"] == "block_loop"]
    assert loop["steps"] == n_blocks * n_denoise + 1 and loop["fused_commits"] == n_blocks - 1
    assert (loop["seqs"], loop["blocks"]) == (len(uids), len(uids) * n_blocks)
    assert loop["forwards"] == loop["blocks"] * (n_denoise + 1)  # of B rows a sequence
    # the kernel's passes: what tiled_passes() says of the metadata the program's forwards are fed
    fed = engine._batch.device_batch
    tok, seq = np.asarray(fed["tok_meta"]), np.asarray(fed["seq_meta"])
    two_tok, two_seq = engine.model._two_blocks(tok, seq)
    assert two_tok.shape == (4, 2 * tok.shape[1]) and two_seq.shape[0] == 2 * seq.shape[0]
    alone = tiled_passes(seq[:, 1], seq[:, 2], tok.shape[1], B)
    fused = tiled_passes(two_seq[:, 1], two_seq[:, 2], two_tok.shape[1], B)
    assert alone == (len(uids), 0, len(uids)) and fused == (2 * len(uids), 0, 2 * len(uids))
    want = [layers * ((loop["steps"] - loop["fused_commits"]) * a + loop["fused_commits"] * f)
            for a, f in zip(alone, fused)]
    assert [loop["tiled_passes"], loop["tiled_one_token_passes"],
            loop["tiled_few_row_passes"]] == want
    assert loop["tiled_passes"] == len(uids) * layers * n_blocks * (n_denoise + 1)
    # the experts' rows: a fused forward routes two blocks' assignments
    assert loop["moe_assignments"] == loop["forwards"] * B * cfg.num_experts_per_tok * layers
    (put, ) = [s["args"] for s in spans if s["name"] == "put"]
    assert put["attention"] == "paged_tiled" and put["tiled_one_token_passes"] == 0
    # 24 rows are one pass of more than one block: the many-row arm's
    assert put["tiled_passes"] == layers and put["tiled_few_row_passes"] == 0
    # the same batch without a block mask: one row a sequence is a one-token pass, and the
    # few-row arm is theirs alone
    causal = type("Causal", (type(engine.model), ), {"attention_block": 0})
    decode = {"tok_meta": np.zeros((4, 64), np.int32),
              "seq_meta": np.array([[0, 1, u, 1] + [0] * 16 for u in range(4)]
                                   + [[0, 0, 0, 0] + [0] * 16] * 4, np.int32)}
    counts = causal.batch_counts(engine.model, decode)
    assert counts["tiled_few_row_passes"] == counts["tiled_one_token_passes"] == 4 * layers
    with_block = engine.model.batch_counts(decode)
    assert with_block["tiled_few_row_passes"] == with_block["tiled_passes"] == 4 * layers
    engine.close()


def test_a_feed_that_is_not_whole_blocks_is_refused_where_the_batch_is_built(model):
    cfg, params = model
    engine = engine_of(cfg, params)
    with pytest.raises(ValueError, match="start at a multiple of the block and number a multiple"):
        engine.put([0], [np.arange(6, dtype=np.int32)])
    engine.flush(0)
    engine.put([1], [np.arange(8, dtype=np.int32)])
    with pytest.raises(ValueError, match="one block of 4 ids and 4 flags a sequence"):
        engine.block_forward([1], [np.zeros(3, np.int32)], [np.ones(3, bool)])
    with pytest.raises(ValueError, match="n_blocks must be >= 1"):
        engine.dispatch_block_loop([1], [np.zeros(B, np.int32)], [np.ones(B, bool)], 0)
    # unchecked too: the mask is right only for whole blocks
    with pytest.raises(ValueError, match="under a block mask of 4"):
        engine.put([1], [np.arange(2, dtype=np.int32)], do_checks=False)
    engine.close()


def test_a_causal_model_has_no_block_steps():
    from deepspeed_tpu.models.mixtral import MixtralConfig, init_params
    groups.initialize_mesh(force=True)
    cfg = MixtralConfig.tiny(dtype=jnp.float32)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=16),
                               max_context=64, max_ragged_batch_size=32,
                               max_ragged_sequence_count=8)
    engine = build_engine(init_params(cfg)[1], cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=BLOCK))
    assert engine.model.attention_block == 0 and engine.model.min_token_bucket == 8
    with pytest.raises(ValueError, match="does not generate by blocks"):
        engine.block_forward([0], [np.zeros(4, np.int32)], [np.ones(4, bool)])
    engine.close()


# ------------------------------------------------------- (b) the scheduler --
@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    engine = engine_of(cfg, params)
    scheduler = ServingScheduler(engine, ServingConfig(decode_chunk=8))
    yield cfg, params, scheduler
    scheduler.stop()
    engine.close()


REQUESTS = [(40, 10), (41, 7), (22, 13), (27, 5), (3, 9), (70, 6), (17, 11), (30, 1)]


def test_requests_get_exactly_max_new_tokens_eight_at_a_time_and_alone(served):
    """Prompts of every residue (one shorter than a block), ``max_new_tokens``
    not multiples of 4: each request streams exactly that many tokens, and they
    are ``generate``'s whether it ran beside seven others or alone."""
    cfg, params, scheduler = served
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n, _ in REQUESTS]
    want = [reference.generate(params, sizes_of(cfg), p, m)[0].tolist()
            for p, (_, m) in zip(prompts, REQUESTS)]
    reqs = [scheduler.submit(p, max_new_tokens=m) for p, (_, m) in zip(prompts, REQUESTS)]
    for req, w, (_, m) in zip(reqs, want, REQUESTS):
        got = req.result(timeout=600)
        assert req.state.name == "DONE" and req.finish_reason == "length"
        assert len(got) == m and list(got) == w
    counters = scheduler.stats()["counters"]
    assert counters["block_loops"] >= 2 and counters["blocks_committed"] >= 8 * 2
    assert counters["denoise_forwards"] == \
        4 * (counters["commit_forwards"] + counters["fused_commit_forwards"])
    assert counters["block_tokens_cut"] > 0 and counters["completed"] == 8
    assert not counters["moe_capacity_steps"] + counters["moe_grouped_steps"] < counters["put_steps"]
    for p, w, (_, m) in list(zip(prompts, want, REQUESTS))[:3]:
        assert list(scheduler.submit(p, max_new_tokens=m).result(timeout=600)) == w
    assert {key[1] for key in scheduler._engine.lowerable_callables()["block_loop"]} == {2}


def test_every_block_is_committed_once_fused_or_alone(served):
    """One request alone, 20 tokens at a ``decode_chunk`` of 8: three launches
    of two blocks (the last's second block is cut whole), and every block the
    scheduler counts as committed was committed by ONE forward: a launch's last
    block's by the commit forward, every other by a fused one."""
    cfg, params, scheduler = served
    before = dict(scheduler.stats()["counters"])
    prompt = np.random.default_rng(21).integers(0, 256, 12).astype(np.int32)
    want = reference.generate(params, sizes_of(cfg), prompt, 20)[0].tolist()
    assert list(scheduler.submit(prompt, max_new_tokens=20).result(timeout=600)) == want
    after = scheduler.stats()["counters"]
    new = {k: after[k] - before[k] for k in ("block_loops", "blocks_committed", "commit_forwards",
                                             "fused_commit_forwards", "denoise_forwards")}
    assert new["block_loops"] == new["commit_forwards"] == 3  # a commit forward a launch
    assert new["commit_forwards"] + new["fused_commit_forwards"] == new["blocks_committed"] == 6
    assert new["denoise_forwards"] == cfg.denoising_steps * new["blocks_committed"]


@pytest.mark.parametrize("n", [1, 2, 3])
def test_a_prompt_shorter_than_a_block_decodes_from_admission(served, n):
    """A prompt of 1 .. B - 1 tokens has no whole block to prefill: it is
    admitted as a decoding request (no ``put`` step is spent on it), its tokens
    are its first block's given rows, and its answer is ``generate``'s."""
    cfg, params, scheduler = served
    prompt = np.random.default_rng(10 + n).integers(0, 256, n).astype(np.int32)
    want = reference.generate(params, sizes_of(cfg), prompt, 6)[0].tolist()
    puts = scheduler.stats()["counters"]["put_steps"]
    req = scheduler.submit(prompt, max_new_tokens=6)
    assert list(req.result(timeout=600)) == want and req.finish_reason == "length"
    assert scheduler.stats()["counters"]["put_steps"] == puts


def test_prompt_steps_and_block_loops_take_turns(model):
    """Arrivals do not starve a decoding request: while a request decodes, no
    two prompt steps are dispatched in a row, however many prompts wait; with
    no one decoding the prompts are fed back to back."""
    cfg, params = model
    engine = engine_of(cfg, params)
    scheduler = ServingScheduler(engine, ServingConfig(decode_chunk=8), start=False)
    rng = np.random.default_rng(2)
    first = scheduler.submit(rng.integers(0, 256, 9).astype(np.int32), max_new_tokens=60)
    kinds = []

    def tick():
        before = dict(scheduler.stats()["counters"])
        scheduler.step()
        after = scheduler.stats()["counters"]
        kinds.extend(kind for kind in ("put_steps", "block_loops")
                     for _ in range(after[kind] - before[kind]))

    while not scheduler.stats()["counters"]["block_loops"]:
        tick()
    # 4 prompts of 150 tokens at a budget of 64 tokens a step: ten prompt steps
    late = [scheduler.submit(rng.integers(0, 256, 150).astype(np.int32), max_new_tokens=4)
            for _ in range(4)]
    del kinds[:]
    while not first.finished:
        tick()
    assert "put_stepsput_steps" not in "".join(kinds), kinds
    assert kinds.count("put_steps") >= 5  # the prompts were fed meanwhile, a step a turn
    while not all(r.finished for r in late):
        tick()
    assert len(first.result(timeout=5)) == 60 and all(len(r.result(timeout=5)) == 4 for r in late)
    scheduler.stop()
    engine.close()


def test_what_a_block_model_does_not_serve_is_refused_by_name(served, model):
    cfg, params, scheduler = served
    with pytest.raises(ValueError, match="served greedily"):
        scheduler.submit(np.arange(8, dtype=np.int32), max_new_tokens=4, temperature=0.7)
    with pytest.raises(ValueError, match="handoff, park and resume frames cannot serve a model "
                                         "that generates by diffusion over blocks of 4"):
        scheduler.submit(np.arange(8, dtype=np.int32), max_new_tokens=4, handoff=True)
    engine = scheduler._engine
    for operation in ("prefix_cache", "kv_tiers", "speculative", "frames", "verify_tree",
                      "compact_kv"):
        assert "blocks" in CACHE_OPERATIONS[operation][2].split()
        assert "diffusion over blocks of 4" in str(engine.cache_refusal(operation))
    for operation in ("offload_sequence", "rollback", "fork_blocks"):
        assert engine.cache_refusal(operation) is None
    with pytest.raises(NotImplementedError, match="diffusion over blocks"):
        engine.verify_tree([0], [])
    other = engine_of(cfg, params)
    for feature in ("prefix_cache", "speculative"):
        with pytest.raises(ValueError, match=f"{feature} cannot serve a model that generates by "
                                             f"diffusion over blocks"):
            ServingScheduler(other, ServingConfig(decode_chunk=8, **{feature: {"enabled": True}}),
                             start=False)
    with pytest.raises(ValueError, match="decode_chunk 6 .* multiple of the block"):
        ServingScheduler(other, ServingConfig(decode_chunk=6), start=False)
    other.close()


# ------------------------------------------------------------ (c) refusals --
@pytest.mark.parametrize("kw, error, said", [
    ({"sliding_window": 4096}, NotImplementedError, "sliding_window 4096"),
    ({"use_sliding_window": True}, NotImplementedError, "use_sliding_window True"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, NotImplementedError, "rope_scaling"),
    ({"tie_word_embeddings": True}, NotImplementedError, "tied embeddings"),
    ({"attention_bias": True}, NotImplementedError, "attention biases"),
    ({"mlp_only_layers": (0, )}, NotImplementedError, r"mlp_only_layers \[0\]"),
    ({"decoder_sparse_step": 2}, NotImplementedError, "decoder_sparse_step 2"),
    ({"remasking_strategy": "low_confidence_dynamic"}, NotImplementedError,
     "remasking_strategy 'low_confidence_dynamic'"),
    ({"block_length": 6}, ValueError, "block_length 6: a power of two that divides"),
    ({"block_length": 128}, ValueError, "block_length 128: a power of two that divides"),
    ({"denoising_steps": 3}, ValueError, "denoising_steps 3 does not divide block_length 4"),
    ({"mask_token_id": 256}, ValueError, "mask_token_id 256 outside the vocabulary"),
    ({"hidden_act": "gelu"}, NotImplementedError, "hidden_act 'gelu'"),
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else None)
def test_the_config_refuses_by_name_what_is_not_served(kw, error, said):
    with pytest.raises(error, match=said):
        sm.SdarMoeConfig.tiny(**kw)


def test_the_published_config_is_the_default_and_two_rows_a_step_is_served(model):
    cfg = sm.SdarMoeConfig()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.vocab_size, cfg.rope_theta, cfg.rms_norm_eps) == \
        (48, 2048, 32, 4, 128, 128, 8, 768, 151936, 1e6, 1e-6)
    assert (cfg.block_length, cfg.denoising_steps, cfg.remasking_strategy, cfg.mask_token_id) == \
        (4, 4, "low_confidence_static", 151669)
    # two rows a denoise forward: three forwards a block, the same rule in program and reference
    tiny, params = model
    two = dataclasses.replace(tiny, denoising_steps=2)
    engine = engine_of(two, params)
    prompt = np.random.default_rng(9).integers(0, 256, 21).astype(np.int32)
    engine.put([0], [prompt[:20]])
    ids, steps = engine.block_loop([0], [np.concatenate([prompt[20:], np.zeros(3, np.int32)])],
                                   [np.arange(B) >= 1], 2)
    want_ids, want_steps = reference.generate(params, sizes_of(two), prompt, 7)
    assert ids[0][1:].tolist() == want_ids.tolist() and steps[0][1:].tolist() == want_steps.tolist()
    assert sorted(steps[0][B:]) == [0, 0, 1, 1]
    engine.close()
