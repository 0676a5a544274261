"""Solar Open 2 served through ``build_engine`` (PR 54): the two forms of the
gated delta rule against each other (the chunked form at the decay where the
factorised product underflows) and the kernel against the recurrence; prefill
in uneven chunks, ``put`` and ``decode_loop`` through the per-sequence state
group against the plain float32 reference's full forward; continuous
batching; the slots; the four shares of an expert layer adding up to the uncut
layer; the pieces of the mathematics the benchmark's controls leave out, each
seen here too; the counters; and each refusal by its message. The tiny model's
delta-rule heads are 128 x 128 as published (two of them), so every engine
test runs the kernel of ``ops/pallas/kda_step.py`` in interpret mode; the pool
off its shape rule (``kda.step`` between the slot copies) is held by
``test_a_pool_off_the_kernels_rule_falls_back_to_the_recurrence``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import solar_open2 as reference
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.model_implementations import registry
from deepspeed_tpu.inference.v2.modules import kda, ssm
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig, MemoryConfig)
from deepspeed_tpu.models import solar_open2 as so2
from deepspeed_tpu.ops.pallas import kda_step
from deepspeed_tpu.utils import groups
from tests.unit.inference.v2.program_hashes import decode_loop_hash

BLOCK = 16
TOL = 1e-4
# sha256 of the tiny model's traced decode_loop program (``program_hashes.decode_loop_hash``)
# re-recorded in PR 60: the chunk's count of routed work holds the grouped kernel's visits too;
# in PR 64: and the sorted rows the layer walked (the 8-row bucket's one tile: a constant)
DECODE_LOOP_HASH = "f62d48bb4c5261ed3793ea13eb0b73f7f450dbe2906152dc072dde8094f296b1"


def sizes_of(cfg):
    """The configuration-file view of a program config, as the reference reads it."""
    sizes = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    sizes["n_routed_experts"] = cfg.experts_held
    sizes["linear_attn_config"] = {"num_heads": cfg.linear_num_heads,
                                   "head_dim": cfg.linear_head_dim, "num_kv_heads": None,
                                   "short_conv_kernel_size": cfg.short_conv_kernel_size}
    sizes["deployment_share"] = {"routed_over": cfg.n_routed_experts,
                                 "experts_held": cfg.experts_held,
                                 "expert_rank": cfg.expert_rank}
    return sizes


def engine_of(cfg, params, kernel=False, blocks=96, slots=6):
    groups.initialize_mesh(force=True)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                          size=blocks),
                               max_context=256, max_ragged_batch_size=64,
                               max_ragged_sequence_count=8, max_tracked_sequences=slots)
    return build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=BLOCK, use_paged_kernel=kernel,
        expert_parallel={"capacity_factor": 4.0}))


@pytest.fixture(scope="module")
def model():
    cfg = so2.SolarOpen2Config.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1)
    return cfg, so2.init_params(cfg, rng=jax.random.PRNGKey(3))[1]


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


@pytest.fixture(scope="module")
def engine(model):
    """ONE engine for the tests that serve (its programs compile once); each
    flushes the sequences it made."""
    return engine_of(*model)


def _reference_rows(cfg, params, ids, rows, **variant):
    """The reference's logits at ``rows`` of ``ids``, padded with token 0 to
    ONE length: the same rows (every layer is causal), one compilation."""
    padded = np.zeros(96, np.int32)
    padded[:ids.size] = ids
    return np.asarray(reference.forward_logits(params, sizes_of(cfg), padded, rows=rows, **variant))


def _want(cfg, params, prompt, feed, **variant):
    return _reference_rows(cfg, params, np.concatenate([prompt, feed]),
                           np.arange(prompt.size - 1, prompt.size + feed.size), **variant)


# ------------------------------------------------------------ (a) one mixer --
def _mixer_inputs(seed, T, H=2, D=128, A=16.0):
    """Rows as the mixer hands them to the delta rule: unit keys with a common
    component (silu's outputs lean positive), a log-decay of ``-A softplus``."""
    rng = np.random.default_rng(seed)
    q = kda.l2_normed(jnp.asarray(rng.normal(size=(T, H, D)), jnp.float32), D**-0.5)
    k = kda.l2_normed(jnp.asarray(np.abs(rng.normal(size=(T, H, D))) + 0.2, jnp.float32))
    v = jnp.asarray(rng.normal(size=(T, H, D)), jnp.float32)
    g = -A * jnp.asarray(np.log1p(np.exp(rng.normal(size=(T, H, D)))), jnp.float32)
    beta = 2 * jax.nn.sigmoid(jnp.asarray(rng.normal(size=(T, H)), jnp.float32))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta, h):
    """``kda.step`` token by token over ONE sequence; h [1, H, d_k, d_v]."""
    outs = []
    for t in range(q.shape[0]):
        o, h = kda.step(q[t:t + 1], k[t:t + 1], v[t:t + 1], jnp.exp(g[t:t + 1]), beta[t:t + 1], h)
        outs.append(o[0])
    return np.stack(outs), np.asarray(h[0])


@pytest.mark.parametrize("rows", [16, 64], ids=["one-sub-chunk", "four-sub-chunks"])
def test_the_chunked_form_is_the_recurrence_where_the_factorised_product_underflows(rows):
    """256 rows at A = 16: a row's log-decay reaches -16 x softplus, so
    ``exp(-G)`` of the factorised form overflows float32 inside one chunk
    (``exp(64 x 16)``); every exponent of ``kda.chunk`` is a difference that is
    at most 0. Chunks of 16 rows are one sub-chunk (the pairwise form alone),
    chunks of 64 four of them (a reference point a sub-chunk)."""
    T = 256
    q, k, v, g, beta = _mixer_inputs(0, T)
    assert float(-g.min()) * rows > 88.0  # exp(-sum g) is past float32 inside a chunk
    h0 = jnp.asarray(np.random.default_rng(1).normal(size=(1, 2, 128, 128)), jnp.float32)
    want_o, want_h = _recurrence(q, k, v, g, beta, h0)
    one_chunk = jax.jit(kda.chunk)
    h, outs = h0[0], []
    for c in range(T // rows):
        at = slice(c * rows, (c + 1) * rows)
        o, h = one_chunk(q[at], k[at], v[at], g[at], beta[at], h)
        outs.append(np.asarray(o))
    assert np.isfinite(np.concatenate(outs)).all()
    assert np.abs(np.concatenate(outs) - want_o).max() < 1e-5
    assert np.abs(np.asarray(h) - want_h).max() < 1e-5


def test_the_chunked_form_holds_at_a_slow_decay_too():
    """At A = 0.05 the state outlives the 128 rows: what a chunk reads of
    ``S_0`` and what it leaves both count."""
    q, k, v, g, beta = _mixer_inputs(2, 128, A=0.05)
    h0 = jnp.asarray(np.random.default_rng(3).normal(size=(1, 2, 128, 128)), jnp.float32)
    want_o, want_h = _recurrence(q, k, v, g, beta, h0)
    one_chunk = jax.jit(kda.chunk)
    h, outs = h0[0], []
    for c in range(2):
        at = slice(c * 64, (c + 1) * 64)
        o, h = one_chunk(q[at], k[at], v[at], g[at], beta[at], h)
        outs.append(np.asarray(o))
    assert np.abs(np.concatenate(outs) - want_o).max() < 1e-5
    assert np.abs(np.asarray(h) - want_h).max() < 1e-5


def _spoilt_pool(seed, shape=(2, 6, 2, 128, 128)):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


def test_the_kernel_in_interpret_mode_is_the_recurrence():
    """``kda_step_in_place`` over a pool that holds something in every slot:
    a live row's state is its slot's (zeros where nothing was seen, whatever
    the slot held) and is left there; a dead row copies nothing and reads
    zeros; no other slot and no other layer changes by a bit."""
    q, k, v, g, beta = _mixer_inputs(4, 5, A=1.0)
    pool = _spoilt_pool(5)
    slot = np.array([3, 1, 0, 5, 2])
    live, started = np.array([1, 1, 0, 1, 1], bool), np.array([1, 0, 1, 1, 1], bool)
    h = jnp.where(started[:, None, None, None], pool[1, slot], 0.0)
    want_o, want_h = kda.step(q, k, v, jnp.exp(g), beta, h)
    assert kda_step.supported(2, 128, 128) and not kda_step.supported(2, 64, 128)
    o, after = kda_step.kda_step_in_place(jnp.asarray(pool), 1, jnp.asarray(slot),
                                          jnp.asarray(live), jnp.asarray(started), q, k, v,
                                          jnp.exp(g), beta)
    o, after = np.asarray(o), np.asarray(after)
    for t in np.flatnonzero(live):
        assert np.abs(o[t] - np.asarray(want_o[t])).max() < 1e-6
        assert np.abs(after[1, slot[t]] - np.asarray(want_h[t])).max() < 1e-6
    assert not o[2].any()
    untouched = [0, 4]  # the dead row's slot and the one nobody named
    np.testing.assert_array_equal(after[1, untouched], pool[1, untouched])
    np.testing.assert_array_equal(after[0], pool[0])


def test_a_pool_off_the_kernels_rule_falls_back_to_the_recurrence():
    """Heads of 64 keys are no whole transpose: ``kda.in_place`` says so by
    the pool's type alone, and ``step_in_place`` runs ``kda.step`` between the
    slot copies, with the kernel's contract."""
    q, k, v, g, beta = _mixer_inputs(6, 3, D=64, A=1.0)
    pool = _spoilt_pool(7, (1, 4, 2, 64, 64))
    assert not kda.in_place(jnp.asarray(pool)) and kda.in_place(jnp.zeros((1, 2, 2, 128, 128)))
    slot, live = np.array([2, 0, 1]), np.array([1, 0, 1], bool)
    started = np.array([0, 1, 1], bool)
    want_o, want_h = kda.step(q, k, v, jnp.exp(g), beta,
                              jnp.where(started[:, None, None, None], pool[0, slot], 0.0))
    o, after = kda.step_in_place(jnp.asarray(pool), 0, jnp.asarray(slot), jnp.asarray(live),
                                 jnp.asarray(started), q, k, v, jnp.exp(g), beta)
    after = np.asarray(after)
    for t in (0, 2):
        assert np.abs(np.asarray(o[t]) - np.asarray(want_o[t])).max() < 1e-6
        assert np.abs(after[0, slot[t]] - np.asarray(want_h[t])).max() < 1e-6
    np.testing.assert_array_equal(after[0, [0, 3]], pool[0, [0, 3]])


def test_the_scan_by_segment_is_the_recurrence_over_a_ragged_batch():
    """Three segments (37, 1 and 9 rows; 17 padding rows) in 64 rows cut into
    chunks of 16: segment 0 straddles three chunks (three visits), the decode
    row between goes through the kernel, segment 2 shares chunks 2 and 3 with
    both; each starts from ITS slot (sequence 2 from zeros: nothing seen) and
    leaves its final state there; a sequence without rows, the padding and the
    other layer change nothing."""
    T, S = 64, 4
    q, k, v, g, beta = _mixer_inputs(8, T, A=2.0)
    token_seq = np.array([0] * 37 + [1] + [2] * 9 + [S - 1] * 17, np.int32)
    valid = np.arange(T) < 47
    pool = _spoilt_pool(9)
    slot = np.array([4, 0, 2, 5])
    started = np.array([1, 1, 0, 1], bool)
    live = np.array([1, 1, 1, 0], bool)
    seq_ntok, seq_start = np.array([37, 1, 9, 0]), np.array([0, 37, 38, 46])
    o, after = jax.jit(kda.scan_in_place, static_argnames="rows")(
        jnp.asarray(pool), 1, jnp.asarray(slot), jnp.asarray(live), jnp.asarray(started),
        jnp.asarray(seq_start), jnp.asarray(seq_ntok), jnp.asarray(token_seq),
        jnp.asarray(valid), q, k, v, g, beta, rows=16)
    o, after = np.asarray(o), np.asarray(after)
    for seq, rows in ((0, slice(0, 37)), (1, slice(37, 38)), (2, slice(38, 47))):
        h0 = jnp.asarray(pool[1, slot[seq]] if started[seq] else np.zeros_like(pool[0, 0]))[None]
        want_o, want_h = _recurrence(q[rows], k[rows], v[rows], g[rows], beta[rows], h0)
        # the visits run in the chunk kernel since PR 55: three bf16 passes on the state's
        # products on every backend (XLA's HIGH is float32 on the CPU)
        assert np.abs(o[rows] - want_o).max() < 5e-5
        assert np.abs(after[1, slot[seq]] - want_h).max() < 5e-5
    assert not o[47:].any()
    np.testing.assert_array_equal(after[1, [1, 3, 5]], pool[1, [1, 3, 5]])
    np.testing.assert_array_equal(after[0], pool[0])
    enters, visits = kda.visits_of(seq_start, seq_ntok, live & (seq_ntok > 1), 16)
    assert list(visits) == [3, 0, 1, 0] and list(enters[[0, 2]]) == [0, 2]


# --------------------------------------------------------------- (b) engine --
def test_prefill_in_uneven_chunks_then_decode_is_the_references_full_forward(model, engine):
    cfg, params = model
    assert registry.model_cls_for(cfg) is type(engine.model)
    assert "solar_open2" in registry.supported_model_types()
    assert cfg.gqa_here == (0, ) and cfg.kda_here == (1, 2)
    assert engine.model.num_kv_layers == 1 and engine.model.min_table_bucket == 16
    kv, state_pool, conv_pool = engine._state_manager.kv_cache.cache
    assert kv.shape[0] == 1 and state_pool.shape == (2, 6, 2, 128, 128) \
        and state_pool.dtype == jnp.float32 and kda.in_place(state_pool)
    # q, k and v's three tails a sequence, 3 x 768 values folded into whole tiles
    assert conv_pool.shape == (2, 6, 8, 384) == (2, 6) + ssm.conv_slot(3, 3 * cfg.kda_width)
    assert ssm.whole_slots(conv_pool)
    prompt, feed = _ids(1, 75), _ids(2, 6)
    want = _want(cfg, params, prompt, feed)
    got, at = [], 0
    for n in (5, 24, 17, 29):  # uneven, on and off the 16-row chunks
        out = np.asarray(engine.put([0], [prompt[at:at + n]]))
        at += n
    got.append(out[0])
    for j in range(feed.size - 1):
        got.append(np.asarray(engine.put([0], [feed[j:j + 1]]))[0])
    assert np.abs(np.stack(got) - want[:-1]).max() < TOL
    looped = np.asarray(engine.decode_loop([0], [feed[-1:]], 4))
    assert int(looped[0][0]) == int(want[-1].argmax())
    # the loop's steps continued the state: its next tokens are the reference's greedy ones
    longer = np.concatenate([prompt, feed, looped[0][:3]])
    again = _reference_rows(cfg, params, longer, np.arange(longer.size - 3, longer.size))
    assert [int(t) for t in looped[0][1:]] == [int(r.argmax()) for r in again]
    assert {key[2] for key in engine.lowerable_callables()["forward"]} == {16}
    engine.flush(0)


# ------------------------------------------------- (c) continuous batching --
def test_one_prefilling_while_two_decode_each_equal_to_its_solo_run(model, engine):
    cfg, params = model
    prompts = [_ids(10, 5), _ids(11, 7), _ids(12, 70)]
    feeds = [_ids(20, 8), _ids(21, 8), _ids(22, 2)]
    want = [_want(cfg, params, p, f) for p, f in zip(prompts, feeds)]
    got = [[], [], []]
    for u in (0, 1):  # the two short ones first: they decode while the long one prefills
        got[u].append(np.asarray(engine.put([u], [prompts[u]]))[0])
    at, step = 0, 0
    while at < prompts[2].size:
        n = min(23, prompts[2].size - at)
        uids, toks = [0, 2, 1], [feeds[0][step:step + 1], prompts[2][at:at + n],
                                 feeds[1][step:step + 1]]
        out = np.asarray(engine.put(uids, toks))
        got[0].append(out[0]), got[1].append(out[2])
        at += n
        step += 1
        if at == prompts[2].size:
            got[2].append(out[1])
    assert step == 4
    # then all three by decode_loop: each sequence's first token from ITS row
    looped = np.asarray(engine.decode_loop([0, 1, 2], [feeds[0][step:step + 1],
                                                       feeds[1][step:step + 1],
                                                       feeds[2][:1]], 4))
    for u in (0, 1, 2):
        rows = np.stack(got[u])
        assert np.abs(rows - want[u][:rows.shape[0]]).max() < TOL
        assert int(looped[u][0]) == int(want[u][rows.shape[0]].argmax())
        engine.flush(u)


# ----------------------------------------------------------------- (d) slots --
def test_a_slot_reused_after_flush_starts_from_zero_and_padding_writes_nothing(model, engine):
    manager = engine._state_manager
    assert manager.free_slots == 6

    def pools():
        return [np.asarray(p) for p in manager.kv_cache.cache[1:]]

    prompt, other = _ids(30, 30), _ids(31, 25)
    before = pools()
    first = np.asarray(engine.put([7], [prompt]))
    slot = manager.get_sequence(7).state_slot
    held = pools()
    assert np.abs(held[0][:, slot] - before[0][:, slot]).max() > 0
    # rows of the bucket beyond the one live sequence, and the padding tokens, wrote nothing
    for was, now in zip(before, held):
        np.testing.assert_array_equal(np.delete(now, slot, axis=1), np.delete(was, slot, axis=1))
    # nor do the seven padding rows of a decode_loop chunk's steps, which move the one slot
    engine.decode_loop([7], [_ids(32, 1)], 4)
    after = pools()
    assert np.abs(after[0][:, slot] - held[0][:, slot]).max() > 0
    for was, now in zip(before, after):
        np.testing.assert_array_equal(np.delete(now, slot, axis=1), np.delete(was, slot, axis=1))
    engine.flush(7)
    assert manager.free_slots == 6 and manager.get_sequence(7) is None
    engine.put([8], [other])  # takes the slot 7 held, its old state still in it
    assert manager.get_sequence(8).state_slot == slot
    engine.flush(8)
    again = np.asarray(engine.put([9], [prompt]))
    assert manager.get_sequence(9).state_slot == slot
    assert np.abs(again - first).max() < 1e-6
    engine.flush(9)


def test_the_counts_say_what_the_delta_rule_did(engine):
    """``kda_rows`` / ``kda_segments`` a step; ``kda_chunk_visits``, the visits
    of the chunked form (a 25-row segment from row 0 has rows in two 16-row
    chunks; a one-row segment none) and ``kda_chunk_visits_in_kernel``, those
    the chunk kernel made (all of them on this pool, none on a pool off its
    rule); ``kda_rows_in_place``, the rows the
    recurrence's kernel served (a ``put``'s one-row segments, every row of a
    ``decode_loop`` chunk); the state group's slots under the hybrid families'
    names."""
    engine.put([0, 1], [_ids(40, 25), _ids(41, 1)])
    put = engine.model.batch_counts(engine._batch, 1)
    want = {"kda_rows": 26 * 2, "kda_segments": 2 * 2, "kda_chunk_visits": 2 * 2,
            "kda_chunk_visits_in_kernel": 2 * 2, "kda_rows_in_place": 1 * 2,
            "ssm_slots_live": 2, "ssm_slots_total": 6}
    assert {k: put[k] for k in want} == want
    kv = engine._state_manager.kv_cache
    held = kv.cache
    try:  # a pool off the kernels' rule: the visits are made, none in a kernel
        kv._cache = (held[0], held[1].astype(jnp.bfloat16), *held[2:])
        off = engine.model.batch_counts(engine._batch, 1)
    finally:
        kv._cache = held
    assert (off["kda_chunk_visits"], off["kda_chunk_visits_in_kernel"],
            off["kda_rows_in_place"]) == (2 * 2, 0, 0)
    engine.decode_loop([0, 1], [_ids(42, 1), _ids(43, 1)], 4)
    chunk = engine.model.batch_counts(engine._batch, 4)
    assert chunk["kda_rows"] == chunk["kda_rows_in_place"] == chunk["kda_segments"] == 2 * 2 * 4
    assert chunk["kda_chunk_visits"] == chunk["kda_chunk_visits_in_kernel"] == 0
    counts = engine.model.dispatch_counts(8, 2, 4)
    assert counts["moe_path"] == "grouped" and counts["moe_assignments"] == 2 * 4 * 3 * 4
    assert engine.model.moe_count_names == ("moe_banks", "moe_assignments_local",
                                             "moe_visits", "moe_rows_walked")
    engine.flush(0), engine.flush(1)


def test_the_decode_loop_program_is_pinned(engine):
    """A ``decode_loop`` chunk's traced program (addresses blanked), so that a
    later change to shared code that moves this family's program says so."""
    assert decode_loop_hash(engine.model) == DECODE_LOOP_HASH



# ------------------------------------------------------------- (e) the share --
def test_the_four_shares_add_up_to_the_uncut_layer(model, engine):
    """At 16 experts in 4 shares: the four ranks' routed parts plus the shared
    expert ONCE are the reference's uncut expert layer; and the served layer's
    part is its rank's."""
    whole = so2.SolarOpen2Config.tiny(dtype=jnp.float32)
    params = so2.init_params(whole, rng=jax.random.PRNGKey(4))[1]
    moe = params["layers_1"]["mlp"]
    u = jnp.asarray(np.random.default_rng(6).normal(size=(24, whole.hidden_size)), jnp.float32)
    routed = dict(top_k=whole.num_experts_per_tok, norm=True, scale=whole.routed_scaling_factor)
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.experts(u, moe, first_held=0, **routed)
        shared = reference.swiglu(u, moe["shared_experts"])
        parts = []
        for rank in range(4):
            held = slice(4 * rank, 4 * rank + 4)
            mine = dict(moe, experts={k: v[held] for k, v in moe["experts"].items()})
            part, _ = reference.experts(u, mine, first_held=4 * rank, **routed)
            parts.append(np.asarray(part - shared))
        assert all(np.abs(p).max() > 1e-3 for p in parts)  # every rank is routed to
        assert np.abs(sum(parts) + np.asarray(shared) - np.asarray(uncut)).max() < 1e-5
    # the served layer of rank 1 (the fixture's) computes rank 1's part
    cfg, mine = model
    served = engine.model
    lp = mine["layers_1"]
    x = jnp.asarray(np.random.default_rng(7).normal(size=(8, cfg.hidden_size)), jnp.float32)
    batch = {"token_valid": jnp.ones(8, bool)}
    got = np.asarray(jax.jit(lambda lp, x: served._ffn_phase(lp, 1, x, batch) - x)(lp, x))
    with jax.default_matmul_precision("highest"):
        h = reference.rms_norm(x, lp["post_attention_layernorm"]["weight"], cfg.rms_norm_eps)
        want, _ = reference.experts(h, lp["mlp"], first_held=4, **routed)
    assert np.abs(got - np.asarray(want)).max() < TOL


# ------------------------------------- (f) what the controls leave out, seen --
@pytest.mark.parametrize("variant", ["beta_one", "no_decay"])
def test_betas_factor_and_the_decay_each_change_the_logits(model, variant):
    """The CPU twins of ``benchmark/tools/controls_kda.py``'s ``beta_one`` and
    ``no_decay``: the reference with the piece left out is far from the served
    logits by the comparison's measure (a fiftieth of the largest logit and more), where
    the reference as written is within 1e-4."""
    cfg, params = model
    prompt, feed = _ids(1, 75), _ids(2, 2)
    want = _want(cfg, params, prompt, feed)
    spoilt = _want(cfg, params, prompt, feed, variant=variant)
    assert np.abs(spoilt - want).max() > 0.02 * np.abs(want).max() > 200 * TOL


# ------------------------------------------------------------- (g) refusals --
@pytest.mark.parametrize("keys, said", [
    (dict(use_rope=True), "use_rope"),
    (dict(kda_use_full_proj=True), "kda_use_full_proj"),
    (dict(linear_num_kv_heads=1), "num_kv_heads"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(tie_word_embeddings=True), "tied embeddings"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_is_not_implemented_is_refused_by_name(keys, said):
    with pytest.raises(NotImplementedError, match=said):
        so2.SolarOpen2Config.tiny(**keys)


def test_a_model_of_one_kind_of_layer_and_a_share_that_does_not_divide_are_refused(model):
    cfg, params = model
    with pytest.raises(ValueError, match="does not divide"):
        so2.SolarOpen2Config.tiny(experts_held=5)
    groups.initialize_mesh(force=True)
    with pytest.raises(NotImplementedError, match="would leave one empty"):
        engine_of(dataclasses.replace(cfg, gqa_layers=(7, )), params)
