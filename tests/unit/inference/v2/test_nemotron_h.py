"""Nemotron-H served through ``build_engine`` (PR 43): the two forms of the
Mamba-2 scan against each other and the token-by-token reference; prefill in
uneven chunks, ``put`` and ``decode_loop`` through the per-sequence state group
against the plain float32 reference's full forward; continuous batching; the
slots (reuse, padding, admission); the shares of an expert layer adding up to
the uncut layer; and each refusal by its message. Since PR 44 a ``decode_loop``
step's recurrence runs in the pool where the pool is on the kernel's shape rule
(``ssm.in_place``): ``model`` (state 16 wide) keeps covering the fallback,
``model_in_place`` (state 128 wide; the kernel in interpret mode) runs three of
the engine's tests beside it and says so in a chunk's counts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import nemotron_h as reference
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.model_implementations import registry
from deepspeed_tpu.inference.v2.model_implementations.nemotron_h_v2 import relu2
from deepspeed_tpu.inference.v2.modules import ssm
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig, MemoryConfig)
from deepspeed_tpu.inference.v2.scheduling_utils import SchedulingError, SchedulingResult
from deepspeed_tpu.models import nemotron_h as nh
from deepspeed_tpu.utils import groups
from tests.unit.inference.v2.program_hashes import decode_loop_hash

BLOCK = 16
TOL = 1e-4


def sizes_of(cfg):
    """The configuration-file view of a program config, as the reference reads it."""
    sizes = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    sizes["n_routed_experts"] = cfg.experts_held
    sizes["deployment_share"] = {"routed_over": cfg.n_routed_experts,
                                 "experts_held": cfg.experts_held,
                                 "expert_rank": cfg.expert_rank}
    return sizes


def engine_of(cfg, params, kernel=False, blocks=96, slots=6, **overrides):
    groups.initialize_mesh(force=True)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                          size=blocks),
                               max_context=256, max_ragged_batch_size=64,
                               max_ragged_sequence_count=8, max_tracked_sequences=slots)
    return build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=BLOCK, use_paged_kernel=kernel,
        expert_parallel={"capacity_factor": 4.0}, **overrides))


@pytest.fixture(scope="module")
def model():
    cfg = nh.NemotronHConfig.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1)
    return cfg, nh.init_params(cfg, rng=jax.random.PRNGKey(3))[1]


@pytest.fixture(scope="module")
def model_in_place():
    """As ``model`` with a state of one lane tile: the pool is on the rule."""
    cfg = nh.NemotronHConfig.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1,
                                  ssm_state_size=128)
    return cfg, nh.init_params(cfg, rng=jax.random.PRNGKey(3))[1]


BOTH_POOLS = pytest.mark.parametrize("which", ["model", "model_in_place"],
                                     ids=["fallback-state-16", "in-place-state-128"])


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def _want(cfg, params, prompt, feed):
    return np.asarray(reference.forward_logits(
        params, sizes_of(cfg), np.concatenate([prompt, feed]),
        rows=np.arange(prompt.size - 1, prompt.size + feed.size)))


# ------------------------------------------------------------ (a) one mixer --
def _mixer_inputs(seed, T, H=8, P=8, G=2, N=16):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(T, H, P)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(T, H)) - 2)).astype(np.float32)
    A = -rng.uniform(1, 16, size=H).astype(np.float32)
    B = rng.normal(size=(T, G, N)).astype(np.float32)
    C = rng.normal(size=(T, G, N)).astype(np.float32)
    return x, dt, A, B, C


def _recurrence(x, dt, A, B, C, h):
    """``ssm.step`` token by token over ONE sequence."""
    ys = []
    for t in range(x.shape[0]):
        y, h = ssm.step(x[t:t + 1], dt[t:t + 1], A, B[t:t + 1], C[t:t + 1], h)
        ys.append(y[0])
    return np.stack(ys), h


def test_the_chunked_form_is_the_recurrence_over_a_ragged_batch():
    """Three segments (17, 1 and 9 rows; five padding rows) in 32 rows cut into
    chunks of 8: segment 0 straddles three chunks, a decode row rides between;
    each starts from ITS state and leaves its final state; a sequence without
    rows and the padding change nothing."""
    T, S = 32, 4
    x, dt, A, B, C = _mixer_inputs(0, T)
    token_seq = np.array([0] * 17 + [1] + [2] * 9 + [S - 1] * 5, np.int32)
    valid = np.arange(T) < 27
    h0 = np.random.default_rng(1).normal(size=(S, 8, 8, 16)).astype(np.float32)
    onehot = ssm.segments(jnp.asarray(token_seq), jnp.asarray(valid), S)
    y, h = ssm.scan_ragged(x, dt, A, B, C, jnp.asarray(h0), onehot, chunk=8)
    for seq, rows in ((0, slice(0, 17)), (1, slice(17, 18)), (2, slice(18, 27))):
        want_y, want_h = _recurrence(x[rows], dt[rows], A, B[rows], C[rows], h0[seq:seq + 1])
        assert np.abs(np.asarray(y[rows]) - want_y).max() < TOL
        assert np.abs(np.asarray(h[seq]) - np.asarray(want_h[0])).max() < TOL
    assert np.array_equal(np.asarray(h[3]), h0[3])
    # one chunk for the whole batch is the same scan
    y1, h1 = ssm.scan_ragged(x, dt, A, B, C, jnp.asarray(h0), onehot, chunk=32)
    assert np.abs(np.asarray(y1[:27]) - np.asarray(y[:27])).max() < TOL
    assert np.abs(np.asarray(h1) - np.asarray(h)).max() < TOL


def test_one_mixer_in_both_forms_is_the_references(model):
    """The reference's Mamba-2 mixer (token by token, ``lax.scan``) of one
    sequence against the served phase fed the same rows in two ``put``-shaped
    steps (the chunked form, state and tail carried) and then row by row (the
    recurrence)."""
    cfg, params = model
    engine = engine_of(cfg, params)
    served = engine.model
    mp = params["layers_0"]["mixer"]
    u = jnp.asarray(np.random.default_rng(5).normal(size=(30, cfg.hidden_size)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.mamba(u, mp, heads=cfg.mamba_num_heads,
                                          head_dim=cfg.mamba_head_dim, groups=cfg.n_groups,
                                          state=cfg.ssm_state_size, eps=cfg.layer_norm_epsilon))
    pools = tuple(engine._state_manager.kv_cache.cache[1:])
    S = 8

    def batch_of(rows, seen, one_token):
        n = rows.shape[0]
        T = 32 if not one_token else 8
        pad = jnp.zeros((T - n, cfg.hidden_size), jnp.float32)
        seq_col = lambda v: np.array([v] + [0] * (S - 1), np.int32)  # noqa: E731
        return jnp.concatenate([rows, pad]), dict(
            token_seq=np.array([0] * n + [S - 1] * (T - n), np.int32),
            token_valid=np.arange(T) < n, seq_seen=seq_col(seen), seq_ntok=seq_col(n),
            last_tok=seq_col(n - 1), seq_valid=np.arange(S) < 1,
            state_slot=np.array([2] + [6] * (S - 1), np.int32), one_token_rows=one_token)

    got, seen = [], 0
    for n in (13, 9):  # the chunked form: 8-row chunks, the second step carries the first's
        h, batch = batch_of(u[seen:seen + n], seen, False)
        out, pools = served._mamba_phase(mp, 0, h, pools, batch)
        got.append(np.asarray(out[:n]))
        seen += n
    while seen < 30:  # the recurrence
        h, batch = batch_of(u[seen:seen + 1], seen, True)
        out, pools = served._mamba_phase(mp, 0, h, pools, batch)
        got.append(np.asarray(out[:1]))
        seen += 1
    assert np.abs(np.concatenate(got) - want).max() < TOL
    # only slot 2 of block 0 was written
    ssm_pool = np.asarray(pools[0])
    assert np.abs(ssm_pool[0, 2]).max() > 0 and not ssm_pool[1:].any()
    assert not np.delete(ssm_pool[0], 2, axis=0).any()


# --------------------------------------------------------------- (b) engine --
@pytest.mark.parametrize("which, kernel", [("model", False), ("model", True),
                                           ("model_in_place", False)],
                         ids=["xla", "pallas-interpret", "xla-state-in-place"])
def test_prefill_in_uneven_chunks_then_decode_is_the_references_full_forward(request, which,
                                                                             kernel):
    cfg, params = request.getfixturevalue(which)
    engine = engine_of(cfg, params, kernel)
    assert ssm.in_place(engine._state_manager.kv_cache.cache[1], cfg.n_groups) \
        == (which == "model_in_place")
    assert registry.model_cls_for(cfg) is type(engine.model)
    assert "nemotron_h" in registry.supported_model_types()
    assert engine.model.num_kv_layers == 1 and engine.model.min_table_bucket == 16
    kv, ssm_pool, conv_pool = engine._state_manager.kv_cache.cache
    assert kv.shape[0] == 1 and ssm_pool.shape == (3, 6, 8, 8, cfg.ssm_state_size) \
        and ssm_pool.dtype == jnp.float32
    assert cfg.conv_dim == 64 + 4 * cfg.ssm_state_size
    # the tails' slot as ``ssm.conv_slot`` states it: 3 x 128 values are no tile and
    # stay [3, C] (XLA's gather and scatter); 3 x 576 fold into [8, 256] with 320
    # zeros behind them (the slot-copy kernels, interpret mode, under the mesh)
    slot = {"model": (3, 128), "model_in_place": (8, 256)}[which]
    assert conv_pool.shape == (3, 6) + slot == (3, 6) + ssm.conv_slot(3, cfg.conv_dim)
    assert ssm.whole_slots(conv_pool) == (which == "model_in_place")
    prompt, feed = _ids(1, 75), _ids(2, 6)
    want = _want(cfg, params, prompt, feed)
    got, at = [], 0
    for n in (5, 24, 17, 29):  # uneven, on and off the 8-row scan chunks
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
    again = np.asarray(reference.forward_logits(params, sizes_of(cfg), longer,
                                                rows=np.arange(longer.size - 3, longer.size)))
    assert [int(t) for t in looped[0][1:]] == [int(r.argmax()) for r in again]
    assert {key[2] for key in engine.lowerable_callables()["forward"]} == {16}


# ------------------------------------------------- (c) continuous batching --
@BOTH_POOLS
def test_one_prefilling_while_two_decode_each_equal_to_its_solo_run(request, which):
    cfg, params = request.getfixturevalue(which)
    prompts = [_ids(10, 9), _ids(11, 14), _ids(12, 70)]
    feeds = [_ids(20, 8), _ids(21, 8), _ids(22, 2)]
    want = [_want(cfg, params, p, f) for p, f in zip(prompts, feeds)]
    engine = engine_of(cfg, params)
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
                                                       feeds[2][:1]], 2))
    for u in (0, 1, 2):
        rows = np.stack(got[u])
        assert np.abs(rows - want[u][:rows.shape[0]]).max() < TOL
        assert int(looped[u][0]) == int(want[u][rows.shape[0]].argmax())


# ------------------------------------------------------------- (d) (e) slots --
@BOTH_POOLS
def test_a_slot_reused_after_flush_starts_from_zero_and_padding_writes_nothing(request, which):
    cfg, params = request.getfixturevalue(which)
    engine = engine_of(cfg, params, slots=2)
    manager = engine._state_manager
    prompt, other = _ids(30, 40), _ids(31, 33)
    first = np.asarray(engine.put([7], [prompt]))
    slot = manager.get_sequence(7).state_slot
    pools = [np.asarray(p) for p in manager.kv_cache.cache[1:]]
    assert np.abs(pools[0][:, slot]).max() > 0
    # rows of the bucket beyond the one live sequence, and the 24 padding tokens, wrote nothing
    assert not np.delete(pools[0], slot, axis=1).any() and not np.delete(pools[1], slot, axis=1).any()
    # nor do the seven padding rows of a decode_loop chunk's steps, which move the one slot
    engine.decode_loop([7], [_ids(32, 1)], 3)
    after = [np.asarray(p) for p in manager.kv_cache.cache[1:]]
    assert np.abs(after[0][:, slot] - pools[0][:, slot]).max() > 0
    assert not np.delete(after[0], slot, axis=1).any() and not np.delete(after[1], slot, axis=1).any()
    engine.flush(7)
    assert manager.free_slots == 2 and manager.get_sequence(7) is None
    engine.put([8], [other])  # takes the slot 7 held, its old state still in it
    assert manager.get_sequence(8).state_slot == slot
    engine.flush(8)
    again = np.asarray(engine.put([9], [prompt]))
    assert manager.get_sequence(9).state_slot == slot
    assert np.abs(again - first).max() < 1e-6


@pytest.mark.parametrize("which, share", [("model", 0), ("model_in_place", 1)],
                         ids=["fallback-state-16", "in-place-state-128"])
def test_a_chunks_counts_say_which_rows_the_kernel_served(request, which, share):
    """``ssm_rows_in_place`` beside ``ssm_tokens`` on a ``decode_loop`` chunk's
    counts: every row where the pool is on the kernel's rule, 0 where it falls
    back; a ``put``'s counts do not have the key. ``ssm_segments_in_place``
    beside ``ssm_segments`` on a ``put``'s: every segment where a slot of the
    pool is whole tiles (``ssm.whole_slots``), 0 where XLA scatters it.
    ``ssm_conv_rows_in_place`` (PR 53) on both: every segment where the CONV
    pool's slot is whole tiles (the tails folded: ``ssm.conv_slot``), 0 where
    XLA gathers and scatters them."""
    cfg, params = request.getfixturevalue(which)
    engine = engine_of(cfg, params)
    engine.put([0, 1], [_ids(40, 9), _ids(41, 5)])
    put = engine.model.batch_counts(engine._batch)
    assert put["ssm_tokens"] == 14 * 3 and "ssm_rows_in_place" not in put
    assert ssm.whole_slots(engine._state_manager.kv_cache.cache[1]) == bool(share)
    assert put["ssm_segments"] == 2 * 3 and put["ssm_segments_in_place"] == share * 2 * 3
    assert ssm.whole_slots(engine._state_manager.kv_cache.cache[2]) == bool(share)
    assert put["ssm_conv_rows_in_place"] == share * 2 * 3
    engine.decode_loop([0, 1], [_ids(42, 1), _ids(43, 1)], 4)
    chunk = engine.model.batch_counts(engine._batch, 4)
    assert chunk["ssm_tokens"] == 2 * 3 * 4 and chunk["ssm_segments"] == 2 * 3 * 4
    assert chunk["ssm_conv_rows_in_place"] == share * chunk["ssm_segments"]
    assert chunk["ssm_rows_in_place"] == share * chunk["ssm_tokens"]
    assert engine.model.batch_counts(engine._batch, 1)["ssm_rows_in_place"] == share * 2 * 3


@pytest.mark.parametrize("which, share", [("model", 0), ("model_in_place", 1)],
                         ids=["fallback-state-16", "in-place-state-128"])
def test_a_puts_counts_say_which_segments_were_scanned_in_their_slot(request, which, share):
    """``ssm_segments_scanned_in_place`` beside ``ssm_segments`` on a ``put``'s
    counts: every segment where the pool is on ``ssm.in_place``'s rule (the scan
    goes by segment, in the pool), 0 where it falls back to ``scan_ragged`` on
    every state; every arg the spans had keeps its value."""
    cfg, params = request.getfixturevalue(which)
    engine = engine_of(cfg, params)
    engine.put([0, 1], [_ids(40, 9), _ids(41, 5)])
    pool = engine._state_manager.kv_cache.cache[1]
    assert ssm.in_place(pool, cfg.n_groups) == bool(share)
    put = engine.model.batch_counts(engine._batch)
    assert put["ssm_segments_scanned_in_place"] == share * put["ssm_segments"] == share * 2 * 3
    had = {"ssm_tokens": 14 * 3, "ssm_segments": 2 * 3, "ssm_slots_live": 2,
           "ssm_segments_in_place": int(ssm.whole_slots(pool)) * 2 * 3}
    assert {k: put[k] for k in had} == had and put["ssm_slots_total"] == pool.shape[1]
    assert "ssm_rows_in_place" not in put
    engine.put([0], [_ids(42, 1)])  # one row: the same rule
    put = engine.model.batch_counts(engine._batch)
    assert put["ssm_segments_scanned_in_place"] == share * put["ssm_segments"] == share * 3


def test_the_folded_tails_are_the_three_rows_bit_for_bit(model_in_place, monkeypatch):
    """The same steps through an engine whose conv pool holds the tails folded
    into whole tiles (``ssm.conv_slot``: the slot-copy kernels) and through one
    told to keep ``[K - 1, C]`` (XLA's gather and scatter): every logit, every
    token, the state pool and the tails themselves come out bit for bit — a
    sequence without rows in a step, a padding row, a new sequence in a slot
    another one left, and the slots nobody held included."""
    cfg, params = model_in_place

    def run():
        engine = engine_of(cfg, params, slots=4)
        outs = [engine.put([0, 1], [_ids(50, 9), _ids(51, 5)]),
                engine.put([1, 2], [_ids(52, 1), _ids(53, 12)])]  # 0 has no row: keeps its tails
        engine.flush(1)
        outs.append(engine.put([3, 0], [_ids(54, 2), _ids(55, 1)]))  # 3 in 1's slot: from zeros
        outs.append(engine.decode_loop([0, 2, 3], [_ids(56, 1), _ids(57, 1), _ids(58, 1)], 3))
        pools = [np.asarray(p) for p in engine._state_manager.kv_cache.cache[1:]]
        engine.close()
        return [np.asarray(o) for o in outs], pools

    outs, (state, tails) = run()
    monkeypatch.setattr(ssm, "conv_slot", lambda rows, channels: (rows, channels))
    plain_outs, (plain_state, plain_tails) = run()
    assert tails.shape == (3, 4, 8, 256) and plain_tails.shape == (3, 4, 3, cfg.conv_dim)
    for got, want in zip(outs, plain_outs):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(state, plain_state)
    np.testing.assert_array_equal(
        tails.reshape(12, 8, 256),
        np.asarray(ssm.fold_tails(jnp.asarray(plain_tails.reshape(12, 3, -1)), (8, 256))))
    assert np.abs(tails).max() > 0


def test_the_decode_loop_program_is_the_one_it_was_before_the_scan_went_by_segment(model_in_place):
    """PR 49 changed what a ``put`` step's Mamba-2 mixers run and nothing a
    ``decode_loop`` chunk runs: its traced program (addresses blanked) hashes
    to what it did at the commit before. Re-pinned by PR 53, which changed it
    on purpose: the convolution's tails leave and enter their folded slots by
    ``ssm.load`` / ``ssm.store_in_place`` (every other family's recorded
    programs, ``test_one_group_programs.py`` and ``test_afmoe.py``, hold).
    Re-pinned by PR 60: the chunk's count of routed work holds the grouped
    kernel's visits beside the banks and the local assignments. Re-pinned by
    PR 64: and the sorted rows the layer walked (no window at this share: the
    bucket's every row, a constant)."""
    cfg, params = model_in_place
    assert decode_loop_hash(engine_of(cfg, params).model) == \
        "22181d5ad18cbf6508ddbd231a31bb9341986d644d87e3955cae2d27dbac7bec"


def test_admission_stops_at_the_last_free_slot(model):
    """A slot a tracked sequence, ``max_tracked_sequences`` of them: the limit
    on tracked sequences, which every admission path counts, IS the count of
    free slots."""
    cfg, params = model
    engine = engine_of(cfg, params, slots=3)
    one = [_ids(40, 1)]
    for uid in (0, 1):
        engine.put([uid], one)
    assert engine._state_manager.free_slots == 1
    assert engine._state_manager.num_slots - engine._state_manager.n_tracked_sequences == 1
    assert engine.can_schedule([2], [1]) == SchedulingResult.Success
    assert engine.can_schedule([2, 3], [1, 1]) == SchedulingResult.EngineSequenceLimitExceeded
    assert engine.can_schedule([0, 1, 2], [1, 1, 1]) == SchedulingResult.Success  # two hold theirs
    engine.put([2], one)
    assert engine.query(3, 1, engine.free_blocks) == (0, 0)
    with pytest.raises(SchedulingError):
        engine.put([3], one)
    with pytest.raises(SchedulingError):
        engine.decode_loop([0, 3], [one[0], one[0]], 2)
    assert engine._state_manager.get_sequence(3) is None  # nothing half-made
    assert engine._state_manager.get_sequence(0).in_flight_tokens == 0
    engine.flush(1)
    assert engine.can_schedule([3], [1]) == SchedulingResult.Success
    engine.put([3], one)
    assert sorted(engine._state_manager.get_sequence(u).state_slot for u in (0, 2, 3)) == [0, 1, 2]


# ------------------------------------------------------------- (f) the share --
def test_the_shares_add_up_to_the_uncut_layer():
    """Rank 0's part + rank 1's part, the shared expert counted once, are the
    reference's uncut expert block; and the served layer's part is each
    rank's."""
    whole = nh.NemotronHConfig.tiny(dtype=jnp.float32)
    params = nh.init_params(whole, rng=jax.random.PRNGKey(4))[1]
    moe = params["layers_1"]["mixer"]
    u = jnp.asarray(np.random.default_rng(6).normal(size=(24, whole.hidden_size)), jnp.float32)
    routed = dict(top_k=whole.num_experts_per_tok, norm=True, scale=whole.routed_scaling_factor)
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.experts(u, moe, first_held=0, **routed)
        shared = reference.mlp(u, moe["shared_experts"])
        parts = []
        for rank in (0, 1):
            held = slice(4 * rank, 4 * rank + 4)
            mine = dict(moe, experts={k: v[held] for k, v in moe["experts"].items()})
            part, _ = reference.experts(u, mine, first_held=4 * rank, **routed)
            parts.append(part)
            layer = RaggedMoE(8, top_k=3, capacity_factor=4.0, score_func="sigmoid",
                              route_scale=2.5, held=4, first_held=4 * rank)
            served = layer(u, moe["gate"], mine["experts"]["wi"], mine["experts"]["wo"],
                           activation=relu2, select_bias=moe["e_score_correction_bias"])
            assert np.abs(np.asarray(served + shared) - np.asarray(part)).max() < 1e-5
    total = parts[0] + parts[1] - shared  # the shared expert once
    assert np.abs(np.asarray(total) - np.asarray(uncut)).max() < 1e-5 * float(jnp.abs(uncut).max())
    # the banks' padding lanes are zero, and the published width is what an expert uses
    assert whole.bank_width == 128 and moe["experts"]["wi"].shape == (8, 64, 128)
    assert not np.asarray(moe["experts"]["wi"][:, :, 48:]).any()
    assert not np.asarray(moe["experts"]["wo"][:, 48:, :]).any()
    assert np.asarray(moe["experts"]["wi"][:, :, :48]).all()


# ------------------------------------------------------------ (g) refusals --
@pytest.mark.parametrize("change, said", [
    (dict(mamba_hidden_act="gelu"), "mamba_hidden_act"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(mamba_proj_bias=True), "the only bias that is implemented"),
    (dict(attention_bias=True), "the only bias that is implemented"),
    (dict(n_group=2, topk_group=1), "a group limit"),
    (dict(tie_word_embeddings=True), "tied embeddings"),
    (dict(sliding_window=128), "sliding_window"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_config_refuses_what_is_not_implemented_by_name(change, said):
    with pytest.raises(NotImplementedError, match=said):
        nh.NemotronHConfig.tiny(**change)


def test_the_config_holds_the_pattern_to_the_depth_and_the_share_to_the_experts():
    with pytest.raises(ValueError, match="names 7 blocks"):
        nh.NemotronHConfig.tiny(num_hidden_layers=6)
    with pytest.raises(ValueError, match="a block is M"):
        nh.NemotronHConfig.tiny(hybrid_override_pattern="MEMAEME")
    with pytest.raises(ValueError, match="does not divide"):
        nh.NemotronHConfig.tiny(experts_held=3)
    dense = nh.NemotronHConfig.tiny(hybrid_override_pattern="M-M*EME")  # a dense block is served
    assert dense.layers_of("-") == (1, ) and dense.layers_of("M") == (0, 2, 5)


def test_a_dense_block_is_served(model):
    cfg = nh.NemotronHConfig.tiny(dtype=jnp.float32, hybrid_override_pattern="M-M*EME")
    params = nh.init_params(cfg, rng=jax.random.PRNGKey(7))[1]
    engine = engine_of(cfg, params)
    prompt = _ids(50, 21)
    want = _want(cfg, params, prompt, _ids(51, 1))
    assert np.abs(np.asarray(engine.put([0], [prompt]))[0] - want[0]).max() < TOL
    with pytest.raises(NotImplementedError, match="without an attention block"):
        engine_of(nh.NemotronHConfig.tiny(hybrid_override_pattern="MEMMEME"), params)


def test_banks_at_the_published_width_are_padded_to_lane_tiles_where_the_model_is_built(model):
    """A checkpoint brings an expert 48 wide, not the 128 lanes ``init_params``
    makes: the serving model pads the banks once, and serves the same logits."""
    cfg, params = model
    narrow = dict(params)
    for li in cfg.layers_of("E"):
        banks = params[f"layers_{li}"]["mixer"]["experts"]
        cut = dict(banks, wi=banks["wi"][:, :, :48], wo=banks["wo"][:, :48, :])
        narrow[f"layers_{li}"] = dict(params[f"layers_{li}"],
                                      mixer=dict(params[f"layers_{li}"]["mixer"], experts=cut))
    prompt = _ids(55, 19)
    engine, held = engine_of(cfg, narrow), engine_of(cfg, params)
    for li in cfg.layers_of("E"):
        banks = engine.model._params[f"layers_{li}"]["mixer"]["experts"]
        assert banks["wi"].shape[-1] == banks["wo"].shape[-2] == cfg.bank_width == 128
    assert held.model._params is params  # nothing to pad: the tree as given
    assert np.array_equal(np.asarray(engine.put([0], [prompt])),
                          np.asarray(held.put([0], [prompt])))
    with pytest.raises(NotImplementedError, match="a gated bank"):
        RaggedMoE.banks_in_lane_tiles(jnp.zeros((2, 8, 96)), jnp.zeros((2, 48, 8)))


def test_the_pools_are_refused_by_name_where_they_cannot_be_held(model, monkeypatch):
    """The default ``max_tracked_sequences`` (2048) is 25 GB of state at the
    published widths: a named error with the bytes, not the device's OOM; and a
    ``model`` mesh axis, which would have to split the pools by head."""
    from deepspeed_tpu.accelerator import get_accelerator
    cfg, params = model
    accelerator = get_accelerator()
    monkeypatch.setattr(type(accelerator), "total_memory", lambda self, i=None: 2**20)
    monkeypatch.setattr(type(accelerator), "available_memory", lambda self, i=None: 2**18)
    with pytest.raises(ValueError, match=r"of 64 slots \(state_manager.max_tracked_sequences.*"
                                         r"lower max_tracked_sequences"):
        engine_of(cfg, params, slots=64)
    monkeypatch.undo()
    try:
        from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
        served = engine_of(cfg, params).model  # engine_of builds its own mesh, without the axis
        groups.initialize_mesh(model_parallel_size=2, force=True)
        with pytest.raises(NotImplementedError, match="on a mesh with model=2"):
            BlockedKVCache(served.kv_cache_config(),
                           MemoryConfig(mode=AllocationMode.ALLOCATE, size=8))
    finally:
        groups.initialize_mesh(force=True)


def test_the_state_manager_refuses_what_moves_block_tables_by_name(model):
    cfg, params = model
    engine = engine_of(cfg, params)
    engine.put([0], [_ids(60, 20)])
    said = "per-sequence state group"
    with pytest.raises(NotImplementedError, match=said):
        engine.offload_sequence(0)
    with pytest.raises(NotImplementedError, match=said):
        engine.export_sequence(0)
    with pytest.raises(NotImplementedError, match=said):
        engine._state_manager.import_sequence({"uid": 5, "seen_tokens": 0, "kv": None})
    with pytest.raises(NotImplementedError, match=said):
        engine._state_manager.create_cached_sequence(5, [], 0)
    with pytest.raises(NotImplementedError, match=said):
        engine._state_manager.kv_cache.fork_blocks([0])
    with pytest.raises(NotImplementedError, match=said):
        engine.rollback(0, 1)
    with pytest.raises(NotImplementedError, match=said):
        engine.compact_accepted(0, 3, [2])
    from deepspeed_tpu.inference.v2.spec.tree import TokenTree
    with pytest.raises(NotImplementedError, match=said):
        engine.verify_tree([0], [TokenTree.chain(_ids(61, 3))])
    assert engine._state_manager.get_sequence(0).in_flight_tokens == 0  # refused before any change


@pytest.mark.parametrize("serving, said", [
    (dict(prefix_cache={"enabled": True}), "prefix_cache cannot serve a per-sequence state"),
    (dict(kv_tiers={"enabled": True}), "kv_tiers cannot serve a per-sequence state"),
    (dict(speculative={"enabled": True}), "speculative cannot serve a per-sequence state"),
], ids=["prefix-cache", "kv-tiers", "speculation"])
def test_the_scheduler_refuses_at_construction_by_name(model, serving, said):
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler
    cfg, params = model
    engine = engine_of(cfg, params)
    with pytest.raises(ValueError, match=said):
        ServingScheduler(engine, ServingConfig(**serving), start=False)
    engine.close()


def test_the_scheduler_serves_waits_for_a_slot_and_refuses_frames(model):
    """Through ``ServingScheduler.submit``: five requests over THREE slots all
    finish with the reference's greedy tokens (pool pressure is answered by
    waiting: offload would leave the slot behind, and the scheduler does not
    try it); handoff, park and resume are refused by name at submission."""
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler
    cfg, params = model
    engine = engine_of(cfg, params, slots=3)
    scheduler = ServingScheduler(engine, ServingConfig(decode_chunk=4))
    try:
        with pytest.raises(ValueError, match="per-sequence state group"):
            scheduler.submit(_ids(70, 8), max_new_tokens=2, handoff=True)
        prompts = [_ids(71 + i, 18 + 7 * i) for i in range(5)]
        handles = [scheduler.submit(p, max_new_tokens=5, temperature=0.0) for p in prompts]
        outs = []
        for h in handles:
            toks = []
            while (tok := h.stream.get(timeout=120)) is not None:
                toks.append(int(tok))
            outs.append(toks)
            assert h.state.name == "DONE"
        assert scheduler.stats()["counters"].get("evictions", 0) == 0
    finally:
        scheduler.stop(drain=False)
    assert engine._state_manager.free_slots == 3
    for prompt, toks in zip(prompts, outs):
        ids = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        rows = np.asarray(reference.forward_logits(params, sizes_of(cfg), ids,
                                                   rows=np.arange(prompt.size - 1, ids.size)))
        assert toks == [int(r.argmax()) for r in rows]
    engine.close()


def test_a_kv_model_builds_the_batch_and_the_cache_it_built_before():
    """A model without a per-sequence state group: ``seq_meta`` has no slot
    column, the cache is the one K/V array, the state manager has no slots, and
    the K/V array's depth is ``num_layers`` (CHANGES.md, PR 43, has the hash of
    a Mistral ``put`` program's HLO on the parent and on this tree: equal)."""
    from deepspeed_tpu.models.llama import LlamaConfig, init_params
    cfg = LlamaConfig.tiny(dtype=jnp.float32) if hasattr(LlamaConfig, "tiny") else None
    if cfg is None:
        pytest.skip("no tiny Llama preset")
    params = init_params(cfg, rng=jax.random.PRNGKey(0))[1]
    engine = engine_of(cfg, params)
    served = engine.model
    assert served.sequence_state == () and served._slot_columns == 0
    assert served.num_kv_layers == served.num_layers
    assert engine._state_manager.free_slots is None and engine._state_manager.num_slots == 0
    engine.put([0], [_ids(80, 9)])
    assert engine._state_manager.get_sequence(0).state_slot is None
    batch = engine._batch.device_batch
    assert batch["seq_meta"].shape == (8, 4 + 4) and served._bucket_of(batch) == (16, 8, 4)
    cache = engine._state_manager.kv_cache.cache
    assert not isinstance(cache, tuple) and cache.shape[0] == served.num_layers
    assert "one_token_rows" not in served._unpack_batch(batch)
