"""LongCat-Flash served through ``build_engine`` (PR 62): two latent layers of
the pool a model layer, a routed branch carried past a half-layer, a router
whose outputs include experts without a bank. Prefill in uneven chunks, ``put``
and ``decode_loop`` against the plain float32 reference's full forward (the
un-absorbed latent attention), on the ``jax.numpy`` arm and with the latent
kernels in interpret mode; the second half's attention on ITS cache layer; the
shares of the experts adding up to the uncut routed branch with the identity
term counted once; ``RaggedMoE``'s grouped path on hand-written routings; the
counters; every other family's program as it was; and each refusal by its
message."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import longcat_flash as reference
from benchmark.tools import controls_longcat
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.model_implementations import registry
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig, MemoryConfig)
from deepspeed_tpu.models import longcat_flash as lf
from deepspeed_tpu.utils import groups

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


def engine_of(cfg, params, kernel=False, blocks=96):
    groups.initialize_mesh(force=True)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                          size=blocks),
                               max_context=256, max_ragged_batch_size=64,
                               max_ragged_sequence_count=8)
    return build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=BLOCK, use_paged_kernel=kernel,
        expert_parallel={"capacity_factor": 4.0}))


@pytest.fixture(scope="module")
def model():
    cfg = lf.LongcatFlashConfig.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1)
    return cfg, lf.init_params(cfg, rng=jax.random.PRNGKey(3))[1]


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


@pytest.fixture(scope="module")
def engine(model):
    """ONE engine for the tests that serve (its programs compile once); each
    flushes the sequences it made."""
    return engine_of(*model)


def _reference_rows(cfg, params, ids, rows):
    """The reference's logits at ``rows`` of ``ids``, padded with token 0 to
    ONE length: the same rows (every layer is causal), one compilation."""
    padded = np.zeros(96, np.int32)
    padded[:ids.size] = ids
    return np.asarray(reference.forward_logits(params, sizes_of(cfg), padded, rows=rows))


def _want(cfg, params, prompt, feed):
    return _reference_rows(cfg, params, np.concatenate([prompt, feed]),
                           np.arange(prompt.size - 1, prompt.size + feed.size))


def _served(engine, prompt, feed, chunks, loop=True):
    """The engine's logits after the prompt (fed in ``chunks``) and after each
    fed token but the last, and ``decode_loop``'s four tokens from the last."""
    got, at = [], 0
    for n in chunks:
        out = np.asarray(engine.put([0], [prompt[at:at + n]]))
        at += n
    assert at == prompt.size
    got.append(out[0])
    for j in range(feed.size - 1):
        got.append(np.asarray(engine.put([0], [feed[j:j + 1]]))[0])
    looped = np.asarray(engine.decode_loop([0], [feed[-1:]], 4))[0] if loop else None
    engine.flush(0)
    return np.stack(got), looped


# --------------------------------------------------------------- (a) engine --
def test_prefill_in_uneven_chunks_then_decode_is_the_references_full_forward(model, engine):
    cfg, params = model
    assert registry.model_cls_for(cfg) is type(engine.model)
    assert "longcat_flash" in registry.supported_model_types()
    served = engine.model
    assert (served.num_layers, served.num_kv_layers) == (2, 4)
    assert served.min_table_bucket == 16 and served.min_sequence_bucket == 8
    assert served.kv_state_widths == (128, )  # 32 + 8 lanes in one whole tile
    (latent_pool, ) = engine._state_manager.kv_cache.cache
    assert latent_pool.shape == (4, 96, BLOCK, 128)
    prompt, feed = _ids(1, 75), _ids(2, 6)
    want = _want(cfg, params, prompt, feed)
    got, looped = _served(engine, prompt, feed, (5, 24, 17, 29))
    assert np.abs(got - want[:-1]).max() < TOL
    assert int(looped[0]) == int(want[-1].argmax())
    # the loop's steps continued the rows of every latent layer: its next tokens are the
    # reference's
    longer = np.concatenate([prompt, feed, looped[:3]])
    again = _reference_rows(cfg, params, longer, np.arange(longer.size - 3, longer.size))
    assert [int(t) for t in looped[1:]] == [int(r.argmax()) for r in again]
    assert {key[2] for key in engine.lowerable_callables()["forward"]} == {16}


def test_decode_loop_is_the_same_steps_one_by_one(model, engine):
    prompt = _ids(5, 33)
    first = np.asarray(engine.put([0], [prompt]))[0]
    token = np.asarray([first.argmax()], np.int32)
    looped = np.asarray(engine.decode_loop([0], [token], 4))[0]
    engine.flush(0)
    engine.put([0], [prompt])
    by_hand = []
    for _ in range(4):
        token = np.asarray([np.asarray(engine.put([0], [token]))[0].argmax()], np.int32)
        by_hand.append(int(token[0]))
    engine.flush(0)
    assert [int(t) for t in looped] == by_hand


def test_the_kernels_in_interpret_mode_are_the_jax_numpy_arm(model):
    """``latent_paged_attention`` on both grids (a 64-token bucket on the tiled
    one, the one-token steps and ``decode_loop`` on the token one), every other
    call on the second half's layer of the pool."""
    cfg, params = model
    engine = engine_of(cfg, params, kernel=True)
    assert engine.model.attention_arm(64) == "latent_tiled" \
        and engine.model.attention_arm(8) == "latent_token"
    # a tiled step's span counts the kernel's passes: a 20-token chunk beside two decode
    # rows in one tile of 64 tokens (4 heads), over the 4 latent layers
    tiled = {"tok_meta": np.zeros((4, 64), np.int32), "seq_meta": np.zeros((8, 4 + 16), np.int32)}
    tiled["seq_meta"][:3, 1:3] = [(1, 0), (1, 1), (20, 21)]
    counts = engine.model.batch_counts(tiled)
    assert (counts["latent_passes"], counts["latent_rider_passes"]) == (3 * 4, 2 * 4)
    prompt, feed = _ids(3, 70), _ids(4, 3)
    want = _want(cfg, params, prompt, feed)
    got, looped = _served(engine, prompt, feed, (41, 29))
    assert np.abs(got - want[:-1]).max() < TOL
    assert int(looped[0]) == int(want[-1].argmax())


def test_two_sequences_batched_are_each_its_solo_run(model, engine):
    cfg, params = model
    prompts, feeds = [_ids(10, 9), _ids(11, 40)], [_ids(20, 3), _ids(21, 3)]
    want = [_want(cfg, params, p, f) for p, f in zip(prompts, feeds)]
    got = [[r] for r in np.asarray(engine.put([0, 1], prompts))]
    for j in range(2):
        out = np.asarray(engine.put([1, 0], [feeds[1][j:j + 1], feeds[0][j:j + 1]]))
        got[1].append(out[0]), got[0].append(out[1])
    for u in (0, 1):
        assert np.abs(np.stack(got[u]) - want[u][:3]).max() < TOL
        engine.flush(u)


# --------------------------------------------------- (b) the two cache layers --
def test_the_second_halfs_attention_reads_its_own_cache_layer(model, engine):
    """A hand-made case where the two differ: after a prefill the pool's layers
    ``2 l`` and ``2 l + 1`` hold different rows (the halves have weights and
    inputs of their own), the engine as built is the reference, and the engine
    whose second half reads the first half's layer is far from it."""
    cfg, params = model
    prompt, feed = _ids(1, 75), _ids(2, 3)
    want = _want(cfg, params, prompt, feed)
    engine.put([0], [prompt[:41]]), engine.put([0], [prompt[41:]])
    (pool, ) = engine._state_manager.kv_cache.cache
    blocks = [int(b) for b in engine._state_manager.get_sequence(0).kv_blocks]
    rows = np.asarray(pool)[:, blocks]
    engine.flush(0)
    assert all(np.abs(rows[li]).max() > 0 for li in range(4))
    assert np.abs(rows[0] - rows[1]).max() > 0.1 and np.abs(rows[2] - rows[3]).max() > 0.1
    _, _, patch = controls_longcat.spoilt("second_half_first_cache", cfg, params, 256)
    with patch:
        got, _ = _served(engine_of(cfg, params), prompt, feed, (41, 34), loop=False)
    assert np.abs(got - want[:-1]).max() > 0.01 * np.abs(want).max() > 5 * TOL


@pytest.mark.parametrize("control", ["no_identity", "branch_early", "no_kv_lora_scale",
                                     "drop_expert"])
def test_each_control_changes_the_logits(model, control):
    """The CPU twins of ``benchmark/tools/controls_longcat.py``'s controls: an
    engine built under one is far from the reference by the comparison's
    measure, where the engine as built is within 1e-4; and the control restores
    what it patched."""
    cfg, params = model
    prompt, feed = _ids(1, 75), _ids(2, 3)
    want = _want(cfg, params, prompt, feed)
    its_cfg, its_params, patch = controls_longcat.spoilt(control, cfg, params, 256)
    with patch:
        got, _ = _served(engine_of(its_cfg, its_params), prompt, feed, (41, 34), loop=False)
    least = {"drop_expert": 5 * TOL}.get(control, 0.01 * np.abs(want).max())
    assert np.abs(got - want[:-1]).max() > least > 4 * TOL
    assert cfg.kv_lora_scale == (64 / 32)**0.5
    assert RaggedMoE._zero_term.__qualname__ == "RaggedMoE._zero_term"


# ------------------------------------------------------------- (c) the share --
def test_the_shares_add_up_to_the_uncut_routed_branch(model, engine):
    """At 16 experts in 4 shares + 8 identity experts: over every
    ``expert_rank``, the routed parts plus the identity term counted ONCE are
    the uncut reference's ``MoE(h)``; and the served layer's part is its
    rank's."""
    whole = lf.LongcatFlashConfig.tiny(dtype=jnp.float32)
    params = lf.init_params(whole, rng=jax.random.PRNGKey(4))[1]
    mp = params["layers_1"]["mlp"]
    h = jnp.asarray(np.random.default_rng(6).normal(size=(24, whole.hidden_size)), jnp.float32)
    routed = dict(top_k=whole.moe_topk, scale=whole.routed_scaling_factor,
                  zero=whole.zero_expert_num)
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.moe(h, mp, first_held=0, **routed)
        _, identity, _ = reference.routing(
            h, mp["gate"], mp["e_score_correction_bias"], top_k=whole.moe_topk,
            scale=whole.routed_scaling_factor, routed=16, first_held=0, held=16)
        identity = np.asarray(h * identity[:, None])
        assert np.abs(identity).max() > 1e-2  # some token chose an identity expert
        parts = []
        for rank in range(4):
            held = slice(4 * rank, 4 * rank + 4)
            mine = dict(mp, experts={k: v[held] for k, v in mp["experts"].items()})
            part, _ = reference.moe(h, mine, first_held=4 * rank, **routed)
            parts.append(np.asarray(part) - identity)
        assert all(np.abs(p).max() > 1e-3 for p in parts)  # every rank is routed to
        assert np.abs(sum(parts) + identity - np.asarray(uncut)).max() < 1e-5
    # the served layer of rank 1 (the fixture's) computes rank 1's part and the identity term
    cfg, mine = model
    served, lp = engine.model, mine["layers_1"]
    batch = {"token_valid": jnp.ones(24, bool)}
    got = np.asarray(jax.jit(lambda lp, h: served._routed_beside_shared(
        1, h, lp["mlp"]["gate"], lp["mlp"]["experts"], lp["mlp"]["e_score_correction_bias"],
        None, batch))(lp, h))
    with jax.default_matmul_precision("highest"):
        want, _ = reference.moe(h, lp["mlp"], first_held=4, **routed)
    assert np.abs(got - np.asarray(want)).max() < TOL


# ------------------------------------------ (d) RaggedMoE's routing, by hand --
def _hand_case(logits, n_experts=4, n_zero=2, top_k=2, scale=3.0, held=None, first_held=0):
    """A layer whose router reads the logits off the first columns of ``h``
    (``gate`` an identity block), so the routing is written by hand."""
    T, E = logits.shape
    assert E == n_experts + n_zero
    M, F = 8, 4
    rng = np.random.default_rng(0)
    h = np.concatenate([logits, rng.normal(size=(T, M - E))], axis=1).astype(np.float32)
    gate = np.zeros((M, E), np.float32)
    gate[:E] = np.eye(E)
    here = n_experts if held is None else held
    wi = 0.3 * rng.normal(size=(here, M, 2 * F)).astype(np.float32)
    wo = 0.3 * rng.normal(size=(here, F, M)).astype(np.float32)
    moe = RaggedMoE(n_experts, top_k=top_k, capacity_factor=n_experts / top_k,
                    norm_topk_prob=False, route_scale=scale, held=held, first_held=first_held,
                    zero_experts=n_zero)
    return moe, tuple(map(jnp.asarray, (h, gate, wi, wo)))


def _by_hand(h, gate, wi, wo, top_k, scale, n_experts, first_held=0):
    """The same layer in plain numpy, token by token."""
    h, gate, wi, wo = (np.asarray(a, np.float64) for a in (h, gate, wi, wo))
    out = np.zeros_like(h)
    logits = h @ gate
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    for t in range(h.shape[0]):
        for e in np.argsort(-p[t], kind="stable")[:top_k]:
            w = scale * p[t, e]
            if e >= n_experts:
                out[t] += w * h[t]
            elif first_held <= e < first_held + wi.shape[0]:
                pre = h[t] @ wi[e - first_held]
                a, b = np.split(pre, 2)
                out[t] += w * ((a / (1 + np.exp(-a)) * b) @ wo[e - first_held])
    return out


@pytest.mark.parametrize("tokens", [3, 96])
def test_a_hand_written_routing_with_an_identity_expert_chosen(tokens):
    """Token 0 chooses experts 1 and 3, token 1 expert 0 and the identity
    expert 4, token 2 the identity experts 5 and 4: the layer is the one
    written out by hand, and counts 3 banks and 3 identity choices a round of
    the three; at 96 tokens too, a bucket that a layer of four experts and no
    identity expert sends down the capacity path, which has no term for one."""
    from deepspeed_tpu.inference.v2.modules.heuristics import moe_implementation
    logits = np.tile(np.array([[0, 5, 0, 4, -5, -5], [5, 0, 0, 0, 4, -5], [0, 0, 0, 0, 4, 5]],
                              np.float32), (tokens // 3, 1))
    moe, (h, gate, wi, wo) = _hand_case(logits)
    assert moe.path(tokens, wo.shape[-2]) == "grouped"
    assert moe_implementation(96, 4, 2, moe.capacity(96), wo.shape[-2]) == "capacity"
    counts = []
    got = moe(h, gate, wi, wo, token_valid=jnp.ones(tokens, bool), banks_out=counts)
    assert np.abs(np.asarray(got) - _by_hand(h, gate, wi, wo, 2, 3.0, 4)).max() < 1e-4
    banks, visits, zero = (int(c) for c in counts[0])
    assert (banks, zero) == (3, tokens) and visits >= 3


def test_where_every_choice_is_an_identity_expert_no_row_reaches_the_grouped_matmul():
    """All tokens choose the two identity experts: the sort has no group, the
    grouped matmul no row and no bank (its counts say so), and the output is
    ``h`` times the two weights; a padding token adds nothing and counts for
    nothing. A share that holds experts 2..3 of 4 sees the same."""
    logits = np.tile(np.array([[0, 0, 0, 0, 6, 5]], np.float32), (4, 1))
    for held, first_held in ((None, 0), (2, 2)):
        moe, (h, gate, wi, wo) = _hand_case(logits, held=held, first_held=first_held)
        counts = []
        valid = jnp.asarray([True, True, True, False])
        got = np.asarray(moe._grouped_forward(h, gate, wi, wo, valid, jax.nn.silu, None, None,
                                              counts))
        want = _by_hand(h, gate, wi, wo, 2, 3.0, 4, first_held)
        assert np.abs(got[:3] - want[:3]).max() < 1e-5 and np.abs(got[3]).max() == 0
        p = jax.nn.softmax(h @ gate, axis=-1)
        assert np.abs(got[:3] - 3.0 * np.asarray(p[:3, 4:].sum(-1, keepdims=True) * h[:3])).max() \
            < 1e-5
        names = (("moe_banks", "moe_visits") if held is None else
                 ("moe_banks", "moe_assignments_local", "moe_visits", "moe_rows_walked")) \
            + ("moe_assignments_zero", )
        read = dict(zip(names, (int(c) for c in counts[0])))
        assert read["moe_assignments_zero"] == 6 and read["moe_banks"] == read["moe_visits"] == 0
        assert read.get("moe_assignments_local", 0) == 0
        assert read.get("moe_rows_walked", 128) == 128  # one row tile: no window to walk


def test_an_expert_parallel_mesh_refuses_experts_without_a_bank_by_name():
    """Four replicas of the ``expert`` axis: the exchange's capacity masks
    have no term for an identity expert, and the layer says so before it
    traces anything."""
    logits = np.random.default_rng(3).normal(size=(8, 6)).astype(np.float32) * 3
    moe, (h, gate, wi, wo) = _hand_case(logits)
    groups.initialize_mesh(expert_parallel_size=4, devices=jax.devices()[:4], force=True)
    try:
        with pytest.raises(NotImplementedError, match="zero_experts"):
            moe(h, gate, wi, wo, token_valid=jnp.ones(8, bool))
    finally:
        groups.initialize_mesh(force=True)


# --------------------------------------------------------------- (e) counts --
def test_the_counts_say_what_the_layers_did(engine):
    """``latent_rows`` / ``latent_context_rows`` as Kimi's, over the FOUR
    latent layers of two model layers; ``moe_assignments`` = live tokens x 4 x
    layers; the device's counts name the identity choices."""
    engine.put([0, 1], [_ids(40, 25), _ids(41, 1)])
    put = engine.model.batch_counts(engine._batch, 1)
    assert put == {"latent_rows": (25 * 26 // 2 + 1) * 4, "latent_context_rows": (25 + 1) * 4}
    engine.decode_loop([0, 1], [_ids(42, 1), _ids(43, 1)], 4)
    counts = engine.model.batch_counts(engine._batch, 4)
    # contexts 26..29 and 2..5, each row its own sequence's one
    assert counts["latent_rows"] == counts["latent_context_rows"] == (110 + 14) * 4
    counts = engine.model.dispatch_counts(8, 2, 4)
    assert counts["moe_path"] == "grouped" and counts["moe_assignments"] == 2 * 4 * 2 * 4
    assert engine.model.moe_count_names == ("moe_banks", "moe_assignments_local", "moe_visits",
                                             "moe_rows_walked", "moe_assignments_zero")
    engine.flush(0), engine.flush(1)
    # the device's own count of a step: 8 identity outputs of 24, 4 choices a token a layer
    engine.put([0], [_ids(44, 40)])
    read = engine.moe_counts(engine.model.last_moe_banks)
    engine.flush(0)
    assert 0 < read["moe_assignments_zero"] < 40 * 4 * 2
    assert 0 < read["moe_assignments_local"] <= 40 * 4 * 2 - read["moe_assignments_zero"]
    # the bucket's every sorted row, both layers: the tiny model's share (4 of 24 outputs) has
    # no window at this bucket
    assert read["moe_rows_walked"] == engine.model.dispatch_counts(64, 40)["moe_rows"]


# ------------------------------------- (f) every other family's program stays --
@pytest.mark.parametrize("family", ["mixtral", "deepseek_v32"])
def test_without_zero_experts_a_family_lowers_the_program_it_did(family):
    """``zero_experts`` = 0 (every family before this one): the ``put`` and the
    chunk programs' recorded hashes (``family_pins.json``: recorded before this
    PR) hold."""
    from tests.unit.inference.v2 import family_pins
    table = family_pins.recorded()
    if jax.__version__ != table["jax"]:
        pytest.skip(f"the recorded jaxpr text is jax {table['jax']}'s")
    seen = family_pins.observed(family)
    assert {k: seen[k] for k in ("put", "chunk")} == \
        {k: table["families"][family][k] for k in ("put", "chunk")}


# ------------------------------------------------------------- (g) refusals --
@pytest.mark.parametrize("keys, said", [
    (dict(attention_method="MHA"), "attention_method"),
    (dict(attention_bias=True), "attention bias"),
    (dict(zero_expert_type="copy"), "zero_expert_type"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_is_not_implemented_is_refused_by_name(keys, said):
    with pytest.raises(NotImplementedError, match=said):
        lf.LongcatFlashConfig.tiny(**keys)


def test_a_share_that_does_not_divide_and_identity_experts_in_groups_are_refused():
    with pytest.raises(ValueError, match="does not divide"):
        lf.LongcatFlashConfig.tiny(experts_held=5)
    with pytest.raises(ValueError, match="belong to no group"):
        RaggedMoE(16, top_k=2, n_group=4, topk_group=2, zero_experts=4)
