"""Kimi Linear served through ``build_engine`` (PR 56): a latent pool and a
per-sequence slot pool in ONE cache. Prefill in uneven chunks, ``put`` and
``decode_loop`` through both against the plain float32 reference's full
forward (the un-absorbed latent attention, the delta rule token by token), on
the ``jax.numpy`` arm and with both families' kernels in interpret mode;
continuous batching; the cache's two kinds of unit reserved, freed and refused
by both names; the four shares of an expert layer adding up to the uncut layer;
what the benchmark's controls spoil, each seen here too; the counters; and each
refusal by its message. The tiny model's delta-rule heads are 128 x 128 as
published (two of them), so the engine runs ``ops/pallas/kda_step.py`` and
``kda_chunk.py`` in interpret mode whatever the attention arm."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import kimi_linear as reference
from benchmark.tools import controls_kimi
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.model_implementations import registry
from deepspeed_tpu.inference.v2.modules import kda, ssm
from deepspeed_tpu.inference.v2.ragged.kv_cache import CACHE_OPERATIONS
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig, MemoryConfig)
from deepspeed_tpu.models import kimi_linear as kl
from deepspeed_tpu.utils import groups
from tests.unit.inference.v2.program_hashes import decode_loop_hash

BLOCK = 16
TOL = 1e-4
# sha256 of the tiny model's traced decode_loop program (``program_hashes.decode_loop_hash``)
# re-recorded in PR 60: the chunk's count of routed work holds the grouped kernel's visits too;
# in PR 64: and the sorted rows the layer walked (the 8-row bucket's one tile: a constant)
DECODE_LOOP_HASH = "9cf5b42ef804a4f295b5b279b2113e136b0cb600e6e5581ed38e2df92b0fbfbf"
# two periods of the tiny preset: KDA (dense), KDA, KDA, MLA, KDA, MLA
LAYERS = dict(num_hidden_layers=6, kda_layers=(1, 2, 3, 5), full_attn_layers=(4, 6))


def sizes_of(cfg):
    """The configuration-file view of a program config, as the reference reads it."""
    sizes = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    sizes.update(num_experts=cfg.experts_held, num_experts_per_token=cfg.num_experts_per_tok,
                 moe_renormalize=cfg.norm_topk_prob, moe_router_activation_func=cfg.scoring_func,
                 num_expert_group=cfg.n_group)
    sizes["linear_attn_config"] = {"num_heads": cfg.linear_num_heads,
                                   "head_dim": cfg.linear_head_dim,
                                   "short_conv_kernel_size": cfg.short_conv_kernel_size,
                                   "kda_layers": list(cfg.kda_layers),
                                   "full_attn_layers": list(cfg.full_attn_layers)}
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
    cfg = kl.KimiLinearConfig.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1, **LAYERS)
    return cfg, kl.init_params(cfg, rng=jax.random.PRNGKey(3))[1]


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
    assert "kimi_linear" in registry.supported_model_types()
    assert cfg.mla_here == (3, 5) and cfg.kda_here == (0, 1, 2, 4) and cfg.is_dense(0)
    served = engine.model
    assert served.num_kv_layers == 2 and served.min_table_bucket == 16
    assert served.kv_state_widths == (128, )  # 32 + 8 lanes in one whole tile
    # ONE cache: a tuple of latent pools where the K/V array stood, the slot pools behind it
    (latent_pool, ), state_pool, conv_pool = engine._state_manager.kv_cache.cache
    assert latent_pool.shape == (2, 96, BLOCK, 128)
    assert state_pool.shape == (4, 6, 2, 128, 128) and state_pool.dtype == jnp.float32 \
        and kda.in_place(state_pool)
    assert conv_pool.shape == (4, 6) + ssm.conv_slot(3, 3 * cfg.kda_width) \
        and ssm.whole_slots(conv_pool)
    prompt, feed = _ids(1, 75), _ids(2, 6)
    want = _want(cfg, params, prompt, feed)
    got, looped = _served(engine, prompt, feed, (5, 24, 17, 29))  # on and off the 16-row chunks
    assert np.abs(got - want[:-1]).max() < TOL
    assert int(looped[0]) == int(want[-1].argmax())
    # the loop's steps continued the state and the rows: its next tokens are the reference's
    longer = np.concatenate([prompt, feed, looped[:3]])
    again = _reference_rows(cfg, params, longer, np.arange(longer.size - 3, longer.size))
    assert [int(t) for t in looped[1:]] == [int(r.argmax()) for r in again]
    assert {key[2] for key in engine.lowerable_callables()["forward"]} == {16}


def test_the_kernels_in_interpret_mode_are_the_jax_numpy_arm(model):
    """``latent_paged_attention`` on both grids (a 64-token bucket on the tiled
    one, the one-token steps and ``decode_loop`` on the token one) over the
    latent pool of a cache that has slots too."""
    cfg, params = model
    engine = engine_of(cfg, params, kernel=True)
    assert engine.model.attention_arm(64) == "latent_tiled" \
        and engine.model.attention_arm(8) == "latent_token"
    # a tiled step's span counts the kernel's passes: a 20-token chunk beside two decode
    # rows in one tile of 64 tokens (4 heads), over the 2 latent layers
    tiled = {"tok_meta": np.zeros((4, 64), np.int32), "seq_meta": np.zeros((8, 4 + 16), np.int32)}
    tiled["seq_meta"][:3, 1:3] = [(1, 0), (1, 1), (20, 21)]
    counts = engine.model._latent_counts(tiled)
    assert (counts["latent_passes"], counts["latent_rider_passes"]) == (3 * 2, 2 * 2)
    prompt, feed = _ids(3, 70), _ids(4, 3)
    want = _want(cfg, params, prompt, feed)
    got, looped = _served(engine, prompt, feed, (41, 29))
    assert np.abs(got - want[:-1]).max() < TOL
    assert int(looped[0]) == int(want[-1].argmax())


# ------------------------------------------------- (b) continuous batching --
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


# ------------------------------------------------ (c) the cache's two units --
def test_blocks_and_a_slot_are_reserved_and_freed_together(model, engine):
    """A sequence takes block ids of the latent pool as it grows and ONE slot
    of the state group at its first token; a flush gives both back; a slot
    reused starts from zero; padding rows write to neither pool."""
    manager = engine._state_manager
    assert (manager.free_slots, manager.free_blocks) == (6, 96)

    def pools():
        (latent, ), *slots = manager.kv_cache.cache
        return np.asarray(latent), [np.asarray(p) for p in slots]

    prompt = _ids(30, 30)
    latent0, slots0 = pools()
    first = np.asarray(engine.put([7], [prompt]))
    seq = manager.get_sequence(7)
    slot, blocks = seq.state_slot, [int(b) for b in seq.kv_blocks]
    assert (manager.free_slots, manager.free_blocks, len(blocks)) == (5, 94, 2)
    latent1, slots1 = pools()
    assert np.abs(latent1[:, blocks]).max() > 0 and np.abs(slots1[0][:, slot]).max() > 0
    # the rows of the bucket beyond the one live sequence, and the padding tokens, wrote nothing
    np.testing.assert_array_equal(np.delete(latent1, blocks, axis=1),
                                  np.delete(latent0, blocks, axis=1))
    for was, now in zip(slots0, slots1):
        np.testing.assert_array_equal(np.delete(now, slot, axis=1), np.delete(was, slot, axis=1))
    engine.decode_loop([7], [_ids(32, 1)], 4)  # 34 tokens: a third block
    assert manager.free_blocks == 93
    engine.flush(7)
    assert (manager.free_slots, manager.free_blocks) == (6, 96) and manager.get_sequence(7) is None
    engine.put([8], [_ids(31, 25)])  # takes the slot 7 held, its old state still in it
    assert manager.get_sequence(8).state_slot == slot
    engine.flush(8)
    again = np.asarray(engine.put([9], [prompt]))
    assert np.abs(again - first).max() < 1e-6
    engine.flush(9)


ASKED = ("prefix_cache", "kv_tiers", "frames", "speculative")


@pytest.mark.parametrize("operation", list(CACHE_OPERATIONS))
def test_the_cache_refuses_by_the_name_of_each_kind_it_is(engine, operation):
    """Latent rows AND slots: an operation that names both kinds is refused by
    both names, one that names one by that one's; what a deployment asks for is
    a ``ValueError``, a call its first kind's error; no row of the table is new."""
    said = {"latent": "a latent KV group (rows of widths (128,)",
            "slots": "a per-sequence state group (['kda', 'conv']"}
    kinds = [k for k in CACHE_OPERATIONS[operation][2].split() if k in said]
    refusal = engine._state_manager.kv_cache.refusal(operation)
    assert kinds and isinstance(refusal, ValueError if operation in ASKED else NotImplementedError)
    assert all(said[k] in str(refusal) for k in kinds)
    if operation in ("prefix_cache", "kv_tiers", "fork_blocks", "gather_blocks"):
        assert kinds == ["latent", "slots"]


def test_what_a_deployment_asks_for_is_refused_where_the_engine_is_built(model):
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler
    cfg, params = model
    engine = engine_of(cfg, params)
    with pytest.raises(ValueError, match=r"latent KV group.* and a per-sequence state group"):
        ServingScheduler(engine, ServingConfig(prefix_cache={"enabled": True}))


# --------------------------------------------------------------- (d) counts --
def test_the_counts_say_what_both_mixers_did(engine):
    """Solar's ``kda_*`` names and meaning over the four delta-rule layers;
    ``latent_rows``, the causal rows the queries attend to over the two latent
    layers (a row at position p, p + 1), and ``latent_context_rows``, a
    sequence's context once a step a layer."""
    engine.put([0, 1], [_ids(40, 25), _ids(41, 1)])
    put = engine.model.batch_counts(engine._batch, 1)
    want = {"kda_rows": 26 * 4, "kda_segments": 2 * 4, "kda_chunk_visits": 2 * 4,
            "kda_chunk_visits_in_kernel": 2 * 4, "kda_rows_in_place": 1 * 4,
            "ssm_slots_live": 2, "ssm_slots_total": 6,
            "latent_rows": (25 * 26 // 2 + 1) * 2, "latent_context_rows": (25 + 1) * 2}
    assert {k: put[k] for k in want} == want
    engine.decode_loop([0, 1], [_ids(42, 1), _ids(43, 1)], 4)
    chunk = engine.model.batch_counts(engine._batch, 4)
    assert chunk["kda_rows"] == chunk["kda_rows_in_place"] == chunk["kda_segments"] == 2 * 4 * 4
    # contexts 26..29 and 2..5, each row its own sequence's one
    assert chunk["latent_rows"] == chunk["latent_context_rows"] == (110 + 14) * 2
    counts = engine.model.dispatch_counts(8, 2, 4)
    assert counts["moe_path"] == "grouped" and counts["moe_assignments"] == 2 * 4 * 5 * 4
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
    whole = kl.KimiLinearConfig.tiny(dtype=jnp.float32)
    params = kl.init_params(whole, rng=jax.random.PRNGKey(4))[1]
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


# ----------------------------------------- (f) what the controls spoil, seen --
@pytest.mark.parametrize("control", ["beta_two", "k_r_rotated", "wrong_latent_layer",
                                     "state_bf16"])
def test_each_control_changes_the_logits(model, control):
    """The CPU twins of ``benchmark/tools/controls_kimi.py``'s controls: an
    engine built under one is far from the reference by the comparison's
    measure (a hundredth of the largest logit and more; the bfloat16 state pool,
    which the chip's comparison cannot see, by ten times the tolerance here),
    where the engine as built is within 1e-4; and the control restores what it
    patched."""
    cfg, params = model
    prompt, feed = _ids(1, 75), _ids(2, 3)
    want = _want(cfg, params, prompt, feed)
    with controls_kimi.spoilt(control):
        got, _ = _served(engine_of(cfg, params), prompt, feed, (41, 34), loop=False)
    least = 10 * TOL if control == "state_bf16" else 0.01 * np.abs(want).max()
    assert np.abs(got - want[:-1]).max() > least > 5 * TOL
    assert cfg.beta_scale == 1.0


# ------------------------------------------------------------- (g) refusals --
@pytest.mark.parametrize("keys, said", [
    (dict(mla_use_nope=False), "mla_use_nope"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(q_lora_rank=32), "q_lora_rank"),
    (dict(scoring_func="softmax"), "moe_router_activation_func"),
    (dict(n_group=4, topk_group=2), "num_expert_group"),
    (dict(tie_word_embeddings=True), "tied embeddings"),
    (dict(kda_layers=(1, 2, 3), full_attn_layers=(8, )), "layer 4 of 4 is in neither"),
    (dict(kda_layers=(1, 2, 3, 4)), "layer 4 of 4 is in both"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_is_not_implemented_is_refused_by_name(keys, said):
    with pytest.raises(NotImplementedError, match=said):
        kl.KimiLinearConfig.tiny(**dict(dict(full_attn_layers=(4, )), **keys))


def test_a_model_of_one_kind_of_layer_and_a_share_that_does_not_divide_are_refused(model):
    cfg, params = model
    with pytest.raises(ValueError, match="does not divide"):
        kl.KimiLinearConfig.tiny(experts_held=5)
    groups.initialize_mesh(force=True)
    only_kda = dataclasses.replace(cfg, num_hidden_layers=3)
    with pytest.raises(NotImplementedError, match="would leave one empty"):
        engine_of(only_kda, params)
