"""Falcon-H1 served through ``build_engine`` (PR 47): a Mamba-2 mixer beside
attention in every layer, against the plain float32 reference's full forward —
prefill in uneven chunks, ``put`` and ``decode_loop`` with sequences joining
and leaving between steps and a slot reused; each of the fourteen multipliers;
the chunked scan against the recurrence at the published widths; the model's
one sequence bucket (and every other family's 8 / 16); the refusals by name.
``model`` (state 16 wide) covers the recurrence's fallback, ``model_in_place``
(state 128 wide; the kernel in interpret mode) the pool updated in place."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import falcon_h1 as reference
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.model_implementations import registry
from deepspeed_tpu.inference.v2.modules import ssm
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig, MemoryConfig)
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import sequence_buckets, token_buckets
from deepspeed_tpu.models import falcon_h1 as fh
from deepspeed_tpu.utils import groups
from tests.unit.inference.v2.program_hashes import decode_loop_hash

BLOCK = 16
TOL = 1e-4


def sizes_of(cfg):
    """The configuration-file view of a program config, as the reference reads it."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def engine_of(cfg, params, kernel=False, blocks=96, slots=6, seqs=12, **overrides):
    groups.initialize_mesh(force=True)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                          size=blocks),
                               max_context=256, max_ragged_batch_size=64,
                               max_ragged_sequence_count=seqs, max_tracked_sequences=slots)
    return build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=BLOCK, use_paged_kernel=kernel, **overrides))


@pytest.fixture(scope="module")
def model():
    cfg = fh.FalconH1Config.tiny(dtype=jnp.float32)
    return cfg, fh.init_params(cfg, rng=jax.random.PRNGKey(3))[1]


@pytest.fixture(scope="module")
def model_in_place():
    """As ``model`` with a state of one lane tile: the pool is on the kernel's rule."""
    cfg = fh.FalconH1Config.tiny(dtype=jnp.float32, mamba_d_state=128)
    return cfg, fh.init_params(cfg, rng=jax.random.PRNGKey(3))[1]


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def _want(cfg, params, prompt, feed):
    return np.asarray(reference.forward_logits(
        params, sizes_of(cfg), np.concatenate([prompt, feed]),
        rows=np.arange(prompt.size - 1, prompt.size + feed.size)))


# ---------------------------------------------------------- (a) the engine --
@pytest.mark.parametrize("which, kernel", [("model", False), ("model_in_place", True)],
                         ids=["xla-state-fallback", "pallas-interpret-state-in-place"])
def test_prefill_in_uneven_chunks_then_decode_is_the_references_full_forward(request, which,
                                                                             kernel):
    """Sequence 0 prefills in uneven chunks (on and off the 8-row scan chunks)
    and decodes by ``put`` and ``decode_loop``; sequence 1 joins while 0
    decodes, leaves (flush), and sequence 2 takes ITS slot and must start from
    zero: every row of every sequence is the reference's. Five queries a K/V
    head on every attention arm; every layer holds its own K/V layer AND its
    own pair of slot pools."""
    cfg, params = request.getfixturevalue(which)
    engine = engine_of(cfg, params, kernel, slots=2)
    served = engine.model
    assert registry.model_cls_for(cfg) is type(served)
    assert "falcon_h1" in registry.supported_model_types()
    assert ssm.in_place(engine._state_manager.kv_cache.cache[1], cfg.mamba_n_groups) \
        == (which == "model_in_place")
    L = cfg.num_hidden_layers
    assert served.num_kv_layers == L == 3 and served.num_heads // served.num_kv_heads == 5
    assert served.min_table_bucket == 16 and served.min_sequence_bucket == 16
    kv, ssm_pool, conv_pool = engine._state_manager.kv_cache.cache
    # the tails' slot as ``ssm.conv_slot`` states it: 3 x 112 values are no tile and
    # stay [3, C] (XLA's gather and scatter); 3 x 560 fold into [8, 256], zeros behind
    slot = {"model": (3, 112), "model_in_place": (8, 256)}[which]
    assert kv.shape[0] == L and conv_pool.shape == (L, 2) + slot \
        and slot == ssm.conv_slot(3, cfg.conv_dim)
    assert ssm.whole_slots(conv_pool) == (which == "model_in_place")
    assert ssm_pool.shape == (L, 2, 6, 8, cfg.mamba_d_state) and ssm_pool.dtype == jnp.float32
    prompts = [_ids(1, 75), _ids(2, 21), _ids(3, 30)]
    feeds = [_ids(4, 8), _ids(5, 2), _ids(6, 3)]
    want = [_want(cfg, params, p, f) for p, f in zip(prompts, feeds)]
    got = [[], [], []]
    at = 0
    for n in (5, 24, 17, 29):
        out = np.asarray(engine.put([0], [prompts[0][at:at + n]]))
        at += n
    got[0].append(out[0])
    # 1 joins: its whole prompt beside 0's decode row, then both decode
    out = np.asarray(engine.put([0, 1], [feeds[0][0:1], prompts[1]]))
    got[0].append(out[0]), got[1].append(out[1])
    out = np.asarray(engine.put([1, 0], [feeds[1][0:1], feeds[0][1:2]]))
    got[1].append(out[0]), got[0].append(out[1])
    slot = engine._state_manager.get_sequence(1).state_slot
    engine.flush(1)  # 1 leaves; 2 takes its slot, whatever it holds
    out = np.asarray(engine.put([2, 0], [prompts[2], feeds[0][2:3]]))
    assert engine._state_manager.get_sequence(2).state_slot == slot
    got[2].append(out[0]), got[0].append(out[1])
    looped = np.asarray(engine.decode_loop([0, 2], [feeds[0][3:4], feeds[2][0:1]], 3))
    for u in (0, 1, 2):
        rows = np.stack(got[u])
        assert np.abs(rows - want[u][:rows.shape[0]]).max() < TOL, u
    assert int(looped[0][0]) == int(want[0][4].argmax())
    assert int(looped[1][0]) == int(want[2][1].argmax())
    # the loop's steps continued both kinds of state: its tokens are the reference's greedy ones
    longer = np.concatenate([prompts[2], feeds[2][:1], looped[1][:2]])
    again = np.asarray(reference.forward_logits(params, sizes_of(cfg), longer,
                                                rows=np.arange(longer.size - 2, longer.size)))
    assert [int(t) for t in looped[1][1:]] == [int(r.argmax()) for r in again]
    # one sequence bucket, one block-table bucket, the token buckets from 16 up
    assert {key[1:] for key in engine.lowerable_callables()["forward"]} == {(16, 16)}
    assert {key[0] for key in engine.lowerable_callables()["forward"]} <= {16, 32, 64}
    assert [key[0] for key in engine.lowerable_callables()["decode_loop"]] == [(16, 16, 16)]
    engine.close()


def test_the_decode_loop_program_is_the_one_it_was_before_the_scan_went_by_segment(model_in_place):
    """PR 49 changed what a ``put`` step's Mamba-2 mixers run and nothing a
    ``decode_loop`` chunk runs: its traced program (addresses blanked) hashes
    to what it did at the commit before. Re-pinned by PR 53, which changed it
    on purpose: the convolution's tails leave and enter their folded slots by
    ``ssm.load`` / ``ssm.store_in_place`` (every other family's recorded
    programs, ``test_one_group_programs.py`` and ``test_afmoe.py``, hold)."""
    cfg, params = model_in_place
    assert decode_loop_hash(engine_of(cfg, params).model) == \
        "544b245da10c228dfc643c9c888821b0a250bc0db57f77db217c83b5021a3837"


def test_the_dispatch_spans_carry_the_states_counters_and_the_sequence_bucket(model_in_place):
    """``inference.put`` and ``inference.decode_loop``: ``ssm_tokens`` /
    ``ssm_segments`` over the model's layers (each has a Mamba-2 mixer), the
    slots held, a ``put``'s final states left in their slots by the kernel, a
    chunk's rows updated in place, and the step's live sequences beside the
    sequence count it was padded to."""
    from deepspeed_tpu import telemetry
    cfg, params = model_in_place
    session = telemetry.configure({"enabled": True, "compile_watch": False})
    try:
        engine = engine_of(cfg, params, slots=5)
        engine.put([0, 1, 2], [_ids(1, 9), _ids(2, 4), _ids(3, 6)])
        engine.decode_loop([0, 1, 2], [_ids(4, 1)] * 3, 2)
        rows = session.spans.export_since(0)["spans"]
        put = next(s for s in rows if s["name"] == "put" and s["cat"] == "inference")["args"]
        loop = next(s for s in rows if s["name"] == "decode_loop"
                    and s["cat"] == "inference")["args"]
        assert (put["ssm_tokens"], put["ssm_segments"]) == (19 * 3, 3 * 3)
        assert put["ssm_segments_in_place"] == put["ssm_segments"]  # a slot is whole tiles
        assert put["ssm_segments_scanned_in_place"] == put["ssm_segments"]  # and on the kernel's rule
        # the convolution's tails too (PR 53): folded into whole tiles, loaded and left by a kernel
        assert put["ssm_conv_rows_in_place"] == put["ssm_segments"]
        assert loop["ssm_conv_rows_in_place"] == loop["ssm_segments"] == 2 * 3 * 3
        assert (put["ssm_slots_live"], put["ssm_slots_total"]) == (3, 5)
        assert put["tokens"] == 19
        assert loop["ssm_tokens"] == loop["ssm_rows_in_place"] == 2 * 3 * 3 and loop["steps"] == 2
        for args in (put, loop):
            assert (args["seqs_live"], args["seq_bucket"]) == (3, 16)
        engine.close()
    finally:
        telemetry.shutdown()


# ------------------------------------------------------ (b) the multipliers --
def _with(cfg, name, value):
    """``cfg`` with one of the fourteen multipliers set: ``ssm_multipliers.2``
    is entry 2 of the tuple."""
    if "." not in name:
        return dataclasses.replace(cfg, **{name: value})
    field, at = name.split(".")
    entries = list(getattr(cfg, field))
    entries[int(at)] = value
    return dataclasses.replace(cfg, **{field: tuple(entries)})


MULTIPLIERS = ["embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
               "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
               "ssm_multipliers.0", "ssm_multipliers.1", "ssm_multipliers.2",
               "ssm_multipliers.3", "ssm_multipliers.4", "ssm_out_multiplier",
               "mlp_multipliers.0", "mlp_multipliers.1"]


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_stands_where_the_reference_has_it(model, name):
    """The multiplier set to ANOTHER value (1.7 x the published one, the
    weights as they are) in program and reference alike: they agree; and the
    program with that one multiplier dropped (1) differs from that reference by
    hundreds of times the tolerance: each of the fourteen is applied, and where
    the reference applies it."""
    base, params = model
    base = dataclasses.replace(base, num_hidden_layers=1)
    published = base.ssm_multipliers[int(name[-1])] if name.startswith("ssm_multipliers") else \
        base.mlp_multipliers[int(name[-1])] if name.startswith("mlp_multipliers") else \
        getattr(base, name)
    cfg = _with(base, name, 1.7 * published)
    prompt = _ids(7, 12)
    want = np.asarray(reference.forward_logits(params, sizes_of(cfg), prompt, rows=[5, 11]))

    def served(cfg):
        engine = engine_of(cfg, params, blocks=8, slots=2)
        rows = [np.asarray(engine.put([0], [prompt[:6]]))[0],
                np.asarray(engine.put([0], [prompt[6:]]))[0]]
        engine.close()
        return np.stack(rows)

    assert np.abs(served(cfg) - want).max() < TOL
    assert np.abs(served(_with(base, name, 1.0)) - want).max() > 100 * TOL


def test_the_seeded_kernels_answer_their_multipliers():
    """``init_params``: kernel x its multiplier has variance 1 / fan_in (the
    three projections into the stream further 1 / (3 x layers)), in_proj's
    column blocks each by their own entry."""
    cfg = fh.FalconH1Config.tiny(dtype=jnp.float32, hidden_size=128, intermediate_size=256)
    p = fh.init_params(cfg, rng=jax.random.PRNGKey(0))[1]
    lp, M, L3 = p["layers_0"], cfg.hidden_size, 3 * cfg.num_hidden_layers

    def var(x, m=1.0):
        return float(np.var(np.asarray(x) * m))

    assert var(p["embed_tokens"]["embedding"], cfg.embedding_multiplier) == pytest.approx(1, rel=.1)
    assert var(p["lm_head"]["kernel"], cfg.lm_head_multiplier) == pytest.approx(1 / M, rel=.1)
    at = 0
    for width, m in cfg.in_proj_columns:
        block = lp["mamba"]["in_proj"]["kernel"][:, at:at + width]
        assert var(block, cfg.ssm_in_multiplier * m) == pytest.approx(1 / M, rel=.25), at
        at += width
    assert at == cfg.in_proj_width == lp["mamba"]["in_proj"]["kernel"].shape[1]
    attn, ff = lp["self_attn"], lp["feed_forward"]
    assert var(attn["k_proj"]["kernel"], cfg.key_multiplier) == pytest.approx(1 / M, rel=.25)
    assert var(attn["o_proj"]["kernel"], cfg.attention_out_multiplier) == \
        pytest.approx(1 / (cfg.num_attention_heads * cfg.head_dim * L3), rel=.1)
    assert var(lp["mamba"]["out_proj"]["kernel"], cfg.ssm_out_multiplier) == \
        pytest.approx(1 / (cfg.d_inner * L3), rel=.1)
    assert var(ff["gate_proj"]["kernel"], cfg.mlp_multipliers[0]) == pytest.approx(1 / M, rel=.1)
    assert var(ff["down_proj"]["kernel"], cfg.mlp_multipliers[1]) == \
        pytest.approx(1 / (cfg.intermediate_size * L3), rel=.1)


# --------------------------------------------- (c) the scan at these widths --
def test_the_chunked_form_is_the_recurrence_at_the_published_widths():
    """``scan_ragged`` against ``ssm.step`` token by token at (heads, head,
    state, groups) = (32, 128, 256, 2): sixteen heads a group, two lane tiles a
    state row; three segments in 16 rows cut into chunks of 8."""
    T, S, H, P, N, G = 16, 3, 32, 128, 256, 2
    rng = np.random.default_rng(0)
    x = rng.normal(size=(T, H, P)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(T, H)) - 2)).astype(np.float32)
    A = -rng.uniform(1, 16, size=H).astype(np.float32)
    B, C = (rng.normal(size=(T, G, N)).astype(np.float32) / 16 for _ in range(2))
    token_seq = np.array([0] * 9 + [1] + [2] * 4 + [S - 1] * 2, np.int32)
    valid = np.arange(T) < 14
    h0 = rng.normal(size=(S, H, P, N)).astype(np.float32)
    onehot = ssm.segments(jnp.asarray(token_seq), jnp.asarray(valid), S)
    y, h = ssm.scan_ragged(x, dt, A, B, C, jnp.asarray(h0), onehot, chunk=8)
    for seq, rows in ((0, slice(0, 9)), (1, slice(9, 10)), (2, slice(10, 14))):
        state = jnp.asarray(h0[seq:seq + 1])
        for t in range(rows.start, rows.stop):
            want, state = ssm.step(x[t:t + 1], dt[t:t + 1], A, B[t:t + 1], C[t:t + 1], state)
            assert np.abs(np.asarray(y[t]) - np.asarray(want[0])).max() < 1e-3
        assert np.abs(np.asarray(h[seq]) - np.asarray(state[0])).max() < 1e-3


# ------------------------------------------------ (d) the sequence bucket --
def test_nine_sequences_land_in_the_models_one_sequence_bucket(model):
    cfg, params = model
    engine = engine_of(cfg, params, slots=12, seqs=20)
    assert engine.model.min_sequence_bucket == 24  # 20 padded to a multiple of 8
    engine.put(list(range(9)), [_ids(u, 2) for u in range(9)])
    batch = engine._batch.device_batch
    assert engine.model._bucket_of(batch) == (32, 24, 16)  # the token bucket starts at 24 too
    engine.put([0], [_ids(50, 1)])
    assert engine.model._bucket_of(engine._batch.device_batch) == (32, 24, 16)
    assert engine.model._synthetic_batch()["seq_meta"].shape == (24, 4 + 16 + 1)
    assert sequence_buckets(20, 24) == [24] and token_buckets(64, 24) == [32, 64]
    engine.close()


def test_a_mistral_batch_still_lands_in_8_and_16():
    from deepspeed_tpu.models.llama import LlamaConfig, init_params
    cfg = LlamaConfig.tiny(dtype=jnp.float32, model_type="mistral")
    engine = engine_of(cfg, init_params(cfg, rng=jax.random.PRNGKey(0))[1], seqs=16, slots=16)
    assert engine.model.min_sequence_bucket == 8
    assert engine.model.kv_cache_config().min_sequence_bucket == 8
    engine.put(list(range(3)), [_ids(u, 2) for u in range(3)])
    assert engine.model._bucket_of(engine._batch.device_batch) == (8, 8, 4)
    engine.put(list(range(9)), [_ids(u, 1) for u in range(9)])
    assert engine.model._bucket_of(engine._batch.device_batch) == (16, 16, 4)
    assert sequence_buckets(16) == [8, 16] and token_buckets(256) == [8, 16, 32, 64, 128, 256]
    engine.close()


# ------------------------------------------------------------ (e) refusals --
@pytest.mark.parametrize("change, said", [
    ({"mamba_norm_before_gate": True}, "gate BEFORE"), ({"mamba_rms_norm": False}, "gate BEFORE"),
    ({"mamba_use_mlp": False}, "feed-forward"), ({"hidden_act": "gelu"}, "only 'silu'"),
    ({"attention_bias": True}, "convolution's"), ({"projectors_bias": True}, "convolution's"),
    ({"tie_word_embeddings": True}, "tied"), ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"attn_layer_indices": (0, )}, "attn_layer_indices"),
    ({"mamba_d_ssm": 40}, "heads of"), ({"ssm_multipliers": (1.0, ) * 4}, "z, x, B, C and dt")])
def test_the_config_refuses_what_is_not_implemented_by_name(change, said):
    with pytest.raises((NotImplementedError, ValueError), match=said):
        fh.FalconH1Config.tiny(**change)


def test_d_inner_is_mamba_d_ssm_and_not_the_expansion():
    cfg = fh.FalconH1Config()
    assert cfg.d_inner == 4096 != cfg.mamba_expand * cfg.hidden_size
    assert (cfg.conv_dim, cfg.in_proj_width) == (5120, 9248)
    assert fh.FalconH1Config.tiny(mamba_d_ssm=None, mamba_expand=1, hidden_size=48).d_inner == 48
    hash(cfg)  # a static argument of the jitted initialisers
