"""Granite 4.0-H served through ``build_engine`` (PR 65): a Mamba-2 or a
position-free softmax mixer AND routed experts beside a shared one in every
layer, four scalar multipliers, a tied head. Prefill in uneven chunks, ``put``
and ``decode_loop`` against the plain float32 reference's full forward, row by
row; two sequences batched; the two shares of the experts, the shared expert
and the mixers counted once, adding up to the uncut reference's layer; one case
a multiplier that fails with it dropped; each control of the cell's tool; the
reference's recurrence against a second one, a loop over tokens; the counters;
and each refusal by its name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import granitemoehybrid as reference
from benchmark.tools import controls_granite
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.model_implementations import registry
from deepspeed_tpu.inference.v2.model_implementations.granitemoehybrid_v2 import \
    GraniteMoeHybridV2Model
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig, MemoryConfig)
from deepspeed_tpu.models import granitemoehybrid as gm
from deepspeed_tpu.utils import groups

BLOCK = 16
TOL = 1e-4  # of the largest logit: the tied head's logits are ~1e-2 at these widths


def sizes_of(cfg):
    """The configuration-file view of a program config, as the reference reads it."""
    sizes = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    sizes["num_local_experts"] = cfg.experts_held
    sizes["deployment_share"] = {"routed_over": cfg.num_local_experts,
                                 "experts_held": cfg.experts_held,
                                 "expert_rank": cfg.expert_rank}
    return sizes


def engine_of(cfg, params, kernel=False, blocks=96):
    groups.initialize_mesh(force=True)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                          size=blocks),
                               max_context=256, max_ragged_batch_size=64,
                               max_ragged_sequence_count=8, max_tracked_sequences=8)
    return build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=BLOCK, use_paged_kernel=kernel,
        expert_parallel={"capacity_factor": 3.0}))


@pytest.fixture(scope="module")
def model():
    cfg = gm.GraniteMoeHybridConfig.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1)
    return cfg, gm.init_params(cfg, rng=jax.random.PRNGKey(3))[1]


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


@pytest.fixture(scope="module")
def engine(model):
    """ONE engine for the tests that serve (its programs compile once); each
    flushes the sequences it made."""
    return engine_of(*model)


def _reference_rows(cfg, params, ids, rows):
    """The reference's logits at ``rows`` of ``ids``, padded with token 0 to
    ONE length: the same rows (every mixer is causal), one compilation."""
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


def _far(got, want):
    """The largest error over the largest logit."""
    return float(np.abs(got - want).max() / np.abs(want).max())


# --------------------------------------------------------------- (a) engine --
def test_prefill_in_uneven_chunks_then_decode_is_the_references_full_forward(model, engine):
    cfg, params = model
    assert registry.model_cls_for(cfg) is type(engine.model) is GraniteMoeHybridV2Model
    assert "granitemoehybrid" in registry.supported_model_types()
    served = engine.model
    # two phases a layer from different mixins: ONE K/V layer, THREE mixers, FOUR expert layers
    assert (served.num_layers, served.num_kv_layers, served.mamba2.mixers, len(served._moes)) == \
        (4, 1, 3, 4)
    assert served._ordinal == {0: 0, 1: 1, 3: 2, 2: 0}
    assert served.min_table_bucket == 16 and served.min_sequence_bucket == 8
    assert served.moe_path(8) == served.moe_path(64) == "grouped"
    kv, ssm_pool, conv_pool = engine._state_manager.kv_cache.cache
    assert kv.shape == (1, 2, 96, 2, BLOCK, 16)
    assert (ssm_pool.shape, ssm_pool.dtype) == ((3, 8, 16, 8, 16), jnp.float32)
    assert conv_pool.shape[:2] == (3, 8)
    assert "lm_head" not in params  # the head is the embedding
    prompt, feed = _ids(1, 75), _ids(2, 6)
    want = _want(cfg, params, prompt, feed)
    got, looped = _served(engine, prompt, feed, (5, 24, 17, 29))
    assert _far(got, want[:-1]) < TOL
    assert int(looped[0]) == int(want[-1].argmax())
    # the loop's steps continued every mixer's state and the K/V rows: its next tokens are
    # the reference's
    longer = np.concatenate([prompt, feed, looped[:3]])
    again = _reference_rows(cfg, params, longer, np.arange(longer.size - 3, longer.size))
    assert [int(t) for t in looped[1:]] == [int(r.argmax()) for r in again]
    assert {key[1:] for key in engine.lowerable_callables()["forward"]} == {(8, 16)}


def test_two_sequences_batched_are_each_its_solo_run(model, engine):
    cfg, params = model
    prompts, feeds = [_ids(10, 9), _ids(11, 40)], [_ids(20, 3), _ids(21, 3)]
    want = [_want(cfg, params, p, f) for p, f in zip(prompts, feeds)]
    got = [[r] for r in np.asarray(engine.put([0, 1], prompts))]
    for j in range(2):
        out = np.asarray(engine.put([1, 0], [feeds[1][j:j + 1], feeds[0][j:j + 1]]))
        got[1].append(out[0]), got[0].append(out[1])
    for u in (0, 1):
        assert _far(np.stack(got[u]), want[u][:3]) < TOL
        engine.flush(u)


def test_the_counters_are_the_mixers_and_the_share(model, engine):
    """``ssm_*`` over THREE mixers (not four layers), the share's ``moe_*``
    names over four expert layers, the sequence bucket's fill."""
    served = engine.model
    assert served.moe_count_names == ("moe_banks", "moe_assignments_local", "moe_visits",
                                      "moe_rows_walked")
    uids, feeds = [0, 1, 2], [_ids(30 + i, n) for i, n in enumerate((16, 4, 8))]
    engine._prepare(None, uids, feeds, True, 28)
    n_padded = engine._batch.device_batch["tok_meta"].shape[1]
    counts = {**served.dispatch_counts(n_padded, 28, 1), **served.batch_counts(engine._batch, 1)}
    assert (counts["ssm_tokens"], counts["ssm_segments"]) == (28 * 3, 3 * 3)
    assert counts["moe_assignments"] == 28 * 3 * 4 and counts["moe_path"] == "grouped"
    assert counts["ssm_slots_total"] == 8
    engine._post_forward(uids)
    for u in uids:
        engine.flush(u)


# ------------------------------------------------------------ (b) the share --
def test_the_two_shares_add_up_to_the_uncut_references_layer(model):
    """One layer, the same weights cut two ways: what rank 0 and rank 1 each
    add to the stream — the mixer and the shared expert whole on both, its own
    four experts' part of the routed sum — with the mixer and the shared expert
    counted ONCE, is what the uncut reference's layer adds (all eight banks)."""
    cfg, _ = model
    whole = dataclasses.replace(cfg, experts_held=8, expert_rank=0, num_hidden_layers=1,
                                layer_types=("mamba", ))
    params = gm.init_params(whole, rng=jax.random.PRNGKey(7))[1]
    p = params["layers_0"]
    x = jnp.asarray(np.random.default_rng(4).normal(size=(40, cfg.hidden_size)), jnp.float32)
    eps, rm = cfg.rms_norm_eps, cfg.residual_multiplier
    settings = reference.layer_settings(sizes_of(whole))
    mixed = reference.mixer_part(x, p, kind="mamba", eps=eps, residual=rm,
                                 settings=settings["mamba"])

    def routed_by(first, held):
        banks = {k: v[first:first + held] for k, v in p["mlp"]["experts"].items()}
        part = dict(p, mlp=dict(p["mlp"], experts=banks))
        out, _ = reference.experts_part(mixed, part, eps=eps, residual=rm,
                                        settings=(("top_k", cfg.num_experts_per_tok),
                                                  ("first_held", first)))
        return out - mixed  # what the layer's second add put into the stream

    f = reference.rms_norm(mixed, p["post_attention_layernorm"]["weight"], eps)
    shared = rm * reference.swiglu(f, p["mlp"]["shared_experts"])
    uncut, lower, upper = routed_by(0, 8), routed_by(0, 4), routed_by(4, 4)
    assert float(jnp.abs(lower - shared).max()) > 1e-3 < float(jnp.abs(upper - shared).max())
    assert float(jnp.abs(lower + upper - shared - uncut).max()) < 1e-5
    # and the served share IS the reference's share (test (a) holds rank 1 of two end to end)


# ------------------------------------------------------- (c) the multipliers --
def _residual_dropped_on(which):
    """``_add`` with the multiplier dropped on a layer's first (the mixer's) or
    second (the experts') add."""
    real = GraniteMoeHybridV2Model._add
    calls = {"n": 0}

    def add(self, x, branch):
        calls["n"] += 1
        if calls["n"] % 2 == which:
            return (x.astype(jnp.float32) + branch.astype(jnp.float32)).astype(x.dtype)
        return real(self, x, branch)
    return add


@pytest.fixture(scope="module")
def small():
    """Two layers, one of each mixer: what the cases that build an engine each
    are served from (one program an engine: two chunks of one bucket)."""
    cfg = gm.GraniteMoeHybridConfig.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1,
                                         num_hidden_layers=2, layer_types=("mamba", "attention"))
    params = gm.init_params(cfg, rng=jax.random.PRNGKey(5))[1]
    prompt = _ids(1, 40)
    return cfg, params, prompt, _reference_rows(cfg, params, prompt, np.asarray([39]))


def _last_row(cfg, params, prompt):
    """The engine's logits after ``prompt`` fed in two chunks of one bucket."""
    engine = engine_of(cfg, params)
    engine.put([0], [prompt[:20]])
    return np.asarray(engine.put([0], [prompt[20:]]))


def test_the_small_engine_as_built_is_the_reference(small):
    cfg, params, prompt, want = small
    assert _far(_last_row(cfg, params, prompt), want) < TOL


@pytest.mark.parametrize("dropped", ["embedding_multiplier", "attention_multiplier",
                                     "residual_on_the_mixer", "residual_on_the_experts",
                                     "logits_scaling"])
def test_each_multiplier_dropped_is_far_from_the_reference(small, monkeypatch, dropped):
    """The engine as built is the reference to 1e-4; built with ONE multiplier
    dropped — the embedding's 12, the softmax scale back at 1 / sqrt(head_dim),
    0.22 on the mixer's add or on the experts', the logits' 1 / 16 — it is far
    from it."""
    cfg, params, prompt, want = small
    if dropped == "embedding_multiplier":
        cfg = dataclasses.replace(cfg, embedding_multiplier=1.0)
    elif dropped == "attention_multiplier":
        cfg = dataclasses.replace(cfg, attention_multiplier=cfg.head_dim**-0.5)
    elif dropped == "logits_scaling":
        cfg = dataclasses.replace(cfg, logits_scaling=1.0)
    else:
        monkeypatch.setattr(GraniteMoeHybridV2Model, "_add",
                            _residual_dropped_on(1 if dropped.endswith("mixer") else 0))
    assert _far(_last_row(cfg, params, prompt), want) > 100 * TOL


@pytest.mark.parametrize("control", ["no_state_carry", "no_conv_carry", "drop_expert"])
def test_each_control_changes_the_logits(small, control):
    """The CPU twins of ``benchmark/tools/controls_granite.py``'s controls of the
    program and of its tree (``residual_one`` and ``softmax_scale`` are the
    multipliers' cases above): an engine built under one is far from the
    reference, and the patch is gone after."""
    cfg, params, prompt, want = small
    cfg_, params_, patch = controls_granite.spoilt(control, cfg, params, 256)
    with patch:
        got = _last_row(cfg_, params_, prompt)
    assert _far(got, want) > 100 * TOL
    from deepspeed_tpu.inference.v2.modules import ssm
    assert ssm.scan_in_place.__module__ == ssm.__name__ == ssm.conv_ragged.__module__


# ---------------------------------------------------------- (d) the reference --
def test_the_references_recurrence_is_a_loop_over_tokens(model):
    """The reference's Mamba-2 mixer (``references/nemotron_h.py:mamba``, one
    ``lax.scan`` step a token) against a second recurrence written out here in
    numpy, float64, token by token and head by head, ONE group."""
    cfg, params = model
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params["layers_0"]["mamba"])
    H, P, N, K = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_conv
    D = H * P
    u = np.random.default_rng(9).normal(size=(11, cfg.hidden_size))
    proj = u @ p["in_proj"]["kernel"]
    z, xbc, dt = proj[:, :D], proj[:, D:D + cfg.conv_dim], proj[:, D + cfg.conv_dim:]
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    state, out = np.zeros((H, P, N)), []
    for t in range(u.shape[0]):
        taps = [xbc[t - (K - 1) + j] if t - (K - 1) + j >= 0 else np.zeros(cfg.conv_dim)
                for j in range(K)]
        c = silu(sum(taps[j] * p["conv1d"]["kernel"][:, j] for j in range(K))
                 + p["conv1d"]["bias"])
        x, B, C = c[:D].reshape(H, P), c[D:D + N], c[D + N:]
        step = np.log1p(np.exp(dt[t] + p["dt_bias"]))
        y = np.zeros((H, P))
        for h in range(H):
            state[h] = np.exp(-step[h] * np.exp(p["A_log"][h])) * state[h] \
                + step[h] * np.outer(x[h], B)
            y[h] = state[h] @ C + p["D"][h] * x[h]
        g = y.reshape(D) * silu(z[t])
        g = g / np.sqrt(np.mean(g * g) + cfg.rms_norm_eps) * p["norm"]["weight"]
        out.append(g @ p["out_proj"]["kernel"])
    with jax.default_matmul_precision("highest"):
        got = reference.mamba(jnp.asarray(u, jnp.float32), params["layers_0"]["mamba"], heads=H,
                              head_dim=P, groups=1, state=N, eps=cfg.rms_norm_eps)
    assert np.abs(np.asarray(got) - np.stack(out)).max() < 1e-4 * np.abs(np.stack(out)).max()


# ------------------------------------------------------------- (e) refusals --
@pytest.mark.parametrize("change,said", [
    ({"position_embedding_type": "rope"}, "position_embedding_type 'rope'"),
    ({"attention_bias": True}, "attention_bias / mamba_proj_bias"),
    ({"mamba_proj_bias": True}, "attention_bias / mamba_proj_bias"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings false"),
    ({"layer_types": ("mamba", "mlp", "attention", "mamba")}, "layer_types ['mlp']"),
    ({"hidden_act": "gelu"}, "hidden_act 'gelu'"),
    ({"experts_held": 3}, "a share of 3 experts"),
])
def test_what_is_not_served_is_refused_by_its_name(change, said):
    with pytest.raises((NotImplementedError, ValueError)) as refused:
        gm.GraniteMoeHybridConfig.tiny(**change)
    assert said in str(refused.value)


def test_a_model_of_one_kind_of_mixer_is_refused_where_it_is_built(model):
    cfg, params = model
    only = dataclasses.replace(cfg, layer_types=("mamba", ) * 4)
    with pytest.raises(NotImplementedError, match="would leave one of them empty"):
        engine_of(only, params)
