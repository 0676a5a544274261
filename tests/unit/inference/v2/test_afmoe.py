"""Trinity-Mini's family served (PR 34, ``model_type="afmoe"``): sigmoid-scored
top-k experts with a selection bias and a route scale beside a shared expert, a
leading dense layer, gated attention with q/k norm, rotary on the window layers
alone, four norms a layer, a scaled embedding, at tiny sizes on the CPU.

The system is held to ``benchmark/references/afmoe.py`` (float32, no cache, no
kernel, one sequence) on LOGITS through chunked prefill across the window ->
release in the window groups -> decode, and through ``decode_loop``. The stack
is the benchmark's: one dense layer then four expert layers in the pattern
s, s, s, f, s (five KV layer groups of one layer), 16 experts top-4, window 16
over 4- or 8-token blocks."""

import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import afmoe as reference
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.model_implementations.afmoe_v2 import AfmoeV2Model
from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _swiglu
from deepspeed_tpu.inference.v2.model_implementations.registry import supported_model_types
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode, DSStateManagerConfig,
                                                               MemoryConfig)
from deepspeed_tpu.models import afmoe, mellum
from deepspeed_tpu.utils import groups
from tests.unit.inference.v2.program_hashes import BUCKETS, _stable

WINDOW, BLOCK, FEED = 16, 4, 32
PATTERN = ("sliding_attention", ) * 3 + ("full_attention", "sliding_attention")
SIZES = dict(vocab_size=256, hidden_size=48, head_dim=16, num_hidden_layers=5,
             num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
             moe_intermediate_size=32, num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
             num_shared_experts=1, sliding_window=WINDOW, layer_types=PATTERN, rms_norm_eps=1e-5)

# Everything is float32 here (weights, pool, reference): what is left is the
# order of float32 sums, ~2e-6 of logits of scale ~3. 1e-4 absolute is 50 x
# that; ``test_the_tolerance_catches_*`` show what another model's answer is off by.
ATOL = 1e-4


def _sizes(cfg, **changed):
    """The configuration as a benchmark file states it (the reference's view)."""
    sizes = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return dict(sizes, layer_types=list(cfg.layer_types)) | changed


def _model(seed=3, **changed):
    cfg = afmoe.AfmoeConfig(dtype=jnp.float32, **dict(SIZES, **changed))
    return cfg, afmoe.init_params(cfg, jax.random.PRNGKey(seed))[1]


@pytest.fixture(scope="module")
def model():
    return _model()


# top-4 of 64: a decode bucket's 8 rows x 4 = 32 assignments can touch at most
# half of the banks, so it routes by sorting (PR 35); a 32-token bucket cannot
# say so and keeps the masks. ``SIZES``' top-4 of 16 keeps them in every bucket.
@pytest.fixture(scope="module")
def wide_model():
    return _model(num_experts=64)


def _engine(model, kernel=False, blocks=400, budget=FEED, block=BLOCK, capacity_factor=4.0,
            max_context=128):
    groups.initialize_mesh(force=True)
    cfg, params = model
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=blocks),
                               max_context=max_context, max_ragged_batch_size=budget,
                               max_ragged_sequence_count=8)
    engine = build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=block, use_paged_kernel=kernel,
        expert_parallel={"capacity_factor": capacity_factor}))
    assert isinstance(engine.model, AfmoeV2Model)
    return engine


def _ids(seed, n, vocab=SIZES["vocab_size"]):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _reference_rows(model, ids, rows, **changed):
    return np.asarray(reference.forward_logits(model[1], _sizes(model[0], **changed), ids,
                                               rows=np.asarray(rows)))


def _feed(engine, uid, ids, chunks):
    out, at = [], 0
    for n in chunks:
        out.append(np.asarray(engine.put([uid], [ids[at:at + n]]))[0])
        at += n
    assert at == len(ids)
    return out


# ---------------------------------------------------------------- the model ---
def test_the_model_is_registered_and_reads_its_layers_from_the_config(model):
    assert "afmoe" in supported_model_types()
    engine = _engine(model)
    m = engine.model
    assert m.head_dim == 16 and m.head_dim != SIZES["hidden_size"] // SIZES["num_attention_heads"]
    assert [m.attention_window_of(li) for li in range(5)] == [16, 16, 16, 0, 16]
    # no shorter period than the stack: five groups of one layer, five tables a sequence
    assert m.group_windows == (16, 16, 16, 0, 16) and engine.n_kv_cache_groups == 5
    assert engine._state_manager.kv_cache.cache.shape == (1, 2, 400, 2, BLOCK, 16)
    with pytest.raises(ValueError, match="no one attention window"):
        m.attention_window
    # one RaggedMoE a SPARSE layer, told what the model says of its router
    assert len(m._moes) == 4 and {(r.score_func, r.route_scale, r.top_k, r.num_experts)
                                  for r in m._moes} == {("sigmoid", 2.826, 4, 16)}
    tree = model[1]
    assert "mlp" in tree["layers_0"] and "block_sparse_moe" not in tree["layers_0"]
    assert set(tree["layers_1"]["block_sparse_moe"]) == {"gate", "expert_bias", "ExpertFFN_0",
                                                         "shared_experts"}


@pytest.mark.parametrize("changed, error, match", [
    (dict(num_expert_groups=2), NotImplementedError, "expert groups"),
    (dict(topk_group=2), ValueError, "1 groups, 2 kept"),
    (dict(score_func="tanh"), NotImplementedError, "score_func"),
    (dict(tie_word_embeddings=True), NotImplementedError, "tied"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}), NotImplementedError, "rope_scaling"),
    (dict(hidden_act="gelu"), NotImplementedError, "hidden_act"),
    (dict(layer_types=("sliding_attention", ) * 4 + ("chunked_attention", )), ValueError,
     "only"),
    (dict(layer_types=PATTERN[:4]), ValueError, "must name 5 layers"),
    (dict(num_dense_layers=5), ValueError, "at least one expert layer"),
    (dict(num_experts_per_tok=17), ValueError, "of 16 experts"),
    (dict(sliding_window=0), ValueError, "sliding_window > 0"),
])
def test_the_config_refuses_what_is_not_implemented(changed, error, match):
    with pytest.raises(error, match=match):
        afmoe.AfmoeConfig(**dict(SIZES, **changed))


def test_the_published_config_is_the_default():
    cfg = afmoe.AfmoeConfig()
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.num_experts, cfg.num_experts_per_tok, cfg.num_shared_experts,
            cfg.moe_intermediate_size, cfg.intermediate_size, cfg.num_dense_layers,
            cfg.sliding_window, cfg.vocab_size, cfg.route_scale, cfg.score_func) == \
        (2048, 128, 32, 4, 128, 8, 1, 1024, 6144, 2, 2048, 200192, 2.826, "sigmoid")
    # every fourth layer full, as the published layer_types list says
    assert cfg.layer_types == (("sliding_attention", ) * 3 + ("full_attention", )) * 8
    assert [cfg.is_dense(i) for i in range(4)] == [True, True, False, False]


# (a) ------------------------------------------- system against the reference ---
# 32 + 32 tokens are buckets of 32 (the second straddles the window's edge), 25
# a bucket of 32, 7 a bucket of 8 (the token grid), then single tokens: the
# sequence crosses the window in prefill AND goes on past it in decode
CHUNKS = [32, 32, 25, 7, 1, 1, 1]


@pytest.mark.parametrize("block", [4, 8])
def test_prefill_in_chunks_release_then_decode_matches_the_float32_reference(model, block):
    engine = _engine(model, block=block)
    ids = _ids(1, sum(CHUNKS))
    got = _feed(engine, 0, ids, CHUNKS)
    want = _reference_rows(model, ids, np.cumsum(CHUNKS) - 1)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=ATOL, rtol=0)
    seq = engine._state_manager.get_sequence(0)
    assert [seq.released_in(g) > 0 for g in range(5)] == [True, True, True, False, True]
    assert engine.released_blocks > 0


def test_decode_loop_crosses_the_window_while_decoding_and_the_window_groups_release(model):
    """A 10-token prompt, then 12 tokens through ``decode_loop`` (the window is
    16: the sequence crosses it INSIDE the loops), then one ``put`` whose logits
    read the cache the loops wrote."""
    engine = _engine(model)
    ids = _ids(3, 10)
    first = _feed(engine, 0, ids, [10])[0]
    np.testing.assert_allclose(first, _reference_rows(model, ids, [9])[0], atol=ATOL, rtol=0)
    seq = engine._state_manager.get_sequence(0)
    tokens, fed = [], int(first.argmax())
    for _ in range(3):
        out = engine.decode_loop([0], [np.array([fed], np.int32)], 4)[0].tolist()
        tokens += [fed] + out[:3]
        fed = out[3]
    assert seq.seen_tokens == 22 and [seq.released_in(g) > 0 for g in range(5)] == \
        [True, True, True, False, True]
    # greedy: each generated token is the reference's argmax given the ones before
    full = np.concatenate([ids, np.array(tokens + [fed], np.int32)])
    want = _reference_rows(model, full, range(9, 23))
    assert full[10:23].tolist() == want[:13].argmax(-1).tolist()
    last = np.asarray(engine.put([0], [full[22:23]]))[0]
    np.testing.assert_allclose(last, want[13], atol=ATOL, rtol=0)


def _prefill_then_loops(engine, prompts, loops, steps):
    """Each prompt in one ``put``, then ``loops`` chunks of ``steps`` greedy
    tokens for all of them together; the logits of every prompt's last row and
    each sequence's prompt + generated tokens."""
    first = [np.asarray(engine.put([u], [p]))[0] for u, p in enumerate(prompts)]
    uids = list(range(len(prompts)))
    fed = [int(f.argmax()) for f in first]
    full = [p.tolist() for p in prompts]
    for _ in range(loops):
        out = np.asarray(engine.decode_loop(uids, [np.array([t], np.int32) for t in fed], steps))
        for u in uids:
            full[u] += [fed[u]] + out[u, :-1].tolist()
            fed[u] = int(out[u, -1])
    last = np.asarray(engine.put(uids, [np.array([t], np.int32) for t in fed]))
    return first, [np.asarray(f + [t], np.int32) for f, t in zip(full, fed)], last


@pytest.mark.parametrize("live", [8, 5], ids=["8 live rows", "5 live rows of 8"])
def test_a_decode_loop_on_the_grouped_path_matches_the_reference_and_the_capacity_path(
        wide_model, live, monkeypatch):
    """The 8-row decode bucket of a top-4-of-64 model routes by sorting inside
    ``decode_loop``'s scan (and in the 8-token ``put`` behind it): greedy tokens
    are the float32 reference's, the logits within ``ATOL`` of it, and equal
    to the capacity path's at the dropless factor."""
    from deepspeed_tpu.inference.v2.modules import heuristics
    prompts = [_ids(40 + u, 5 + 2 * u) for u in range(live)]

    def run():
        engine = _engine(wide_model, capacity_factor=64 / 4)  # dropless on the masks too
        return engine, _prefill_then_loops(engine, prompts, loops=2, steps=4)

    engine, (first, full, last) = run()
    assert engine.model.moe_path(8) == "grouped" and engine.model.moe_path(32) == "capacity"
    for u, (prompt, ids) in enumerate(zip(prompts, full)):
        want = _reference_rows(wide_model, ids, range(prompt.size - 1, ids.size))
        np.testing.assert_allclose(first[u], want[0], atol=ATOL, rtol=0)
        assert ids[prompt.size:].tolist() == want[:-1].argmax(-1).tolist()
        np.testing.assert_allclose(last[u], want[-1], atol=ATOL, rtol=0)
    monkeypatch.setattr(heuristics, "MOE_BANKS_TOUCHED_MAX", 0)  # the rule before PR 35
    masks, (_, full_masks, last_masks) = run()
    assert masks.model.moe_path(8) == "capacity"
    assert [f.tolist() for f in full_masks] == [f.tolist() for f in full]
    np.testing.assert_allclose(last, last_masks, atol=1e-5, rtol=0)


def test_sequences_on_both_sides_of_the_window_share_a_batch(model):
    engine = _engine(model)
    a, b, c = _ids(5, 40), _ids(6, 9), _ids(7, 21)
    _feed(engine, 0, a[:32], [32])
    logits = np.asarray(engine.put([0, 1, 2], [a[32:], b, c[:15]]))
    np.testing.assert_allclose(logits[0], _reference_rows(model, a, [39])[0], atol=ATOL, rtol=0)
    np.testing.assert_allclose(logits[1], _reference_rows(model, b, [8])[0], atol=ATOL, rtol=0)
    np.testing.assert_allclose(logits[2], _reference_rows(model, c, [14])[0], atol=ATOL, rtol=0)
    out = np.asarray(engine.decode_loop([0, 1, 2], [a[39:40] * 0 + 7, b[8:9] * 0 + 7,
                                                    c[15:16]], 2))
    assert out.shape == (3, 2)


def test_the_kernel_arm_matches_the_reference_at_head_dim_128():
    """The Pallas kernel (interpret mode) takes each layer's own window, table
    and cache layer, and q and k normed, unrotated on the full layer."""
    model = _model(seed=5, hidden_size=64, head_dim=128, num_attention_heads=2,
                   num_key_value_heads=1, num_experts=4, num_experts_per_tok=2)
    ids, chunks = _ids(2, 72), [32, 32, 7, 1]
    want = _reference_rows(model, ids, np.cumsum(chunks) - 1)
    engine = _engine(model, kernel=True, block=8, capacity_factor=2.0)
    assert engine.model.attention_arm(64) == "paged_tiled"
    for g, w in zip(_feed(engine, 0, ids, chunks), want):
        np.testing.assert_allclose(g, w, atol=ATOL, rtol=0)
    assert engine._state_manager.get_sequence(0).released_in(0) > 0


@pytest.mark.parametrize("what, changed", [
    ("a window on the full layer", dict(layer_types=["sliding_attention"] * 5)),
    ("no window at all", dict(sliding_window=10**6)),
    ("softmax scores", dict(score_func="softmax")),
    ("no route scale", dict(route_scale=1.0)),
    ("weights not renormalised", dict(route_norm=False)),
    ("top-3 routing", dict(num_experts_per_tok=3)),
    ("an unscaled embedding", dict(mup_enabled=False)),
])
def test_the_tolerance_catches_another_models_answer(model, what, changed):
    engine = _engine(model)
    ids = _ids(4, 96)
    got = _feed(engine, 0, ids, [32, 32, 32])[-1]
    np.testing.assert_allclose(got, _reference_rows(model, ids, [95])[0], atol=ATOL, rtol=0)
    other = _reference_rows(model, ids, [95], **changed)[0]
    assert np.abs(other - got).max() > 30 * ATOL, what


@pytest.mark.parametrize("what", ["the router's matmul", "the sigmoid"])
def test_a_router_in_bf16_fails_the_tolerance(model, what, monkeypatch):
    """A lower precision than stated would fail: the router's logits, or only
    its sigmoid, computed in bf16 move the routing weights by ~2^-9 and flip
    choices, and the float32 tolerance does not hold."""

    def in_bf16(self, h, gate_w, gate_seed=None, replica=None):
        if what == "the sigmoid":
            logits = h.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        else:
            logits = h.astype(jnp.bfloat16) @ gate_w.astype(jnp.bfloat16)
        return jax.nn.sigmoid(logits.astype(jnp.bfloat16)).astype(jnp.float32)

    monkeypatch.setattr(RaggedMoE, "_router_probs", in_bf16)
    engine = _engine(model)
    ids = _ids(4, 96)
    got = np.stack(_feed(engine, 0, ids, [32, 32, 32]))
    want = _reference_rows(model, ids, [31, 63, 95])
    assert np.abs(got - want).max() > 3 * ATOL


# (b) ------------------------------------------------------------ the routing ---
def _plain_moe(h, gate_w, wi, wo, bias, top_k, score_func, norm, scale):
    """Every token through the top-k of score + bias, weighted by the SCORES,
    one assignment at a time, in float64."""
    h64 = np.asarray(h, np.float64)
    logits = h64 @ np.asarray(gate_w, np.float64)
    if score_func == "sigmoid":
        scores = 1.0 / (1.0 + np.exp(-logits))
    else:
        scores = np.exp(logits - logits.max(-1, keepdims=True))
        scores /= scores.sum(-1, keepdims=True)
    keys = scores + (0.0 if bias is None else np.asarray(bias, np.float64))
    out = np.zeros_like(h64)
    chosen_all = []
    for t in range(h.shape[0]):
        chosen = np.argsort(-keys[t], kind="stable")[:top_k]
        chosen_all.append(sorted(chosen.tolist()))
        weights = scores[t, chosen] / ((scores[t, chosen].sum() + 1e-20) if norm else 1.0) * scale
        for e, w in zip(chosen, weights):
            gate, up = np.split(h64[t] @ np.asarray(wi[e], np.float64), 2)
            out[t] += w * ((gate / (1 + np.exp(-gate)) * up) @ np.asarray(wo[e], np.float64))
    return out, chosen_all, scores


def _bank(seed, T, M, E, F, bias_scale=0.3):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (T, M)), jax.random.normal(k[1], (M, E)) / math.sqrt(M),
            jax.random.normal(k[2], (E, M, 2 * F)) / math.sqrt(M),
            jax.random.normal(k[3], (E, F, M)) / math.sqrt(F),
            bias_scale * jax.random.normal(k[4], (E, )))


@pytest.mark.parametrize("biased", [False, True], ids=["no bias", "bias"])
@pytest.mark.parametrize("score_func", ["sigmoid", "softmax"])
@pytest.mark.parametrize("top_k", [1, 2, 8])
def test_the_router_picks_by_score_plus_bias_and_weighs_by_score(top_k, score_func, biased):
    groups.initialize_mesh(force=True)
    E = 16
    h, gate_w, wi, wo, bias = _bank(top_k, 24, 32, E, 16)
    bias = bias if biased else None
    moe = RaggedMoE(num_experts=E, top_k=top_k, capacity_factor=E / top_k, score_func=score_func,
                    route_scale=2.826)
    got = np.asarray(moe(h, gate_w, wi, wo, select_bias=bias))
    want, chosen, scores = _plain_moe(h, gate_w, wi, wo, bias, top_k, score_func, True, 2.826)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    if biased and top_k > 1:  # (one renormalised weight is the route scale whatever was picked)
        # the case the bias is there for: it changes WHO is chosen on some rows
        by_score = [sorted(np.argsort(-scores[t])[:top_k].tolist()) for t in range(24)]
        assert by_score != chosen
        # and weights taken from score + bias are another answer
        keys = np.asarray(moe._router_probs(h, gate_w)) + np.asarray(bias)
        topk_p, _ = moe._choose(jnp.asarray(keys, jnp.float32))
        right_p, _ = moe._choose(moe._router_probs(h, gate_w), bias)
        assert np.abs(np.asarray(topk_p) - np.asarray(right_p)).max() > 1e-3


def test_a_bias_flips_one_choice_and_the_weights_stay_the_scores():
    """Scores 0.9, 0.8, 0.7, 0.6 and a bias of +0.25 on the last: top-2 of the
    scores is {0, 1}, of score + bias {0, 3}; the weights are 0.9 and 0.6
    renormalised and scaled, not 0.9 and 0.85."""
    moe = RaggedMoE(num_experts=4, top_k=2, score_func="sigmoid", route_scale=2.0)
    scores = jnp.asarray([[0.9, 0.8, 0.7, 0.6]], jnp.float32)
    p, e = moe._choose(scores)
    assert sorted(np.asarray(e)[0].tolist()) == [0, 1]
    p, e = moe._choose(scores, jnp.asarray([0.0, 0.0, 0.0, 0.25]))
    assert np.asarray(e)[0].tolist() == [0, 3]
    np.testing.assert_allclose(np.asarray(p)[0], [2.0 * 0.9 / 1.5, 2.0 * 0.6 / 1.5], rtol=1e-6)
    raw = RaggedMoE(num_experts=4, top_k=2, score_func="sigmoid", norm_topk_prob=False)
    np.testing.assert_allclose(np.asarray(raw._choose(scores, jnp.asarray([0, 0, 0, 0.25]))[0])[0],
                               [0.9, 0.6], rtol=1e-6)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        RaggedMoE(num_experts=4, top_k=2, score_func="tanh")


@pytest.mark.parametrize("skew", ["spread", "every token to one expert"])
def test_capacity_path_grouped_path_and_reference_agree_at_top_8_of_128(skew):
    """128 experts 16 wide: a 128-token bucket routes by sorting (the masks
    would be 128 x 128 x 128 elements), the same tokens 32 at a time take the
    masks. With every token sent to the SAME 8 experts a capacity factor of
    128 / 8 still holds every assignment (capacity = tokens)."""
    groups.initialize_mesh(force=True)
    E, k, F = 128, 8, 16
    h, gate_w, wi, wo, bias = _bank(11, 128, 32, E, F, bias_scale=0.02)
    if skew != "spread":
        bias = jnp.where(jnp.arange(E) < k, 5.0, 0.0)  # score + bias: the first 8, always
    moe = RaggedMoE(num_experts=E, top_k=k, capacity_factor=E / k, score_func="sigmoid",
                    route_scale=2.826)
    assert moe.path(128, F) == "grouped" and moe.path(32, F) == "capacity"
    want, chosen, _ = _plain_moe(h, gate_w, wi, wo, bias, k, "sigmoid", True, 2.826)
    if skew != "spread":
        assert all(c == list(range(k)) for c in chosen)
    grouped = np.asarray(moe(h, gate_w, wi, wo, select_bias=bias))
    masks = np.concatenate([np.asarray(moe(h[i:i + 32], gate_w, wi, wo, select_bias=bias))
                            for i in range(0, 128, 32)])
    np.testing.assert_allclose(grouped, want, atol=5e-5, rtol=0)
    np.testing.assert_allclose(masks, want, atol=5e-5, rtol=0)
    # padding rows take no slot and no weight on either path
    valid = jnp.arange(128) < 100
    for T in (128, 32):
        out = np.asarray(moe(h[:T], gate_w, wi, wo, token_valid=valid[:T] if T == 128
                             else jnp.arange(32) < 20, select_bias=bias))
        n = 100 if T == 128 else 20
        np.testing.assert_allclose(out[:n], want[:n], atol=5e-5, rtol=0)
        assert np.abs(out[n:]).max() == 0.0


# (c) ------------------------------------------- what else the layers are made of ---
def _phase_model(model):
    engine = _engine(model)
    return engine.model, model[1]


def _x(seed, T=6, M=SIZES["hidden_size"]):
    return jax.random.normal(jax.random.PRNGKey(seed), (T, M), jnp.float32)


def test_the_shared_expert_is_counted_once(model):
    m, params = _phase_model(model)
    cfg, li, x = model[0], 2, _x(0)
    lp = params[f"layers_{li}"]
    mp = lp["block_sparse_moe"]
    h = np.asarray(reference.rms_norm(x, lp["pre_mlp_layernorm"]["weight"], cfg.rms_norm_eps))
    routed, _, _ = _plain_moe(h, mp["gate"], mp["ExpertFFN_0"]["wi"], mp["ExpertFFN_0"]["wo"],
                              mp["expert_bias"], 4, "sigmoid", True, cfg.route_scale)
    shared = np.asarray(reference.swiglu(jnp.asarray(h), mp["shared_experts"]))

    def out(times):
        return np.asarray(x + reference.rms_norm(jnp.asarray(routed + times * shared, jnp.float32),
                                                 lp["post_mlp_layernorm"]["weight"],
                                                 cfg.rms_norm_eps))

    got = np.asarray(m._ffn_phase(params, li, x))
    np.testing.assert_allclose(got, out(1), atol=2e-5, rtol=0)
    assert np.abs(got - out(2)).max() > 1e-2 and np.abs(got - out(0)).max() > 1e-2


def test_a_leading_layer_is_the_dense_swiglu_under_the_same_two_norms(model):
    m, params = _phase_model(model)
    cfg, x = model[0], _x(1)
    lp = params["layers_0"]
    assert lp["mlp"]["gate_proj"]["kernel"].shape == (48, 96)
    want = x + reference.rms_norm(
        reference.swiglu(reference.rms_norm(x, lp["pre_mlp_layernorm"]["weight"],
                                            cfg.rms_norm_eps), lp["mlp"]),
        lp["post_mlp_layernorm"]["weight"], cfg.rms_norm_eps)
    np.testing.assert_allclose(np.asarray(m._ffn_phase(params, 0, x)), np.asarray(want),
                               atol=2e-5, rtol=0)
    # the one function a Llama layer's mlp and a shared expert go through
    h = _x(2)
    np.testing.assert_allclose(np.asarray(_swiglu(h, lp["mlp"])),
                               np.asarray(reference.swiglu(h, lp["mlp"])), atol=2e-5, rtol=0)


def _attn_phase(m, params, li, x, pos, seen):
    """The attention phase over a stand-in for the paged kernel that returns
    ``v`` repeated to the heads and keeps the q and k it was given."""

    def attn_fn(q, k, v, cache, li):
        seen.append((np.asarray(q), np.asarray(k)))
        return jnp.repeat(v, q.shape[1] // v.shape[1], axis=1), cache

    out, _ = m._attn_phase(params, li, x, None, attn_fn, {"token_pos": jnp.asarray(pos)})
    return np.asarray(out)


def test_q_and_k_are_normed_a_head_and_only_a_window_layer_rotates_them(model):
    m, params = _phase_model(model)
    x, seen = _x(3), []
    here = _attn_phase(m, params, 3, x, np.arange(6), seen)       # the full layer
    there = _attn_phase(m, params, 3, x, np.arange(6) + 37, seen)  # every position moved
    np.testing.assert_array_equal(here, there)
    np.testing.assert_array_equal(seen[0][0], seen[1][0])
    q, k = seen[0]
    # rms over each head's 16 entries is 1 (the gains are ones)
    np.testing.assert_allclose(np.sqrt((q * q).mean(-1)), 1.0, atol=1e-3)
    np.testing.assert_allclose(np.sqrt((k * k).mean(-1)), 1.0, atol=1e-3)
    seen.clear()
    _attn_phase(m, params, 0, x, np.arange(6), seen)              # a window layer
    _attn_phase(m, params, 0, x, np.arange(6) + 37, seen)
    assert np.abs(seen[0][0] - seen[1][0]).max() > 0.1 and np.abs(seen[0][1] - seen[1][1]).max() > 0.1
    # position 0 is the identity rotation: the window layer's q at 0 is normed, unrotated
    np.testing.assert_allclose(np.sqrt((seen[0][0] ** 2).mean(-1)), 1.0, atol=1e-3)
    # a scale on q_proj is undone by the norm
    scaled = jax.tree.map(lambda a: a, params)
    scaled["layers_3"] = dict(params["layers_3"], self_attn=dict(
        params["layers_3"]["self_attn"],
        q_proj={"kernel": params["layers_3"]["self_attn"]["q_proj"]["kernel"] * 3.0}))
    _attn_phase(m, scaled, 3, x, np.arange(6), seen)
    np.testing.assert_allclose(seen[-1][0], q, atol=1e-4)


def test_the_output_gate_halves_the_heads_output_where_its_weights_are_zero(model, monkeypatch):
    m, params = _phase_model(model)
    x = _x(4)
    # read the branch before its out-norm: a uniform halving is what a norm removes
    monkeypatch.setattr(AfmoeV2Model, "_attn_out", lambda self, lp, y: y)
    lp = params["layers_1"]
    ap = lp["self_attn"]
    zero = dict(params, layers_1=dict(lp, self_attn=dict(ap, gate_proj={
        "kernel": jnp.zeros_like(ap["gate_proj"]["kernel"])})))
    h = reference.rms_norm(x, lp["input_layernorm"]["weight"], model[0].rms_norm_eps)
    v = (h @ ap["v_proj"]["kernel"]).reshape(6, 2, 16)
    heads = jnp.repeat(v, 2, axis=1).reshape(6, 64)
    got = _attn_phase(m, zero, 1, x, np.arange(6), [])
    np.testing.assert_allclose(got, np.asarray(x + (0.5 * heads) @ ap["o_proj"]["kernel"]),
                               atol=2e-5, rtol=0)
    gated = _attn_phase(m, params, 1, x, np.arange(6), [])
    want = x + (heads * jax.nn.sigmoid(h @ ap["gate_proj"]["kernel"])) @ ap["o_proj"]["kernel"]
    np.testing.assert_allclose(gated, np.asarray(want), atol=2e-5, rtol=0)


@pytest.mark.parametrize("norm, phase", [("input_layernorm", "attn"),
                                         ("post_attention_layernorm", "attn"),
                                         ("pre_mlp_layernorm", "ffn"),
                                         ("post_mlp_layernorm", "ffn")])
def test_each_of_the_four_norms_is_applied_where_it_belongs(model, norm, phase):
    """A post norm's gain scales what its branch adds; a pre norm's gain reaches
    the branch's input (and a post norm then removes a uniform scale of it, so
    the gain is changed on half the channels)."""
    m, params = _phase_model(model)
    x, li = _x(5), 2
    lp = params[f"layers_{li}"]

    def branch(tree):
        if phase == "attn":
            return _attn_phase(m, tree, li, x, np.arange(6), []) - np.asarray(x)
        return np.asarray(m._ffn_phase(tree, li, x)) - np.asarray(x)

    base = branch(params)
    w = lp[norm]["weight"]
    if norm.startswith("post"):
        changed = dict(params, **{f"layers_{li}": dict(lp, **{norm: {"weight": w * 2.0}})})
        np.testing.assert_allclose(branch(changed), 2.0 * base, atol=2e-5, rtol=0)
        # re-normed: each token's branch has the rms the gain says
        np.testing.assert_allclose(np.sqrt((base * base).mean(-1)), afmoe.branch_gain(model[0]),
                                   rtol=1e-3)
    else:
        half = jnp.where(jnp.arange(w.shape[0]) % 2 == 0, 3.0, 1.0) * w
        changed = dict(params, **{f"layers_{li}": dict(lp, **{norm: {"weight": half}})})
        assert np.abs(branch(changed) - base).max() > 1e-2


def test_the_embedding_is_multiplied_by_the_root_of_the_hidden_size(model):
    m, params = _phase_model(model)
    ids = jnp.asarray([3, 200, 7])
    table = params["embed_tokens"]["embedding"]
    np.testing.assert_allclose(np.asarray(m.embed(params, ids)),
                               np.asarray(table[ids]) * math.sqrt(48), rtol=1e-6)
    plain = _model(mup_enabled=False)
    engine = _engine(plain)
    np.testing.assert_allclose(np.asarray(engine.model.embed(plain[1], ids)),
                               np.asarray(plain[1]["embed_tokens"]["embedding"][ids]), rtol=1e-6)
    # the stream starts near rms 1 either way: the initialiser knows of the scale
    assert 0.8 < float(jnp.sqrt((m.embed(params, jnp.arange(256)) ** 2).mean())) < 1.2


def test_the_initialiser_is_seeded_and_scales_what_writes_into_the_stream(model):
    cfg, params = model
    again = afmoe.init_params(cfg, jax.random.PRNGKey(3))[1]
    other = afmoe.init_params(cfg, jax.random.PRNGKey(4))[1]
    flat = jax.tree.leaves(params)
    assert all(bool((a == b).all()) for a, b in zip(flat, jax.tree.leaves(again)))
    assert not all(bool((a == b).all()) for a, b in zip(flat, jax.tree.leaves(other)))
    lp = params["layers_2"]
    gain = 1 / math.sqrt(10)
    for name in ("post_attention_layernorm", "post_mlp_layernorm"):
        np.testing.assert_allclose(np.asarray(lp[name]["weight"]), gain, rtol=1e-6)
    for name in ("input_layernorm", "pre_mlp_layernorm"):
        np.testing.assert_allclose(np.asarray(lp[name]["weight"]), 1.0)
    bank = lp["block_sparse_moe"]
    # a routed expert's wo is 1.5 / top-k of a shared expert's down projection
    ratio = float(jnp.std(bank["ExpertFFN_0"]["wo"]) /
                  jnp.std(bank["shared_experts"]["down_proj"]["kernel"]))
    assert ratio == pytest.approx(1.5 / 4, rel=0.1) and afmoe.routed_out_scale(cfg) == 0.375
    bias = np.asarray(bank["expert_bias"])
    assert bias.dtype == np.float32 and 0.005 < bias.std() < 0.05
    assert bank["gate"].dtype == jnp.float32


# (d) ------------------------------------------------- spans, counters, serving ---
@pytest.mark.parametrize("which, loop_path", [("model", "capacity"), ("wide_model", "grouped")],
                         ids=["top-4 of 16", "top-4 of 64"])
def test_put_and_decode_loop_spans_carry_the_routed_work_and_nothing_of_the_models_shape(
        request, which, loop_path):
    from deepspeed_tpu import telemetry
    model = request.getfixturevalue(which)
    session = telemetry.configure({"enabled": True, "compile_watch": False})
    try:
        engine = _engine(model)
        ids = _ids(8, 20)
        engine.put([0], [ids])
        engine.decode_loop([0], [ids[:1]], 4)
        rows = session.spans.export_since(0)["spans"]
        put = next(s for s in rows if s["name"] == "put" and s["cat"] == "inference")
        loop = next(s for s in rows if s["name"] == "decode_loop" and s["cat"] == "inference")
        # live tokens x top-k x expert layers (the dense layer routes nothing)
        assert put["args"]["moe_assignments"] == 20 * 4 * 4 and put["args"]["moe_path"] == "capacity"
        assert put["args"]["tokens"] == 20 and loop["args"]["steps"] == 4
        # a chunk: its bucket's path, and the work of its steps together
        assert loop["args"]["moe_path"] == loop_path == engine.last_moe_path
        assert loop["args"]["moe_assignments"] == 1 * 4 * 4 * 4  # rows x top-k x layers x steps
        moe = engine.model._moes[0]
        assert loop["args"]["moe_rows"] == 4 * 4 * (
            128 if loop_path == "grouped" else moe.num_experts * moe.capacity(8))
        # a constant of the configuration times the tokens is no span arg
        for span in (put, loop):
            assert not {"shared_rows", "dense_layers"} & set(span["args"])
    finally:
        telemetry.shutdown()


@pytest.mark.parametrize("which, chunk_path, other", [("model", "capacity", "grouped"),
                                                      ("wide_model", "grouped", "capacity")],
                         ids=["top-4 of 16", "top-4 of 64"])
def test_the_serving_scheduler_serves_it_past_the_window(request, which, chunk_path, other):
    """... and counts its ``decode_loop`` chunks by the path their bucket
    routes on, beside the ``put`` steps."""
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler
    model = request.getfixturevalue(which)
    engine = _engine(model)
    scheduler = ServingScheduler(engine, ServingConfig(decode_chunk=4))
    try:
        prompts = [_ids(20 + i, n) for i, n in enumerate((9, 30))]
        handles = [scheduler.submit(p, max_new_tokens=14, temperature=0.0) for p in prompts]
        outs = []
        for handle in handles:
            toks = []
            while (tok := handle.stream.get(timeout=120)) is not None:
                toks.append(tok)
            outs.append(toks)
            assert handle.state.name == "DONE"
        counters = scheduler.stats()["counters"]
    finally:
        scheduler.stop(drain=False)
    for prompt, toks in zip(prompts, outs):
        assert len(toks) == 14
        full = np.concatenate([prompt, np.asarray(toks, np.int32)])
        want = _reference_rows(model, full, range(prompt.size - 1, full.size - 1))
        assert toks == want.argmax(-1).tolist()
    assert counters["put_steps"] > 0 and counters["moe_capacity_steps"] > 0
    assert counters["moe_grouped_steps"] + counters["moe_capacity_steps"] == counters["put_steps"]
    # a step is a ``put`` step or a chunk, counted when it is fetched (two
    # sequences under a cap of eight: every plan is open, and what goes behind
    # a step in flight does so at that step's commit time)
    fetched = counters["pipelined_steps"] + sum(
        n for name, n in counters.items() if name.startswith("drained_steps_"))
    assert counters["pipelined_steps"] == counters["open_behind_steps"]
    assert 2 <= counters[f"moe_{chunk_path}_chunks"] == fetched - counters["put_steps"]
    assert counters[f"moe_{other}_chunks"] == 0


def test_what_needs_one_whole_block_table_refuses(model):
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler
    engine = _engine(model)
    with pytest.raises(ValueError, match="sliding-window model"):
        ServingScheduler(engine, ServingConfig(prefix_cache={"enabled": True}))


# (d2) ------------- what the chip's comparison cannot see of the routed experts ---
@pytest.mark.parametrize("control", ["drop_expert", "drop_1_in_8", "wrong_bank", "fp8_banks"])
def test_float32_holds_what_the_loose_tolerance_lets_through(model, control):
    """On the chip 87 % of a check's rows sit on four times the tolerance (a routing
    toss-up in some layer) and an honest flip is half a layer's routed sum, so ONE
    expert's dead bank or the banks alone in fp8 read ``correct`` there (PERF.md section
    6, PR 34: ``benchmark/tools/controls.py``). Here, in float32, nothing flips: the
    engine on weights spoilt the same way misses the reference by far more than the
    1e-4 the unspoilt engine holds."""
    from benchmark.tools import controls
    cfg, params = model
    ids = _ids(31, FEED)
    want = _reference_rows(model, ids, [FEED - 1])

    def last_row(tree):
        engine = _engine((cfg, tree))
        return np.asarray(engine.put([0], [ids]))[0]

    np.testing.assert_allclose(last_row(params), want[0], atol=1e-4, rtol=0)
    spoilt = controls.spoil(jax.tree.map(jnp.copy, params), control)  # spoil consumes its tree
    assert np.abs(last_row(spoilt) - want[0]).max() > 20 * 1e-4


# (e) ------------------------------ the other models' programs are the parent's ---
# sha256 of the traced serving programs (``program_hashes._stable``) of a tiny
# Mellum engine, taken at the commit BEFORE this family (4abd2f5, jax 0.9.0)
# under tests/conftest.py's eight virtual CPU devices: top-8 of 64, whose
# 8-token bucket takes the masks (the one-pass fill) and whose 128-token bucket
# routes by sorting. The router's score function, bias and scale, the attention
# phase's hooks and the factored SwiGLU must leave them letter for letter; the
# Mixtral and Mistral programs are ``test_one_group_programs.py``'s twelve.
# The two 128-token forwards (the grouped bucket) were re-recorded in PR 37: the
# one difference is one more output, the banks each expert layer's routing
# touched (``(group_sizes > 0).sum()`` a layer, stacked); the four programs on
# the capacity path are the parent's still. ``mellum.kernel.forward.128x8x8`` was
# re-recorded again in PR 42 (the query-tiled kernel's one-token pass) and in PR 51
# (that kernel's few-row arm and its first chunk under the insert: the kernel's body alone, as
# ``test_one_group_programs.py`` says); the gather
# arm's, the 8-token and the chunk's programs held. The two ``decode_loop``
# programs were re-recorded in PR 46 with ``test_one_group_programs.py``'s four:
# the loop lost its unused temperature and key, and nothing else. The two
# 128-token forwards were re-recorded in PR 60: the one difference is the
# banks' output, now ``[expert layers, (banks, visits)]`` (the grouped
# kernel's visits ride beside the banks); the four capacity programs hold.
_MELLUM_PARENT = {
    "mellum.gather.forward.8x8x4": "98d1d447896ec022ef33d977031c4731e75ba00e9aae31b2e52c2ddfbc751fea",
    "mellum.gather.forward.128x8x8": "81830235be7d13a1bb8145af82ca61a87d51b0fb9ac87dff936588f8c9d30cde",
    "mellum.gather.decode_loop": "9c67c90e4a53015f5d646b9a49c696c15a20da16a633a0668829b71759c08121",
    "mellum.kernel.forward.8x8x4": "2857ae3f6a0f05a300e1c4d552b4455cb6ee85431770ab01a80eaea76e50f73b",
    "mellum.kernel.forward.128x8x8": "6676dd001e82bdc96885de9b4f7f55acaa52625a0226846adeec9ed52abe7642",
    "mellum.kernel.decode_loop": "ea7d90a737b8ecd9974fea2c7be90f64289de2a83ac6fe37c2a137a1da8ff349",
}


@pytest.fixture(scope="module")
def mellum_texts():
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded jaxpr text is jax 0.9.0's")
    out = {}
    for kernel in (False, True):
        groups.initialize_mesh(force=True)
        cfg = mellum.MellumConfig.tiny(dtype=jnp.float32, hidden_size=256, head_dim=128,
                                       num_attention_heads=2, num_key_value_heads=1,
                                       num_experts=64, num_experts_per_tok=8,
                                       moe_intermediate_size=16)
        _, params = mellum.init_params(cfg, jax.random.PRNGKey(0))
        mgr = DSStateManagerConfig(
            memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=64), max_context=128,
            max_ragged_batch_size=128, max_ragged_sequence_count=8)
        engine = build_engine(params, cfg, RaggedInferenceEngineConfig(
            state_manager=mgr, kv_block_size=16, use_paged_kernel=kernel,
            expert_parallel={"capacity_factor": 8.0}))
        m = engine.model
        assert [m.moe_path(b[0]) for b in BUCKETS] == ["capacity", "grouped"]
        cache = m.state_manager.kv_cache.cache
        arm = "kernel" if kernel else "gather"
        for bucket in BUCKETS:
            jaxpr = jax.make_jaxpr(m._forward_impl)(m._params, cache, m._synthetic_batch(bucket))
            out[f"mellum.{arm}.forward.{'x'.join(map(str, bucket))}"] = _stable(jaxpr)
        jaxpr = jax.make_jaxpr(lambda p, c, d: m._decode_loop_impl(p, c, d, n_steps=4))(
            m._params, cache, m._synthetic_batch(BUCKETS[0]))
        out[f"mellum.{arm}.decode_loop"] = _stable(jaxpr)
        engine.close()
    return out


@pytest.mark.parametrize("name", list(_MELLUM_PARENT))
def test_mellum_traces_the_program_it_traced_before_this_family(mellum_texts, name):
    assert hashlib.sha256(mellum_texts[name].encode()).hexdigest() == _MELLUM_PARENT[name]
