"""DeepSeek-V3.2 served through ``build_engine`` (PR 40): prefill in chunks,
``put``, mixed steps and ``decode_loop`` through the latent cache against the
plain float32 reference's full forward, on both sides of ``index_topk``;
absorbed = expanded; the selection; the group limit; and the shares of a layer
adding up to the uncut layer."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import deepseek_v32 as reference
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig, MemoryConfig)
from deepspeed_tpu.models import deepseek_v32 as ds
from deepspeed_tpu.ops.pallas import latent_attention
from deepspeed_tpu.utils import groups

BLOCK = 16
TOL = 1e-4


def sizes_of(cfg):
    """The configuration-file view of a program config, as the reference reads it."""
    sizes = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    sizes["rope_scaling"] = dict(cfg.rope_scaling) if cfg.rope_scaling else None
    sizes["n_routed_experts"] = cfg.experts_held
    sizes["deployment_share"] = {"routed_over": cfg.n_routed_experts,
                                 "expert_rank": cfg.expert_rank}
    return sizes


def engine_of(cfg, params, kernel=False, blocks=96, max_context=256, **overrides):
    """``max_context`` 256 = 16 blocks is four times the 4 that hold ``index_topk``
    = 32 keys: ONE block-table bucket, one program a token bucket; 512 keeps the
    buckets 4, 8, ... (``DeepseekV32V2Model.min_table_bucket``)."""
    groups.initialize_mesh(force=True)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                          size=blocks),
                               max_context=max_context, max_ragged_batch_size=64,
                               max_ragged_sequence_count=8)
    return build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=BLOCK, use_paged_kernel=kernel,
        expert_parallel={"capacity_factor": 4.0}, **overrides))


@pytest.fixture(scope="module")
def model():
    cfg = ds.DeepseekV32Config.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1)
    return cfg, ds.init_params(cfg, rng=jax.random.PRNGKey(3))[1]


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


@pytest.mark.parametrize("kernel, max_context, floor", [(False, 512, 4), (True, 512, 4),
                                                        (False, 256, 16)],
                         ids=["xla", "pallas-interpret", "xla-one-table-bucket"])
def test_prefill_in_chunks_then_decode_is_the_references_full_forward(model, kernel, max_context,
                                                                      floor):
    """Two sequences, one ending under ``index_topk`` = 32 keys and one far past
    it, prefilled TOGETHER in chunks (a mixed step: the short one decodes while
    the long one still prefills), then fed token by token through ``put`` and
    last through ``decode_loop``: every returned row is the reference's. Where
    the whole table is at most four times what holds ``index_topk`` keys every
    step runs the ONE bucket's programs: a context under ``index_topk`` is
    scored there and keeps every key."""
    cfg, params = model
    engine = engine_of(cfg, params, kernel, max_context=max_context)
    assert engine.model.min_table_bucket == floor and engine.n_kv_cache_groups == 1
    prompts, feeds = [_ids(1, 75), _ids(2, 20)], [_ids(3, 6), _ids(4, 6)]
    sizes = sizes_of(cfg)
    want = [np.asarray(reference.forward_logits(
        params, sizes, np.concatenate([p, f]), rows=np.arange(p.size - 1, p.size + f.size)))
        for p, f in zip(prompts, feeds)]
    got = [[], []]
    fed, steps = [0, 0], [0, 0]
    while fed[0] < prompts[0].size:
        uids, toks = [], []
        for u in (0, 1):
            if fed[u] < prompts[u].size:
                uids.append(u), toks.append(prompts[u][fed[u]:fed[u] + 24])
            elif steps[u] < feeds[u].size - 1:  # a decode row riding with the other's chunk
                uids.append(u), toks.append(feeds[u][steps[u]:steps[u] + 1])
        logits = np.asarray(engine.put(uids, toks))
        for u, t, row in zip(uids, toks, logits):
            if fed[u] < prompts[u].size:
                fed[u] += t.size
                if fed[u] == prompts[u].size:
                    got[u].append(row)
            else:
                steps[u] += 1
                got[u].append(row)
    while min(steps) < feeds[0].size - 1:
        uids = [u for u in (0, 1) if steps[u] < feeds[u].size - 1]
        logits = np.asarray(engine.put(uids, [feeds[u][steps[u]:steps[u] + 1] for u in uids]))
        for u, row in zip(uids, logits):
            steps[u] += 1
            got[u].append(row)
    for u in (0, 1):
        rows = np.stack(got[u])
        assert np.abs(rows - want[u][:rows.shape[0]]).max() < TOL
    looped = np.asarray(engine.decode_loop([0, 1], [f[-1:] for f in feeds], 4))
    assert [int(looped[u][0]) for u in (0, 1)] == [int(want[u][-1].argmax()) for u in (0, 1)]
    assert {key[2] for key in engine.lowerable_callables()["forward"]} <= \
        ({4, 8, 16} if floor == 4 else {16})


def test_absorbed_is_expanded():
    """One query against a latent pool: the absorbed form (q_nope W_UK against
    c_kv, the output through W_UV) gives the expanded attention's numbers."""
    rng = np.random.default_rng(0)
    H, N, R, V, C, K = 4, 16, 8, 16, 32, 40
    q_nope, q_pe = rng.normal(size=(H, N)), rng.normal(size=(H, R))
    c_kv, k_pe = rng.normal(size=(K, C)), rng.normal(size=(K, R))
    w_uk, w_uv = rng.normal(size=(C, H, N)), rng.normal(size=(C, H, V))
    k_nope = np.einsum("kc,chn->khn", c_kv, w_uk)
    v = np.einsum("kc,chv->khv", c_kv, w_uv)
    logits = np.einsum("hn,khn->hk", q_nope, k_nope) + q_pe @ k_pe.T
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    expanded = np.einsum("hk,khv->hv", probs, v)

    W = latent_attention.padded_width(C + R)
    pool = np.zeros((1, 3, BLOCK, W), np.float32)
    pool[0].reshape(-1, W)[:K, :C], pool[0].reshape(-1, W)[:K, C:C + R] = c_kv, k_pe
    q_abs = np.einsum("hn,chn->hc", q_nope, w_uk)
    q_row = np.zeros((1, H, W), np.float32)
    q_row[0, :, :C], q_row[0, :, C:C + R] = q_abs, q_pe
    table = np.array([[0, 1, 2, -1]], np.int32)
    out = latent_attention.latent_paged_attention_xla(
        jnp.asarray(q_row), jnp.asarray(pool), 0, table, np.zeros(1, np.int32),
        np.array([K - 1], np.int32), np.ones(1, bool), value_width=C)
    absorbed = np.einsum("hc,chv->hv", np.asarray(out)[0], w_uv)
    assert np.abs(absorbed - expanded).max() < 1e-4 * np.abs(expanded).max()


def test_the_selection_is_the_references_and_the_most_recent_keys_are_not(model):
    """The threshold keeps exactly the reference's ``index_topk`` keys for every
    query whose gap is outside rounding; a control that keeps the most recent
    keys instead reads wrong logits."""
    cfg, params = model
    engine = engine_of(cfg, params)
    ids = _ids(7, 90)
    sizes = sizes_of(cfg)
    picks = []
    want = np.asarray(reference.forward_logits(params, sizes, ids, selection_gaps=picks))
    got = np.concatenate([np.asarray(engine.put([0], [ids[i:i + 1]])) for i in range(90)])
    clear = np.asarray(picks[0]) > 1e-4
    assert clear[cfg.index_topk:].sum() > 40  # most rows past index_topk have a clear gap
    assert np.abs(got - want)[clear].max() < TOL

    scores = jnp.asarray(np.random.default_rng(0).normal(size=(6, 64)), jnp.float32)
    threshold = np.asarray(latent_attention.kth_largest(scores, 10))
    assert (threshold == np.sort(np.asarray(scores), axis=1)[:, -10]).all()
    assert ((np.asarray(scores) >= threshold[:, None]).sum(1) == 10).all()

    # the control: the most recent index_topk keys in place of the top ones
    from benchmark.tools import controls_latent
    _, _, patch = controls_latent.spoilt("recent", cfg, params, 256)
    with patch:
        control = engine_of(cfg, params)
        spoilt = np.concatenate([np.asarray(control.put([0], [ids[i:i + 1]]))
                                 for i in range(90)])
    assert np.abs(spoilt - want)[cfg.index_topk + 8:].max() > 100 * TOL
    # ... and nothing stays patched
    assert np.abs(np.asarray(engine.put([1], [ids[:60]])) - want[59]).max() < TOL


def test_the_group_limit_on_a_hand_written_case():
    """8 experts in 4 groups of 2, 2 groups kept, top-2: the two best groups by
    the SUM of their two scores + bias win, the pick is inside them, and the
    weights are the chosen SCORES renormalised and scaled."""
    moe = RaggedMoE(num_experts=8, top_k=2, score_func="sigmoid", route_scale=2.5, n_group=4,
                    topk_group=2)
    #             g0          g1          g2          g3
    scores = jnp.asarray([[0.9, 0.1, 0.5, 0.55, 0.6, 0.58, 0.2, 0.3]], jnp.float32)
    # group sums 1.0, 1.05, 1.18, 0.5: g2 and g1 kept; expert 0 (the largest score) is out
    w, e = moe._choose(scores)
    assert sorted(np.asarray(e)[0].tolist()) == [4, 5]
    assert np.allclose(np.asarray(w).sum(), 2.5)
    # a bias picks and does not weigh: +0.2 on expert 2 brings g1 first and expert 2 in
    bias = jnp.zeros(8).at[2].set(0.2)
    w, e = moe._choose(scores, bias)
    assert sorted(np.asarray(e)[0].tolist()) == [2, 4]
    picked = np.asarray(scores)[0, np.asarray(e)[0]]
    assert np.allclose(np.asarray(w)[0], picked / picked.sum() * 2.5)
    # without the limit expert 0 is chosen
    assert 0 in np.asarray(RaggedMoE(num_experts=8, top_k=2, score_func="sigmoid")
                           ._choose(scores)[1])[0]
    with pytest.raises(ValueError, match="groups"):
        RaggedMoE(num_experts=8, top_k=2, n_group=3)
    with pytest.raises(ValueError, match="groups"):
        RaggedMoE(num_experts=8, top_k=4, n_group=4, topk_group=1)


def _moe_inputs(seed=0, T=24, M=32, E=16, F=16):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(T, M)), jnp.float32),
            jnp.asarray(rng.normal(size=(M, E)), jnp.float32),
            jnp.asarray(rng.normal(size=(E, M, 2 * F)) / np.sqrt(M), jnp.float32),
            jnp.asarray(rng.normal(size=(E, F, M)) / np.sqrt(F), jnp.float32),
            jnp.asarray(0.1 * rng.normal(size=(E, )), jnp.float32))


def _routed_reference(h, gate, wi, wo, bias, first=0, held=None, **router):
    """The routed sum by the plain reference's routing, over experts first..first+held."""
    held = wi.shape[0] if held is None else held
    weights, _ = reference.routing(h, gate, bias, top_k=4, n_group=4, topk_group=2, scale=2.5,
                                   first_held=first, held=held)
    out = 0.0
    for j in range(held):
        g, u = jnp.split(h @ wi[first + j], 2, axis=-1)
        out = out + (jax.nn.silu(g) * u) @ wo[first + j] * weights[:, j][:, None]
    return np.asarray(out)


@pytest.mark.parametrize("path", ["capacity", "grouped"])
def test_capacity_is_grouped_is_the_reference_under_the_group_limit(path, monkeypatch):
    from deepspeed_tpu.inference.v2.modules import heuristics
    monkeypatch.setattr(heuristics, "moe_implementation", lambda *a, **k: path)
    groups.initialize_mesh(force=True)
    h, gate, wi, wo, bias = _moe_inputs()
    moe = RaggedMoE(num_experts=16, top_k=4, capacity_factor=4.0, score_func="sigmoid",
                    route_scale=2.5, n_group=4, topk_group=2)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(moe(h, gate, wi, wo, select_bias=bias))
        want = _routed_reference(h, gate, wi, wo, bias)
    assert np.abs(got - want).max() < TOL * np.abs(want).max()


@pytest.mark.parametrize("path", ["capacity", "grouped"])
def test_the_shares_add_up_to_the_uncut_layer(path, monkeypatch):
    """Four chips share a layer of 16 experts: each holds 4 and computes its own
    part; the four parts sum to the layer that holds every expert (the shared
    expert is every chip's and is counted once, outside). A share counts the
    banks and the assignments that landed on it."""
    from deepspeed_tpu.inference.v2.modules import heuristics
    monkeypatch.setattr(heuristics, "moe_implementation", lambda *a, **k: path)
    groups.initialize_mesh(force=True)
    h, gate, wi, wo, bias = _moe_inputs(1)
    router = dict(num_experts=16, top_k=4, capacity_factor=4.0, score_func="sigmoid",
                  route_scale=2.5, n_group=4, topk_group=2)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(RaggedMoE(**router)(h, gate, wi, wo, select_bias=bias))
        total, landed = 0.0, 0
        for rank in range(4):
            share = RaggedMoE(held=4, first_held=4 * rank, **router)
            counts = []
            part = np.asarray(share(h, gate, wi[4 * rank:4 * rank + 4], wo[4 * rank:4 * rank + 4],
                                    select_bias=bias, banks_out=counts))
            want = _routed_reference(h, gate, wi, wo, bias, first=4 * rank, held=4)
            assert np.abs(part - want).max() < TOL * np.abs(whole).max()
            total = total + part
            if path == "grouped":
                banks, assignments, visits, walked = np.asarray(counts[0])
                assert 0 <= banks <= 4 and visits == banks  # one row tile: a visit a bank
                assert walked == 128  # ... and no window on it
                landed += int(assignments)
    assert np.abs(total - whole).max() < TOL * np.abs(whole).max()
    if path == "grouped":
        assert landed == h.shape[0] * 4  # every assignment landed on exactly one share


def test_a_share_routes_by_sorting_whatever_the_bucket_and_refuses_a_mesh():
    from deepspeed_tpu.inference.v2.modules.heuristics import moe_implementation
    for tokens in (8, 32, 64, 128, 256):
        assert moe_implementation(tokens, 256, 8, tokens, 2048, held=16) == "grouped"
    # a layer that holds every expert answers as it did
    assert moe_implementation(128, 256, 8, 128, 2048) == \
        moe_implementation(128, 256, 8, 128, 2048, held=256)
    assert moe_implementation(8, 256, 8, 8, 2048, expert_parallel=2, held=16) == "capacity"
    with pytest.raises(ValueError, match="experts"):
        RaggedMoE(num_experts=16, top_k=2, held=4, first_held=14)


def test_the_config_refuses_what_is_not_implemented_and_keeps_the_published_defaults():
    cfg = ds.DeepseekV32Config(rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                                             "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                                             "original_max_position_embeddings": 4096})
    assert (cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.num_attention_heads,
            cfg.index_n_heads, cfg.index_topk, cfg.n_routed_experts, cfg.experts_held,
            cfg.n_group, cfg.topk_group) == (7168, 1536, 512, 128, 64, 2048, 256, 256, 8, 4)
    assert cfg.latent_width == 576 and cfg.qk_head_dim == 192
    assert cfg.softmax_scale == pytest.approx(192**-0.5 * (0.1 * np.log(40) + 1)**2)
    assert cfg.rope()["attention_factor"] == 1.0  # cos / sin unscaled
    for changed, error in ((dict(scoring_func="softmax"), NotImplementedError),
                           (dict(hidden_act="gelu"), NotImplementedError),
                           (dict(tie_word_embeddings=True), NotImplementedError),
                           (dict(rope_scaling={"type": "linear", "factor": 2}),
                            NotImplementedError),
                           (dict(first_k_dense_replace=61), ValueError),
                           (dict(experts_held=24), ValueError),
                           (dict(experts_held=16, expert_rank=16), ValueError),
                           (dict(topk_group=9), ValueError)):
        with pytest.raises(error):
            ds.DeepseekV32Config(**changed)


def test_the_spans_name_the_arm_and_count_the_index_and_the_local_assignments(model):
    cfg, params = model
    engine = engine_of(cfg, params)
    m = engine.model
    assert m.attention_arm(8) == m.attention_arm(64) == "latent_xla"
    assert engine_of(cfg, params, True).model.attention_arm(8) == "latent_token"
    assert engine_of(cfg, params, True).model.attention_arm(64) == "latent_tiled"
    assert m.moe_count_names == ("moe_banks", "moe_assignments_local", "moe_visits",
                                 "moe_rows_walked")
    assert m.moe_path(8) == m.moe_path(64) == "grouped"
    # a decode row at position 49 in a bucket of 4 x 16 = 64 > 32 keys: 50 scored, 32 read
    batch = {"tok_meta": np.array([[1] * 8, [0] * 8, [49] + [0] * 7, [1] + [0] * 7]),
             "seq_meta": np.zeros((8, 4 + 4), np.int32)}
    assert m.batch_counts(batch) == {"index_keys": 50 * 3, "index_selected": 32 * 3}
    assert m.batch_counts(batch, steps=2) == {"index_keys": 101 * 3, "index_selected": 64 * 3}
    # a table of 2 x 16 = 32 keys selects everything: nothing scored
    short = dict(batch, seq_meta=np.zeros((8, 4 + 2), np.int32))
    assert m.batch_counts(short) == {"index_keys": 0, "index_selected": 0}
    assert engine.moe_counts(np.array([[3, 5, 4, 128], [2, 4, 2, 256]])) == \
        {"moe_banks": 5, "moe_assignments_local": 9, "moe_visits": 6, "moe_rows_walked": 384}
    # on the tiled grid the span counts the kernel's passes too: a 20-token chunk beside two
    # decode rows in one tile of 64 tokens (4 heads), over the 3 layers; none on the token grid
    tiled = {"tok_meta": np.zeros((4, 64), np.int32), "seq_meta": np.zeros((8, 4 + 2), np.int32)}
    tiled["seq_meta"][:3, 1:3] = [(1, 0), (1, 1), (20, 21)]
    served = engine_of(cfg, params, True).model
    assert served.batch_counts(tiled) == {"index_keys": 0, "index_selected": 0,
                                          "latent_passes": 3 * 3, "latent_rider_passes": 2 * 3}
    assert "latent_passes" not in served.batch_counts(short) | m.batch_counts(tiled)


def test_a_model_with_a_group_limit_that_holds_every_expert_is_served_as_afmoe():
    """``models/afmoe.py`` no longer refuses ``n_group`` / ``topk_group``: the
    router is told, and the published values (1) build the program they built."""
    from deepspeed_tpu.models import afmoe
    cfg = afmoe.AfmoeConfig.tiny(num_experts=8, num_experts_per_tok=2, n_group=4, topk_group=2)
    from deepspeed_tpu.inference.v2.model_implementations.afmoe_v2 import AfmoeV2Model
    groups.initialize_mesh(force=True)
    _, params = afmoe.init_params(cfg)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=64),
                               max_context=64, max_ragged_batch_size=32,
                               max_ragged_sequence_count=8)
    engine = build_engine(params, cfg, RaggedInferenceEngineConfig(state_manager=mgr,
                                                                   kv_block_size=4))
    assert isinstance(engine.model, AfmoeV2Model)
    assert {(r.n_group, r.topk_group) for r in engine.model._moes} == {(4, 2)}
    assert np.isfinite(np.asarray(engine.put([0], [_ids(0, 9)]))).all()


def test_the_selections_swing_bounds_what_a_swap_at_the_threshold_moves(model):
    """The reference's ``selection_swing`` (what moving ONE key of the band round
    a query's threshold in or out of the selection adds to the stream, over the
    stream) against swaps made on purpose: index weights perturbed by 2^-7,
    inside the band, change NOTHING but which keys near the threshold are
    selected, so a row's output moves only by its swapped keys, each by no more
    than the row's swing. Rows that select everything have no swing, and a row
    whose swing is over ``SELECTION_SWING`` is returned as a toss-up."""
    cfg, params = model
    sizes = sizes_of(cfg)
    layer = params["layers_0"]
    S = 96
    x = jnp.asarray(np.random.default_rng(5).normal(size=(S, cfg.hidden_size)), jnp.float32)
    inv_freq = reference.yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, sizes["rope_scaling"])
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None]
    kw = dict(shape=reference._shape(sizes), scale=reference.softmax_scale(sizes),
              eps=cfg.rms_norm_eps)
    out, _, swing = (np.asarray(a) for a in reference.attention_part(x, layer, angles, **kw))
    assert (swing[:cfg.index_topk] == 0).all() and (swing[cfg.index_topk:] > 0).sum() > 20
    proj = layer["self_attn"]["indexer"]["weights_proj"]["kernel"]
    noise = 1 + 2.0**-7 * np.random.default_rng(6).normal(size=proj.shape)
    indexer = dict(layer["self_attn"]["indexer"], weights_proj={"kernel": proj * noise})
    nudged = dict(layer, self_attn=dict(layer["self_attn"], indexer=indexer))
    other = np.asarray(reference.attention_part(x, nudged, angles, **kw)[0])
    moved = np.linalg.norm(other - out, axis=-1) / np.linalg.norm(out, axis=-1)
    assert (moved[:cfg.index_topk] == 0).all()
    assert (moved > 0).sum() >= 3  # the nudge does swap keys
    assert (moved <= 3 * swing + 1e-6).all()

    gaps, swings = [], []
    reference.forward_logits(params, sizes, _ids(7, 90), routing_gaps=gaps, selection_swings=swings)
    tossed = np.asarray(swings[0]) > reference.SELECTION_SWING
    assert tossed.any() and (np.asarray(gaps[0])[tossed] == 0).all()
    assert (np.asarray(gaps[0])[~tossed] > 0).all()


def test_the_family_is_imported_when_a_config_first_names_it():
    """The registry lists ``deepseek_v32`` without importing it: a program that
    serves another family pays nothing for this one."""
    import subprocess
    import sys
    code = ("import sys\n"
            "from deepspeed_tpu.inference.v2.model_implementations import registry\n"
            "assert 'deepseek_v32' in registry.supported_model_types()\n"
            "assert 'deepspeed_tpu.models.deepseek_v32' not in sys.modules\n"
            "assert 'deepspeed_tpu.ops.pallas.latent_attention' not in sys.modules\n"
            "from deepspeed_tpu.models.deepseek_v32 import DeepseekV32Config\n"
            "print(registry.model_cls_for(DeepseekV32Config.tiny()).__name__)\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip().endswith("DeepseekV32V2Model")
