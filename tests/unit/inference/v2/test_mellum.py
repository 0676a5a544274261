"""Mellum-2 served (PR 30): top-k-of-many dropless routing, window and full
layers side by side in one KV pool (layer groups), and a rotary table a layer
type (YaRN on the full layers), at tiny sizes on the CPU.

The system is held to ``benchmark/references/mellum.py`` (float32, no cache, no
kernel, one sequence) on LOGITS through chunked prefill across the window ->
release in the window groups -> decode. Two periods of the 3-window-1-full
pattern: 8 layers, 16 experts top-4, window 16 over 4- or 8-token blocks,
contexts to 96; YaRN's original context is cut to 32 so that its ramp and its
attention factor matter inside them."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import mellum as reference
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.model_implementations.mellum_v2 import MellumV2Model
from deepspeed_tpu.inference.v2.model_implementations.registry import supported_model_types
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode, DSStateManagerConfig,
                                                               MemoryConfig)
from deepspeed_tpu.models import mellum
from deepspeed_tpu.utils import groups

WINDOW, BLOCK, FEED = 16, 4, 32
ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                           "original_max_position_embeddings": 32, "beta_fast": 4.0,
                           "beta_slow": 1.0, "attention_factor": 0.1 * math.log(4.0) + 1.0},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0}}
SIZES = dict(vocab_size=256, hidden_size=48, head_dim=16, num_hidden_layers=8,
             num_attention_heads=4, num_key_value_heads=2, num_experts=16, num_experts_per_tok=4,
             moe_intermediate_size=32, norm_topk_prob=True, sliding_window=WINDOW,
             rope_parameters=ROPE, rms_norm_eps=1e-6)

# Everything is float32 here (weights, pool, reference): what is left is the
# order of float32 sums, ~2e-6 of logits of scale ~3 (read: 2.0e-6 worst over
# these feeds). 1e-4 absolute is 50 x that; a wrong window, table, block or
# rotary table, or one dropped expert assignment, moves a logit by 1e-2 or more
# (``test_the_tolerance_catches_*`` show three of them).
ATOL = 1e-4


def _sizes(cfg):
    """The configuration as a benchmark file states it (the reference's view)."""
    return dict(SIZES, layer_types=list(cfg.layer_types), mlp_layer_types=list(cfg.mlp_layer_types))


@pytest.fixture(scope="module")
def model():
    cfg = mellum.MellumConfig(dtype=jnp.float32, **SIZES)
    assert cfg.layer_types == ("sliding_attention", ) * 3 + ("full_attention", ) + \
        ("sliding_attention", ) * 3 + ("full_attention", )
    _, params = mellum.init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


MANY_EXPERTS = dict(num_hidden_layers=4, num_experts=64, num_experts_per_tok=8,
                    moe_intermediate_size=16)


@pytest.fixture(scope="module")
def many_experts():
    """One period (3 window layers + 1 full) with Mellum's routing, 8 of 64,
    and experts 16 wide: its 128-token bucket crosses ``heuristics.
    moe_implementation``'s rule (the masks would be 128 x 64 x 128 = 2^20
    elements) and routes by sorting; every smaller bucket takes the masks."""
    cfg = mellum.MellumConfig(dtype=jnp.float32, **dict(SIZES, **MANY_EXPERTS))
    _, params = mellum.init_params(cfg, jax.random.PRNGKey(5))
    return cfg, params


def _engine(model, kernel=False, blocks=160, budget=FEED, block=BLOCK, capacity_factor=4.0,
            max_context=128):
    groups.initialize_mesh(force=True)
    cfg, params = model
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=blocks),
                               max_context=max_context, max_ragged_batch_size=budget,
                               max_ragged_sequence_count=8)
    engine = build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=block, use_paged_kernel=kernel,
        expert_parallel={"capacity_factor": capacity_factor}))
    assert isinstance(engine.model, MellumV2Model)
    return engine


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n).astype(np.int32)


def _reference_rows(model, ids, rows, **changed):
    return np.asarray(reference.forward_logits(model[1], dict(_sizes(model[0]), **changed), ids,
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
    assert "mellum" in supported_model_types()
    engine = _engine(model)
    m = engine.model
    assert m.head_dim == 16 and m.head_dim != SIZES["hidden_size"] // SIZES["num_attention_heads"]
    assert [m.attention_window_of(li) for li in range(8)] == [16, 16, 16, 0] * 2
    assert m.group_windows == (16, 16, 16, 0) and engine.n_kv_cache_groups == 4
    with pytest.raises(ValueError, match="no one attention window"):
        m.attention_window
    # 8 layers in 4 groups: a block id holds 2 layers of one group
    assert engine._state_manager.kv_cache.cache.shape == (2, 2, 160, 2, BLOCK, 16)
    assert m.kv_cache_config().num_allocation_groups == 4
    # each layer type rotates by its own rule; YaRN's carries the attention factor
    ones = jnp.ones((1, 1, 16), jnp.float32)
    assert float(m._rotate(0, ones, jnp.zeros((1, ), jnp.int32))[0, 0, 0]) == 1.0
    assert float(m._rotate(3, ones, jnp.zeros((1, ), jnp.int32))[0, 0, 0]) == \
        pytest.approx(ROPE["full_attention"]["attention_factor"])


@pytest.mark.parametrize("changed, error, match", [
    (dict(mlp_layer_types=("dense", ) + ("sparse", ) * 7), NotImplementedError, "dense"),
    (dict(rope_parameters=dict(ROPE, full_attention={"rope_type": "llama3", "rope_theta": 1e4})),
     NotImplementedError, "llama3"),
    (dict(layer_types=("chunked_attention", ) * 8), ValueError, "chunked_attention"),
    (dict(num_experts_per_tok=17), ValueError, "num_experts_per_tok"),
    (dict(layer_types=("full_attention", ) * 3), ValueError, "must name 8 layers"),
])
def test_the_config_refuses_what_is_not_implemented(changed, error, match):
    with pytest.raises(error, match=match):
        mellum.MellumConfig(**dict(SIZES, **changed))


def test_the_published_config_is_the_default():
    cfg = mellum.MellumConfig()
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.vocab_size, cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.sliding_window, cfg.num_hidden_layers) == \
        (2304, 128, 32, 4, 98304, 64, 8, 896, 1024, 28)
    assert cfg.layer_types.count("full_attention") == 7 and cfg.layer_types[3] == "full_attention"
    assert [cfg.window_of(li) for li in range(4)] == [1024, 1024, 1024, 0]
    hash(cfg)  # a static argument of the jitted initialisers


# (d) ------------------------------------------------------- the YaRN table ---
@pytest.mark.parametrize("position", [0, 8191, 8192, 16383])
def test_the_yarn_table_is_the_closed_form(position):
    """Mellum-2's published numbers: theta 5e5, factor 16, original context
    8192, beta_fast 32, beta_slow 1, head_dim 128: the ramp runs over dimension
    pairs 18..35, pairs below keep their frequency, pairs above are divided by
    16, and cos and sin carry 0.1 ln 16 + 1."""
    rope = mellum.MellumConfig().rope_of("full_attention")
    theta, d = 500000.0, 128
    low = math.floor(d * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(theta)))
    high = math.ceil(d * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(theta)))
    assert (low, high) == (18, 35)
    m = np.arange(64, dtype=np.float64)
    plain = theta**(-2 * m / d)
    ramp = np.clip((m - low) / (high - low), 0, 1)
    inv_freq = plain / 16 * ramp + plain * (1 - ramp)
    assert np.array_equal(inv_freq[:19], plain[:19]) and np.allclose(inv_freq[35:], plain[35:] / 16)
    factor = 1.2772588722239782
    assert factor == pytest.approx(0.1 * math.log(16) + 1)

    got_freq, got_factor = mellum.rope_inv_freq(rope, d)
    np.testing.assert_allclose(got_freq, inv_freq, rtol=1e-12)
    assert got_factor == factor
    cos, sin = mellum.rotary_cos_sin(rope, jnp.arange(16384), d)
    assert cos.shape == (16384, 64)
    # the table's angles are float32 products: an angle of 1.6e4 rad carries
    # 2^-24 x 1.6e4 = 1e-3 rad of rounding, so 3e-3 absolute on cos and sin
    np.testing.assert_allclose(np.asarray(cos[position]), np.cos(position * inv_freq) * factor,
                               atol=3e-3, rtol=0)
    np.testing.assert_allclose(np.asarray(sin[position]), np.sin(position * inv_freq) * factor,
                               atol=3e-3, rtol=0)
    # the window layers' table is the plain one
    cos_w, _ = mellum.rotary_cos_sin(mellum.MellumConfig().rope_of("sliding_attention"),
                                     jnp.arange(16384), d)
    np.testing.assert_allclose(np.asarray(cos_w[position]), np.cos(position * plain), atol=3e-3,
                               rtol=0)
    # and the reference computes the same frequencies on its own
    ref_freq, ref_factor = reference.rotary_frequencies(rope, d)
    np.testing.assert_allclose(ref_freq, inv_freq, rtol=1e-12)
    assert ref_factor == factor


# (a) ------------------------------------------- system against the reference ---
# 40 and 23 tokens are buckets of 64 (the tile grid; the first straddles the
# window's edge), 7 a bucket of 8 (the token grid), then single tokens
CHUNKS = [32, 32, 25, 7, 1, 1, 1]


@pytest.mark.parametrize("block", [4, 8])
def test_prefill_in_chunks_release_then_decode_matches_the_float32_reference(model, block):
    engine = _engine(model, block=block)
    ids = _ids(1, sum(CHUNKS))
    got = _feed(engine, 0, ids, CHUNKS)
    rows = np.cumsum(CHUNKS) - 1
    want = _reference_rows(model, ids, rows)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=ATOL, rtol=0)
    seq = engine._state_manager.get_sequence(0)
    assert seq.released_in(0) > 0 and seq.released_in(3) == 0 and engine.released_blocks > 0


def test_the_kernel_arm_matches_the_reference_at_head_dim_128():
    """The Pallas kernel (interpret mode) takes each layer's own window, table
    and cache layer: one period, 128-wide heads (the kernel's lane width)."""
    sizes = dict(SIZES, hidden_size=64, head_dim=128, num_attention_heads=2,
                 num_key_value_heads=1, num_hidden_layers=4, num_experts=4, num_experts_per_tok=2)
    cfg = mellum.MellumConfig(dtype=jnp.float32, **sizes)
    model = (cfg, mellum.init_params(cfg, jax.random.PRNGKey(5))[1])
    ids = _ids(2, 72)
    chunks = [32, 32, 7, 1]
    want = np.asarray(reference.forward_logits(
        model[1], dict(sizes, layer_types=list(cfg.layer_types),
                       mlp_layer_types=list(cfg.mlp_layer_types)), ids,
        rows=np.cumsum(chunks) - 1))
    engine = _engine(model, kernel=True, block=8, capacity_factor=2.0)
    assert engine.model.attention_arm(64) == "paged_tiled"
    for g, w in zip(_feed(engine, 0, ids, chunks), want):
        np.testing.assert_allclose(g, w, atol=ATOL, rtol=0)
    assert engine._state_manager.get_sequence(0).released_in(0) > 0


def test_decode_loop_continues_past_the_window(model):
    engine = _engine(model)
    ids = _ids(3, 70)
    _feed(engine, 0, ids[:64], [32, 32])
    tokens = engine.decode_loop([0], [ids[64:65]], 4)[0]
    # greedy: each generated token is the reference's argmax given the ones before
    full = np.concatenate([ids[:65], tokens[:3]])
    want = _reference_rows(model, full, [64, 65, 66, 67])
    assert tokens.tolist() == want.argmax(-1).tolist()


@pytest.mark.parametrize("what, changed", [
    ("a window on the full layers", dict(layer_types=["sliding_attention"] * 8)),
    ("no window at all", dict(sliding_window=10**6)),
    ("the plain rotary table everywhere",
     dict(rope_parameters=dict(ROPE, full_attention=ROPE["sliding_attention"]))),
    ("top-3 routing", dict(num_experts_per_tok=3)),
])
def test_the_tolerance_catches_another_models_answer(model, what, changed):
    engine = _engine(model)
    ids = _ids(4, 96)
    got = _feed(engine, 0, ids, [32, 32, 32])[-1]
    np.testing.assert_allclose(got, _reference_rows(model, ids, [95])[0], atol=ATOL, rtol=0)
    other = _reference_rows(model, ids, [95], **changed)[0]
    assert np.abs(other - got).max() > 100 * ATOL, what


# (b) ------------------------------------------------------------ the routing ---
def _dense_moe(h, gate_w, wi, wo, top_k, norm):
    """Every token through its top-k experts, one assignment at a time."""
    probs = jax.nn.softmax(np.asarray(h, np.float64) @ np.asarray(gate_w, np.float64), axis=-1)
    probs = np.asarray(probs)
    out = np.zeros_like(np.asarray(h, np.float64))
    for t in range(h.shape[0]):
        chosen = np.argsort(-probs[t])[:top_k]
        weights = probs[t, chosen] / (probs[t, chosen].sum() if norm else 1.0)
        for e, w in zip(chosen, weights):
            gate, up = np.split(np.asarray(h[t], np.float64) @ np.asarray(wi[e], np.float64), 2)
            out[t] += w * ((gate / (1 + np.exp(-gate)) * up) @ np.asarray(wo[e], np.float64))
    return out


@pytest.mark.parametrize("norm", [True, False], ids=["renormalised", "raw"])
@pytest.mark.parametrize("top_k", [1, 2, 4, 8])
def test_ragged_moe_top_k_against_the_dense_reference(top_k, norm):
    groups.initialize_mesh(force=True)
    E, T, M, F = 16, 40, 32, 24
    rng = np.random.default_rng(top_k)
    h = jnp.asarray(rng.normal(size=(T, M)), jnp.float32).at[:, 0].set(1.0)
    gate_w = jnp.asarray(rng.normal(size=(M, E)), jnp.float32)
    # every token prefers expert 0: a capacity below T would drop assignments
    gate_w = gate_w.at[0, 0].set(30.0)
    wi = jnp.asarray(rng.normal(size=(E, M, 2 * F)) / np.sqrt(M), jnp.float32)
    wo = jnp.asarray(rng.normal(size=(E, F, M)) / np.sqrt(F), jnp.float32)
    valid = jnp.arange(T) < 33
    moe = RaggedMoE(num_experts=E, top_k=top_k, capacity_factor=E / top_k, norm_topk_prob=norm)
    # dropless: an expert has a slot for every token of the bucket
    assert moe.capacity(T) == T and moe.expert_rows(T) == E * T
    with jax.default_matmul_precision("highest"):
        got = np.asarray(moe(h, gate_w, wi, wo, token_valid=valid))
    want = _dense_moe(h, gate_w, wi, wo, top_k, norm)
    np.testing.assert_allclose(got[:33], want[:33], atol=2e-5, rtol=0)
    assert not got[33:].any()
    # no assignment dropped: the dispatch mask holds top_k slots for every live token
    probs = moe._router_probs(h, gate_w) * valid[:, None]
    _, dispatch = moe._pack(probs, valid, moe.capacity(T), jnp.float32)
    assert np.asarray(dispatch).sum(axis=(1, 2)).tolist() == [top_k] * 33 + [0] * 7
    # and at a capacity factor of 1 the favourite expert overflows
    tight = RaggedMoE(num_experts=E, top_k=top_k, capacity_factor=1.0, norm_topk_prob=norm)
    _, dropped = tight._pack(probs, valid, tight.capacity(T), jnp.float32)
    assert np.asarray(dropped).sum() < 33 * top_k


@pytest.mark.parametrize("capacity_factor", [1.0, 16.0], ids=["overflowing", "dropless"])
@pytest.mark.parametrize("top_k", [1, 2, 4, 8])
def test_one_pass_fill_gives_every_assignment_the_level_by_level_slot(top_k, capacity_factor):
    """Top-k above 2 fills the capacity slots in one pass; the slots, and what
    is dropped when an expert overflows, are the level-by-level loop's."""
    import jax
    E, T = 16, 40
    rng = np.random.default_rng(top_k)
    logits = rng.normal(size=(T, E)) * 2.0
    logits[:, 0] += 8.0  # everyone's first choice: it overflows unless capacity = tokens
    probs = jax.nn.softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    valid = jnp.asarray(rng.random(T) < 0.8)
    moe = RaggedMoE(num_experts=E, top_k=top_k, capacity_factor=capacity_factor / top_k)
    C = moe.capacity(T)
    topk_p, topk_e = jax.lax.top_k(probs * valid[:, None], top_k)
    blank = lambda dtype: jnp.zeros((T, E, C), dtype)
    by_level = moe._fill_level_by_level(topk_p, topk_e, valid, C, blank(jnp.float32),
                                        blank(jnp.bfloat16))
    one_pass = moe._fill_in_one_pass(topk_p, topk_e, valid, C, blank(jnp.float32),
                                     blank(jnp.bfloat16))
    for a, b in zip(by_level, one_pass):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    kept = float(np.asarray(one_pass[1], np.float32).sum())
    assert (kept == int(valid.sum()) * top_k) == (capacity_factor == 16.0)


def test_ragged_moe_refuses_a_top_k_it_cannot_route():
    for top_k in (0, 5):
        with pytest.raises(ValueError, match="top_k"):
            RaggedMoE(num_experts=4, top_k=top_k)


def test_the_put_span_counts_expert_rows_and_assignments(model):
    engine = _engine(model)
    # 20 live tokens in a 32-token bucket: 8 layers x 16 experts x 32 slots
    # ... and on the capacity path every bank of every layer is read
    assert engine.model.dispatch_counts(32, 20) == {"moe_path": "capacity",
                                                    "moe_rows": 8 * 16 * 32,
                                                    "moe_assignments": 20 * 4 * 8,
                                                    "moe_banks": 8 * 16}


def test_the_put_span_counts_the_rows_of_the_path_the_bucket_takes(many_experts):
    """64 experts top-8: the 128-token bucket routes by sorting (a row a padded
    assignment), the 64-token one through the masks (every slot of every
    expert); the span of a step says which (``moe_path``)."""
    from deepspeed_tpu import telemetry
    engine = _engine(many_experts, budget=128, capacity_factor=8.0, max_context=256)
    counts = engine.model.dispatch_counts
    assert counts(128, 100) == {"moe_path": "grouped", "moe_rows": 4 * 128 * 8,
                                "moe_assignments": 100 * 8 * 4}
    assert counts(64, 50) == {"moe_path": "capacity", "moe_rows": 4 * 64 * 64,
                              "moe_assignments": 50 * 8 * 4, "moe_banks": 4 * 64}
    session = telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    try:
        engine.put([0], [_ids(40, 128)])
        engine.put([0], [_ids(41, 5)])
        spans = [s for s in telemetry.get_span_recorder().tail(64) if s["name"] == "put"]
    finally:
        session.close()
    assert [(s["args"]["moe_path"], s["args"]["moe_rows"]) for s in spans] == [
        ("grouped", 4 * 1024), ("capacity", 4 * 64 * 8)]


# (c) ----------------------------------------------------------- layer groups ---
def test_a_window_group_holds_its_bound_and_the_full_group_everything(model):
    engine = _engine(model)
    capacity = engine.free_blocks
    m, sm = engine.model, engine._state_manager
    bound = (WINDOW + FEED - 1) // BLOCK + 2
    ids = _ids(5, 120)
    seen = 0
    for n in [32, 32, 32, 8, 8, 1, 1, 1, 1, 1, 1, 1, 1]:
        engine.put([0], [ids[seen:seen + n]])
        seen += n
        seq = sm.get_sequence(0)
        whole = -(-seen // BLOCK)
        assert seq.cur_allocated_blocks == whole
        for g in range(3):
            assert seq.live_blocks_in(g) <= m.max_live_blocks_in(g, seen) <= bound
            # exactly what the next query can still see, in whole blocks
            assert seq.released_in(g) == max(seen - WINDOW + 1, 0) // BLOCK
        assert seq.live_blocks_in(3) == m.max_live_blocks_in(3, seen) == whole
        assert seq.live_blocks == sum(seq.live_blocks_in(g) for g in range(4)) <= \
            m.max_live_blocks(seen)
        assert engine.free_blocks == capacity - seq.live_blocks
        assert engine._live_blocks_by_kind() == {
            "full": whole, "window": sum(seq.live_blocks_in(g) for g in range(3))}
    assert seq.released_blocks == 0  # the full group keeps position 0
    engine.flush(0)
    assert engine.free_blocks == capacity


def test_admission_counts_a_block_id_a_group(model):
    engine = _engine(model, blocks=40)
    # 32 tokens = 8 blocks of positions x 4 groups
    assert engine.query(0, 32, engine.free_blocks) == (32, 32)
    assert engine.query(0, 64, engine.free_blocks) == (40, 40)  # 10 entries x 4 is the pool
    engine.put([0], [_ids(6, 32)])
    assert engine.free_blocks == 40 - 32 + 3 * 4  # each window group gave 4 back
    from deepspeed_tpu.inference.v2.scheduling_utils import SchedulingResult
    assert engine.can_schedule([0], [20]) == SchedulingResult.Success
    assert engine.can_schedule([0], [24]) == SchedulingResult.KVCacheLimitExceeded
    with pytest.raises(ValueError, match="need more KV blocks"):
        engine.put([0], [_ids(7, 24)], do_checks=False)


def test_a_released_block_handed_to_another_sequence_is_never_read_by_the_first(model):
    """A's window groups give blocks back; B takes them (the allocator hands
    out the newest free block first) and writes its own keys there, in window
    AND full layers; A's next logits are what they are with the pool to itself."""
    ids_a, ids_b = _ids(8, 81), _ids(9, 40)
    want = _feed(_engine(model), 0, ids_a, [32, 32, 16, 1])[-1]

    engine = _engine(model)
    seq_a, ever = None, set()
    for at in (0, 32, 64):
        engine.put([0], [ids_a[at:min(at + 32, 80)]])
        seq_a = engine._state_manager.get_sequence(0)
        ever |= {int(b) for b in seq_a.live_kv_blocks}
    held = {int(b) for b in seq_a.live_kv_blocks}
    gone = sorted(ever - held)
    assert gone and len(held) == seq_a.live_blocks
    pool = engine._state_manager.kv_cache
    before = np.asarray(pool.cache[:, :, gone])
    # B in 4-token steps: each takes one block id a group off the head of the
    # free list, so its full group and its window groups all land on A's old blocks
    on_full, on_window = set(), set()
    for at in range(0, 40, BLOCK):
        engine.put([1], [ids_b[at:at + BLOCK]])
        tables = engine._state_manager.get_sequence(1).block_tables
        assert not {int(b) for b in tables.ravel() if b >= 0} & held
        on_full |= set(tables[3].tolist()) & set(gone)
        on_window |= {int(b) for b in tables[:3].ravel()} & set(gone)
    assert on_full and on_window
    after = np.asarray(pool.cache[:, :, gone])
    written = {b for i, b in enumerate(gone) if np.abs(after[:, :, i] - before[:, :, i]).max() > 0}
    assert written == on_full | on_window
    got = np.asarray(engine.put([0], [ids_a[80:]]))[0]
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    np.testing.assert_allclose(got, _reference_rows(model, ids_a, [80])[0], atol=ATOL, rtol=0)


def test_offload_and_restore_keep_every_groups_table(model):
    engine = _engine(model)
    ids = _ids(10, 71)
    want = _feed(_engine(model), 0, ids, [32, 32, 6, 1])[-1]
    _feed(engine, 0, ids[:70], [32, 32, 6])
    seq = engine._state_manager.get_sequence(0)
    released, live = [seq.released_in(g) for g in range(4)], seq.live_blocks
    engine.offload_sequence(0)
    assert engine.free_blocks == 160 and engine._restore_cost(0, seq) == live
    _feed(engine, 1, _ids(11, 32), [32])
    got = np.asarray(engine.put([0], [ids[70:]]))[0]
    assert all(seq.released_in(g) >= released[g] for g in range(4)) and seq.released_in(3) == 0
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)


def test_what_needs_one_whole_block_table_refuses(model):
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler
    engine = _engine(model)
    for feature in ("prefix_cache", "kv_tiers"):
        with pytest.raises(ValueError, match="sliding-window model"):
            ServingScheduler(engine, ServingConfig(**{feature: {"enabled": True}}), start=False)
    scheduler = ServingScheduler(engine, ServingConfig(), start=False)
    try:
        for flag in ("handoff", "park"):
            with pytest.raises(ValueError, match="sliding-window model"):
                scheduler.submit(_ids(12, 8), max_new_tokens=2, **{flag: True})
    finally:
        scheduler.stop(drain=False)
    engine.put([0], [_ids(13, 12)])
    with pytest.raises(ValueError, match="4 block tables"):
        engine.export_sequence(0)
    with pytest.raises(ValueError, match="4 block tables"):
        engine._state_manager.create_cached_sequence(5, [1, 2, 3, 4], 16)
    engine.put([0], [_ids(14, 30)])
    with pytest.raises(ValueError, match="already released"):
        engine.rollback(0, 20)
    engine.rollback(0, 1)


def test_tree_verify_repacks_the_accepted_path_in_every_group(model):
    """A branching draft tree: the accepted nodes' K/V move to contiguous
    slots through each group's own table (``compact_kv``)."""
    from deepspeed_tpu.inference.v2.spec import TokenTree
    engine = _engine(model)
    ids = _ids(15, 24)
    engine.put([0], [ids[:20]])
    # root 20; children a (21) and b (wrong); a's child is ids[22]
    tree = TokenTree(tokens=np.asarray([ids[20], 7, ids[21], ids[22]], np.int32),
                     parents=np.asarray([-1, 0, 0, 2], np.int32))
    engine.verify_tree([0], [tree], greedy=True)
    engine.compact_accepted(0, tree.size, [2, 3])
    got = np.asarray(engine.put([0], [ids[23:24]]))[0]
    np.testing.assert_allclose(got, _reference_rows(model, ids, [23])[0], atol=ATOL, rtol=0)


# ------------------------------------------------------ through the scheduler ---
def _greedy_streams(scheduler, prompts, n):
    """Submit ``prompts`` for ``n`` greedy tokens each; the streamed tokens."""
    handles = [scheduler.submit(p, max_new_tokens=n, temperature=0.0) for p in prompts]
    outs = []
    for h in handles:
        toks = []
        while True:
            tok = h.stream.get(timeout=120)
            if tok is None:
                break
            toks.append(int(tok))
        assert h.state.name == "DONE", h.error
        outs.append(toks)
    return outs


def test_grouped_chunks_and_capacity_decodes_give_the_references_logits(many_experts):
    """``build_engine`` -> ``ServingScheduler`` with a 128-token budget. The
    engine alone first: a 201-token prompt fed as a grouped 128-token chunk, a
    72-token chunk on the capacity path and a decode step gives the float32
    reference's logits at each end. Then the scheduler: prompts prefill in
    grouped chunks and decode in capacity buckets, greedy tokens against the
    reference, and the counters show both paths engaged."""
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler
    engine = _engine(many_experts, blocks=400, budget=128, capacity_factor=8.0, max_context=256)
    assert [engine.model.moe_path(t) for t in (8, 64, 128)] == ["capacity", "capacity", "grouped"]
    # the engine alone: a grouped chunk, a capacity chunk, a decode step
    ids = _ids(50, 201)
    ends = _feed(engine, 9, ids, [128, 72, 1])
    want = _reference_rows(many_experts, ids, [127, 199, 200], **MANY_EXPERTS)
    for got, row in zip(ends, want):
        np.testing.assert_allclose(got, row, atol=ATOL, rtol=0)
    engine.flush(9)
    # decode_chunk 1: every decode step is a ``put`` step the counters see
    scheduler = ServingScheduler(engine, ServingConfig(decode_chunk=1))
    try:
        prompts = [_ids(51, 200), _ids(52, 140), _ids(53, 30)]
        outs = _greedy_streams(scheduler, prompts, 6)
        counters = scheduler.stats()["counters"]
    finally:
        scheduler.stop(drain=False)
    for prompt, toks in zip(prompts, outs):
        assert len(toks) == 6
        full = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        want = _reference_rows(many_experts, full, np.arange(prompt.size - 1, full.size),
                               **MANY_EXPERTS)
        top2 = np.sort(want, axis=-1)[:, -2:]
        decided = (top2[:, 1] - top2[:, 0]) > 10 * ATOL
        assert (np.asarray(toks)[decided] == want.argmax(-1)[decided]).all()
    assert counters["moe_grouped_steps"] >= 2 and counters["moe_capacity_steps"] >= 5
    assert counters["moe_grouped_steps"] + counters["moe_capacity_steps"] == counters["put_steps"]


def test_the_serving_scheduler_serves_it_past_the_window(model):
    """``build_engine`` -> ``ServingScheduler``: the path every serving cell
    takes. Greedy tokens against the reference's argmax, prompts on both sides
    of the window, and the pool whole again at the end."""
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler
    engine = _engine(model)
    capacity = engine.free_blocks
    scheduler = ServingScheduler(engine, ServingConfig(decode_chunk=4))
    try:
        prompts = [_ids(20, 70), _ids(21, 9), _ids(22, 40)]
        outs = _greedy_streams(scheduler, prompts, 6)
    finally:
        scheduler.stop(drain=False)
    for prompt, toks in zip(prompts, outs):
        assert len(toks) == 6
        full = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        want = _reference_rows(model, full, np.arange(prompt.size - 1, full.size))
        top2 = np.sort(want, axis=-1)[:, -2:]
        decided = (top2[:, 1] - top2[:, 0]) > 10 * ATOL
        assert (np.asarray(toks)[decided] == want.argmax(-1)[decided]).all()
    assert engine.released_blocks > 0 and engine.free_blocks == capacity
