"""The latent KV group (PR 40): a token's state a layer is a latent row and an
index key under one block table; the pool accounts a block id's bytes from the
state spec; what moves block contents refuses a latent group by name; and the
Pallas kernels in interpret mode against ``jax.numpy``."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode, KVCacheConfig,
                                                               MemoryConfig)
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import RaggedBatchWrapper
from deepspeed_tpu.ops.pallas import latent_attention as la
from deepspeed_tpu.utils import groups


def _cache(**kw):
    groups.initialize_mesh(force=True)
    config = KVCacheConfig(block_size=128, cache_shape=(5, 1, 192), state_widths=(640, 128),
                           cache_dtype="bfloat16", **kw)
    return BlockedKVCache(config, MemoryConfig(mode=AllocationMode.ALLOCATE, size=6))


def test_a_block_id_is_priced_from_the_state_spec_and_the_pools_share_one_table():
    cache = _cache()
    latent, index = cache.cache
    assert latent.shape == (5, 6, 128, 640) and index.shape == (5, 6, 128, 128)
    assert latent.dtype == index.dtype == jnp.bfloat16
    # 128 tokens x 5 layers x (640 + 128) values x 2 bytes; a token a layer: 1,536 B
    assert cache.block_bytes == 128 * 5 * 768 * 2 == 960 * 1024
    assert cache.block_bytes // (128 * 5) == 1536
    # the K/V pair's price is unchanged
    groups.initialize_mesh(force=True)
    pair = BlockedKVCache(KVCacheConfig(block_size=64, cache_shape=(5, 4, 128)),
                          MemoryConfig(mode=AllocationMode.ALLOCATE, size=3))
    assert pair.block_bytes == 64 * 2 * 5 * 4 * 128 * 2 and pair.cache.shape == (5, 2, 3, 4, 64, 128)
    # one allocator: a block id addresses both pools
    ids = cache.reserve(2)
    assert cache.free_blocks == 4
    cache.free(ids)
    assert cache.free_blocks == 6


@pytest.mark.parametrize("call, what", [
    (lambda c: c.fork_blocks([0]), "fork_blocks"),
    (lambda c: c.gather_blocks([0]), "gather_blocks"),
    (lambda c: c.scatter_blocks(np.zeros((5, 2, 1, 1, 128, 192))), "scatter_blocks"),
    (lambda c: c.offload([0]), "gather_blocks"),
])
def test_what_moves_block_contents_refuses_a_latent_group_by_name(call, what):
    with pytest.raises(NotImplementedError, match=f"{what}.*latent group"):
        call(_cache())


def test_the_scheduler_refuses_prefix_cache_and_tiers_for_a_latent_group():
    import jax
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import deepseek_v32 as ds
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler
    groups.initialize_mesh(force=True)
    cfg = ds.DeepseekV32Config.tiny(dtype=jnp.float32)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=32),
                               max_context=64, max_ragged_batch_size=32,
                               max_ragged_sequence_count=8)
    engine = build_engine(ds.init_params(cfg, rng=jax.random.PRNGKey(0))[1], cfg,
                          RaggedInferenceEngineConfig(state_manager=mgr, kv_block_size=8))
    assert engine.model.kv_state_widths == (128, 16) and engine.n_kv_cache_groups == 1
    with pytest.raises(ValueError, match="latent KV group"):
        ServingScheduler(engine, ServingConfig(prefix_cache={"enabled": True}))
    with pytest.raises(NotImplementedError, match="latent KV group"):
        engine.model.compact_kv(None, [0], [1])
    engine.close()


def test_the_smallest_table_bucket_is_the_wrappers():
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import DSSequenceDescriptor
    for least, want in ((4, 4), (16, 16)):
        wrapper = RaggedBatchWrapper(DSStateManagerConfig(max_ragged_batch_size=32,
                                                          max_ragged_sequence_count=8),
                                     block_size=8, min_table_bucket=least)
        seq = DSSequenceDescriptor(0, max_blocks_per_seq=64)
        seq.extend_kv_cache(np.arange(3))
        seq.pre_forward(5)
        wrapper.insert_sequence(seq, np.arange(5))
        assert wrapper.finalize()["seq_meta"].shape == (8, 4 + want)


# ----------------------------------------------------------------- kernels ---
def _case(rng, T, seqs, S=8, MB=4, bs=16, H=4, C=32, R=8, NH=8, D=16):
    """A ragged batch over random pools: ``seqs`` = (seen, new tokens) a sequence."""
    W, L, NB = la.padded_width(C + R), 2, S * MB + 3
    latent = jnp.asarray(rng.normal(size=(L, NB, bs, W)), jnp.float32).at[..., C + R:].set(0)
    index = jnp.asarray(rng.normal(size=(L, NB, bs, D)), jnp.float32)
    table = np.full((S, MB), -1, np.int32)
    perm = rng.permutation(NB)
    seen, ntok, last = (np.zeros(S, np.int32) for _ in range(3))
    tseq, tpos, tval = np.full(T, S - 1, np.int32), np.zeros(T, np.int32), np.zeros(T, bool)
    cursor = 0
    for i, (a, n) in enumerate(seqs):
        table[i, :-(-(a + n) // bs)] = perm[i * MB:i * MB - (-(a + n) // bs)]
        seen[i], ntok[i] = a, n
        tseq[cursor:cursor + n], tval[cursor:cursor + n] = i, True
        tpos[cursor:cursor + n] = a + np.arange(n)
        cursor += n
        last[i] = cursor - 1
    q = 0.3 * jnp.asarray(rng.normal(size=(T, H, W)), jnp.float32).at[..., C + R:].set(0)
    q_i = jnp.asarray(rng.normal(size=(T, NH, D)), jnp.float32)
    w_i = jnp.asarray(rng.normal(size=(T, NH)), jnp.float32)
    return dict(latent=latent, index=index, q=q, q_i=q_i, w_i=w_i, C=C,
                kernel=(table, seen, ntok, last), xla=(table, tseq, tpos, tval), valid=tval)


# a step on the tiled grid as the kernel's geometries meet it (16 blocks of 16: a one-token
# pass walks 128 keys an iteration): a chunk that crosses a tile's edge at 8 and 16, a rider
# in the same tile as that chunk's last tokens, a chunk that crosses the edge at 32, a
# sequence of no tokens, then riders (each hands its walk on to the next in its tile; not
# over a tile's edge, tokens 63 | 64, nor over a sequence of no tokens) whose contexts are
# one key, shorter than one deep chunk, one whole, not a whole number and the whole table,
# the last of them a tile of riders only (tokens 56.., 64.. at 16 and 32 tokens a tile)
# before rows of no sequence
_MIXED = [(30, 20), (200, 1), (0, 35), (17, 0), (0, 1), (5, 1), (127, 1), (130, 1), (255, 1),
          (40, 1), (77, 1), (128, 1), (3, 1), (9, 0), (254, 1), (100, 1), (19, 1), (64, 1)]


@pytest.mark.parametrize("selected", [False, True], ids=["every-key", "selected"])
@pytest.mark.parametrize("T, seqs, tile, sizes", [
    (8, [(40, 1), (3, 1), (63, 1), (0, 1), (17, 1)], 1, {}),     # the per-token grid: decode rows
    (64, [(30, 20), (3, 1), (0, 35), (50, 1), (17, 3)], 64, {}),  # the tiled grid: one tile
    (128, _MIXED, 8, dict(S=18, MB=16, H=128)),
    (128, _MIXED, 16, dict(S=18, MB=16, H=64)),
    (128, _MIXED, 32, dict(S=18, MB=16, H=32)),
], ids=["token-grid", "tiled-grid", "tile-of-8", "tile-of-16", "tile-of-32"])
def test_the_kernels_in_interpret_mode_are_the_jax_numpy_arm(T, seqs, tile, sizes, selected):
    c = _case(np.random.default_rng(T), T, seqs, **sizes)
    assert la.tile_tokens(T, c["q"].shape[1]) == tile
    scores_x = np.asarray(la.latent_index_scores_xla(c["q_i"], c["w_i"], c["index"], 1, *c["xla"]))
    scores_k = np.asarray(la.latent_index_scores(c["q_i"], c["w_i"], c["index"], 1, *c["kernel"],
                                                 interpret=True))
    live = scores_x > 0.5 * la.NEG_INF
    assert (live == (scores_k > 0.5 * la.NEG_INF)).all()  # the same keys are scored
    assert np.abs(scores_k - scores_x)[live].max() < 1e-4
    threshold = la.kth_largest(jnp.asarray(scores_k), 8)
    kept = (scores_k >= np.asarray(threshold)[:, None]) & live
    assert (kept.sum(1)[c["valid"]] == np.minimum(live.sum(1), 8)[c["valid"]]).all()
    selection = (jnp.asarray(scores_k), threshold) if selected else ()
    want = np.asarray(la.latent_paged_attention_xla(c["q"], c["latent"], 1, *c["xla"],
                                                    *selection, value_width=c["C"]))
    got = np.asarray(la.latent_paged_attention(c["q"], c["latent"], 1, *c["kernel"],
                                               *selection, value_width=c["C"], interpret=True))
    assert np.abs(got - want).max() < 1e-4
    assert np.abs(got[~c["valid"]]).max() == 0  # a row of no sequence is zero


@pytest.mark.parametrize("heads, tile, chunk_tiles", [(32, 32, 7), (64, 16, 14), (128, 8, 28)])
def test_the_host_counts_the_passes_the_tiled_kernel_makes(heads, tile, chunk_tiles):
    """``tiled_passes`` by ``_attn_kernel``'s rule: 32 decode rows beside a
    224-token chunk are 32 rider passes and the chunk's tiles; a sequence of no
    tokens is no pass; a chunk's last tile that owns ONE token walks as a rider."""
    ntok = np.array([1] * 32 + [0, 224], np.int32)
    last = np.cumsum(ntok).astype(np.int32) - 1
    assert la.tile_tokens(256, heads) == tile
    assert la.tiled_passes(ntok, last, 256, heads) == (32 + chunk_tiles, 32)
    ntok = np.array([tile + 1, 0, 5], np.int32)  # tokens 0..tile, then tile + 1 .. tile + 5
    assert la.tiled_passes(ntok, np.cumsum(ntok) - 1, 256, heads) == (3, 1)


def test_kth_largest_is_exact_on_ties_negatives_and_short_rows():
    rows = np.array([[3.0, -1.0, 3.0, 0.0, -0.0, 2.5, -7.0, 1e-30],
                     [la.NEG_INF] * 6 + [0.25, -0.5]], np.float32)
    for k in (1, 2, 3, 5, 8):
        got = np.asarray(la.kth_largest(jnp.asarray(rows), k))
        assert (got == np.sort(rows, axis=1)[:, -k]).all()
    # fewer live entries than k: the threshold is the floor, and the mask is the live ones
    threshold = np.asarray(la.kth_largest(jnp.asarray(rows), 4))[1]
    assert threshold == np.float32(la.NEG_INF)
