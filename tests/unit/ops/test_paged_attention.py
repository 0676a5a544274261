"""Pallas paged-attention kernel vs dense reference (reference:
tests for blocked_flash / ragged_ops kernels, run as Pallas-vs-jnp
comparisons per SURVEY.md §4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention_prefill,
                                                      paged_attention_update)


def _dense_reference(q, cache, li, table, token_seq, token_pos, token_valid, window=0):
    """Per-token dense attention over the block-table history (cache already
    contains every token's K/V, including the queries' own); with ``window``
    over the last ``window`` keys only, and no table entry before them read."""
    T, H, D = q.shape
    L, _, NB, KVH, bs, _ = cache.shape
    S, MB = table.shape
    rep = H // KVH
    out = np.zeros((T, H, D), np.float32)
    for t in range(T):
        if not token_valid[t]:
            continue
        s, pos = int(token_seq[t]), int(token_pos[t])
        first = max(pos - window + 1, 0) if window else 0
        n = pos + 1 - first
        k = np.zeros((n, KVH, D), np.float32)
        v = np.zeros((n, KVH, D), np.float32)
        for p in range(first, pos + 1):
            bid = int(table[s, p // bs])
            k[p - first] = np.asarray(cache[li, 0, bid, :, p % bs], np.float32)
            v[p - first] = np.asarray(cache[li, 1, bid, :, p % bs], np.float32)
        for h in range(H):
            kv = h // rep
            logits = (np.asarray(q[t, h], np.float32) @ k[:, kv].T) / np.sqrt(D)
            w = np.exp(logits - logits.max())
            w /= w.sum()
            out[t, h] = w @ v[:, kv]
    return out


@pytest.mark.parametrize("kvh", [4, 2])  # MHA and GQA
def test_paged_attention_matches_dense(kvh):
    rng = np.random.default_rng(0)
    L, NB, bs, D, H = 2, 12, 16, 128, 4
    S, MB = 3, 4
    cache0 = rng.normal(size=(L, 2, NB, kvh, bs, D)).astype(np.float32)
    # per-seq block tables with distinct blocks
    perm = rng.permutation(NB)[:S * MB].reshape(S, MB)
    table = jnp.asarray(perm, jnp.int32)

    # token mix: decode token for seq0 (pos 20), mid-prefill token for seq1,
    # fresh token for seq2, one padding row
    token_seq = jnp.asarray([0, 1, 2, 3], jnp.int32)
    token_pos = jnp.asarray([20, 7, 0, 0], jnp.int32)
    token_valid = jnp.asarray([1, 1, 1, 0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(4, H, D)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(4, kvh, D)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(4, kvh, D)), jnp.float32)

    # expected cache: each valid token's K/V written at its (block, offset)
    exp_cache = cache0.copy()
    for li in range(L):
        for t in range(4):
            if not int(token_valid[t]):
                continue
            s, pos = int(token_seq[t]), int(token_pos[t])
            bid = int(perm[s, pos // bs])
            exp_cache[li, 0, bid, :, pos % bs] = np.asarray(k_new[t])
            exp_cache[li, 1, bid, :, pos % bs] = np.asarray(v_new[t])

    cache = jnp.asarray(cache0)
    for li in range(L):
        got, cache = paged_attention_update(q, k_new, v_new, cache, li, table,
                                            token_seq, token_pos, token_valid)
        want = _dense_reference(q, jnp.asarray(exp_cache), li, np.asarray(table),
                                np.asarray(token_seq), np.asarray(token_pos),
                                np.asarray(token_valid))
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(cache), exp_cache, rtol=0, atol=0)

    # all-invalid batch: no output, no cache mutation
    out2, cache2 = paged_attention_update(q, k_new, v_new, jnp.asarray(exp_cache), 0,
                                          table, token_seq, token_pos,
                                          jnp.zeros(4, jnp.int32))
    assert not np.any(np.asarray(out2))
    np.testing.assert_allclose(np.asarray(cache2), exp_cache, rtol=0, atol=0)


# tiled mode: (seen, new tokens) per sequence, in batch order; bucket tokens
TILED_BATCHES = {
    # one sequence's chunk starting mid-block (seen > 0) and crossing block
    # boundaries (16-token blocks) and the tile boundary at token 64
    "chunk-crossing-blocks": ([(37, 70)], 128),
    # a 2-tile chunk with decode rows riding along, before and after it
    "chunk-plus-decode-rows": ([(3, 1), (20, 100), (33, 1), (0, 1)], 128),
    # a whole tile (tokens 64..127) of padding
    "padding-tile": ([(0, 50)], 128),
    "first-prefill-one-tile": ([(0, 64)], 64),
}


@pytest.mark.parametrize("kvh", [4, 2])  # MHA and GQA
@pytest.mark.parametrize("batch", list(TILED_BATCHES))
def test_paged_attention_prefill_matches_dense(kvh, batch):
    """The query-tiled grid against the dense reference, and the pool's blocks:
    every inserted row lands, every other element is bit-identical."""
    seqs, T = TILED_BATCHES[batch]
    rng = np.random.default_rng(0)
    L, NB, bs, D, H = 2, 40, 16, 128, 4
    S, MB = 8, 8
    cache0 = rng.normal(size=(L, 2, NB, kvh, bs, D)).astype(np.float32)
    # distinct blocks per sequence; the pool's LAST block belongs to nobody
    free = list(rng.permutation(NB - 1))
    table = np.full((S, MB), -1, np.int32)
    token_seq = np.full(T, S - 1, np.int32)
    token_pos = np.zeros(T, np.int32)
    token_valid = np.zeros(T, np.int32)
    seq_seen, seq_ntok, last_tok = (np.zeros(S, np.int32) for _ in range(3))
    cursor = 0
    for s, (seen, n) in enumerate(seqs):
        for b in range(-(-(seen + n) // bs)):
            table[s, b] = free.pop()
        token_seq[cursor:cursor + n] = s
        token_pos[cursor:cursor + n] = np.arange(seen, seen + n)
        token_valid[cursor:cursor + n] = 1
        cursor += n
        seq_seen[s], seq_ntok[s], last_tok[s] = seen, n, cursor - 1
    q = rng.normal(size=(T, H, D)).astype(np.float32)
    k_new = rng.normal(size=(T, kvh, D)).astype(np.float32)
    v_new = rng.normal(size=(T, kvh, D)).astype(np.float32)

    exp_cache = cache0.copy()
    for t in range(cursor):
        bid = table[token_seq[t], token_pos[t] // bs]
        exp_cache[:, 0, bid, :, token_pos[t] % bs] = k_new[t]
        exp_cache[:, 1, bid, :, token_pos[t] % bs] = v_new[t]

    cache = jnp.asarray(cache0)
    for li in range(L):
        got, cache = paged_attention_prefill(q, k_new, v_new, cache, li, table, seq_seen,
                                             seq_ntok, last_tok)
        want = _dense_reference(q, exp_cache, li, table, token_seq, token_pos, token_valid)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
        assert not np.any(np.asarray(got)[cursor:])  # padding rows are zero
    np.testing.assert_array_equal(np.asarray(cache), exp_cache)

    # no live sequence: no output, no cache mutation
    out2, cache2 = paged_attention_prefill(q, k_new, v_new, jnp.asarray(exp_cache), 0, table,
                                           seq_seen, np.zeros(S, np.int32), last_tok)
    assert not np.any(np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(cache2), exp_cache)


# ---- sliding window: both grids, window 16 over 4-token blocks ---------------
WINDOW, WBS = 16, 4


def _release_passed(table, cache, seq_next_pos, window, bs):
    """What the pool's rolling release leaves a later step: the table entries
    of blocks wholly behind ``next_pos - window + 1`` are holes (-1), and the
    blocks they named hold another owner's data — NaN here, so that one
    dereference of a released block poisons the output."""
    table, cache = table.copy(), cache.copy()
    for s, pos in seq_next_pos.items():
        for b in range(max(pos - window + 1, 0) // bs):
            cache[:, :, table[s, b]] = np.nan
            table[s, b] = -1
    return table, cache


@pytest.mark.parametrize("kvh", [4, 2])  # MHA and GQA
def test_token_grid_window_matches_dense_masked(kvh):
    """Decode rows at 3-4 x the window, one exactly at the window's edge (it
    still sees position 0), one inside it, one padding row; the blocks the
    window has passed are released and overwritten."""
    rng = np.random.default_rng(0)
    L, NB, bs, D, H = 2, 80, WBS, 128, 4
    S, MB = 8, 16
    positions = [50, 63, WINDOW - 1, WINDOW, 5, 0]
    T = 8
    cache0 = rng.normal(size=(L, 2, NB, kvh, bs, D)).astype(np.float32)
    cache0[:, :, 0] = 0.0  # block 0 is nobody's: where a hole's -1 clamps to
    free = list(rng.permutation(np.arange(1, NB)))
    table = np.full((S, MB), -1, np.int32)
    token_seq = np.full(T, S - 1, np.int32)
    token_pos = np.zeros(T, np.int32)
    token_valid = np.zeros(T, np.int32)
    for s, pos in enumerate(positions):
        for b in range(pos // bs + 1):
            table[s, b] = free.pop()
        token_seq[s], token_pos[s], token_valid[s] = s, pos, 1
    q = rng.normal(size=(T, H, D)).astype(np.float32)
    k_new = rng.normal(size=(T, kvh, D)).astype(np.float32)
    v_new = rng.normal(size=(T, kvh, D)).astype(np.float32)
    exp_cache = cache0.copy()
    for t, pos in enumerate(positions):
        exp_cache[:, 0, table[t, pos // bs], :, pos % bs] = k_new[t]
        exp_cache[:, 1, table[t, pos // bs], :, pos % bs] = v_new[t]

    holes, poisoned = _release_passed(table, cache0, dict(enumerate(positions)), WINDOW, bs)
    assert (holes[0, :8] == -1).all() and holes[2, 0] >= 0 and holes[3, 0] >= 0
    cache = jnp.asarray(poisoned)
    for li in range(L):
        got, cache = paged_attention_update(q, k_new, v_new, cache, li, holes, token_seq,
                                            token_pos, token_valid, window=WINDOW)
        want = _dense_reference(q, exp_cache, li, table, token_seq, token_pos, token_valid,
                                window=WINDOW)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    # the window changes the answer (the test would pass a kernel without one otherwise)
    full = _dense_reference(q, exp_cache, 0, table, token_seq, token_pos, token_valid)
    assert np.abs(full[0] - want[0]).max() > 1e-3


WINDOW_TILED_BATCHES = {
    # a first chunk that straddles the window's edge: queries 0..15 see all
    # they have, queries 16..69 lose keys; the tile's first and last query
    # see different first blocks
    "chunk-straddling-the-edge": ([(0, 70)], 128),
    # a later chunk far past the window (3-4 x), starting mid-block, over the
    # tile boundary, with decode rows riding along past and inside the window
    "late-chunk-plus-decode-rows": ([(61, 1), (45, 100), (7, 1), (WINDOW - 1, 1)], 128),
    "one-tile-at-the-edge": ([(WINDOW - 3, 64)], 64),
}


@pytest.mark.parametrize("kvh", [4, 2])  # MHA and GQA
@pytest.mark.parametrize("batch", list(WINDOW_TILED_BATCHES))
def test_tile_grid_window_matches_dense_masked(kvh, batch):
    seqs, T = WINDOW_TILED_BATCHES[batch]
    rng = np.random.default_rng(0)
    L, NB, bs, D, H = 2, 120, WBS, 128, 4
    S, MB = 8, 64
    cache0 = rng.normal(size=(L, 2, NB, kvh, bs, D)).astype(np.float32)
    cache0[:, :, 0] = 0.0
    free = list(rng.permutation(np.arange(1, NB)))
    table = np.full((S, MB), -1, np.int32)
    token_seq = np.full(T, S - 1, np.int32)
    token_pos = np.zeros(T, np.int32)
    token_valid = np.zeros(T, np.int32)
    seq_seen, seq_ntok, last_tok = (np.zeros(S, np.int32) for _ in range(3))
    cursor = 0
    for s, (seen, n) in enumerate(seqs):
        for b in range(-(-(seen + n) // bs)):
            table[s, b] = free.pop()
        token_seq[cursor:cursor + n] = s
        token_pos[cursor:cursor + n] = np.arange(seen, seen + n)
        token_valid[cursor:cursor + n] = 1
        cursor += n
        seq_seen[s], seq_ntok[s], last_tok[s] = seen, n, cursor - 1
    q = rng.normal(size=(T, H, D)).astype(np.float32)
    k_new = rng.normal(size=(T, kvh, D)).astype(np.float32)
    v_new = rng.normal(size=(T, kvh, D)).astype(np.float32)
    exp_cache = cache0.copy()
    for t in range(cursor):
        bid = table[token_seq[t], token_pos[t] // bs]
        exp_cache[:, 0, bid, :, token_pos[t] % bs] = k_new[t]
        exp_cache[:, 1, bid, :, token_pos[t] % bs] = v_new[t]

    # released before this step: what is behind the window of each sequence's
    # FIRST query of the step
    holes, poisoned = _release_passed(table, cache0, {s: seen for s, (seen, _) in enumerate(seqs)},
                                      WINDOW, bs)
    cache = jnp.asarray(poisoned)
    for li in range(L):
        got, cache = paged_attention_prefill(q, k_new, v_new, cache, li, holes, seq_seen,
                                             seq_ntok, last_tok, window=WINDOW)
        want = _dense_reference(q, exp_cache, li, table, token_seq, token_pos, token_valid,
                                window=WINDOW)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
        assert not np.any(np.asarray(got)[cursor:])
    live = ~np.isnan(poisoned)
    np.testing.assert_array_equal(np.asarray(cache)[live], exp_cache[live])


# sha256 of str(jax.make_jaxpr(...)) (addresses blanked) of both grids at the
# shapes below, taken from the commit BEFORE the kernel had a window argument
# (d15f72e, jax 0.9.0): with window == 0 the traced program is that one.
_PRE_WINDOW_JAXPR = {
    "update": "eac6774739b3692404a729d6558959bb2d91e2e90a4447e8f1ae91da79a697f7",
    "prefill": "ab0af99cf22339658ef1e5723906e91a18e334a90a28f839deefe5955e66ed0e",
}


@pytest.mark.parametrize("grid", list(_PRE_WINDOW_JAXPR))
def test_window_zero_traces_the_program_it_always_did(grid):
    import hashlib
    import re
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded jaxpr text is jax 0.9.0's")
    L, NB, KVH, bs, D, H, S, MB = 2, 12, 2, 16, 128, 4, 8, 4
    cache = jnp.zeros((L, 2, NB, KVH, bs, D), jnp.float32)
    table = jnp.zeros((S, MB), jnp.int32)
    if grid == "update":
        fn, T, meta = paged_attention_update, 8, [jnp.zeros((8, ), jnp.int32)] * 3
    else:
        fn, T, meta = paged_attention_prefill, 64, [jnp.zeros((S, ), jnp.int32)] * 3
    q = jnp.zeros((T, H, D), jnp.float32)
    kn = jnp.zeros((T, KVH, D), jnp.float32)

    def text(**kw):
        jaxpr = jax.make_jaxpr(lambda *a: fn(*a[:4], 1, *a[4:], interpret=False, **kw))(
            q, kn, kn, cache, table, *meta)
        return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))

    assert hashlib.sha256(text(window=0).encode()).hexdigest() == _PRE_WINDOW_JAXPR[grid]
    assert text(window=0) == text()
    assert text(window=16) != text()


def test_padding_tokens_never_corrupt_last_block():
    """Regression (code-review r3): -1 scatter indices WRAP in jax; padding
    tokens must route to a positive OOB sentinel or they overwrite block NB-1
    on the XLA gather path."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)
    from deepspeed_tpu.models.llama import LlamaConfig, init_params
    from deepspeed_tpu.utils import groups

    groups.initialize_mesh(force=True)
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    _, params = init_params(cfg)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=8),
                               max_context=128)
    eng = build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=16, use_paged_kernel=False))
    # decode bucket pads 1 token -> 8: 7 padding tokens per forward
    eng.put([0], [np.asarray([1, 2, 3], np.int64)])
    last_block_before = np.asarray(eng._state_manager.kv_cache.cache[:, :, -1])
    eng.put([0], [np.asarray([4], np.int64)])
    last_block_after = np.asarray(eng._state_manager.kv_cache.cache[:, :, -1])
    np.testing.assert_array_equal(last_block_after, last_block_before)


@pytest.mark.parametrize("prompt_tokens", [21, 90], ids=["token-grid", "tiled-prefill"])
def test_engine_kernel_vs_dense_path(prompt_tokens):
    """Full engine equivalence: forcing the Pallas kernel must reproduce the
    XLA gather path's logits through prefill + decode. 21 tokens are a bucket
    of 32 (the per-token grid); 90 are a bucket of 128 (the query-tiled grid,
    two tiles crossing five 16-token blocks), then decode rows on the token grid."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)
    from deepspeed_tpu.models.llama import LlamaConfig, init_params
    from deepspeed_tpu.utils import groups

    groups.initialize_mesh(force=True)
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    _, params = init_params(cfg)

    def ecfg(kernel):
        mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                              size=64), max_context=512)
        return RaggedInferenceEngineConfig(state_manager=mgr, kv_block_size=16,
                                           use_paged_kernel=kernel)

    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, prompt_tokens)

    outs = {}
    for kernel in (False, True):
        eng = build_engine(params, cfg, ecfg(kernel))
        logits = [np.asarray(eng.put([0], [prompt]))]
        for _ in range(3):
            nxt = int(np.argmax(logits[-1][0]))
            logits.append(np.asarray(eng.put([0], [np.asarray([nxt])])))
        outs[kernel] = logits
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-5)


def test_engine_mixed_put_kernel_vs_gather_path():
    """One ``put`` carrying a decode row and another sequence's second chunk
    (``seq_seen`` > 0, 70 tokens: a bucket of 128 on the query-tiled grid):
    the kernel arm's logits are the gather arm's."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)
    from deepspeed_tpu.models.llama import LlamaConfig, init_params
    from deepspeed_tpu.utils import groups

    groups.initialize_mesh(force=True)
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    _, params = init_params(cfg)
    rng = np.random.default_rng(5)
    first, chunk_a, chunk_b = (rng.integers(0, cfg.vocab_size, n) for n in (19, 40, 70))

    outs = {}
    for kernel in (False, True):
        mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                              size=64), max_context=512)
        eng = build_engine(params, cfg, RaggedInferenceEngineConfig(
            state_manager=mgr, kv_block_size=16, use_paged_kernel=kernel))
        assert eng.model.attention_arm(128) == ("paged_tiled" if kernel else "xla_gather")
        nxt = int(np.argmax(np.asarray(eng.put([0], [first]))[0]))
        eng.put([1], [chunk_a])
        outs[kernel] = np.asarray(eng.put([0, 1], [np.asarray([nxt]), chunk_b]))
    np.testing.assert_allclose(outs[False], outs[True], rtol=3e-5, atol=3e-5)


def test_decode_loop_kernel_vs_gather_path():
    """engine.decode_loop (the on-device scan) must generate identical greedy
    tokens whichever attention implementation runs inside the scan."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)
    from deepspeed_tpu.models.llama import LlamaConfig, init_params
    from deepspeed_tpu.utils import groups

    groups.initialize_mesh(force=True)
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    _, params = init_params(cfg)

    def ecfg(kernel):
        mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                              size=64), max_context=512)
        return RaggedInferenceEngineConfig(state_manager=mgr, kv_block_size=16,
                                           use_paged_kernel=kernel)

    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 19)
    toks = {}
    for kernel in (False, True):
        eng = build_engine(params, cfg, ecfg(kernel))
        first = int(np.argmax(np.asarray(eng.put([0], [prompt]))[0]))
        toks[kernel] = eng.decode_loop([0], [np.asarray([first])], 4)
    np.testing.assert_array_equal(toks[False], toks[True])
