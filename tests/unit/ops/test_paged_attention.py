"""Pallas paged-attention kernel vs dense reference (reference:
tests for blocked_flash / ragged_ops kernels, run as Pallas-vs-jnp
comparisons per SURVEY.md §4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention_prefill,
                                                      paged_attention_update)


def _dense_reference(q, cache, li, table, token_seq, token_pos, token_valid, window=0, block=0):
    """Per-token dense attention over the block-table history (cache already
    contains every token's K/V, including the queries' own); with ``window``
    over the last ``window`` keys only, and no table entry before them read;
    with ``block`` over every key up to the END of the query's block of
    ``block`` positions (blocks counted from position 0)."""
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
        if block:
            pos = (pos // block + 1) * block - 1
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


def _ragged_batch(seqs, T, S, MB, bs, free):
    """``seqs``: (seen, new tokens) per sequence, in batch order, in a bucket of
    ``T`` tokens; blocks drawn from ``free``. Returns the block table, the
    per-token and the per-sequence metadata, and the number of live tokens."""
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
    return table, (token_seq, token_pos, token_valid), (seq_seen, seq_ntok, last_tok), cursor


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


def _check_tile_grid(seqs, T, *, H, kvh, bs, NB, MB, window=0, S=8, block=0):
    """The query-tiled grid against the dense reference, and the pool's blocks:
    every inserted row lands, every other element is bit-identical. Under a
    ``window`` the blocks behind each sequence's FIRST query of the step were
    released before it: holes in the table, NaN in the pool."""
    rng = np.random.default_rng(0)
    L, D = 2, 128
    cache0 = rng.normal(size=(L, 2, NB, kvh, bs, D)).astype(np.float32)
    cache0[:, :, 0] = 0.0  # block 0 is nobody's: where a hole's -1 clamps to
    # distinct blocks per sequence; the pool's LAST block belongs to nobody
    free = list(rng.permutation(np.arange(1, NB - 1)))
    table, tok, seq, cursor = _ragged_batch(seqs, T, S, MB, bs, free)
    q = rng.normal(size=(T, H, D)).astype(np.float32)
    k_new = rng.normal(size=(T, kvh, D)).astype(np.float32)
    v_new = rng.normal(size=(T, kvh, D)).astype(np.float32)

    exp_cache = cache0.copy()
    for t in range(cursor):
        bid = table[tok[0][t], tok[1][t] // bs]
        exp_cache[:, 0, bid, :, tok[1][t] % bs] = k_new[t]
        exp_cache[:, 1, bid, :, tok[1][t] % bs] = v_new[t]

    walked, pool = table, cache0
    if window:
        walked, pool = _release_passed(table, cache0, {s: seen for s, (seen, _) in enumerate(seqs)},
                                       window, bs)
    cache = jnp.asarray(pool)
    for li in range(L):
        got, cache = paged_attention_prefill(q, k_new, v_new, cache, li, walked, *seq,
                                             window=window, block=block)
        want = _dense_reference(q, exp_cache, li, table, *tok, window=window, block=block)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
        assert not np.any(np.asarray(got)[cursor:])  # padding rows are zero
    live = ~np.isnan(pool)
    np.testing.assert_array_equal(np.asarray(cache)[live], exp_cache[live])
    return q, k_new, v_new, exp_cache, table, seq


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
    # ---- one-token passes (lo == hi in the kernel) beside a chunk's pass ----
    # k = 1 at tile row 0 (and at position 0: its only key is its own), the chunk behind it
    "one-token-row-0": ([(0, 1), (5, 63)], 64),
    # k = 3 in the middle of the tile, padding behind them
    "three-one-token-rows-mid-tile": ([(12, 30), (40, 1), (3, 1), (60, 1)], 64),
    # k = 7 behind the chunk: rows 57..63, the tile's last row among them
    "seven-one-token-rows-to-row-63": ([(9, 57)] + [(11 * i + 2, 1) for i in range(7)], 64),
    # a chunk of 65: its last token falls alone into the next tile
    "chunk-end-alone-in-next-tile": ([(37, 65)], 128),
    # no chunk at all: every pass owns one token, each row must land in its block
    "only-one-token-rows": ([(17, 1), (0, 1), (33, 1), (63, 1), (64, 1)], 64),
}


@pytest.mark.parametrize("kvh", [4, 2])  # MHA and GQA
@pytest.mark.parametrize("batch", list(TILED_BATCHES))
def test_paged_attention_prefill_matches_dense(kvh, batch):
    """The query-tiled grid against the dense reference, and the pool's blocks:
    every inserted row lands, every other element is bit-identical."""
    seqs, T = TILED_BATCHES[batch]
    q, k_new, v_new, exp_cache, table, (seq_seen, _, last_tok) = _check_tile_grid(
        seqs, T, H=4, kvh=kvh, bs=16, NB=40, MB=8)

    # no live sequence: no output, no cache mutation
    out2, cache2 = paged_attention_prefill(q, k_new, v_new, jnp.asarray(exp_cache), 0, table,
                                           seq_seen, np.zeros(8, np.int32), last_tok)
    assert not np.any(np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(cache2), exp_cache)


# ---- sliding window: both grids, window 16 over 4-token blocks ---------------
WINDOW, WBS = 16, 4


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
    # one-token passes PAST the window (first visible block > 0, the entries
    # before it released), one at its edge, beside a chunk past it too
    "one-token-rows-past-the-window": ([(61, 1), (90, 1), (30, 57), (WINDOW, 1), (77, 1)], 64),
    # the chunk's last token alone in the next tile, past the window
    "chunk-end-alone-past-the-window": ([(40, 65), (120, 1)], 128),
}


@pytest.mark.parametrize("kvh", [4, 2])  # MHA and GQA
@pytest.mark.parametrize("batch", list(WINDOW_TILED_BATCHES))
def test_tile_grid_window_matches_dense_masked(kvh, batch):
    seqs, T = WINDOW_TILED_BATCHES[batch]
    _check_tile_grid(seqs, T, H=4, kvh=kvh, bs=WBS, NB=120, MB=64, window=WINDOW)


@pytest.mark.parametrize("window", [0, WINDOW], ids=["full", "window"])
@pytest.mark.parametrize("rep", [1, 4, 8])
def test_one_token_passes_for_every_group_size(rep, window):
    """``rep`` query heads a KV head: a one-token pass's rows a head are ``rep``
    (padded to a sublane tile in the kernel), whatever the tile's slab holds."""
    seqs = [(70, 1), (21, 40), (3, 1), (100, 1), (0, 1)]
    _check_tile_grid(seqs, 64, H=2 * rep, kvh=2, bs=WBS, NB=80, MB=32, window=window)


@pytest.mark.parametrize("grid", ["token-grid", "tile-chunk-and-decode-rows",
                                  "tile-one-token-rows"])
def test_five_queries_a_kv_head_on_both_grids(grid):
    """Falcon-H1's 20 query heads over 4 K/V heads (PR 47): FIVE queries a K/V
    head, the first group size served that is no power of two (a one-token
    pass pads 5 rows to a sublane tile; the per-token grid reshapes 20 heads to
    4 x 5)."""
    H, kvh = 20, 4
    if grid != "token-grid":
        seqs, T = {"tile-chunk-and-decode-rows": ([(3, 1), (20, 100), (33, 1), (0, 1)], 128),
                   "tile-one-token-rows": ([(17, 1), (0, 1), (33, 1), (63, 1), (64, 1)], 64)}[grid]
        _check_tile_grid(seqs, T, H=H, kvh=kvh, bs=16, NB=40, MB=8)
        return
    rng = np.random.default_rng(5)
    L, NB, bs, D, S, MB, T = 2, 12, 16, 128, 3, 4, 8
    cache0 = rng.normal(size=(L, 2, NB, kvh, bs, D)).astype(np.float32)
    table = rng.permutation(NB)[:S * MB].reshape(S, MB).astype(np.int32)
    token_seq = np.array([0, 1, 2] + [S] * 5, np.int32)
    token_pos = np.array([20, 7, 0] + [0] * 5, np.int32)
    token_valid = (np.arange(T) < 3).astype(np.int32)
    q = rng.normal(size=(T, H, D)).astype(np.float32)
    k_new = rng.normal(size=(T, kvh, D)).astype(np.float32)
    v_new = rng.normal(size=(T, kvh, D)).astype(np.float32)
    exp_cache = cache0.copy()
    for t in range(3):
        bid = table[token_seq[t], token_pos[t] // bs]
        exp_cache[:, 0, bid, :, token_pos[t] % bs] = k_new[t]
        exp_cache[:, 1, bid, :, token_pos[t] % bs] = v_new[t]
    cache = jnp.asarray(cache0)
    for li in range(L):
        got, cache = paged_attention_update(q, k_new, v_new, cache, li, jnp.asarray(table),
                                            token_seq, token_pos, token_valid)
        want = _dense_reference(q, jnp.asarray(exp_cache), li, table,
                                np.minimum(token_seq, S - 1), token_pos, token_valid)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(cache), exp_cache)


# a block mask (PR 50): every sequence's rows are whole blocks of 4 that start at a
# multiple of 4 in the batch and in the sequence
BLOCK_MASK_BATCHES = {
    # sixteen block steps: a block starts at EVERY multiple of 4 of the tile
    "a-block-at-every-multiple-of-4": ([(4 * ((7 * i) % 16), 4) for i in range(16)], 64, 16),
    # prompt chunks of whole blocks; the second sequence's 8 rows straddle the tile
    # boundary (rows 60..67): a block on each side, none across it
    "chunks-across-the-tile-boundary": ([(4, 60), (8, 8), (0, 44)], 128, 8),
    # a chunk crossing KV blocks of 16 beside block steps deep in their contexts
    "chunk-and-block-steps": ([(100, 4), (12, 40), (0, 4), (60, 4), (36, 12)], 64, 8),
}


@pytest.mark.parametrize("kvh", [4, 2])  # MHA and GQA
@pytest.mark.parametrize("batch", list(BLOCK_MASK_BATCHES))
def test_tile_grid_under_a_block_mask_matches_the_dense_mask(kvh, batch):
    """``block`` 4: a row sees every key up to its block's end — the later rows
    of its own block, inserted by the same pass, among them."""
    seqs, T, S = BLOCK_MASK_BATCHES[batch]
    _check_tile_grid(seqs, T, H=4, kvh=kvh, bs=16, NB=80, MB=8, S=S, block=4)


# the few-row arm (PR 51): a pass of no more rows than one block computes its own rows.
# (seen, new) per sequence, the bucket's tokens, the sequence slots; then the heads, the
# K/V heads and the table's width
FEW_ROW_BATCHES = {
    **{name: batch + (4, 4, 8) for name, batch in BLOCK_MASK_BATCHES.items()},
    # SDAR's block step: 32 sequences x one block over two tiles, eight queries a K/V
    # head; contexts on both sides of a chunk's 8 x 16 = 128 keys, so some passes find
    # their own block in chunk 0 and some in their last of two; two sequences start at
    # position 0
    "32-block-steps-over-two-tiles": ([(4 * ((11 * i) % 48), 4) for i in range(32)], 128, 32,
                                      32, 4, 16),
    # one tile: a prompt chunk of 24 rows (the many-row arm) between block steps, one of
    # them past a chunk's keys, and padding behind them; the second tile all padding
    "chunk-block-steps-and-padding": ([(40, 4), (8, 24), (0, 4), (132, 4)], 128, 8, 8, 2, 16),
}


@pytest.mark.parametrize("batch", list(FEW_ROW_BATCHES))
def test_few_row_passes_under_a_block_mask_match_the_dense_mask(batch):
    """Every pass of one block takes the few-row arm (``tiled_passes`` counts
    them by the kernel's rule); outputs against the dense mask, the pool bit
    for bit."""
    from deepspeed_tpu.ops.pallas.paged_attention import tiled_passes
    seqs, T, S, H, kvh, MB = FEW_ROW_BATCHES[batch]
    blocks = sum(-(-(seen + n) // 16) for seen, n in seqs)
    *_, (_, seq_ntok, last_tok) = _check_tile_grid(seqs, T, H=H, kvh=kvh, bs=16, NB=blocks + 8,
                                                   MB=MB, S=S, block=4)
    passes, one_token, few = tiled_passes(seq_ntok, last_tok, T, 4)
    assert (passes, one_token, few) == _passes_by_the_kernels_rule(seq_ntok, last_tok, T, 4)
    # a block step is one few-row pass; so is a chunk's one block alone in a tile
    assert one_token == 0 and few >= sum(n == 4 for _, n in seqs) and few > 0


@pytest.mark.parametrize("block", [8, 16])
def test_a_few_row_pass_of_a_wider_block_places_its_rows_with_one_select(block):
    """From a block of 8 on, a block is the aligned group of rows that holds it
    in the tile's state: block steps (one at position 0, one past a chunk's
    keys) around a chunk of two blocks (the many-row arm)."""
    from deepspeed_tpu.ops.pallas.paged_attention import tiled_passes
    seqs = [(2 * block, block), (0, block), (block, 2 * block), (9 * block, block)]
    *_, (_, seq_ntok, last_tok) = _check_tile_grid(seqs, 128, H=4, kvh=2, bs=16, NB=40, MB=16,
                                                   block=block)
    assert tiled_passes(seq_ntok, last_tok, 128, block) == (4, 0, 3)


def test_a_block_mask_changes_what_a_row_sees_and_eight_queries_a_kv_head_hold():
    """SDAR's 32 query heads over 4 K/V heads, eight a head; and the causal
    program on the same batch differs in every row but a block's last."""
    seqs = [(8, 4), (0, 24), (40, 4)]
    q, k_new, v_new, exp_cache, table, seq = _check_tile_grid(
        seqs, 64, H=32, kvh=4, bs=16, NB=40, MB=8, block=4)
    rng = np.random.default_rng(0)
    cache0 = rng.normal(size=exp_cache.shape).astype(np.float32)
    masked, _ = paged_attention_prefill(q, k_new, v_new, jnp.asarray(exp_cache), 0, table, *seq,
                                        block=4)
    causal, _ = paged_attention_prefill(q, k_new, v_new, jnp.asarray(exp_cache), 0, table, *seq)
    differs = np.abs(np.asarray(masked) - np.asarray(causal)).max(axis=(1, 2)) > 1e-3
    assert differs[:32].tolist() == [True, True, True, False] * 8 and cache0.shape


def test_the_token_grid_and_a_window_refuse_a_block_mask_by_name():
    args = (jnp.zeros((8, 4, 128)), jnp.zeros((8, 2, 128)), jnp.zeros((8, 2, 128)),
            jnp.zeros((1, 2, 4, 2, 16, 128)), 0, jnp.zeros((8, 4), jnp.int32))
    with pytest.raises(ValueError, match="per-token grid.* cannot serve a block mask"):
        paged_attention_update(*args, jnp.zeros(8, jnp.int32), jnp.zeros(8, jnp.int32),
                               jnp.ones(8, jnp.int32), block=4)
    tiled = (jnp.zeros((64, 4, 128)), jnp.zeros((64, 2, 128)), jnp.zeros((64, 2, 128))) + args[3:]
    meta = (jnp.zeros(8, jnp.int32), ) * 3
    for kw in ({"block": 6}, {"block": 128}, {"block": 4, "window": 32}):
        with pytest.raises(ValueError, match="a block mask of"):
            paged_attention_prefill(*tiled, *meta, **kw)


def _passes_by_the_kernels_rule(seq_ntok, last_tok, bucket_tokens, block=0, tq=64):
    """(sequence, tile) pairs, those with lo == hi, and those of no more rows
    than one block (the few-row arm's), as ``_tiled_kernel``'s ``sort`` decides
    them, one pair at a time."""
    passes = one_token = few = 0
    for n, last in zip(seq_ntok, last_tok):
        for t0 in range(0, bucket_tokens, tq):
            lo, hi = max(last - n + 1, t0), min(last, t0 + tq - 1)
            if n > 0 and lo <= hi:
                passes += 1
                one_token += lo == hi
                few += hi - lo < max(block, 1)
    return passes, one_token, few


@pytest.mark.parametrize("block", [0, 4])
@pytest.mark.parametrize("batch", list(TILED_BATCHES) + list(WINDOW_TILED_BATCHES)
                         + list(BLOCK_MASK_BATCHES))
def test_tiled_passes_counts_what_the_kernel_walks(batch, block):
    from deepspeed_tpu.ops.pallas.paged_attention import tiled_passes
    seqs, T, *slots = {**TILED_BATCHES, **WINDOW_TILED_BATCHES, **BLOCK_MASK_BATCHES}[batch]
    _, _, (_, seq_ntok, last_tok), _ = _ragged_batch(seqs, T, *(slots or [8]), 64, 4,
                                                     list(range(1000)))
    counted = tiled_passes(seq_ntok, last_tok, T, block)
    assert counted == _passes_by_the_kernels_rule(seq_ntok, last_tok, T, block)
    # without a block mask the few-row arm is the one-token passes'; a block only adds to it
    assert counted[2] >= counted[1] and (block or counted[2] == counted[1])
    assert tiled_passes(np.zeros(8, np.int32), last_tok[:8], T, block) == (0, 0, 0)


def test_tiled_passes_of_known_batches():
    from deepspeed_tpu.ops.pallas.paged_attention import tiled_passes

    def count(batch, block=0):
        seqs, T, *slots = batch
        _, _, (_, seq_ntok, last_tok), _ = _ragged_batch(seqs, T, *(slots or [8]), 64, 4,
                                                         list(range(1000)))
        return tiled_passes(seq_ntok, last_tok, T, block)

    assert count(TILED_BATCHES["chunk-plus-decode-rows"]) == (5, 3, 3)  # the chunk: tiles 0 and 1
    assert count(TILED_BATCHES["chunk-end-alone-in-next-tile"]) == (2, 1, 1)
    assert count(TILED_BATCHES["seven-one-token-rows-to-row-63"]) == (8, 7, 7)
    assert count(TILED_BATCHES["padding-tile"]) == (1, 0, 0)
    # under a block mask of 4: sixteen block steps, every pass the few-row arm's; a chunk's
    # passes are not, whatever they own of a tile (rows 60..63 of the second sequence's 8
    # are one block alone in tile 0: a few-row pass)
    assert count(BLOCK_MASK_BATCHES["a-block-at-every-multiple-of-4"], 4) == (16, 0, 16)
    assert count(BLOCK_MASK_BATCHES["chunks-across-the-tile-boundary"], 4) == (4, 0, 2)
    assert count(BLOCK_MASK_BATCHES["chunk-and-block-steps"], 4) == (5, 0, 3)
    assert count(BLOCK_MASK_BATCHES["chunk-and-block-steps"]) == (5, 0, 0)


# sha256 of str(jax.make_jaxpr(...)) (addresses blanked) of both grids at the
# shapes below, taken from the commit BEFORE the kernel had a window argument
# (d15f72e, jax 0.9.0): with window == 0 the traced program is that one.
# ``prefill`` was re-recorded in PR 42, whose one-token pass changed the tiled
# kernel's body with and without a window, and again in PR 51, whose one-token
# arm is the arm of every pass of no more rows than one block (at ``block`` 0 the
# one-token passes still: the same arithmetic, the selection and the placing
# written for a block's rows) and every pass starts its walk's first chunk under
# its insert (the same copies, started earlier); ``update`` is still d15f72e's.
_PRE_WINDOW_JAXPR = {
    "update": "eac6774739b3692404a729d6558959bb2d91e2e90a4447e8f1ae91da79a697f7",
    "prefill": "3fae050ac42b357558d0b4d4f23c839657184bc27b52405a311c7a058ff9a067",
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


@pytest.mark.parametrize("decode_rows", [1, 2])
def test_engine_mixed_put_kernel_vs_gather_path(decode_rows):
    """One ``put`` carrying ``decode_rows`` decode rows and another sequence's
    second chunk (``seq_seen`` > 0, 70 tokens: a bucket of 128 on the
    query-tiled grid): the kernel arm's logits are the gather arm's, and under
    a telemetry session the kernel arm's span counts the grid's passes (the
    decode rows one token each; the chunk the rest of tile 0 and tile 1)."""
    from deepspeed_tpu import telemetry
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
    firsts = [rng.integers(0, cfg.vocab_size, n) for n in (19, 33)[:decode_rows]]
    chunk_a, chunk_b = (rng.integers(0, cfg.vocab_size, n) for n in (40, 70))
    chunk_uid = decode_rows

    outs, args = {}, {}
    for kernel in (False, True):
        mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                              size=64), max_context=512)
        eng = build_engine(params, cfg, RaggedInferenceEngineConfig(
            state_manager=mgr, kv_block_size=16, use_paged_kernel=kernel))
        assert eng.model.attention_arm(128) == ("paged_tiled" if kernel else "xla_gather")
        nxt = [np.asarray([int(np.argmax(np.asarray(eng.put([uid], [first]))[0]))])
               for uid, first in enumerate(firsts)]
        eng.put([chunk_uid], [chunk_a])
        session = telemetry.configure({"enabled": True, "compile_watch": False})
        try:
            outs[kernel] = np.asarray(eng.put(list(range(decode_rows)) + [chunk_uid],
                                              nxt + [chunk_b]))
            (span, ) = [s for s in session.spans.export_since(0)["spans"]
                        if s["name"] == "put" and s["cat"] == "inference"]
            args[kernel] = span["args"]
        finally:
            telemetry.shutdown()
    np.testing.assert_allclose(outs[False], outs[True], rtol=3e-5, atol=3e-5)
    layers = cfg.num_hidden_layers
    assert args[True]["attention"] == "paged_tiled"
    assert args[True]["tiled_passes"] == (decode_rows + 2) * layers
    assert args[True]["tiled_one_token_passes"] == decode_rows * layers
    # no block mask: the few-row arm is the one-token passes'
    assert args[True]["tiled_few_row_passes"] == args[True]["tiled_one_token_passes"]
    assert "tiled_passes" not in args[False] and "tiled_one_token_passes" not in args[False]


def test_batch_counts_are_the_tiled_grids_alone():
    """``batch_counts`` on hand-built batches: the kernel's rule times the
    layers on the query-tiled arm, nothing on the per-token grid or the gather
    arm."""
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

    def model(kernel):
        mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                              size=64), max_context=512)
        return build_engine(params, cfg, RaggedInferenceEngineConfig(
            state_manager=mgr, kv_block_size=16, use_paged_kernel=kernel)).model

    def batch(seqs, T):
        _, _, seq, _ = _ragged_batch(seqs, T, 8, 32, 16, list(range(1000)))
        seq_meta = np.concatenate([np.stack(seq + (np.ones(8, np.int32), ), axis=1),
                                   np.zeros((8, 32), np.int32)], axis=1)
        return {"tok_meta": np.zeros((4, T), np.int32), "seq_meta": seq_meta}

    tiled, gather = model(True), model(False)
    for seqs, T in list(TILED_BATCHES.values()) + [([(5, 250)] + [(9 * i, 1) for i in range(6)],
                                                    256)]:
        passes, one_token, few = _passes_by_the_kernels_rule(
            *batch(seqs, T)["seq_meta"][:, 1:3].T, T)
        assert few == one_token <= passes and passes > 0
        assert tiled.batch_counts(batch(seqs, T)) == {
            "tiled_passes": passes * tiled.num_layers,
            "tiled_one_token_passes": one_token * tiled.num_layers,
            "tiled_few_row_passes": one_token * tiled.num_layers}
        assert gather.batch_counts(batch(seqs, T)) == {}
    # a decode bucket is the per-token grid's: nothing to count, a chunk of steps neither
    decode = batch([(7, 1), (30, 1)], 8)
    assert tiled.attention_arm(8) == "paged_token"
    assert tiled.batch_counts(decode) == {} and tiled.batch_counts(decode, 4) == {}


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
