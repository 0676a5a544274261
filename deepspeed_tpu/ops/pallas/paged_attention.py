"""Pallas paged (blocked) attention over the ragged KV cache.

Reference role: ``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash/
blocked_flash.cpp:101`` + ``blocked_kv_rotary.cu:385`` (the KV insert) —
attention that walks each sequence's block table instead of densifying
history, so cost scales with *live* tokens, not the padded table width or the
pool's size (VERDICT r2 weak #4).

TPU design, one fused kernel per layer, its grid chosen from the bucket:

- the paged cache is ALIASED in/out of the kernel (``input_output_aliases``)
  and updated in place — an XLA-side scatter and block-table gather make the
  compiler relayout the whole donated pool at the program's entry and exit and
  copy one layer's K or V plane per gather (PERF.md §6, PR 24: 41 % of a
  256-token step's device time on a 1.6 GB pool);
- the block table is walked in CHUNKS of 8 blocks — 16 outstanding async DMAs
  double-buffered against the previous chunk's online-softmax update. Padding
  has zero blocks and skips everything; HBM traffic is the live KV bytes,
  never the bucket ceiling.

``paged_attention_update`` — decode buckets (≤ 32 tokens): grid over TOKENS,
sequentially executed. Program t first DMAs its own new K/V row into its
sequence's block (one full-block read-modify-write), then walks the table; a
chunk's 8 ``[KVH, bs, D]`` tiles form a 128-lane ``[KVH, rep, 8*bs]`` logits
tile — one VPU-native softmax step per chunk.

``paged_attention_prefill`` — prefill and mixed buckets (> 32 tokens): grid
over QUERY TILES of ``TQ`` consecutive tokens of the ragged batch, and inside a
tile one pass per SEQUENCE that has tokens in it (a sequence's tokens are
contiguous and in position order: ``ragged_wrapper.insert_sequence``; which
rows are whose is computed in the kernel from ``seq_seen`` / ``seq_ntok`` /
``last_tok``, the scalar prefetch). A pass inserts its rows into their blocks
in place (at most ``TQ / bs + 1`` block read-modify-writes, the rows moved to
their offsets by a 0/1 selection matmul), then walks the sequence's table
ONCE; the walk's first chunk is fetched UNDER the insert, all but the blocks
the insert rewrites, which follow when they have landed. A pass pays for the
rows it owns:

- one of MORE rows than one block of the mask (than one token without a block
  mask: a prompt chunk) computes per KV head a ``[rep·TQ, 8·bs]`` logits tile on
  the MXU, operands in the wider of the queries' and the pool's dtype, float32
  accumulation, the mask from positions; rows of other sequences see no key;
- one of NO MORE rows than one block — a block step's block under a block
  mask, a decode row riding beside a chunk, a chunk's last token alone in the
  next tile — picks its ``rep`` rows a KV head for each row of the block out
  of the slab and walks the same chunks with a ``[KVH, rep·block, 8·bs]`` logits
  tile (the per-token grid's at ``block`` 0); the rows of one block see the same
  keys, so one mask serves them. It then puts its softmax state into its rows
  of the tile's. At SDAR's shape (32 block steps a call, four KV heads) such a
  pass costs ~6.7 us where it cost ~19 as a tile's 512 rows a KV head (PERF.md
  section 6, PR 51); what is left above its K/V bytes' ~2.6 us is the insert's
  own chain between two passes (fetch a block, wait, merge, write, wait): a
  pass's insert does not yet run under the previous pass's walk.

A tile with no tokens writes zeros and touches nothing.

Sliding window (``window`` > 0, static; 0 = full causal and the program it
always was): both grids mask ``kv_pos > q_pos - window`` AND start their walk
at the first block that holds a visible key — for a tile pass, visible to its
FIRST query — so a sequence's K/V traffic is bounded by ``window`` + the pass's
queries (+ the last chunk's padding), not by its context. Table entries before
that block are never read: the pool releases those blocks as the window passes
them (``transformer_base.maybe_free_kv``) and leaves -1 there.

Block mask (``block`` > 0, static, a power of two that divides ``TQ``; 0 =
causal and the program it always was): a model that generates by diffusion over
blocks lets every position of a block see the whole block, so a query at
``q_pos`` sees the keys up to its block's END, ``q_pos | (block - 1)``. Only the
TILE grid takes it: a pass inserts its rows before it walks, and the caller
feeds whole blocks that start at multiples of ``block`` in the batch
(``ragged/ragged_wrapper.py`` checks both), so a block never straddles two
passes and the walk's last block is the one it always was. The token grid
inserts row t and attends before row t + 1 is in the pool: it refuses.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
CHUNK = 8  # KV blocks fetched per loop iteration
TQ = 64  # query rows of a tile (tiled mode); every bucket over 32 tokens is a multiple
TOKEN_GRID_MAX = 32  # largest bucket the per-token grid takes


def _stage_blocks(bs):
    """Blocks that TQ consecutive positions can touch."""
    return 1 + -(-(TQ - 1) // bs)


def _few_rows(rep, block, itemsize):
    """Query rows a KV head of a few-row pass: ``rep`` for each row of a block
    (of the one token without a block mask), padded to a sublane tile of the
    operands' dtype."""
    tile = 8 * (4 // itemsize)
    return -(-rep * max(block, 1) // tile) * tile


def tile_grid_vmem_bytes(H, KVH, D, bs, itemsize=2, block=0):
    """VMEM a query-tiled call holds: the double-buffered K/V chunks, the
    insert's staging blocks, the grouped queries, the softmax state (a per-row
    scalar pads to 128 lanes), a few-row pass's queries and state, and the
    pipelined q / out / new-K/V blocks."""
    kv_block = KVH * bs * D * itemsize
    tile = H * TQ * D
    few = KVH * _few_rows(H // KVH, block, itemsize)
    return ((2 * 2 * CHUNK + 2 * _stage_blocks(bs)) * kv_block + tile * itemsize
            + tile * 4 + 2 * H * TQ * 128 * 4 + few * (D * itemsize + D * 4 + 128 * 4)
            + 2 * 2 * (tile + KVH * TQ * D) * itemsize)


# VMEM the compiler grants a kernel that asks for nothing, and the most a kernel
# here asks for (a v5e core has 128 MiB)
SCOPED_VMEM_BYTES = 16 * 2**20
VMEM_CEILING_BYTES = 48 * 2**20


def vmem_params(held: int) -> dict:
    """``pallas_call``'s keywords for a kernel that holds ``held`` bytes of
    VMEM: none while a quarter of the compiler's own scoped limit is to spare
    (every configuration up to 32 query heads of 128: their programs lower as
    they always did), else a limit of its own with a quarter to spare (64 query
    heads over 8 K/V heads of 128 hold 20.7 MiB on the tile grid)."""
    if held <= SCOPED_VMEM_BYTES * 3 // 4:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(held * 5 // 4, VMEM_CEILING_BYTES))}


def tiled_passes(seq_ntok, last_tok, bucket_tokens, block=0, tile=TQ):
    """The (sequence, tile) pairs a query-tiled call works through, those that
    own ONE token of their tile, and those that take the few-row arm (no more
    rows than one block of ``block``; the one-token ones without a block mask),
    by ``_tiled_kernel``'s rule, from the host's copy of the scalar prefetch
    (numpy ``[S]``); ``tile``: the tile's tokens, for a grid with another."""
    n, last = np.asarray(seq_ntok)[:, None], np.asarray(last_tok)[:, None]
    t0 = np.arange(0, bucket_tokens, tile)[None, :]
    lo, hi = np.maximum(last - n + 1, t0), np.minimum(last, t0 + tile - 1)
    owned = (n > 0) & (lo <= hi)
    return (int(owned.sum()), int((owned & (lo == hi)).sum()),
            int((owned & (hi - lo < max(block, 1))).sum()))


def _first_visible_block(pos, window, bs):
    """The block that holds the oldest key a query at ``pos`` sees under a
    sliding ``window`` (> 0): keys ``pos - window + 1 .. pos``."""
    return jnp.maximum(pos - window + 1, 0) // bs


def _kernel(li, S, MB, bs, rep, scale, window,
            # scalar prefetch
            table_ref, seq_ref, pos_ref, valid_ref,
            # inputs
            q_ref, kn_ref, vn_ref, cache_ref,
            # outputs
            out_ref, cache_out_ref,
            # scratch
            k_buf, v_buf, kv_stage, sems, wsem):
    t = pl.program_id(0)
    seq = jnp.minimum(seq_ref[t], S - 1)
    pos = pos_ref[t]
    valid = valid_ref[t] > 0
    nblocks = jnp.where(valid, jnp.minimum(pos // bs + 1, MB), 0)
    if window:
        # the walk starts at the first block with a visible key: blocks wholly
        # behind the window are never fetched (the pool may have released them)
        b_first = _first_visible_block(pos, window, bs)
        nblocks = jnp.maximum(nblocks - b_first, 0)
    nchunks = pl.cdiv(nblocks, CHUNK)

    KVH, _, D = k_buf.shape[2:]
    q = q_ref[0].reshape(KVH, rep, D).astype(jnp.float32) * scale

    # ---- insert this token's K/V into its block (reference blocked_kv_rotary).
    # Full-block read-modify-write: Mosaic only DMAs contiguous tiles, and one
    # [KVH, bs, D] block round-trip per token is noise next to the table walk.
    own_bid = jnp.maximum(table_ref[seq, jnp.minimum(pos // bs, MB - 1)], 0)
    off = pos % bs

    @pl.when(valid)
    def _():
        ck = pltpu.make_async_copy(cache_out_ref.at[li, 0, own_bid], kv_stage.at[0],
                                   wsem.at[0])
        cv = pltpu.make_async_copy(cache_out_ref.at[li, 1, own_bid], kv_stage.at[1],
                                   wsem.at[1])
        ck.start()
        cv.start()
        ck.wait()
        cv.wait()
        # masked whole-block select: dynamic sublane stores need 8-alignment
        # Mosaic can't prove, a lane-wise where needs nothing
        row = jax.lax.broadcasted_iota(jnp.int32, (KVH, bs, 1), 1)
        kv_stage[0] = jnp.where(row == off, kn_ref[0][:, None, :], kv_stage[0])
        kv_stage[1] = jnp.where(row == off, vn_ref[0][:, None, :], kv_stage[1])
        wk = pltpu.make_async_copy(kv_stage.at[0], cache_out_ref.at[li, 0, own_bid],
                                   wsem.at[0])
        wv = pltpu.make_async_copy(kv_stage.at[1], cache_out_ref.at[li, 1, own_bid],
                                   wsem.at[1])
        wk.start()
        wv.start()
        wk.wait()
        wv.wait()

    # ---- walk the block table, double-buffered chunks ------------------------
    def chunk_copies(c, slot):
        copies = []
        for j in range(CHUNK):
            b = c * CHUNK + j
            if window:
                b = b + b_first
            b = jnp.minimum(b, MB - 1)
            bid = jnp.maximum(table_ref[seq, b], 0)
            copies.append(pltpu.make_async_copy(cache_out_ref.at[li, 0, bid],
                                                k_buf.at[slot, j], sems.at[0, slot, j]))
            copies.append(pltpu.make_async_copy(cache_out_ref.at[li, 1, bid],
                                                v_buf.at[slot, j], sems.at[1, slot, j]))
        return copies

    @pl.when(nchunks > 0)
    def _():
        for cp in chunk_copies(0, 0):
            cp.start()

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nchunks)
        def _():
            for cp in chunk_copies(c + 1, jax.lax.rem(c + 1, 2)):
                cp.start()

        for cp in chunk_copies(c, slot):
            cp.wait()
        logit_parts = []
        v_parts = []
        for j in range(CHUNK):
            k = k_buf[slot, j].astype(jnp.float32)  # [KVH, bs, D]
            logit_parts.append(jax.lax.dot_general(
                q, k, (((2, ), (2, )), ((0, ), (0, ))),
                preferred_element_type=jnp.float32))  # [KVH, rep, bs]
            v_parts.append(v_buf[slot, j].astype(jnp.float32))
        logits = jnp.concatenate(logit_parts, axis=-1)       # [KVH, rep, CHUNK*bs]
        v = jnp.concatenate(v_parts, axis=1)                 # [KVH, CHUNK*bs, D]

        kv_pos = c * (CHUNK * bs) + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, CHUNK * bs), 2)
        if window:
            kv_pos = kv_pos + b_first * bs
        mask = kv_pos <= pos
        if window:
            mask &= kv_pos > pos - window
        logits = jnp.where(mask, logits, NEG_INF)

        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(logits - m_new[..., None]), 0.0)
        l_new = l * alpha + p.sum(axis=-1)
        pv = jax.lax.dot_general(p, v, (((2, ), (1, )), ((0, ), (0, ))),
                                 preferred_element_type=jnp.float32)  # [KVH, rep, D]
        return m_new, l_new, acc * alpha[..., None] + pv

    m0 = jnp.full((KVH, rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((KVH, rep), jnp.float32)
    acc0 = jnp.zeros((KVH, rep, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nchunks, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out = jnp.where(valid, out, 0.0)
    out_ref[0] = out.reshape(1, KVH * rep, D).astype(out_ref.dtype)[0]


@functools.partial(jax.jit, static_argnames=("layer_idx", "interpret", "window", "block"),
                   donate_argnums=(3, ))
def paged_attention_update(q, k_new, v_new, cache, layer_idx, block_table, token_seq,
                           token_pos, token_valid, interpret=None, window=0, block=0):
    """Fused KV-insert + blocked attention for one layer.

    q: [T, H, D]; k_new/v_new: [T, KVH, D]; cache: [L, 2, NB, KVH, bs, D]
    (donated; updated in place). ``window`` > 0: a token at position p sees
    keys ``p - window + 1 .. p`` only, and table entries of blocks wholly
    before them are never read. ``block`` must be 0: this grid attends row t
    before row t + 1 is in the pool. Returns (attn_out [T, H, D], cache)."""
    if block:
        raise ValueError(
            f"paged_attention_update (the per-token grid) cannot serve a block mask (block="
            f"{block}): it inserts a row and attends before the later rows of its block are in "
            f"the pool; a model with a block mask takes the tile grid "
            f"(paged_attention_prefill) at every bucket")
    T, H, D = q.shape
    L, _, NB, KVH, bs, Dc = cache.shape
    assert D == Dc and H % KVH == 0
    S, MB = block_table.shape
    rep = H // KVH
    scale = 1.0 / (D**0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(T, ),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((1, KVH, D), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((1, KVH, D), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # cache in HBM, aliased in/out
        ],
        out_specs=[
            pl.BlockSpec((1, H, D), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, CHUNK, KVH, bs, D), cache.dtype),
            pltpu.VMEM((2, CHUNK, KVH, bs, D), cache.dtype),
            pltpu.VMEM((2, KVH, bs, D), cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2, CHUNK)),
            pltpu.SemaphoreType.DMA((2, )),
        ],
    )
    kernel = functools.partial(_kernel, layer_idx, S, MB, bs, rep, scale, int(window))
    out, new_cache = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, H, D), q.dtype),
                   jax.ShapeDtypeStruct(cache.shape, cache.dtype)],
        input_output_aliases={7: 1},  # cache operand (after 4 scalar-prefetch args)
        interpret=interpret,
        name="paged_attention_update",
    )(block_table.astype(jnp.int32), token_seq.astype(jnp.int32),
      token_pos.astype(jnp.int32), token_valid.astype(jnp.int32),
      q, k_new.astype(cache.dtype), v_new.astype(cache.dtype), cache)
    return out, new_cache


def _tiled_kernel(S, MB, bs, rep, scale, precision, window, block,
                  # scalar prefetch
                  layer_ref, table_ref, seen_ref, ntok_ref, last_ref,
                  # inputs
                  q_ref, kn_ref, vn_ref, cache_ref,
                  # outputs
                  out_ref, cache_out_ref,
                  # scratch
                  q_s, m_s, l_s, acc_s, q1_s, l1_s, acc1_s, k_buf, v_buf, kv_stage, sems, wsem):
    # Loops over heads and blocks are ``fori_loop``s, not Python loops: one
    # kernel is traced and lowered for every bucket's program at every start
    # of the server, and an unrolled body costs that 8 x over. (A few-row
    # pass takes its KV heads as the batch of one ``dot_general``, as the
    # per-token grid does: its chains of a few vregs a head must overlap.)
    t0 = pl.program_id(0) * TQ
    li = layer_ref[0]
    KVH, D = k_buf.shape[1], k_buf.shape[3]
    dtype = k_buf.dtype  # the pool's
    op_dtype = q_s.dtype  # the matmuls' operands: the wider of the queries' and the pool's
    B = max(block, 1)  # the rows a few-row pass owns, at most

    def each(n, fn):
        jax.lax.fori_loop(0, n, lambda i, carry: fn(i), None)

    def head_rows(h):
        """Head h's TQ rows of its KV group's ``[rep*TQ, ·]`` slab, and its lanes
        of a token-major ``[TQ, H*D]`` block."""
        return (h // rep, pl.ds(pl.multiple_of((h % rep) * TQ, TQ), TQ)), \
            pl.ds(pl.multiple_of(h * D, D), D)

    def group_queries(h):
        # row r*TQ + t of q_s[g] is token t under head g*rep + r
        slab, lanes = head_rows(h)
        q_s[slab] = q_ref[:, lanes]

    each(KVH * rep, group_queries)
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    row_tok = t0 + jnp.bitwise_and(
        jax.lax.broadcasted_iota(jnp.int32, (rep * TQ, 1), 0), TQ - 1)
    tile_tok = t0 + jax.lax.broadcasted_iota(jnp.int32, (1, TQ), 1)

    def one_sequence(s, lo, hi, shift):
        """Tokens lo..hi of the batch (inside this tile) belong to sequence
        ``s``; token t sits at position t + shift."""
        p_lo, p_hi = lo + shift, hi + shift
        b0 = p_lo // bs
        b1 = jnp.minimum(p_hi // bs, MB - 1)

        def block_id(b):
            return jnp.maximum(table_ref[s, jnp.minimum(b, MB - 1)], 0)

        # ---- insert the rows into their blocks, in place ---------------------
        def stage_copy(j, kv, write):
            block, stage = cache_out_ref.at[li, kv, block_id(b0 + j)], kv_stage.at[j, kv]
            src, dst = (stage, block) if write else (block, stage)
            return pltpu.make_async_copy(src, dst, wsem.at[j, kv])

        n_touched = b1 - b0 + 1  # <= kv_stage.shape[0]
        tok_pos = jnp.where((tile_tok >= lo) & (tile_tok <= hi), tile_tok + shift, -1)

        def fetch(j):
            stage_copy(j, 0, False).start()
            stage_copy(j, 1, False).start()

        def merge(j):
            stage_copy(j, 0, False).wait()
            stage_copy(j, 1, False).wait()
            slot_pos = (b0 + j) * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, TQ), 0)
            # 0/1 selection: block row <- the tile row at that position (a dynamic
            # sublane shift Mosaic cannot prove aligned, done exactly on the MXU)
            sel = (slot_pos == tok_pos).astype(dtype)
            written = (slot_pos[:, :1] >= p_lo) & (slot_pos[:, :1] <= p_hi)

            def head(g):
                lanes = pl.ds(pl.multiple_of(g * D, D), D)
                for kv, new_ref in enumerate((kn_ref, vn_ref)):
                    rows = jnp.dot(sel, new_ref[:, lanes], preferred_element_type=jnp.float32,
                                   precision=precision).astype(dtype)
                    kv_stage[j, kv, g] = jnp.where(written, rows, kv_stage[j, kv, g])

            each(KVH, head)
            stage_copy(j, 0, True).start()
            stage_copy(j, 1, True).start()

        def landed(j):
            stage_copy(j, 0, True).wait()
            stage_copy(j, 1, True).wait()

        # ---- walk the block table once, double-buffered chunks --------------
        if window:
            # from the first block that holds a key the pass's FIRST query
            # sees: blocks wholly behind the window are never fetched (the
            # pool may have released them)
            b_first = _first_visible_block(p_lo, window, bs)
            nchunks = pl.cdiv(b1 + 1 - b_first, CHUNK)
        else:
            b_first = 0
            nchunks = pl.cdiv(b1 + 1, CHUNK)

        def chunk_copies(c, slot, j):
            bid = block_id(c * CHUNK + j + b_first)
            rows = pl.ds(pl.multiple_of(j * bs, bs), bs)
            return (pltpu.make_async_copy(cache_out_ref.at[li, 0, bid], k_buf.at[slot, :, rows],
                                          sems.at[0, slot, j]),
                    pltpu.make_async_copy(cache_out_ref.at[li, 1, bid], v_buf.at[slot, :, rows],
                                          sems.at[1, slot, j]))

        def start_chunk(c, slot, rewritten=None):
            """Start chunk c's fetches: all of them, or (chunk 0) those of the
            blocks the insert rewrites / of the others."""
            def start(j):
                def go():
                    for cp in chunk_copies(c, slot, j):
                        cp.start()
                if rewritten is None:
                    go()
                else:
                    b = c * CHUNK + j + b_first
                    pl.when(((b >= b0) & (b <= b1)) == rewritten)(go)
            each(CHUNK, start)

        # the walk's first chunk is fetched UNDER the insert, but for the blocks
        # the insert rewrites (its own: the last of the walk, so in chunk 0 only
        # under a chunk's keys), which follow once they have landed
        each(n_touched, fetch)
        start_chunk(0, 0, rewritten=False)
        each(n_touched, merge)
        each(n_touched, landed)
        start_chunk(0, 0, rewritten=True)

        def wait_chunk(c, slot):
            def wait(j):
                for cp in chunk_copies(c, slot, j):
                    cp.wait()
            each(CHUNK, wait)

        def walk(attend, state=None):
            """``attend(slot, visible, state) -> state`` over the chunks in
            turn; ``visible(q_pos)``: the chunk's keys a query at ``q_pos``
            (any shape that broadcasts against ``[..., CHUNK*bs]``) sees. Chunk 0
            is on its way."""
            def chunk(c, state):
                slot = jax.lax.rem(c, 2)

                @pl.when(c + 1 < nchunks)
                def _():
                    start_chunk(c + 1, 1 - slot)

                wait_chunk(c, slot)

                def visible(q_pos):
                    kv_pos = c * (CHUNK * bs) + jax.lax.broadcasted_iota(
                        jnp.int32, (1, ) * (q_pos.ndim - 1) + (CHUNK * bs, ), q_pos.ndim - 1)
                    if window:
                        kv_pos = kv_pos + b_first * bs
                    if block:  # up to the END of the query's block (-1 stays -1)
                        q_pos = jnp.bitwise_or(q_pos, block - 1)
                    mask = kv_pos <= q_pos
                    if window:
                        mask &= kv_pos > q_pos - window
                    return mask

                return attend(slot, visible, state)

            return jax.lax.fori_loop(0, nchunks, chunk, state)

        @pl.when(hi - lo >= B)
        def _():
            # more rows than one block: the tile's rows under the pass's mask;
            # a row of another sequence (or of padding) sees no key
            q_pos = jnp.where((row_tok >= lo) & (row_tok <= hi), row_tok + shift, -1)

            def attend(slot, visible, _):
                mask = visible(q_pos)  # [rep*TQ, CHUNK*bs]

                def head(g):
                    logits = jax.lax.dot_general(
                        q_s[g], k_buf[slot, g].astype(op_dtype), (((1, ), (1, )), ((), ())),
                        preferred_element_type=jnp.float32, precision=precision) * scale
                    # masked logits sit BELOW the running max's floor, so their exp
                    # is 0 even for a row that has seen no key yet
                    logits = jnp.where(mask, logits, 2 * NEG_INF)
                    m_prev = m_s[g]
                    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
                    p = jnp.exp(logits - m_new)
                    alpha = jnp.exp(m_prev - m_new)
                    l_s[g] = l_s[g] * alpha + p.sum(axis=1, keepdims=True)
                    acc_s[g] = acc_s[g] * alpha + jnp.dot(
                        p.astype(op_dtype), v_buf[slot, g].astype(op_dtype),
                        preferred_element_type=jnp.float32, precision=precision)
                    m_s[g] = m_new

                each(KVH, head)

            walk(attend)

        @pl.when(hi - lo < B)
        def _():
            # no more rows than ONE block (a block step under a block mask; one
            # token without one: a decode row beside a chunk, a chunk's end
            # alone in the next tile): the pass's ``rep`` rows a KV head for
            # each row of the block, not the slab
            t = lo - t0
            R = q1_s.shape[1]  # rep * B, padded to a sublane tile; the rows past are zero
            r = jax.lax.broadcasted_iota(jnp.int32, (R, rep * TQ), 0)
            slab_row = jax.lax.broadcasted_iota(jnp.int32, (R, rep * TQ), 1)
            # row r of the pass: head r // B of the KV group, the pass's row r % B
            r_head, r_row = jnp.right_shift(r, B.bit_length() - 1), jnp.bitwise_and(r, B - 1)
            # 0/1 selection of the pass's rows out of the slab, exact on the MXU
            sel = ((slab_row == r_head * TQ + t + r_row) & (r_head < rep)
                   & (r_row <= hi - lo)).astype(op_dtype)

            def pick(g):
                q1_s[g] = jnp.dot(sel, q_s[g], preferred_element_type=jnp.float32,
                                  precision=precision).astype(op_dtype)

            each(KVH, pick)
            q1 = q1_s[...]
            # the rows of one block see the same keys: up to the block's end
            pos = jnp.full((1, 1, 1), p_lo, jnp.int32)

            def attend(slot, visible, state):
                m_prev, l, acc = state
                logits = jax.lax.dot_general(
                    q1, k_buf[slot].astype(op_dtype), (((2, ), (2, )), ((0, ), (0, ))),
                    preferred_element_type=jnp.float32, precision=precision) * scale
                logits = jnp.where(visible(pos), logits, 2 * NEG_INF)  # [KVH, R, CHUNK*bs]
                m_new = jnp.maximum(m_prev, logits.max(axis=2, keepdims=True))
                p = jnp.exp(logits - m_new)
                alpha = jnp.exp(m_prev - m_new)
                pv = jax.lax.dot_general(
                    p.astype(op_dtype), v_buf[slot].astype(op_dtype),
                    (((2, ), (1, )), ((0, ), (0, ))),
                    preferred_element_type=jnp.float32, precision=precision)
                return m_new, l * alpha + p.sum(axis=2, keepdims=True), acc * alpha + pv

            _, l1_s[...], acc1_s[...] = walk(attend, (
                jnp.full((KVH, R, 1), NEG_INF, jnp.float32), jnp.zeros((KVH, R, 1), jnp.float32),
                jnp.zeros((KVH, R, D), jnp.float32)))

            # the pass's rows of the tile's state (a row belongs to ONE pass:
            # nothing was there), by a select on the aligned group of rows
            # that holds them
            G = max(B, 8)
            at = t - jax.lax.rem(t, G)
            in_group = jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)

            def place(h):
                g, r0 = h // rep, (h % rep) * B
                group = (g, pl.ds(pl.multiple_of((h % rep) * TQ + at, 8), G))
                l, acc = l_s[group], acc_s[group]
                if B >= 8:  # a block starts at a multiple of B: it IS the group
                    hit, rows = in_group <= hi - lo, pl.ds(pl.multiple_of(r0, 8), B)
                    l, acc = jnp.where(hit, l1_s[g, rows], l), jnp.where(hit, acc1_s[g, rows], acc)
                else:  # up to four rows of a group of 8, one by one
                    for j in range(B):
                        hit = in_group == jnp.where(j <= hi - lo, t - at + j, -1)
                        l = jnp.where(hit, l1_s[g, pl.ds(r0 + j, 1)], l)
                        acc = jnp.where(hit, acc1_s[g, pl.ds(r0 + j, 1)], acc)
                l_s[group], acc_s[group] = l, acc

            each(KVH * rep, place)

    def sequence(s):
        n, last = ntok_ref[s], last_ref[s]
        first = last - n + 1
        lo, hi = jnp.maximum(first, t0), jnp.minimum(last, t0 + TQ - 1)

        @pl.when((n > 0) & (lo <= hi))
        def _():
            one_sequence(s, lo, hi, seen_ref[s] - first)

    each(S, sequence)

    def write_out(h):
        slab, lanes = head_rows(h)
        out_ref[:, lanes] = (acc_s[slab] / jnp.maximum(l_s[slab], 1e-20)).astype(out_ref.dtype)

    each(KVH * rep, write_out)


@functools.partial(jax.jit, static_argnames=("interpret", "window", "block"),
                   donate_argnums=(3, ))
def paged_attention_prefill(q, k_new, v_new, cache, layer_idx, block_table, seq_seen,
                            seq_ntok, last_tok, interpret=None, window=0, block=0):
    """Fused KV-insert + blocked attention for one layer, query-tiled: for the
    buckets of more than ``TOKEN_GRID_MAX`` tokens (a multiple of ``TQ``).

    q: [T, H, D]; k_new/v_new: [T, KVH, D]; cache: [L, 2, NB, KVH, bs, D]
    (donated; updated in place); block_table: [S, MB]; seq_seen / seq_ntok /
    last_tok: [S] — sequence s holds tokens ``last_tok - seq_ntok + 1 ..
    last_tok`` of the batch at positions ``seq_seen ..``; a slot with
    ``seq_ntok <= 0`` is empty. ``layer_idx`` is an operand, not a constant:
    a model's layers share ONE kernel in the compiled program. ``window`` as
    in :func:`paged_attention_update`; a pass walks from the first block its
    first query sees. A (sequence, tile) pass that owns no more rows than one
    block (one token where ``block`` is 0) computes those rows' ``H`` alone;
    one that owns more, the tile's ``H * TQ`` under the tokens' masks
    (:func:`tiled_passes` counts them on the host). ``block`` > 0: a query
    sees the keys up to its block's end; every sequence's rows are then whole
    blocks that start at a multiple of ``block`` in the batch and in the
    sequence (the caller's to hold). Returns
    (attn_out [T, H, D], cache); rows of no sequence are zero."""
    T, H, D = q.shape
    L, _, NB, KVH, bs, Dc = cache.shape
    assert D == Dc and H % KVH == 0 and T % TQ == 0
    if block and (block & (block - 1) or TQ % block or window):
        raise ValueError(f"a block mask of {block} positions: a power of two that divides the "
                         f"tile's {TQ} rows, and no sliding window ({window}) beside it")
    S, MB = block_table.shape
    rep = H // KVH
    scale = 1.0 / (D**0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    op_dtype = jnp.promote_types(q.dtype, cache.dtype)
    # float32 operands (tests, a float32 pool) must not pass through bf16 on the MXU
    precision = jax.lax.Precision.HIGHEST if op_dtype == jnp.float32 else None
    n_stage = _stage_blocks(bs)
    few_rows = _few_rows(rep, block, jnp.dtype(op_dtype).itemsize)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(T // TQ, ),
        in_specs=[
            pl.BlockSpec((TQ, H * D), lambda i, *_: (i, 0)),
            pl.BlockSpec((TQ, KVH * D), lambda i, *_: (i, 0)),
            pl.BlockSpec((TQ, KVH * D), lambda i, *_: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # cache in HBM, aliased in/out
        ],
        out_specs=[
            pl.BlockSpec((TQ, H * D), lambda i, *_: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((KVH, rep * TQ, D), op_dtype),           # head-grouped queries
            pltpu.VMEM((KVH, rep * TQ, 1), jnp.float32),        # running max
            pltpu.VMEM((KVH, rep * TQ, 1), jnp.float32),        # running sum
            pltpu.VMEM((KVH, rep * TQ, D), jnp.float32),        # accumulator
            pltpu.VMEM((KVH, few_rows, D), op_dtype),           # a few-row pass: its queries,
            pltpu.VMEM((KVH, few_rows, 1), jnp.float32),        # running sum
            pltpu.VMEM((KVH, few_rows, D), jnp.float32),        # and accumulator
            pltpu.VMEM((2, KVH, CHUNK * bs, D), cache.dtype),
            pltpu.VMEM((2, KVH, CHUNK * bs, D), cache.dtype),
            pltpu.VMEM((n_stage, 2, KVH, bs, D), cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2, CHUNK)),
            pltpu.SemaphoreType.DMA((n_stage, 2)),
        ],
    )
    kernel = functools.partial(_tiled_kernel, S, MB, bs, rep, scale, precision, int(window),
                               int(block))
    out, new_cache = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, H * D), q.dtype),
                   jax.ShapeDtypeStruct(cache.shape, cache.dtype)],
        input_output_aliases={8: 1},  # cache operand (after 5 scalar-prefetch args)
        interpret=interpret,
        name="paged_attention_prefill",
        **vmem_params(tile_grid_vmem_bytes(H, KVH, D, bs, jnp.dtype(op_dtype).itemsize, block)),
    )(jnp.asarray(layer_idx, jnp.int32).reshape(1), block_table.astype(jnp.int32),
      seq_seen.astype(jnp.int32), seq_ntok.astype(jnp.int32), last_tok.astype(jnp.int32),
      q.astype(op_dtype).reshape(T, H * D), k_new.astype(cache.dtype).reshape(T, KVH * D),
      v_new.astype(cache.dtype).reshape(T, KVH * D), cache)
    return out.reshape(T, H, D), new_cache
