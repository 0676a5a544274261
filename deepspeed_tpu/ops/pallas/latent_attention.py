"""Pallas paged attention over a LATENT cache, and the learned key selection
that goes with it (multi-head latent attention with an index of keys: the
DeepSeek-V3.2 family).

A token's cached state a layer is not a K/V pair of heads but two rows, both
addressed by the sequence's one block table:

- the **latent row** ``[c_kv (kv_lora_rank) | k_pe (qk_rope_head_dim) | 0]`` in
  the latent pool ``[layers, blocks, block_size, W]``. ``W`` is the row padded
  to whole 128-lane tiles (576 -> 640: what the device's tiling makes of a
  576-wide row anyway, here said aloud so that the contraction over a row is
  whole tiles). Every head reads the same row: the queries come ABSORBED
  (``q_nope W_UK``, ``[heads, kv_lora_rank]`` beside ``q_pe``), a key's logit
  is one dot product with its row, and the value is the row's first
  ``kv_lora_rank`` lanes (``W_UV`` is applied to the heads' outputs);
- the **index key** (``index_head_dim`` wide) in the index pool
  ``[layers, blocks, block_size, D_I]``.

Both kernels run one grid, over QUERY TILES of ``tq`` consecutive tokens of
the ragged batch, and inside a tile one pass per sequence that has tokens in it
(``seq_seen`` / ``seq_ntok`` / ``last_tok``: the scalar prefetch of
``paged_attention_prefill``); a pass walks the sequence's block table in
double-buffered chunks. ``tq`` = 1 for the decode buckets (a tile is one token:
its heads are the MXU's rows); for prefill and mixed buckets
(:func:`tile_tokens`) attention's tile is as many tokens as give ``TILE_ROWS``
rows = tokens x heads whatever the head count, and the geometry of a PASS
follows what the pass owns: several tokens of the tile, the tile's rows
against two blocks of keys an iteration (its matmuls bind it); ONE token (a
decode row riding beside a chunk: half of a mixed step's passes), that token's
``heads`` rows alone against eight blocks an iteration, as the per-token grid
walks (the copies' latency binds it), and its walk handed on to the next such
pass of the tile. The new rows are in the pools already: the caller scatters
them (one ``[tokens, W]`` update a pool, in place).

``latent_index_scores``: ``I(t, s) = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``
for every key s <= t of t's sequence, float32 ``[tokens, max_blocks x
block_size]`` by key POSITION, ``NEG_INF`` elsewhere.

``kth_largest``: the exact k-th largest of each row, by bisection over the
float's bits (32 counting passes, no sort): the selection keeps
``I >= kth_largest(I, index_topk)``.

``latent_paged_attention``: softmax over the keys whose score reaches the
row's threshold (all causal keys without scores), online over the chunks. What
the mask leaves out is still read: the walk is over the context's blocks, and
the count a roofline is held to is the selected rows'.

Everywhere but the TPU the three are ``jax.numpy`` (``*_xla``): what the
kernels are tested against, in interpret mode, and what the CPU serves with.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import paged_attention

NEG_INF = -1e30
TOKEN_GRID_MAX = 32  # largest bucket the per-token grid takes
TILE_ROWS = 1024  # MXU rows (tokens x heads) of attention's tile on the tiled grid ...
TILE_TOKENS_MIN = 8  # ... and the fewest tokens a tile holds (the index kernel's tile)
# blocks fetched a loop iteration: a one-token pass's walk is bound by the DMAs'
# latency (eight in flight), a many-token pass's by its matmuls (two)
ONE_TOKEN_BLOCKS, TILE_BLOCKS = 8, 2
LANES = 128


def padded_width(width):
    """A row's width in whole lane tiles."""
    return -(-width // LANES) * LANES


def tile_tokens(bucket_tokens, heads=None):
    """Tokens of a query tile: 1 on the per-token grid; on the tiled grid the
    floor, or for a call that says its ``heads`` (attention's) the power of two
    that gives ``TILE_ROWS`` rows (32 tokens of 32 heads, 16 of 64, 8 of 128
    and more), cut to one that divides the bucket."""
    if bucket_tokens <= TOKEN_GRID_MAX:
        return 1
    if heads is None:
        return TILE_TOKENS_MIN
    tq = max(TILE_TOKENS_MIN, 1 << (max(TILE_ROWS // heads, 1).bit_length() - 1))
    while bucket_tokens % tq:
        tq //= 2
    return tq


def _chunk_blocks(one_token, max_blocks):
    return min(ONE_TOKEN_BLOCKS if one_token else TILE_BLOCKS, max_blocks)


def tiled_passes(seq_ntok, last_tok, bucket_tokens, heads):
    """The (sequence, tile) pairs attention's tiled call works through and those
    of them that own ONE token of their tile (the deep walk), from the host's
    copy of the scalar prefetch: ``_attn_kernel``'s rule is the paged kernel's,
    at this grid's tile."""
    return paged_attention.tiled_passes(seq_ntok, last_tok, bucket_tokens,
                                        tile=tile_tokens(bucket_tokens, heads))[:2]


def _each(n, fn):
    jax.lax.fori_loop(0, n, lambda i, carry: fn(i), None)


def _for_each_pass(S, tq, t0, seen_ref, ntok_ref, last_ref, fn):
    """``fn(s, lo, hi, shift)`` for every sequence s with tokens ``lo..hi`` of
    the batch inside the tile that starts at token ``t0``; token t sits at
    position t + shift."""

    def sequence(s):
        n, last = ntok_ref[s], last_ref[s]
        first = last - n + 1
        lo, hi = jnp.maximum(first, t0), jnp.minimum(last, t0 + tq - 1)

        @pl.when((n > 0) & (lo <= hi))
        def _():
            fn(s, lo, hi, seen_ref[s] - first)

    _each(S, sequence)


def _walk(table_ref, pool_ref, li, s, MB, bs, chunk, nblocks, buf, sems, body, chain=None):
    """``body(c, slot)`` for each chunk c of ``chunk`` blocks of sequence s's
    first ``nblocks`` blocks, block j of the chunk in rows ``j*bs..`` of
    ``buf[slot]``. A block past the last is the last again (its keys are past
    every query: masked by position).

    ``chain`` = ``(state, follows, s_next, nblocks_next)`` hands the walk on from
    one one-token pass of a tile to the next: ``state`` (SMEM ``[2]``) says
    whether this walk's first chunk is in flight already and in which slot,
    and where a one-token pass of sequence ``s_next`` ``follows`` in the tile,
    its first chunk is started behind this walk's last (a pass neither starts
    its walk cold nor ends on a chunk with no copy behind it)."""
    nchunks = pl.cdiv(nblocks, chunk)

    def copies(s, nblocks, c, slot):
        out = []
        for j in range(chunk):
            b = jnp.minimum(c * chunk + j, nblocks - 1)
            bid = jnp.maximum(table_ref[s, jnp.minimum(b, MB - 1)], 0)
            out.append(pltpu.make_async_copy(pool_ref.at[li, bid],
                                             buf.at[slot, pl.ds(j * bs, bs)], sems.at[slot, j]))
        return out

    def start(*chunk_of):
        for cp in copies(*chunk_of):
            cp.start()

    if chain is None:
        first = 0
        start(s, nblocks, 0, 0)
    else:
        state, follows, s_next, nblocks_next = chain
        in_flight = state[0] == 1
        first = jnp.where(in_flight, state[1], 0)
        pl.when(jnp.logical_not(in_flight))(lambda: start(s, nblocks, 0, 0))

    def one(c):
        slot = jax.lax.rem(first + c, 2)
        pl.when(c + 1 < nchunks)(lambda: start(s, nblocks, c + 1, 1 - slot))
        if chain is not None:
            pl.when((c + 1 == nchunks) & follows)(
                lambda: start(s_next, nblocks_next, 0, 1 - slot))
        for cp in copies(s, nblocks, c, slot):
            cp.wait()
        body(c, slot)

    _each(nchunks, one)
    if chain is not None:
        state[0] = follows.astype(jnp.int32)
        state[1] = jax.lax.rem(first + nchunks, 2)  # the slot behind the last chunk's


def _row_positions(tq, t0, lo, hi, shift):
    """``[tq, 1]``: the position of each token of the tile that this pass
    owns, -1 for the others (they see no key)."""
    tok = t0 + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    return jnp.where((tok >= lo) & (tok <= hi), tok + shift, -1)


# ------------------------------------------------------------ index scores --
def _index_kernel(S, MB, bs, tq, NH, chunk, precision,
                  layer_ref, table_ref, seen_ref, ntok_ref, last_ref,
                  q_ref, w_ref, pool_ref, out_ref, k_buf, sems):
    li = layer_ref[0]
    t0 = pl.program_id(0) * tq  # read here: not inside a loop's or a branch's body
    out_ref[...] = jnp.full(out_ref.shape, NEG_INF, out_ref.dtype)
    q = q_ref[0]  # [NH * tq, D], row j * tq + t: token t under index head j
    w = w_ref[0]  # [NH * tq, 1] float32

    def one_pass(s, lo, hi, shift):
        q_pos = _row_positions(tq, t0, lo, hi, shift)
        nblocks = jnp.minimum((hi + shift) // bs + 1, MB)

        def body(c, slot):
            logits = jax.lax.dot_general(q, k_buf[slot], (((1, ), (1, )), ((), ())),
                                         preferred_element_type=jnp.float32,
                                         precision=precision)  # [NH * tq, chunk * bs]
            vals = jnp.maximum(logits, 0.0) * w
            vals = vals.reshape(NH, tq, chunk * bs).sum(axis=0) if tq > 1 \
                else vals.sum(axis=0, keepdims=True)
            kv_pos = c * (chunk * bs) + jax.lax.broadcasted_iota(jnp.int32, (1, chunk * bs), 1)
            cols = pl.ds(pl.multiple_of(c * (chunk * bs), chunk * bs), chunk * bs)
            out_ref[0, :, cols] = jnp.where(kv_pos <= q_pos, vals, out_ref[0, :, cols])

        _walk(table_ref, pool_ref, li, s, MB, bs, chunk, nblocks, k_buf, sems, body)

    _for_each_pass(S, tq, t0, seen_ref, ntok_ref, last_ref, one_pass)


def _head_major(x, tq):
    """``[T, H, D]`` -> ``[T / tq, H * tq, D]``, row ``h * tq + t`` of a tile."""
    T, H, D = x.shape
    return x.reshape(T // tq, tq, H, D).transpose(0, 2, 1, 3).reshape(T // tq, H * tq, D)


def _meta(layer_idx, block_table, seq_seen, seq_ntok, last_tok):
    return (jnp.asarray(layer_idx, jnp.int32).reshape(1), block_table.astype(jnp.int32),
            seq_seen.astype(jnp.int32), seq_ntok.astype(jnp.int32), last_tok.astype(jnp.int32))


def _precision(dtype):
    # float32 operands (tests, a float32 pool) must not pass through bf16 on the MXU
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


@functools.partial(jax.jit, static_argnames=("interpret", ))
def latent_index_scores(q_index, weights, index_pool, layer_idx, block_table, seq_seen,
                        seq_ntok, last_tok, interpret=None):
    """q_index: ``[T, NH, D]``; weights: ``[T, NH]`` float32 (the scales folded
    in); index_pool: ``[L, NB, bs, D]``; the sequences as
    ``paged_attention_prefill`` takes them. Returns float32 ``[T, MB * bs]``:
    row t's score of the key at each POSITION of its sequence up to its own,
    ``NEG_INF`` past it and in the rows of no sequence."""
    T, NH, D = q_index.shape
    _, _, bs, Dc = index_pool.shape
    S, MB = block_table.shape
    tq = tile_tokens(T)
    assert D == Dc and T % tq == 0
    chunk = _chunk_blocks(tq == 1, MB)
    assert MB % chunk == 0
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    op_dtype = index_pool.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(T // tq, ),
        in_specs=[pl.BlockSpec((1, NH * tq, D), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec((1, NH * tq, 1), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tq, MB * bs), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, chunk * bs, D), op_dtype),
                        pltpu.SemaphoreType.DMA((2, chunk))])
    kernel = functools.partial(_index_kernel, S, MB, bs, tq, NH, chunk, _precision(op_dtype))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T // tq, tq, MB * bs), jnp.float32),
        interpret=interpret, name="latent_index_scores",
    )(*_meta(layer_idx, block_table, seq_seen, seq_ntok, last_tok),
      _head_major(q_index.astype(op_dtype), tq),
      _head_major(weights.astype(jnp.float32)[:, :, None], tq), index_pool)
    return out.reshape(T, MB * bs)


# ------------------------------------------------------------ the selection --
def kth_largest(scores, k):
    """The k-th largest value of each row of float32 ``scores`` ``[T, K]``
    (k <= K), exactly: the float's bits are mapped to unsigned integers in the
    floats' order and the answer is built bit by bit, each bit one count of the
    row's entries at or above a candidate. 32 passes over the array and no
    sort; ``scores >= kth_largest(scores, k)`` keeps k entries of a row (more
    only where the k-th ties with the next)."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    # negative floats order backwards by their bits: flip all but the sign
    ordered = jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)
    keys = jax.lax.bitcast_convert_type(ordered, jnp.uint32) ^ jnp.uint32(0x80000000)

    def bit(i, found):
        candidate = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (keys >= candidate[:, None]).sum(axis=1) >= k
        return jnp.where(enough, candidate, found)

    found = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:1], jnp.uint32))
    ordered = jax.lax.bitcast_convert_type(found ^ jnp.uint32(0x80000000), jnp.int32)
    bits = jnp.where(ordered < 0, ordered ^ jnp.int32(0x7fffffff), ordered)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


# --------------------------------------------------------------- attention --
def _attn_kernel(S, MB, bs, tq, H, C, chunk, deep, selected, precision,
                 layer_ref, table_ref, seen_ref, ntok_ref, last_ref, *refs):
    """Queries, softmax state and output are ``[tq, H, .]``: a token is an index
    of the leading (untiled) dimension, its heads the sublanes. A pass that owns
    ONE token of the tile (a decode row riding beside a chunk: half of a mixed
    step's passes) computes that token's ``H`` rows alone over ``deep`` blocks
    an iteration, its walk handed on to a one-token pass that follows it in the
    tile (``_walk``'s ``chain``; ``state`` carries it); a pass that owns several
    computes the tile's ``tq x H`` rows under the tokens' masks over ``chunk``
    blocks an iteration (the first of the buffer's ``deep``)."""
    if selected:
        q_ref, scores_ref, thr_ref, pool_ref, out_ref, m_s, l_s, acc_s, k_buf, sems, state = refs
    else:
        q_ref, pool_ref, out_ref, m_s, l_s, acc_s, k_buf, sems, state = refs
    li = layer_ref[0]
    t0 = pl.program_id(0) * tq  # read here: not inside a loop's or a branch's body
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    state[0] = 0  # no walk is handed on from another tile

    def attend(n, at, q_pos, nblocks, s, blocks, chain=None):
        """Online softmax of ``n`` tokens' rows (``at``: their index in the
        tile's leading dimension) over sequence s's first ``nblocks`` blocks,
        ``blocks`` of them an iteration; ``q_pos`` ``[n, 1, 1]``: their
        positions, -1 for a token of another pass (it sees no key); ``chain``:
        ``_walk``'s."""
        width = blocks * bs
        q = q_ref[0, at].reshape(n * H, q_ref.shape[-1])  # the softmax scale folded in

        def body(c, slot):
            rows = k_buf[slot, pl.ds(0, width)]  # [width, W]
            logits = jax.lax.dot_general(q, rows, (((1, ), (1, )), ((), ())),
                                         preferred_element_type=jnp.float32,
                                         precision=precision).reshape(n, H, width)
            kv_pos = c * width + jax.lax.broadcasted_iota(jnp.int32, (1, 1, width), 2)
            mask = kv_pos <= q_pos  # [n, 1, width]
            if selected:
                cols = pl.ds(pl.multiple_of(c * width, width), width)
                mask &= scores_ref[0, at, :, cols].reshape(n, 1, width) >= \
                    thr_ref[0, at].reshape(n, 1, 1)
            # masked logits sit BELOW the running max's floor, so their exp is 0
            # even for a row that has seen no key yet
            logits = jnp.where(mask, logits, 2 * NEG_INF)
            m_prev = m_s[at].reshape(n, H, 1)
            m_new = jnp.maximum(m_prev, logits.max(axis=2, keepdims=True))
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_s[at] = (l_s[at].reshape(n, H, 1) * alpha
                       + p.sum(axis=2, keepdims=True)).reshape(l_s[at].shape)
            pv = jnp.dot(p.reshape(n * H, width).astype(rows.dtype), rows[:, :C],
                         preferred_element_type=jnp.float32, precision=precision)
            acc_s[at] = (acc_s[at].reshape(n, H, C) * alpha
                         + pv.reshape(n, H, C)).reshape(acc_s[at].shape)
            m_s[at] = m_new.reshape(m_s[at].shape)

        _walk(table_ref, pool_ref, li, s, MB, bs, blocks, nblocks, k_buf, sems, body, chain)

    def one_pass(s, lo, hi, shift):
        nblocks = jnp.minimum((hi + shift) // bs + 1, MB)
        if tq == 1:
            attend(1, slice(None), jnp.full((1, 1, 1), lo + shift, jnp.int32), nblocks, s, deep)
            return

        @pl.when(lo == hi)
        def _():
            # a decode row of the NEXT sequence in this tile: its walk is handed on
            s_next = jnp.minimum(s + 1, S - 1)
            at_next = last_ref[s_next]
            follows = (s + 1 < S) & (ntok_ref[s_next] == 1) & (at_next >= t0) & (at_next < t0 + tq)
            attend(1, lo - t0, jnp.full((1, 1, 1), lo + shift, jnp.int32), nblocks, s, deep,
                   (state, follows, s_next, jnp.minimum(seen_ref[s_next] // bs + 1, MB)))

        @pl.when(lo != hi)
        def _():
            tok = t0 + jax.lax.broadcasted_iota(jnp.int32, (tq, 1, 1), 0)
            attend(tq, slice(None), jnp.where((tok >= lo) & (tok <= hi), tok + shift, -1),
                   nblocks, s, chunk)

    _for_each_pass(S, tq, t0, seen_ref, ntok_ref, last_ref, one_pass)
    out_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-20)).astype(out_ref.dtype)


def attention_vmem_bytes(tq, H, W, C, bs, chunk, deep, itemsize, selected_keys=0):
    """VMEM attention's call holds: the pipelined query and output tiles (and a
    selection's scores) twice, the softmax state (a per-row scalar pads to 128
    lanes), the double-buffered rows of the deeper walk, and the widest
    iteration's values (a many-token pass's logits and weights in float32, the
    weights as operands, their product with the rows; a one-token pass's over
    the deeper walk)."""
    rows = tq * H
    step = max(rows * chunk, H * deep) * bs * (4 + 4 + itemsize) + rows * C * 4
    return (2 * rows * (W + C) * itemsize + 2 * tq * (selected_keys + LANES) * 4
            + rows * (C + 2 * LANES) * 4 + 2 * deep * bs * W * itemsize + step)


@functools.partial(jax.jit, static_argnames=("value_width", "interpret"))
def latent_paged_attention(q, latent_pool, layer_idx, block_table, seq_seen, seq_ntok, last_tok,
                           scores=None, threshold=None, *, value_width, interpret=None):
    """q: ``[T, H, W]`` absorbed queries, the softmax scale folded in, zero in
    the lanes the rows pad; latent_pool: ``[L, NB, bs, W]``; ``scores`` /
    ``threshold``: :func:`latent_index_scores`'s ``[T, MB * bs]`` and the
    per-row least selected score ``[T]``, or neither (every causal key).
    Returns ``[T, H, value_width]``: the softmax-weighted sum of the rows'
    first ``value_width`` lanes; rows of no sequence are zero."""
    T, H, W = q.shape
    _, _, bs, Wc = latent_pool.shape
    S, MB = block_table.shape
    tq = tile_tokens(T, H)
    assert W == Wc and T % tq == 0
    chunk, deep = _chunk_blocks(tq == 1, MB), _chunk_blocks(True, MB)
    assert MB % deep == 0
    selected = scores is not None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    op_dtype = latent_pool.dtype
    in_specs = [pl.BlockSpec((1, tq, H, W), lambda i, *_: (i, 0, 0, 0))]
    operands = [q.astype(op_dtype).reshape(T // tq, tq, H, W)]
    if selected:
        in_specs += [pl.BlockSpec((1, tq, 1, MB * bs), lambda i, *_: (i, 0, 0, 0)),
                     pl.BlockSpec((1, tq, 1, 1), lambda i, *_: (i, 0, 0, 0))]
        operands += [scores.reshape(T // tq, tq, 1, MB * bs),
                     threshold.astype(jnp.float32).reshape(T // tq, tq, 1, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(T // tq, ),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tq, H, value_width), lambda i, *_: (i, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((tq, H, 1), jnp.float32),
                        pltpu.VMEM((tq, H, 1), jnp.float32),
                        pltpu.VMEM((tq, H, value_width), jnp.float32),
                        pltpu.VMEM((2, deep * bs, W), op_dtype),
                        pltpu.SemaphoreType.DMA((2, deep)),
                        pltpu.SMEM((2, ), jnp.int32)])
    kernel = functools.partial(_attn_kernel, S, MB, bs, tq, H, value_width, chunk, deep,
                               selected, _precision(op_dtype))
    held = attention_vmem_bytes(tq, H, W, value_width, bs, chunk, deep, op_dtype.itemsize,
                                MB * bs if selected else 0)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T // tq, tq, H, value_width), q.dtype),
        interpret=interpret,
        name="latent_paged_attention_token" if tq == 1 else "latent_paged_attention_tiled",
        **paged_attention.vmem_params(held),
    )(*_meta(layer_idx, block_table, seq_seen, seq_ntok, last_tok), *operands, latent_pool)
    return out.reshape(T, H, value_width)


# ------------------------------------------------- the same in jax.numpy ----
def _gathered(pool, layer_idx, block_table, token_seq):
    """Each token's sequence's rows by position: ``[T, MB * bs, width]``."""
    S, MB = block_table.shape
    rows = pool[layer_idx][jnp.maximum(block_table, 0)]  # [S, MB, bs, width]
    return rows.reshape(S, MB * rows.shape[2], rows.shape[3])[token_seq]


def _causal(token_pos, token_valid, n_keys):
    return (jnp.arange(n_keys)[None, :] <= token_pos[:, None]) & token_valid[:, None]


def latent_index_scores_xla(q_index, weights, index_pool, layer_idx, block_table, token_seq,
                            token_pos, token_valid):
    keys = _gathered(index_pool, layer_idx, block_table, token_seq)  # [T, K, D]
    logits = jnp.einsum("tjd,tkd->tjk", q_index.astype(keys.dtype), keys,
                        preferred_element_type=jnp.float32, precision=_precision(keys.dtype))
    scores = (jnp.maximum(logits, 0.0) * weights.astype(jnp.float32)[:, :, None]).sum(axis=1)
    return jnp.where(_causal(token_pos, token_valid, keys.shape[1]), scores, NEG_INF)


def latent_paged_attention_xla(q, latent_pool, layer_idx, block_table, token_seq, token_pos,
                               token_valid, scores=None, threshold=None, *, value_width):
    rows = _gathered(latent_pool, layer_idx, block_table, token_seq)  # [T, K, W]
    precision = _precision(rows.dtype)
    logits = jnp.einsum("thw,tkw->thk", q.astype(rows.dtype), rows,
                        preferred_element_type=jnp.float32, precision=precision)
    mask = _causal(token_pos, token_valid, rows.shape[1])
    if scores is not None:
        mask &= scores >= threshold[:, None]
    probs = jax.nn.softmax(jnp.where(mask[:, None, :], logits, NEG_INF), axis=-1)
    probs = jnp.where(mask[:, None, :], probs, 0.0)  # a row of no sequence: zero, not uniform
    out = jnp.einsum("thk,tkc->thc", probs.astype(rows.dtype), rows[:, :, :value_width],
                     preferred_element_type=jnp.float32, precision=precision)
    return out.astype(q.dtype)
