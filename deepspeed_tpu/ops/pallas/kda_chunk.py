"""Pallas chunked form of the gated delta rule (KDA) over the per-sequence
state pool, in place: a ``put`` step's visits of one delta-rule layer in ONE
call (``modules/kda.py:scan_in_place``; the mathematics is ``kda.chunk``'s,
which tier-1 holds this kernel to).

A VISIT is a segment of more than one row against one chunk of ``Q`` rows of
the flat batch it has rows in; the step's visits come in the segments' order
(``kda.visits_of``), a table of scalars a visit (slot, chunk, the segment's
rows ``[lo, hi)`` of the chunk, whether it is the segment's first or last
visit and whether the sequence has seen anything: scalar prefetch). The grid is
``(tiles of heads, visits)``, the visits inside: a tile ``[tile, d_k, d_v]`` of
a segment's state is copied from its slot into vector memory on the segment's
first visit (while the visit before it is computed; zeros where nothing was
seen, whatever the slot held), stays there through the segment's visits, and
is copied back to the SAME slot after its last, while the next segment's tile
is on its way in. The pool stays in HBM, ALIASED in and out, and nothing else
of it is touched. The rows of a chunk (q, k, v, the log-decay and beta, as the
mixer makes them: ``[T, heads, 128]``) come in by block, a chunk's block once
for the visits that share it; a visit's outputs are zeros outside its own
rows, so the visits that share a chunk of the batch add up in the one output
block (by head, ``[heads, T, d_v]``). A chunk no visit reaches is never
written: the caller reads its own rows only.

A visit, the tile's heads side by side (``S`` is ``[d_k, d_v]``)::

    G    = cumsum(g)                       a log-step scan, float32
    U    = [k exp(G); q exp(G)] S_0        what the state holds / answers
    rhs  = beta (v - U_k)
    [A; P][I, J < I] = [beta k; q]_I exp(G_I - ref_I) . (k exp(ref_I - G))_J
    per column a of the diagonal blocks, every sub-chunk I at once, in order:
      kk, qk [s] = sum_d [beta_s k_s; q_s] k_r exp(G_s - G_r)   r = row a of I(s)
      Z[s] -= kk[s] Z[r] ;  N[s] -= kk[s] N[r]     s under r (N starts as A's
                                                   blocks under the diagonal's)
    W_I  = Z_I - N[I, J < I] W_J           sub-chunk by sub-chunk
    o    = U_q + P W
    S_C  = diag(exp(G_C)) S_0 + (k exp(G_C - G))^T W

Every exponent is a difference that is at most 0 (``modules/kda.py`` says why
no factorised form is allowed): a pair of rows of one sub-chunk of ``SUB`` rows
takes the pairwise form, a pair of two sub-chunks factors through ``ref_I``
(``G`` as the later sub-chunk starts). The unit-triangular system is solved by
substitution, elementwise in float32, a COLUMN a step (row a of each diagonal
block is final when its column is applied: ``kda.chunk``'s substitution a row,
its sums in another order) and on the right-hand side itself, so no ``[C, C]``
inverse is made; the sub-chunks are then coupled in order, as ``kda.chunk``
does. The products that read or make the state run as three bf16 passes
written out (XLA's ``HIGH``, which Mosaic does not lower); the coupling at
``HIGHEST``.

Two layouts. The column loop works BY ROW, ``[rows, heads, lanes]``: a row is a
leading index (row a of a sub-chunk is a dynamic index that costs nothing and
broadcasts over its sub-chunk's rows as whole vregs) and the tile's eight
heads are a vreg's sublanes; its rows are kept first-halves-then-second-halves
of the sub-chunks, so the last eight columns touch the second halves alone.
The products work BY HEAD, ``[heads, rows, lanes]``; the two are a transpose
of the leading axes apart. The layer's ordinal and every count are operands: a
program's layers share ONE traced and lowered kernel.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import kda_step

LANES = kda_step.LANES
SUB = 16  # rows a sub-chunk (``modules/kda.py:SUB``)
BUFFERS = 3  # state tiles in VMEM: the segment computed, the next on its way in, the last out
# a visit's flags
FIRST, LAST, STARTED, NEW_CHUNK = 1, 2, 4, 8

_f32, _bf16 = jnp.float32, jnp.bfloat16


def supported(H, dk, dv, rows):
    """The shape rule, the same on every backend: ``kda_step``'s heads (128 x
    128) and a chunk of whole sub-chunks (so of whole sublane tiles)."""
    tile = kda_step.tiling(H, dk, dv)  # a row block holds the tile's heads down its sublanes
    return kda_step.supported(H, dk, dv) and rows % SUB == 0 and (tile % 8 == 0 or tile == H)


def max_visits(T, S, rows):
    """The most visits a step of ``T`` rows in ``S`` segments makes: a chunk is
    visited once and once more for every segment that starts inside it."""
    return T // rows + S - 1


def _bdot(a, b, contract, passes=3):
    """A product a head (the leading axis of both), ``contract`` the two
    contracted axes, float32 in and out: three bf16 passes written out (hi x
    hi + hi x lo + lo x hi: XLA's ``HIGH``, which Mosaic does not lower), or
    the six of ``HIGHEST``."""
    dims = (contract, ((0, ), (0, )))
    if passes == 6:
        return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=_f32)

    def split(x):
        hi = x.astype(_bf16)
        return hi, (x - hi.astype(_f32)).astype(_bf16)

    def dot(x, y):
        return jax.lax.dot_general(x, y, dims, preferred_element_type=_f32)

    (ah, al), (bh, bl) = split(a), split(b)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _turn(x):
    """By row ``[rows, heads, lanes]`` to by head ``[heads, rows, lanes]``, or back."""
    return jnp.transpose(x, (1, 0, 2))


def _by_half(x):
    """Rows in the column loop's order: every sub-chunk's first eight rows,
    then every sub-chunk's last eight (a leading axis: whole vregs move)."""
    subs = x.shape[0] // SUB
    return jnp.concatenate([x[i * SUB + h * 8:i * SUB + h * 8 + 8]
                            for h in range(2) for i in range(subs)], axis=0)


def _by_row(x):
    """:func:`_by_half` undone."""
    subs = x.shape[0] // SUB
    return jnp.concatenate([x[(h * subs + i) * 8:(h * subs + i) * 8 + 8]
                            for i in range(subs) for h in range(2)], axis=0)


def _kernel(HT, V,
            # scalar prefetch
            meta_ref, slot_ref, chunk_ref, lo_ref, hi_ref, flag_ref, ord_ref,
            # inputs
            q_ref, k_ref, v_ref, g_ref, beta_ref, pool_ref,
            # outputs
            o_ref, pool_out_ref,
            # scratch
            state, in_sem, out_sem, pending, gk, kbs, qs, wn, ps, po, wh):
    j, n = pl.program_id(0), pl.program_id(1)
    mi, count, nseg = meta_ref[0], meta_ref[1], meta_ref[2]
    ht = state.shape[1]
    Q = qs.shape[0]
    subs = Q // SUB

    def buffer_of(tile, visit):
        return jax.lax.rem(tile * nseg + ord_ref[visit], BUFFERS)

    def fetch(tile, visit, buf):
        return pltpu.make_async_copy(
            pool_out_ref.at[mi, slot_ref[visit], pl.ds(tile * ht, ht)], state.at[buf],
            in_sem.at[buf])

    def store(tile, visit, buf):
        return pltpu.make_async_copy(
            state.at[buf], pool_out_ref.at[mi, slot_ref[visit], pl.ds(tile * ht, ht)],
            out_sem.at[buf])

    def settle(buf):  # the store that last left ``state[buf]``, if it is still out
        @pl.when(pending[buf] > 0)
        def _():
            store(0, 0, buf).wait()  # a wait needs the copy's size, not its place
            pending[buf] = 0

    def make_room(tile, visit):
        """Before visit ``visit`` of tile ``tile``, where it opens a segment:
        its buffer's last store settled, and its tile on the way in if the
        sequence has seen anything."""
        flags = flag_ref[visit]

        @pl.when((flags & FIRST) > 0)
        def _():
            buf = buffer_of(tile, visit)
            settle(buf)

            @pl.when((flags & STARTED) > 0)
            def _():
                fetch(tile, visit, buf).start()

    @pl.when((j == 0) & (n == 0))
    def _():
        for buf in range(BUFFERS):
            pending[buf] = 0

        @pl.when(count > 0)
        def _():
            make_room(0, 0)

    flags = flag_ref[n]

    @pl.when(n < count)
    def _():
        # the visit after this one: the next of this tile, or the next tile's first
        wraps = n + 1 >= count
        tile2, visit2 = jnp.where(wraps, j + 1, j), jnp.where(wraps, 0, n + 1)

        @pl.when(tile2 < HT)
        def _():
            make_room(tile2, visit2)

        buf = buffer_of(j, n)

        @pl.when((flags & (FIRST | STARTED)) == (FIRST | STARTED))
        def _():
            fetch(j, n, buf).wait()

        # zeros where nothing was seen, whatever the buffer held
        held = ((flags & STARTED) > 0) | ((flags & FIRST) == 0)
        S0 = jnp.where(held, state[buf], 0.0)
        # by ROW [rows, heads, lanes]: a row is a leading index, the tile's heads its sublanes
        row = jax.lax.broadcasted_iota(jnp.int32, (Q, 1, 1), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, LANES), 2)
        mine = (row >= lo_ref[n]) & (row < hi_ref[n])
        q, k, v, g = (jnp.where(mine, ref[...], 0.0) for ref in (q_ref, k_ref, v_ref, g_ref))
        # beta comes with the layer's heads along the lanes: this tile's down the sublanes
        head = jax.lax.broadcasted_iota(jnp.int32, (1, ht, 1), 1) + j * ht
        wide = jax.lax.broadcasted_iota(jnp.int32, (1, 1, beta_ref.shape[2]), 2)
        beta = jnp.sum(jnp.where(wide == head, beta_ref[...], 0.0), axis=2, keepdims=True)
        beta = jnp.where(mine, beta, 0.0)
        G, shift = g, 1
        while shift < Q:  # the inclusive sums down the rows, a log-step scan
            G = G + jnp.concatenate([jnp.zeros((shift, ht, LANES), _f32), G[:-shift]], axis=0)
            shift *= 2
        kb = k * beta
        gk[:, :, :LANES], gk[:, :, LANES:] = _by_half(G), _by_half(k)
        kbs[...], qs[...] = _by_half(kb), _by_half(q)
        # by HEAD [heads, rows, lanes] for the products
        Gh, kh, kbh, qh = (_turn(a) for a in (G, k, kb, q))
        Gamma = jnp.exp(Gh)
        U = _bdot(jnp.concatenate([kh * Gamma, qh * Gamma], axis=1), S0, ((2, ), (1, )))
        # the right-hand side, W when solved | A's blocks under the diagonal's
        wn[:, :, :LANES] = _by_half(beta * (v - _turn(U[:, :Q])))
        wn[:, :, LANES:] = jnp.zeros((Q, ht, LANES), _f32)
        ps[...] = jnp.zeros((Q, ht, LANES), _f32)  # the queries' pairs inside a sub-chunk, [s, r]
        po[...] = jnp.zeros((ht, Q, LANES), _f32)  # and against the earlier sub-chunks, by head
        for i in range(1, subs):  # sub-chunk i's rows against the EARLIER sub-chunks', through ref
            at = slice(i * SUB, (i + 1) * SUB)
            ref = Gh[:, i * SUB - 1:i * SUB]
            mid = jnp.exp(Gh[:, at] - ref)
            left = jnp.concatenate([kbh[:, at] * mid, qh[:, at] * mid], axis=1)
            right = kh[:, :i * SUB] * jnp.exp(jnp.minimum(ref - Gh[:, :i * SUB], 0.0))
            both = _bdot(left, right, ((2, ), (2, )))  # [heads, 2 SUB, i SUB]
            under = _turn(both[:, :SUB])
            for h in range(2):
                wn[pl.ds((h * subs + i) * 8, 8), :, LANES:LANES + i * SUB] = under[h * 8:h * 8 + 8]
            po[:, at, :i * SUB] = both[:, SUB:]

        def columns(half):
            """Columns ``8 half .. 8 half + 8`` of every diagonal block, one a
            step: each row s under it against row a of ITS sub-chunk,
            pairwise, the exponent a difference; then the substitution's step
            a (row a of each block is final: what it weighs leaves the rows
            under it). The first eight columns have rows under them in both
            halves of a sub-chunk, the last eight in the second half alone."""
            start, size = half * Q // 2, Q - half * Q // 2
            below = pl.ds(start, size)
            place = start + jax.lax.broadcasted_iota(jnp.int32, (size, 1, 1), 0)
            in_sub = (place & 7) + 8 * (place >= Q // 2).astype(jnp.int32)
            first_of = ((place & (Q // 2 - 1)) >> 3) * SUB
            # the column of its diagonal block a lane of ``ps`` holds, where that pair is r <= s
            holds = jnp.where(lane - first_of <= in_sub, lane - first_of, -1)

            def rows_of(ref, a):  # row a of every sub-chunk, over its sub-chunk's rows under it
                eight = [jnp.broadcast_to(ref[pl.ds(start + i * 8 + a - 8 * half, 1)],
                                          (8, ht, ref.shape[2])) for i in range(subs)]
                return jnp.concatenate(eight * (2 - half), axis=0)

            def column(a, carry):
                other = rows_of(gk, a)
                pairs = jnp.exp(jnp.minimum(gk[below, :, :LANES] - other[:, :, :LANES], 0.0)) \
                    * other[:, :, LANES:]
                kk = jnp.sum(pairs * kbs[below], axis=2, keepdims=True)
                qk = jnp.sum(pairs * qs[below], axis=2, keepdims=True)
                ps[below] = jnp.where(holds == a, qk, ps[below])
                wn[below] = wn[below] - jnp.where(in_sub > a, kk, 0.0) * rows_of(wn, a)
                return carry

            jax.lax.fori_loop(8 * half, 8 * half + 8, column, None)

        columns(0)
        columns(1)

        wh[...] = _turn(_by_row(wn[:, :, :LANES]))
        under = _turn(_by_row(wn[:, :, LANES:]))
        for i in range(1, subs):  # W_i = Z_i - N[i, J < i] W_J: the blocks under the diagonal's
            at = slice(i * SUB, (i + 1) * SUB)
            wh[:, at] = wh[:, at] - _bdot(under[:, at, :i * SUB], wh[:, :i * SUB], ((2, ), (1, )),
                                          passes=6)
        W = wh[...]
        P = po[...] + _turn(_by_row(ps[...]))
        o = U[:, Q:] + _bdot(P[:, :, :Q], W, ((2, ), (1, )))
        # the chunk's decay a channel, down the sublanes: one transpose for the tile's heads
        last = G[Q - 1]  # [heads, d_k]
        down = jnp.concatenate([jnp.exp(last), jnp.zeros((LANES - ht, LANES), _f32)], axis=0).T
        through = jnp.stack([jnp.broadcast_to(down[:, h:h + 1], (LANES, LANES)) for h in range(ht)])
        state[buf] = through * S0 + _bdot(kh * jnp.exp(Gh[:, Q - 1:Q] - Gh), W, ((1, ), (1, )))

        @pl.when((flags & NEW_CHUNK) > 0)
        def _():
            o_ref[...] = o

        @pl.when((flags & NEW_CHUNK) == 0)
        def _():
            o_ref[...] = o_ref[...] + o

        @pl.when((flags & LAST) > 0)
        def _():
            store(j, n, buf).start()
            pending[buf] = 1

    @pl.when((j == HT - 1) & (n == V - 1))
    def _():
        for buf in range(BUFFERS):
            settle(buf)


def visit_table(slot, started, seq_start, seq_ntok, enters, visits, rows, V):
    """The step's visits in the segments' order, ``V`` entries (the live ones
    first): ``(count and segments visited [2], slot, chunk, lo, hi, flags,
    ordinal)``, int32. A visit past the count names the last live visit's
    chunk, so that no block moves for it."""
    S = slot.shape[0]
    i32 = jnp.int32
    visits = visits.astype(i32)
    ends = jnp.cumsum(visits)
    count = ends[-1]
    n = jnp.arange(V, dtype=i32)
    at = jnp.minimum(n, jnp.maximum(count - 1, 0))  # a dead visit reads the last live one's
    seg = jnp.minimum(jnp.sum(ends[None, :] <= at[:, None], axis=1), S - 1).astype(i32)
    nth = at - (ends[seg] - visits[seg])
    chunk = jnp.where(count > 0, enters.astype(i32)[seg] + nth, 0)
    start = seq_start.astype(i32)[seg] - chunk * rows
    lo = jnp.clip(start, 0, rows)
    hi = jnp.clip(start + seq_ntok.astype(i32)[seg], 0, rows)
    live = n < count
    new_chunk = jnp.concatenate([jnp.ones((1, ), bool), chunk[1:] != chunk[:-1]])
    flags = (FIRST * (live & (nth == 0)) + LAST * (live & (nth == visits[seg] - 1))
             + STARTED * (live & started[seg]) + NEW_CHUNK * (live & new_chunk)).astype(i32)
    ordinal = (jnp.cumsum(visits > 0) - 1).astype(i32)[seg]
    return (jnp.stack([count, jnp.sum(visits > 0).astype(i32)]), slot.astype(i32)[seg], chunk,
            lo, hi, flags, ordinal)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"), donate_argnums=(0, ))
def kda_chunk_in_place(pool, block, slot, started, seq_start, seq_ntok, enters, visits, q, k, v,
                       g, beta, rows, interpret=None):
    """The chunked form over delta-rule layer ``block`` (its ordinal in the
    pool; an operand) for every segment ``visits`` gives a visit, each one's
    state read from its slot (zeros where ``started`` is false) and left there.

    pool: ``f32[layers, slots, H, d_k, d_v]`` (donated; updated in place);
    slot, started, seq_start, seq_ntok ``[S]``: a segment's slot (distinct
    among the visited), whether its sequence has seen a token, its first row
    of the flat batch and its rows; enters, visits ``[S]``: ``kda.visits_of``
    (the chunk of ``rows`` rows its first row lies in; the chunks it has rows
    in, 0 for a segment that is not scanned here); q, k, g ``[T, H, d_k]`` (g
    the log-decay, at most 0), v ``[T, H, d_v]``, beta ``[T, H]``. Returns ``(o
    [T, H, d_v] float32, pool)`` as ``kda.chunk`` a visit would; a row of a
    chunk no visit reaches is NOT written (the caller selects its own rows)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    S = slot.shape[0]
    assert pool.dtype == jnp.float32 and pool.shape[2:] == (H, dk, dv), (pool.shape, q.shape, dv)
    assert supported(H, dk, dv, rows) and T % rows == 0, (H, dk, dv, rows, T)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ht = kda_step.tiling(H, dk, dv)
    HT, V = H // ht, max_visits(T, S, rows)
    counts, *table = visit_table(jnp.clip(slot, 0, pool.shape[1] - 1), started, seq_start,
                                 seq_ntok, enters, visits, rows, V)
    meta = jnp.concatenate([jnp.asarray(block, jnp.int32).reshape(1), counts])

    def rows_of(width, heads):  # a chunk's rows: of the tile's heads, or (beta) of all
        tile = (lambda j: j) if heads == ht else (lambda j: 0)
        return pl.BlockSpec((rows, heads, width),
                            lambda j, n, meta, slot, chunk, *_: (chunk[n], tile(j), 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(HT, V),
        in_specs=[rows_of(dk, ht), rows_of(dk, ht), rows_of(dv, ht), rows_of(dk, ht),
                  rows_of(H, 1),  # beta, the layer's heads along the lanes
                  pl.BlockSpec(memory_space=pl.ANY)],  # the pool in HBM, aliased in/out
        out_specs=[pl.BlockSpec((ht, rows, dv),
                                lambda j, n, meta, slot, chunk, *_: (j, chunk[n], 0)),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((BUFFERS, ht, dk, dv), _f32),
            pltpu.SemaphoreType.DMA((BUFFERS, )),
            pltpu.SemaphoreType.DMA((BUFFERS, )),
            pltpu.SMEM((BUFFERS, ), jnp.int32),
            pltpu.VMEM((rows, ht, 2 * LANES), _f32),  # G | k, by row
            pltpu.VMEM((rows, ht, LANES), _f32),  # beta k
            pltpu.VMEM((rows, ht, LANES), _f32),  # q
            pltpu.VMEM((rows, ht, 2 * LANES), _f32),  # W | A's blocks under the diagonal's
            pltpu.VMEM((rows, ht, LANES), _f32),  # the queries' pairs inside a sub-chunk
            pltpu.VMEM((ht, rows, LANES), _f32),  # and against the earlier sub-chunks, by head
            pltpu.VMEM((ht, rows, LANES), _f32),  # W by head
        ],
    )
    o, pool = pl.pallas_call(
        functools.partial(_kernel, HT, V),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((H, T, dv), _f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={12: 1},  # the pool (after 7 scalar-prefetch args and 5 row operands)
        interpret=interpret,
        name="kda_chunk_in_place",
    )(meta, *table, *(a.astype(_f32) for a in (q, k, v, g)), beta.astype(_f32).reshape(T, 1, H),
      pool)
    return jnp.swapaxes(o, 0, 1), pool
