"""Pallas gated delta-rule recurrence (KDA) over the per-sequence state pool,
in place: ``ssm_step.py``'s walk with another body.

A ``decode_loop`` step hands a delta-rule mixer one token a sequence. The state
that token reads and leaves is its sequence's slot of the engine's pool
``f32[layers, slots, H, d_k, d_v]`` (``ragged/kv_cache.py``), 4 MiB a slot at
the published widths (64 x 128 x 128): twice the size at which XLA cuts a
gather by slicing its operand, the whole pool (PERF.md section 6, PR 47 / 48).
The pool stays in HBM and is ALIASED in and out; the kernel walks the step's
rows as ``ssm_step_in_place`` does: a live row's slot is cut into tiles of
heads ``[tile, d_k, d_v]``, a tile is copied into VMEM, updated, read and
copied back to the SAME slot while the ``FETCHES - 1`` tiles behind it are on
their way in and the tile ahead of it on its way out; a row that is nobody's
(``live`` false) copies nothing and reads zeros; a sequence with nothing seen
(``started`` false) is not read and starts from zeros whatever its slot held. A
``put`` step runs it too, for its segments of one row
(``modules/kda.py:scan_in_place``).

The body, a head (``S`` is ``[d_k, d_v]``: the key's channels down the
sublanes, the value's along the lanes)::

    S <- diag(alpha) S                    the decay, a CHANNEL of the key
    u  = S^T k                            what the state holds for this key
    S <- S + k (beta (v - u))^T           the rank-one correction
    o  = S^T q

all in float32 on the vector units: no matmul touches the state, so no bf16
pass can. ``alpha``, ``k`` and ``q`` are lane-dense rows of d_k; each crosses to
"a value a sublane, across the lanes" through a 128 x 128 transpose (the XLU),
and the two readings ``S^T k`` / ``S^T q`` are sums down the sublanes, which
leave ``v``'s own lane-dense order. ``beta`` is a scalar a head (SMEM). Tiles
are walked by a ``fori_loop`` and the layer's ordinal is an operand: a
program's layers share ONE traced and lowered kernel.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_BYTES = 512 * 1024  # a head tile's ceiling; FETCHES + STORES tiles are held
FETCHES, STORES = 3, 2  # tiles in VMEM on their way in (the one computed among them) / out
LANES = 128


def tiling(H, dk, dv):
    """Heads a tile: the most that divide H and whose float32 ``[., d_k, d_v]``
    is at most ``TILE_BYTES``."""
    fit = max(1, TILE_BYTES // (dk * dv * 4))
    return max(d for d in range(1, fit + 1) if H % d == 0)


def supported(H, dk, dv):
    """The shape rule, the same on every backend: a head's state is one 128 x
    128 transpose high and one lane tile wide (the published widths)."""
    return dk == LANES and dv == LANES


def _kernel(T,
            # scalar prefetch
            block_ref, slot_ref, live_ref, started_ref, beta_ref,
            # inputs
            rows_ref, pool_ref,
            # outputs
            y_ref, pool_out_ref,
            # scratch
            in_buf, out_buf, in_sem, out_sem, pending):
    t = pl.program_id(0)
    mi = block_ref[0]
    _, ht, dk, dv = in_buf.shape
    HT = beta_ref.shape[1] // ht

    def fetch(row, j):
        buf = jax.lax.rem(row * HT + j, FETCHES)
        return pltpu.make_async_copy(pool_out_ref.at[mi, slot_ref[row], pl.ds(j * ht, ht)],
                                     in_buf.at[buf], in_sem.at[buf])

    def store(row, j, buf):
        return pltpu.make_async_copy(out_buf.at[buf],
                                     pool_out_ref.at[mi, slot_ref[row], pl.ds(j * ht, ht)],
                                     out_sem.at[buf])

    def settle(buf):  # the store that last left ``out_buf[buf]``, if it is still out
        @pl.when(pending[buf] > 0)
        def _():
            store(t, 0, buf).wait()  # a wait needs the copy's size, not its place
            pending[buf] = 0

    def fetch_ahead(row, ahead):
        """Start the fetch of the tile ``ahead`` tiles past ``row``'s first,
        where there is such a tile and its row reads its slot: somebody's, with
        something seen."""
        row, j = row + ahead // HT, jax.lax.rem(ahead, HT)
        at = jnp.minimum(row, T - 1)

        @pl.when((row < T) & (live_ref[at] > 0) & (started_ref[at] > 0))
        def _():
            fetch(at, j).start()

    def each(n, fn):
        jax.lax.fori_loop(0, n, lambda i, carry: fn(i), None)

    @pl.when(t == 0)
    def _():
        for buf in range(STORES):
            pending[buf] = 0
        for ahead in range(FETCHES - 1):
            fetch_ahead(0, ahead)

    live = live_ref[t] > 0
    started = started_ref[t] > 0

    def across(row):  # [1, 128] -> [128, 128]: lane l of the row down sublane l's lanes
        return jnp.broadcast_to(row, (LANES, LANES)).T

    def one_tile(j):
        fetch_ahead(t, j + FETCHES - 1)

        @pl.when(started)
        def _():
            fetch(t, j).wait()

        ibuf = jax.lax.rem(t * HT + j, FETCHES)
        obuf = jax.lax.rem(t * HT + j, STORES)
        settle(obuf)
        for i in range(ht):  # traced once a kernel: the tiles' loop is the lowering's
            h = j * ht + i
            row = rows_ref[0, h]  # [4, 128]: alpha, k, q (d_k each) and v (d_v), lane-dense
            alpha, k, q = across(row[0:1]), across(row[1:2]), across(row[2:3])
            state = jnp.where(started, in_buf[ibuf, i], 0.0) * alpha  # [d_k, d_v]
            held = jnp.sum(state * k, axis=0, keepdims=True)  # S^T k: [1, d_v]
            state = state + k * (beta_ref[t, h] * (row[3:4] - held))
            out_buf[obuf, i] = state
            y_ref[0, h] = jnp.sum(state * q, axis=0, keepdims=True)
        store(t, j, obuf).start()
        pending[obuf] = 1

    @pl.when(live)
    def _():
        each(HT, one_tile)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)
        # the tiles behind are fetched from here all the same
        each(HT, lambda j: fetch_ahead(t, j + FETCHES - 1))

    @pl.when(t == T - 1)
    def _():
        for buf in range(STORES):
            settle(buf)


@functools.partial(jax.jit, static_argnames=("interpret", ), donate_argnums=(0, ))
def kda_step_in_place(pool, block, slot, live, started, q, k, v, alpha, beta, interpret=None):
    """One token a row through delta-rule layer ``block`` (its ordinal in the
    pool; an operand), each live row's state updated in its slot.

    pool: ``f32[layers, slots, H, d_k, d_v]`` (donated; updated in place);
    slot, live, started: ``[T]``, a row's slot (distinct among live rows; a row
    that is not live may name any), whether the row is somebody's, whether its
    sequence has seen a token; q, k ``[T, H, d_k]`` (normed and scaled as the
    mixer reads them); v ``[T, H, d_v]``; alpha ``[T, H, d_k]`` the decay a
    channel, in (0, 1]; beta ``[T, H]``. Returns ``(o [T, H, d_v] float32,
    pool)`` as :func:`deepspeed_tpu.inference.v2.modules.kda.step` on the
    gathered states would, a dead row's ``o`` zeros."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    assert pool.dtype == jnp.float32 and pool.shape[2:] == (H, dk, dv), (pool.shape, q.shape, dv)
    assert supported(H, dk, dv), (H, dk, dv)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ht = tiling(H, dk, dv)
    f32 = jnp.float32
    rows = jnp.stack([alpha.astype(f32), k.astype(f32), q.astype(f32), v.astype(f32)], axis=2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(T, ),
        in_specs=[pl.BlockSpec((1, H, 4, LANES), lambda t, *_: (t, 0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],  # the pool in HBM, aliased in/out
        out_specs=[pl.BlockSpec((1, H, 1, LANES), lambda t, *_: (t, 0, 0, 0)),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((FETCHES, ht, dk, dv), f32),
            pltpu.VMEM((STORES, ht, dk, dv), f32),
            pltpu.SemaphoreType.DMA((FETCHES, )),
            pltpu.SemaphoreType.DMA((STORES, )),
            pltpu.SMEM((STORES, ), jnp.int32),
        ],
    )
    y, pool = pl.pallas_call(
        functools.partial(_kernel, T),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, H, 1, LANES), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},  # the pool (after 5 scalar-prefetch args and the rows)
        interpret=interpret,
        name="kda_step_in_place",
    )(jnp.asarray(block, jnp.int32).reshape(1),
      jnp.clip(slot, 0, pool.shape[1] - 1).astype(jnp.int32), live.astype(jnp.int32),
      started.astype(jnp.int32), beta.astype(f32), rows, pool)
    return y.reshape(T, H, dv), pool
