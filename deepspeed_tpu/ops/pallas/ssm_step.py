"""Pallas Mamba-2 recurrence over the per-sequence state pool, in place.

A ``decode_loop`` step hands a Mamba-2 block one token a sequence. The state
that token reads and leaves is its sequence's slot of the engine's pool
``f32[blocks, slots, H, P, N]`` (``ragged/kv_cache.py``), 2 MiB a slot at the
published widths. Gathering the rows' slots, selecting them against zeros and
scattering them back moves each state three times for one update
(PERF.md section 6, PR 44); here the pool stays in HBM, is ALIASED in and out
(``input_output_aliases``, as ``paged_attention_update`` aliases the K/V
array), and the kernel walks the step's rows. A ``put`` step runs it too
(``modules/ssm.py:scan_in_place``, PR 49): its segments of ONE row, 31 of a
chat step's 32, are such rows — row i the sequence's one row, ``live`` false
for a sequence with no row or with many — and the kernel is the one a
``decode_loop`` step runs, unchanged. The walk:

- grid over ROWS, executed in order. A live row's slot is cut into tiles of
  heads ``[tile, P, N]`` (:func:`tiling`); a tile is copied into VMEM, updated
  ``h = exp(dt a) h + (dt x) (x) B`` and read ``y = h C``, and copied back to
  the SAME slot, while the ``FETCHES - 1`` tiles behind it (the next row's
  first ones behind a row's last) are on their way in and the tile ahead of it
  on its way out. Reads and writes share the memory's stream: together they
  reach ~640 GB/s on a v5e, and the arithmetic (a third of that time) is under
  them;
- **a row that is nobody's** (``live`` false) copies nothing in either
  direction and its ``y`` is zeros. The copies are manual and under the row's
  predicate, never a ``BlockSpec`` that routes a dead row to a real slot: the
  pipeline would fetch a live row's slot before the row ahead had written it
  back. Live rows of one step hold distinct slots, so their copies never meet;
- **a sequence with nothing seen** (``started`` false) is not read: it starts
  from zeros whatever its slot held (a ``where``, a reused slot may hold
  anything);
- **the state is float32 and is read in float32**, on the vector units: the
  update is elementwise, the reading sums a state row's N columns. No matmul
  touches the state, so no bf16 pass can.

N is the lane axis and P the sublane axis of a state tile, and ``dt x`` and
``y`` are lane-dense rows of (head, p): both cross between the two through a
128 x 128 transpose (the XLU), a UNIT of heads at a time — the fewest heads
whose state rows are whole transposes (two at P = 64), or every head with the
rows padded where H does not split so. ``dt x`` of a unit, broadcast down the
sublanes and transposed, is each state row's value across its lanes; the
products ``h C`` of a unit, transposed and added down the sublanes, are the
rows' sums side by side, ``y``'s own order (a lane reduction a state row costs
three times the arithmetic: 2.0 against 0.7 us a 512 KiB tile on the chip). B
and C ``[T, G, N]`` are a row a group, broadcast over sublanes; ``exp(dt a)`` is
a scalar a head (SMEM). Tiles and units are walked by ``fori_loop``s and the
block's ordinal is an operand: the body is traced and lowered for every
``decode_loop`` program at every start of the server, and 64 heads unrolled in
Python, once a block, cost that 3 s a program (17 s of a 51 s set-up).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_BYTES = 512 * 1024  # a head tile's ceiling; FETCHES + STORES tiles are held
FETCHES, STORES = 3, 2  # tiles in VMEM on their way in (the one computed among them) / out
LANES = 128


def tiling(H, P, N):
    """``(heads a unit, heads a tile)``: a unit is the fewest heads whose
    ``[., P]`` state rows are whole 128-row transposes, every head where H is
    not a multiple of that (its rows then padded); a tile the most units that
    divide H's and whose float32 ``[., P, N]`` is at most ``TILE_BYTES``."""
    unit = LANES // math.gcd(P, LANES)
    if H % unit:
        unit = H
    fit = max(1, TILE_BYTES // (unit * P * N * 4))
    return unit, unit * max(d for d in range(1, fit + 1) if (H // unit) % d == 0)


def supported(H, P, N, G):
    """The shape rule, the same on every backend: a head's state is whole lane
    tiles wide and whole sublane tiles high, heads split evenly over groups,
    and a unit of heads fits a tile."""
    return N % LANES == 0 and P % 8 == 0 and H % G == 0 \
        and tiling(H, P, N)[0] * P * N * 4 <= TILE_BYTES


def _kernel(T, unit,
            # scalar prefetch
            block_ref, slot_ref, live_ref, started_ref, decay_ref,
            # inputs
            x_ref, b_ref, c_ref, pool_ref,
            # outputs
            y_ref, pool_out_ref,
            # scratch
            in_buf, out_buf, in_sem, out_sem, pending):
    t = pl.program_id(0)
    mi = block_ref[0]
    _, ht, P, N = in_buf.shape
    H = decay_ref.shape[1]
    R = H // b_ref.shape[1]  # heads a group
    HT = H // ht
    rows = x_ref.shape[2] * LANES  # state rows a unit, padded to whole transposes

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

    def each(n, fn, unroll=False):
        jax.lax.fori_loop(0, n, lambda i, carry: fn(i), None, unroll=unroll)

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

        def one_unit(u):
            at = j * (ht // unit) + u  # the unit among the row's
            x = x_ref[0, at]  # [rows / 128, 128]: dt x of the unit's (head, p), lane-dense
            x = jnp.concatenate([across(x[r:r + 1]) for r in range(rows // LANES)], axis=0)
            reads = []
            for i in range(unit):
                h = at * unit + i
                g = h // R
                state = jnp.where(started, in_buf[ibuf, u * unit + i], 0.0)  # [P, N]
                mine = x[i * P:(i + 1) * P]
                state = state * decay_ref[t, h] + jnp.tile(mine, (1, N // LANES)) * b_ref[0, g]
                out_buf[obuf, u * unit + i] = state
                read = state * c_ref[0, g]
                reads.append(sum(read[:, k:k + LANES] for k in range(0, N, LANES)))
            if rows > unit * P:
                reads.append(jnp.zeros((rows - unit * P, LANES), jnp.float32))
            reads = jnp.concatenate(reads, axis=0)  # a row's sum over lanes: its column's, across
            y_ref[0, at] = jnp.concatenate(
                [jnp.sum(reads[r:r + LANES].T, axis=0, keepdims=True)
                 for r in range(0, rows, LANES)], axis=0)

        # traced once, unrolled by the lowering: side by side the units' transposes overlap
        # (one unit an iteration, its two transposes in a chain, took 91 us a block-step for 58)
        each(ht // unit, one_unit, unroll=True)
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
def ssm_step_in_place(pool, block, slot, live, started, x, dt, A, B, C, interpret=None):
    """One token a row through Mamba-2 block ``block`` (its ordinal in the
    pool; an operand, so that a program's blocks share ONE traced and lowered
    kernel), each live row's state updated in its slot.

    pool: ``f32[blocks, slots, H, P, N]`` (donated; updated in place); slot,
    live, started: ``[T]``, a row's slot (distinct among live rows; a row that
    is not live may name any), whether the row is somebody's, whether its
    sequence has seen a token; x ``[T, H, P]``; dt ``[T, H]`` (after the
    softplus); A ``[H]``; B, C ``[T, G, N]``. Returns ``(y [T, H, P] float32,
    pool)`` as :func:`deepspeed_tpu.inference.v2.modules.ssm.step` on the
    gathered states would, a dead row's ``y`` zeros."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    assert pool.dtype == jnp.float32 and pool.shape[2:] == (H, P, N), (pool.shape, x.shape, N)
    assert supported(H, P, N, G), (H, P, N, G)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    unit, ht = tiling(H, P, N)
    units = H // unit
    rows = -(-unit * P // LANES) * LANES  # state rows a unit, padded to whole transposes
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32)[None, :])
    xdt = (x.astype(f32) * dt[..., None]).reshape(T, units, unit * P)
    xdt = jnp.pad(xdt, ((0, 0), (0, 0), (0, rows - unit * P)))

    by_unit = pl.BlockSpec((1, units, rows // LANES, LANES), lambda t, *_: (t, 0, 0, 0))
    by_group = pl.BlockSpec((1, G, 1, N), lambda t, *_: (t, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(T, ),
        in_specs=[by_unit, by_group, by_group,
                  pl.BlockSpec(memory_space=pl.ANY)],  # the pool in HBM, aliased in/out
        out_specs=[by_unit, pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((FETCHES, ht, P, N), f32),
            pltpu.VMEM((STORES, ht, P, N), f32),
            pltpu.SemaphoreType.DMA((FETCHES, )),
            pltpu.SemaphoreType.DMA((STORES, )),
            pltpu.SMEM((STORES, ), jnp.int32),
        ],
    )
    y, pool = pl.pallas_call(
        functools.partial(_kernel, T, unit),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, units, rows // LANES, LANES), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},  # the pool (after 5 scalar-prefetch args)
        interpret=interpret,
        name="ssm_step_in_place",
    )(jnp.asarray(block, jnp.int32).reshape(1),
      jnp.clip(slot, 0, pool.shape[1] - 1).astype(jnp.int32), live.astype(jnp.int32),
      started.astype(jnp.int32), decay, xdt.reshape(T, units, rows // LANES, LANES),
      B.astype(f32)[:, :, None], C.astype(f32)[:, :, None], pool)
    return y.reshape(T, units, rows)[:, :, :unit * P].reshape(T, H, P), pool
