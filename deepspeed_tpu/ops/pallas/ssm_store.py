"""Pallas copies of per-sequence states out of and into their slots of a pool.

A ``put`` step's chunked scan starts each sequence from the state in its slot
of the engine's pool ``[blocks, slots, ...]`` (``ragged/kv_cache.py``) and
hands back one final state a sequence ``[S, ...]`` that belongs in that slot.
XLA cuts a gather of rows above 2 MiB into pieces by first slicing its
OPERAND, the whole pool, once a block: at Falcon-H1-34B's widths (4 MiB a
slot) 18 GiB moved a step for 0.26 GiB of states, and a second pool's worth of
temporaries; its scatter stayed in place, at the compiler's discretion
(PERF.md section 6, PR 48). Here the pool stays in HBM and the
kernel walks the rows: a live row's state is ONE copy HBM to HBM between its
slot and its row of ``states``, ``IN_FLIGHT`` of them on their way at a time;
**a row that is not live** copies nothing. :func:`ssm_store_in_place` ALIASES
the pool in and out (``input_output_aliases``, as ``ssm_step_in_place`` does);
live rows hold distinct slots, so their copies never meet. A copy of whole
slots needs only that a slot is whole tiles (:func:`supported`), whatever its
rank and dtype. The block's ordinal is an operand, so a program's blocks share
one traced and lowered kernel a direction.

Since PR 53 the same two kernels move the convolution's tails of every step,
``put`` and ``decode_loop`` (``modules/ssm.py:conv_slot``: a sequence's ``K -
1`` rows folded into one bf16 slot ``[8, 128 k]``). That pool is small enough
for the chip's vector memory (28 MiB), and XLA's memory-space assignment
then carries it there and back around the kernels, a pass over the pool a
block: the pool is held to HBM on both sides of the call.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

IN_FLIGHT = 4  # copies on their way at a time
LANES, SUBLANES = 128, 8


def supported(shape):
    """The shape rule, the same on every backend: a slot's two minor dimensions
    are whole (sublane, lane) tiles. The chip's compiler pads any other slot
    in HBM and then refuses to cut the pool by slot."""
    return len(shape) >= 4 and shape[-1] % LANES == 0 and shape[-2] % SUBLANES == 0


def _kernel(store, block_ref, slot_ref, live_ref, *refs):
    """Scalar prefetch, then (store) states, pool -> pool aliased, or (load)
    pool -> states, then the copies' semaphores."""
    if store:
        states_ref, _, pool_ref, sems = refs
    else:
        pool_ref, states_ref, sems = refs
    S = states_ref.shape[0]
    mi = block_ref[0]

    def copy(row):
        ends = states_ref.at[row], pool_ref.at[mi, slot_ref[row]]
        return pltpu.make_async_copy(*(ends if store else ends[::-1]),
                                     sems.at[jax.lax.rem(row, IN_FLIGHT)])

    def one_row(row, carry):
        behind = jnp.maximum(row - IN_FLIGHT, 0)  # the row that last used this row's semaphore

        @pl.when((row >= IN_FLIGHT) & (live_ref[behind] > 0))
        def _():
            copy(behind).wait()

        at = jnp.minimum(row, S - 1)

        @pl.when((row < S) & (live_ref[at] > 0))
        def _():
            copy(at).start()

        return carry

    jax.lax.fori_loop(0, S + IN_FLIGHT, one_row, None)  # the last IN_FLIGHT turns only wait


def _call(store, pool, block, slot, live, states, interpret):
    """``states``: the rows (store) or their shape and dtype (load)."""
    assert supported(pool.shape), pool.shape
    assert states.shape[1:] == pool.shape[2:] and states.dtype == pool.dtype, \
        (pool.shape, pool.dtype, states.shape, states.dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    out = pool if store else states
    out_shape = jax.ShapeDtypeStruct(out.shape, out.dtype)
    if not interpret:
        # the pool stays in HBM on both sides of the call (the module's last paragraph)
        pool = pltpu.with_memory_space_constraint(pool, pltpu.HBM)
        if store:
            out_shape = pltpu.HBM(out.shape, out.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, store),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1, ),
            in_specs=[in_hbm, in_hbm] if store else [in_hbm], out_specs=in_hbm,
            scratch_shapes=[pltpu.SemaphoreType.DMA((IN_FLIGHT, ))]),
        out_shape=out_shape,
        input_output_aliases={4: 0} if store else {},  # the pool, after 3 scalars and the states
        interpret=interpret,
        name="ssm_store_in_place" if store else "ssm_load",
    )(jnp.asarray(block, jnp.int32).reshape(1),
      jnp.clip(slot, 0, pool.shape[1] - 1).astype(jnp.int32), live.astype(jnp.int32),
      *((states, pool) if store else (pool, )))


def ssm_store_in_place(pool, block, slot, live, states, interpret=None):
    """``states[i]`` stored into ``pool[block, slot[i]]`` where ``live[i]``.

    pool: ``[blocks, slots, ...]`` (updated in place where the caller donates
    it); block: the ordinal, an operand; slot, live: ``[S]``, a row's slot
    (distinct among live rows; a row that is not live may name any) and whether
    it stores; states ``[S, ...]`` of the pool's trailing shape and dtype.
    Returns the pool: bit for bit ``pool.at[block, where(live, slot,
    slots)].set(states, mode="drop")``."""
    return _call(True, pool, block, slot, live, states, interpret)


def ssm_load(pool, block, slot, live, interpret=None):
    """``[S, ...]``: row i is ``pool[block, slot[i]]`` where ``live[i]`` and
    NOTHING WRITTEN (whatever the memory held) where not: the caller selects."""
    rows = jax.ShapeDtypeStruct((slot.shape[0], ) + pool.shape[2:], pool.dtype)
    return _call(False, pool, block, slot, live, rows, interpret)
