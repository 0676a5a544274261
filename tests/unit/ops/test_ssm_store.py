"""The slot copies of a ``put`` step (``ops/pallas/ssm_store.py``) in interpret
mode, bit for bit against the dropping scatter and the gather they replace:
``pool.at[block, where(live, slot, n_slots)].set(states, mode="drop")`` and
``pool[block, slot]``. A ``nan`` canary fills every slot the step does not
name and every other block: a copy that strays shows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.modules import ssm
from deepspeed_tpu.ops.pallas import ssm_step, ssm_store

BLOCKS, SLOTS = 3, 8
# a slot's shape and dtype: Mamba-2 states on the step kernel's rule; a bf16 pool
# (the step kernel reads float32 alone); a slot of another rank; a head a unit of
# the step kernel's that no tile holds (136 x 1024 float32 > 512 KiB); the
# convolution's bf16 tails of Nemotron-3-Nano's and Falcon-H1-34B's published
# widths, folded (``ssm.conv_slot``, PR 53)
ON_RULE = [((4, 8, 128), jnp.float32), ((2, 16, 256), jnp.bfloat16), ((8, 256), jnp.float32),
           ((1, 136, 1024), jnp.float32), ((8, 2304), jnp.bfloat16), ((8, 1920), jnp.bfloat16)]
ON_RULE_IDS = ["f32-heads-of-8x128", "bf16-heads-of-16x256", "no-head-axis",
               "off-the-step-kernels-tile", "bf16-conv-tails-nemotron", "bf16-conv-tails-falcon-h1"]
# off the copies' rule (and ``ssm_step.supported``'s): a head's rows are no whole
# sublane tile, its columns no whole lane tile
OFF_RULE = [((4, 5, 128), jnp.float32), ((4, 8, 16), jnp.float32), ((3, 5, 16), jnp.bfloat16),
            ((3, 6144), jnp.bfloat16)]
OFF_RULE_IDS = ["five-rows-a-head", "sixteen-columns", "bf16-off-both",
                "bf16-conv-tails-as-three-rows"]


def _case(slot_shape, dtype, slot, live, seed=0):
    """A pool of canaries but for the rows' slots, and the rows."""
    r = np.random.default_rng(seed)
    pool = np.full((BLOCKS, SLOTS) + slot_shape, np.nan, np.float32)
    named = np.clip(np.asarray(slot), 0, SLOTS - 1)
    pool[:, named] = r.standard_normal((BLOCKS, len(slot)) + slot_shape)
    states = r.standard_normal((len(slot), ) + slot_shape)
    return jnp.asarray(pool, dtype), jnp.asarray(slot, jnp.int32), jnp.asarray(live, bool), \
        jnp.asarray(states, dtype)


def _scatter(pool, block, slot, live, states):
    return pool.at[block, jnp.where(live, slot, SLOTS)].set(states, mode="drop")


def _same(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


# (slot, live): rows 1 and 3 are nobody's and name a live row's slot and one past
# the last; the last slot; adjacent slots; more rows than copies in flight
ROWS = [([2, 2, 7, 8, 4], [1, 0, 1, 0, 1]), ([7], [1]), ([3, 4, 5, 2], [1, 1, 1, 1]),
        ([5, 0, 3, 6, 1, 7, 2, 5, 0, 8, 8], [1, 1, 0, 1, 1, 1, 1, 0, 0, 0, 0]),
        ([1, 6, 6], [0, 0, 0])]
ROWS_IDS = ["dead-rows-between-live-ones", "the-last-slot", "adjacent-slots",
            "more-rows-than-copies-in-flight", "every-row-dead"]


@pytest.mark.parametrize("slot, live", ROWS, ids=ROWS_IDS)
@pytest.mark.parametrize("slot_shape, dtype", ON_RULE, ids=ON_RULE_IDS)
def test_the_store_is_the_dropping_scatter_bit_for_bit(slot_shape, dtype, slot, live):
    """Dead rows write nothing: the canaries of every slot the live rows do not
    name, and of the other blocks, come out as they went in."""
    assert ssm_store.supported((BLOCKS, SLOTS) + slot_shape)
    pool, slot, live, states = _case(slot_shape, dtype, slot, live)
    got = ssm_store.ssm_store_in_place(pool, 1, slot, live, states)
    _same(got, _scatter(pool, 1, slot, live, states))
    untouched = np.ones((BLOCKS, SLOTS), bool)
    untouched[1, np.asarray(slot)[np.asarray(live)]] = False
    _same(got[untouched], pool[untouched])
    assert bool(jnp.isnan(got[0].astype(jnp.float32)).any())  # a canary was there to be kept


@pytest.mark.parametrize("slot, live", ROWS, ids=ROWS_IDS)
@pytest.mark.parametrize("slot_shape, dtype", ON_RULE, ids=ON_RULE_IDS)
def test_the_load_is_the_gather_on_live_rows_and_zeros_elsewhere(slot_shape, dtype, slot, live):
    """Through the module's entry, which selects: a row that is not live reads
    zeros whatever its slot held (a canary) and whatever the kernel left."""
    pool, slot, live, _ = _case(slot_shape, dtype, slot, live, seed=1)
    assert ssm.whole_slots(pool)
    got = ssm.load(pool, 2, slot, live)
    want = jnp.where(live.reshape((-1, ) + (1, ) * len(slot_shape)),
                     pool[2, jnp.minimum(slot, SLOTS - 1)], 0)
    _same(got, want)
    assert not bool(jnp.isnan(got.astype(jnp.float32)).any())


@pytest.mark.parametrize("slot_shape, dtype", ON_RULE[:2], ids=ON_RULE_IDS[:2])
def test_two_blocks_of_one_pool_in_turn_each_leave_the_others_slots(slot_shape, dtype):
    """The block's ordinal is an operand: one jitted program, traced once,
    serves block 0 and then block 2 of the pool it is handed back."""
    pool, slot, live, states = _case(slot_shape, dtype, [6, 1, 3], [1, 1, 0])
    store = jax.jit(lambda *args: ssm_store.ssm_store_in_place(*args), donate_argnums=(0, ))
    want = _scatter(_scatter(pool, 0, slot, live, states), 2, slot, live, -states)
    got = store(store(pool + 0, jnp.int32(0), slot, live, states), jnp.int32(2), slot, live, -states)
    assert store._cache_size() == 1
    _same(got, want)
    _same(got[1], pool[1])


@pytest.mark.parametrize("in_flight", [1, 2, 8])
def test_any_number_of_copies_in_flight_walks_every_row(monkeypatch, in_flight):
    monkeypatch.setattr(ssm_store, "IN_FLIGHT", in_flight)
    pool, slot, live, states = _case((2, 8, 128), jnp.float32, [5, 0, 3, 6, 1, 7, 2], [1, 1, 0, 1, 1, 0, 1])
    _same(ssm_store.ssm_store_in_place(pool, 0, slot, live, states),
          _scatter(pool, 0, slot, live, states))
    _same(jnp.where(live[:, None, None, None], ssm_store.ssm_load(pool, 1, slot, live), 0),
          jnp.where(live[:, None, None, None], pool[1, slot], 0))


@pytest.mark.parametrize("slot_shape, dtype", ON_RULE + OFF_RULE, ids=ON_RULE_IDS + OFF_RULE_IDS)
def test_the_modules_entry_chooses_by_the_pools_type_alone_and_agrees(slot_shape, dtype):
    """``ssm.store_in_place`` / ``ssm.load`` run the kernels where a slot is
    whole tiles and XLA's scatter and gather where not: the same pool either
    way, and states of another dtype are cast to the pool's."""
    pool, slot, live, states = _case(slot_shape, dtype, [2, 2, 7, 8, 4], [1, 0, 1, 0, 1], seed=2)
    on_rule = (slot_shape, dtype) in ON_RULE
    assert ssm.whole_slots(pool) == on_rule
    if not on_rule and len(slot_shape) == 3:
        assert not ssm_step.supported(*slot_shape, 1)
    text = jax.jit(ssm.store_in_place, static_argnums=1).lower(pool, 1, slot, live, states).as_text()
    assert ("scatter" in text) != on_rule  # interpret mode spells a copy dynamic_update_slice
    text = jax.jit(ssm.load, static_argnums=1).lower(pool, 1, slot, live).as_text()
    assert ("gather" in text) != on_rule
    _same(ssm.store_in_place(pool, 1, slot, live, states.astype(jnp.float32)),
          _scatter(pool, 1, slot, live, states))
    _same(ssm.load(pool, 1, slot, live),
          jnp.where(live.reshape((-1, ) + (1, ) * len(slot_shape)),
                    pool[1, jnp.minimum(slot, SLOTS - 1)], 0))


def test_a_pool_off_the_rule_is_refused_by_the_kernels_themselves():
    pool, slot, live, states = _case((4, 5, 128), jnp.float32, [1], [1])
    with pytest.raises(AssertionError):
        ssm_store.ssm_store_in_place(pool, 0, slot, live, states)
    with pytest.raises(AssertionError):
        ssm_store.ssm_load(pool, 0, slot, live)


@pytest.mark.parametrize("rows, channels, slot", [
    (3, 6144, (8, 2304)), (3, 5120, (8, 1920)), (3, 576, (8, 256)), (3, 128, (3, 128)),
    (3, 112, (3, 112))],
    ids=["nemotron-3-nano", "falcon-h1-34b", "padded-behind", "tiny-nemotron", "tiny-falcon-h1"])
def test_the_conv_tails_slot_is_whole_tiles_where_the_tails_fill_one(rows, channels, slot):
    """``ssm.conv_slot``: the published widths of both hybrid families fold
    into whole (8, 128) tiles without padding and are on the kernels' rule; the
    tiny test configurations' tails are no tile and stay ``[K - 1, C]``, off it
    (so both paths stay covered); ``[K - 1, C]`` itself is never on the rule.
    The fold keeps the values in their order, zeros behind, and comes back bit
    for bit."""
    assert ssm.conv_slot(rows, channels) == slot
    folds = slot != (rows, channels)
    assert ssm_store.supported((6, 128) + slot) == folds == (rows * channels >= 8 * 128)
    assert not ssm_store.supported((6, 128, rows, channels))
    tail = jnp.asarray(np.random.default_rng(3).standard_normal((5, rows, channels)), jnp.bfloat16)
    folded = ssm.fold_tails(tail, slot)
    assert folded.shape == (5, ) + slot and folded.dtype == tail.dtype
    flat = np.asarray(folded.astype(jnp.float32)).reshape(5, -1)
    np.testing.assert_array_equal(flat[:, :rows * channels],
                                  np.asarray(tail.astype(jnp.float32)).reshape(5, -1))
    assert not flat[:, rows * channels:].any()
    assert flat.shape[1] - rows * channels == {(3, 576): 320}.get((rows, channels), 0)
    _same(ssm.unfold_tails(folded, rows, channels), tail)
