"""The in-place Mamba-2 recurrence kernel (``ops/pallas/ssm_step.py``) in
interpret mode, float32, against ``ssm.step`` on gathered states: what the
kernel must keep of the gather, the select against zeros and the dropping
scatter it replaces in a ``decode_loop`` step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.modules import ssm
from deepspeed_tpu.ops.pallas import ssm_step

TOL = 1e-5
# (H, P, N, G): the published widths cut in heads only (units of two heads); a
# tiny shape whose every head is less than one transpose high (one padded unit);
# two lane tiles of state, a group a head, six heads where a unit would be eight;
# Falcon-H1-34B's published widths whole (PR 47): a unit is ONE head (P = 128 is
# a whole transpose), two lane tiles a state row, 16 heads a group, 8 tiles a row
PUBLISHED, TINY, WIDE, FALCON_H1 = (16, 64, 128, 2), (8, 8, 128, 2), (6, 16, 256, 3), \
    (32, 128, 256, 2)
SHAPES = pytest.mark.parametrize("shape", [PUBLISHED, TINY, WIDE, FALCON_H1],
                                 ids=["published-16-heads", "tiny", "two-lane-tiles",
                                      "falcon-h1-32-heads-of-128x256"])


def _case(shape, slot, live, started, blocks=2, slots=8, seed=0, fill=None):
    """A pool and a step's rows; ``fill``: what the rows' slots hold instead of
    a drawn state."""
    H, P, N, G = shape
    T = len(slot)
    r = np.random.default_rng(seed)
    pool = r.standard_normal((blocks, slots, H, P, N)).astype(np.float32)
    if fill is not None:
        pool[:, np.asarray(slot)] = fill
    rows = (jnp.asarray(slot, jnp.int32), jnp.asarray(live, bool), jnp.asarray(started, bool),
            jnp.asarray(r.standard_normal((T, H, P)), jnp.float32),
            jnp.asarray(r.random((T, H)) * 0.5 + 0.01, jnp.float32),
            -jnp.asarray(r.random(H) + 0.5, jnp.float32),
            jnp.asarray(r.standard_normal((T, G, N)), jnp.float32),
            jnp.asarray(r.standard_normal((T, G, N)), jnp.float32))
    return pool, rows


def _reference(pool, block, slot, live, started, x, dt, A, B, C):
    """``ssm.step`` on the rows' gathered states, a live row's state put back
    by hand: ``(y with a dead row's zeros, the pool)``."""
    state = jnp.where(started[:, None, None, None], jnp.asarray(pool)[block, slot], 0.0)
    y, state = ssm.step(x, dt, A, B, C, state)
    want = np.array(pool)
    for t in np.flatnonzero(np.asarray(live)):
        want[block, int(slot[t])] = np.asarray(state[t])
    return np.where(np.asarray(live)[:, None, None], np.asarray(y), 0.0), want


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), want, rtol=TOL, atol=TOL)


@SHAPES
def test_every_row_live_is_the_recurrence_on_the_gathered_states(shape):
    assert ssm_step.supported(*shape)
    assert ssm_step.tiling(*shape[:3]) == {PUBLISHED: (2, 16), TINY: (8, 8), WIDE: (6, 6), FALCON_H1: (1, 4)}[shape]
    pool, rows = _case(shape, slot=[5, 0, 3, 6], live=[1] * 4, started=[1] * 4)
    y, got = ssm_step.ssm_step_in_place(jnp.asarray(pool), 1, *rows)
    want_y, want = _reference(pool, 1, *rows)
    _close(y, want_y)
    _close(got, want)
    assert got.dtype == jnp.float32 and y.dtype == jnp.float32


@SHAPES
def test_padding_rows_between_live_ones_write_nothing_and_read_zeros(shape):
    """Rows 1 and 3 are nobody's and name a live row's slot and one past the
    last: the other slots come out bit for bit, their ``y`` rows are zeros."""
    slot, live = [2, 2, 7, 8, 4], [1, 0, 1, 0, 1]
    pool, rows = _case(shape, slot=slot, live=live, started=[1] * 5, seed=1)
    y, got = ssm_step.ssm_step_in_place(jnp.asarray(pool), 0, *rows)
    want_y, want = _reference(pool, 0, jnp.minimum(rows[0], 7), *rows[1:])
    _close(y, want_y)
    _close(got, want)
    assert not np.asarray(y)[[1, 3]].any()
    untouched = [s for s in range(8) if s not in (2, 7, 4)]
    np.testing.assert_array_equal(np.asarray(got)[0, untouched], pool[0, untouched])
    np.testing.assert_array_equal(np.asarray(got)[1], pool[1])


@SHAPES
def test_a_sequence_with_nothing_seen_starts_from_zeros_over_a_slot_of_nan(shape):
    """A product with ``started`` would keep the ``nan``; the slot is not read."""
    pool, rows = _case(shape, slot=[1, 6, 3], live=[1, 1, 1], started=[0, 1, 0], seed=2,
                       fill=np.nan)
    pool[:, 6] = np.random.default_rng(9).standard_normal(pool.shape[2:])
    y, got = ssm_step.ssm_step_in_place(jnp.asarray(pool), 1, *rows)
    want_y, want = _reference(pool, 1, *rows)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(got)[1, [1, 6, 3]]).all()
    _close(y, want_y)
    _close(got[1], want[1])


@pytest.mark.parametrize("slot", [[3, 4, 5, 2], [7, 0, 6], [7]],
                         ids=["adjacent-slots", "last-slot-first", "last-slot-alone"])
@pytest.mark.parametrize("shape", [PUBLISHED, TINY], ids=["published-16-heads", "tiny"])
def test_rows_in_adjacent_slots_and_in_the_last_slot(shape, slot):
    n = len(slot)
    pool, rows = _case(shape, slot=slot, live=[1] * n, started=[1] * n, seed=3)
    y, got = ssm_step.ssm_step_in_place(jnp.asarray(pool), 0, *rows)
    want_y, want = _reference(pool, 0, *rows)
    _close(y, want_y)
    _close(got, want)


@pytest.mark.parametrize("shape", [PUBLISHED, TINY], ids=["published-16-heads", "tiny"])
def test_two_blocks_of_one_pool_each_leave_the_others_slots_untouched(shape):
    """Block 0 then block 2 of a three-block pool, the same rows: each call
    changes its own block's live slots and nothing else."""
    pool, rows = _case(shape, slot=[4, 1], live=[1, 1], started=[1, 0], blocks=3, seed=4)
    _, first = ssm_step.ssm_step_in_place(jnp.asarray(pool), 0, *rows)
    first = np.asarray(first)
    np.testing.assert_array_equal(first[1:], pool[1:])
    _close(first, _reference(pool, 0, *rows)[1])
    y, second = ssm_step.ssm_step_in_place(jnp.asarray(first), 2, *rows)
    want_y, want = _reference(first, 2, *rows)
    np.testing.assert_array_equal(np.asarray(second)[:2], first[:2])
    _close(y, want_y)
    _close(second, want)


def test_more_tiles_than_buffers_walk_the_rows_in_order(monkeypatch):
    """Tiles of one unit of two heads: eight a row, so fetches run ahead across
    rows, past a dead row and a row that reads nothing."""
    monkeypatch.setattr(ssm_step, "TILE_BYTES", 2 * 64 * 128 * 4)
    jax.clear_caches()
    assert ssm_step.tiling(16, 64, 128) == (2, 2)
    pool, rows = _case(PUBLISHED, slot=[5, 1, 0, 7, 2, 6], live=[1, 0, 1, 1, 0, 1],
                       started=[1, 1, 0, 1, 1, 1], seed=5)
    y, got = ssm_step.ssm_step_in_place(jnp.asarray(pool), 1, *rows)
    jax.clear_caches()
    want_y, want = _reference(pool, 1, *rows)
    _close(y, want_y)
    _close(got, want)


@pytest.mark.parametrize("shape, dtype", [((8, 8, 16, 2), jnp.float32), ((4, 4, 128, 2), jnp.float32),
                                          (TINY, jnp.bfloat16)],
                         ids=["state-16-wide", "head-dim-4", "a-bf16-pool"])
def test_a_pool_off_the_rule_falls_back_and_agrees(shape, dtype):
    """``ssm.step_in_place`` chooses by the pool's type alone: off the rule it
    is ``ssm.step`` between a gather and a dropping scatter."""
    H, P, N, G = shape
    pool, rows = _case(shape, slot=[2, 8, 5], live=[1, 0, 1], started=[1, 1, 0], seed=6)
    pool = np.asarray(jnp.asarray(pool, dtype).astype(jnp.float32))  # what the dtype holds
    assert not ssm.in_place(jnp.zeros((2, 8, H, P, N), dtype), G)
    y, got = ssm.step_in_place(jnp.asarray(pool, dtype), 1, *rows)
    want_y, want = _reference(pool, 1, jnp.minimum(rows[0], 7), *rows[1:])
    tol = TOL if dtype == jnp.float32 else 2e-2
    live = np.asarray(rows[1])
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live], rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want, rtol=tol, atol=tol)
    assert got.dtype == dtype


@SHAPES
def test_a_pool_on_the_rule_runs_the_kernel_through_the_modules_entry(shape):
    H, P, N, G = shape
    pool, rows = _case(shape, slot=[2, 8, 5], live=[1, 0, 1], started=[1, 1, 0], seed=7)
    assert ssm.in_place(jnp.asarray(pool), G)
    text = jax.jit(ssm.step_in_place, static_argnums=1).lower(jnp.asarray(pool), 0, *rows).as_text()
    assert "gather" not in text and "scatter" not in text
    y, got = ssm.step_in_place(jnp.asarray(pool), 0, *rows)
    want_y, want = _reference(pool, 0, jnp.minimum(rows[0], 7), *rows[1:])
    _close(y, want_y)
    _close(got, want)
