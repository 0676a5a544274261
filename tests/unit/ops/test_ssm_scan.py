"""A ``put`` step's scan by segment, in the pool (``ssm.scan_in_place``), in
interpret mode, float32: against ``ssm.scan_ragged`` on the gathered states
(the form it replaces on a pool on the rule and falls back to off it) AND
against ``ssm.step`` token by token, at both hybrid families' widths cut in
heads only."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.modules import ssm

TOL = 1e-4  # tests/unit/inference/v2/test_nemotron_h.py's
# (H, P, N, G): Falcon-H1-34B's head (128 x 256, a unit one head, two heads a
# group) and Nemotron-3-Nano's (64 x 128, a unit two heads, a group a unit)
FALCON_H1, NEMOTRON = (4, 128, 256, 2), (8, 64, 128, 4)
SHAPES = pytest.mark.parametrize("shape", [FALCON_H1, NEMOTRON],
                                 ids=["falcon-h1-heads-of-128x256", "nemotron-heads-of-64x128"])
CHUNK = 8


def _case(shape, ntok, slot, started=None, T=None, blocks=2, slots=8, seed=0, fill=None,
          dtype=np.float32):
    """A pool and a step: sequence i holds ``ntok[i]`` rows (None: not a
    sequence at all), side by side in the sequences' order, padding behind."""
    H, P, N, G = shape
    S = len(ntok)
    valid = np.array([n is not None for n in ntok])
    ntok = np.array([n or 0 for n in ntok], np.int32)
    T = T or int(-(-max(ntok.sum(), 1) // CHUNK) * CHUNK)
    started = np.ones(S, bool) if started is None else np.asarray(started, bool)
    r = np.random.default_rng(seed)
    pool = r.standard_normal((blocks, slots, H, P, N)).astype(dtype)
    if fill is not None:
        pool[:, np.asarray(slot)[valid & ~started]] = fill
    token_seq = np.concatenate([np.repeat(np.arange(S), ntok),
                                np.full(T - ntok.sum(), S - 1)]).astype(np.int32)
    batch = dict(slot=jnp.asarray(slot, jnp.int32), live=jnp.asarray(valid & (ntok > 0)),
                 started=jnp.asarray(valid & started),
                 seq_start=jnp.asarray(np.cumsum(ntok) - ntok, jnp.int32), seq_ntok=jnp.asarray(ntok),
                 token_seq=jnp.asarray(token_seq), token_valid=jnp.arange(T) < ntok.sum())
    x = r.standard_normal((T, H, P)).astype(np.float32)
    dt = np.log1p(np.exp(r.standard_normal((T, H)) - 2)).astype(np.float32)
    A = -r.uniform(1, 16, size=H).astype(np.float32)
    B, C = r.standard_normal((2, T, G, N)).astype(np.float32)
    return pool, batch, tuple(jnp.asarray(v) for v in (x, dt, A, B, C))


def _scan(pool, block, batch, rows, chunk=CHUNK):
    return ssm.scan_in_place(jnp.asarray(pool), block, *batch.values(), *rows, chunk=chunk)


def _ragged(pool, block, batch, rows, chunk=CHUNK):
    """The form replaced: ``scan_ragged`` on the gathered states, a live
    sequence's final state put back by hand."""
    slot = np.minimum(np.asarray(batch["slot"]), pool.shape[1] - 1)
    h0 = np.where(np.asarray(batch["started"])[:, None, None, None],
                  pool[block, slot].astype(np.float32), 0.0)
    onehot = ssm.segments(batch["token_seq"], batch["token_valid"], slot.size)
    y, h = ssm.scan_ragged(*rows, jnp.asarray(h0), onehot, chunk)
    want = np.array(pool)
    for i in np.flatnonzero(np.asarray(batch["live"])):
        want[block, slot[i]] = np.asarray(h[i])
    return np.where(np.asarray(batch["token_valid"])[:, None, None], np.asarray(y), 0.0), want


def _token_by_token(pool, block, batch, rows):
    """``ssm.step`` over each live sequence's rows in turn."""
    x, dt, A, B, C = rows
    y, want = np.zeros(x.shape, np.float32), np.array(pool)
    for i in np.flatnonzero(np.asarray(batch["live"])):
        s, at = int(batch["slot"][i]), int(batch["seq_start"][i])
        h = jnp.asarray(pool[block, s:s + 1]) if bool(batch["started"][i]) \
            else jnp.zeros((1, ) + pool.shape[2:], jnp.float32)
        for t in range(at, at + int(batch["seq_ntok"][i])):
            row, h = ssm.step(x[t:t + 1], dt[t:t + 1], A, B[t:t + 1], C[t:t + 1], h)
            y[t] = np.asarray(row[0])
        want[block, s] = np.asarray(h[0])
    return y, want


def _agrees(pool, block, batch, rows, got_y, got, tol=TOL):
    for want_y, want in (_ragged(pool, block, batch, rows), _token_by_token(pool, block, batch, rows)):
        np.testing.assert_allclose(np.asarray(got_y), want_y, rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)
    others = np.ones(pool.shape[:2], bool)
    others[block, np.asarray(batch["slot"])[np.asarray(batch["live"])]] = False
    np.testing.assert_array_equal(np.asarray(got)[others], pool[others])  # bit for bit


@SHAPES
def test_every_segment_one_row_is_the_recurrence_in_the_slot(shape):
    assert ssm.in_place(jnp.zeros((2, 8) + shape[:3], jnp.float32), shape[3])
    pool, batch, rows = _case(shape, ntok=[1, 1, 1, 1, 1], slot=[5, 0, 3, 6, 2])
    y, got = _scan(pool, 1, batch, rows)
    _agrees(pool, 1, batch, rows, y, got)
    assert y.dtype == jnp.float32 and got.dtype == jnp.float32 and y.shape == rows[0].shape


@SHAPES
def test_one_long_segment_straddling_two_chunks_beside_one_row_segments(shape):
    """A ``put`` step as the chat cell makes it: decode rows around ONE prompt
    chunk of 13 rows, which starts in the batch's first chunk of 8 and ends in
    its second: two visits, each beside one-row segments' rows in its chunk."""
    pool, batch, rows = _case(shape, ntok=[1, 1, 13, 1, 1], slot=[5, 0, 3, 6, 2], T=24, seed=1)
    y, got = _scan(pool, 0, batch, rows)
    _agrees(pool, 0, batch, rows, y, got)
    assert not np.asarray(y)[17:].any()  # nobody's rows


@SHAPES
def test_several_multi_row_segments_in_one_step(shape):
    """Any number of prompt chunks a step (Dynamic SplitFuse): 2, 9, 8 and 17
    rows (one, two, two and three visits: a segment pays a visit a chunk of the
    batch it has rows in) with one-row segments between, some chunks visited by
    two segments."""
    pool, batch, rows = _case(shape, ntok=[2, 1, 9, 8, 1, 17], slot=[1, 7, 4, 0, 5, 2], T=40, seed=2)
    assert int(batch["token_valid"].sum()) == 38
    y, got = _scan(pool, 1, batch, rows)
    _agrees(pool, 1, batch, rows, y, got)


@SHAPES
def test_a_sequence_without_rows_keeps_its_slot_bit_for_bit(shape):
    """Sequence 1 is live in the engine and has no row in the step, 3 is no
    sequence and names one past the last slot: neither slot is written."""
    pool, batch, rows = _case(shape, ntok=[1, 0, 11, None, 1], slot=[5, 3, 0, 8, 2], seed=3)
    y, got = _scan(pool, 0, batch, rows)
    _agrees(pool, 0, batch, rows, y, got)
    np.testing.assert_array_equal(np.asarray(got)[0, 3], pool[0, 3])
    np.testing.assert_array_equal(np.asarray(got)[1], pool[1])


@SHAPES
def test_a_sequence_with_nothing_seen_starts_from_zeros_over_a_slot_of_nan(shape):
    """One row and many: a product with ``started`` would keep the ``nan``; the
    slot is not read. The long segment's SECOND visit reads what its first left."""
    pool, batch, rows = _case(shape, ntok=[1, 12, 1, 3], slot=[1, 6, 3, 4], started=[0, 0, 1, 0],
                              seed=4, fill=np.nan)
    y, got = _scan(pool, 1, batch, rows)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(got)[1, [1, 6, 3, 4]]).all()
    pool = np.nan_to_num(pool)  # the references' gathers: nothing of a slot not started is read
    _agrees(pool, 1, batch, rows, y, np.nan_to_num(np.asarray(got)))


@pytest.mark.parametrize("ntok, slot", [([3, 1, 2, 1], [3, 4, 5, 2]), ([9, 1, 1], [7, 0, 6]), ([10], [7])],
                         ids=["adjacent-slots", "last-slot-first", "last-slot-alone"])
@SHAPES
def test_segments_in_adjacent_slots_and_in_the_last_slot(shape, ntok, slot):
    pool, batch, rows = _case(shape, ntok=ntok, slot=slot, seed=5)
    y, got = _scan(pool, 0, batch, rows)
    _agrees(pool, 0, batch, rows, y, got)


@SHAPES
def test_two_blocks_of_one_pool_in_turn(shape):
    """Block 0 then block 2 of a three-block pool, the same step: each call
    changes its own block's live slots and nothing else."""
    pool, batch, rows = _case(shape, ntok=[1, 10, 1], slot=[4, 1, 6], started=[1, 0, 1], blocks=3, seed=6)
    y, first = _scan(pool, 0, batch, rows)
    first = np.asarray(first)
    _agrees(pool, 0, batch, rows, y, first)
    y, second = _scan(first, 2, batch, rows)
    _agrees(first, 2, batch, rows, y, second)
    np.testing.assert_array_equal(np.asarray(second)[:2], first[:2])


@SHAPES
def test_the_batch_in_one_chunk_and_in_many_is_the_same_scan(shape):
    pool, batch, rows = _case(shape, ntok=[1, 21, 1, 6], slot=[2, 5, 0, 7], T=32, seed=7)
    y8, got8 = _scan(pool, 1, batch, rows, chunk=8)
    y32, got32 = _scan(pool, 1, batch, rows, chunk=32)
    _agrees(pool, 1, batch, rows, y32, got32)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y32), rtol=TOL, atol=TOL)
    np.testing.assert_allclose(np.asarray(got8), np.asarray(got32), rtol=TOL, atol=TOL)


@pytest.mark.parametrize("shape, dtype", [((8, 8, 16, 2), np.float32), ((4, 4, 128, 2), np.float32),
                                          (NEMOTRON, jnp.bfloat16)],
                         ids=["state-16-wide", "head-dim-4", "a-bf16-pool"])
def test_a_pool_off_the_rule_falls_back_and_agrees(shape, dtype):
    """Off ``ssm.in_place``'s rule the step is ``scan_ragged`` between
    ``ssm.load`` and ``ssm.store_in_place``, as it was."""
    pool, batch, rows = _case(shape, ntok=[1, 11, 0, 1], slot=[2, 5, 8, 7], started=[1, 0, 1, 1], seed=8)
    pool = np.asarray(jnp.asarray(pool, dtype).astype(jnp.float32))  # what the dtype holds
    assert not ssm.in_place(jnp.zeros(pool.shape, dtype), shape[3])
    y, got = ssm.scan_in_place(jnp.asarray(pool, dtype), 1, *batch.values(), *rows, chunk=CHUNK)
    assert got.dtype == dtype
    tol = TOL if dtype == np.float32 else 2e-2
    want_y, want = _ragged(pool, 1, batch, rows)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want, rtol=tol, atol=tol)


@pytest.mark.parametrize("shape, on_the_rule", [(FALCON_H1, True), (NEMOTRON, True),
                                                ((8, 8, 16, 2), False)],
                         ids=["falcon-h1", "nemotron", "state-16-wide"])
def test_the_modules_entry_chooses_by_the_pools_type_alone(shape, on_the_rule):
    """On the rule: the step kernel over the pool and a loop of one-state
    visits, no state a SEQUENCE of the step anywhere; off it, the slot copies
    (or XLA's gather) around ``scan_ragged``."""
    pool, batch, rows = _case(shape, ntok=[1, 11, 1, 1], slot=[2, 5, 0, 7])
    assert ssm.in_place(jnp.asarray(pool), shape[3]) == on_the_rule
    scan = jax.jit(ssm.scan_in_place, static_argnums=1, static_argnames="chunk")
    text = scan.lower(jnp.asarray(pool), 0, *batch.values(), *rows, chunk=CHUNK).as_text()
    H, P, N, _ = shape
    states = f"tensor<4x{H}x{P}x{N}xf32>"  # [S, H, P, N]
    assert ("ssm_step_in_place" in text) == on_the_rule
    assert ("stablehlo.while" in text) == on_the_rule
    assert (states in text) != on_the_rule
