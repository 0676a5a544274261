"""The chunked delta rule as a kernel (PR 55, ``ops/pallas/kda_chunk.py``), in
interpret mode, held to ``kda.chunk`` and to the token-by-token ``kda.step``:
(a) the regime where a factorised product underflows and a slow decay; (b) a
ragged batch (a segment crossing three chunks, two segments sharing one chunk,
one-row segments between them, nobody's rows, the bucket's padding); (c) the
slots (a sequence with nothing seen starts from zeros whatever its slot held,
one that is not live keeps its slot bit for bit, no other slot changes); (d) a
pool off the kernel's rule takes the ``jax.numpy`` path. The engine-level tests
of ``test_solar_open2.py`` run the kernel too (128 x 128 heads, chunks of 16)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.modules import kda
from deepspeed_tpu.ops.pallas import kda_chunk
from tests.unit.inference.v2.test_solar_open2 import _mixer_inputs, _recurrence, _spoilt_pool

# three bf16 passes on the state's products (XLA's HIGH), against float32 on the CPU
TOL = 5e-5


def _one_segment(q, k, v, g, beta, pool, slot, rows, started=True):
    """The kernel over ONE segment of all T rows against slot ``slot`` of layer 1."""
    T = q.shape[0]
    one = lambda x, dtype: jnp.asarray([x], dtype)  # noqa: E731
    enters, visits = kda.visits_of(np.array([0]), np.array([T]), np.array([True]), rows)
    return kda_chunk.kda_chunk_in_place(
        jnp.asarray(pool), 1, one(slot, jnp.int32), one(started, bool), one(0, jnp.int32),
        one(T, jnp.int32), jnp.asarray(enters), jnp.asarray(visits), q, k, v, g, beta, rows=rows)


@pytest.mark.parametrize("A, T, rows", [(16.0, 128, 64), (16.0, 64, 16), (0.05, 128, 64)],
                         ids=["underflow-four-sub-chunks", "underflow-one-sub-chunk",
                              "slow-decay"])
def test_the_kernel_is_the_chunked_form_and_the_recurrence(A, T, rows):
    """(a) At A = 16 ``exp(-G)`` of a factorised form is past float32 inside a
    chunk; at A = 0.05 the state outlives the rows, so what a visit reads of
    ``S_0`` and what it leaves both count. One segment, a visit a chunk, the
    state staying in vector memory between them."""
    q, k, v, g, beta = _mixer_inputs(10, T, A=A)
    if A > 1:
        assert float(-g.min()) * rows > 88.0
    pool = _spoilt_pool(11)
    o, after = _one_segment(q, k, v, g, beta, pool, 3, rows)
    o, after = np.asarray(o), np.asarray(after)
    h0 = jnp.asarray(pool[1, 3])
    want_o, want_h = _recurrence(q, k, v, g, beta, h0[None])
    assert np.isfinite(o).all()
    assert np.abs(o - want_o).max() < TOL and np.abs(after[1, 3] - want_h).max() < TOL
    h, outs = h0, []
    for c in range(T // rows):
        at = slice(c * rows, (c + 1) * rows)
        o_c, h = kda.chunk(q[at], k[at], v[at], g[at], beta[at], h)
        outs.append(np.asarray(o_c))
    assert np.abs(o - np.concatenate(outs)).max() < TOL
    assert np.abs(after[1, 3] - np.asarray(h)).max() < TOL
    others = [s for s in range(pool.shape[1]) if s != 3]
    np.testing.assert_array_equal(after[1, others], pool[1, others])
    np.testing.assert_array_equal(after[0], pool[0])


RAGGED = {
    # 70 rows cross chunks 0, 1 and 2 of 32; two decode rows; 20 rows share chunk 2 with the
    # first segment; a sequence without rows; 36 rows of padding (chunk 3 is nobody's)
    "three-chunks-and-a-shared-one": (128, 32, [70, 1, 1, 20, 0]),
    # segments that end and start inside one chunk of 64, decode rows between them
    "two-in-one-chunk-of-64": (128, 64, [30, 1, 25, 1, 50, 0]),
    # every row a decode row or nobody's: no visit at all
    "no-visit": (64, 16, [1, 1, 0, 1]),
}


@pytest.mark.parametrize("T, rows, ntoks", RAGGED.values(), ids=RAGGED.keys())
def test_the_scan_by_segment_through_the_kernel_over_a_ragged_batch(T, rows, ntoks):
    """(b), (c): ``scan_in_place`` with the visits in the kernel. Every
    segment starts from ITS slot (zeros where nothing was seen, whatever the
    slot held) and leaves its final state there; the second of the longer
    segments has seen nothing; the last live one is NOT live (its rows are
    somebody's in the batch, its slot must not change); nobody's rows read
    zeros; no other slot and no other layer changes by a bit."""
    S = len(ntoks)
    q, k, v, g, beta = _mixer_inputs(12, T, A=2.0)
    seq_ntok = np.array(ntoks)
    seq_start = np.concatenate([[0], np.cumsum(seq_ntok)[:-1]])
    n = int(seq_ntok.sum())
    token_seq = np.concatenate([np.repeat(np.arange(S), seq_ntok), np.full(T - n, S - 1)])
    valid = np.arange(T) < n
    pool = _spoilt_pool(13, (2, 8, 2, 128, 128))
    slot = np.array([6, 1, 4, 0, 2, 7])[:S]
    longer = np.flatnonzero(seq_ntok > 1)
    started = np.ones(S, bool)
    live = seq_ntok > 0
    if len(longer) > 1:
        started[longer[1]] = False
    if len(longer) > 2:
        live[longer[-1]] = False
    assert kda.chunks_in_kernel(jnp.asarray(pool), rows)
    o, after = jax.jit(kda.scan_in_place, static_argnames="rows")(
        jnp.asarray(pool), 1, jnp.asarray(slot), jnp.asarray(live), jnp.asarray(started),
        jnp.asarray(seq_start), jnp.asarray(seq_ntok), jnp.asarray(token_seq.astype(np.int32)),
        jnp.asarray(valid), q, k, v, g, beta, rows=rows)
    o, after = np.asarray(o), np.asarray(after)
    touched = []
    for seq in np.flatnonzero(live):
        at = slice(seq_start[seq], seq_start[seq] + seq_ntok[seq])
        h0 = jnp.asarray(pool[1, slot[seq]] if started[seq] else np.zeros_like(pool[0, 0]))[None]
        want_o, want_h = _recurrence(q[at], k[at], v[at], g[at], beta[at], h0)
        assert np.abs(o[at] - want_o).max() < TOL, seq
        assert np.abs(after[1, slot[seq]] - want_h).max() < TOL, seq
        touched.append(slot[seq])
    for seq in np.flatnonzero(~live & (seq_ntok > 0)):  # somebody's rows, nobody's state
        assert not o[seq_start[seq]:seq_start[seq] + seq_ntok[seq]].any()
    assert not o[n:].any()
    untouched = [s for s in range(pool.shape[1]) if s not in touched]
    np.testing.assert_array_equal(after[1, untouched], pool[1, untouched])
    np.testing.assert_array_equal(after[0], pool[0])


def test_a_visit_table_names_each_visit_and_parks_the_rest():
    """The scalars the kernel walks by: the visits in the segments' order, the
    rows ``[lo, hi)`` of the chunk that are the segment's, first / last / seen /
    new-chunk, the segment's ordinal among the visited; a visit past the count
    is not live and names the last live visit's chunk, so no block moves."""
    seq_ntok, seq_start = np.array([70, 1, 20, 0]), np.array([0, 70, 71, 91])
    started = np.array([True, True, False, True])
    enters, visits = kda.visits_of(seq_start, seq_ntok, seq_ntok > 1, 32)
    V = kda_chunk.max_visits(128, 4, 32)
    counts, slot, chunk, lo, hi, flags, ordinal = (np.asarray(a) for a in kda_chunk.visit_table(
        jnp.asarray([5, 6, 7, 8]), jnp.asarray(started), jnp.asarray(seq_start),
        jnp.asarray(seq_ntok), jnp.asarray(enters), jnp.asarray(visits), 32, V))
    F = kda_chunk
    assert V == 7 and list(counts) == [4, 2]
    assert list(slot[:4]) == [5, 5, 5, 7] and list(chunk) == [0, 1, 2, 2, 2, 2, 2]
    assert list(zip(lo[:4], hi[:4])) == [(0, 32), (0, 32), (0, 6), (7, 27)]
    assert list(flags[:4]) == [F.FIRST | F.STARTED | F.NEW_CHUNK, F.STARTED | F.NEW_CHUNK,
                               F.LAST | F.STARTED | F.NEW_CHUNK, F.FIRST | F.LAST]
    assert not flags[4:].any() and list(ordinal[:4]) == [0, 0, 0, 1]


@pytest.mark.parametrize("shape, dtype, D", [((1, 4, 2, 64, 64), jnp.float32, 64),
                                             ((1, 4, 2, 128, 128), jnp.bfloat16, 128)],
                         ids=["heads-of-64", "a-bf16-pool"])
def test_a_pool_off_the_rule_takes_the_numpy_path(shape, dtype, D, monkeypatch):
    """(d) By the pool's type and shape alone, the same answer on every
    backend: heads off 128 x 128 or a pool that is not float32 run
    ``kda.chunk`` a visit, with the kernel's contract; the kernel is not
    called."""
    def never(*args, **kwargs):
        raise AssertionError("the chunk kernel was called on a pool off its rule")

    monkeypatch.setattr(kda_chunk, "kda_chunk_in_place", never)
    pool = jnp.asarray(_spoilt_pool(14, shape)).astype(dtype)
    assert not kda.chunks_in_kernel(pool, 16)
    assert kda.chunks_in_kernel(jnp.zeros((1, 2, 2, 128, 128)), 16)
    assert not kda.chunks_in_kernel(jnp.zeros((1, 2, 2, 128, 128)), 8)  # no whole sub-chunk
    T = 32
    q, k, v, g, beta = _mixer_inputs(15, T, D=D, A=1.0)
    slot, live, started = np.array([2, 0]), np.array([1, 1], bool), np.array([1, 0], bool)
    seq_ntok, seq_start = np.array([21, 11]), np.array([0, 21])
    token_seq = np.repeat(np.arange(2), seq_ntok).astype(np.int32)
    o, after = kda.scan_in_place(pool, 0, jnp.asarray(slot), jnp.asarray(live),
                                 jnp.asarray(started), jnp.asarray(seq_start),
                                 jnp.asarray(seq_ntok), jnp.asarray(token_seq),
                                 jnp.ones(T, bool), q, k, v, g, beta, rows=16)
    tol = 1e-5 if dtype == jnp.float32 else 0.1  # a bf16 pool rounds the state it keeps
    for seq in range(2):
        at = slice(seq_start[seq], seq_start[seq] + seq_ntok[seq])
        h0 = pool[0, slot[seq]].astype(jnp.float32) * started[seq]
        want_o, want_h = _recurrence(q[at], k[at], v[at], g[at], beta[at], h0[None])
        assert np.abs(np.asarray(o[at]) - want_o).max() < tol
        assert np.abs(np.asarray(after[0, slot[seq]], np.float32) - want_h).max() < tol
    np.testing.assert_array_equal(np.asarray(after[0, [1, 3]], np.float32),
                                  np.asarray(pool[0, [1, 3]], np.float32))
