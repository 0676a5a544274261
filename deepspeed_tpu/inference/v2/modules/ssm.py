"""The Mamba-2 mixer's state-space part over a ragged batch, in two forms that
agree (tier-1 holds them against each other and against the token-by-token
reference, ``tests/unit/inference/v2/test_nemotron_h.py``,
``tests/unit/ops/test_ssm_scan.py``). Which runs where: a ``put`` step runs
:func:`scan_in_place`, the scan by SEGMENT inside the engine's pool — a
sequence's state is visited once a mixer, where it lies in its slot, by that
sequence's rows alone: a segment of one row (31 of a chat step's 32) is the
recurrence, all of them in one call of the step kernel; a longer one goes
through the matrix form (:func:`_chunk`) against its own state, a visit a
chunk of the batch it has rows in; a ``decode_loop`` step runs :func:`step_in_place`, the recurrence
inside that pool; :func:`step` is the recurrence as written, the reference the
others are held to. A pool off the kernel's shape rule (:func:`in_place`)
falls back: a ``put`` step to :func:`scan_ragged` on EVERY sequence's state,
each copied out of its slot by :func:`load` and back by
:func:`store_in_place` (until PR 49 every ``put`` step: at 32 sequences a step
that form contracts every row against every sequence's state and re-lays and
carries all of them through HBM, 3.6 ms a mixer at Falcon-H1-34B's widths
where the visits take 0.7; PERF.md section 6, PR 49), a ``decode_loop`` step
to :func:`step` between a gather and a scatter.

A head's state is ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t`` in
``R^{P x N}``, float32, and ``y_t = h_t C_t``; heads read B and C by group
(head h reads group ``h // (H / G)``). The state belongs to a SEQUENCE: a step
is handed each sequence's state ``[S, H, P, N]`` as it was left by the step
before and hands back what the step leaves. Neither form makes a state a
token.

:func:`scan_ragged`, a ``put`` step: flat tokens of several sequences, a
sequence's tokens side by side (its SEGMENT), decode rows of one token beside
a prefill chunk. The published chunked form over that axis: the batch is cut
into chunks of ``chunk`` tokens; inside a chunk ``y = (L * C B^T) (dt x)``
with ``L[t, s]`` the decay from s to t where s and t are one sequence's and
``s <= t`` and 0 elsewhere, plus each token's reading of its sequence's state
AS IT ENTERED THE CHUNK through the decay from the segment's start; across
chunks each sequence's state is carried: decayed by the whole of its segment's
part in the chunk, plus what that part adds. A segment may straddle chunks, a
chunk may hold many segments. The sums of log-decays are float32 (the masks'
matmuls at ``highest`` precision). The products that read or make the state
run at ``high`` precision (three bf16 passes where the backend's default for
float32 operands is one): a float32 state read through one bf16 pass is a bf16
state, and the noise it puts on every later row's hidden state is enough to
flip a router's choice at gaps the comparison with the float32 reference holds
to its tight tolerance (read on the chip: PERF.md section 6, PR 43).

:func:`step`: one token a sequence, the recurrence as written above, on states
handed in and handed back. :func:`step_in_place`, a ``decode_loop`` step: the
same over the engine's pool ``[blocks, slots, H, P, N]``, a row's state read
from its slot and left there — one Pallas kernel
(``ops/pallas/ssm_step.py``) where :func:`in_place` says the shapes allow it,
:func:`step` between a gather and a scatter where not.

:func:`conv_ragged` / :func:`conv_step`: the causal depthwise convolution in
front of the scan, a token seeing the ``K - 1`` rows before it of ITS OWN
sequence, those ahead of a segment's first row from the sequence's kept tail.
The pool keeps a sequence's tails FOLDED into whole tiles (:func:`conv_slot`,
:func:`fold_tails` / :func:`unfold_tails`), so that :func:`load` and
:func:`store_in_place` move a step's own tails by their kernel as they move a
float32 state.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import ssm_step, ssm_store

_HIGHEST = jax.lax.Precision.HIGHEST
_HIGH = jax.lax.Precision.HIGH


def segments(token_seq, token_valid, n_seqs: int):
    """``[T, S]`` bool: token t is a live token of sequence i."""
    return (token_seq[:, None] == jnp.arange(n_seqs)[None, :]) & token_valid[:, None]


# ------------------------------------------------------------- convolution --
def conv_ragged(xbc, weight, bias, tail, token_seq, seq_start, seq_ntok):
    """``xbc`` [T, C]; ``weight`` [C, K]; ``tail`` [S, K - 1, C], a sequence's
    last K - 1 rows before this step (zeros for a new one); ``token_seq`` [T]
    each token's sequence (a padding row's output is nobody's); ``seq_start``
    [S] the flat index of each segment's first token, ``seq_ntok`` [S] its
    length. Returns ``(out [T, C] float32, the new tails [S, K - 1, C])``; a
    sequence without tokens keeps its tail."""
    T = xbc.shape[0]
    K = weight.shape[1]
    local = jnp.arange(T) - seq_start[token_seq]  # the token's place in its segment
    x32 = xbc.astype(jnp.float32)
    w = weight.astype(jnp.float32)
    out = x32 * w[None, :, K - 1] + bias.astype(jnp.float32)[None, :]
    for d in range(1, K):  # the row d places back
        here = jnp.pad(x32, ((d, 0), (0, 0)))[:T]
        kept = tail[token_seq, jnp.clip(K - 1 + local - d, 0, K - 2)].astype(jnp.float32)
        out = out + jnp.where((local >= d)[:, None], here, kept) * w[None, :, K - 1 - d]
    # the tail a segment leaves: the last K - 1 rows of (old tail ++ segment)
    j = jnp.arange(K - 1)[None, :]
    at = seq_ntok[:, None] - (K - 1) + j  # [S, K - 1], the row's place in the segment
    new = x32[jnp.clip(seq_start[:, None] + at, 0, T - 1)]
    old = jnp.take_along_axis(tail.astype(jnp.float32),
                              jnp.clip(at + K - 1, 0, K - 2)[:, :, None], axis=1)
    return out, jnp.where((at >= 0)[:, :, None], new, old).astype(tail.dtype)


def conv_step(xbc, weight, bias, tail):
    """One token a sequence: ``xbc`` [S, C], ``tail`` [S, K - 1, C]."""
    window = jnp.concatenate([tail.astype(jnp.float32), xbc.astype(jnp.float32)[:, None]], axis=1)
    out = jnp.einsum("skc,ck->sc", window, weight.astype(jnp.float32)) \
        + bias.astype(jnp.float32)[None, :]
    return out, window[:, 1:].astype(tail.dtype)


def conv_slot(rows: int, channels: int):
    """The slot a pool keeps a sequence's ``rows`` = K - 1 tails of ``channels``
    channels in. Where they fill a tile: their ``rows x channels`` values in
    order, folded to whole (sublane, lane) tiles ``[8, 128 k]`` with zeros behind
    them where the widths do not divide (``ssm_store.supported``'s rule; a pool
    of ``[3, C]`` rows the chip's compiler re-lays in tiles of four around a
    chunk's steps and carries whole through vector memory twice a step: PERF.md
    section 6, PR 53). Fewer values than one tile stay ``[rows, channels]``: the
    padding would multiply them."""
    tile = ssm_store.SUBLANES * ssm_store.LANES
    if rows * channels < tile:
        return rows, channels
    return ssm_store.SUBLANES, -(-rows * channels // tile) * ssm_store.LANES


def fold_tails(tail, slot):
    """``tail`` [S, K - 1, C] as rows of the pool's ``slot`` shape
    (:func:`conv_slot`): the same values in the same order, zeros behind."""
    flat = tail.reshape(tail.shape[0], -1)
    flat = jnp.pad(flat, ((0, 0), (0, slot[0] * slot[1] - flat.shape[1])))
    return flat.reshape((-1, ) + tuple(slot))


def unfold_tails(rows, k_less_one: int, channels: int):
    """:func:`fold_tails` back: ``rows`` [S, *slot] as ``[S, K - 1, C]``."""
    flat = rows.reshape(rows.shape[0], -1)[:, :k_less_one * channels]
    return flat.reshape(-1, k_less_one, channels)


# -------------------------------------------------------------------- scan --
def _chunk(x, dt, a, B, C, h, onehot):
    """One chunk of Q tokens. x [Q, G, R, P]; dt, a [Q, G, R] (a = dt x A, 0
    for a row that is no sequence's); B, C [Q, G, N]; h [S, G, R, P, N];
    onehot [Q, S]."""
    Q = x.shape[0]
    f32 = jnp.float32
    oh = onehot.astype(f32)
    same = (oh @ oh.T) > 0  # 0 / 1 operands: exact whatever the precision
    at = jnp.arange(Q)
    causal = same & (at[None, :] <= at[:, None])  # [t, s]: s is t's own, at or before it
    after = same & (at[None, :] > at[:, None])
    # float32 sums of log-decays: from the segment's start to t (inclusive),
    # from behind t to the end of the segment's part in this chunk, the whole part
    lcs = jnp.einsum("ts,sgr->tgr", causal.astype(f32), a, precision=_HIGHEST)
    rest = jnp.einsum("ts,sgr->tgr", after.astype(f32), a, precision=_HIGHEST)
    whole = jnp.einsum("ti,tgr->igr", oh, a, precision=_HIGHEST)
    # inside the chunk
    decay = jnp.where(causal[:, :, None, None],
                      jnp.exp(jnp.minimum(lcs[:, None] - lcs[None, :], 0.0)), 0.0)  # [t, s, G, R]
    cb = jnp.einsum("tgn,sgn->tsg", C, B, precision=_HIGH)
    y = jnp.einsum("tsgr,sgrp->tgrp", decay * cb[..., None] * dt[None], x, precision=_HIGH)
    # the state that entered: each token reads ITS sequence's, one contraction
    # over (sequence, n) with the others' rows zero
    c_own = oh[:, None, :, None] * C[:, :, None, :]  # [Q, G, S, N]
    y = y + jnp.exp(lcs)[..., None] * jnp.einsum("tgin,igrpn->tgrp", c_own, h, precision=_HIGH)
    # the state that leaves
    b_own = oh[:, :, None, None] * B[:, None, :, :]  # [Q, S, G, N]
    add = jnp.einsum("tgrp,tign->igrpn", x * (dt * jnp.exp(rest))[..., None], b_own,
                     precision=_HIGH)
    return y, jnp.exp(whole)[..., None, None] * h + add


def scan_ragged(x, dt, A, B, C, h0, onehot, chunk: int):
    """x [T, H, P]; dt [T, H] float32 (after the softplus); A [H] (negative);
    B, C [T, G, N]; h0 [S, H, P, N] float32; onehot :func:`segments`. Returns
    ``(y [T, H, P] float32, h [S, H, P, N])``; a row that is no sequence's
    changes no state, a sequence without tokens keeps its own."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    R = H // G
    S = h0.shape[0]
    Q = min(chunk, T)
    assert T % Q == 0, (T, chunk)
    f32 = jnp.float32
    live = onehot.any(axis=1)
    dt = dt.astype(f32).reshape(T, G, R)
    a = jnp.where(live[:, None, None], dt * A.astype(f32).reshape(1, G, R), 0.0)
    x = x.astype(f32).reshape(T, G, R, P)
    B, C = B.astype(f32), C.astype(f32)
    h = h0.reshape(S, G, R, P, N)
    ys = []
    for c in range(T // Q):
        rows = slice(c * Q, (c + 1) * Q)
        y, h = _chunk(x[rows], dt[rows], a[rows], B[rows], C[rows], h, onehot[rows])
        ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    return y.reshape(T, H, P), h.reshape(S, H, P, N)


def step(x, dt, A, B, C, h):
    """The recurrence, one token a sequence: x [S, H, P]; dt [S, H]; B, C
    [S, G, N]; h [S, H, P, N] float32. Returns ``(y [S, H, P], h)``."""
    S, H, P = x.shape
    G, N = B.shape[1:]
    R = H // G
    f32 = jnp.float32
    dt = dt.astype(f32).reshape(S, G, R)
    decay = jnp.exp(dt * A.astype(f32).reshape(1, G, R))
    x = x.astype(f32).reshape(S, G, R, P) * dt[..., None]
    h = h.reshape(S, G, R, P, N) * decay[..., None, None] \
        + x[..., None] * B.astype(f32)[:, :, None, None, :]
    y = (h * C.astype(f32)[:, :, None, None, :]).sum(-1)
    return y.reshape(S, H, P), h.reshape(S, H, P, N)


def in_place(pool, groups: int) -> bool:
    """Whether :func:`step_in_place` runs the kernel on this pool ``[blocks,
    slots, H, P, N]``: by its type alone, the same answer on every backend."""
    return pool.dtype == jnp.float32 and ssm_step.supported(*pool.shape[2:], groups)


def step_in_place(pool, block, slot, live, started, x, dt, A, B, C):
    """:func:`step` over the pool's block ``block``: row t's state is slot
    ``slot[t]``'s (zeros where ``started[t]`` is false, whatever the slot
    held) and is left there where ``live[t]``; a row that is not live writes
    nothing. Live rows hold distinct slots. Returns ``(y [T, H, P], pool)``; a
    dead row's ``y`` is nobody's."""
    if in_place(pool, B.shape[1]):
        return ssm_step.ssm_step_in_place(pool, block, slot, live, started, x, dt, A, B, C)
    y, state = step(x, dt, A, B, C, load(pool, block, slot, started))
    return y, store_in_place(pool, block, slot, live, state)


def scan_in_place(pool, block, slot, live, started, seq_start, seq_ntok, token_seq, token_valid,
                  x, dt, A, B, C, chunk: int):
    """A ``put`` step's scan over the pool's block ``block``, by SEGMENT:
    sequence i's rows are the ``seq_ntok[i]`` rows from ``seq_start[i]`` of the
    flat batch (``token_seq`` [T] each row's sequence, ``token_valid`` whether
    it is anybody's); its state is slot ``slot[i]``'s (zeros where
    ``started[i]`` is false, whatever the slot held) and its final state is
    left there where ``live[i]``; a sequence that is not live, or without
    rows, keeps its slot bit for bit. x [T, H, P]; dt [T, H]; A [H]; B, C
    [T, G, N]. Returns ``(y [T, H, P] float32, pool)``; nobody's row reads
    zeros.

    Where :func:`in_place`, the LENGTH of a segment decides its visit, and no
    state leaves its slot but the one being visited: a segment of one row is
    the recurrence, all of them in one call of the step kernel (row i the
    sequence's one row); a longer one goes through :func:`_chunk` against ITS
    state alone, a visit a chunk of the batch it has rows in, a loop over the
    step's visits in the segments' order (a device-side count: any number of
    prompt chunks a step). Elsewhere :func:`scan_ragged` on every sequence's
    state between :func:`load` and :func:`store_in_place`."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    S = slot.shape[0]
    if not in_place(pool, G):
        state = load(pool, block, slot, started)
        y, state = scan_ragged(x, dt, A, B, C, state, segments(token_seq, token_valid, S), chunk)
        return y, store_in_place(pool, block, slot, live, state)
    f32 = jnp.float32
    R, Q = H // G, min(chunk, T)
    assert T % Q == 0, (T, chunk)
    # one row: the recurrence in the slot
    first = jnp.clip(seq_start, 0, T - 1)
    one = live & (seq_ntok == 1)
    y_one, pool = ssm_step.ssm_step_in_place(pool, block, slot, one, started, x[first], dt[first],
                                             A, B[first], C[first])
    # longer: the matrix form. The batch is cut into chunks of Q rows as
    # scan_ragged cuts it; a visit is one segment's part in one chunk, its rows
    # picked by the mask, so a chunk of y is read and written whole
    dt = dt.astype(f32).reshape(T, G, R)
    a = jnp.where(token_valid[:, None, None], dt * A.astype(f32).reshape(1, G, R), 0.0)
    rows = [r.reshape((T // Q, Q) + r.shape[1:])
            for r in (x.astype(f32).reshape(T, G, R, P), dt, a, B.astype(f32), C.astype(f32))]
    owner = jnp.where(token_valid, token_seq, -1).reshape(T // Q, Q)  # a row's segment, -1 nobody
    enters = first // Q  # the chunk a segment's first row lies in
    visits = jnp.where(live & (seq_ntok > 1), (first + seq_ntok - 1) // Q - enters + 1, 0)
    ends = jnp.cumsum(visits)

    def visit(v, carry):
        y, pool = carry
        i = jnp.minimum(jnp.sum(ends <= v), S - 1)  # the visit's segment
        k = v - (ends[i] - visits[i])  # its k-th chunk
        c = enters[i] + k
        mine = owner[c] == i  # [Q]: the chunk's rows of segment i
        where = (block, jnp.minimum(slot[i], pool.shape[1] - 1), 0, 0, 0)
        h = jax.lax.dynamic_slice(pool, where, (1, 1, H, P, N))
        h = jnp.where(started[i] | (k > 0), h, 0.0).reshape(1, G, R, P, N)
        y_c, h = _chunk(*(r[c] for r in rows), h, mine[:, None])
        y = y.at[c].set(jnp.where(mine, jnp.moveaxis(y_c.reshape(Q, H, P), 0, -1), y[c]))
        return y, jax.lax.dynamic_update_slice(pool, h.reshape(1, 1, H, P, N), where)

    # the visits' y rides as [chunks, H, P, Q]: rows on the lanes is the layout the
    # products' results have, and a chunk is then one whole piece of the carry (as
    # [chunks, Q, H, P] the compiler laid Nemotron's with the chunks inside the
    # rows' tiles, and a chunk's update cost 64 us, a third of the scan)
    y, pool = jax.lax.fori_loop(0, ends[-1], visit, (jnp.zeros((T // Q, H, P, Q), f32), pool))
    y = jnp.moveaxis(y, -1, 1).reshape(T, H * P)
    y_one = y_one.reshape(S, H * P)[token_seq]  # whole rows: a gather of [H, 64] pieces is 4 x slower
    return jnp.where((one[token_seq] & token_valid)[:, None], y_one, y).reshape(T, H, P), pool


def whole_slots(pool) -> bool:
    """Whether :func:`load` and :func:`store_in_place` run their kernel on this
    pool ``[blocks, slots, ...]``: by its type alone, the same answer on every
    backend."""
    return ssm_store.supported(pool.shape)


def load(pool, block, slot, started):
    """``[S, ...]``: row i is slot ``slot[i]`` of the pool's block ``block``
    where ``started[i]`` and zeros where not, whatever the slot held (one past
    the last included). One Pallas kernel over the pool itself
    (``ops/pallas/ssm_store.py``) where :func:`whole_slots`: XLA's gather of
    rows above 2 MiB first slices the whole pool, and around a gather of the
    convolution's three-row tails it re-lays the pool (PERF.md section 6, PR
    53)."""
    if whole_slots(pool):
        rows = ssm_store.ssm_load(pool, block, slot, started)
    else:
        rows = pool[block, jnp.minimum(slot, pool.shape[1] - 1)]
    return jnp.where(started.reshape((-1, ) + (1, ) * (rows.ndim - 1)), rows, 0)


def store_in_place(pool, block, slot, live, states):
    """``states[i]`` ``[S, ...]`` left in slot ``slot[i]`` of the pool's block
    ``block`` where ``live[i]``; a row that is not live writes nothing. Live
    rows hold distinct slots. The same kernel the other way, the pool aliased
    in and out, where :func:`whole_slots`. Returns the pool."""
    states = states.astype(pool.dtype)
    if whole_slots(pool):
        return ssm_store.ssm_store_in_place(pool, block, slot, live, states)
    return pool.at[block, jnp.where(live, slot, pool.shape[1])].set(states, mode="drop")


def gated_norm(y, z, weight, groups: int, eps: float):
    """``RMSNorm_grouped(y silu(z)) weight``: the gate BEFORE the norm, the
    norm over ``groups`` groups of the channels. y, z [T, D]."""
    T, D = y.shape
    g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).reshape(T, groups, D // groups)
    g = g * jax.lax.rsqrt(jnp.square(g).mean(axis=-1, keepdims=True) + eps)
    return g.reshape(T, D) * weight.astype(jnp.float32)[None, :]
