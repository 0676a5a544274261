"""The gated delta-rule mixer's state part ("KDA": Kimi Delta Attention,
arXiv:2510.26692) over a ragged batch, in two forms that agree (tier-1 holds
them against each other and against the token-by-token reference,
``tests/unit/inference/v2/test_solar_open2.py``).

A head's state is a matrix ``S`` in ``R^{d_k x d_v}``, float32, and belongs to
a SEQUENCE. A token decays it by CHANNEL of the key, reads what it holds for
its key, writes a rank-one correction, and reads it with its query::

    S~  = diag(alpha_t) S_{t-1}            alpha_t = exp(g_t) in (0, 1]^{d_k}
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

:func:`step` is that recurrence as written, the reference the others are held
to. :func:`step_in_place`, a ``decode_loop`` step: the same over the engine's
pool ``[layers, slots, H, d_k, d_v]``, a row's state read from its slot and
left there by one Pallas kernel (``ops/pallas/kda_step.py``) where
:func:`in_place` says the shapes allow it, :func:`step` between ``ssm.load``
and ``ssm.store_in_place`` where not. :func:`scan_in_place`, a ``put`` step:
the scan by SEGMENT inside the pool, as ``ssm.scan_in_place`` visits it — a
segment of one row is the recurrence, all of them in one call of the step
kernel; a longer one goes through the chunked form against ITS state alone, a
visit a chunk of the batch it has rows in: the step's visits in ONE call of
the chunk kernel (``ops/pallas/kda_chunk.py``) where :func:`chunks_in_kernel`
says the shapes allow it, a loop of :func:`chunk` visits where not.
:func:`chunk` is the form tier-1 holds that kernel to.

**The chunked form** (one sequence's C rows, ``G_t`` the sum of ``g`` from the
chunk's start through t)::

    A[s, r] = beta_s (k_s (.) exp(G_s - G_r)) . k_r        r < s, 0 elsewhere
    W = (I + A)^-1 diag(beta) (V - (K (.) exp(G)) S_0)
    o_t = S_0^T (q_t (.) exp(G_t)) + sum_{s <= t} ((q_t (.) exp(G_t - G_s)) . k_s) w_s
    S_C = diag(exp(G_C)) S_0 + sum_s (k_s (.) exp(G_C - G_s)) w_s^T

Mamba-2's chunk does not carry over: the decay is a vector a head, so the
pairwise ``exp(G_s - G_r)`` does not factor out of the inner product, and the
correction needs the inverse of a unit lower-triangular ``[C, C]`` a head a
chunk. The factorised ``(k_s exp(G_s)) . (k_r / exp(G_r))`` divides by a
product that underflows (a step's ``-g`` reaches 16 and more): every exponent
here is a DIFFERENCE that is at most 0. The chunk is cut into sub-chunks of
``SUB`` rows; a pair of rows of one sub-chunk takes the pairwise form, a pair
of two sub-chunks factors through a reference point between them (``G`` as the
later sub-chunk starts: both factors are at most 1, and one underflows only
where the product does). The inverse is by substitution, rows inside a
sub-chunk and sub-chunks inside the chunk: ``(I + A)^-1`` as a product of
powers of A loses everything to cancellation where the keys of a chunk are
alike. The products that read or make the state run at ``high`` precision
(three bf16 passes), ``modules/ssm.py``'s finding for a float32 state; the
small ``[C, C]`` ones at ``highest``.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.modules import ssm
from deepspeed_tpu.ops.pallas import kda_chunk, kda_step

_HIGHEST = jax.lax.Precision.HIGHEST
_HIGH = jax.lax.Precision.HIGH
SUB = kda_chunk.SUB  # rows a sub-chunk
NORM_EPS = 1e-6  # under the square root of a head's L2 norm


def l2_normed(x, scale: float = 1.0):
    """``x / |x|`` over the last axis (a head), times ``scale``, float32."""
    x = x.astype(jnp.float32)
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + NORM_EPS) * scale)


def decay(f, dt_bias, A_log, heads: int):
    """The log-decay a channel, ``g = -exp(A_log[h]) softplus(f + dt_bias)``:
    f [T, H x d_k] -> [T, H, d_k] float32, at most 0."""
    T = f.shape[0]
    g = jax.nn.softplus(f.astype(jnp.float32) + dt_bias.astype(jnp.float32)[None, :])
    return -jnp.exp(A_log.astype(jnp.float32))[None, :, None] * g.reshape(T, heads, -1)


def gated_norm(o, gate, weight, eps: float):
    """``RMSNorm_head(o) weight (.) sigmoid(gate)``: the norm over each head's
    d_v. o [T, H, d_v] float32; gate [T, H x d_v]; weight [d_v]."""
    T, H, D = o.shape
    o = o * jax.lax.rsqrt(jnp.square(o).mean(axis=-1, keepdims=True) + eps)
    o = o * weight.astype(jnp.float32)[None, None, :]
    return o.reshape(T, H * D) * jax.nn.sigmoid(gate.astype(jnp.float32))


# -------------------------------------------------------------- recurrence --
def step(q, k, v, alpha, beta, h):
    """The recurrence, one token a sequence: q, k, alpha [S, H, d_k]; v [S, H,
    d_v]; beta [S, H]; h [S, H, d_k, d_v] float32. Returns ``(o [S, H, d_v],
    h)``."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    h = h * alpha.astype(f32)[..., None]
    held = jnp.sum(h * k[..., None], axis=-2)  # S^T k
    h = h + k[..., None] * (beta.astype(f32)[..., None] * (v - held))[..., None, :]
    return jnp.sum(h * q[..., None], axis=-2), h


def in_place(pool) -> bool:
    """Whether :func:`step_in_place` runs the kernel on this pool ``[layers,
    slots, H, d_k, d_v]``: by its type alone, the same answer on every
    backend."""
    return pool.dtype == jnp.float32 and kda_step.supported(*pool.shape[2:])


def chunks_in_kernel(pool, rows: int) -> bool:
    """Whether :func:`scan_in_place` runs its visits of ``rows`` rows by the
    chunk kernel (``ops/pallas/kda_chunk.py``) on this pool: by its type and
    the chunk's rows alone, the same answer on every backend."""
    return pool.dtype == jnp.float32 and kda_chunk.supported(*pool.shape[2:], rows)


def step_in_place(pool, block, slot, live, started, q, k, v, alpha, beta):
    """:func:`step` over the pool's layer ``block``: row t's state is slot
    ``slot[t]``'s (zeros where ``started[t]`` is false, whatever the slot
    held) and is left there where ``live[t]``; a row that is not live writes
    nothing. Live rows hold distinct slots. Returns ``(o [T, H, d_v], pool)``;
    a dead row's ``o`` is nobody's."""
    if in_place(pool):
        return kda_step.kda_step_in_place(pool, block, slot, live, started, q, k, v, alpha, beta)
    o, state = step(q, k, v, alpha, beta, ssm.load(pool, block, slot, started))
    return o, ssm.store_in_place(pool, block, slot, live, state)


# ------------------------------------------------------------ chunked form --
def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` [..., c, c] strictly lower-triangular, by
    substitution a row: row i of the inverse is ``e_i - sum_{j < i} a[i, j]
    row_j``, elementwise in float32."""
    c = a.shape[-1]
    x = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    for i in range(1, c):
        x = x.at[..., i, :].add(-jnp.sum(a[..., i, :, None] * x, axis=-2))
    return x


def chunk(q, k, v, g, beta, h, sub: int = SUB):
    """The chunked form over ONE sequence's C rows against its state: q, k, g
    [C, H, d_k] (g the log-decay, at most 0); v [C, H, d_v]; beta [C, H]; h [H,
    d_k, d_v] float32. A row that is not the sequence's is handed in as zeros
    (g, beta, k, q: it decays nothing, writes nothing and reads zeros).
    Returns ``(o [C, H, d_v] float32, h)``."""
    C, H, dk = q.shape
    c = min(sub, C)
    n = C // c
    assert n * c == C, (C, sub)
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=0)  # [C, H, d_k]: from the chunk's start through the row
    Gb = G.reshape(n, c, H, dk)
    # G as each sub-chunk starts: between a row of it and a row of an earlier one
    ref = jnp.concatenate([jnp.zeros((1, H, dk), f32), Gb[:-1, -1]])
    both = jnp.concatenate([k.reshape(n, c, H, dk), q.reshape(n, c, H, dk)], axis=1)  # [n, 2c, ..]
    Gboth = jnp.concatenate([Gb, Gb], axis=1)
    # a row of sub-chunk I against every row r of an EARLIER one, through ref[I]
    left = both * jnp.exp(Gboth - ref[:, None])
    right = k[None] * jnp.exp(jnp.minimum(ref[:, None] - G[None], 0.0))  # [n, C, H, d_k]
    off = jnp.einsum("nshd,nrhd->nsrh", left, right, precision=_HIGH)  # [n, 2c, C, H]
    earlier = (jnp.arange(C) // c)[None, :] < jnp.arange(n)[:, None]  # [n, C]
    off = jnp.where(earlier[:, None, :, None], off, 0.0)
    # a row against a row of ITS sub-chunk: pairwise, the exponent a difference
    diff = jnp.minimum(Gboth[:, :, None] - Gb[:, None, :], 0.0)  # [n, 2c, c, H, d_k]
    own = jnp.sum(both[:, :, None] * k.reshape(n, 1, c, H, dk) * jnp.exp(diff), axis=-1)
    own = own[:, :, None] * jnp.eye(n, dtype=f32)[:, None, :, None, None]  # on the diagonal
    pairs = off + own.reshape(n, 2 * c, C, H)
    kk, qk = pairs[:, :c].reshape(C, C, H), pairs[:, c:].reshape(C, C, H)  # [s, r, H]
    at = jnp.arange(C)
    A = jnp.where((at[None, :] < at[:, None])[..., None], kk, 0.0) * beta[:, None, :]
    P = jnp.where((at[None, :] <= at[:, None])[..., None], qk, 0.0)

    # W = (I + A)^-1 beta (V - (K exp(G)) S_0), sub-chunk by sub-chunk
    Gamma = jnp.exp(G)
    rhs = beta[..., None] * (v - jnp.einsum("chk,hkv->chv", k * Gamma, h, precision=_HIGH))
    Ab = jnp.moveaxis(A, -1, 0).reshape(H, n, c, n, c)
    inverse = _unit_lower_inverse(jnp.stack([Ab[:, i, :, i] for i in range(n)], axis=1))
    rhs = jnp.moveaxis(rhs, 1, 0).reshape(H, n, c, -1)  # [H, n, c, d_v]
    W = []
    for i in range(n):
        r = rhs[:, i]
        if i:
            r = r - jnp.einsum("hsjr,hjrv->hsv", Ab[:, i, :, :i], jnp.stack(W, axis=1),
                               precision=_HIGHEST)
        W.append(jnp.einsum("hsr,hrv->hsv", inverse[:, i], r, precision=_HIGHEST))
    W = jnp.moveaxis(jnp.stack(W, axis=1).reshape(H, C, -1), 0, 1)  # [C, H, d_v]

    o = jnp.einsum("chk,hkv->chv", q * Gamma, h, precision=_HIGH) \
        + jnp.einsum("tsh,shv->thv", P, W, precision=_HIGH)
    h = Gamma[-1][..., None] * h \
        + jnp.einsum("shk,shv->hkv", k * jnp.exp(G[-1][None] - G), W, precision=_HIGH)
    return o, h


def visits_of(seq_start, seq_ntok, longer, rows: int):
    """``(enters [S], visits [S])``: the chunk of ``rows`` rows a segment's
    first row lies in, and how many chunks a segment that ``longer`` marks has
    rows in (0 for the others). numpy or jax arrays alike."""
    enters = seq_start // rows
    return enters, longer * ((seq_start + seq_ntok - 1) // rows - enters + 1)


def _visits_in_numpy(pool, block, slot, started, enters, visits, token_seq, token_valid, q, k, v,
                      g, beta, Q):
    """The step's visits through :func:`chunk`, a loop in the segments' order,
    each against ITS state alone, sliced out of its slot and written back:
    where the pool is off the kernel's rule. Returns ``(o [T, H x d_v], pool)``."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    S = slot.shape[0]
    f32 = jnp.float32
    by_chunk = [a.astype(f32).reshape((T // Q, Q) + a.shape[1:]) for a in (q, k, v, g, beta)]
    owner = jnp.where(token_valid, token_seq, -1).reshape(T // Q, Q)  # a row's segment, -1 nobody
    ends = jnp.cumsum(visits)

    def visit(n, carry):
        o, pool = carry
        i = jnp.minimum(jnp.sum(ends <= n), S - 1)  # the visit's segment
        nth = n - (ends[i] - visits[i])  # its nth chunk
        c = enters[i] + nth
        mine = owner[c] == i  # [Q]: the chunk's rows of segment i
        where = (block, jnp.minimum(slot[i], pool.shape[1] - 1), 0, 0, 0)
        h = jax.lax.dynamic_slice(pool, where, (1, 1, H, dk, dv))
        h = jnp.where(started[i] | (nth > 0), h, 0.0).reshape(H, dk, dv).astype(f32)
        masked = [jnp.where(mine.reshape((Q, ) + (1, ) * (a.ndim - 2)), a[c], 0.0)
                  for a in by_chunk]
        o_c, h = chunk(*masked, h)
        o = o.at[c].set(jnp.where(mine[:, None], o_c.reshape(Q, H * dv), o[c]))
        h = h.reshape(1, 1, H, dk, dv).astype(pool.dtype)
        return o, jax.lax.dynamic_update_slice(pool, h, where)

    o, pool = jax.lax.fori_loop(0, ends[-1], visit, (jnp.zeros((T // Q, Q, H * dv), f32), pool))
    return o.reshape(T, H * dv), pool


def scan_in_place(pool, block, slot, live, started, seq_start, seq_ntok, token_seq, token_valid,
                  q, k, v, g, beta, rows: int):
    """A ``put`` step's scan over the pool's layer ``block``, by SEGMENT
    (``ssm.scan_in_place``'s walk): sequence i's rows are the ``seq_ntok[i]``
    rows from ``seq_start[i]`` of the flat batch; its state is slot
    ``slot[i]``'s (zeros where ``started[i]`` is false, whatever the slot held)
    and its final state is left there where ``live[i]``; a sequence that is not
    live, or without rows, keeps its slot bit for bit. q, k, g [T, H, d_k]; v
    [T, H, d_v]; beta [T, H]. A segment of one row is the recurrence, all of
    them in one call of :func:`step_in_place` (row i the sequence's one row); a
    longer one goes through the chunked form against ITS state alone, a visit a
    chunk of ``rows`` rows of the batch it has rows in, the step's visits in
    the segments' order: one call of the chunk kernel over the pool in place
    (:func:`chunks_in_kernel`), or a loop of :func:`chunk` visits; no state
    leaves its slot but the one being visited. Returns ``(o [T, H, d_v]
    float32, pool)``; nobody's row reads zeros."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    S = slot.shape[0]
    Q = min(rows, T)
    assert T % Q == 0, (T, rows)
    first = jnp.clip(seq_start, 0, T - 1)
    one = live & (seq_ntok == 1)
    o_one, pool = step_in_place(pool, block, slot, one, started, q[first], k[first], v[first],
                                jnp.exp(g[first]), beta[first])
    enters, visits = visits_of(first, seq_ntok, live & (seq_ntok > 1), Q)
    if chunks_in_kernel(pool, Q):
        o, pool = kda_chunk.kda_chunk_in_place(pool, block, slot, started, first, seq_ntok, enters,
                                               visits, q, k, v, g, beta, rows=Q)
        scanned = (visits > 0)[token_seq] & token_valid  # a chunk no visit reached is not written
        o = jnp.where(scanned[:, None], o.reshape(T, H * dv), 0.0)
    else:
        o, pool = _visits_in_numpy(pool, block, slot, started, enters, visits, token_seq,
                                   token_valid, q, k, v, g, beta, Q)
    o_one = o_one.reshape(S, H * dv)[token_seq]
    o = jnp.where((one[token_seq] & token_valid)[:, None], o_one, o)
    return o.reshape(T, H, dv), pool
