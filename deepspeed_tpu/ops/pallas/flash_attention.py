"""Blocked (flash) causal attention.

TPU-native replacement for the reference's attention kernels: the inference-v2
``blocked_flash`` binding (``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash``)
and the training softmax/attention CUDA kernels (``csrc/transformer/softmax_kernels.cu``).

Design:
- Forward: a Pallas kernel, grid over (batch*heads, q_blocks); each program streams
  KV blocks through VMEM with an online-softmax accumulator (flash-attention-2
  schedule). Causal masking skips fully-masked KV blocks. The backward's softmax
  stats (lse) are saved lane-broadcast as a second output.
- Backward: hand Pallas kernels (``_flash_bwd_pallas``): a dK/dV kernel owning one
  KV block and streaming q/do rows, and a dQ kernel owning one Q block and
  streaming KV — the FA2 backward, O(S) memory. The blockwise-JAX backward
  (``_flash_bwd_manual``) stays as the tests' numerical oracle only.
- CPU (tests): interpret mode.

Layout: q, k, v are [B, S, H, D] (kv may have fewer heads — GQA is expanded by the
caller or here via repeat).
"""

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _on_cpu():
    return jax.default_backend() == "cpu"


def _fit_block(seq_len, cap):
    """Largest divisor of seq_len that is <= cap (block shapes must tile S)."""
    b = min(cap, seq_len)
    while seq_len % b:
        b -= 1
    return b


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, causal,
                block_q, block_k, nkb):
    """Flash-attention-2 schedule: grid (bh, q_blocks, kv_blocks); the kv dim is the
    innermost (sequential) grid axis so Pallas double-buffers the K/V block fetches
    while the scratch accumulators carry the online softmax across iterations."""
    from jax.experimental import pallas as pl

    q_idx = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: block fully above the diagonal contributes nothing
    run = (kb * block_k <= q_idx * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[...].astype(jnp.float32)  # [bq, d]
        k_blk = k_ref[...].astype(jnp.float32)  # [bk, d]
        v_blk = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[...][:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_scr[...] = l_scr[...] * alpha + jnp.broadcast_to(jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v_blk, (((1, ), (0, )), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kb == nkb - 1)
    def _finish():
        l = l_scr[...][:, :1]
        o_ref[...] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            # softmax stats for the backward, lane-broadcast ([bq, 128] — the
            # TPU-tileable layout for per-row scalars)
            lse_ref[...] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))


def _flash_fwd_pallas(q, k, v, scale, causal, block_q=512, block_k=1024, save_lse=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    block_q = _fit_block(S, block_q)
    block_k = _fit_block(S, block_k)
    nkb = S // block_k

    qr = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal, block_q=block_q,
                               block_k=block_k, nkb=nkb)
    if not save_lse:
        inner = kernel

        def kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
            inner(q_ref, k_ref, v_ref, o_ref, None, m_scr, l_scr, acc_scr)
    on_cpu = _on_cpu()
    scratch = [
        pltpu.VMEM((block_q, 128), jnp.float32),  # m (lane-broadcast)
        pltpu.VMEM((block_q, 128), jnp.float32),  # l (lane-broadcast)
        pltpu.VMEM((block_q, D), jnp.float32),  # acc
    ]
    out_specs = [pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B * H, S, D), q.dtype)]
    if save_lse:
        out_specs.append(pl.BlockSpec((None, block_q, 128), lambda b, i, j: (b, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B * H, S, 128), jnp.float32))
    kwargs = {}
    if not on_cpu:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    outs = pl.pallas_call(
        kernel,
        grid=(B * H, S // block_q, nkb),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=out_specs if save_lse else out_specs[0],
        out_shape=out_shape if save_lse else out_shape[0],
        scratch_shapes=scratch,
        interpret=on_cpu,
        name="flash_attention_fwd",
        **kwargs,
    )(qr, kr, vr)
    if save_lse:
        out, lse = outs
        # keep ONE lane as the residual: all 128 are identical, and holding
        # the broadcast across the fwd→bwd window would cost 128x the bytes
        # of the per-row scalar (2x the attention output itself)
        return out.reshape(B, H, S, D).transpose(0, 2, 1, 3), lse[..., :1]
    return outs.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _blockwise_attention_ref(q, k, v, scale, causal, block_k=256):
    """Memory-efficient pure-JAX attention (scan over KV blocks) — used for the
    VJP recompute and as numerical reference."""
    B, S, H, D = q.shape
    block_k = _fit_block(S, block_k)
    nkb = S // block_k
    q32 = q.astype(jnp.float32)
    q_pos = jnp.arange(S)

    def body(carry, kb):
        m, l, acc = carry
        start = kb * block_k
        k_blk = jax.lax.dynamic_slice_in_dim(k, start, block_k, axis=1).astype(jnp.float32)
        v_blk = jax.lax.dynamic_slice_in_dim(v, start, block_k, axis=1).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bqhk", q32, k_blk) * scale
        if causal:
            k_pos = start + jnp.arange(block_k)
            s = jnp.where(q_pos[None, :, None, None] >= k_pos[None, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bqhk,bkhd->bqhd", p, v_blk)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, S, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, H), jnp.float32)
    a0 = jnp.zeros((B, S, H, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nkb))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _expand_gqa(q, k, v):
    H, KVH = q.shape[2], k.shape[2]
    if KVH != H:
        rep = H // KVH
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, scale=1.0, causal=True):
    k, v = _expand_gqa(q, k, v)
    return _flash_fwd_pallas(q, k, v, scale, causal)


def _flash_bwd_manual(q, k, v, out, g, scale, causal, block_k=256):
    """Hand-written flash-attention-2 backward (no autodiff): recompute the
    softmax statistics blockwise, then a second blockwise pass produces
    dq/dk/dv. Differentiating the scan instead (the previous implementation)
    made XLA stack per-block residuals — O(S^2/block) memory, OOM at 4k+.
    All inputs [B, S, H, D] (GQA pre-expanded)."""
    B, S, H, D = q.shape
    bk = _fit_block(S, block_k)
    nkb = S // bk
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    q_pos = jnp.arange(S)

    def logits_block(j):
        k_blk = jax.lax.dynamic_slice_in_dim(kf, j * bk, bk, axis=1)
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, k_blk) * scale
        if causal:
            k_pos = j * bk + jnp.arange(bk)
            s = jnp.where(q_pos[None, :, None, None] >= k_pos[None, None, None, :], s, NEG_INF)
        return s, k_blk

    # pass 1: log-sum-exp per query row (running max/sum; no stacked residuals)
    def lse_body(carry, j):
        m, l = carry
        s, _ = logits_block(j)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(s - m_new[..., None]), axis=-1)
        return (m_new, l), None

    m0 = jnp.full((B, S, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, H), jnp.float32)
    (m, l), _ = jax.lax.scan(lse_body, (m0, l0), jnp.arange(nkb))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)  # [B, S, H]

    # pass 2: per-block p recomputed and discarded
    def bwd_body(dq, j):
        s, k_blk = logits_block(j)
        v_blk = jax.lax.dynamic_slice_in_dim(vf, j * bk, bk, axis=1)
        p = jnp.exp(s - lse[..., None])  # masked entries: exp(NEG_INF - lse) = 0
        dv_j = jnp.einsum("bqhk,bqhd->bkhd", p, gf)
        dp = jnp.einsum("bqhd,bkhd->bqhk", gf, v_blk)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bqhk,bkhd->bqhd", ds, k_blk) * scale
        dk_j = jnp.einsum("bqhk,bqhd->bkhd", ds, qf) * scale
        return dq, (dk_j, dv_j)

    dq, (dk_s, dv_s) = jax.lax.scan(bwd_body, jnp.zeros_like(qf), jnp.arange(nkb))
    dk = jnp.moveaxis(dk_s, 0, 1).reshape(B, S, H, D)
    dv = jnp.moveaxis(dv_s, 0, 1).reshape(B, S, H, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr, *, scale, causal, block_q, block_k, nqb):
    """dK/dV: grid (BH, kv_blocks, q_steps) — each program owns one KV block
    and streams the q/do/lse/delta row blocks through (FA2 backward, the role
    of the reference's csrc/transformer training kernels)."""
    from jax.experimental import pallas as pl

    kb = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = (qi * block_q + block_q - 1 >= kb * block_k) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[...].astype(jnp.float32)        # [bq, d]
        do = do_ref[...].astype(jnp.float32)      # [bq, d]
        k_blk = k_ref[...].astype(jnp.float32)    # [bk, d]
        v_blk = v_ref[...].astype(jnp.float32)
        lse = lse_ref[...][:, :1]                 # [bq, 1]
        delta = delta_ref[...][:, :1]
        s = jax.lax.dot_general(q, k_blk, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                      # masked: exp(NEG_INF - lse) = 0
        dv_scr[...] += jax.lax.dot_general(p, do, (((0, ), (0, )), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_blk, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_scr[...] += jax.lax.dot_general(ds, q, (((0, ), (0, )), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(qi == nqb - 1)
    def _finish():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
                   scale, causal, block_q, block_k, nkb):
    """dQ: grid (BH, q_blocks, kv_steps) — each program owns one Q block and
    streams the KV blocks through."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = (kb * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        k_blk = k_ref[...].astype(jnp.float32)
        v_blk = v_ref[...].astype(jnp.float32)
        lse = lse_ref[...][:, :1]
        delta = delta_ref[...][:, :1]
        s = jax.lax.dot_general(q, k_blk, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v_blk, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[...] += jax.lax.dot_general(ds, k_blk, (((1, ), (0, )), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(kb == nkb - 1)
    def _finish():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, g, lse, scale, causal, block_q=512, block_k=512):
    """Hand Pallas backward (VERDICT r4 #6): dq/dk/dv via two kernels over the
    forward-saved lse, delta precomputed in XLA. [B, S, H, D] in/out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    bq = _fit_block(S, block_q)
    bk = _fit_block(S, block_k)
    nqb, nkb = S // bq, S // bk

    qr = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    dor = g.transpose(0, 2, 1, 3).reshape(B * H, S, D).astype(q.dtype)
    # delta = rowsum(dO * O); single-lane [BH, S, 1] like the lse residual
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 1).reshape(B * H, S)[..., None]

    on_cpu = _on_cpu()
    kwargs = {}
    if not on_cpu:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, block_q=bq,
                          block_k=bk, nqb=nqb),
        grid=(B * H, nkb, nqb),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda b, j, i: (b, i, 0)),   # q rows
            pl.BlockSpec((None, bq, D), lambda b, j, i: (b, i, 0)),   # do rows
            pl.BlockSpec((None, bq, 1), lambda b, j, i: (b, i, 0)),   # lse rows
            pl.BlockSpec((None, bq, 1), lambda b, j, i: (b, i, 0)),   # delta rows
            pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),   # k block
            pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),   # v block
        ],
        out_specs=[pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),
                   pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B * H, S, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=on_cpu,
        name="flash_attention_bwd_dkv",
        **kwargs,
    )(qr, dor, lse, delta, kr, vr)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, block_q=bq,
                          block_k=bk, nkb=nkb),
        grid=(B * H, nqb, nkb),
        in_specs=[
            pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),   # k block
            pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),   # v block
            pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),   # q rows
            pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),   # do rows
            pl.BlockSpec((None, bq, 1), lambda b, i, j: (b, i, 0)),   # lse
            pl.BlockSpec((None, bq, 1), lambda b, i, j: (b, i, 0)),   # delta
        ],
        out_specs=pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=on_cpu,
        name="flash_attention_bwd_dq",
        **kwargs,
    )(kr, vr, qr, dor, lse, delta)

    back = lambda x: x.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    dk, dv = dkv
    return back(dq), back(dk), back(dv)


# the tests' oracle switch: the blockwise-JAX backward is what the Pallas
# backward is compared against. Nothing else selects it — a backward the
# compiler refuses raises (tests/unit/ops/test_tpu_compile.py asks the chip's
# compiler at the training shapes, without a chip)
_FORCE_MANUAL_BWD = False


def _fa_fwd(q, k, v, scale, causal):
    ke, ve = _expand_gqa(q, k, v)
    # `out` is a live activation either way — saving it adds no memory (XLA
    # aliases); lse feeds the hand backward kernels
    out, lse = _flash_fwd_pallas(q, ke, ve, scale, causal, save_lse=True)
    return out, (q, k, v, out, lse)


def _fa_bwd(scale, causal, res, g):
    q, k, v, out, lse = res
    kvh = k.shape[2]
    ke, ve = _expand_gqa(q, k, v)
    if _FORCE_MANUAL_BWD:
        dq, dke, dve = _flash_bwd_manual(q, ke, ve, out, g, scale, causal)
    else:
        dq, dke, dve = _flash_bwd_pallas(q, ke, ve, out, g, lse, scale, causal)
    if kvh != q.shape[2]:  # fold expanded GQA grads back onto kv heads
        rep = q.shape[2] // kvh
        B, S, _, D = dke.shape
        dk = dke.reshape(B, S, kvh, rep, D).sum(axis=3)
        dv = dve.reshape(B, S, kvh, rep, D).sum(axis=3)
    else:
        dk, dv = dke, dve
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)
