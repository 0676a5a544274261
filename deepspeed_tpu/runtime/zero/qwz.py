"""qwZ — ZeRO++ quantized weight all-gather.

Reference: ``deepspeed/runtime/zero/partition_parameters.py:1152``
(``all_gather_coalesced`` with ``quantization`` — each rank quantizes its
shard to int8 + scales, all-gathers the int8 payload, dequantizes after) and
``CUDAQuantizer`` at ``partition_parameters.py:731`` over
``csrc/quantization/quantize.cu``.

TPU formulation: under ZeRO-3 the forward/backward parameter all-gathers are
inserted by the SPMD partitioner at each weight's consumer. qwZ interposes on
the master→compute cast: the (still sharded) fp32 shard is quantized to int8
with per-row scales along the ZeRO-sharded dimension — an elementwise op, so
no pre-gather communication — and a sharding constraint then *forces the
all-gather on the int8 payload* (1 byte/element on the ICI wire instead of 2)
before the dequantize+cast runs replicated. XLA fuses dequant into each
weight's consumer. Gradients take the straight-through path (``custom_vjp``
identity): the quantization error perturbs the forward like the reference's,
while the backward reduce-scatter stays exact.
"""

import functools

import numpy as np

from deepspeed_tpu.utils import groups


def qwz_supported(stage: int) -> bool:
    return stage >= 3


def _sharded_dim(spec, zero_axes):
    """The dim of ``spec`` carrying any ZeRO axis, or None (replicated /
    TP-only leaves have nothing to gather cheaply)."""
    zset = set(zero_axes)
    for d, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry, )
        if any(ax in zset for ax in axes):
            return d
    return None


def _gathered_spec(spec, zero_axes):
    """``spec`` with the ZeRO axes removed (TP/EP placement survives)."""
    from jax.sharding import PartitionSpec as P
    zset = set(zero_axes)
    out = []
    for entry in tuple(spec):
        if entry is None:
            out.append(None)
            continue
        axes = tuple(ax for ax in (entry if isinstance(entry, tuple) else (entry, ))
                     if ax not in zset)
        out.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    return P(*out)


def _pack_nibbles(q, axis):
    """int8 values in [-7, 7] → two 4-bit nibbles per byte along ``axis``
    (which must have even size)."""
    import jax.numpy as jnp
    q = jnp.moveaxis(q, axis, -1)
    lo = q[..., 0::2] & 0xF
    hi = q[..., 1::2] & 0xF
    return jnp.moveaxis((lo | (hi << 4)).astype(jnp.int8), -1, axis)


def _unpack_nibbles(p, axis):
    import jax.numpy as jnp
    p = jnp.moveaxis(p, axis, -1)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    lo = lo - 16 * (lo >= 8)  # sign-extend 4-bit two's complement
    hi = hi - 16 * (hi >= 8)
    q = jnp.stack([lo, hi], axis=-1).reshape(*p.shape[:-1], p.shape[-1] * 2)
    return jnp.moveaxis(q.astype(jnp.int8), -1, axis)


def _nibble_pack_dim(shape, gather_dim, spec=None, mesh=None):
    """A non-gather dim to pack nibble pairs along (packing a non-gather dim
    keeps the all-gather untouched); None = int4 unavailable for this leaf.

    The packed dim must stay divisible by any mesh axes sharding it (a TP
    dim halved below its axis size breaks shard_map), so the requirement is
    ``shape[d] % (2 * prod(axis sizes on d)) == 0``; unsharded dims are
    preferred to avoid resharding the strided nibble slices."""
    def axis_prod(d):
        if spec is None or mesh is None or d >= len(tuple(spec)):
            return 1
        entry = tuple(spec)[d]
        if entry is None:
            return 1
        axes = entry if isinstance(entry, tuple) else (entry, )
        return int(np.prod([mesh.shape.get(ax, 1) for ax in axes]))

    candidates = [d for d in range(len(shape) - 1, -1, -1)
                  if d != gather_dim and shape[d] % (2 * axis_prod(d)) == 0]
    unsharded = [d for d in candidates if axis_prod(d) == 1]
    if unsharded:
        return unsharded[0]
    return candidates[0] if candidates else None


def _make_quantized_gather(dim, spec, gathered_spec, gather_axes, mesh, compute_dtype,
                           bits=8, shard_shape=None):
    """fp32 shard -> compute-dtype full weight, moving int8 (or packed int4)
    over the wire.

    The all-gather is an *explicit* ``jax.lax.all_gather`` on the s8 payload
    inside ``shard_map`` — a mere sharding constraint lets the partitioner
    hoist the int8→fp convert ahead of the gather and put fp32 on the wire
    (observed; the same reason qgZ routes through shard_map).

    Straight-through: the vjp is identity (grad flows to the master shard as
    if the cast were exact) — the partitioner still emits the exact
    reduce-scatter for the gradient.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axis_name = gather_axes if len(gather_axes) > 1 else gather_axes[0]
    # the scale is size-1 on every dim but ``dim``: only that entry survives
    scale_spec = P(*[entry if i == dim else None for i, entry in enumerate(tuple(spec))])
    scale_gathered = P(*[entry if i == dim else None
                         for i, entry in enumerate(tuple(gathered_spec))])

    def gather_block(q_blk, s_blk):
        q_full = jax.lax.all_gather(q_blk, axis_name, axis=dim, tiled=True)
        s_full = jax.lax.all_gather(s_blk, axis_name, axis=dim, tiled=True)
        return q_full, s_full

    gather_sm = jax.shard_map(gather_block, mesh=mesh, in_specs=(spec, scale_spec),
                              out_specs=(gathered_spec, scale_gathered),
                              check_vma=False)

    pack_dim = _nibble_pack_dim(shard_shape, dim, spec, mesh) \
        if (bits == 4 and shard_shape) else None
    use_int4 = bits == 4 and pack_dim is not None

    @jax.custom_vjp
    def qgather(w):
        # per-row symmetric quantization along the ZeRO-sharded dim: the scale
        # reduces every OTHER dim, so it is elementwise w.r.t. the sharding —
        # no communication before the gather
        levels = 7.0 if use_int4 else 127.0
        red = tuple(i for i in range(w.ndim) if i != dim)
        scale = jnp.max(jnp.abs(w), axis=red, keepdims=True) / levels
        scale = jnp.maximum(scale, 1e-12)
        q = jnp.clip(jnp.round(w / scale), -levels, levels).astype(jnp.int8)
        if use_int4:
            # two nibbles/byte along a non-gather dim: half the gather bytes,
            # and the all-gather itself is untouched
            q = _pack_nibbles(q, pack_dim)
        q, scale = gather_sm(q, scale)
        if use_int4:
            q = _unpack_nibbles(q, pack_dim)
        return (q.astype(jnp.float32) * scale).astype(compute_dtype)

    def fwd(w):
        # 0-d residual carries the master dtype (a bare dtype is not a pytree leaf)
        return qgather(w), jnp.zeros((), w.dtype)

    def bwd(res, g):
        # restore the master dtype: the incoming cotangent arrives in
        # compute dtype (bf16), and the optimizer accumulates in fp32
        return (g.astype(res.dtype), )

    qgather.defvjp(fwd, bwd)
    return qgather


def make_qwz_cast(param_shardings, mesh, compute_dtype, zero_axes=None,
                  threshold: int = 2048, bits: int = 8):
    """Build the qwZ master→compute cast for the engine's parameter tree.

    Leaves that are floating, ndim>=2, >= ``threshold`` elements AND actually
    ZeRO-sharded take the quantized gather; everything else (norm scales,
    biases, small or replicated params) casts exactly. ``bits`` = 8 or 4
    (4 = nibble-packed wire payload; leaves with no even-size non-gather dim
    fall back to int8).
    """
    import jax
    import jax.numpy as jnp

    if bits not in (8, 4):
        raise ValueError(f"zero_quantized_weights_bits must be 8 or 4, got {bits}")
    zero_axes = tuple(zero_axes) if zero_axes is not None else groups.get_zero_partition_axes()
    zero_axes = tuple(ax for ax in zero_axes if mesh.shape.get(ax, 1) > 1)

    def leaf_cast_factory(sharding, shape):
        spec = getattr(sharding, "spec", None)
        dim = _sharded_dim(spec, zero_axes) if spec is not None else None
        if dim is None:
            return None
        entry = tuple(spec)[dim]
        gather_axes = tuple(ax for ax in (entry if isinstance(entry, tuple) else (entry, ))
                            if ax in set(zero_axes))
        return _make_quantized_gather(dim, spec, _gathered_spec(spec, zero_axes),
                                      gather_axes, mesh, compute_dtype,
                                      bits=bits, shard_shape=shape)

    def cast(params):
        def one(w, sharding):
            if not hasattr(w, "dtype") or not jnp.issubdtype(w.dtype, jnp.floating):
                return w  # match cast_tree: non-floating leaves pass through
            if w.ndim < 2 or int(np.prod(w.shape)) < threshold:
                return w.astype(compute_dtype)
            fn = leaf_cast_factory(sharding, tuple(w.shape))
            if fn is None:
                return w.astype(compute_dtype)
            return fn(w)

        return jax.tree.map(one, params, param_shardings)

    return cast
