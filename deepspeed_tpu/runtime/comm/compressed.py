"""Compressed collectives: 1-bit error-feedback allreduce and qgZ-style
quantized reduce-scatter.

Reference: ``deepspeed/runtime/comm/nccl.py:51`` (compressed_allreduce — sign
compression with worker+server error feedback, chunked all-to-all then
allgather) and ``deepspeed/runtime/comm/coalesced_collectives.py:31``
(all_to_all_quant_reduce — ZeRO++ qgZ int8 hierarchical gradient reduction,
backed by ``csrc/quantization`` swizzled-quant kernels).

TPU formulation: the same wire math expressed over a mesh axis inside
``shard_map`` — XLA lowers the exchanges to the identical
all-to-all/reduce-scatter/all-gather pattern on ICI/DCN, with the quantized
payloads as int8 arrays (1 byte/element on the wire instead of 4). The sign
compression keeps both error-feedback states exactly as the reference does:
``worker_error`` is full-size per rank, ``server_error`` is chunk-size.
"""

from typing import Tuple

import numpy as np

from deepspeed_tpu.utils import groups


def _sign_compress(x):
    """1-bit compression: per-tensor L1 scale + sign (reference
    NcclBackend.compressed_allreduce worker phase)."""
    import jax.numpy as jnp
    scale = jnp.mean(jnp.abs(x))
    sign = jnp.sign(x).astype(jnp.int8)  # torch semantics: sign(0) == 0
    return scale, sign


def compressed_allreduce_local(x, worker_error, server_error, axis_name: str, n_ranks: int):
    """The per-rank body (call inside shard_map/jit with ``axis_name`` bound).

    x: this rank's full-size tensor [N] (N divisible by n_ranks);
    worker_error: [N]; server_error: [N // n_ranks].
    Returns (averaged tensor [N], new_worker_error, new_server_error)."""
    import jax
    import jax.numpy as jnp

    N = x.shape[0]
    chunk = N // n_ranks

    # worker compression
    compensated = x + worker_error
    w_scale, w_sign = _sign_compress(compensated)
    new_worker_error = compensated - w_scale * w_sign.astype(x.dtype)

    # exchange: every rank receives all ranks' signs for ITS chunk — the
    # reference's chunked all_to_all; int8 on the wire
    my_signs = jax.lax.all_to_all(w_sign.reshape(n_ranks, chunk), axis_name, 0, 0,
                                  tiled=True)  # [n_ranks, chunk] int8, rows = sources
    scales = jax.lax.all_gather(w_scale, axis_name)  # [n_ranks] f32
    server_avg = jnp.einsum("r,rc->c", scales, my_signs.astype(x.dtype)) / n_ranks

    # server compression of the owned chunk
    comp_server = server_avg + server_error
    s_scale, s_sign = _sign_compress(comp_server)
    new_server_error = comp_server - s_scale * s_sign.astype(x.dtype)

    # allgather the compressed server chunks back to everyone
    all_signs = jax.lax.all_gather(s_sign, axis_name)       # [n_ranks, chunk] int8
    all_scales = jax.lax.all_gather(s_scale, axis_name)     # [n_ranks]
    out = (all_scales[:, None] * all_signs.astype(x.dtype)).reshape(N)
    return out, new_worker_error, new_server_error


def compressed_allreduce(tensor, worker_error, server_error, axis_name=None, mesh=None):
    """Host-level entry: runs the 1-bit allreduce over a mesh axis via
    shard_map; inputs are replicated full-size arrays (the engine's grads)."""
    import jax
    from jax.sharding import PartitionSpec as P

    axis_name = axis_name or groups.DATA_AXIS
    mesh = mesh if mesh is not None else groups.get_mesh()
    n = int(mesh.shape.get(axis_name, 1))
    if n <= 1:
        return tensor, worker_error, server_error

    fn = jax.shard_map(
        lambda x, we, se: compressed_allreduce_local(x[0], we[0], se[0], axis_name, n),
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        # worker/server error feedback is PER-RANK state: keep it sharded over
        # the axis (reference: each rank persists its own worker_error buffer)
        out_specs=(P(), P(axis_name), P(axis_name)),
        check_vma=False)
    # feed each rank its own (replicated) copy: stack over the axis
    import jax.numpy as jnp
    xs = jnp.broadcast_to(tensor, (n, ) + tensor.shape)
    wes = worker_error.reshape((n, -1)) if worker_error.ndim == 1 and \
        worker_error.shape[0] == n * tensor.shape[0] else jnp.broadcast_to(
            worker_error, (n, ) + worker_error.shape)
    ses = server_error.reshape((n, -1))
    out, we, se = fn(xs, wes, ses)
    # flat stacked layouts ([n*N] / [N]) so the next call's reshape round-trips
    return out, we.reshape(-1), se.reshape(-1)


def quantized_reduce_scatter_local(x, axis_name: str, n_ranks: int, block: int = 512):
    """qgZ-analog body (inside shard_map): blockwise-int8 quantize the local
    gradient, all-to-all the int8 payload + f32 block scales, dequantize and
    sum locally → this rank's reduced chunk. 4x wire compression vs f32
    reduce-scatter (reference all_to_all_quant_reduce,
    coalesced_collectives.py:31)."""
    import jax
    import jax.numpy as jnp

    N = x.shape[0]
    chunk = N // n_ranks
    # pad each rank's chunk up to whole blocks so any N divisible by n_ranks
    # works (the padding quantizes to exact zeros and is sliced off)
    nb = -(-chunk // block)
    pad = nb * block - chunk

    v = x.reshape(n_ranks, chunk)
    if pad:
        v = jnp.pad(v, ((0, 0), (0, pad)))
    v = v.reshape(n_ranks, nb, block)
    scale = jnp.max(jnp.abs(v), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)

    q_recv = jax.lax.all_to_all(q, axis_name, 0, 0, tiled=True)          # int8 wire
    s_recv = jax.lax.all_to_all(scale, axis_name, 0, 0, tiled=True)      # f32 scales
    deq = q_recv.astype(jnp.float32) * s_recv
    return jnp.sum(deq, axis=0).reshape(nb * block)[:chunk]


def quantized_reduce_scatter(tensor, axis_name=None, mesh=None, block: int = 512):
    """Host-level qgZ-style reduce-scatter: dim0 of ``tensor`` = per-rank
    contiguous input copies (the comm API's layout); returns dim0 = per-rank
    reduced chunks."""
    import jax
    from jax.sharding import PartitionSpec as P

    axis_name = axis_name or groups.DATA_AXIS
    mesh = mesh if mesh is not None else groups.get_mesh()
    n = int(mesh.shape.get(axis_name, 1))
    if n <= 1:
        return tensor
    if tensor.shape[-1] % n != 0:
        raise ValueError(f"reduce-scatter length {tensor.shape[-1]} must be divisible "
                         f"by the axis size {n} (pad the flat gradient first)")

    fn = jax.shard_map(
        lambda x: quantized_reduce_scatter_local(x[0], axis_name, n, block),
        mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name), check_vma=False)
    return fn(tensor)
