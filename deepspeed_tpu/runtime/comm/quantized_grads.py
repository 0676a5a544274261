"""qgZ gradient-path wiring: int8 reduce-scatter of data-parallel gradients.

Reference: ``deepspeed/runtime/zero/stage_1_and_2.py`` with
``zero_quantized_gradients: true`` routing gradient reduction through
``coalesced_collectives.all_to_all_quant_reduce`` (ZeRO++ qgZ,
coalesced_collectives.py:73): gradients cross the wire as int8 blocks + fp32
scales (4× compression) and are dequant-summed on the receiving rank.

TPU formulation: the implicit SPMD gradient psum can't carry a custom wire
dtype — XLA owns it. So when qgZ is enabled the engine computes *per-rank
local* gradients inside ``shard_map`` over the data axis (no implicit
reduction exists there), flattens them, and reduces with the same blockwise
int8 all-to-all the comm tier provides
(``runtime/comm/compressed.quantized_reduce_scatter_local``). The HLO then
really contains an s8 all-to-all — wire compression, not decoration.

Scope (same envelope the reference ships): ZeRO ≤ 2 (params replicated across
the data axis) and data-parallel-only meshes; the engine falls back to the
exact psum path otherwise, with a warning.
"""

from functools import partial

from deepspeed_tpu.runtime.comm.compressed import quantized_reduce_scatter_local
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.logging import logger


def qgz_supported(mesh, stage: int) -> bool:
    """qgZ wiring needs replicated params (stage ≤ 2) and a pure-DP mesh."""
    if stage > 2:
        return False
    if mesh.shape.get(groups.DATA_AXIS, 1) <= 1:
        return False
    for ax in (groups.PIPE_AXIS, groups.HPZ_AXIS, groups.EXPERT_AXIS,
               groups.SEQ_AXIS, groups.MODEL_AXIS):
        if mesh.shape.get(ax, 1) > 1:
            return False
    return True


def make_qgz_micro_grads(loss_fn, takes_rng, compute_dtype, accum_dtype, mesh,
                         block: int = 512):
    """Build a ``(params, batch, rng, scale) -> (loss, grads)`` function whose
    data-parallel gradient reduction is the int8 reduce-scatter.

    Returned grads are replicated full trees in ``accum_dtype`` (the engine's
    ``out_shardings`` then reshard them into the ZeRO-2 partition — a layout
    move, not another reduction)."""
    import jax
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree
    from jax.sharding import PartitionSpec as P

    axis = groups.DATA_AXIS
    n = int(mesh.shape[axis])

    def local_body(params, batch, rng, scale):
        # per-rank: local-batch gradients, NO implicit cross-rank reduction
        def scaled(p):
            from deepspeed_tpu.runtime.utils import cast_tree
            cp = cast_tree(p, compute_dtype)
            out = loss_fn(cp, batch, rng) if takes_rng else loss_fn(cp, batch)
            loss = out[0] if isinstance(out, tuple) else out
            return loss.astype(jnp.float32) * scale, loss

        (_, loss), grads = jax.value_and_grad(scaled, has_aux=True)(params)
        flat, _ = ravel_pytree(jax.tree.map(lambda g: g.astype(jnp.float32), grads))
        pad = (-flat.shape[0]) % (n * block)
        flat = jnp.pad(flat, (0, pad))
        # int8 wire: blockwise quant + all-to-all + dequant-sum → my chunk
        chunk = quantized_reduce_scatter_local(flat, axis, n, block) / n
        return jax.lax.pmean(loss, axis), chunk

    def fn(params, batch, rng, scale):
        sample = jax.eval_shape(
            lambda p: ravel_pytree(p)[0],
            jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32), params))
        total = sample.shape[0]

        body = jax.shard_map(
            local_body,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params),
                      jax.tree.map(lambda _: P(axis), batch),
                      P(), P()),
            out_specs=(P(), P(axis)),
            check_vma=False)
        loss, flat = body(params, batch, rng, scale)
        # unravel the (sharded) flat vector back into the gradient tree
        _, unravel = ravel_pytree(
            jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), params))
        grads = unravel(flat[:total])
        return loss, jax.tree.map(lambda g: g.astype(accum_dtype), grads)

    return fn
