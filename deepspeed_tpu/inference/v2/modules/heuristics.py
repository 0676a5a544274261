"""Module-implementation heuristics.

Reference: ``deepspeed/inference/v2/modules/heuristics.py:36-165``
(``instantiate_attn/linear/moe/...`` — pick a concrete kernel implementation
from the registry given the model+engine config). The TPU build has two real
attention implementations to arbitrate between; everything else is one
XLA-fused implementation, so the heuristic surface is exactly this choice.
"""

from deepspeed_tpu.utils.logging import logger


def attention_implementation(model, engine_config, bucket_tokens: int) -> str:
    """Pick the attention arm for a (model, bucket) pair.

    Returns ``"paged_token"`` or ``"paged_tiled"`` — the Pallas kernel of
    ops/pallas/paged_attention.py (the reference's blocked_flash role) on its
    per-token grid (buckets of at most ``TOKEN_GRID_MAX`` = 32 tokens: decode
    steps and ``decode_loop``) or its query-tile grid (every larger bucket:
    prefill and mixed steps) — or ``"xla_gather"`` (scatter + dense
    per-sequence gather: whole-pool layout copies every step on a TPU, PERF.md
    §6 PR 24; what the CPU runs, and the check the kernel is tested against).
    A sliding-window model (``attention_window`` > 0) is chosen for like any
    other: every arm masks the window, and the kernel also starts its block
    walk at the window's first block. Policy:

    - an explicit ``use_paged_kernel`` config wins: kernel (grid by bucket) or
      gather;
    - otherwise the kernel needs a TPU backend and VMEM room for its
      double-buffered K/V chunks and, on the tile grid, the tile's state.
    """
    from deepspeed_tpu.ops.pallas.paged_attention import (CHUNK, TOKEN_GRID_MAX,
                                                          tile_grid_vmem_bytes)
    flag = getattr(engine_config, "use_paged_kernel", None)
    kernel = "paged_token" if bucket_tokens <= TOKEN_GRID_MAX else "paged_tiled"
    if flag is not None:
        return kernel if flag else "xla_gather"
    import jax
    if jax.default_backend() != "tpu":
        return "xla_gather"
    bs = engine_config.kv_block_size
    scratch_bytes = 2 * 2 * CHUNK * model.num_kv_heads * bs * model.head_dim * 2
    held = scratch_bytes if kernel == "paged_token" else tile_grid_vmem_bytes(
        model.num_heads, model.num_kv_heads, model.head_dim, bs)
    # of the ~16MB a kernel may use: half for the chunks, three quarters in all
    if scratch_bytes > 8 * 1024 * 1024 or held > 12 * 1024 * 1024:
        logger.warning(f"paged kernel K/V scratch {scratch_bytes >> 20}MB ({held >> 20}MB held "
                       f"in all) exceeds VMEM budget (kv_block_size={bs}); using the XLA "
                       f"gather path")
        return "xla_gather"
    return kernel
