"""Module-implementation heuristics.

Reference: ``deepspeed/inference/v2/modules/heuristics.py:36-165``
(``instantiate_attn/linear/moe/...`` — pick a concrete kernel implementation
from the registry given the model+engine config). The TPU build has two real
attention implementations to arbitrate between; everything else is one
XLA-fused implementation, so the heuristic surface is that choice and, for a
sparse model on one replica, how the tokens reach their experts
(``moe_implementation``) and, where a layer holds a share of them, how many of
the sorted rows it walks (``moe_row_window``).
"""

import math

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
    walk at the window's first block. A model that attends under a block mask
    (``attention_block`` > 0: generation by diffusion over blocks) takes the
    tile grid at EVERY bucket: a tile's pass inserts its rows before it walks,
    so a row sees the later rows of its block; the token grid attends row t
    before row t + 1 is in the pool, and refuses such a model by name. Policy:

    - an explicit ``use_paged_kernel`` config wins: kernel (grid by bucket) or
      gather;
    - otherwise the kernel needs a TPU backend and VMEM room for its
      double-buffered K/V chunks and, on the tile grid, the tile's state.
    """
    from deepspeed_tpu.ops.pallas.paged_attention import (CHUNK, TOKEN_GRID_MAX,
                                                          VMEM_CEILING_BYTES,
                                                          tile_grid_vmem_bytes)
    flag = getattr(engine_config, "use_paged_kernel", None)
    kernel = ("paged_token" if bucket_tokens <= TOKEN_GRID_MAX
              and not getattr(model, "attention_block", 0) else "paged_tiled")
    if flag is not None:
        return kernel if flag else "xla_gather"
    import jax
    if jax.default_backend() != "tpu":
        return "xla_gather"
    bs = engine_config.kv_block_size
    scratch_bytes = 2 * 2 * CHUNK * model.num_kv_heads * bs * model.head_dim * 2
    held = scratch_bytes if kernel == "paged_token" else tile_grid_vmem_bytes(
        model.num_heads, model.num_kv_heads, model.head_dim, bs,
        block=getattr(model, "attention_block", 0))
    # half of the ~16MB a kernel is granted unasked for the chunks; in all, three
    # quarters of the most the kernel asks for (paged_attention.vmem_params)
    if scratch_bytes > 8 * 1024 * 1024 or held > VMEM_CEILING_BYTES * 3 // 4:
        logger.warning(f"paged kernel K/V scratch {scratch_bytes >> 20}MB ({held >> 20}MB held "
                       f"in all) exceeds VMEM budget (kv_block_size={bs}); using the XLA "
                       f"gather path")
        return "xla_gather"
    return kernel


# The grouped path is taken where the one-hot masks of the capacity path cost a
# real share of the experts. Per buffer row the two mask einsums
# (``tec,tm->ecm`` and ``tec,ecm->tm``) are 4*T*M flops and the expert GEMMs
# 6*M*F, so the masks are 2T / 3F of the experts: 1/20 is where the traces
# began to see them (``moe_route_busy_pct`` 0.3-2.9 on Mixtral's F = 14336 at
# T <= 256, where the share is at most 1.2 %; 30 + 10 unscoped on Mellum's
# F = 896 at T = 256, where it is 19 %: PERF.md section 6, PR 32).
MOE_MASK_SHARE_MIN = 1 / 20
# ... and where the masks are arrays worth the name: under 2^20 elements
# ([tokens, experts, capacity]; a float32 and an activation-dtype one) their
# fill is microseconds, less than a sort and two gathers of the rows.
MOE_MASK_ELEMENTS_MIN = 1 << 20
# The other thing the capacity path pays for is the BANKS: its einsums multiply
# every expert's matrices whatever was routed. A bucket whose assignments
# (tokens x top-k) number at most half the experts cannot touch more than half
# of the banks, whatever the router does, and the grouped kernel reads only the
# banks that have rows: a decode step's 8 rows at top-8 of 128 touch ~52 banks
# a layer, 0.94 ms of both projections where the einsums over all 128 take
# 2.15 (a TPU v5e; PERF.md section 6, PR 35). The bound is the guaranteed one,
# not the expected share under some router: Mellum's 8 rows at top-8 of 64
# (~42 banks expected, 64 possible) stay on the capacity path.
MOE_BANKS_TOUCHED_MAX = 1 / 2


def moe_implementation(tokens: int, num_experts: int, top_k: int, capacity: int,
                       intermediate: int, expert_parallel: int = 1, held=None) -> str:
    """How a ``tokens``-token bucket reaches its experts: ``"grouped"`` (rows
    sorted by expert, one grouped matmul a projection that reads only the banks
    that have rows, dropless whatever the skew) or ``"capacity"``
    (``[tokens, experts, capacity]`` one-hot masks into static per-expert
    buffers, every bank multiplied). A pure function of static shapes:
    ``top_k`` experts a token, ``capacity`` what the capacity path would give
    an expert for this bucket, ``intermediate`` the experts' width F,
    ``expert_parallel`` the size of the mesh's expert axis (its two
    all-to-alls need the static per-destination buffers, so anything over 1
    answers ``capacity``), ``held`` the experts a layer holds where that is a
    SHARE of the ``num_experts`` it routes over: most assignments then land on
    no bank of the layer's, the capacity path would give every held expert a
    slot a token to stay dropless, and such a layer routes by sorting whatever
    the bucket."""
    if expert_parallel > 1:
        return "capacity"
    if held is not None and held < num_experts:
        return "grouped"
    if tokens * top_k <= num_experts * MOE_BANKS_TOUCHED_MAX:
        return "grouped"
    mask_share = 2 * tokens / (3 * intermediate)
    mask_elements = tokens * num_experts * capacity
    if mask_share >= MOE_MASK_SHARE_MIN and mask_elements >= MOE_MASK_ELEMENTS_MIN:
        return "grouped"
    return "capacity"


# A layer that holds a SHARE of the experts it routes over sorts the choices
# that landed on it first and, uniformly routed, expects ``tokens * top_k *
# held / outputs`` of them. Its dispatch, activation and combine walk the
# sorted rows a WINDOW of that many times this factor at a time (whole row
# tiles), as far as the device's own count reaches: one window unless the
# router is skewed. LongCat's 256-token step walks 256 of 3,072 rows for ~64
# that land (a binomial's 24 standard deviations of room), DeepSeek's 512 of
# 2,048 for ~131. A smaller factor saves microseconds of a window's gather and
# matmul; 4 keeps the second window for a router that is wrong, not unlucky.
MOE_WINDOW_FACTOR = 4


def moe_row_window(tokens: int, top_k: int, outputs: int, held=None):
    """Rows of the sorted buffer a layer that holds ``held`` of its router's
    ``outputs`` (the experts with a bank and without) walks at a time from
    dispatch to combine: ``MOE_WINDOW_FACTOR`` times the expected local rows
    in whole row tiles, never under one; or None where there is no window: a
    layer that holds every expert (every row is local), and a share whose
    window would pass half of the bucket's ``padded_rows(tokens * top_k)``
    (a loop that saves less than half is not worth a program of its own:
    Kimi's 64 of 256, Nemotron's 64 of 128, any 8-row decode bucket, whose
    rows are one tile). A pure function of static shapes; how many windows a
    step walks is the device's to say (``RaggedMoE._grouped_forward``)."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import ROW_TILE, padded_rows
    if held is None or held >= outputs:
        return None
    expected = tokens * top_k * held / outputs
    window = max(ROW_TILE, padded_rows(math.ceil(MOE_WINDOW_FACTOR * expected)))
    return window if 2 * window <= padded_rows(tokens * top_k) else None
