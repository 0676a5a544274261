"""Grouped matmul: expert-sorted rows against a bank of expert matrices.

``rows [R, K]`` are sorted by group, ``group_sizes [G]`` says how many rows
each group has, ``bank [G, K, N]`` holds a matrix a group: the result's row r
is ``rows[r] @ bank[group of r]``, accumulated in float32. The reference's
CUTLASS grouped GEMM (``moe_gemm.cu``) in the role ``RaggedMoE``'s grouped
path gives it.

The kernel is ``megablox.gmm``'s design (``jax.experimental.pallas.ops.tpu.
megablox``) cut to what this path needs. Rows are cut into tiles of
``ROW_TILE``; a VISIT is one (group, row tile) pair in which the group has
rows, and the visits are laid out group-major from ``group_sizes``
(:func:`group_visits`: once a layer, shared by the layer's two projections).
The grid is (column block, visit) with the visit's group and row tile read by
scalar prefetch: the whole contraction is one block (``tk = K``), so a group's
``[K, tn]`` block of the bank stays in VMEM across its row tiles and a row
tile stays across the groups that share it — every byte of a group's bank is
read once, and a group without rows is no visit: its bank is not read at all
(the visit dimension of the grid is the dynamic count of visits).
A visit multiplies the whole row tile and stores only its group's rows.
The bank is an operand: one kernel a projection serves every layer of a
program.

Measured at Mellum-2's shapes (2,048 rows over 64 groups, banks
``[64, 2304, 1792]`` and ``[64, 896, 2304]`` bf16, a TPU v5e; PERF.md section
6, PR 32): both projections 1.30 ms a layer with column blocks of 896 and 1152,
1.36 at 512 and 768, 1.49 at 256, 10.4 with the contraction cut into 128s, and
5.15 for ``jax.lax.ragged_dot`` as XLA's TPU backend lowers it; the padded
einsums of the capacity path take 1.55.

Measured at a decode step's shape (PR 35; Trinity-Mini's 8 rows x top-8 = 64
assignments in ONE 128-row tile over 128 groups, banks ``[128, 2048, 2048]``
and ``[128, 1024, 2048]`` bf16, column blocks of 1024 = bank tiles of 4 and
2 MiB; PERF.md section 6, PR 35): both projections 0.94 ms a layer with 53
groups touched (a random router's), 1.13 with 64, 2.21 with a row in each of
the 128 and 0.04 with all rows in one: 710-730 GB/s of the TOUCHED banks'
bytes whatever their number, where the capacity path's einsums over all 128
banks take 2.15. A row tile of 64 or 32 for the same rows reads the same
(0.94, 0.94): a visit's matmul hides behind its bank tile's DMA, so the tile
stays 128 for every bucket. Inside ``decode_loop``'s ``lax.scan`` the kernel
runs as it does outside.

Everywhere else (the CPU that the tests run on) it is ``jax.lax.ragged_dot``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows a row tile holds; the sorted buffer is padded to a multiple of it.
ROW_TILE = 128
# The most a bank tile [K, tn] may hold: two of them (the pipeline's double
# buffer) with the row tile and the accumulator stay under the 16 MiB of VMEM
# a kernel gets by default ...
BANK_TILE_BYTES = 4 * 2**20
# ... and the widest column block: past 1152 (nine lane tiles) a block measured
# no faster (1.288 against 1.294 ms a layer) and took 0.12 s longer to compile.
COLUMN_TILE_MAX = 1152


def padded_rows(rows: int) -> int:
    """``rows`` rounded up to whole row tiles."""
    return -(-rows // ROW_TILE) * ROW_TILE


def lane_padded(width: int) -> int:
    """``width`` in whole 128-lane tiles: what :func:`column_tile` asks of a
    bank's output width (a device array's minor dimension is tiled so anyway)."""
    return -(-width // 128) * 128


def column_tile(K: int, N: int, itemsize: int):
    """The column block ``tn`` for rows ``[R, K]`` against a bank ``[G, K, N]``,
    or None where the shapes are not the kernel's (a width that is no multiple
    of the 128 lanes, a contraction too long for one block)."""
    if K % 128 or N % 128 or K * 128 * itemsize > BANK_TILE_BYTES:
        return None
    return max(n for n in range(128, min(N, COLUMN_TILE_MAX) + 1, 128)
               if N % n == 0 and K * n * itemsize <= BANK_TILE_BYTES)


def group_visits(group_sizes, rows: int):
    """The kernel's schedule for ``rows`` sorted rows in groups of
    ``group_sizes [G]``: ``(offsets [G + 1], groups [V], tiles [V], visits)``
    — each group's first row, then for visit v its group and its row tile, and
    how many of the ``V = rows / ROW_TILE + G - 1`` slots are visits (a group
    is visited once a row tile it has rows in; a tile is shared by at most the
    groups that start in it, so V bounds the sum). Group-major, row tiles
    ascending: consecutive visits share a group or a row tile."""
    G = group_sizes.shape[0]
    V = rows // ROW_TILE + G - 1
    group_sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(group_sizes)
    first_tile = (ends - group_sizes) // ROW_TILE
    n_tiles = jnp.where(group_sizes > 0, (ends - 1) // ROW_TILE - first_tile + 1, 0)
    visit_end = jnp.cumsum(n_tiles)
    slots = jnp.arange(V, dtype=jnp.int32)
    # the group of visit v: the first whose visits end behind v
    groups = jnp.minimum((visit_end[None, :] <= slots[:, None]).sum(1, dtype=jnp.int32), G - 1)
    tiles = first_tile[groups] + slots - (visit_end - n_tiles)[groups]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return offsets, groups, jnp.clip(tiles, 0, rows // ROW_TILE - 1), visit_end[-1]


def _kernel(offsets, groups, tiles, rows_ref, bank_ref, out_ref):
    visit = pl.program_id(1)
    group = groups[visit]
    row = tiles[visit] * ROW_TILE + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    mine = (row >= offsets[group]) & (row < offsets[group + 1])
    acc = jnp.dot(rows_ref[...], bank_ref[...], preferred_element_type=jnp.float32)
    # the row tile's other rows are another visit's (or nobody's: left as found)
    out_ref[...] = jnp.where(mine, acc, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def grouped_matmul(rows, bank, group_sizes, out_dtype, *, visits=None, interpret=False):
    """``rows [R, K] @ bank[g] [K, N]`` group by group; rows behind the last
    group come out undefined (the kernel never visits them). ``visits`` is
    :func:`group_visits` of ``group_sizes`` where the caller has it already."""
    R, K = rows.shape
    tn = column_tile(K, bank.shape[2], bank.dtype.itemsize)
    if not (interpret or jax.default_backend() == "tpu") or tn is None or R % ROW_TILE:
        return jax.lax.ragged_dot(rows, bank, group_sizes, preferred_element_type=out_dtype)
    if visits is None:
        visits = group_visits(group_sizes, R)
    return _projection(*visits, rows, bank, tn=tn, out_dtype=jnp.dtype(out_dtype),
                       interpret=interpret)


# Under ``jax.jit``: the layers of a program (and the programs of a process)
# that call it with the same shapes share one trace, and a program lowers the
# kernel once a projection, not once a layer (0.19 s against 0.59 s of
# lowering a 4-layer program from a warm cache: PERF.md section 6, PR 32).
@functools.partial(jax.jit, static_argnames=("tn", "out_dtype", "interpret"))
def _projection(offsets, groups, tiles, n_visits, rows, bank, *, tn, out_dtype, interpret):
    R, K = rows.shape
    N = bank.shape[2]
    call = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((R, N), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((ROW_TILE, K), lambda n, v, offsets, groups, tiles: (tiles[v], 0)),
                      pl.BlockSpec((None, K, tn),
                                   lambda n, v, offsets, groups, tiles: (groups[v], 0, n))],
            out_specs=pl.BlockSpec((ROW_TILE, tn),
                                   lambda n, v, offsets, groups, tiles: (tiles[v], n)),
            grid=(N // tn, n_visits)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * K * N, transcendentals=0,
            bytes_accessed=(bank.size + (N // tn) * R * K) * bank.dtype.itemsize
            + R * N * out_dtype.itemsize),
        name="grouped_matmul", interpret=interpret)
    return call(offsets, groups, tiles, rows, bank)
