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
``[K, tn]`` tile of the bank stays in VMEM across its row tiles and a row
tile stays across the groups that share it — every byte of a group's bank is
read once, and a group without rows is no visit: its bank is not read at all
(the visit dimension of the grid is the dynamic count of visits).
A visit multiplies the whole row tile and stores only its group's rows.
The bank is an operand: one kernel a projection serves every layer of a
program.

The bank stays in HBM and the kernel walks its own ring of bank tiles (PR 60).
The tiles a call reads are ONE stream — column block by column block, each the
groups that have rows in order (``rank`` / ``touched`` of the schedule) — and a
group's first visit waits for its tile and starts the fetch of the tile
``RING_DEPTH - 1`` groups on, into the slot the group before has left. With
Pallas's own pipeline (the bank a ``BlockSpec``: two buffers, the next VISIT's
blocks fetched while a visit computes) the visit before a group's further row
tile had no bank tile to fetch behind its matmul and the next group's tile
started late: 11 us a further visit at Mellum's shapes. The ring holds three
tiles whatever the shapes (with the row tile and the output tile at most
20.25 MiB for a shape ``column_tile`` admits), and the call states the VMEM
it holds (:func:`ring_vmem_bytes`, 14.25 MiB for Mellum's gate|up bank) as
``paged_attention.vmem_params`` has it.
``pl.Buffered(3)`` on the bank's ``BlockSpec`` would say the same in a line:
jax 0.9.0's Mosaic lowering takes one or two buffers only.

Measured at Mellum-2's shapes (2,048 rows over 64 groups, banks
``[64, 2304, 1792]`` and ``[64, 896, 2304]`` bf16, a TPU v5e, the kernel alone
in a loop inside one program; PERF.md section 6, PR 60 step 0; the bytes take
0.97 ms at 819 GB/s): with a random router's group sizes (79 visits, 15 of
them a group's further row tile) both projections 1.257 ms a layer on Pallas's
pipeline, 1.139 on a ring of two tiles, 1.088 on three (what lands), 1.092 on
four; with 32 rows in every group (64 visits, no further one) 1.089 on the
pipeline and 1.097-1.101 on the ring: three tiles hide every further visit,
and a second DMA in flight does not lift the stream's own ~89 %. A visit that
multiplies only the 32-row sub-tiles that hold its group's rows reads 1.165 /
1.086 (it halves what a further visit costs and hides none of it). Outputs are
the same bits in every form. Earlier (PR 32): 1.30 ms with column blocks of
896 and 1152, 1.36 at 512 and 768, 1.49 at 256, 10.4 with the contraction cut
into 128s, and 5.15 for ``jax.lax.ragged_dot`` as XLA's TPU backend lowers it;
the padded einsums of the capacity path take 1.55.

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
runs as it does outside. With the ring (PR 60, 51 groups touched): 0.889 on
the pipeline, 0.887-0.890 on the ring: one row tile has no further visit.

Everywhere else (the CPU that the tests run on) it is ``jax.lax.ragged_dot``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.paged_attention import vmem_params

# Rows a row tile holds; the sorted buffer is padded to a multiple of it.
ROW_TILE = 128
# The most a bank tile [K, tn] may hold: the ring's three with the row tile
# and the output tile twice are what the call states (``ring_vmem_bytes``),
# at most 20.25 MiB of a core's 128 ...
BANK_TILE_BYTES = 4 * 2**20
# ... the bank tiles the kernel's ring holds: a third hides a group's further
# row tiles (1.139 -> 1.088 ms a layer at Mellum's shapes), a fourth measured
# nothing more (1.092) ...
RING_DEPTH = 3
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


def _row_tiles(group_sizes):
    """``(ends [G], first_tile [G], n_tiles [G])``: behind each group's last
    row, the row tile its first row lies in, and how many row tiles it has
    rows in (0 for a group without rows)."""
    ends = jnp.cumsum(group_sizes)
    first_tile = (ends - group_sizes) // ROW_TILE
    n_tiles = jnp.where(group_sizes > 0, (ends - 1) // ROW_TILE - first_tile + 1, 0)
    return ends, first_tile, n_tiles


def visit_count(group_sizes):
    """How many visits :func:`group_visits` lays out for ``group_sizes [G]``:
    less the groups that have rows, the visits that are a group's FURTHER row
    tile (their bank tile is in VMEM already)."""
    return _row_tiles(group_sizes.astype(jnp.int32))[2].sum(dtype=jnp.int32)


def group_visits(group_sizes, rows: int):
    """The kernel's schedule for ``rows`` sorted rows in groups of
    ``group_sizes [G]``: ``(offsets [G + 1], groups [V], tiles [V], rank [G],
    touched [G], visits)`` — each group's first row; for visit v its group and
    its row tile; the bank stream (``rank[g]`` groups up to and with g have
    rows, and ``touched[b]`` is the b-th of them: the banks in the order they
    are read); and how many of the ``V = rows / ROW_TILE + G - 1`` slots are
    visits (a group is visited once a row tile it has rows in; a tile is
    shared by at most the groups that start in it, so V bounds the sum).
    Group-major, row tiles ascending: consecutive visits share a group or a
    row tile."""
    G = group_sizes.shape[0]
    V = rows // ROW_TILE + G - 1
    group_sizes = group_sizes.astype(jnp.int32)
    ends, first_tile, n_tiles = _row_tiles(group_sizes)
    visit_end = jnp.cumsum(n_tiles)
    slots = jnp.arange(V, dtype=jnp.int32)
    # the group of visit v: the first whose visits end behind v
    groups = jnp.minimum((visit_end[None, :] <= slots[:, None]).sum(1, dtype=jnp.int32), G - 1)
    tiles = first_tile[groups] + slots - (visit_end - n_tiles)[groups]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    rank = jnp.cumsum(group_sizes > 0, dtype=jnp.int32)
    # the b-th group with rows: the first that b + 1 such groups end with
    touched = jnp.minimum((rank[None, :] <= slots[:G, None]).sum(1, dtype=jnp.int32), G - 1)
    return (offsets, groups, jnp.clip(tiles, 0, rows // ROW_TILE - 1), rank, touched,
            visit_end[-1])


def ring_vmem_bytes(K: int, tn: int, itemsize: int, out_itemsize: int) -> int:
    """VMEM a call holds: the ring of bank tiles, the pipelined row tile and
    output tile (two buffers each) and the float32 product with the tile it is
    merged into."""
    return (RING_DEPTH * K * tn * itemsize + 2 * ROW_TILE * K * itemsize
            + 2 * ROW_TILE * tn * out_itemsize + 2 * ROW_TILE * tn * 4)


def _kernel(offsets, groups, tiles, rank, touched, rows_ref, bank_hbm, out_ref,
            ring, arrived):
    block, visit = pl.program_id(0), pl.program_id(1)
    tn = out_ref.shape[1]
    group = groups[visit]
    n_banks = rank[rank.shape[0] - 1]
    # the call's bank stream: column block by column block, each the touched
    # groups in order; this visit's bank tile is its ``fetch``-th
    fetch = block * n_banks + rank[group] - 1
    n_fetches = pl.num_programs(0) * n_banks

    def copy(f):
        column = pl.multiple_of(f // n_banks * tn, 128)
        return pltpu.make_async_copy(bank_hbm.at[touched[f % n_banks], :, pl.ds(column, tn)],
                                     ring.at[f % RING_DEPTH], arrived.at[f % RING_DEPTH])

    # a group's first visit waits for its tile and starts the fetch of the tile
    # ``RING_DEPTH - 1`` groups on, into the slot the group before has left: a
    # further row tile of this group computes under the fetches in flight
    @pl.when((visit == 0) | (groups[jnp.maximum(visit - 1, 0)] != group))
    def _():
        @pl.when(fetch == 0)
        def _():
            for first in range(RING_DEPTH - 1):
                pl.when(first < n_fetches)(copy(first).start)

        @pl.when(fetch + RING_DEPTH - 1 < n_fetches)
        def _():
            copy(fetch + RING_DEPTH - 1).start()

        copy(fetch).wait()

    row = tiles[visit] * ROW_TILE + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    mine = (row >= offsets[group]) & (row < offsets[group + 1])
    acc = jnp.dot(rows_ref[...], ring[fetch % RING_DEPTH], preferred_element_type=jnp.float32)
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
def _projection(offsets, groups, tiles, rank, touched, n_visits, rows, bank, *, tn, out_dtype,
                interpret):
    R, K = rows.shape
    N = bank.shape[2]
    itemsize = bank.dtype.itemsize
    call = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((R, N), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[pl.BlockSpec((ROW_TILE, K),
                                   lambda n, v, offsets, groups, tiles, *_: (tiles[v], 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((ROW_TILE, tn),
                                   lambda n, v, offsets, groups, tiles, *_: (tiles[v], n)),
            grid=(N // tn, n_visits),
            scratch_shapes=[pltpu.VMEM((RING_DEPTH, K, tn), bank.dtype),
                            pltpu.SemaphoreType.DMA((RING_DEPTH, ))]),
        # the stream runs on from a column block into the next: both grid
        # dimensions in order (Pallas's default)
        **vmem_params(ring_vmem_bytes(K, tn, itemsize, out_dtype.itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * K * N, transcendentals=0,
            bytes_accessed=(bank.size + (N // tn) * R * K) * itemsize
            + R * N * out_dtype.itemsize),
        name="grouped_matmul", interpret=interpret)
    return call(offsets, groups, tiles, rank, touched, rows, bank)
