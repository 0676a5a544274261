"""``RaggedMoE``'s grouped path (PR 32): on one replica, where
``heuristics.moe_implementation`` says so, the assignments are sorted by expert
and the experts are one grouped matmul a projection: no ``[tokens, experts,
capacity]`` mask, no capacity, nothing dropped, and no bank read that has no
row (PR 35: a decode step's 8 rows at top-8 of 128). Held here, in float32 on
the CPU, to a dense reference and to the capacity path; the rule is held as a
table over the buckets the benchmark's configurations warm, and at Mixtral's
PUBLISHED shapes ``RaggedMoE.__call__`` must trace what the capacity path
traces (every program the Mixtral cells run is then the parent's)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.modules import heuristics
from deepspeed_tpu.inference.v2.modules.heuristics import moe_implementation
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import token_buckets
from deepspeed_tpu.ops.pallas.grouped_matmul import (RING_DEPTH, ROW_TILE, column_tile,
                                                      group_visits, grouped_matmul, padded_rows,
                                                      ring_vmem_bytes, visit_count)
from deepspeed_tpu.ops.pallas.paged_attention import SCOPED_VMEM_BYTES, VMEM_CEILING_BYTES
from deepspeed_tpu.utils import groups


def _layer(E, T, M, F, seed=0, favourite=None):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(T, M)), jnp.float32).at[:, 0].set(1.0)
    gate_w = jnp.asarray(rng.normal(size=(M, E)), jnp.float32)
    if favourite is not None:  # every token's first choice
        gate_w = gate_w.at[0, favourite].set(30.0)
    wi = jnp.asarray(rng.normal(size=(E, M, 2 * F)) / np.sqrt(M), jnp.float32)
    wo = jnp.asarray(rng.normal(size=(E, F, M)) / np.sqrt(F), jnp.float32)
    return h, gate_w, wi, wo


def _dense_reference(h, gate_w, wi, wo, top_k, norm):
    """Every expert over every token, weighted by the routing weight (0 for an
    expert the token did not choose), in float64."""
    h, gate_w, wi, wo = (np.asarray(a, np.float64) for a in (h, gate_w, wi, wo))
    logits = h @ gate_w
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    chosen = np.argsort(-probs, axis=-1, kind="stable")[:, :top_k]
    weights = np.zeros_like(probs)
    np.put_along_axis(weights, chosen, np.take_along_axis(probs, chosen, -1), -1)
    if norm:
        weights /= weights.sum(-1, keepdims=True)
    pre = np.einsum("tm,emf->etf", h, wi)
    gate, up = np.split(pre, 2, axis=-1)
    out = np.einsum("etf,efm->etm", gate / (1 + np.exp(-gate)) * up, wo)
    return np.einsum("te,etm->tm", weights, out)


def _grouped(moe, h, gate_w, wi, wo, valid=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(moe._grouped_forward(h, gate_w, wi, wo, valid, jax.nn.silu, None))


def _capacity(moe, h, gate_w, wi, wo, valid=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(moe._dense_forward(h, gate_w, wi, wo, valid, jax.nn.silu, None))


# ------------------------------------------------------------------ the path ---
@pytest.mark.parametrize("norm", [True, False], ids=["renormalised", "raw"])
@pytest.mark.parametrize("top_k", [1, 2, 4, 8])
def test_the_grouped_path_against_the_dense_reference(top_k, norm):
    groups.initialize_mesh(force=True)
    E, T, M, F = 16, 40, 32, 24
    h, gate_w, wi, wo = _layer(E, T, M, F, seed=top_k)
    moe = RaggedMoE(num_experts=E, top_k=top_k, capacity_factor=1.0, norm_topk_prob=norm)
    got = _grouped(moe, h, gate_w, wi, wo)
    np.testing.assert_allclose(got, _dense_reference(h, gate_w, wi, wo, top_k, norm),
                               atol=1e-4, rtol=0)


@pytest.mark.parametrize("top_k", [1, 2, 4, 8])
def test_invalid_tokens_change_no_valid_tokens_output_and_no_group(top_k):
    groups.initialize_mesh(force=True)
    E, T, M, F, live = 16, 40, 32, 24, 33
    h, gate_w, wi, wo = _layer(E, T, M, F, seed=10 + top_k)
    valid = jnp.arange(T) < live
    moe = RaggedMoE(num_experts=E, top_k=top_k)
    got = _grouped(moe, h, gate_w, wi, wo, valid)
    other = _grouped(moe, h.at[live:].set(1e3), gate_w, wi, wo, valid)
    alone = _grouped(moe, h[:live], gate_w, wi, wo)
    np.testing.assert_array_equal(got[:live], other[:live])
    np.testing.assert_allclose(got[:live], alone, atol=1e-5, rtol=0)
    assert not got[live:].any()
    # group_sizes count the live tokens' assignments alone
    sizes = _group_sizes(moe, h, gate_w, valid)
    assert sizes.sum() == live * top_k
    np.testing.assert_array_equal(sizes, _group_sizes(moe, h[:live], gate_w, None))


def _group_sizes(moe, h, gate_w, valid):
    """``group_sizes`` as ``_grouped_forward`` hands them to the experts."""
    seen = []
    moe._grouped_ffn = lambda buf, wi, wo, sizes, act: (seen.append(sizes),
                                                        jnp.zeros(buf.shape, jnp.float32))[1]
    try:
        moe._grouped_forward(h, gate_w, None, None, valid, jax.nn.silu, None)
    finally:
        del moe._grouped_ffn
    return np.asarray(seen[0])


@pytest.mark.parametrize("top_k", [1, 2, 4, 8])
def test_one_expert_takes_every_token_and_nothing_is_dropped(top_k):
    """Every token's first choice is expert 3, and with a sharp router most
    experts receive nothing: the grouped path computes every assignment where
    the capacity path at ``capacity_factor`` 1.0 drops."""
    groups.initialize_mesh(force=True)
    E, T, M, F = 16, 40, 32, 24
    h, gate_w, wi, wo = _layer(E, T, M, F, seed=20 + top_k, favourite=3)
    gate_w = gate_w.at[0, 8:].set(-30.0)  # half the experts are nobody's choice
    moe = RaggedMoE(num_experts=E, top_k=top_k, capacity_factor=1.0)
    sizes = _group_sizes(moe, h, gate_w, None)
    assert sizes[3] == T and sizes.sum() == T * top_k and not sizes[8:].any()
    want = _dense_reference(h, gate_w, wi, wo, top_k, True)
    np.testing.assert_allclose(_grouped(moe, h, gate_w, wi, wo), want, atol=1e-4, rtol=0)
    assert moe.capacity(T) < T
    dropping = _capacity(moe, h, gate_w, wi, wo)
    assert np.abs(dropping - want).max() > 1e-2


@pytest.mark.parametrize("top_k", [1, 2, 4, 8])
def test_equal_to_the_capacity_path_at_the_dropless_factor(top_k):
    groups.initialize_mesh(force=True)
    E, T, M, F = 16, 40, 32, 24
    h, gate_w, wi, wo = _layer(E, T, M, F, seed=30 + top_k, favourite=0)
    valid = jnp.arange(T) < 33
    moe = RaggedMoE(num_experts=E, top_k=top_k, capacity_factor=E / top_k)
    np.testing.assert_allclose(_grouped(moe, h, gate_w, wi, wo, valid),
                               _capacity(moe, h, gate_w, wi, wo, valid), atol=1e-5, rtol=0)


def test_under_jit_with_the_layers_bank_an_operand():
    """One jitted layer function, two layers' banks through it: the bank is an
    operand of the program, not a constant in it."""
    groups.initialize_mesh(force=True)
    E, T, M, F, top_k = 16, 40, 32, 24, 4
    moe = RaggedMoE(num_experts=E, top_k=top_k)
    traces = []

    @jax.jit
    def layer(h, gate_w, wi, wo, valid):
        traces.append(1)
        return moe._grouped_forward(h, gate_w, wi, wo, valid, jax.nn.silu, None)

    valid = jnp.arange(T) < 37
    for seed in (40, 41):
        h, gate_w, wi, wo = _layer(E, T, M, F, seed=seed)
        with jax.default_matmul_precision("highest"):
            got = np.asarray(layer(h, gate_w, wi, wo, valid))
        want = _dense_reference(h, gate_w, wi, wo, top_k, True)
        np.testing.assert_allclose(got[:37], want[:37], atol=1e-4, rtol=0)
    assert len(traces) == 1
    assert not jax.make_jaxpr(layer)(h, gate_w, wi, wo, valid).consts


def test_the_sorted_buffer_pads_to_whole_row_tiles():
    assert [padded_rows(n) for n in (1, 128, 129, 1024, 2048)] == [128, 128, 256, 1024, 2048]
    moe = RaggedMoE(num_experts=64, top_k=8, capacity_factor=8.0)
    assert moe.expert_rows(256, path="grouped") == 2048
    assert moe.expert_rows(256) == moe.expert_rows(256, path="capacity") == 64 * 256


# ------------------------------------------------------------------ the rule ---
MIXTRAL = dict(E=8, k=2, F=14336, M=4096, factor=4.0)   # mixtral-8x7b-serve-1chip
MELLUM = dict(E=64, k=8, F=896, M=2304, factor=8.0)     # mellum2-12b-a2.5b-serve-1chip
TRINITY = dict(E=128, k=8, F=1024, M=2048, factor=16.0)  # trinity-mini-serve-1chip


def _path(sizes, tokens, ep=1):
    moe = RaggedMoE(num_experts=sizes["E"], top_k=sizes["k"], capacity_factor=sizes["factor"])
    return moe.path(tokens, sizes["F"], ep)


@pytest.mark.parametrize("tokens", token_buckets(256))
def test_every_bucket_the_mixtral_configuration_warms_takes_the_capacity_path(tokens):
    assert _path(MIXTRAL, tokens) == "capacity"


@pytest.mark.parametrize("tokens,want", [(t, "grouped" if t >= 128 else "capacity")
                                         for t in token_buckets(256)])
def test_mellums_full_chunks_take_the_grouped_path(tokens, want):
    assert _path(MELLUM, tokens) == want


@pytest.mark.parametrize("tokens,want", [(8, "grouped"), (16, "capacity"), (32, "capacity"),
                                         (64, "capacity"), (128, "grouped"), (256, "grouped")])
def test_trinitys_decode_bucket_and_its_full_chunks_take_the_grouped_path(tokens, want):
    """8 rows x top-8 = 64 assignments cannot touch more than half of the 128
    banks; 16 rows can touch them all; the full chunks are PR 32's clause."""
    assert tokens in token_buckets(256)
    assert _path(TRINITY, tokens) == want


def test_the_buckets_are_the_ones_the_table_is_about():
    assert token_buckets(256)[-2:] == [128, 256] and 64 in token_buckets(256)


@pytest.mark.parametrize("sizes", [MIXTRAL, MELLUM], ids=["mixtral", "mellum"])
@pytest.mark.parametrize("ep", [2, 4, 8])
def test_an_expert_mesh_axis_takes_the_capacity_path(sizes, ep):
    assert _path(sizes, 256, ep) == "capacity"


@pytest.mark.parametrize("ep", [2, 4, 8])
def test_an_expert_mesh_axis_keeps_trinitys_decode_bucket_on_the_capacity_path(ep):
    assert _path(TRINITY, 8) == "grouped" and _path(TRINITY, 8, ep) == "capacity"


def test_the_rule_is_three_clauses_on_static_shapes():
    """One replica; the masks' share of the experts' flops (2T / 3F) with the
    masks' size; and the banks a bucket's assignments can touch at all."""
    E, k, C = 64, 8, 256
    big = heuristics.MOE_MASK_ELEMENTS_MIN
    assert moe_implementation(256, E, k, C, 896) == "grouped"
    # experts so wide that the masks are under a twentieth of them
    assert moe_implementation(256, E, k, C, 2 * 256 * 20 // 3 + 1) == "capacity"
    assert moe_implementation(256, E, k, C, 2 * 256 * 20 // 3) == "grouped"
    # masks too small to be worth a sort
    assert moe_implementation(128, 4, 2, 128, 128) == "capacity"
    assert 128 * 4 * 128 < big <= 128 * 64 * 128
    # the banks: both sides of tokens x top_k = experts / 2, whatever the width
    for F in (128, 1024, 14336):
        assert moe_implementation(8, 128, 8, 8, F) == "grouped"      # 64 = 128 / 2
        assert moe_implementation(8, 126, 8, 8, F) == "capacity"     # 64 > 63
        assert moe_implementation(9, 128, 8, 9, F) == "capacity"     # 72 > 64
        assert moe_implementation(16, 128, 4, 8, F) == "grouped"     # 64 again, by another way
        assert moe_implementation(8, 128, 8, 8, F, expert_parallel=2) == "capacity"
    assert moe_implementation(8, 64, 8, 8, 896) == "capacity"        # Mellum's decode bucket
    assert moe_implementation(8, 8, 2, 8, 14336) == "capacity"       # Mixtral's


# -------------------------------------------- Mixtral's programs stay as they are ---
@pytest.mark.parametrize("tokens", token_buckets(256))
def test_at_mixtrals_published_shapes_the_call_traces_the_capacity_path(tokens):
    """On abstract arrays (no memory, no flops): what ``RaggedMoE.__call__``
    traces for a bucket of the Mixtral configuration is, letter for letter,
    what ``_dense_forward`` (the capacity path) traces."""
    groups.initialize_mesh(force=True)
    E, M, F = MIXTRAL["E"], MIXTRAL["M"], MIXTRAL["F"]
    moe = RaggedMoE(num_experts=E, top_k=MIXTRAL["k"], capacity_factor=MIXTRAL["factor"])
    shapes = (jax.ShapeDtypeStruct((tokens, M), jnp.bfloat16),
              jax.ShapeDtypeStruct((M, E), jnp.bfloat16),
              jax.ShapeDtypeStruct((E, M, 2 * F), jnp.bfloat16),
              jax.ShapeDtypeStruct((E, F, M), jnp.bfloat16),
              jax.ShapeDtypeStruct((tokens, ), jnp.bool_))
    called = jax.make_jaxpr(lambda h, g, wi, wo, v: moe(
        h, g, wi, wo, token_valid=v, activation=jax.nn.silu, gate_seed=jnp.int32(0)))(*shapes)
    capacity = jax.make_jaxpr(lambda h, g, wi, wo, v: moe._dense_forward(
        h, g, wi, wo, v, jax.nn.silu, jnp.int32(0)))(*shapes)
    assert str(called) == str(capacity)
    assert " sort[" not in str(called) and "ragged_dot" not in str(called)


def test_at_mellums_published_shapes_the_full_chunk_traces_the_grouped_path():
    groups.initialize_mesh(force=True)
    E, M, F = MELLUM["E"], MELLUM["M"], MELLUM["F"]
    moe = RaggedMoE(num_experts=E, top_k=MELLUM["k"], capacity_factor=MELLUM["factor"])

    def text(tokens):
        shapes = (jax.ShapeDtypeStruct((tokens, M), jnp.bfloat16),
                  jax.ShapeDtypeStruct((M, E), jnp.bfloat16),
                  jax.ShapeDtypeStruct((E, M, 2 * F), jnp.bfloat16),
                  jax.ShapeDtypeStruct((E, F, M), jnp.bfloat16),
                  jax.ShapeDtypeStruct((tokens, ), jnp.bool_))
        return str(jax.make_jaxpr(lambda h, g, wi, wo, v: moe(
            h, g, wi, wo, token_valid=v, activation=jax.nn.silu))(*shapes))

    full, small = text(256), text(64)
    assert " sort[" in full and f"[{256 * 8},{M}]" in full.replace(" ", "")
    assert f"{256},{E},{256}]" not in full.replace(" ", "")  # no [T, E, C] mask
    assert " sort[" not in small and f"{64},{E},{64}]" in small.replace(" ", "")


# ---------------------------------------------------------------- the kernel ---
def test_the_bank_tile_keeps_the_contraction_whole_and_fits_the_budget():
    assert column_tile(2304, 1792, 2) == 896    # Mellum's gate|up bank, bf16
    assert column_tile(896, 2304, 2) == 1152    # its down bank
    assert column_tile(4096, 28672, 2) == 512   # Mixtral's, were it ever taken
    assert column_tile(48, 32, 4) is None and column_tile(128, 192, 4) is None  # not whole lanes
    assert column_tile(32768, 128, 2) is None   # a contraction over one block
    # the VMEM the kernel states (PR 60): a ring of THREE bank tiles, the row
    # tile and the output tile twice, the float32 product and what it merges
    # into; past the compiler's own 16 MiB less a quarter it asks for its own
    MiB = 2**20
    assert RING_DEPTH == 3
    for K, N, out in ((2304, 1792, 2), (896, 2304, 4), (2048, 2048, 2), (1024, 2048, 4)):
        tn = column_tile(K, N, 2)
        held = ring_vmem_bytes(K, tn, 2, out)
        assert K * tn * 2 <= 4 * MiB
        assert held == 3 * K * tn * 2 + 2 * 128 * K * 2 + 2 * 128 * tn * (out + 4)
    assert ring_vmem_bytes(2304, 896, 2, 2) == 14.25 * MiB > SCOPED_VMEM_BYTES * 3 // 4
    assert ring_vmem_bytes(896, 1152, 2, 4) < SCOPED_VMEM_BYTES * 3 // 4  # asks for nothing
    # the most a shape the kernel admits holds (a contraction as long as one
    # block allows, or the widest column block of a tile of 4 MiB) with its
    # quarter to spare stays under the most a kernel here asks for
    assert column_tile(16384, 128, 2) == 128 and column_tile(16384 + 128, 128, 2) is None
    for K, tn in ((16384, 128), (1792, 1152), (2048, 1024)):
        assert K * tn * 2 <= 4 * MiB
        assert ring_vmem_bytes(K, tn, 2, 4) * 5 // 4 <= 20.25 * MiB * 5 // 4 < VMEM_CEILING_BYTES


@pytest.mark.parametrize("sizes", [(37, 0, 91, 128), (256, 0, 0, 0), (0, 0, 0, 3), (128, 128, 0, 0),
                                   (1, 126, 2, 127), (0, 0, 0, 0)])
def test_the_visits_cover_every_groups_rows_once_group_major(sizes):
    """Every (group, row tile) pair in which the group has rows is a visit, in
    group-major order, and nothing else is."""
    R, G = 256, len(sizes)
    offsets, groups, tiles, rank, touched, n = (
        np.asarray(a) for a in group_visits(jnp.asarray(sizes, jnp.int32), R))
    starts = np.cumsum([0] + list(sizes))
    np.testing.assert_array_equal(offsets, starts)
    want = [(g, t) for g in range(G) for t in range(R // 128)
            if sizes[g] and starts[g] < (t + 1) * 128 and starts[g + 1] > t * 128]
    assert groups.shape == tiles.shape == (R // 128 + G - 1, )
    assert list(zip(groups[:n].tolist(), tiles[:n].tolist())) == want
    assert (tiles >= 0).all() and (tiles < R // 128).all() and (groups < G).all()
    # the bank stream (PR 60): the groups that have rows, in order, and each
    # group's place in it; the count alone is what the step's span carries
    with_rows = [g for g in range(G) if sizes[g]]
    assert rank.shape == touched.shape == (G, ) and (touched < G).all()
    np.testing.assert_array_equal(rank, np.cumsum(np.asarray(sizes) > 0))
    assert touched[:len(with_rows)].tolist() == with_rows
    assert int(visit_count(jnp.asarray(sizes, jnp.int32))) == int(n) == len(want)


def _parent_kernel(rows, bank, group_sizes, out_dtype):
    """The kernel as PR 59 had it, in interpret mode: the bank a ``BlockSpec``
    of Pallas's own pipeline (one fetch in flight, one visit ahead), the same
    visits, the same whole-tile product and merge. What PR 60's ring must
    equal bit for bit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (R, K), N = rows.shape, bank.shape[2]
    tn = column_tile(K, N, bank.dtype.itemsize)
    offsets, groups, tiles, _, _, n_visits = group_visits(group_sizes, R)

    def kernel(offsets, groups, tiles, rows_ref, bank_ref, out_ref):
        group, tile = groups[pl.program_id(1)], tiles[pl.program_id(1)]
        row = tile * ROW_TILE + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
        mine = (row >= offsets[group]) & (row < offsets[group + 1])
        acc = jnp.dot(rows_ref[...], bank_ref[...], preferred_element_type=jnp.float32)
        out_ref[...] = jnp.where(mine, acc, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((R, N), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((ROW_TILE, K), lambda n, v, _, groups, tiles: (tiles[v], 0)),
                      pl.BlockSpec((None, K, tn), lambda n, v, _, groups, tiles: (groups[v], 0, n))],
            out_specs=pl.BlockSpec((ROW_TILE, tn), lambda n, v, _, groups, tiles: (tiles[v], n)),
            grid=(N // tn, n_visits)),
        interpret=True)(offsets, groups, tiles, rows, bank)


def _decode_step_sizes(kind, G=128, assignments=64):
    """Group sizes of a decode step's (at most) 64 assignments over 128 groups."""
    rng = np.random.default_rng(len(kind))
    sizes = np.zeros(G, np.int32)
    # live rows, each the top-8 of a random order: 8 touch ~52 distinct groups;
    # with 3 the rest of the row tile is nobody's
    live = {"routed": 8, "few-live": 3}.get(kind)
    if live:
        np.add.at(sizes, np.concatenate([rng.permutation(G)[:8] for _ in range(live)]), 1)
    elif kind == "one-group":
        sizes[G // 3] = assignments
    else:  # "distinct"
        sizes[rng.permutation(G)[:assignments]] = 1
    return sizes


DECODE_KINDS = ["routed", "one-group", "distinct", "few-live"]


@pytest.mark.parametrize("kind", DECODE_KINDS)
def test_a_decode_steps_schedule_visits_each_group_that_has_rows_once(kind):
    """64 assignments pad to ONE row tile: a visit a non-empty group, in group
    order, and the grid's dynamic extent is the number of distinct groups, so
    an untouched group's bank is no visit's operand."""
    sizes = _decode_step_sizes(kind)
    R = padded_rows(64)
    assert R == 128
    _, groups, tiles, rank, stream, n = (np.asarray(a) for a in group_visits(jnp.asarray(sizes), R))
    touched = np.flatnonzero(sizes)
    assert int(n) == touched.size <= 64
    assert {"one-group": 1, "distinct": 64}.get(kind, int(n)) == int(n)
    np.testing.assert_array_equal(groups[:n], touched)
    # one row tile: every visit is its group's first, the stream is the visits
    np.testing.assert_array_equal(stream[:n], touched)
    assert rank[-1] == n == int(visit_count(jnp.asarray(sizes)))
    assert not tiles.any() and groups.shape == (1 + 128 - 1, )


@pytest.mark.parametrize("kind", DECODE_KINDS)
def test_the_kernel_at_a_decode_steps_shape_is_the_xla_arm_and_reads_no_untouched_bank(kind):
    """Interpret mode against ``jax.lax.ragged_dot`` for 64 assignments over
    128 groups, most of them empty; then every UNTOUCHED bank filled with NaN:
    the kernel's output on the covered rows stays what it was (an empty
    group's bank is never multiplied in: the capacity path's einsums would
    spread the NaN over every row)."""
    sizes = _decode_step_sizes(kind)
    rng = np.random.default_rng(7)
    R, K, N, G = 128, 128, 256, sizes.size
    rows = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    bank = jnp.asarray(rng.normal(size=(G, K, N)) / np.sqrt(K), jnp.float32)
    group_sizes = jnp.asarray(sizes)
    covered = int(sizes.sum())
    with jax.default_matmul_precision("highest"):
        got = np.asarray(grouped_matmul(rows, bank, group_sizes, jnp.float32, interpret=True))
        want = np.asarray(grouped_matmul(rows, bank, group_sizes, jnp.float32))
        poisoned = jnp.where((group_sizes > 0)[:, None, None], bank, jnp.nan)
        alone = np.asarray(grouped_matmul(rows, poisoned, group_sizes, jnp.float32, interpret=True))
        parent = np.asarray(_parent_kernel(rows, bank, group_sizes, jnp.float32))
    np.testing.assert_allclose(got[:covered], want[:covered], atol=1e-5, rtol=0)
    np.testing.assert_array_equal(got[:covered], parent[:covered])  # bit for bit (PR 60)
    assert np.isfinite(alone[:covered]).all()
    np.testing.assert_array_equal(alone[:covered], got[:covered])


def _mellum_chunk_sizes(kind):
    """Group sizes of a 256-token chunk's 2,048 assignments over 64 groups."""
    if kind == "even":  # 32 rows a group: no group straddles a row tile
        return (32, ) * 64
    rng = np.random.default_rng(60)
    sizes = np.zeros(64, np.int32)  # a random router: each token the top-8 of a random order
    np.add.at(sizes, np.concatenate([rng.permutation(64)[:8] for _ in range(256)]), 1)
    return tuple(sizes.tolist())


@pytest.mark.parametrize("sizes,R,N,further", [
    ((37, 0, 91, 128), 256, 256, 0), ((256, 0, 0, 0), 256, 256, 1), ((10, 20, 30, 40), 256, 256, 0),
    (_mellum_chunk_sizes("routed"), 2048, 1280, 15), (_mellum_chunk_sizes("even"), 2048, 1280, 0),
    ((0, 0, 640, 0, 0), 640, 1280, 4), ((100, 0, 412, 0, 1, 127), 640, 1280, 3),
    ((0, 0, 0, 1), 128, 256, 0)],
    ids=["ragged", "one-group", "short", "mellum-chunk-routed", "mellum-chunk-32-a-group",
         "one-group-five-tiles", "four-tiles-between-others", "one-row"])
def test_the_pallas_arm_in_interpret_mode_is_the_xla_arm(sizes, R, N, further):
    """The Pallas kernel through the interpreter against ``jax.lax.ragged_dot``
    on the rows the groups cover (what lies behind them is undefined), and
    against the parent's kernel BIT FOR BIT (PR 60: the ring changes when a
    bank tile arrives, never what is multiplied). Mellum's chunk: 2,048 rows
    over 64 groups, routed (groups straddle row tiles: ``further`` visits are
    a group's further row tile) and 32 rows a group (none is); 1,280 columns
    are two column blocks, so the stream runs on from one into the next; a
    group of five row tiles (one expert takes every token); and fewer bank
    tiles than the ring holds."""
    rng = np.random.default_rng(sum(sizes))
    K, G = 128, len(sizes)
    rows = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    bank = jnp.asarray(rng.normal(size=(G, K, N)) / np.sqrt(K), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    assert column_tile(K, N, 4) == {256: 256, 1280: 640}[N] and R == padded_rows(R)
    assert int(visit_count(group_sizes)) - int((group_sizes > 0).sum()) == further
    with jax.default_matmul_precision("highest"):
        got = np.asarray(grouped_matmul(rows, bank, group_sizes, jnp.float32, interpret=True))
        want = np.asarray(grouped_matmul(rows, bank, group_sizes, jnp.float32))
        parent = np.asarray(_parent_kernel(rows, bank, group_sizes, jnp.float32))
    covered = sum(sizes)
    np.testing.assert_allclose(got[:covered], want[:covered], atol=1e-5, rtol=0)
    np.testing.assert_array_equal(got[:covered], parent[:covered])
    starts = np.cumsum([0] + list(sizes))
    for g in range(G):
        rows_g = np.asarray(rows[starts[g]:starts[g + 1]], np.float64)
        np.testing.assert_allclose(want[starts[g]:starts[g + 1]],
                                   rows_g @ np.asarray(bank[g], np.float64), atol=1e-4, rtol=0)
