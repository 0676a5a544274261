"""The row window of a layer that holds a SHARE of its experts (PR 64): the
stable sort places what landed here first, so dispatch, the experts with their
activation and the combine walk the sorted rows a window of
``heuristics.moe_row_window`` rows at a time until they are past the device's
own count of local assignments: one window when what landed fits it, every
window when a skewed router lands everything here, none when nothing landed.
Held here in float32 on the CPU: the windowed program is the un-windowed one
and a plain float64 reference whatever the router (softmax or sigmoid, a
selection bias, experts without a bank, invalid tokens), a skewed router loses
nothing, the same inside ``lax.scan`` (the ``decode_loop`` form),
``moe_rows_walked`` says how many rows were walked; the rule is a table over
the cells' shapes; and a layer without a window lowers to a program without a
loop."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.modules.heuristics import MOE_WINDOW_FACTOR, moe_row_window
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from deepspeed_tpu.ops.pallas.grouped_matmul import ROW_TILE, padded_rows
from deepspeed_tpu.utils import groups

T, M, F = 64, 32, 24
# 4 of 64 outputs held at top-4: 16 rows expected of 256, a window of one row tile
SHARE = dict(top_k=4, held=4, first_held=8, capacity_factor=1.0)
ROUTERS = {
    "softmax": dict(num_experts=64),
    "identity_experts": dict(num_experts=48, zero_experts=16),
    "sigmoid_and_bias": dict(num_experts=64, score_func="sigmoid", route_scale=2.5),
    "raw_weights": dict(num_experts=64, norm_topk_prob=False),
}
ROWS, WINDOW = padded_rows(T * SHARE["top_k"]), ROW_TILE


def _layer(router, seed=0, skew=False):
    rng = np.random.default_rng(seed)
    outputs = router["num_experts"] + router.get("zero_experts", 0)
    h = jnp.asarray(rng.normal(size=(T, M)), jnp.float32).at[:, 0].set(1.0)
    gate_w = jnp.asarray(rng.normal(size=(M, outputs)), jnp.float32)
    if skew:  # (almost) every token's every choice is an expert held here
        first = SHARE["first_held"]
        gate_w = gate_w.at[0, first:first + SHARE["held"]].set(40.0)
    wi = jnp.asarray(rng.normal(size=(SHARE["held"], M, 2 * F)) / np.sqrt(M), jnp.float32)
    wo = jnp.asarray(rng.normal(size=(SHARE["held"], F, M)) / np.sqrt(F), jnp.float32)
    bias = (jnp.asarray(rng.normal(size=(outputs, )) * 0.1, jnp.float32)
            if router.get("score_func") == "sigmoid" else None)
    if skew and bias is not None:  # a sigmoid saturates: the bias picks
        bias = bias.at[first:first + SHARE["held"]].set(2.0)
    valid = jnp.asarray(rng.random(T) < 0.8)
    return h, gate_w, wi, wo, bias, valid


def _reference(router, h, gate_w, wi, wo, bias, valid):
    """Token by token in float64: the chosen experts that are held here through
    their banks, the chosen experts without a bank as the token itself."""
    h, gate_w, wi, wo = (np.asarray(a, np.float64) for a in (h, gate_w, wi, wo))
    logits = h @ gate_w
    if router.get("score_func") == "sigmoid":
        scores = 1 / (1 + np.exp(-logits))
    else:
        scores = np.exp(logits - logits.max(-1, keepdims=True))
        scores /= scores.sum(-1, keepdims=True)
    pick = scores if bias is None else scores + np.asarray(bias, np.float64)
    k, first, held = SHARE["top_k"], SHARE["first_held"], SHARE["held"]
    out = np.zeros_like(h)
    for t in np.flatnonzero(np.asarray(valid)):
        chosen = np.argsort(-pick[t], kind="stable")[:k]
        weights = scores[t, chosen]
        if router.get("norm_topk_prob", True):
            weights = weights / weights.sum()
        weights = weights * router.get("route_scale", 1.0)
        for e, w in zip(chosen, weights):
            if e >= router["num_experts"]:
                out[t] += w * h[t]
            elif first <= e < first + held:
                gate, up = np.split(h[t] @ wi[e - first], 2)
                out[t] += w * ((gate / (1 + np.exp(-gate)) * up) @ wo[e - first])
    return out


def _forward(moe, h, gate_w, wi, wo, bias, valid):
    """``(output, the layer's counts by name)`` of the grouped path."""
    banks = []
    with jax.default_matmul_precision("highest"):
        out = moe._grouped_forward(h, gate_w, wi, wo, valid, jax.nn.silu, None, bias, banks)
    names = ("moe_banks", "moe_assignments_local", "moe_visits", "moe_rows_walked")
    return np.asarray(out), dict(zip(names, np.asarray(banks[0]).tolist()))


def _without_window(moe):
    moe.row_window = lambda tokens: None
    return moe


# ------------------------------------------------------------------ the walk ---
@pytest.mark.parametrize("with_invalid", [False, True], ids=["all_valid", "invalid_tokens"])
@pytest.mark.parametrize("router", ROUTERS)
def test_the_window_is_the_whole_walk_and_the_reference(router, with_invalid):
    groups.initialize_mesh(force=True)
    router = ROUTERS[router]
    h, gate_w, wi, wo, bias, valid = _layer(router, seed=len(router))
    valid = valid if with_invalid else None
    moe = RaggedMoE(**SHARE, **router)
    assert moe.row_window(T) == WINDOW
    got, counts = _forward(moe, h, gate_w, wi, wo, bias, valid)
    whole, counts_whole = _forward(_without_window(RaggedMoE(**SHARE, **router)),
                                   h, gate_w, wi, wo, bias, valid)
    want = _reference(router, h, gate_w, wi, wo, bias, np.ones(T, bool) if valid is None else valid)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    np.testing.assert_allclose(got, whole, atol=1e-4, rtol=0)
    assert 0 < counts["moe_assignments_local"] <= WINDOW
    assert counts["moe_rows_walked"] == WINDOW and counts_whole["moe_rows_walked"] == ROWS
    assert {k: v for k, v in counts.items() if k != "moe_rows_walked"} == \
        {k: v for k, v in counts_whole.items() if k != "moe_rows_walked"}


@pytest.mark.parametrize("router", ROUTERS)
def test_a_skewed_router_walks_every_window_and_drops_nothing(router):
    groups.initialize_mesh(force=True)
    router = ROUTERS[router]
    h, gate_w, wi, wo, bias, _ = _layer(router, seed=7, skew=True)
    got, counts = _forward(RaggedMoE(**SHARE, **router), h, gate_w, wi, wo, bias, None)
    assert counts["moe_assignments_local"] > WINDOW
    assert counts["moe_rows_walked"] == ROWS
    whole, _ = _forward(_without_window(RaggedMoE(**SHARE, **router)), h, gate_w, wi, wo, bias, None)
    np.testing.assert_allclose(got, whole, atol=1e-4, rtol=0)
    np.testing.assert_allclose(got, _reference(router, h, gate_w, wi, wo, bias, np.ones(T, bool)),
                               atol=1e-4, rtol=0)


@pytest.mark.parametrize("router", ["softmax", "identity_experts"])
def test_inside_a_scan_each_step_walks_the_windows_it_needs(router):
    """``decode_loop``'s form: the loop over windows inside ``lax.scan``; the
    middle step's router is skewed, so one program walks one window in a step
    and every window in the next."""
    groups.initialize_mesh(force=True)
    router = ROUTERS[router]
    h, gate_w, wi, wo, bias, valid = _layer(router, seed=3)
    _, skewed, *_ = _layer(router, seed=3, skew=True)
    gates = jnp.stack([gate_w, skewed, gate_w])
    moe = RaggedMoE(**SHARE, **router)

    def step(carry, gate):
        banks = []
        out = moe._grouped_forward(h * carry, gate, wi, wo, None, jax.nn.silu, None, bias, banks)
        return carry + 0.5, (out, banks[0])

    with jax.default_matmul_precision("highest"):
        _, (outs, counts) = jax.jit(lambda: jax.lax.scan(step, jnp.float32(1.0), gates))()
    local, walked = np.asarray(counts)[:, 1], np.asarray(counts)[:, 3]
    assert walked.tolist() == [WINDOW, ROWS, WINDOW] and local[1] > WINDOW >= max(local[0], local[2])
    for i in range(3):
        want = _reference(router, h * (1 + 0.5 * i), gates[i], wi, wo, bias, np.ones(T, bool))
        np.testing.assert_allclose(np.asarray(outs[i]), want, atol=1e-4, rtol=0)


@pytest.mark.parametrize("router", ["softmax", "identity_experts"])
def test_a_step_on_which_nothing_landed_walks_no_window(router):
    """Every choice another chip's (or an identity expert's): no row is
    gathered, no bank read, and the output is the identity experts' term."""
    groups.initialize_mesh(force=True)
    router = ROUTERS[router]
    h, gate_w, wi, wo, bias, _ = _layer(router, seed=11)
    first = SHARE["first_held"]
    gate_w = gate_w.at[0, first:first + SHARE["held"]].set(-40.0)
    got, counts = _forward(RaggedMoE(**SHARE, **router), h, gate_w, wi, wo, bias, None)
    assert counts == {"moe_banks": 0, "moe_assignments_local": 0, "moe_visits": 0,
                      "moe_rows_walked": 0}
    np.testing.assert_allclose(got, _reference(router, h, gate_w, wi, wo, bias, np.ones(T, bool)),
                               atol=1e-4, rtol=0)
    assert (np.abs(got).max() > 0) == bool(router.get("zero_experts"))


def test_under_jit_through_the_call_a_share_counts_the_rows_it_walked():
    groups.initialize_mesh(force=True)
    router = ROUTERS["identity_experts"]
    h, gate_w, wi, wo, bias, valid = _layer(router, seed=5)
    moe = RaggedMoE(**SHARE, **router)

    @jax.jit
    def run(h, gate_w, wi, wo, valid):
        banks = []
        return moe(h, gate_w, wi, wo, token_valid=valid, banks_out=banks), banks[0]

    with jax.default_matmul_precision("highest"):
        out, counts = run(h, gate_w, wi, wo, valid)
    assert counts.shape == (5, ) and int(counts[3]) == WINDOW  # [.., walked, zero]
    np.testing.assert_allclose(np.asarray(out), _reference(router, h, gate_w, wi, wo, bias, valid),
                               atol=1e-4, rtol=0)


# ------------------------------------------------------------------ the rule ---
# (tokens, top_k, router outputs, held) at the shapes the benchmark's share cells warm
RULE = {
    "longcat_put_256": ((256, 12, 768, 16), 256),
    "longcat_put_128": ((128, 12, 768, 16), 128),
    "longcat_chunk_32": ((32, 12, 768, 16), 128),
    "deepseek_put_256": ((256, 8, 256, 16), 512),
    "deepseek_put_32": ((32, 8, 256, 16), 128),
    "solar_put_256": ((256, 8, 320, 40), 1024),
    "kimi_put_256": ((256, 8, 256, 64), None),
    "nemotron_put_256": ((256, 6, 128, 64), None),
    "deepseek_decode_8": ((8, 8, 256, 16), None),
    "longcat_decode_8": ((8, 12, 768, 16), None),
    "every_expert_held": ((256, 8, 64, None), None),
    "a_share_that_is_all": ((256, 8, 64, 64), None),
}


@pytest.mark.parametrize("case", RULE)
def test_the_rule_over_the_share_cells_shapes(case):
    (tokens, top_k, outputs, held), want = RULE[case]
    assert moe_row_window(tokens, top_k, outputs, held) == want
    if want is not None:
        rows = padded_rows(tokens * top_k)
        assert want % ROW_TILE == 0 and ROW_TILE <= want <= rows // 2
        assert want >= MOE_WINDOW_FACTOR * tokens * top_k * held / outputs


def test_the_layer_asks_the_rule_with_every_output_of_its_router():
    moe = RaggedMoE(num_experts=512, zero_experts=256, top_k=12, held=16, capacity_factor=1.0)
    assert [moe.row_window(t) for t in (8, 32, 256)] == [None, 128, 256]
    assert RaggedMoE(num_experts=64, top_k=8).row_window(256) is None


# -------------------------------------------------------------- the lowering ---
def _lowered(tokens, debug_info=False, **layer):
    moe = RaggedMoE(**{"capacity_factor": 1.0, **layer})
    outputs = moe.num_experts + moe.zero_experts

    def run(h, gate_w, wi, wo, valid):
        banks = []
        with jax.named_scope("moe"):  # as every model program calls the layer
            return moe(h, gate_w, wi, wo, token_valid=valid, banks_out=banks), banks

    shapes = (jax.ShapeDtypeStruct((tokens, 128), jnp.bfloat16),
              jax.ShapeDtypeStruct((128, outputs), jnp.float32),
              jax.ShapeDtypeStruct((moe.experts_here, 128, 256), jnp.bfloat16),
              jax.ShapeDtypeStruct((moe.experts_here, 128, 128), jnp.bfloat16),
              jax.ShapeDtypeStruct((tokens, ), jnp.bool_))
    return jax.jit(run).lower(*shapes).as_text(debug_info=debug_info)


def _operations(text):
    """How often each operation occurs in a lowered module's text."""
    counts = {}
    for op in re.findall(r"= \"?((?:stablehlo|chlo)\.\w+)", text):
        counts[op] = counts.get(op, 0) + 1
    return counts


NO_WINDOW = {
    "every_expert": dict(tokens=256, num_experts=64, top_k=8, capacity_factor=8.0),
    "every_expert_decode_bucket": dict(tokens=8, num_experts=128, top_k=8),
    "identity_experts_all_held": dict(tokens=256, num_experts=16, top_k=4, zero_experts=8),
    "kimis_share": dict(tokens=256, num_experts=256, top_k=8, held=64, score_func="sigmoid"),
    "nemotrons_share": dict(tokens=256, num_experts=128, top_k=6, held=64, score_func="sigmoid"),
    "deepseeks_decode_bucket": dict(tokens=8, num_experts=256, top_k=8, held=16),
    "longcats_decode_bucket": dict(tokens=8, num_experts=512, top_k=12, held=16,
                                   zero_experts=256),
}


@pytest.mark.parametrize("case", NO_WINDOW)
def test_a_layer_without_a_window_lowers_to_the_walk_of_every_row(case):
    """No loop, no conditional, no matmul onto tokens: two sorts (the order and its
    inverse), two gathers (dispatch and the way back) and the two projections,
    as before the window was there."""
    groups.initialize_mesh(force=True)
    text = _lowered(**NO_WINDOW[case])
    ops = _operations(text)
    assert not {"stablehlo.case", "stablehlo.if", "stablehlo.while"} & set(ops)
    assert "HIGHEST" not in text
    assert ops["stablehlo.sort"] == 2
    projections = len(re.findall(r"stablehlo\.dot_general.*x(?:256|128)xbf16>\) ->", text))
    assert projections == 2, ops


@pytest.mark.parametrize("case,window", [("deepseek", 512), ("longcat", 256)])
def test_a_windowed_layer_lowers_to_one_loop_over_windows(case, window):
    """One ``while`` whose body gathers ``window`` rows, runs the two
    projections on them and sums them onto the tokens by a float32 matmul: no
    second arm, no gather of every row, no inverse permutation (one sort)."""
    groups.initialize_mesh(force=True)
    layer = {"deepseek": dict(num_experts=256, top_k=8, held=16),
             "longcat": dict(num_experts=512, top_k=12, held=16, zero_experts=256)}[case]
    text = _lowered(256, **layer)
    ops = _operations(text)
    assert ops.get("stablehlo.while", 0) == 1 and "stablehlo.case" not in ops
    assert ops["stablehlo.sort"] == 1
    rows = padded_rows(256 * layer["top_k"])
    assert re.search(rf"stablehlo\.gather.*-> tensor<{window}x128xbf16>", text)
    assert not re.search(rf"stablehlo\.gather.*-> tensor<{rows}x128x", text)
    assert re.search(rf"dot_general.*HIGHEST.*tensor<256x{window}xf32>, tensor<{window}x128xf32>",
                     text)


WINDOWED = {"deepseek": dict(num_experts=256, top_k=8, held=16),
            "longcat": dict(num_experts=512, top_k=12, held=16, zero_experts=256)}


def _metric_pattern(name):
    path = os.path.join(os.path.dirname(__file__), *[os.pardir] * 4, "benchmark", "metrics",
                        name + ".json")
    with open(path) as f:
        return re.compile(json.load(f)["params"]["pattern"])


@pytest.mark.parametrize("case", WINDOWED)
def test_the_loops_operations_carry_the_scopes_the_benchmark_reads(case):
    """An operation's path in the lowered text is its ``tf_op`` in the chip's
    trace, and ``moe_route_busy_pct`` searches it for ``moe/route|dispatch|combine/``
    as it stands. JAX lowers a ``while``'s body under ``while/body`` with the
    name stack begun anew, so the body names the layer's scope again
    (``moe.LOOP_SCOPE``): the window's gather, its matmul onto the tokens and
    everything else the body does outside the experts is read by the metric
    that read the walk of every row, and the experts' by ``moe_busy_pct`` alone."""
    groups.initialize_mesh(force=True)
    text = _lowered(256, debug_info=True, **WINDOWED[case])
    paths = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"\(', text, flags=re.M))
    route, busy = _metric_pattern("moe_route_busy_pct"), _metric_pattern("moe_busy_pct")

    def path_of(operation):
        lines = [line for line in text.splitlines() if re.search(operation, line)]
        assert len(lines) == 1, (operation, lines)
        return paths[re.search(r"loc\((#loc\d+)\)$", lines[0]).group(1)]

    window = moe_row_window(256, WINDOWED[case]["top_k"], sum(
        WINDOWED[case].get(key, 0) for key in ("num_experts", "zero_experts")), 16)
    gather = path_of(rf"stablehlo\.gather.*-> tensor<{window}x128xbf16>")
    onto_tokens = path_of(r"dot_general.*HIGHEST")
    assert "/while/body/" in gather and gather.endswith("/dispatch/gather") and route.search(gather)
    assert "/while/body/" in onto_tokens and route.search(onto_tokens)
    body = {p for p in paths.values() if "/while/body/" in p}
    outside_experts = {p for p in body if not re.search(r"(^|/)moe/experts(/|$)", p)}
    unread = {p for p in outside_experts if not route.search(p)}
    assert unread <= {"jit(run)/moe/while/body/add"}, unread  # the next window's start
    assert all(busy.search(p) for p in body)
    assert not any(route.search(p) for p in body - outside_experts)
