"""Gate sensitivity: the gate is proven to catch what it claims to catch.

Each test INJECTS one regression class into a program and asserts the
budget check trips the matching assertion:

1. force an f32 upcast   -> the dtype audit's exact f32-dot count trips;
2. double a collective payload -> the per-collective byte budget trips;
3. widen the draft tree  -> the tree-verify flops budget trips.

1 and 3 regress a REAL flagship program against its checked-in budget; 2
uses a minimal synthetic program with an in-test baseline so the injected
delta is exactly one structural change."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.perf import gate
from deepspeed_tpu.perf.budgets import budget_from_stats, check_stats
from deepspeed_tpu.perf.hlo_stats import stats_from_callable, stats_from_lowered
from deepspeed_tpu.perf.programs import build_train_engine, train_batch_example

pytestmark = pytest.mark.perfgate


def _train_stats(dtype=None):
    engine, cfg = build_train_engine(dtype=dtype)
    lowered = engine.lower_train_batch(batch=train_batch_example(cfg))
    return stats_from_lowered(lowered, name="zero3_train_batch")


def test_f32_upcast_trips_dtype_audit():
    stats = _train_stats(dtype=jnp.float32)
    violations = gate.check_program("zero3_train_batch", stats)
    tripped = [v.metric for v in violations]
    assert "f32_dot_count" in tripped, f"tripped only: {tripped}"
    f32v = next(v for v in violations if v.metric == "f32_dot_count")
    assert f32v.budget == 0 and f32v.measured > 0


def test_doubling_collective_payload_trips_byte_budget(mesh8):
    def make(cols):
        x = jax.device_put(jnp.ones((256, cols), jnp.float32),
                           NamedSharding(mesh8, P("data", None)))
        fn = jax.jit(lambda a: a.sum(axis=0),
                     out_shardings=NamedSharding(mesh8, P()))
        return stats_from_callable(fn, x, name="grad_reduce")

    baseline = make(8)
    assert baseline.collective_bytes_total > 0, "no collective to budget"
    budget = budget_from_stats(baseline)
    doubled = make(16)  # the reduced payload doubles: f32[8] -> f32[16]
    violations = check_stats(doubled, budget)
    tripped = [v.metric for v in violations]
    assert any(m.endswith(".bytes") or m == "collective_bytes_total"
               for m in tripped), f"tripped only: {tripped}"


def test_widening_the_draft_tree_trips_tree_verify_flops_budget():
    """The spec_tree_verify budget is pinned to the smallest decode bucket:
    a tree that outgrows it (wider/deeper than the node budget the baseline
    shipped with) pads into the next token bucket, and the extra attention +
    unembed work must trip the flops ratchet — the gate proves the budgeted
    'tree costs one forward' claim is falsifiable, not vacuous."""
    from deepspeed_tpu.perf.programs import build_v2_engine

    engine, _ = build_v2_engine()
    wide = stats_from_lowered(engine.lower_verify(bucket=(16, 8, 4), tree=True,
                                                  greedy=True),
                              name="spec_tree_verify")
    tripped = [v.metric for v in gate.check_program("spec_tree_verify", wide)]
    assert "flops" in tripped, f"tripped only: {tripped}"
