"""Budget ratchet semantics: one-sided, exact dtype audit, collective keys."""

import json

import pytest

from deepspeed_tpu.perf.budgets import (_SCALAR_METRICS, DEFAULT_TOLERANCES, PRINTED_ONLY,
                                        Budget, budget_from_stats, check_stats,
                                        list_budgets, load_budget, write_budget)
from deepspeed_tpu.perf.hlo_stats import HloStats


def _stats(**kw):
    base = dict(name="prog", platform="cpu", flops=1e9, bytes_accessed=1e8,
                peak_bytes=10**7, argument_bytes=10**6, output_bytes=10**5,
                collective_bytes_total=4096, fusion_count=10,
                entry_instruction_count=20, dot_count=6, f32_dot_count=0,
                dots_by_dtype={"bf16": 6},
                collectives={"all-gather/g8": {"op": "all-gather", "group_size": 8,
                                               "count": 4, "bytes": 2048},
                             "all-reduce/g8": {"op": "all-reduce", "group_size": 8,
                                               "count": 4, "bytes": 2048}})
    base.update(kw)
    return HloStats(**base)


@pytest.fixture
def budget():
    return budget_from_stats(_stats(), note="test baseline")


def test_identical_stats_pass(budget):
    assert check_stats(_stats(), budget) == []


def test_improvements_never_trip(budget):
    better = _stats(flops=5e8, bytes_accessed=1e7, peak_bytes=10**6,
                    fusion_count=3, dot_count=2,
                    collectives={"all-gather/g8": {"op": "all-gather", "group_size": 8,
                                                   "count": 1, "bytes": 100}})
    better.collective_bytes_total = 100
    assert check_stats(better, budget) == []


def test_small_drift_within_tolerance_passes(budget):
    drift = _stats(output_bytes=int(10**5 * 1.05))  # tol 0.10
    assert check_stats(drift, budget) == []


@pytest.mark.parametrize("metric,value", [
    ("flops", 1e9 * 1.2),
    ("argument_bytes", int(10**6 * 1.2)),
    ("output_bytes", int(10**5 * 1.2)),
    ("collective_bytes_total", 8192),
    ("dot_count", 7),
])
def test_regressions_trip(budget, metric, value):
    bad = _stats(**{metric: value})
    tripped = [v.metric for v in check_stats(bad, budget)]
    assert metric in tripped


def test_dtype_audit_is_exact(budget):
    bad = _stats(f32_dot_count=1, dot_count=7, dots_by_dtype={"bf16": 6, "f32": 1})
    tripped = [v.metric for v in check_stats(bad, budget)]
    assert "f32_dot_count" in tripped and "dot_count" in tripped


def test_new_collective_key_trips(budget):
    bad = _stats()
    bad.collectives["all-to-all/g8"] = {"op": "all-to-all", "group_size": 8,
                                        "count": 1, "bytes": 64}
    vs = check_stats(bad, budget)
    assert any(v.metric == "collectives[all-to-all/g8]" for v in vs)


def test_collective_payload_growth_trips(budget):
    bad = _stats()
    bad.collectives["all-gather/g8"] = {"op": "all-gather", "group_size": 8,
                                        "count": 4, "bytes": 4096}
    vs = check_stats(bad, budget)
    assert any(v.metric == "collectives[all-gather/g8].bytes" for v in vs)


def test_collective_count_growth_trips(budget):
    bad = _stats()
    bad.collectives["all-reduce/g8"] = {"op": "all-reduce", "group_size": 8,
                                        "count": 5, "bytes": 2048}
    vs = check_stats(bad, budget)
    assert any(v.metric == "collectives[all-reduce/g8].count" for v in vs)


def test_per_budget_tolerance_override(budget):
    budget.tolerances["output_bytes"] = 0.5
    assert check_stats(_stats(output_bytes=int(10**5 * 1.4)), budget) == []


def test_violation_message_names_everything(budget):
    v = check_stats(_stats(flops=1e12), budget)[0]
    msg = str(v)
    assert "prog" in msg and "flops" in msg and "limit" in msg


# ----------------------------------------------------------------- file i/o --
def test_write_load_round_trip(tmp_path, budget):
    path = write_budget(str(tmp_path), budget)
    assert path.endswith("prog.json")
    loaded = load_budget(str(tmp_path), "prog")
    assert loaded.to_json() == budget.to_json()
    assert list_budgets(str(tmp_path)) == ["prog"]


def test_missing_budget_names_the_rebaseline_path(tmp_path):
    with pytest.raises(FileNotFoundError, match="dstpu_perfgate rebaseline"):
        load_budget(str(tmp_path), "nope")


def test_schema_version_mismatch_rejected(tmp_path, budget):
    path = write_budget(str(tmp_path), budget)
    doc = json.load(open(path))
    doc["schema_version"] = 99
    json.dump(doc, open(path, "w"))
    with pytest.raises(ValueError, match="schema_version"):
        load_budget(str(tmp_path), "prog")


def test_checked_in_budgets_exist_for_every_flagship_program():
    """The acceptance bar: every flagship program ships a budget file."""
    from deepspeed_tpu.perf.budgets import default_budgets_dir
    from deepspeed_tpu.perf.programs import FLAGSHIP_PROGRAMS
    have = set(list_budgets(default_budgets_dir()))
    assert have >= set(FLAGSHIP_PROGRAMS), \
        f"missing budget files for {sorted(set(FLAGSHIP_PROGRAMS) - have)}"
    for name in FLAGSHIP_PROGRAMS:
        b = load_budget(default_budgets_dir(), name)
        assert b.platform == "cpu"
        assert b.stats["flops"] > 0


def test_a_budget_holds_only_what_the_gate_judges(budget):
    """No file fences a compiler metric (XLA's cost model, buffer assignment,
    fusion pass; jax's lowering): a number held there would read as a limit
    nobody checks. And none lacks a judged one, which ``check_stats`` would
    pass over in silence."""
    from deepspeed_tpu.perf.budgets import default_budgets_dir
    judged = set(_SCALAR_METRICS)
    assert judged | {"collective_bytes", "collective_count"} == set(DEFAULT_TOLERANCES)
    assert not judged & set(PRINTED_ONLY)
    held = {name: load_budget(default_budgets_dir(), name)
            for name in list_budgets(default_budgets_dir())}
    held["(budget_from_stats)"] = budget
    for name, b in held.items():
        assert not set(PRINTED_ONLY) & set(b.stats), name
        assert judged | {"collectives"} <= set(b.stats), name
        assert set(b.tolerances) <= set(DEFAULT_TOLERANCES), name
    # the compiler's numbers are still recorded, for ``inspect`` to print
    assert set(PRINTED_ONLY) <= set(_stats().to_dict())
