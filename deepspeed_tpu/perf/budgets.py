"""Budget ratchet files: the checked-in fence a program's HLO stats must stay
inside.

A budget is a JSON snapshot of a program's :class:`HloStats` (less the
``PRINTED_ONLY`` compiler metrics) plus per-metric tolerances. Checks are
ONE-SIDED: a metric may improve freely (fewer flops, fewer collectives,
smaller outputs) but may not exceed ``value * (1 + tol)`` — that is the
ratchet. Two exact-by-default families ride along:

- the dtype audit (``f32_dot_count``/``dot_count``): an accidental f32 upcast
  on a bf16 path is a new f32 dot, tolerance 0;
- per-collective entries: payload bytes and op count per (op, group-size)
  key, and a collective key that did not exist at baseline is a violation
  outright (a NEW collective in a jitted program is always worth a human
  look).

Re-baselining is deliberate: ``bin/dstpu_perfgate rebaseline`` rewrites the
files; review the diff like any other code change.
"""

import json
import math
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from deepspeed_tpu.perf.hlo_stats import HloStats

SCHEMA_VERSION = 1

# metric -> (one-sided) relative tolerance. Counts are exact; byte/flop
# totals get slack. Every judged metric is a fact about the program AS
# WRITTEN: the shapes it takes and returns, the matmuls and collectives jax
# lowers it to.
DEFAULT_TOLERANCES: Dict[str, float] = {
    "flops": 0.05,
    "argument_bytes": 0.05,
    "output_bytes": 0.10,
    "collective_bytes_total": 0.05,
    "dot_count": 0.0,
    "f32_dot_count": 0.0,
    "collective_bytes": 0.05,   # per-collective entries
    "collective_count": 0.0,
}

_SCALAR_METRICS = ("flops", "argument_bytes", "output_bytes",
                   "collective_bytes_total", "dot_count", "f32_dot_count")

# What XLA's cost model, buffer assignment and fusion pass, and jax's
# lowering, make of the program: these move with the toolchain, with no
# commit of this repo to blame, so a budget neither holds nor judges them
# (HloStats records them and ``dstpu_perfgate inspect`` prints them).
PRINTED_ONLY = ("bytes_accessed", "peak_bytes", "fusion_count",
                "entry_instruction_count", "stablehlo_op_count")


@dataclass
class Violation:
    program: str
    metric: str
    measured: float
    budget: float
    limit: float
    detail: str = ""

    def __str__(self) -> str:
        msg = (f"[{self.program}] {self.metric}: measured {self.measured:g} "
               f"> limit {self.limit:g} (budget {self.budget:g})")
        return msg + (f" — {self.detail}" if self.detail else "")


@dataclass
class Budget:
    program: str
    stats: dict                              # HloStats.to_dict() snapshot
    tolerances: Dict[str, float] = field(default_factory=dict)
    platform: str = "cpu"
    created: str = ""
    note: str = ""
    roofline: Optional[dict] = None          # informational v5e prediction

    def tol(self, metric: str) -> float:
        if metric in self.tolerances:
            return self.tolerances[metric]
        return DEFAULT_TOLERANCES.get(metric, 0.0)

    def to_json(self) -> dict:
        return {"schema_version": SCHEMA_VERSION, "program": self.program,
                "platform": self.platform, "created": self.created,
                "note": self.note, "tolerances": self.tolerances,
                "stats": self.stats, "roofline": self.roofline}

    @staticmethod
    def from_json(d: dict) -> "Budget":
        if d.get("schema_version") != SCHEMA_VERSION:
            raise ValueError(f"budget schema_version {d.get('schema_version')!r} != "
                             f"{SCHEMA_VERSION} — rebaseline with dstpu_perfgate")
        return Budget(program=d["program"], stats=d["stats"],
                      tolerances=d.get("tolerances", {}),
                      platform=d.get("platform", "cpu"),
                      created=d.get("created", ""), note=d.get("note", ""),
                      roofline=d.get("roofline"))


def default_budgets_dir() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "budgets")


def budget_path(budgets_dir: str, program: str) -> str:
    return os.path.join(budgets_dir, f"{program}.json")


def budget_from_stats(stats: HloStats, program: Optional[str] = None,
                      tolerances: Optional[Dict[str, float]] = None,
                      note: str = "", roofline: Optional[dict] = None) -> Budget:
    kept = {k: v for k, v in stats.to_dict().items() if k not in PRINTED_ONLY}
    return Budget(program=program or stats.name, stats=kept,
                  tolerances=dict(tolerances or {}), platform=stats.platform,
                  created=time.strftime("%Y-%m-%d %H:%M:%S UTC", time.gmtime()),
                  note=note, roofline=roofline)


def write_budget(budgets_dir: str, budget: Budget) -> str:
    os.makedirs(budgets_dir, exist_ok=True)
    path = budget_path(budgets_dir, budget.program)
    with open(path, "w") as f:
        json.dump(budget.to_json(), f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def load_budget(budgets_dir: str, program: str) -> Budget:
    path = budget_path(budgets_dir, program)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no budget file for program {program!r} at {path} — create one "
            f"with: bin/dstpu_perfgate rebaseline --program {program}")
    with open(path) as f:
        return Budget.from_json(json.load(f))


def list_budgets(budgets_dir: str) -> List[str]:
    if not os.path.isdir(budgets_dir):
        return []
    return sorted(os.path.splitext(f)[0] for f in os.listdir(budgets_dir)
                  if f.endswith(".json"))


def check_stats(stats: HloStats, budget: Budget) -> List[Violation]:
    """All budget violations in ``stats`` (empty list = inside budget)."""
    out: List[Violation] = []
    measured = stats.to_dict()
    budgeted = budget.stats

    for metric in _SCALAR_METRICS:
        m = measured.get(metric)
        b = budgeted.get(metric)
        if m is None or b is None:
            continue
        limit = float(b) * (1.0 + budget.tol(metric))
        # integer counts: an exact-tolerance check must not trip on float
        # representation (limit == b exactly when tol is 0)
        if float(m) > limit + 1e-9:
            out.append(Violation(budget.program, metric, float(m), float(b), limit))

    b_coll = budgeted.get("collectives", {}) or {}
    for key, mc in (measured.get("collectives", {}) or {}).items():
        bc = b_coll.get(key)
        if bc is None:
            out.append(Violation(budget.program, f"collectives[{key}]",
                                 mc["count"], 0.0, 0.0,
                                 detail="collective op absent from the baseline appeared"))
            continue
        byte_limit = bc["bytes"] * (1.0 + budget.tol("collective_bytes"))
        if mc["bytes"] > byte_limit + 1e-9:
            out.append(Violation(budget.program, f"collectives[{key}].bytes",
                                 mc["bytes"], bc["bytes"], byte_limit,
                                 detail="collective payload grew"))
        count_limit = math.floor(bc["count"] * (1.0 + budget.tol("collective_count")) + 1e-9)
        if mc["count"] > count_limit:
            out.append(Violation(budget.program, f"collectives[{key}].count",
                                 mc["count"], bc["count"], count_limit,
                                 detail="more collective ops than the baseline"))
    return out
