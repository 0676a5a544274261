"""Chip-independent perf gates: static HLO cost/roofline analysis.

Tier-1 runs on the CPU, where no time or utilization means anything, so the
regression fence it can hold is *structural*: every flagship computation (ZeRO-3
``train_batch``, flash fwd+bwd, the paged ``decode_loop`` step, the int4
decode matmul, the prefix-cache suffix prefill) is lowered under
``JAX_PLATFORMS=cpu``, and facts about the program as written — FLOPs,
argument and output bytes, collective payloads, dot counts and dtypes — are
ratcheted against checked-in budget files in tier-1. What the compiler makes
of it (bytes moved, live-buffer peak, fusion and op counts) is recorded and
printed, never judged: it moves with the toolchain, not with a commit.

Layers:

- :mod:`~deepspeed_tpu.perf.hlo_stats` — extraction: lowered program →
  :class:`HloStats` (cost_analysis + memory_analysis + StableHLO/compiled
  HLO text parsing);
- :mod:`~deepspeed_tpu.perf.chip_specs` — per-chip peak specs (v5e first);
- :mod:`~deepspeed_tpu.perf.roofline` — :class:`HloStats` × chip spec →
  predicted step time / MFU upper bound and the binding resource;
- :mod:`~deepspeed_tpu.perf.budgets` — the ratchet: budget JSON files,
  violation checking, deliberate re-baselining;
- :mod:`~deepspeed_tpu.perf.programs` — builders for the flagship
  programs, via the engines' official lowering hooks
  (``lowerable_callables`` / ``lower_*``);
- :mod:`~deepspeed_tpu.perf.gate` — the tier-1 pytest harness
  (``-m perfgate``) plus the ``bin/dstpu_perfgate`` CLI entry points.
"""

from deepspeed_tpu.perf.budgets import (Budget, Violation, budget_from_stats, check_stats,
                                        load_budget, write_budget)
from deepspeed_tpu.perf.chip_specs import CHIP_SPECS, ChipSpec, get_chip_spec
from deepspeed_tpu.perf.hlo_stats import (CollectiveStats, HloStats, stats_from_callable,
                                          stats_from_lowered)
from deepspeed_tpu.perf.roofline import RooflinePrediction, predict

__all__ = [
    "Budget", "Violation", "budget_from_stats", "check_stats", "load_budget",
    "write_budget", "CHIP_SPECS", "ChipSpec", "get_chip_spec", "CollectiveStats",
    "HloStats", "stats_from_callable", "stats_from_lowered", "RooflinePrediction",
    "predict",
]
