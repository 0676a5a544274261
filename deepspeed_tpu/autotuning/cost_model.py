"""Autotuning cost model.

Reference: ``deepspeed/autotuning/tuner/cost_model.py`` (XGBoost regressor
over measured experiments) + ``model_based_tuner.py`` (rank candidates by
predicted cost, measure the most promising first).

TPU formulation, two tiers:

- an ANALYTIC prior from one profile pass (parameter count, device HBM):
  per-config memory estimate — master fp32 + compute copy + grads + Adam
  moments, each divided by the ZeRO degree their stage shards them at, opt
  state dropped to host when offloaded — prunes configs that cannot fit
  before anything runs; plus a throughput prior (micro·GAS amortizes the
  per-step optimizer/master traffic; remat trades ~30% more FLOPs for memory).
- a LEARNED refinement: after each measured run, a ridge regression over
  config features re-ranks the remaining candidates (the reference's
  XGBoost role, dependency-free).
"""

from typing import Dict, List, Optional

import numpy as np


def device_memory_bytes() -> Optional[int]:
    """The first device's memory limit as its backend reports it; None where
    the backend reports none (the CPU does not). A backend that cannot be
    asked raises — no size is assumed for a device nobody saw."""
    import jax
    stats = jax.devices()[0].memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    return None


class AnalyticCostModel:
    """Static prior from one profile pass (no experiment runs)."""

    def __init__(self, n_params: int, zero_degree: int, hbm_bytes: Optional[int] = None,
                 bytes_per_token_act: float = 0.0):
        self.n_params = n_params
        self.zero_degree = max(1, zero_degree)
        self.hbm = hbm_bytes if hbm_bytes is not None else device_memory_bytes()
        self.act_bpt = bytes_per_token_act

    def memory_bytes(self, cfg: Dict) -> float:
        """Estimated peak HBM for a candidate (params+opt+grads+activations)."""
        stage = int(cfg.get("zero_optimization.stage", 0))
        offload = str(cfg.get("zero_optimization.offload_optimizer.device", "none"))
        micro = int(cfg.get("train_micro_batch_size_per_gpu", 1))
        remat = bool(cfg.get("remat", True))
        Z = self.zero_degree
        p = self.n_params
        # the fp32 master copy is optimizer state: ZeRO shards it from stage 1
        # (charging it unsharded at stages 1/2 over-estimates by ~4P(1-1/Z)
        # and prunes viable candidates as predicted-OOM)
        master = 4 * p / (Z if stage >= 1 else 1)
        compute = 2 * p  # bf16 copy is materialized per step regardless of stage
        grads = 4 * p / (Z if stage >= 2 else 1)
        opt = 8 * p / (Z if stage >= 1 else 1)
        if offload in ("cpu", "nvme"):
            opt = 0
        act = self.act_bpt * micro * (0.35 if remat else 1.0)
        return master + compute + grads + opt + act

    def fits(self, cfg: Dict, safety: float = 0.85) -> bool:
        """False only for a config predicted over a KNOWN limit: with no
        limit reported there is nothing to prune against."""
        return self.hbm is None or self.memory_bytes(cfg) <= self.hbm * safety

    def throughput_prior(self, cfg: Dict) -> float:
        """Relative samples/sec prior (unitless; ordering is what matters):
        bigger micro·GAS amortizes the ~12·P bytes/step of optimizer+master
        traffic; offloaded optimizers pay host PCIe/DMA per step; remat costs
        ~30% extra FLOPs."""
        micro = int(cfg.get("train_micro_batch_size_per_gpu", 1))
        gas = int(cfg.get("gradient_accumulation_steps", 1))
        offload = str(cfg.get("zero_optimization.offload_optimizer.device", "none"))
        remat = bool(cfg.get("remat", True))
        compute = 1.0 * (1.3 if remat else 1.0)          # per-sample compute cost
        step_overhead = (12.0 if offload == "none" else 40.0) / (micro * gas)
        return micro * gas / (compute * micro * gas + step_overhead)


class LearnedCostModel:
    """Ridge regression over config features, refit after every measurement
    (the reference's XGBoost cost model role)."""

    FEATURES = ("micro", "gas", "stage", "offload", "remat", "log_tokens")

    def __init__(self, l2: float = 1e-3):
        self.l2 = l2
        self._X: List[np.ndarray] = []
        self._y: List[float] = []
        self._w = None

    @staticmethod
    def featurize(cfg: Dict) -> np.ndarray:
        micro = int(cfg.get("train_micro_batch_size_per_gpu", 1))
        gas = int(cfg.get("gradient_accumulation_steps", 1))
        return np.asarray([
            micro,
            gas,
            int(cfg.get("zero_optimization.stage", 0)),
            1.0 if str(cfg.get("zero_optimization.offload_optimizer.device", "none")) != "none" else 0.0,
            1.0 if cfg.get("remat", True) else 0.0,
            np.log1p(micro * gas),
        ], np.float64)

    def observe(self, cfg: Dict, throughput: float) -> None:
        self._X.append(self.featurize(cfg))
        self._y.append(float(throughput))
        X = np.stack(self._X)
        X = np.concatenate([X, np.ones((len(X), 1))], axis=1)
        y = np.asarray(self._y)
        A = X.T @ X + self.l2 * np.eye(X.shape[1])
        self._w = np.linalg.solve(A, X.T @ y)

    @property
    def trained(self) -> bool:
        return self._w is not None and len(self._y) >= 3

    def predict(self, cfg: Dict) -> float:
        x = np.concatenate([self.featurize(cfg), [1.0]])
        return float(x @ self._w)
