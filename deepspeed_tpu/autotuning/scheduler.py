"""Launcher-scheduled autotuning experiments.

Reference: ``deepspeed/autotuning/scheduler.py`` (``ResourceManager`` —
``schedule_experiments`` queues experiment dirs, ``run_experiment:375``
launches each as a separate DeepSpeed job and parses its metric file;
a crashed or OOM-killed experiment fails alone and the search continues).

TPU formulation: each experiment goes through the ``dstpu`` launcher
(``deepspeed_tpu.launcher.runner`` → ``launch.py`` → the experiment process
running ``autotuning.exp_runner``), so a candidate gets a fresh process —
fresh XLA state, its own HBM lifetime, and a crash that cannot take the
tuner down. Experiments run SERIALLY: a chip belongs to one process at a
time, unlike the reference's multi-node round-robin over idle hosts. For the
same reason the tuner process itself must stay off the backend while an
experiment runs (see ``Autotuner._profile``).
"""

import json
import os
import signal
import subprocess
import sys
from typing import Any, Dict, List, Optional

from deepspeed_tpu.utils.logging import logger

DEFAULT_EXPERIMENT_TIMEOUT_S = 900


class ResourceManager:
    """Runs experiment processes and harvests their results.json."""

    def __init__(self, results_dir: str, model_factory: str, steps: int = 3,
                 warmup: int = 1, timeout_s: int = DEFAULT_EXPERIMENT_TIMEOUT_S,
                 num_chips: int = 1, env: Optional[Dict[str, str]] = None):
        self.results_dir = results_dir
        self.model_factory = model_factory
        self.steps = steps
        self.warmup = warmup
        self.timeout_s = timeout_s
        self.num_chips = num_chips
        self.env = env

    def _launch_cmd(self, exp_dir: str) -> List[str]:
        # route through the real launcher (reference parity): runner.py picks
        # LocalRunner for one node, launch.py execs the experiment module with
        # the rank env the comm layer reads
        return [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
                "--num_nodes", "1", "--num_chips", str(self.num_chips),
                "--launcher", "local", "--module",
                "deepspeed_tpu.autotuning.exp_runner", exp_dir]

    @staticmethod
    def _killpg(proc, sig):
        try:
            os.killpg(proc.pid, sig)  # start_new_session=True → pid == pgid
        except (ProcessLookupError, PermissionError):
            pass

    def run_experiment(self, exp_id: Any, config: dict) -> dict:
        """Launch one candidate; return its results.json contents (or a
        structured error when the process died without writing one)."""
        exp_dir = os.path.join(self.results_dir, f"exp_{exp_id}")
        os.makedirs(exp_dir, exist_ok=True)
        with open(os.path.join(exp_dir, "exp.json"), "w") as f:
            json.dump({"config": config, "model_factory": self.model_factory,
                       "steps": self.steps, "warmup": self.warmup}, f, indent=2)
        result_path = os.path.join(exp_dir, "results.json")
        if os.path.exists(result_path):
            os.unlink(result_path)

        env = dict(os.environ)
        if self.env:
            env.update(self.env)
        cmd = self._launch_cmd(exp_dir)
        logger.info(f"autotuning scheduler: exp_{exp_id}: {' '.join(cmd)}")
        rc: Any
        with open(os.path.join(exp_dir, "stdout.log"), "wb") as out, \
                open(os.path.join(exp_dir, "stderr.log"), "wb") as err:
            # own process group so a timeout can reap the WHOLE tree: a bare
            # child kill would orphan launch.py and the experiment process
            # (launch.py detaches its children into their own sessions), and
            # the orphans would starve every later experiment
            proc = subprocess.Popen(cmd, env=env, stdout=out, stderr=err,
                                    start_new_session=True)
            try:
                rc = proc.wait(timeout=self.timeout_s)
            except subprocess.TimeoutExpired:
                rc = "timeout"
                # SIGTERM the group first: launch.py's handler forwards the
                # signal to its detached children before exiting
                self._killpg(proc, signal.SIGTERM)
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    self._killpg(proc, signal.SIGKILL)
                    proc.wait()

        if os.path.exists(result_path):
            with open(result_path) as f:
                result = json.load(f)
        else:
            # hard death (OOM kill / XLA abort / timeout): no results.json —
            # exactly the failure mode in-process measurement cannot survive
            result = {"error": f"experiment process died without results "
                               f"(rc={rc}); see {exp_dir}/stderr.log"}
        result["exp_dir"] = exp_dir
        result["rc"] = rc
        return result
