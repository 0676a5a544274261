"""Launcher tests.

Reference: ``tests/unit/launcher/test_run.py`` (hostfile + filter parsing) and
``test_multinode_runner.py`` (command construction) — pure logic; plus an
end-to-end 2-process local launch that trains through the engine with a real
``jax.distributed`` coordination-service rendezvous (the reference's
DistributedExec analog, but through the actual CLI path)."""

import os
import subprocess
import sys
import socket
import textwrap
from collections import OrderedDict

import pytest

from deepspeed_tpu.launcher.launch import decode_world_info, encode_world_info
from deepspeed_tpu.launcher.runner import fetch_hostfile, parse_resource_filter, _world_info


def _write(tmp_path, text):
    p = tmp_path / "hostfile"
    p.write_text(textwrap.dedent(text))
    return str(p)


def test_fetch_hostfile(tmp_path):
    path = _write(tmp_path, """\
        # comment
        worker-0 slots=4
        worker-1 slots=2
        """)
    pool = fetch_hostfile(path)
    assert pool == OrderedDict([("worker-0", 4), ("worker-1", 2)])


def test_fetch_hostfile_bad_line(tmp_path):
    path = _write(tmp_path, "worker-0 slots=four\n")
    with pytest.raises(ValueError):
        fetch_hostfile(path)


def test_fetch_hostfile_missing():
    assert fetch_hostfile("/nonexistent/hostfile") is None


def test_include_filter(tmp_path):
    pool = fetch_hostfile(_write(tmp_path, "a slots=4\nb slots=4\n"))
    active = parse_resource_filter(pool, include_str="a:0,2@b")
    assert active == OrderedDict([("a", [0, 2]), ("b", [0, 1, 2, 3])])


def test_exclude_filter(tmp_path):
    pool = fetch_hostfile(_write(tmp_path, "a slots=2\nb slots=2\n"))
    active = parse_resource_filter(pool, exclude_str="b:1")
    assert active == OrderedDict([("a", [0, 1]), ("b", [0])])
    active = parse_resource_filter(pool, exclude_str="a")
    assert active == OrderedDict([("b", [0, 1])])


def test_include_exclude_mutually_exclusive(tmp_path):
    pool = fetch_hostfile(_write(tmp_path, "a slots=2\n"))
    with pytest.raises(ValueError):
        parse_resource_filter(pool, include_str="a", exclude_str="a")


def test_world_info_roundtrip():
    active = OrderedDict([("a", [0, 1]), ("b", [0])])
    world = _world_info(active)
    assert world == OrderedDict([("a", [0, 1]), ("b", [2])])
    assert decode_world_info(encode_world_info(world)) == {"a": [0, 1], "b": [2]}


def test_pdsh_cmd_construction():
    from deepspeed_tpu.launcher.multinode_runner import PDSHRunner

    args = type("A", (), dict(master_addr="10.0.0.1", master_port=29500, module=False,
                              no_python=False, user_script="train.py",
                              user_args=["--epochs", "2"]))()
    world = OrderedDict([("a", [0, 1]), ("b", [2, 3])])
    cmd = PDSHRunner(args, world).get_cmd({"PYTHONPATH": "/repo"}, OrderedDict([("a", [0, 1]), ("b", [0, 1])]))
    assert cmd[0] == "pdsh"
    assert "a,b" in cmd
    assert "export PYTHONPATH=/repo;" in cmd
    assert "%n" in cmd  # per-node rank expansion
    assert cmd[-2:] == ["--epochs", "2"]


def test_slurm_cmd_construction():
    from deepspeed_tpu.launcher.multinode_runner import SlurmRunner

    args = type("A", (), dict(master_addr="10.0.0.1", master_port=29500, module=False,
                              no_python=False, slurm_comment="", user_script="train.py",
                              user_args=[]))()
    world = OrderedDict([("a", [0]), ("b", [1])])
    cmd = SlurmRunner(args, world).get_cmd({}, world)
    assert cmd[:3] == ["srun", "--nodes", "2"]
    assert any("$SLURM_NODEID" in c for c in cmd)


def test_local_launch_needs_a_count_and_never_imports_jax():
    """The launcher is the parent of the processes that own the chips: it must
    not touch (or even import) jax, so with no hostfile and no --num_chips it
    has nothing to count with and says so."""
    code = textwrap.dedent("""\
        import sys
        from deepspeed_tpu.launcher import runner
        try:
            runner.main(["--hostfile", "/nonexistent/hostfile", "train.py"])
        except SystemExit as e:
            print("REFUSED:", e)
        print("jax imported:", "jax" in sys.modules)
        """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "REFUSED:" in r.stdout and "--num_chips" in r.stdout
    assert "jax imported: False" in r.stdout


TRAIN_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import deepspeed_tpu
deepspeed_tpu.comm.init_distributed()  # must precede any backend-initializing jax call
from deepspeed_tpu.utils import groups

import flax.linen as nn
import jax.numpy as jnp

class Loss(nn.Module):
    @nn.compact
    def __call__(self, batch):
        x, y = batch
        out = nn.Dense(8)(x)
        return jnp.mean((out - y) ** 2)

model = Loss()
rng = np.random.default_rng(0)
batch = (rng.normal(size=(8, 8)).astype(np.float32), rng.normal(size=(8, 8)).astype(np.float32))
params = model.init(jax.random.PRNGKey(0), batch)["params"]
cfg = {"train_micro_batch_size_per_gpu": 1, "gradient_accumulation_steps": 1,
       "optimizer": {"type": "AdamW", "params": {"lr": 0.01}},
       "zero_optimization": {"stage": 2}}
engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=cfg)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8
l0 = float(engine.train_batch(batch=batch))
l1 = float(engine.train_batch(batch=batch))
assert l1 < l0, (l0, l1)
with open(os.environ["MARKER_DIR"] + f"/rank{jax.process_index()}", "w") as f:
    f.write(f"{l0} {l1}")
"""


@pytest.mark.nightly
def test_local_two_process_training(tmp_path):
    """dstpu CLI end-to-end: 2 local processes x 4 virtual chips rendezvous via
    the coordination service and run ZeRO-2 train_batch on the joint mesh."""
    script = tmp_path / "train2.py"
    script.write_text(TRAIN_SCRIPT)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    env = os.environ.copy()
    env["MARKER_DIR"] = str(tmp_path)
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    rc = subprocess.call([sys.executable, "-m", "deepspeed_tpu.launcher.runner",
                          "--hostfile", "/nonexistent", "--num_chips", "2",
                          "--master_port", str(port), str(script)],
                         env=env, timeout=540)
    assert rc == 0
    assert (tmp_path / "rank0").exists() and (tmp_path / "rank1").exists()
