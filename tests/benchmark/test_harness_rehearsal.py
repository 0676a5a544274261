"""The harness end to end on the CPU, at tiny sizes, through the test-only
entry: every kind of cell, traced and not, and the result line against the
contract's keys. No number printed here is a device metric, and the line says
so in every metric's name."""

import io
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness
from tests.benchmark import tiny

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench") / "root")


def _run(root, cell, trace, seconds=1.5, seed=0):
    out = io.StringIO()
    rc = harness.run_cell(root, cell, seed, seconds, trace, rehearsal=True, out=out)
    assert rc == 0
    return tiny.last_line(out.getvalue()), out.getvalue()


def _check_line(line, root, cell, traced):
    assert set(line) - {"compared"} == LINE_KEYS | ({"breakdown"} if traced else set())
    if "train" not in cell:
        # a served cell's line ends with each number that decided ``correct`` beside its limit
        assert list(line)[-1] == "compared" and line["compared"]
        assert all(0 <= value <= limit for value, limit in line["compared"].values())
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert DEVICE_KEYS <= set(line["device"]) and line["device"]["platform"] == "cpu"
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in harness.metrics_for(bench, cell, traced)}
    assert line["metrics"], "a cell reports at least one metric"
    for name, m in line["metrics"].items():
        assert name.startswith("cpu_rehearsal."), "a CPU number under a device metric's name"
        plain = name[len("cpu_rehearsal."):]
        assert plain in declared and m["unit"] == declared[plain]["unit"]
        assert isinstance(m["value"], float) and m["value"] == m["value"]
    return {n[len("cpu_rehearsal."):]: m["value"] for n, m in line["metrics"].items()}


@pytest.mark.parametrize("cell,expected", [
    ("tiny-mixtral-open", {"ttft_p50_ms", "tpot_p50_ms", "serve_tokens_per_s", "setup_s"}),
    ("tiny-mixtral-closed", {"tpot_p50_ms", "serve_tokens_per_s", "setup_s"}),
    ("tiny-mistral-open", {"ttft_p50_ms", "tpot_p50_ms", "serve_tokens_per_s", "setup_s"}),
    ("tiny-mistral-train", {"train_tokens_per_s", "setup_s"}),
])
def test_untraced_run_prints_the_cells_end_to_end_metrics(root, cell, expected):
    line, _ = _run(root, cell, trace=0)
    values = _check_line(line, root, cell, traced=False)
    assert set(values) >= expected  # a later metric may list the cell
    assert all(v > 0 for v in values.values()), "end-to-end metrics are never 0"
    assert line["device"]["count"] == (4 if cell.endswith("train") else 1)


@pytest.mark.parametrize("cell,expected", [
    ("tiny-mixtral-open", {"ttft_p90_ms", "gen_late_p99_ms", "slo_met_pct",
                           "sched_queue_wait_p50_ms", "sched_seqs_per_step", "kv_blocks_peak_pct",
                           "step_decode_p50_ms", "step_any_p50_ms", "step_prefill_p50_ms",
                           "compiles_in_window"}),
    ("tiny-mixtral-closed", {"sched_seqs_per_step", "kv_blocks_peak_pct", "step_decode_p50_ms",
                             "step_any_p50_ms", "compiles_in_window"}),
    ("tiny-mistral-train", {"compiles_in_window"}),
])
def test_traced_run_prints_the_per_layer_metrics_it_can_read_off_the_chip(root, cell, expected):
    """Spans, counters and harness samples are read on the CPU too; what needs
    the chip's trace, peaks or memory statistics finds nothing and is left out."""
    line, text = _run(root, cell, trace=1)
    values = _check_line(line, root, cell, traced=True)
    assert set(values) >= expected  # a later metric may list the cell
    assert values["compiles_in_window"] == 0
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "end-to-end numbers under tracing" in text
    if "closed" in cell:
        assert "decode_loop's first token" in text  # greedy cells check decode_loop too
        assert values["sched_seqs_per_step"] > 1


def test_same_seed_same_requests_and_the_untraced_result_is_kept_for_the_overhead_line(root):
    a, _ = _run(root, "tiny-mixtral-open", trace=0, seed=5)
    b, _ = _run(root, "tiny-mixtral-open", trace=0, seed=5)
    assert a["attempted"] == b["attempted"]
    _, text = _run(root, "tiny-mixtral-open", trace=1, seed=5)
    assert "tracing overhead against the last untraced run" in text


def test_run_py_refuses_off_the_chip_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(tiny.REPO, "benchmark", "run.py"), "--workload",
         "mixtral-chat-steady", "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=tiny.REPO)
    assert done.returncode != 0
    assert "not 'tpu'" in done.stderr
    assert "metrics" not in done.stdout and "ttft" not in done.stdout


def test_run_py_fails_where_only_the_benchmark_is(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under ``paths``
    has no program to measure: non-zero exit, no result."""
    import shutil
    shutil.copytree(os.path.join(tiny.REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload",
         "mixtral-chat-steady", "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path)
    assert done.returncode != 0
    assert "metrics" not in done.stdout


def test_a_wrong_guess_of_the_buckets_is_logged_not_fatal_and_learned_buckets_are_warmed(tmp_path):
    """The warm-up guesses the program's buckets by a copy of its padding rule.
    When the program's rule moves on, a run must still end; and what an earlier
    run met unwarmed (the cell's learned file) is warmed by the next."""
    root = tiny.make_root(tmp_path / "root")
    with open(os.path.join(root, "benchmark", "runners", "serve.py"), "a") as f:
        f.write("\n\ndef _pad_tokens(n):  # a rule the program does not have\n    return 24\n")
    first, text = _run(root, "tiny-mixtral-closed", trace=1)
    assert first["correct"] is True and first["failed"] == 0
    assert "guessed buckets are not among the engine's programs" in text
    tiny.write_json(os.path.join(root, ".benchmark_state", "tiny-mixtral-closed.programs.json"),
                    {"forward": [[8, 8, 4], [16, 8, 4]], "decode_loop": [[[8, 8, 4], 4, False]]})
    again, text_again = _run(root, "tiny-mixtral-closed", trace=1)
    assert again["correct"] is True

    def warmed(log):
        return int(log.split("warm-up: ")[-1].split(" programs")[0])
    assert warmed(text_again) == warmed(text) + 3
