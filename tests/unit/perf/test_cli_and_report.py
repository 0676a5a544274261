"""bin/dstpu_perfgate + dstpu_report --perf plumbing."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
BIN = os.path.join(REPO, "bin")


def _run(script, *args, timeout=300):
    return subprocess.run([sys.executable, os.path.join(BIN, script), *args],
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow  # two subprocess jax imports + flash builds; the diff/check
# logic itself is tier-1-covered by tests/unit/perf/test_gate.py
def test_dstpu_perfgate_diff_single_program(tmp_path):
    """End-to-end CLI on the cheapest flagship program: rebaseline into a
    scratch dir, then diff against it (rc 0, table rendered, JSON written)."""
    r = _run("dstpu_perfgate", "rebaseline", "--program", "flash_attention_fwd_bwd",
             "--budgets", str(tmp_path), "--note", "cli test")
    assert r.returncode == 0, r.stderr
    assert (tmp_path / "flash_attention_fwd_bwd.json").exists()

    out = tmp_path / "gate_report.json"
    r = _run("dstpu_perfgate", "diff", "--program", "flash_attention_fwd_bwd",
             "--budgets", str(tmp_path), "--json", str(out))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "flash_attention_fwd_bwd" in r.stdout
    assert "within budgets" in r.stdout
    report = json.loads(out.read_text())
    assert report["ok"] is True

    # dstpu_report --perf renders the dir (budgets + the report the CLI wrote)
    r = _run("dstpu_report", "--perf", str(tmp_path))
    assert r.returncode == 0, r.stderr
    assert "flash_attention_fwd_bwd" in r.stdout
    assert "roofline" in r.stdout


def test_dstpu_perfgate_rejects_unknown_program():
    r = _run("dstpu_perfgate", "diff", "--program", "nope")
    assert r.returncode == 2
    assert "unknown program" in r.stdout


def test_dstpu_report_perf_renders_violating_report(tmp_path):
    """--perf on a gate-report JSON: pure rendering, rc 1 on violations."""
    report = {
        "kind": "dstpu_perfgate_report", "chip": "v5e", "ok": False,
        "programs": {
            "zero3_train_batch": {
                "ok": False,
                "stats": {"flops": 5.1e7, "bytes_accessed": 2.2e7,
                          "peak_bytes": 2.1e6, "collective_bytes_total": 1.1e6,
                          "f32_dot_count": 61},
                "roofline": {"chip": "v5e", "bound": "memory", "step_s": 2.7e-5,
                             "mfu_bound": 0.015},
                "budget_created": "2026-08-04", "budget_missing": False,
                "meta": {},
                "violations": [{"metric": "f32_dot_count", "measured": 61,
                                "budget": 0, "limit": 0,
                                "detail": "accidental f32 upcast"}],
            }
        },
    }
    p = tmp_path / "report.json"
    p.write_text(json.dumps(report))
    r = _run("dstpu_report", "--perf", str(p))
    assert r.returncode == 1
    assert "VIOLATION f32_dot_count" in r.stdout
    assert "budget violations" in r.stdout


def test_dstpu_report_perf_checked_in_budgets():
    """The shipped budgets dir renders without touching jax."""
    budgets = os.path.join(REPO, "deepspeed_tpu", "perf", "budgets")
    r = _run("dstpu_report", "--perf", budgets)
    assert r.returncode == 0, r.stderr
    assert "zero3_train_batch" in r.stdout
    assert "prefix_suffix_prefill" in r.stdout


def test_dstpu_report_perf_bad_path():
    r = _run("dstpu_report", "--perf", "/nonexistent/thing")
    assert r.returncode == 2
