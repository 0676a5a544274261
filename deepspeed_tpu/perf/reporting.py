"""Pure rendering for perf-gate artifacts (no jax imports — safe for
``bin/dstpu_report --perf`` on a machine with no backend at all).

Input is either a gate-report JSON (``dstpu_perfgate diff --json <out>``)
or a budgets directory; output is the human table."""

import json
import os
from typing import List

from deepspeed_tpu.perf.budgets import list_budgets

GREEN_OK = "\033[92m[OK]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


def _fmt_bytes(n) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:,.1f} {unit}"
        n /= 1024
    return f"{n:,.1f} GiB"


def _fmt_flops(n) -> str:
    n = float(n)
    for unit, div in (("GF", 1e9), ("MF", 1e6), ("kF", 1e3)):
        if abs(n) >= div:
            return f"{n / div:,.2f} {unit}"
    return f"{n:,.0f} F"


def render_gate_report(report: dict, checked: bool = True) -> str:
    """``checked=False`` renders stats/rooflines only — ``inspect`` never
    consults the budget files, so it must not print a budget verdict a
    ``diff`` would contradict."""
    lines: List[str] = []
    lines.append("-" * 78)
    title = f"perf gate report (chip model: {report.get('chip', '?')})"
    if not checked:
        title += " — stats only, budgets NOT checked (run diff)"
    lines.append(title)
    lines.append("-" * 78)
    header = (f"{'program':<26} {'flops':>10} {'bytes':>12} {'peak':>12} "
              f"{'coll':>10} {'f32dots':>7}" + ("  verdict" if checked else ""))
    lines.append(header)
    for name, prog in sorted(report.get("programs", {}).items()):
        s = prog.get("stats", {})
        verdict = ""
        if checked:
            verdict = "  " + (GREEN_OK if prog.get("ok") else RED_NO)
            if prog.get("budget_missing"):
                verdict += " (no budget file — rebaseline)"
        lines.append(f"{name:<26} {_fmt_flops(s.get('flops', 0)):>10} "
                     f"{_fmt_bytes(s.get('bytes_accessed', 0)):>12} "
                     f"{_fmt_bytes(s.get('peak_bytes', 0)):>12} "
                     f"{_fmt_bytes(s.get('collective_bytes_total', 0)):>10} "
                     f"{s.get('f32_dot_count', 0):>7}{verdict}")
        rl = prog.get("roofline") or {}
        if rl:
            lines.append(f"{'':<26} roofline: {rl.get('bound', '?')}-bound, "
                         f"step >= {rl.get('step_s', 0) * 1e6:,.1f} us, "
                         f"MFU <= {rl.get('mfu_bound', 0):.1%}")
        for v in prog.get("violations", []):
            lines.append(f"{'':<26} VIOLATION {v['metric']}: measured "
                         f"{v['measured']:g} > limit {v['limit']:g} "
                         f"(budget {v['budget']:g})"
                         + (f" — {v['detail']}" if v.get("detail") else ""))
    lines.append("-" * 78)
    if checked:
        lines.append(f"verdict ................ "
                     f"{GREEN_OK + ' within budgets' if report.get('ok') else RED_NO + ' budget violations'}")
    return "\n".join(lines)


def render_budgets_dir(budgets_dir: str) -> str:
    lines = ["-" * 78, f"perf budgets in {budgets_dir}", "-" * 78]
    names = list_budgets(budgets_dir)
    if not names:
        lines.append("(no budget files; create them with bin/dstpu_perfgate rebaseline)")
    for name in names:
        with open(os.path.join(budgets_dir, f"{name}.json")) as f:
            b = json.load(f)
        s = b.get("stats", {})
        lines.append(f"{name:<26} flops={_fmt_flops(s.get('flops', 0))} "
                     f"args={_fmt_bytes(s.get('argument_bytes', 0))} "
                     f"out={_fmt_bytes(s.get('output_bytes', 0))} "
                     f"dots={s.get('dot_count', 0)} "
                     f"colls={len(s.get('collectives', {}))} "
                     f"created={b.get('created', '?')}")
        rl = b.get("roofline") or {}
        if rl:
            lines.append(f"{'':<26} roofline({rl.get('chip', '?')}): "
                         f"{rl.get('bound', '?')}-bound, "
                         f"step >= {rl.get('step_s', 0) * 1e6:,.1f} us, "
                         f"MFU <= {rl.get('mfu_bound', 0):.1%}")
    lines.append("-" * 78)
    return "\n".join(lines)


def perf_report(path: str) -> int:
    """``dstpu_report --perf <budgets-dir | gate-report.json>``. A directory
    renders its budget files (and, if a ``gate_report.json`` the CLI wrote is
    present, the current-vs-budget table from it); a file is a gate report.
    Returns a process exit code (1 = violations recorded)."""
    if os.path.isfile(path):
        with open(path) as f:
            report = json.load(f)
        print(render_gate_report(report))
        return 0 if report.get("ok") else 1
    if not os.path.isdir(path):
        print(f"--perf: {path} is neither a budgets dir nor a gate-report JSON")
        return 2
    rc = 0
    report_path = os.path.join(path, "gate_report.json")
    if os.path.isfile(report_path):
        with open(report_path) as f:
            report = json.load(f)
        print(render_gate_report(report))
        rc = 0 if report.get("ok") else 1
    print(render_budgets_dir(path))
    return rc
