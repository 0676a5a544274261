"""A later PR adds a configuration, a traffic mix, a cell, a metric and a reader
as NEW FILES plus new entries in ``BENCHMARK.json``, and edits no file the
benchmark already has. This test does exactly that in a throw-away copy."""

import hashlib
import io
import json
import os

from benchmark import harness
from tests.benchmark import tiny


def _digests(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in base or ".benchmark_state" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[path] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_cell_a_metric_and_a_reader_arrive_as_files(tmp_path):
    root = tiny.make_root(tmp_path / "root")
    before = _digests(root)

    # a configuration and a traffic mix: data files only
    config = dict(tiny.TINY_MISTRAL_SERVE, num_hidden_layers=1)
    tiny.write_json(os.path.join(root, "benchmark", "configs", "later-config.json"), config)
    traffic = dict(tiny.TRAFFIC["tiny-closed"], params=dict(tiny.TRAFFIC["tiny-closed"]["params"],
                                                            clients=2))
    tiny.write_json(os.path.join(root, "benchmark", "traffic", "later-traffic.json"), traffic)
    # a per-layer metric on an existing reader (data only), one on a new reader
    tiny.write_json(os.path.join(root, "benchmark", "metrics", "later_decode_p90_ms.json"),
                    {"reader": "serve_latency", "params": {"what": "tpot", "percentile": 90}})
    tiny.write_json(os.path.join(root, "benchmark", "metrics", "later_requests_sent.json"),
                    {"reader": "later_count", "params": {"scale": 1.0}})
    with open(os.path.join(root, "benchmark", "readers", "later_count.py"), "w") as f:
        f.write("def read(run, params, env):\n"
                "    return params['scale'] * len(run['requests'])\n")
    # and the entries
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "later-config", "source": "none: a test preset",
                             "file": "benchmark/configs/later-config.json", "reduced": [],
                             "why": "added by a later PR"})
    bench["workloads"].append({"name": "later-cell", "config": "later-config",
                               "traffic": "later-traffic", "chips": 1, "why": "added later"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "tiny-mixtral-closed" in m["workloads"]:
            m["workloads"].append("later-cell")
    for name, unit in (("later_decode_p90_ms", "ms"), ("later_requests_sent", "count")):
        bench["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                                   "source": "host_clock", "layer": "a later layer",
                                   "moves": "tpot_p50_ms", "workloads": ["later-cell"]})
    tiny.write_json(path, bench)

    out = io.StringIO()
    assert harness.run_cell(root, "later-cell", 0, 1.0, 1, rehearsal=True, out=out) == 0
    line = tiny.last_line(out.getvalue())
    assert line["correct"] and line["failed"] == 0
    assert line["metrics"]["cpu_rehearsal.later_requests_sent"]["value"] >= line["attempted"]
    assert line["metrics"]["cpu_rehearsal.later_decode_p90_ms"]["unit"] == "ms"
    out = io.StringIO()
    assert harness.run_cell(root, "later-cell", 0, 1.0, 0, rehearsal=True, out=out) == 0
    assert "cpu_rehearsal.serve_tokens_per_s" in tiny.last_line(out.getvalue())["metrics"]

    after = _digests(root)
    assert {p: d for p, d in after.items() if p in before} == before, "an existing file changed"
    assert len(after) == len(before) + 5
