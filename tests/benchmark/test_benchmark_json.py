"""``BENCHMARK.json`` against the rules of its contract that a file can break,
and against the files it names."""

import json
import os
import re

import pytest

from benchmark import harness
from tests.benchmark import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(tiny.REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells must fit: 2 + 14 x cells runs
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(bench["paths"]) <= 16 and len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    assert any(w.startswith(tuple(p + "/" for p in bench["paths"])) for w in bench["command"])


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["name"] in used, "every configuration keeps a cell"
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        with open(os.path.join(tiny.REPO, c["file"])) as f:
            doc = json.load(f)
        assert doc["source"] == c["source"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size|per_tok|head)", key)
            assert doc["reduced_from"][key] != doc[key]
        assert set(doc["reduced_from"]) == set(c["reduced"])


def test_workloads(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert _line(w["why"])
        harness.resolve(tiny.REPO, w["name"])  # the files it names are there and parse


def test_metrics(bench):
    e2e, layers = bench["end_to_end"], bench["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in bench["workloads"]}
    e2e_cells = {m["name"]: set(m.get("workloads", cells)) for m in e2e}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert e2e_cells["setup_s"] == cells
    for m in layers:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        # a per-layer metric is reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= e2e_cells[m["moves"]]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + layers:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        path = os.path.join(tiny.REPO, "benchmark", "metrics", f"{m['name']}.json")
        with open(path) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "readers",
                                           f"{spec['reader']}.py"))
    for cell in cells:
        mine = [m["name"] for m in e2e if cell in e2e_cells[m["name"]]]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in m.get("workloads", cells) for m in layers)
    on_disk = {f[:-5] for f in os.listdir(os.path.join(tiny.REPO, "benchmark", "metrics"))}
    assert on_disk == set(names), "a metric file without an entry, or an entry without a file"


def test_layer_names_are_the_ones_perf_md_lists(bench):
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def _spec(name):
    with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{name}.json")) as f:
        return json.load(f)


def _second_names(bench):
    """Entries that are an earlier entry's reading (one reader, one ``params``, moving the same
    end-to-end metric) under another name: a later cell joins the first name's list."""
    first, again = {}, []
    for m in bench["per_layer"]:
        spec = _spec(m["name"])
        key = (spec["reader"], json.dumps(spec.get("params"), sort_keys=True), m["moves"])
        if first.setdefault(key, m["name"]) != m["name"]:
            again.append((m["name"], first[key]))
    return again


def _without_a_list(bench):
    # ``compiles_in_window`` is every cell's, those of later PRs too: the one entry without a list
    return [m["name"] for m in bench["per_layer"]
            if "workloads" not in m and m["name"] != "compiles_in_window"]


def _listed_and_no_cell(bench):
    cells = {w["name"] for w in bench["workloads"]}
    return [(m["name"], w) for m in bench["end_to_end"] + bench["per_layer"]
            for w in m.get("workloads", []) if w not in cells]


def _files_without_an_entry(bench):
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    return sorted(f for f in os.listdir(os.path.join(tiny.REPO, "benchmark", "metrics"))
                  if f[:-5] not in names)


@pytest.mark.parametrize("found", [_second_names, _without_a_list, _listed_and_no_cell,
                                   _files_without_an_entry], ids=lambda f: f.__name__.strip("_"))
def test_one_name_a_reading(bench, found):
    """The door PR 61 shut: ``per_layer`` reached its 128 because 21 entries were an accepted
    reader with the very same ``params`` under a second name. A cell is added to a LIST."""
    assert found(bench) == []
