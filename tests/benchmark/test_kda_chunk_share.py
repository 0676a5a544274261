"""``kda_chunk_in_kernel_share`` (PR 55): of the visits a ``put`` step's scan
made through the chunked form of the delta rule, the share the chunk kernel
made in the pool (``ops/pallas/kda_chunk.py``), from the ``inference.put`` spans'
``kda_chunk_visits_in_kernel`` / ``kda_chunk_visits``. Found BY NAME; the reader
is the accepted ``span_arg_ratio``, handed the two names by a data file."""

import json
import os

import pytest

from benchmark import harness
from benchmark.readers import span_arg_ratio
from tests.benchmark import tiny

NAME, CELL = "kda_chunk_in_kernel_share", "solar-open2-longctx-reason-closed"


@pytest.fixture(scope="module")
def bench():
    return harness.resolve(tiny.REPO, CELL)[0]


def _spec():
    with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{NAME}.json")) as f:
        return json.load(f)


def test_the_share_is_listed_by_name_beside_the_recurrences_share(bench):
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    beside = next(m for m in bench["per_layer"] if m["name"] == "kda_rows_in_place_share")
    assert entry == {**beside, "name": NAME}
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"]) == \
        ("ratio", "higher", "program_counter", "tpot_p50_ms") and CELL in entry["workloads"]
    assert NAME in {m["name"] for m in harness.metrics_for(bench, CELL, True)}
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        assert f"`{NAME}`" in f.read()


def test_its_file_is_data_for_the_accepted_reader():
    spec = _spec()
    assert spec == {"reader": "span_arg_ratio",
                    "params": {"name": "put", "cat": "inference",
                               "numerator": ["kda_chunk_visits_in_kernel"],
                               "denominator": ["kda_chunk_visits"]}}
    assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "readers", "span_arg_ratio.py"))


def _run(rows):
    spans = [{"name": name, "cat": "inference", "ts_us": 1_000_000 * (i + 1), "dur_us": 10,
              "args": args} for i, (name, args) in enumerate(rows)]
    return {"spans": spans, "window": {"began": 0.0, "ended": 100.0}}


@pytest.mark.parametrize("rows, want", [
    # every visit in the kernel; a decode_loop chunk has no visit and is another span
    ([("put", {"kda_chunk_visits": 12, "kda_chunk_visits_in_kernel": 12}),
      ("put", {"kda_chunk_visits": 3, "kda_chunk_visits_in_kernel": 3}),
      ("decode_loop", {"kda_chunk_visits": 0, "kda_chunk_visits_in_kernel": 0})], 1.0),
    # a pool off the kernel's rule: the visits are made, none in the kernel
    ([("put", {"kda_chunk_visits": 12, "kda_chunk_visits_in_kernel": 0})], 0.0),
    # a program without the counter (the parent's): nothing to read, and no raise
    ([("put", {"kda_chunk_visits": 12})], None),
    # no visit in the window: nothing to read
    ([("put", {"kda_chunk_visits": 0, "kda_chunk_visits_in_kernel": 0})], None),
], ids=["all-in-kernel", "off-the-rule", "parent-without-the-counter", "no-visit"])
def test_the_reader_gives_the_share_and_nothing_where_there_is_nothing(rows, want, monkeypatch):
    monkeypatch.setattr(span_arg_ratio.host_phases, "on_chip", lambda env: True)
    monkeypatch.setattr(span_arg_ratio.spans, "in_window", lambda rows, run: rows)
    assert span_arg_ratio.read(_run(rows), _spec()["params"], {}) == want
