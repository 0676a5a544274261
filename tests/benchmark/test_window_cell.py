"""The window cell's files (PR 26): the configuration, traffic and metrics
resolve, the cell rehearses at a tiny preset, the family refuses a program that
would serve it on the gather arm on a TPU, and the windowed count of operations
and bytes is bounded by the unclamped one."""

import io
import json
import os
import types

import pytest

from benchmark import harness, opcount
from benchmark.models import mistral_windowed
from benchmark.readers import span_arg_per_second, trace_window_paged_roofline
from tests.benchmark import tiny

CELL = "mistral-longdoc-closed"
NEW_METRICS = ("paged_window_tiled_roofline", "paged_window_token_roofline",
               "kv_window_released_blocks")


@pytest.fixture(scope="module")
def resolved():
    return harness.resolve(tiny.REPO, CELL)


def test_the_cell_is_the_one_the_issue_names(resolved):
    bench, cell, config, traffic = resolved
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("mistral-7b-serve-1chip", "longdoc-closed", 1)
    assert config["family"] == "mistral_windowed" and config["mode"] == "serve"
    assert config["sliding_window"] == 4096 and config["reduced_from"] == {"num_hidden_layers": 32}
    sm = config["engine"]["state_manager"]
    assert (config["engine"]["kv_block_size"], sm["max_context"], sm["max_ragged_batch_size"],
            sm["max_ragged_sequence_count"], config["serving"]["decode_chunk"]) == \
        (64, 8192, 256, 8, 8)
    p = traffic["params"]
    assert traffic["kind"] == "closed_clients" and (p["clients"], p["requests_per_client"]) == (8, 64)
    assert p["prompt"] == {"dist": "lognormal", "median": 6144, "sigma": 0.2, "min": 4608,
                           "max": 7680}
    assert p["output"] == {"dist": "lognormal", "median": 192, "sigma": 0.4, "min": 64, "max": 384}
    assert p["temperature"] == 0.0 and p["prompt"]["min"] >= config["sliding_window"] + 512
    assert p["prompt"]["max"] + p["output"]["max"] <= sm["max_context"]
    # the weights and the pool are ~70 % of the chip
    layers, kv = config["num_hidden_layers"], config["num_key_value_heads"] * config["head_dim"]
    h, i, v = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    weights = 2 * (layers * (2 * h * h + 2 * h * kv + 3 * h * i + 2 * h) + 2 * v * h + h)
    pool = sm["memory_config"]["size"] * layers * 2 * kv * config["engine"]["kv_block_size"] * 2
    assert 0.68 <= (weights + pool) / opcount.PEAKS["TPU v5 lite"]["hbm_bytes"] <= 0.72


def test_the_programs_to_warm_are_the_forty_two_the_budget_was_set_by(resolved):
    from benchmark.runners import serve
    _, _, config, traffic = resolved
    forward, loops = serve.reachable_programs(config["engine"], config["serving"],
                                              traffic["params"])
    assert (len(forward), len(loops)) == (36, 6)
    assert {mb for _, _, mb in forward} == {4, 8, 16, 32, 64, 128}


def test_its_metrics_are_listed_and_the_whole_context_roofline_is_not(resolved):
    bench = resolved[0]
    traced = {m["name"] for m in harness.metrics_for(bench, CELL, True)}
    assert set(NEW_METRICS) <= traced and "paged_attn_roofline" not in traced
    assert {"paged_attn_busy_pct", "paged_prefill_busy_pct", "attn_busy_pct", "device_idle_pct",
            "kv_blocks_peak_pct", "compiles_in_window"} <= traced
    assert {m["name"] for m in harness.metrics_for(bench, CELL, False)} == \
        {"tpot_p50_ms", "serve_tokens_per_s", "setup_s"}
    for name in NEW_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
        with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{name}.json")) as f:
            assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "readers",
                                               f"{json.load(f)['reader']}.py"))


# ------------------------------------------------------------ the refusal ---
def _old_rule(model, engine_config, bucket_tokens):
    """The parent's rule, as far as a window model goes."""
    if getattr(model, "attention_window", 0):
        return "xla_gather"
    return "paged_token" if bucket_tokens <= 32 else "paged_tiled"


def test_the_family_refuses_the_gather_arm_on_a_tpu_and_nothing_else(resolved, monkeypatch):
    import jax
    config = resolved[2]
    message = mistral_windowed.refusal(config, "tpu", rule=_old_rule)
    assert message and "343 ms" in message and "xla_gather" in message and "PERF.md" in message
    assert mistral_windowed.refusal(config, "cpu", rule=_old_rule) is None  # the CPU's own arm
    assert mistral_windowed.refusal(dict(config, sliding_window=None), "tpu", rule=_old_rule) is None
    # the program's rule as it is now, asked as on a TPU: the tile grid
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert mistral_windowed.arm_of_a_window_bucket(config) == "paged_tiled"
    assert mistral_windowed.refusal(config, "tpu") is None
    mistral_windowed.program_config(config)
    from deepspeed_tpu.inference.v2.modules import heuristics
    monkeypatch.setattr(heuristics, "attention_implementation", _old_rule)
    with pytest.raises(SystemExit, match="343 ms"):
        mistral_windowed.program_config(config)


# ------------------------------------------------------- the windowed count ---
@pytest.mark.parametrize("queries", [
    [[100]], [[4096]], [[4097]], [[8000]], [list(range(4000, 4256))], [list(range(6000, 6256))],
    [[5000], [], [100], list(range(7000, 7064))],
])
def test_windowed_count_is_bounded_by_the_whole_context_count(queries):
    shape = (32, 8, 128, 64)
    whole = opcount.paged_attention(queries, *shape)
    windowed = trace_window_paged_roofline.windowed_paged_attention(queries, 4096, *shape)
    assert windowed[0] <= whole[0] and windowed[1] <= whole[1]
    inside = all(c <= 4096 for contexts in queries for c in contexts)
    assert (windowed == whole) == inside
    assert trace_window_paged_roofline.windowed_paged_attention(queries, 0, *shape) == whole


def test_windowed_count_of_one_chunk_and_one_decode_row():
    shape = (32, 8, 128, 64)
    flops, nbytes = trace_window_paged_roofline.windowed_paged_attention(
        [list(range(6001, 6257))], 4096, *shape)
    assert flops == 256 * 4 * 32 * 128 * 4096
    blocks = -(-(4096 + 255) // 64)
    assert nbytes == 2 * blocks * 64 * 8 * 128 * 2 + 2 * 256 * 32 * 128 * 2
    flops, nbytes = trace_window_paged_roofline.windowed_paged_attention([[7000]], 4096, *shape)
    assert flops == 4 * 32 * 128 * 4096 and nbytes == 2 * 64 * 64 * 8 * 128 * 2 + 2 * 32 * 128 * 2


# ------------------------------------------------------------- the readers ---
def _spans(steps):
    """Step spans as the scheduler records them: ``(ts_us, [(uid, phase, tokens)], K)``."""
    rows = []
    for ts, members, k in steps:
        for uid, phase, n in members:
            rows.append({"name": phase, "cat": "serving", "ts_us": ts, "dur_us": 900,
                         "args": {"uid": uid, "tokens": n}})
        if k > 1:
            rows.append({"name": "decode_loop", "cat": "inference", "ts_us": ts + 10,
                         "dur_us": 100, "args": {"steps": k}})
        rows.append({"name": "prepare", "cat": "inference", "ts_us": ts + 1, "dur_us": 50,
                     "args": {"released_blocks": 4 if members[0][1] == "prefill" else 0}})
    return rows


def _run_and_env(device_ops):
    steps = [(1_000_000 + 1000 * i, [(1, "prefill", 256)], 1) for i in range(20)]  # 5120 tokens
    steps += [(1_030_000, [(1, "decode", 1), (2, "prefill", 255)], 1),
              (1_031_000, [(1, "decode", 8)], 8)]
    run = {"spans": _spans(steps), "t0": 1.0, "seconds": 1.0, "mode": "serve",
           "trace_slice": types.SimpleNamespace(began=1.0, ended=2.0),
           "model": {"n_heads": 32, "n_kv_heads": 8, "head_dim": 128, "n_layers": 2,
                     "block_size": 64}}
    trace = types.SimpleNamespace(devices={0: device_ops}, host=[])
    env = {"trace": trace, "peaks": opcount.PEAKS["TPU v5 lite"],
           "config": {"sliding_window": 4096}}
    return run, env


def test_roofline_readers_split_the_steps_by_grid_and_stay_under_the_whole_context_reading():
    ops = [(0, 40_000_000, "paged_attention_prefill"), (50_000_000, 51_000_000,
                                                       "paged_attention_update")]
    run, env = _run_and_env(ops)
    tiled = trace_window_paged_roofline.read(
        run, {"pattern": "paged_attention_prefill", "min_tokens": 33}, env)
    token = trace_window_paged_roofline.read(
        run, {"pattern": "paged_attention_update", "max_tokens": 32}, env)
    assert 0 < tiled < 100 and 0 < token < 100
    # the same steps priced over the whole context ask for more than the window does
    from benchmark.readers import trace_paged_roofline
    whole = trace_paged_roofline.read(
        run, {"pattern": "paged_attention_update", "kernel_max_tokens": 32}, env)
    assert token < whole
    # no window in the configuration: the two readings are one
    env["config"] = {"sliding_window": None}
    assert trace_window_paged_roofline.read(
        run, {"pattern": "paged_attention_update", "max_tokens": 32}, env) == pytest.approx(whole)
    # a program without the kernel's events (the parent on the gather arm): nothing to read
    run, env = _run_and_env([(0, 1000, "fusion.1")])
    assert trace_window_paged_roofline.read(
        run, {"pattern": "paged_attention_prefill", "min_tokens": 33}, env) is None


def test_released_blocks_are_summed_per_second_and_a_program_without_them_reads_nothing():
    run, env = _run_and_env([(0, 1000, "fusion.1")])
    params = {"name": "prepare", "cat": "inference", "arg": "released_blocks"}
    assert span_arg_per_second.read(run, params, env) == 4 * 20 + 0 + 0
    for s in run["spans"]:
        s["args"].pop("released_blocks", None)  # the parent's spans
    assert span_arg_per_second.read(run, params, env) is None
    env["trace"] = None  # the CPU rehearsal
    assert span_arg_per_second.read(*_run_and_env([])[:1], params, env) is None


# -------------------------------------------------------------- rehearsal ---
def test_the_cell_rehearses_at_a_tiny_preset(tmp_path):
    """Window 16 over 4-token blocks, prompts of 3-4 windows, through the
    harness's test-only entry: the family, the traffic, the new metric files and
    readers all load; every request crosses the window and the check holds
    prefill-in-chunks, release and decode to the float32 reference."""
    root = tiny.make_root(tmp_path / "root")
    config = dict(tiny.TINY_MISTRAL_SERVE, family="mistral_windowed", sliding_window=16,
                  engine={"kv_block_size": 4,
                          "state_manager": {"memory_config": {"mode": "allocate", "size": 96},
                                            "max_context": 96, "max_ragged_batch_size": 32,
                                            "max_ragged_sequence_count": 8}})
    tiny.write_json(os.path.join(root, "benchmark", "configs", "tiny-window.json"), config)
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", "longdoc-closed.json")) as f:
        traffic = json.load(f)
    traffic.update(tiny._TIMES)
    traffic["params"].update(clients=3, requests_per_client=40,
                             prompt={"dist": "lognormal", "median": 60, "sigma": 0.2, "min": 48,
                                     "max": 76},
                             output={"dist": "uniform", "min": 4, "max": 12})
    tiny.write_json(os.path.join(root, "benchmark", "traffic", "tiny-longdoc.json"), traffic)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-window", "source": "none: a test preset",
                             "file": "benchmark/configs/tiny-window.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "tiny-window-longdoc", "config": "tiny-window",
                               "traffic": "tiny-longdoc", "chips": 1, "why": "CPU rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "tiny-mixtral-closed" in m["workloads"]:
            m["workloads"].append("tiny-window-longdoc")
    tiny.write_json(path, bench)

    for trace in (0, 1):
        out = io.StringIO()
        assert harness.run_cell(root, "tiny-window-longdoc", 3, 1.5, trace, rehearsal=True,
                                out=out) == 0
        text = out.getvalue()
        line = tiny.last_line(text)
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
        assert text.count("-> ok") >= 4 and "WRONG" not in text
    # the traced run: what needs the chip's trace finds nothing and is left out
    assert line["metrics"]["cpu_rehearsal.compiles_in_window"]["value"] == 0
    assert line["metrics"]["cpu_rehearsal.kv_blocks_peak_pct"]["value"] < 40
    for name in NEW_METRICS:
        assert f"metric {name}: nothing to read, left out" in text


# ------------------------------------------- the gap labels, by bisection ---
@pytest.mark.parametrize("seed", range(6))
def test_bisected_gap_labels_are_the_walked_ones(seed):
    """``interval_lookup.attribute`` against the ``trace_reduce.attribute`` it
    stands in for: step-like intervals (sorted, disjoint, touching or apart),
    gaps inside, across, between and outside them."""
    import random

    from benchmark import interval_lookup
    rng = random.Random(seed)
    labelled, t = [], 1000
    for i in range(400):
        t += rng.choice([0, 0, 3, 50])
        d = rng.randint(1, 40)
        labelled.append((t, t + d, f"label{i % 7}"))
        t += d
    for _ in range(3000):
        a = rng.randint(900, t + 100)
        gap = (a, a + rng.choice([1, 2, 10, 60, 400]))
        assert interval_lookup.attribute(gap, labelled) == interval_lookup._plain(gap, labelled)
    # overlapping or unsorted intervals (the harness's own annotations of several
    # threads) are walked as before
    mixed = labelled[::-1] + [(1000, t, "whole")]
    for _ in range(200):
        a = rng.randint(900, t)
        assert interval_lookup.attribute((a, a + 30), mixed) == interval_lookup._plain((a, a + 30),
                                                                                       mixed)


def test_the_family_installs_the_bisection_for_its_own_process_only():
    from benchmark import interval_lookup, trace_reduce
    assert trace_reduce.attribute is interval_lookup.attribute  # mistral_windowed was imported
    assert interval_lookup._plain.__module__ == "benchmark.trace_reduce"


# ----------------------------------- the family's weights and its reference ---
def test_family_weights_are_the_programs_and_its_reference_is_the_plain_one_padded():
    import jax
    import numpy as np

    from benchmark.references import mistral as plain
    sizes = dict(tiny.TINY_MISTRAL_SERVE, sliding_window=16, num_hidden_layers=3)
    cfg = mistral_windowed.program_config(sizes)
    params = mistral_windowed.serving_params(cfg, 2147483900)
    whole = mistral_windowed.mistral.serving_params(cfg, 2147483900)
    assert jax.tree.structure(params) == jax.tree.structure(whole)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(whole)))
    layers = [np.asarray(params["model"][f"layers_{i}"]["mlp"]["up_proj"]["kernel"], np.float32)
              for i in range(3)]
    assert not np.allclose(layers[0], layers[1]) and not np.allclose(layers[1], layers[2])
    assert abs(layers[2].std() / np.asarray(
        whole["model"]["layers_2"]["mlp"]["up_proj"]["kernel"], np.float32).std() - 1) < 0.1
    again = mistral_windowed.serving_params(cfg, 2147483900)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)))

    ids = np.random.default_rng(0).integers(0, 256, 50).astype(np.int32)  # max_context 64
    rows = np.arange(41, 50)
    np.testing.assert_allclose(
        np.asarray(mistral_windowed.reference.forward_logits(params, sizes, ids, rows=rows)),
        np.asarray(plain.forward_logits(params, sizes, ids, rows=rows)), atol=2e-5, rtol=0)
