"""The Mellum cell's files (PR 30): the configuration against the catalog row,
the traffic and the metrics resolve, the family module refuses a program
without ``MellumConfig`` at once, the cell rehearses at a tiny preset, and the
new readers on fixtures."""

import io
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import harness, opcount
from benchmark.readers import span_arg_ratio, trace_mixed_paged_roofline
from tests.benchmark import tiny

CELL, CONFIG = "mellum2-repoctx-closed", "mellum2-12b-a2.5b-serve-1chip"
NEW_METRICS = ("kv_full_layer_blocks_pct", "moe_rows_per_assignment",
               "paged_mixed_tiled_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def resolved():
    return harness.resolve(tiny.REPO, CELL)


def test_the_cell_is_the_one_the_issue_names(resolved):
    bench, cell, config, traffic = resolved
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "repoctx-closed", 1)
    assert config["family"] == "mellum" and config["mode"] == "serve"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == config["source"]
    assert config["reduced_from"] == {"num_hidden_layers": 28}
    assert config["num_hidden_layers"] in (4, 8)  # whole periods of the 3-window-1-full pattern
    sm = config["engine"]["state_manager"]
    assert (config["engine"]["kv_block_size"], sm["max_context"], sm["max_ragged_batch_size"],
            sm["max_ragged_sequence_count"], config["serving"]["decode_chunk"]) == \
        (64, 16384, 256, 8, 8)
    # dropless: capacity = tokens
    assert config["engine"]["expert_parallel"]["capacity_factor"] == \
        config["num_experts"] / config["num_experts_per_tok"] == 8.0
    p = traffic["params"]
    assert traffic["kind"] == "closed_clients" and (p["clients"], p["requests_per_client"]) == (8, 256)
    assert p["prompt"] == {"dist": "lognormal", "median": 2048, "sigma": 0.9, "min": 128,
                           "max": 15872}
    assert p["output"] == {"dist": "lognormal", "median": 32, "sigma": 0.6, "min": 8, "max": 128}
    assert p["temperature"] == 0.0 and p["prompt"]["max"] + p["output"]["max"] <= sm["max_context"]
    assert (traffic["lead_in_s"], traffic["drain_s"], traffic["trace_start_s"],
            traffic["trace_length_s"]) == (8.0, 6.0, 10.0, 4.0)
    assert {k for k in config if k.endswith("_why")} == {"engine_why", "serving_why"}
    assert {"qk_norm", "mtp_head", "max_context"} <= set(config["assumed"])


def test_every_number_of_the_catalog_row_is_in_the_file_and_depth_is_the_only_cut(resolved):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog beside the model-configs guide is not here")
    config = resolved[2]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == config["source"])
    differs = [k for k, v in row["config"].items() if config.get(k) != v]
    assert differs == ["num_hidden_layers"]
    assert config["num_hidden_layers"] % 4 == 0 and \
        config["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]


def test_the_weights_and_the_pool_are_seventy_percent_of_the_chip(resolved):
    config = resolved[2]
    layers, h, d = config["num_hidden_layers"], config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"] * d, config["num_key_value_heads"] * d
    experts = config["num_experts"] * 3 * h * config["moe_intermediate_size"]
    layer = 2 * h * heads + 2 * h * kv + h * config["num_experts"] + experts + 2 * h
    weights = 2 * (layers * layer + 2 * config["vocab_size"] * h + h)
    # a block id holds the layers of ONE of the four groups
    block = (layers // 4) * 2 * kv * config["engine"]["kv_block_size"] * 2
    pool = config["engine"]["state_manager"]["memory_config"]["size"] * block
    hbm = opcount.PEAKS["TPU v5 lite"]["hbm_bytes"]
    assert 0.68 <= (weights + pool) / hbm <= 0.72
    assert 2 * (4 * layer + 2 * config["vocab_size"] * h) / hbm < 0.25 < (weights + pool) / hbm


def test_the_programs_to_warm_are_the_forty_nine_the_depth_was_chosen_by(resolved):
    from benchmark.runners import serve
    _, _, config, traffic = resolved
    forward, loops = serve.reachable_programs(config["engine"], config["serving"],
                                              traffic["params"])
    assert (len(forward), len(loops)) == (42, 7)
    assert {mb for _, _, mb in forward} == {4, 8, 16, 32, 64, 128, 256}


def test_its_metrics_are_listed_and_the_one_window_rooflines_are_not(resolved):
    bench = resolved[0]
    traced = {m["name"] for m in harness.metrics_for(bench, CELL, True)}
    assert set(NEW_METRICS) <= traced
    assert not {"paged_attn_roofline", "paged_window_tiled_roofline",
                "paged_window_token_roofline"} & traced
    # decode-only steps are ~40 of a run's ~5,700: a 4 s slice can hold none, and a
    # metric a traced run may find nothing for cannot list the cell (PERF.md section 7)
    assert "paged_attn_busy_pct" not in traced
    assert {"moe_busy_pct", "moe_route_busy_pct", "attn_busy_pct",
            "paged_prefill_busy_pct", "sched_pipelined_per_s",
            "device_idle_pct", "kv_blocks_peak_pct", "hbm_peak_pct", "compiles_in_window"} <= traced
    assert {m["name"] for m in harness.metrics_for(bench, CELL, False)} == \
        {"tpot_p50_ms", "serve_tokens_per_s", "setup_s"}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW_METRICS}
    for name in NEW_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["layer"] in layers
        with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{name}.json")) as f:
            assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "readers",
                                               f"{json.load(f)['reader']}.py"))


# ------------------------------------------------------------ the refusal ---
def test_a_program_without_mellum_config_exits_at_once_with_a_message(tmp_path):
    """The parent's tree under this PR's benchmark files: the family module is
    loaded in a process where ``deepspeed_tpu.models.mellum`` cannot be
    imported, and exits before anything is made."""
    code = (
        "import sys, importlib.abc\n"
        "class Absent(importlib.abc.MetaPathFinder):\n"
        "    def find_spec(self, name, path, target=None):\n"
        "        if name == 'deepspeed_tpu.models.mellum':\n"
        "            raise ImportError('No module named deepspeed_tpu.models.mellum')\n"
        "sys.meta_path.insert(0, Absent())\n"
        "from benchmark import harness\n"
        f"harness._load_module({tiny.REPO!r}, 'models', 'mellum')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=tiny.REPO, capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=tiny.REPO, JAX_PLATFORMS="cpu"))
    assert done.returncode == 1
    assert "no deepspeed_tpu.models.mellum" in done.stderr and "Nothing was measured" in done.stderr
    assert "Traceback" not in done.stderr


def test_the_family_builds_the_programs_config_from_the_file(resolved):
    family = harness._load_module(tiny.REPO, "models", "mellum")
    config = resolved[2]
    cfg = family.program_config(config)
    n = config["num_hidden_layers"]
    assert cfg.num_hidden_layers == n and cfg.layer_types == tuple(config["layer_types"][:n])
    assert (cfg.head_dim, cfg.hidden_size, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.vocab_size, cfg.sliding_window) == (128, 2304, 64, 8, 98304, 1024)
    assert cfg.rope_of("full_attention")["rope_type"] == "yarn"
    assert [cfg.window_of(i) for i in range(4)] == [1024, 1024, 1024, 0]


# ---------------------------------------------------------- the mixed count ---
SHAPE = (32, 4, 128, 64)


@pytest.mark.parametrize("queries", [
    [[100]], [[1024]], [[1025]], [[16000]], [list(range(900, 1156))], [list(range(8000, 8256))],
    [[5000], [], [100], list(range(15000, 15064))],
])
def test_a_window_layers_count_is_bounded_by_a_full_layers(queries):
    whole = opcount.paged_attention(queries, *SHAPE)
    windowed = trace_mixed_paged_roofline.windowed_paged_attention(queries, 1024, *SHAPE)
    assert windowed[0] <= whole[0] and windowed[1] <= whole[1]
    inside = all(c <= 1024 for contexts in queries for c in contexts)
    assert (windowed == whole) == inside
    assert trace_mixed_paged_roofline.windowed_paged_attention(queries, 0, *SHAPE) == whole


def test_mixed_least_time_is_three_window_layers_and_one_full_a_period():
    peaks = opcount.PEAKS["TPU v5 lite"]
    queries = [list(range(8001, 8257))]
    full = opcount.roofline_seconds(*opcount.paged_attention(queries, *SHAPE), peaks)[0]
    window = opcount.roofline_seconds(
        *trace_mixed_paged_roofline.windowed_paged_attention(queries, 1024, *SHAPE), peaks)[0]
    assert window < full / 5
    got = trace_mixed_paged_roofline.mixed_least_seconds(queries, [1024, 1024, 1024, 0] * 2,
                                                         SHAPE, peaks)
    assert got == pytest.approx(6 * window + 2 * full)
    config = {"layer_types": ["sliding_attention"] * 3 + ["full_attention"] * 25,
              "sliding_window": 1024, "num_hidden_layers": 8}
    assert trace_mixed_paged_roofline.layer_windows(config) == [1024] * 3 + [0] * 5
    assert trace_mixed_paged_roofline.layer_windows({"sliding_window": 4096}) is None


# ------------------------------------------------------------- the readers ---
def _spans(steps):
    """Step spans as the scheduler records them, with the engine's ``prepare``
    and ``put`` spans as PR 30 fills them."""
    rows = []
    for ts, members, k in steps:
        for uid, phase, n in members:
            rows.append({"name": phase, "cat": "serving", "ts_us": ts, "dur_us": 900,
                         "args": {"uid": uid, "tokens": n}})
        rows.append({"name": "prepare", "cat": "inference", "ts_us": ts + 1, "dur_us": 50,
                     "args": {"released_blocks": 0, "live_blocks_full": 30,
                              "live_blocks_window": 60}})
        if k > 1:
            rows.append({"name": "decode_loop", "cat": "inference", "ts_us": ts + 10,
                         "dur_us": 100, "args": {"steps": k}})
        else:
            tokens = sum(n for _, _, n in members)
            rows.append({"name": "put", "cat": "inference", "ts_us": ts + 60, "dur_us": 100,
                         "args": {"tokens": tokens, "moe_rows": 8 * 64 * 256,
                                  "moe_assignments": tokens * 8 * 8}})
    return rows


CONFIG_FIXTURE = {"layer_types": ["sliding_attention"] * 3 + ["full_attention"] * 25,
                  "sliding_window": 1024, "num_hidden_layers": 4, "num_attention_heads": 32,
                  "num_key_value_heads": 4, "head_dim": 128, "hidden_size": 2304,
                  "engine": {"kv_block_size": 64}}


def _run_and_env(device_ops, config=CONFIG_FIXTURE):
    steps = [(1_000_000 + 1000 * i, [(1, "prefill", 256)], 1) for i in range(20)]  # 5120 tokens
    steps += [(1_030_000, [(1, "decode", 1), (2, "prefill", 255)], 1),
              (1_031_000, [(1, "decode", 8)], 8)]
    run = {"spans": _spans(steps), "t0": 1.0, "seconds": 1.0, "mode": "serve",
           "trace_slice": types.SimpleNamespace(began=1.0, ended=2.0),
           # what runners/serve.py works out for this model: hidden / heads, NOT head_dim
           "model": {"n_heads": 32, "n_kv_heads": 4, "head_dim": 72, "n_layers": 4,
                     "block_size": 64}}
    trace = types.SimpleNamespace(devices={0: device_ops}, host=[])
    return run, {"trace": trace, "peaks": opcount.PEAKS["TPU v5 lite"], "config": config}


def test_mixed_roofline_splits_the_steps_by_grid_and_lies_between_the_other_two_readings():
    ops = [(0, 40_000_000, "paged_attention_prefill"), (50_000_000, 51_000_000,
                                                       "paged_attention_update")]
    run, env = _run_and_env(ops)
    tiled = trace_mixed_paged_roofline.read(
        run, {"pattern": "paged_attention_prefill", "min_tokens": 33}, env)
    token = trace_mixed_paged_roofline.read(
        run, {"pattern": "paged_attention_update", "max_tokens": 32}, env)
    assert 0 < tiled < 100 and 0 < token < 100
    # every layer clamped asks for less, every layer whole for more
    every = dict(CONFIG_FIXTURE, layer_types=["sliding_attention"] * 28)
    none = dict(CONFIG_FIXTURE, layer_types=["full_attention"] * 28)
    params = {"pattern": "paged_attention_update", "max_tokens": 32}
    clamped = trace_mixed_paged_roofline.read(run, params, _run_and_env(ops, every)[1])
    whole = trace_mixed_paged_roofline.read(run, params, _run_and_env(ops, none)[1])
    assert clamped < token < whole
    assert token == pytest.approx(0.75 * clamped + 0.25 * whole)
    # a configuration with one window for every layer says nothing layer by layer
    run, env = _run_and_env(ops, {"sliding_window": 4096})
    assert trace_mixed_paged_roofline.read(run, params, env) is None
    # a program without the kernel's events: nothing to read
    run, env = _run_and_env([(0, 1000, "fusion.1")])
    assert trace_mixed_paged_roofline.read(run, params, env) is None


def test_span_ratios_and_a_program_without_the_args_reads_nothing():
    run, env = _run_and_env([(0, 1000, "fusion.1")])
    full = {"name": "prepare", "cat": "inference", "numerator": ["live_blocks_full"],
            "denominator": ["live_blocks_full", "live_blocks_window"], "scale": 100.0}
    rows = {"name": "put", "cat": "inference", "numerator": ["moe_rows"],
            "denominator": ["moe_assignments"]}
    assert span_arg_ratio.read(run, full, env) == pytest.approx(100.0 * 30 / 90)
    # 21 put steps of 8 x 64 x 256 rows over (20 x 256 + 256) tokens x 8 x 8 assignments
    assert span_arg_ratio.read(run, rows, env) == pytest.approx(21 * 8 * 64 * 256 / (5376 * 64))
    for s in run["spans"]:  # the parent's spans
        for arg in ("live_blocks_full", "live_blocks_window", "moe_rows", "moe_assignments"):
            s["args"].pop(arg, None)
    assert span_arg_ratio.read(run, full, env) is None
    assert span_arg_ratio.read(run, rows, env) is None
    run, env = _run_and_env([])  # the CPU rehearsal: no device plane
    assert span_arg_ratio.read(run, full, env) is None


# -------------------------------------------------------------- rehearsal ---
TINY_MELLUM = {
    "family": "mellum", "mode": "serve", "torch_dtype": "float32", "attention_bias": False,
    "head_dim": 16, "hidden_size": 48, "max_position_embeddings": 512,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 4, "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 4,
    "num_hidden_layers": 4, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                           "original_max_position_embeddings": 32, "beta_fast": 4.0,
                           "beta_slow": 1.0},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0}},
    "sliding_window": 16, "tie_word_embeddings": False, "vocab_size": 256,
    "engine": {"kv_block_size": 4,
               "state_manager": {"memory_config": {"mode": "allocate", "size": 256},
                                 "max_context": 96, "max_ragged_batch_size": 32,
                                 "max_ragged_sequence_count": 8},
               "expert_parallel": {"capacity_factor": 2.0}},
    "serving": {"decode_chunk": 4, "queue_capacity": 1024},
}


def test_the_cell_rehearses_at_a_tiny_preset(tmp_path):
    """Window 16 over 4-token blocks, prompts on both sides of it, top-4 of 8,
    through the harness's test-only entry: the family, the traffic, the new
    metric files and readers all load, and the check holds prefill in chunks,
    release in the window groups and decode to the float32 reference."""
    root = tiny.make_root(tmp_path / "root")
    tiny.write_json(os.path.join(root, "benchmark", "configs", "tiny-mellum.json"), TINY_MELLUM)
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", "repoctx-closed.json")) as f:
        traffic = json.load(f)
    traffic.update(tiny._TIMES)
    traffic["params"].update(clients=3, requests_per_client=40,
                             prompt={"dist": "lognormal", "median": 30, "sigma": 0.9, "min": 6,
                                     "max": 76},
                             output={"dist": "uniform", "min": 4, "max": 12})
    tiny.write_json(os.path.join(root, "benchmark", "traffic", "tiny-repoctx.json"), traffic)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-mellum", "source": "none: a test preset",
                             "file": "benchmark/configs/tiny-mellum.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "tiny-mellum-repoctx", "config": "tiny-mellum",
                               "traffic": "tiny-repoctx", "chips": 1, "why": "CPU rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "tiny-mixtral-closed" in m["workloads"]:
            m["workloads"].append("tiny-mellum-repoctx")
    tiny.write_json(path, bench)

    for trace in (0, 1):
        out = io.StringIO()
        assert harness.run_cell(root, "tiny-mellum-repoctx", 2**31 + 11, 1.5, trace,
                                rehearsal=True, out=out) == 0
        text = out.getvalue()
        line = tiny.last_line(text)
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
        assert text.count("-> ok") >= 4 and "WRONG" not in text
    assert line["metrics"]["cpu_rehearsal.compiles_in_window"]["value"] == 0
    for name in NEW_METRICS:
        assert f"metric {name}: nothing to read, left out" in text
