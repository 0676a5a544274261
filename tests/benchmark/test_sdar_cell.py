"""The SDAR cell's files (PR 50): the configuration against the catalog row and
its own arithmetic (which adds up to the tree ``init_params`` makes), the
traffic and the metrics as the issue gives them, the family module refuses a
program without ``SdarMoeConfig`` at once, the new readers (a block loop's span
read as the chunk span it is; each row priced to its block's end; nothing to
read and no raise on another program), and the cell and its controls rehearsed
at a tiny preset through ``runners/serve_blocks.py``."""

import io
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness, opcount
from benchmark.readers import (span_arg_ratio, trace_block_expert_roofline,
                               trace_block_paged_roofline, trace_expert_roofline)
from tests.benchmark import tiny

CELL, CONFIG = "sdar-30b-a3b-chat32-closed", "sdar-30b-a3b-serve-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("block_forwards_per_token", "block_loop_launch_p50_ms",
               "block_loop_round_trip_p50_ms", "unmask_busy_pct", "commit_busy_pct",
               "block_moe_grouped_roofline", "paged_block_tiled_roofline")
# accepted metrics that would misread this cell: a block chunk is no ``decode_loop`` span (they
# divide by ``loop_steps`` or look for that dispatch), ``hidden_size / heads`` is not the head,
# ``diffusion/unmask`` is a scope of the program's, not unscoped time
NOT_ITS = {"step_decode_p50_ms", "step_any_p50_ms", "step_device_any_p50_ms",
           "idle_in_engine_pct", "paged_attn_roofline", "paged_attn_busy_pct",
           "unscoped_busy_pct", "moe_grouped_roofline", "moe_banks_per_assignment",
           "chunk_launch_p50_ms", "serve_tokens_per_s", "ttft_p50_ms"}
GIB = 2**30


@pytest.fixture(scope="module")
def resolved():
    return harness.resolve(tiny.REPO, CELL)


def test_the_cell_is_the_one_the_issue_names(resolved):
    bench, cell, config, traffic = resolved
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "chat32-closed", 1)
    assert len(cell["why"]) <= 200 and "static schedule" in cell["why"] and "7 of 48" in cell["why"]
    assert config["family"] == "sdar_moe" and config["mode"] == "serve_blocks"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] == list(config["reduced_from"])
    assert entry["source"] == config["source"] and "deployment_share" not in config
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] == [CELL]
    sm = config["engine"]["state_manager"]
    assert (config["engine"]["kv_block_size"], sm["max_context"], sm["max_ragged_batch_size"],
            sm["max_ragged_sequence_count"], config["serving"]["decode_chunk"],
            config["engine"]["expert_parallel"]["capacity_factor"]) == (64, 2048, 256, 32, 8, 16.0)
    p = traffic["params"]
    assert traffic["kind"] == "closed_clients" and (p["clients"], p["requests_per_client"]) == (32, 24)
    assert p["prompt"] == {"dist": "uniform", "min": 256, "max": 768}
    assert p["output"]["median"] == 512 and p["temperature"] == 0.0
    assert p["prompt"]["max"] + p["output"]["max"] == 1600 <= sm["max_context"]
    # the check's longest state: the longest prompt and five blocks, whole blocks, one length
    assert config["reference_pad_to"] >= p["prompt"]["max"] + 5 * 4
    assert config["reference_pad_to"] % config["assumed"]["block_length"] == 0
    assumed = config["assumed"]
    assert (assumed["block_length"], assumed["denoising_steps"], assumed["remasking_strategy"],
            assumed["mask_token_id"]) == (4, 4, "low_confidence_static", 151669)
    assert {"block_length", "denoising_steps", "remasking_strategy", "mask_token_id",
            "qk_norm", "init"} <= set(assumed["why"])
    assert "FLAG" in assumed["why"]["mask_token_id"] and "remember" in assumed["note"]
    assert "WHAT THE CUT DISTORTS" in config["deployment"]


def test_every_key_of_the_catalog_row_is_in_the_file_and_only_the_depth_differs(resolved):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog beside the model-configs guide is not here")
    config = resolved[2]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == config["source"])
    assert row["name"] == "SDAR-30B-A3B-Chat" and len(row["config"]) == 24
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert value == config["reduced_from"][key] == 48 and 4 <= config[key] <= 7
        else:
            assert key in config and config[key] == value, key
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["num_experts"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["vocab_size"]) == \
        (2048, 32, 4, 128, 128, 768, 8, 151936)


def test_the_files_arithmetic_adds_up_to_the_tree_init_params_makes(resolved):
    """The deployment's parameter counts, from the file's numbers alone, and
    against the shapes ``init_params`` makes from the program's config (nothing
    is computed: ``jax.eval_shape``)."""
    import jax
    from deepspeed_tpu.models import sdar_moe
    c = resolved[2]
    M, V, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    H, KVH, D = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    E, F = c["num_experts"], c["moe_intermediate_size"]
    rest = 2 * M * H * D + 2 * M * KVH * D + M * E + 2 * M + 2 * D
    expert = 3 * M * F
    layer = rest + E * expert
    ends = 2 * V * M + M
    assert (round(rest / 1e6, 2), round(expert / 1e6, 3), round(E * expert / 1e6, 2)) == \
        (19.14, 4.719, 603.98)
    assert round(layer / 1e6, 2) == 623.12 and round(2 * layer / GIB, 3) == 1.161
    assert round(ends / 1e6, 2) == 622.33
    total = L * layer + ends
    family = harness._load_module(tiny.REPO, "models", "sdar_moe")
    cfg = family.program_config(c)
    tree = jax.eval_shape(lambda: sdar_moe.init_params(cfg, param_dtype=cfg.dtype)[1])
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree)) == total
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree["layers_0"])) == layer
    block = c["engine"]["kv_block_size"] * L * 2 * KVH * D * 2
    pool = c["engine"]["state_manager"]["memory_config"]["size"] * block
    held = (2 * total + pool) / (16 * GIB)
    assert 0.65 <= held <= 0.75
    if L == 7:
        assert round(total / 1e6, 1) == 4984.2 and round(2 * total / GIB, 2) == 9.28
        assert block == 896 * 1024 and round(pool / GIB, 2) == 1.97
        for said in ("19.14 M", "603.98 M", "623.12 M", "1.161 GiB", "622.33 M", "4984.2 M",
                     "9.28 GiB", "1.97 GiB", "70.3 %", "147456 tokens"):
            assert said in c["deployment"], said
        assert round(100 * held, 1) == 70.3


def test_every_engine_key_says_why(resolved):
    config = resolved[2]
    assert {k for k in config if k.endswith("_why")} == {"engine_why", "serving_why"}
    engine = config["engine"]
    keys = {"kv_block_size", "memory_config", "capacity_factor"} | \
        (set(engine["state_manager"]) - {"memory_config"})
    assert keys | {"correct", "num_hidden_layers"} == set(config["engine_why"])
    assert set(config["serving"]) == set(config["serving_why"])
    assert all(len(why) > 40 for why in config["engine_why"].values())
    assert "FALSE" in config["engine_why"]["correct"]  # the controls' readings
    assert "tile" in config["engine_why"]["max_ragged_sequence_count"]


def test_its_metrics_are_listed_and_each_new_one_names_a_reader_that_exists(resolved):
    bench = resolved[0]
    traced = {m["name"] for m in harness.metrics_for(bench, CELL, True)}
    assert set(NEW_METRICS) <= traced and len(bench["per_layer"]) <= 128
    assert {"moe_busy_pct", "moe_route_busy_pct", "attn_busy_pct", "paged_prefill_busy_pct",
            "serve_generated_tokens_per_s", "device_idle_pct", "kv_blocks_peak_pct",
            "compiles_in_window", "sched_seqs_per_step", "engine_prepare_p50_ms"} <= traced
    assert not NOT_ITS & traced
    assert {m["name"] for m in harness.metrics_for(bench, CELL, False)} == \
        {"tpot_p50_ms", "setup_s"}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW_METRICS}
    for name in NEW_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == "tpot_p50_ms"
        assert entry["layer"] in layers
        if name.endswith("_roofline"):
            assert (entry["unit"], entry["better"], entry["source"]) == \
                ("%", "higher", "device_trace")
        with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{name}.json")) as f:
            assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "readers",
                                               f"{json.load(f)['reader']}.py"))
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        perf = f.read()
    assert all(f"`{name}`" in perf for name in NEW_METRICS)
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024


def test_a_program_without_the_family_exits_at_once_with_a_message():
    code = ("import sys\n"
            "sys.modules['deepspeed_tpu.models.sdar_moe'] = None\n"
            "from benchmark import harness\n"
            f"harness._load_module({tiny.REPO!r}, 'models', 'sdar_moe')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=tiny.REPO, capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=tiny.REPO, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0
    assert "diffusion over blocks" in done.stderr and "Nothing was measured" in done.stderr


def test_the_family_builds_the_programs_config_from_the_file(resolved):
    family = harness._load_module(tiny.REPO, "models", "sdar_moe")
    cfg = family.program_config(resolved[2])
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.hidden_size) == \
        (resolved[2]["num_hidden_layers"], 151936, 2048)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (32, 4, 128)
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id) == (4, 4, 151669)
    assert cfg.rope_theta == 1e6 and cfg.mlp_only_layers == () and cfg.rope_scaling is None
    hash(cfg)


# ---------------------------------------------------------------- readers ---
def test_a_row_is_priced_for_the_keys_up_to_its_blocks_end():
    assert trace_block_paged_roofline.block_contexts(8, 4, 4) == [12, 12, 12, 12]
    assert trace_block_paged_roofline.block_contexts(0, 6, 4) == [4, 4, 4, 4, 8, 8]
    assert trace_block_paged_roofline.block_contexts(5, 2, 1) == [6, 7]  # block 1 is causal


def _env(config, ops, logged):
    trace = SimpleNamespace(devices={0: ops}, host=[])
    return {"trace": trace, "peaks": opcount.PEAKS["TPU v5 lite"], "config": config,
            "log": logged.append, "host_phases": ([], {})}


def test_the_tile_grids_least_is_every_forward_of_every_block_in_the_slice(resolved):
    """A step whose members all decode is a block loop: the blocks and the
    forwards a block that its ``inference.block_loop`` span (same tick) says it
    dispatched — two blocks of five here, ONE block where the scheduler fell
    back to one; a prefill step one forward; contexts grow by what was fed and
    by the loop's blocks whole. Nothing to read, and no raise, where the
    configuration has no block length, the trace no such kernel or the
    program no such span."""
    config = resolved[2]
    L = config["num_hidden_layers"]
    shape = (32, 4, 128, 64)
    ops = [(0, 1_000_000, "%paged_attention_prefill.3 = custom-call(%q)"),
           (2_000_000, 2_500_000, "%fusion.1 = fusion(%x)")]
    slice_ = SimpleNamespace(began=0.0, ended=4.0, sync_clock=None)

    def member(uid, phase, tokens, ts, tick):
        return {"name": phase, "cat": "serving", "ts_us": ts, "dur_us": 10,
                "args": {"uid": uid, "tokens": tokens, "tick": tick}}

    def loop(tick, seqs, n_blocks, ts):
        return {"name": "block_loop", "cat": "inference", "ts_us": ts, "dur_us": 5,
                "args": {"tick": tick, "seqs": seqs, "blocks": seqs * n_blocks,
                         "forwards": seqs * n_blocks * 5}}

    spans = [member(7, "prefill", 256, 100, 1), member(7, "prefill", 44, 200, 2),
             member(7, "decode", 7, 300, 3), member(9, "decode", 8, 300, 3), loop(3, 2, 2, 301),
             member(7, "decode", 4, 400, 4), loop(4, 1, 1, 401),  # one block: no room for two
             member(7, "decode", 8, 5_000_000, 5), loop(5, 1, 2, 5_000_001)]  # after the slice
    run = {"trace_slice": slice_, "spans": spans, "t0": 0.0, "seconds": 45.0}
    logged = []
    got = trace_block_paged_roofline.read(run, {"pattern": "paged_attention_prefill"},
                                          _env(config, ops, logged))
    peaks = opcount.PEAKS["TPU v5 lite"]

    def seconds(queries):
        return opcount.roofline_seconds(*opcount.paged_attention(queries, *shape), peaks)[0]

    ctx = trace_block_paged_roofline.block_contexts
    least = seconds([ctx(0, 256, 4)]) + seconds([ctx(256, 44, 4)]) \
        + 5 * seconds([ctx(300, 4, 4), ctx(0, 4, 4)]) + 5 * seconds([ctx(304, 4, 4), ctx(4, 4, 4)]) \
        + 5 * seconds([ctx(308, 4, 4)])
    assert got == pytest.approx(100.0 * L * least / 1e-3)
    assert "17 forwards of the slice" in logged[0]
    assert 0 < got < 100
    params = {"pattern": "paged_attention_prefill"}
    assert trace_block_paged_roofline.read(run, {"pattern": "no_such_kernel"},
                                           _env(config, ops, logged)) is None
    assert trace_block_paged_roofline.read(run, params, _env(resolved[0] and {
        k: v for k, v in config.items() if k != "assumed"}, ops, logged)) is None
    assert trace_block_paged_roofline.read(dict(run, spans=[]), params,
                                           _env(config, ops, logged)) is None
    no_loops = [s for s in spans if s["name"] != "block_loop" and s["name"] != "prefill"]
    assert trace_block_paged_roofline.read(dict(run, spans=no_loops), params,
                                           _env(config, ops, logged)) is None


def test_a_block_loops_span_is_read_as_the_chunk_span_it_is(resolved, monkeypatch):
    """The accepted expert reader is handed the run with ``inference.block_loop``
    renamed: its carriers then hold the loop's steps, banks and assignments; a
    run without such spans is handed on as it is."""
    seen = {}

    def fake_read(run, params, env):
        seen["rows"] = trace_expert_roofline.carriers(run["spans"], params["moe_path"])
        return 12.5

    monkeypatch.setattr(trace_expert_roofline, "read", fake_read)
    args = {"moe_path": "grouped", "moe_banks": 7680, "moe_assignments": 61440, "steps": 10}
    spans = [{"name": "block_loop", "cat": "inference", "ts_us": 5, "dur_us": 2, "args": args},
             {"name": "block_loop", "cat": "sched", "ts_us": 9, "dur_us": 2, "args": args},
             {"name": "put", "cat": "inference", "ts_us": 1, "dur_us": 2, "args": {}}]
    params = {"pattern": "^%?grouped_matmul", "moe_path": "grouped"}
    assert trace_block_expert_roofline.read({"spans": spans}, params, {}) == 12.5
    assert seen["rows"] == [(5, 7, 10, 7680, 61440)]
    assert spans[0]["name"] == "block_loop"  # the run's own rows are not touched
    trace_block_expert_roofline.read({"spans": spans[2:]}, params, {})
    assert seen["rows"] == []
    # the ratio's file on the accepted parametric reader: forwards a sequence over tokens kept
    with open(os.path.join(tiny.REPO, "benchmark", "metrics", "block_forwards_per_token.json")) as f:
        spec = json.load(f)
    run = {"spans": [{"name": "block_loop", "cat": "inference", "ts_us": 1e6, "dur_us": 1,
                      "args": {"forwards": 320, "tokens": 250}}], "t0": 0.0, "seconds": 45.0}
    env = {"trace": SimpleNamespace(devices={0: [(0, 1, "x")]})}
    assert span_arg_ratio.read(run, spec["params"], env) == pytest.approx(1.28)
    assert span_arg_ratio.read(dict(run, spans=[]), spec["params"], env) is None


# -------------------------------------------------------------- rehearsal ---
TINY = {
    "family": "sdar_moe", "mode": "serve_blocks", "torch_dtype": "float32",
    "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 96, "vocab_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
    "max_position_embeddings": 512, "reference_pad_to": 96,
    "assumed": {"block_length": 4, "denoising_steps": 4,
                "remasking_strategy": "low_confidence_static", "mask_token_id": 255,
                "init": {"attention_gain": 1.0, "expert_gain": 1.0}},
    "engine": {"kv_block_size": 8,
               "state_manager": {"memory_config": {"mode": "allocate", "size": 256},
                                 "max_context": 128, "max_ragged_batch_size": 64,
                                 "max_ragged_sequence_count": 16},
               "expert_parallel": {"capacity_factor": 4.0}},
    "serving": {"decode_chunk": 8, "queue_capacity": 1024},
}


def _tiny_root(tmp_path):
    """A throw-away benchmark root with the cell ``tiny-sdar-chat``."""
    root = tiny.make_root(tmp_path / "root")
    tiny.write_json(os.path.join(root, "benchmark", "configs", "tiny-sdar.json"), TINY)
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", "chat32-closed.json")) as f:
        traffic = json.load(f)
    traffic.update(tiny._TIMES)
    traffic["params"].update(clients=10, requests_per_client=40,
                             prompt={"dist": "uniform", "min": 20, "max": 72},
                             output={"dist": "lognormal", "median": 16, "sigma": 0.25, "min": 8,
                                     "max": 24})
    tiny.write_json(os.path.join(root, "benchmark", "traffic", "tiny-chat32.json"), traffic)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-sdar", "source": "none: a test preset",
                             "file": "benchmark/configs/tiny-sdar.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "tiny-sdar-chat", "config": "tiny-sdar",
                               "traffic": "tiny-chat32", "chips": 1, "why": "CPU rehearsal"})
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        own = json.load(f)
    listed = {m["name"] for m in own["end_to_end"] + own["per_layer"]
              if CELL in m.get("workloads", [])}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in listed:
            m["workloads"] = m["workloads"] + ["tiny-sdar-chat"]
    tiny.write_json(path, bench)
    return root


def test_the_cell_rehearses_at_a_tiny_preset(tmp_path):
    """Two layers, blocks of 4 under a 64-token budget, ten clients in ONE
    sequence bucket of 16, through the harness's test-only entry and
    ``runners/serve_blocks.py``: the family, the traffic, the new metric files
    and readers all load; the check's three parts hold (every denoise forward
    of two blocks, every choice of the loop's two blocks, the block after it)
    for prompts of all four residues; the window serves block loops and whole-
    block prompt chunks only, and compiles nothing."""
    root = _tiny_root(tmp_path)
    out = io.StringIO()
    assert harness.run_cell(root, "tiny-sdar-chat", 2**31 + 50, 1.5, 1, rehearsal=True,
                            out=out) == 0  # traced: what an untraced run does, and the readers
    text = out.getvalue()
    line = tiny.last_line(text)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # a forward a masked row of the first blocks (4 + 3 + 2 + 1, the probe's 2), four a later
    # block (3 a prompt), the block after the loop, and the best rows' line; a choice a
    # walked forward
    walked = 4 + 3 + 2 + 1 + 2 + 5 * 3 * 4
    assert text.count("-> ok") == walked + 5 + 1 and "WRONG" not in text
    assert f"the block loop's {walked} choices" in text
    assert f"{walked + 5} reference forwards" in text
    assert f"{walked} of its tokens are the walk's own greedy token" in text
    assert "tokens ([0, 1, 2, 3] mod 4, and the probe)" in text
    assert line["metrics"]["cpu_rehearsal.compiles_in_window"]["value"] == 0
    assert "programs first met after warm-up" not in text
    assert "'block_loops'" in text and "'blocks_committed'" in text
    assert "cpu_rehearsal.tpot_p50_ms" not in line["metrics"]  # traced: the per-layer line
    # every metric this cell brings reads only beside a chip's trace
    for name in NEW_METRICS:
        assert f"metric {name}: nothing to read, left out" in text


def test_the_controls_run_through_the_harness_comparison_at_a_tiny_preset(tmp_path, capsys,
                                                                        monkeypatch):
    """``benchmark/tools/controls_sdar.py`` on the tiny cell, float32: the engine
    as built reads ``correct``; a causal mask, a skipped commit and rows
    unmasked left to right each read false at this precision (on the chip the
    last two sit inside bf16's noise: the configuration says which); the exit
    code says every control was caught; a control restores what it patched."""
    import jax
    from benchmark.runners import serve_blocks
    from benchmark.tools import controls_sdar
    from deepspeed_tpu.inference.v2.model_implementations.transformer_base import (
        DSTransformerModelBase as base)
    from deepspeed_tpu.ops.pallas import paged_attention
    before = (jax.lax.top_k, base._forward_impl, base._gather_attention,
              paged_attention.paged_attention_prefill)
    root = _tiny_root(tmp_path)
    # one chunk of the loop a prompt: half the reference forwards of the cell's check, which
    # test_the_cell_rehearses_at_a_tiny_preset runs whole
    monkeypatch.setattr(serve_blocks, "CHECK_CHUNKS", 1)
    rc = controls_sdar.main(["--workload", "tiny-sdar-chat", "--seeds", str(2**31 + 50),
                             "--controls", "baseline,causal_mask,no_commit,left_to_right",
                             "--rehearsal", "1", "--root", root])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    read = {name: c["correct"] for name, c in result["controls"].items()}
    assert list(read) == ["baseline", "causal_mask", "no_commit", "left_to_right"]
    assert read.pop("baseline") is True and not any(read.values()), read
    assert rc == 0 and result["controls"]["baseline"]["rows"] == (12 + 20 + 5) * 4
    assert result["controls"]["baseline"]["median_log2"] < result["tolerance_log2"] - 8
    assert before == (jax.lax.top_k, base._forward_impl, base._gather_attention,
                      paged_attention.paged_attention_prefill)
