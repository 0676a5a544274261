"""The DeepSeek-V3.2 cell's files (PR 40): the configuration against the catalog
row, the traffic and the metrics as the issue gives them, the family module
refuses a program without ``DeepseekV32Config`` at once, the count the new
rooflines are held to, and the cell rehearsed at a tiny preset."""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness, opcount
from benchmark.readers import trace_latent_paged_roofline as roofline
from tests.benchmark import tiny

CELL, CONFIG = "deepseek-v32-longctx-reason-closed", "deepseek-v32-serve-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 61, "first_k_dense_replace": 3, "n_routed_experts": 256,
           "vocab_size": 129280}
NEW_METRICS = ("attn_index_busy_pct", "attn_index_topk_busy_pct", "attn_latent_proj_busy_pct",
               "index_selected_share", "moe_local_assignment_share",
               "paged_latent_token_roofline", "paged_latent_tiled_roofline",
               "paged_index_roofline", "latent_kernels_busy_pct", "moe_share_grouped_roofline",
               "moe_banks_per_local_assignment")
# the names PR 40 had to give three accepted readers a second time, and the accepted names
# that list this cell since PR 61: one name a reading
FOLDED = {"chunk_launch_latent_p50_ms": "chunk_launch_p50_ms",
          "chunk_round_trip_latent_p50_ms": "chunk_round_trip_p50_ms",
          "idle_in_chunk_run_latent_pct": "idle_in_chunk_run_pct"}


@pytest.fixture(scope="module")
def resolved():
    return harness.resolve(tiny.REPO, CELL)


def test_the_cell_is_the_one_the_issue_names(resolved):
    bench, cell, config, traffic = resolved
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longctx-reason-closed", 1)
    assert config["family"] == "deepseek_v32" and config["mode"] == "serve"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(REDUCED) and config["reduced_from"] == REDUCED
    assert entry["source"] == config["source"]
    sm = config["engine"]["state_manager"]
    assert (sm["max_context"], sm["max_ragged_batch_size"], sm["max_ragged_sequence_count"],
            config["serving"]["decode_chunk"]) == (8192, 256, 8, 8)
    p = traffic["params"]
    assert traffic["kind"] == "closed_clients" and (p["clients"], p["requests_per_client"]) == (8, 16)
    assert p["prompt"] == {"dist": "uniform", "min": 4096, "max": 6144}
    assert p["output"] == {"dist": "lognormal", "median": 768, "sigma": 0.25, "min": 512,
                           "max": 1280}
    assert p["temperature"] == 0.0
    assert p["prompt"]["max"] + p["output"]["max"] == 7424 <= sm["max_context"]
    assert (traffic["lead_in_s"], traffic["drain_s"], traffic["trace_start_s"],
            traffic["trace_length_s"]) == (8.0, 6.0, 10.0, 4.0)
    # every prompt is past index_topk: the selection is inside every decode step and the check
    assert p["prompt"]["min"] > config["index_topk"]
    assert config["reference_pad_to"] >= p["prompt"]["max"] + 8
    share = config["deployment_share"]
    assert (share["chips_sharing_a_layer"], share["routed_over"], share["experts_held"],
            share["vocabulary_slices"]) == (16, 256, 16, 8)
    assert share["experts_held"] == config["n_routed_experts"]
    assert 0 <= share["expert_rank"] < 16
    assert {"reference_code", "init", "hadamard", "num_nextn_predict_layers", "torch_dtype"} <= \
        set(config["assumed"])


def test_every_number_of_the_catalog_row_is_in_the_file_or_in_reduced(resolved):
    config = resolved[2]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V3.2")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert value == REDUCED[key] and config[key] != value
        else:
            assert config[key] == value, key
    # no width is cut
    assert not {k for k in REDUCED if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["vocab_size"]) == (5, 1, 16, 16160)
    assert config["vocab_size"] * 8 == REDUCED["vocab_size"]


def test_the_weights_are_half_the_chip_and_the_pool_brings_it_to_seventy_percent(resolved):
    c = resolved[2]
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    attention = (h * c["q_lora_rank"] + c["q_lora_rank"] * heads * qk
                 + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
                 + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"] + c["v_head_dim"])
                 + heads * c["v_head_dim"] * h)
    indexer = (c["q_lora_rank"] * c["index_n_heads"] * c["index_head_dim"]
               + h * c["index_head_dim"] + h * c["index_n_heads"])
    assert (attention + indexer) / 1e6 == pytest.approx(201.1, abs=0.1)
    expert = 3 * h * c["moe_intermediate_size"]
    sparse = (c["n_routed_experts"] + c["n_shared_experts"]) * expert + 256 * h
    dense_layers = c["first_k_dense_replace"]
    weights = 2 * (c["num_hidden_layers"] * (attention + indexer)
                   + dense_layers * 3 * h * c["intermediate_size"]
                   + (c["num_hidden_layers"] - dense_layers) * sparse + 2 * c["vocab_size"] * h)
    hbm = opcount.PEAKS["TPU v5 lite"]["hbm_bytes"]
    assert weights / 2**30 == pytest.approx(8.63, abs=0.02) and weights / hbm > 0.5
    # a block id: 128 tokens x 5 layers x (the latent row in 640 lanes + the index key)
    block = c["engine"]["kv_block_size"] * c["num_hidden_layers"] * (640 + 128) * 2
    assert block == 960 * 1024
    pool = c["engine"]["state_manager"]["memory_config"]["size"] * block
    assert 0.68 <= (weights + pool) / hbm <= 0.72


def test_the_guessed_buckets_fall_into_one_block_table_bucket(resolved):
    from benchmark.runners import serve
    _, _, config, traffic = resolved
    forward, loops = serve.reachable_programs(config["engine"], config["serving"],
                                              traffic["params"])
    # the harness guesses 4..64; index_topk / block = 16 and the whole table,
    # max_context / block = 64, is four times that: the program has the one bucket
    # (every decode row of this traffic is past 4096 tokens, so every step of the
    # window would be in it whatever the floor)
    guessed = sorted({mb for _, _, mb in forward})
    assert guessed == [4, 8, 16, 32, 64]
    engine = config["engine"]
    assert config["index_topk"] // engine["kv_block_size"] == 16
    from benchmark.readers import trace_latent_paged_roofline as roofline
    least = roofline.table_floor(engine["kv_block_size"], config["index_topk"],
                                 engine["state_manager"]["max_context"])
    assert least == engine["state_manager"]["max_context"] // engine["kv_block_size"] == 64
    assert "min_table_bucket" not in engine
    assert traffic["params"]["prompt"]["min"] > 32 * engine["kv_block_size"] - 1
    assert len({(t, s, max(mb, least)) for t, s, mb in forward}) == 6
    assert len({max(k[0][2], least) for k in loops}) == 1
    rng = np.random.default_rng(0)
    from benchmark.traffic_kinds import _draw
    lengths = sorted(_draw.lengths(traffic["params"]["prompt"], serve.CHECK_PROMPTS, rng))
    assert all(length > config["index_topk"] for length in lengths)


def test_its_metrics_are_listed_and_the_ones_that_price_kv_heads_are_not(resolved):
    bench = resolved[0]
    traced = {m["name"] for m in harness.metrics_for(bench, CELL, True)}
    assert set(NEW_METRICS) <= traced
    assert not {n for n in traced if n.startswith(("paged_attn_", "paged_window_", "paged_mixed_",
                                                   "paged_prefill_"))}
    assert {"moe_busy_pct", "attn_busy_pct", "step_decode_p50_ms", "step_any_p50_ms",
            "device_idle_pct", "kv_blocks_peak_pct", "compiles_in_window",
            "serve_generated_tokens_per_s", "dense_ffn_busy_pct", "moe_shared_busy_pct",
            "unscoped_busy_pct"} | set(FOLDED.values()) <= traced
    # ``moe_grouped_roofline`` prices a step by every assignment the router made: 16 x what
    # lands on this chip's experts; the cell reports ``moe_share_grouped_roofline`` instead
    assert not ({"moe_grouped_roofline", "moe_banks_per_assignment"} | set(FOLDED)) & traced
    assert {m["name"] for m in harness.metrics_for(bench, CELL, False)} == \
        {"tpot_p50_ms", "setup_s"}
    layers = {m["layer"] for m in bench["per_layer"]}
    for name in NEW_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == "tpot_p50_ms"
        assert entry["layer"] in layers
        if name.endswith("_roofline"):
            assert entry["unit"] == "%" and entry["better"] == "higher"
        with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{name}.json")) as f:
            assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "readers",
                                               f"{json.load(f)['reader']}.py"))


def test_the_roofline_count_prices_the_selected_rows_and_the_scored_keys():
    # one decode query over 5000 keys: 2048 attended, every key scored
    flops, nbytes = roofline.latent_attention([[5000]], 128, 576, 512, 2048)
    assert flops == 2 * 128 * (576 + 512) * 2048
    assert nbytes == 2048 * 1152 + 128 * (576 + 512) * 2
    flops, nbytes = roofline.index_scores([[5000]], 64, 128)
    assert flops == 2 * 64 * 128 * 5000
    assert nbytes == 5000 * 256 + 64 * 128 * 2 + 64 * 4 + 4 * 5000
    # a short context is attended whole; a chunk's rows are read once a sequence
    assert roofline.latent_attention([[10, 11]], 128, 576, 512, 2048)[0] == \
        2 * 128 * 1088 * 21
    assert roofline.latent_attention([[10, 11]], 128, 576, 512, 2048)[1] == \
        11 * 1152 + 2 * 128 * 1088 * 2
    # the bucket rule: tables to index_topk keys are one bucket and select everything
    assert roofline.table_floor(128, 2048, 163840) == 16
    assert [roofline.table_bucket_keys(n, 128, 16) for n in (1, 2048, 2049, 4097)] == \
        [2048, 2048, 4096, 8192]
    # the cell's whole table is four times that floor: one bucket, every step scores
    assert roofline.table_floor(128, 2048, 8192) == 64
    assert [roofline.table_bucket_keys(n, 128, 64) for n in (1, 4097)] == [8192, 8192]
    # 242 flop a byte: the absorbed form sits at the v5e ridge (197e12 / 819e9 = 240)
    peaks = opcount.PEAKS["TPU v5 lite"]
    assert 2 * 128 * 1088 / 1152 == pytest.approx(241.8, abs=0.1)
    assert peaks["bf16_flops_per_s"] / peaks["hbm_bytes_per_s"] == pytest.approx(240.5, abs=0.1)


def test_a_program_without_the_family_exits_at_once_with_a_message():
    code = ("import sys\n"
            "sys.modules['deepspeed_tpu.models.deepseek_v32'] = None\n"
            "from benchmark import harness\n"
            f"harness._load_module({tiny.REPO!r}, 'models', 'deepseek_v32')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=tiny.REPO, capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=tiny.REPO, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0
    assert "cannot serve a model with a latent KV cache" in done.stderr
    assert "Nothing was measured" in done.stderr


def test_the_family_builds_the_programs_config_from_the_file(resolved):
    family = harness._load_module(tiny.REPO, "models", "deepseek_v32")
    config = resolved[2]
    cfg = family.program_config(config)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_rank) == \
        (256, 16, config["deployment_share"]["expert_rank"])
    assert cfg.first_expert_held == 16 * cfg.expert_rank
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace, cfg.vocab_size) == (5, 1, 16160)
    assert (cfg.n_group, cfg.topk_group, cfg.index_topk, cfg.kv_lora_rank) == (8, 4, 2048, 512)
    assert cfg.softmax_scale == pytest.approx(192**-0.5 * 1.3689**2, rel=1e-4)


# -------------------------------------------------------------- rehearsal ---
TINY = {
    "family": "deepseek_v32", "mode": "serve", "torch_dtype": "float32",
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 64, "index_head_dim": 16, "index_n_heads": 8, "index_topk": 32,
    "intermediate_size": 96, "kv_lora_rank": 32, "max_position_embeddings": 512,
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_group": 4, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 2, "num_key_value_heads": 4,
    "num_nextn_predict_layers": 1, "q_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 64, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 2, "topk_method": "noaux_tc", "v_head_dim": 16,
    "vocab_size": 256,
    "deployment_share": {"chips_sharing_a_layer": 4, "routed_over": 16, "experts_held": 4,
                         "expert_rank": 2},
    "reference_pad_to": 96,
    "engine": {"kv_block_size": 8,  # max_context / block = 16 entries: one bucket, as the real cell
               "state_manager": {"memory_config": {"mode": "allocate", "size": 256},
                                 "max_context": 128, "max_ragged_batch_size": 32,
                                 "max_ragged_sequence_count": 8},
               "expert_parallel": {"capacity_factor": 4.0}},
    "serving": {"decode_chunk": 4, "queue_capacity": 1024},
}


def _tiny_root(tmp_path):
    """A throw-away benchmark root with the cell ``tiny-deepseek-reason``."""
    root = tiny.make_root(tmp_path / "root")
    tiny.write_json(os.path.join(root, "benchmark", "configs", "tiny-deepseek.json"), TINY)
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", "longctx-reason-closed.json")) as f:
        traffic = json.load(f)
    traffic.update(tiny._TIMES)
    traffic["params"].update(clients=3, requests_per_client=40,
                             prompt={"dist": "uniform", "min": 20, "max": 72},
                             output={"dist": "lognormal", "median": 16, "sigma": 0.25, "min": 8,
                                     "max": 24})
    tiny.write_json(os.path.join(root, "benchmark", "traffic", "tiny-longctx.json"), traffic)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-deepseek", "source": "none: a test preset",
                             "file": "benchmark/configs/tiny-deepseek.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "tiny-deepseek-reason", "config": "tiny-deepseek",
                               "traffic": "tiny-longctx", "chips": 1, "why": "CPU rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "tiny-mixtral-closed" in m["workloads"]:
            m["workloads"].append("tiny-deepseek-reason")
    tiny.write_json(path, bench)
    return root


def test_the_cell_rehearses_at_a_tiny_preset(tmp_path):
    """``index_topk`` 32 over 8-token blocks, prompts on both sides of it, a
    share of 4 of 16 experts in 4 groups, through the harness's test-only
    entry: the family, the traffic, the new metric files and readers all load,
    and the check holds prefill in chunks, the selection and decode through
    ``put`` and ``decode_loop`` to the float32 reference."""
    root = _tiny_root(tmp_path)
    out = io.StringIO()
    assert harness.run_cell(root, "tiny-deepseek-reason", 2**31 + 40, 1.5, 1, rehearsal=True,
                            out=out) == 0  # traced: what an untraced run does, and the readers
    text = out.getvalue()
    line = tiny.last_line(text)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert text.count("-> ok") >= 4 and "WRONG" not in text
    assert line["metrics"]["cpu_rehearsal.compiles_in_window"]["value"] == 0
    # every metric this cell brings reads only beside a chip's trace
    for name in NEW_METRICS:
        assert f"metric {name}: nothing to read, left out" in text


def test_the_controls_run_through_the_harness_comparison_at_a_tiny_preset(tmp_path, capsys):
    """``benchmark/tools/controls_latent.py`` on the tiny cell, float32: the weights as the
    seed makes them read ``correct`` and every control reads false but the latent row in
    fp8, which 32 keys a query average away at this size (what they read in bfloat16 at
    the real sizes is the chip's to say: PERF.md section 6, PR 40); the exit code says
    whether every control was caught."""
    from benchmark.tools import controls_latent
    root = _tiny_root(tmp_path)
    rc = controls_latent.main(["--workload", "tiny-deepseek-reason", "--seed", str(2**31 + 40),
                               "--rehearsal", "1", "--root", root])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    read = {name: c["correct"] for name, c in result["controls"].items()}
    assert list(read) == ["baseline"] + list(controls_latent.CONTROLS)
    assert read["baseline"] is True
    seen = {name for name, correct in read.items() if not correct}
    assert seen >= set(controls_latent.CONTROLS) - {"latent_fp8"}
    assert rc == (0 if "latent_fp8" in seen else 4)
    # a control restores what it patched
    from deepspeed_tpu.inference.v2.model_implementations import deepseek_v32_v2 as served
    from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _rotate_half
    assert served._rotate_half is _rotate_half


def test_the_stated_row_limits_pass_the_seeds_weights_and_fail_them_through_fp8(tmp_path, capsys):
    """The tiny cell under the REAL configuration's ``check`` group (PR 61: the
    worst tight row at 2^-5.0, a toss-up row at 2^-3.84, the median row at
    2^-5.5): the weights as the seed makes them are inside all three and the
    result's numbers say so; every matrix through fp8, the nearest precision
    below the configuration's, moves every row, and the MEDIAN row is over its
    limit (what the chip reads: PERF.md section 2)."""
    from benchmark.tools import controls_latent
    root = _tiny_root(tmp_path)
    _, _, real, _ = harness.resolve(tiny.REPO, CELL)
    path = os.path.join(root, "benchmark", "configs", "tiny-deepseek.json")
    tiny.write_json(path, dict(TINY, check=real["check"]))
    rc = controls_latent.main(["--workload", "tiny-deepseek-reason", "--seed", str(2**31 + 41),
                               "--controls", "baseline,fp8_weights", "--rehearsal", "1",
                               "--root", root])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["tolerance_log2"] == real["check"]["tight_row_log2"]
    honest, spoilt = result["controls"]["baseline"], result["controls"]["fp8_weights"]
    assert honest["correct"] is True and spoilt["correct"] is False
    assert all(v <= limit for v, limit in honest["compared_log2"].values())
    assert honest["compared_log2"]["median_row"][1] == real["check"]["median_row_log2"]
    value, limit = spoilt["compared_log2"]["median_row"]
    assert value > limit


def test_the_tool_that_reads_the_rows_a_limit_is_set_from_runs_at_a_tiny_preset(tmp_path, capsys):
    """``benchmark/tools/check_rows.py``: two seeds in one process, the second
    through fp8 too; a line a seed with the worst tight, worst loose and median
    row, and a file a seed with every row."""
    from benchmark.tools import check_rows
    root = _tiny_root(tmp_path)
    seeds = [2**31 + 42, 7]
    rc = check_rows.main(["--workload", "tiny-deepseek-reason", "--seeds", ",".join(map(str, seeds)),
                          "--control-seeds", "7", "--rehearsal", "1", "--root", root,
                          "--out", "rows"])
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and [line["seed"] for line in lines] == seeds
    assert "fp8_weights" not in lines[0] and lines[0]["seed_weights"]["rows"] == 32
    honest, spoilt = lines[1]["seed_weights"], lines[1]["fp8_weights"]
    assert spoilt["median_log2"] > honest["median_log2"] + 3  # float32 against float8
    with open(os.path.join(root, "rows", "tiny-deepseek-reason.7.json")) as f:
        record = json.load(f)
    assert len(record["fp8_weights"]["error"]) == len(record["seed_weights"]["loose"]) == 32
    assert record["limits_log2"]["median"] is None  # the tiny preset states no limits


def test_the_new_readers_find_nothing_on_a_program_without_the_family_and_do_not_raise(resolved):
    """On the parent the trace has no latent kernel and the spans no local count:
    each reader returns None (the metric is left out of the line), whatever the
    configuration it is handed."""
    from types import SimpleNamespace
    from benchmark.readers import trace_share_expert_roofline as share
    config = resolved[2]
    trace = SimpleNamespace(devices={0: [(0, 1000, "fusion.1"), (1000, 3000, "grouped_matmul.3")]},
                            host=[])
    slice_ = SimpleNamespace(began=0.0, ended=4.0, sync_clock=None)
    spans = [{"name": "decode_loop", "cat": "inference", "ts_us": 10, "dur_us": 5,
              "args": {"steps": 8, "moe_path": "grouped", "moe_banks": 40,
                       "moe_assignments": 2048}}]
    run = {"trace_slice": slice_, "spans": spans, "t0": 0.0, "seconds": 45.0}
    env = {"trace": trace, "peaks": opcount.PEAKS["TPU v5 lite"], "config": config,
           "log": lambda message: None}
    for kind, pattern in (("attention", "latent_paged_attention_token"),
                          ("attention", "latent_paged_attention_tiled"),
                          ("index", "latent_index_scores")):
        assert roofline.read(run, {"pattern": pattern, "kind": kind}, env) is None
    params = {"pattern": "^%?grouped_matmul", "moe_path": "grouped"}
    assert share.read(run, params, env) is None  # no span carries moe_assignments_local
    assert share.read(run, params, dict(env, config={"num_hidden_layers": 5})) is None
    # a configuration of another family has no latent row to price
    mellum = harness.resolve(tiny.REPO, "mellum2-repoctx-closed")[2]
    assert roofline.read(run, {"pattern": "paged_attention", "kind": "attention"},
                         dict(env, config=mellum)) is None
