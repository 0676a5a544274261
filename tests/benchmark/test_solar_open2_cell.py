"""The Solar-Open2 cell's files (PR 54): the configuration against the catalog
row and its own arithmetic (the bytes re-reckoned from the file are the tree
``init_params`` makes), the traffic and the metrics as the issue gives them,
the family module refuses a program without ``SolarOpen2Config`` at once, the
count the delta rule's rooflines are held to, and the cell and its controls
rehearsed at a tiny preset. Every entry is found BY NAME: nothing here pins a
position or a count of ``BENCHMARK.json``'s lists."""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness, opcount
from benchmark.readers import trace_kda_roofline
from tests.benchmark import tiny

CELL, CONFIG = "solar-open2-longctx-reason-closed", "solar-open2-250b-serve-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608}
NEW_METRICS = ("kda_busy_pct", "kda_step_roofline", "kda_chunk_roofline",
               "kda_rows_in_place_share", "ssm_state_slots_peak_pct",
               "moe_share_grouped_roofline")
# the names PR 54 had to give two accepted readers a second time, and the accepted names that
# list this cell since PR 61: one name a reading. ``moe_share_grouped_roofline``'s reader
# itself now hands on only a configuration it can read, as the wrapper under the second name did
FOLDED = {"kda_state_slots_peak_pct": "ssm_state_slots_peak_pct",
          "moe_swiglu_held_grouped_roofline": "moe_share_grouped_roofline"}
# accepted metrics that would MISREAD this cell and are not its: ``unscoped_busy_pct`` and
# ``unscoped_hybrid_busy_pct`` name no ``kda`` scope and would count the delta-rule layers as
# the compiler's own; ``ssm_rows_per_step``, ``ssm_*_roofline`` and ``moe_relu2_grouped_roofline``
# read a ``hybrid_override_pattern`` and Mamba-2 widths the file has not; ``ssm_busy_pct`` and
# ``ssm_in_place_row_share`` read ``ssm`` scopes and counters this family does not write;
# ``paged_attn_roofline`` prices attention in all four layers (one has it);
# ``paged_mixed_*_roofline`` a window layer group; ``moe_grouped_roofline`` and
# ``moe_banks_per_assignment`` every assignment the router made, eight times what lands here;
# ``dense_ffn_busy_pct`` an ``mlp`` scope no layer has
NOT_ITS = {"unscoped_busy_pct", "unscoped_hybrid_busy_pct", "ssm_rows_per_step", "ssm_busy_pct",
           "ssm_scan_roofline", "ssm_step_roofline", "ssm_in_place_row_share",
           "moe_relu2_grouped_roofline", "paged_attn_roofline", "paged_mixed_token_roofline",
           "paged_mixed_tiled_roofline", "moe_grouped_roofline", "moe_banks_per_assignment",
           "dense_ffn_busy_pct"}
GIB = 2**30


@pytest.fixture(scope="module")
def resolved():
    return harness.resolve(tiny.REPO, CELL)


def test_the_cell_is_the_one_the_issue_names(resolved):
    bench, cell, config, traffic = resolved
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longctx-reason-closed-48", 1) and len(cell["why"]) <= 200
    assert config["family"] == "solar_open2" and config["mode"] == "serve"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(REDUCED) and config["reduced_from"] == REDUCED
    assert entry["source"] == config["source"] and entry["file"].endswith(f"{CONFIG}.json")
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] == [CELL]
    engine = config["engine"]
    sm = engine["state_manager"]
    assert (engine["kv_block_size"], sm["max_context"], sm["max_ragged_batch_size"],
            sm["max_ragged_sequence_count"], sm["max_tracked_sequences"],
            sm["memory_config"]["size"], config["serving"]["decode_chunk"]) == \
        (128, 8192, 256, 8, 128, 4096, 8)
    p = traffic["params"]
    assert traffic["kind"] == "closed_clients" and (p["clients"], p["requests_per_client"]) == (8, 48)
    assert p["prompt"] == {"dist": "uniform", "min": 4096, "max": 6144}
    assert p["output"] == {"dist": "lognormal", "median": 768, "sigma": 0.25, "min": 512,
                           "max": 1280} and p["temperature"] == 0.0
    assert (traffic["lead_in_s"], traffic["drain_s"], traffic["trace_start_s"],
            traffic["trace_length_s"]) == (8.0, 6.0, 10.0, 4.0)
    assert p["prompt"]["max"] + p["output"]["max"] == 7424 <= sm["max_context"]
    assert config["reference_pad_to"] >= p["prompt"]["max"] + 8
    # DeepSeek-V3.2's cell is sent the same lengths on the same clock, request for request
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", "longctx-reason-closed.json")) as f:
        theirs = json.load(f)
    assert {k: v for k, v in theirs["params"].items() if k != "requests_per_client"} == \
        {k: v for k, v in p.items() if k != "requests_per_client"}
    assert all(theirs[k] == traffic[k] for k in ("kind", "lead_in_s", "drain_s", "trace_start_s",
                                                 "trace_length_s"))
    share = config["deployment_share"]
    assert (share["chips_sharing_a_layer"], share["routed_over"], share["experts_held"],
            share["expert_rank"], share["vocabulary_slices"], share["vocabulary_slice"]) == \
        (8, 320, 40, 0, 8, 0)
    assert share["experts_held"] == config["n_routed_experts"]
    assert {"modelling_code", "gqa", "init", "torch_dtype", "unused_keys"} <= set(config["assumed"])
    assert "2510.26692" in config["assumed"]["modelling_code"]
    assert "FLOAT32" in config["assumed"]["torch_dtype"]
    assert "WHAT THE CUT DISTORTS" in config["deployment"]


def test_every_number_of_the_catalog_row_is_in_the_file_or_in_reduced(resolved):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog beside the model-configs guide is not here")
    config = resolved[2]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == config["source"])
    assert row["name"] == "Solar-Open2-250B" and len(row["config"]) >= 27
    for key, value in row["config"].items():
        if key in REDUCED:
            assert value == REDUCED[key] and config[key] != value
        else:
            assert config[key] == value, key  # the nested linear_attn_config whole
    # gqa_layers is kept whole; the four layers served are one period, the published 1 : 3
    served = [i in config["gqa_layers"] for i in range(config["num_hidden_layers"])]
    assert served == [True, False, False, False] and len(config["gqa_layers"]) == 12


def test_the_bytes_re_reckoned_from_the_file_are_the_tree_init_params_makes(resolved):
    """The issue's arithmetic, from the file's numbers alone, against the tree
    the program makes for the file (``jax.eval_shape``: nothing is allocated)."""
    import jax
    c = resolved[2]
    M, V, F = c["hidden_size"], c["vocab_size"], c["moe_intermediate_size"]
    lin = c["linear_attn_config"]
    W, D, K = lin["num_heads"] * lin["head_dim"], lin["head_dim"], lin["short_conv_kernel_size"]
    E = c["reduced_from"]["n_routed_experts"]
    expert = 3 * M * F
    moe = c["n_routed_experts"] * expert + c["n_shared_experts"] * expert + M * E
    gqa = 3 * M * c["num_attention_heads"] * c["head_dim"] \
        + 2 * M * c["num_key_value_heads"] * c["head_dim"]
    kda = 4 * M * W + 2 * (M * D + D * W) + M * lin["num_heads"] + 3 * W * K
    small = W + lin["num_heads"] + D  # dt_bias, A_log, o_norm
    assert [round(n / 1e6, 2) for n in (expert, moe, gqa, kda)] == [15.73, 646.18, 109.05, 137.72]
    float32 = M * E + E + 2 * M  # a layer's router, selection bias and two norms
    gqa_layer = (moe + E + 2 * M + gqa, 2 * (moe + gqa - M * E) + 4 * float32)
    kda_layer = (moe + E + 2 * M + kda + small,
                 2 * (moe + kda - M * E - 3 * W * K) + 4 * (float32 + 3 * W * K + small))
    ends = (2 * V * M + M, 2 * 2 * V * M + 4 * M)
    total = [g + 3 * k + e for g, k, e in zip(gqa_layer, kda_layer, ends)]
    assert [round(n / 1e6, 2) for n in (gqa_layer[0], kda_layer[0], ends[0], total[0])] == \
        [755.25, 783.93, 201.33, 3308.35]
    family = harness._load_module(tiny.REPO, "models", "solar_open2")
    cfg = family.program_config(c)
    from deepspeed_tpu.models import solar_open2
    tree = jax.eval_shape(lambda: solar_open2.init_params(cfg, param_dtype=cfg.dtype)[1])
    leaves = jax.tree.leaves(tree)
    assert [sum(int(np.prod(x.shape)) for x in leaves),
            sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)] == total
    assert total[1] == 6627908864 and round(total[1] / GIB, 2) == 6.17
    # the pools beside them
    sm = c["engine"]["state_manager"]
    slot = 3 * (4 * lin["num_heads"] * D * D + 2 * (K - 1) * 3 * W)
    block = c["engine"]["kv_block_size"] * 2 * c["num_key_value_heads"] * c["head_dim"] * 2
    assert block == 512 * 1024 and round(slot / 2**20, 2) == 12.42
    pools = sm["max_tracked_sequences"] * slot + sm["memory_config"]["size"] * block
    assert round(sm["max_tracked_sequences"] * slot / GIB, 2) == 1.55
    assert 0.60 < (total[1] + pools) / (16 * GIB) < 0.62
    for said in ("3308.35 M", "6,627,908,864 bytes", "6.17 GiB", "12.42 MiB", "1.55 GiB",
                 "60.8 %"):
        assert said in c["deployment"], said


def test_every_engine_key_says_why(resolved):
    config = resolved[2]
    engine = config["engine"]
    keys = {"kv_block_size"} | set(engine["state_manager"]) | set(engine["expert_parallel"])
    assert keys | {"correct"} == set(config["engine_why"])
    assert set(config["serving"]) == set(config["serving_why"])
    assert all(len(why) > 40 for why in config["engine_why"].values())


def test_its_metrics_are_listed_by_name(resolved):
    bench = resolved[0]
    traced = {m["name"] for m in harness.metrics_for(bench, CELL, True)}
    assert set(NEW_METRICS) <= traced and len(NEW_METRICS) <= 6
    assert {"moe_busy_pct", "moe_route_busy_pct", "moe_shared_busy_pct", "attn_busy_pct",
            "attn_gate_norm_busy_pct", "paged_attn_busy_pct", "paged_prefill_busy_pct",
            "device_idle_pct", "kv_blocks_peak_pct", "compiles_in_window",
            "serve_generated_tokens_per_s", "step_device_any_p50_ms", "step_decode_p50_ms",
            "sched_seqs_per_step", "idle_in_engine_pct", "idle_waiting_pct",
            "idle_in_host_stall_pct", "gc_pause_ms_per_s"} <= traced
    assert not NOT_ITS & traced
    assert set(FOLDED.values()) <= traced and not set(FOLDED) & traced
    assert {m["name"] for m in harness.metrics_for(bench, CELL, False)} == \
        {"tpot_p50_ms", "setup_s"}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW_METRICS}
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        perf = f.read()
    for name in NEW_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == "tpot_p50_ms"
        assert entry["layer"] in layers and f"`{name}`" in perf
        if name.endswith("_roofline"):
            assert (entry["unit"], entry["better"], entry["source"]) == \
                ("%", "higher", "device_trace")
        with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{name}.json")) as f:
            assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "readers",
                                               f"{json.load(f)['reader']}.py"))


def test_the_roofline_count_prices_a_state_a_visit_and_the_recurrence_a_row():
    # one decode row through one layer: its 4 MiB state read and written, 7 flop an element
    flops, nbytes = trace_kda_roofline.kda_work(1, 1, 64, 128, 128)
    assert flops == 7 * 64 * 128 * 128
    assert nbytes == 2 * 4 * 64 * 128 * 128 + 4 * (64 * 4 * 128 + 64 + 64 * 128)
    # a 64-row visit of ONE sequence reads and writes the state once, not 64 times
    flops, nbytes = trace_kda_roofline.kda_work(64, 1, 64, 128, 128)
    assert flops == 64 * 7 * 64 * 128 * 128
    assert nbytes == 2 * 4 * 64 * 128 * 128 + 64 * 4 * (64 * 4 * 128 + 64 + 64 * 128)
    # a decode row is memory-bound on a v5e: 8 MiB at 819 GB/s, 10.4 us a row a layer
    peaks = opcount.PEAKS["TPU v5 lite"]
    least, bound = opcount.roofline_seconds(*trace_kda_roofline.kda_work(1, 1, 64, 128, 128), peaks)
    assert bound == "memory" and least == pytest.approx(10.44e-6, rel=0.01)
    # a program without the spans, a configuration without the mixer: nothing to read
    env = {"trace": None, "peaks": peaks, "config": {"linear_attn_config": None}}
    assert trace_kda_roofline.read({"trace_slice": None}, {"kind": "step"}, env) is None
    # the held banks' reader hands on only a configuration it can read: one that holds a share,
    # and counts its expert layers by depth and not by a pattern of blocks
    from benchmark.readers import trace_share_expert_roofline as held
    for config in ({"deployment_share": {"experts_held": 2}, "hybrid_override_pattern": "ME"},
                   {"deployment_share": {}}, {}):
        assert held.read({}, {}, {"config": config}) is None


def test_a_program_without_the_family_exits_at_once_with_a_message():
    code = ("import sys\n"
            "sys.modules['deepspeed_tpu.models.solar_open2'] = None\n"
            "from benchmark import harness\n"
            f"harness._load_module({tiny.REPO!r}, 'models', 'solar_open2')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=tiny.REPO, capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=tiny.REPO, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0
    assert "cannot serve a model with gated delta-rule layers" in done.stderr
    assert "Nothing was measured" in done.stderr


def test_the_family_builds_the_programs_config_from_the_file(resolved):
    family = harness._load_module(tiny.REPO, "models", "solar_open2")
    cfg = family.program_config(resolved[2])
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_rank, cfg.first_expert_held) == \
        (320, 40, 0, 0)
    assert (cfg.num_hidden_layers, cfg.gqa_here, cfg.kda_here, cfg.vocab_size) == \
        (4, (0, ), (1, 2, 3), 24576)
    assert (cfg.linear_num_heads, cfg.linear_head_dim, cfg.short_conv_kernel_size,
            cfg.kda_width, cfg.beta_scale) == (64, 128, 4, 8192, 2.0)
    assert (cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.moe_intermediate_size) == \
        (8, 1, 1280)


# -------------------------------------------------------------- rehearsal ---
TINY = {
    "family": "solar_open2", "mode": "serve", "torch_dtype": "float32",
    "gqa_layers": [0, 4], "gqa_interval": 3, "num_hidden_layers": 3, "hidden_size": 64,
    "vocab_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 2,
                           "num_kv_heads": None},
    "moe_intermediate_size": 32, "n_routed_experts": 4, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "first_k_dense_replace": 0, "use_rope": False, "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512,
    "deployment_share": {"chips_sharing_a_layer": 4, "routed_over": 16, "experts_held": 4,
                         "expert_rank": 1},
    "reference_pad_to": 96,
    "engine": {"kv_block_size": 8,
               "state_manager": {"memory_config": {"mode": "allocate", "size": 256},
                                 "max_context": 128, "max_ragged_batch_size": 32,
                                 "max_ragged_sequence_count": 8, "max_tracked_sequences": 12},
               "expert_parallel": {"capacity_factor": 4.0}},
    "serving": {"decode_chunk": 4, "queue_capacity": 1024},
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A throw-away benchmark root with the cell ``tiny-solar-reason``."""
    root = tiny.make_root(tmp_path_factory.mktemp("solar") / "root")
    tiny.write_json(os.path.join(root, "benchmark", "configs", "tiny-solar.json"), TINY)
    with open(os.path.join(tiny.REPO, "benchmark", "traffic",
                           "longctx-reason-closed-48.json")) as f:
        traffic = json.load(f)
    traffic.update(tiny._TIMES)
    traffic["params"].update(clients=3, requests_per_client=40,
                             prompt={"dist": "uniform", "min": 20, "max": 72},
                             output={"dist": "lognormal", "median": 16, "sigma": 0.25, "min": 8,
                                     "max": 24})
    tiny.write_json(os.path.join(root, "benchmark", "traffic", "tiny-reason.json"), traffic)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-solar", "source": "none: a test preset",
                             "file": "benchmark/configs/tiny-solar.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "tiny-solar-reason", "config": "tiny-solar",
                               "traffic": "tiny-reason", "chips": 1, "why": "CPU rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "tiny-mixtral-closed" in m["workloads"] \
                and m["name"] not in NOT_ITS:
            m["workloads"].append("tiny-solar-reason")
    tiny.write_json(path, bench)
    return root


def test_the_cell_rehearses_at_a_tiny_preset(tiny_root):
    """One GQA and two delta-rule layers, 16-row visits under a 32-token budget
    (the check's four prompts prefilled together in shares of 8: every ``put``
    is four segments), 4 of 16 experts held, through the harness's test-only
    entry: the family, the traffic, the new metric files and readers all load,
    and the check holds prefill in chunks with the state carried, ``put`` and
    ``decode_loop`` to the float32 reference."""
    out = io.StringIO()
    assert harness.run_cell(tiny_root, "tiny-solar-reason", 2**31 + 54, 1.5, 1, rehearsal=True,
                            out=out) == 0  # traced: what an untraced run does, and the readers
    text = out.getvalue()
    line = tiny.last_line(text)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert text.count("-> ok") >= 4 and "WRONG" not in text
    assert line["metrics"]["cpu_rehearsal.compiles_in_window"]["value"] == 0


def test_the_controls_run_through_the_harness_comparison_at_a_tiny_preset(tiny_root, capsys):
    """``benchmark/tools/controls_kda.py`` on the tiny cell, float32: a
    convolution's tail not carried from one ``put`` to the next reads false
    (the engine as built reads ``correct`` in the rehearsal above; the state's
    carry, beta's factor and the decay are held against the reference by
    ``tests/unit/inference/v2/test_solar_open2.py``; all five controls ran so
    by hand and on the chip, PR 54); and a control restores what it patched."""
    from benchmark.tools import controls_kda
    rc = controls_kda.main(["--workload", "tiny-solar-reason", "--seed", str(2**31 + 54),
                            "--rehearsal", "1", "--root", tiny_root, "--controls",
                            "no_conv_carry"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    read = {name: c["correct"] for name, c in result["controls"].items()}
    assert read == {"no_conv_carry": False} and rc == 0
    from deepspeed_tpu.inference.v2.modules import kda, ssm
    from deepspeed_tpu.models.solar_open2 import SolarOpen2Config
    assert kda.scan_in_place.__module__ == kda.decay.__module__ == kda.__name__
    assert ssm.conv_ragged.__module__ == ssm.__name__
    assert SolarOpen2Config.tiny().beta_scale == 2.0
    from benchmark.tools import controls_ssm
    assert controls_ssm.spoilt.__module__ == controls_ssm.__name__
    assert "state_bf16" in controls_ssm.CONTROLS
