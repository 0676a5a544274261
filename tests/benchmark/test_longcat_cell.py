"""The LongCat-Flash cell's files (PR 62): the configuration against the
catalog row and its own arithmetic (the bytes re-reckoned from the file are
the tree ``init_params`` makes), the traffic and the metrics as the issue gives
them, the family module refuses a program without ``LongcatFlashConfig`` at
once, and the cell and its controls rehearsed at a tiny preset. Every entry is
found BY NAME: nothing here pins a position, a count or a whole ``workloads``
list of ``BENCHMARK.json``."""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness
from tests.benchmark import tiny

CELL, CONFIG = "longcat-flash-omni-agent32-closed", "longcat-flash-omni-serve-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
NEW_METRICS = ("moe_zero_assignment_share", "moe_zero_busy_pct")
# accepted metrics that would MISREAD this cell and are not its: ``paged_latent_*`` /
# ``paged_index_roofline`` bound a context by ``index_topk``; ``moe_grouped_roofline`` /
# ``moe_banks_per_assignment`` price every choice the router made, 48 x what lands here;
# ``paged_*`` a K/V kernel no layer runs; ``hbm_peak_pct`` moves a metric the cell does not
# report; the state-space and delta-rule families' own. And three that read NOTHING in this
# cell's slice (my chip run, PR 62): nine steps in ten carry a prompt chunk, so a 4 s slice
# holds no ``decode_loop`` chunk and no step on the token grid — ``chunk_round_trip_p50_ms``,
# ``idle_in_chunk_run_pct``, ``kl_latent_token_roofline``
NOT_ITS = {"chunk_round_trip_p50_ms", "idle_in_chunk_run_pct", "kl_latent_token_roofline",
           "paged_latent_token_roofline", "paged_latent_tiled_roofline", "paged_index_roofline",
           "index_selected_share", "latent_kernels_busy_pct", "moe_grouped_roofline",
           "moe_banks_per_assignment", "moe_shared_busy_pct", "attn_gate_norm_busy_pct",
           "paged_attn_busy_pct", "paged_prefill_busy_pct", "paged_attn_roofline",
           "hbm_peak_pct", "ssm_busy_pct", "kda_busy_pct", "ssm_state_slots_peak_pct",
           "unscoped_hybrid_busy_pct", "h1_unscoped_busy_pct", "moe_relu2_grouped_roofline"}
GIB = 2**30


@pytest.fixture(scope="module")
def resolved():
    return harness.resolve(tiny.REPO, CELL)


def test_the_cell_is_the_one_the_issue_names(resolved):
    bench, cell, config, traffic = resolved
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "agent32-closed", 1) \
        and len(cell["why"]) <= 200
    assert config["family"] == "longcat_flash" and config["mode"] == "serve"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(REDUCED) and config["reduced_from"] == REDUCED
    assert entry["source"] == config["source"] and entry["file"].endswith(f"{CONFIG}.json")
    assert len(entry["why"]) <= 200
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] == [CELL]
    engine = config["engine"]
    sm = engine["state_manager"]
    assert (engine["kv_block_size"], sm["max_context"], sm["max_ragged_batch_size"],
            sm["max_ragged_sequence_count"], sm["memory_config"]["size"],
            config["serving"]["decode_chunk"]) == (128, 8192, 256, 32, 2048, 8)
    p = traffic["params"]
    assert traffic["kind"] == "closed_clients" and (p["clients"], p["requests_per_client"]) == (32, 16)
    assert p["prompt"] == {"dist": "uniform", "min": 2048, "max": 4096}
    assert p["output"] == {"dist": "lognormal", "median": 512, "sigma": 0.25, "min": 320,
                           "max": 832} and p["temperature"] == 0.0
    assert (traffic["lead_in_s"], traffic["drain_s"], traffic["trace_start_s"],
            traffic["trace_length_s"]) == (8.0, 6.0, 10.0, 4.0)
    assert p["prompt"]["max"] + p["output"]["max"] == 4928 <= sm["max_context"]
    assert config["reference_pad_to"] >= p["prompt"]["max"] + 8
    share = config["deployment_share"]
    assert (share["chips_sharing_a_layer"], share["routed_over"], share["experts_held"],
            share["zero_experts_whole"], share["vocabulary_slices"]) == (32, 512, 16, 256, 8)
    assert share["experts_held"] == config["n_routed_experts"] \
        and 0 <= share["expert_rank"] < 32
    assert {"modelling_code", "init", "init_gains", "torch_dtype", "aliases"} <= \
        set(config["assumed"])
    # the aliases an accepted reader reads a size under stand beside the published key
    for alias, published in (("num_hidden_layers", "num_layers"),
                             ("moe_intermediate_size", "expert_ffn_hidden_size")):
        assert config[alias] == config[published] and published in config["assumed"]["aliases"]
    assert "WHAT THE CUT DISTORTS" in config["deployment"]


def test_every_number_of_the_catalog_row_is_in_the_file_or_in_reduced(resolved):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog beside the model-configs guide is not here")
    config = resolved[2]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == config["source"])
    assert row["name"] == "LongCat-Flash-Omni" and len(row["config"]) >= 22
    for key, value in row["config"].items():
        if key in REDUCED:
            assert value == REDUCED[key] and config[key] != value
        else:
            assert config[key] == value, key
    # the floors: a whole period (one layer) and four layers, 8+ experts, an eighth of the rows
    assert config["num_layers"] >= 4 and config["n_routed_experts"] >= 8 \
        and config["vocab_size"] * 8 >= row["vocab_size"]


def test_the_bytes_re_reckoned_from_the_file_are_the_tree_init_params_makes(resolved):
    """The issue's arithmetic, from the file's numbers alone, against the tree
    the program makes for the file (``jax.eval_shape``: nothing is allocated)."""
    import jax
    c = resolved[2]
    M, V, n = c["hidden_size"], c["vocab_size"], c["num_layers"]
    H, C, Q, N, R, Vd = (c["num_attention_heads"], c["kv_lora_rank"], c["q_lora_rank"],
                         c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"])
    outputs = c["reduced_from"]["n_routed_experts"] + c["zero_expert_num"]
    mla = M * Q + Q * H * (N + R) + M * (C + R) + C * H * (N + Vd) + H * Vd * M
    dense = 3 * M * c["ffn_hidden_size"]
    experts = c["n_routed_experts"] * 3 * M * c["expert_ffn_hidden_size"]
    router = M * outputs
    layer = 2 * mla + 2 * dense + router + experts
    assert [round(x / 1e6, 2) for x in (mla, dense, router, experts, layer)] == \
        [90.57, 226.49, 4.72, 603.98, 1242.82]
    # float32: the router, its bias and every norm's gain; the rest bf16
    norms = 2 * (2 * M + Q + C)
    params = n * (layer + outputs + norms) + 2 * V * M + M
    nbytes = n * (2 * (layer - router) + 4 * (router + outputs + norms)) + 2 * 2 * V * M + 4 * M
    family = harness._load_module(tiny.REPO, "models", "longcat_flash")
    cfg = family.program_config(c)
    from deepspeed_tpu.models import longcat_flash
    tree = jax.eval_shape(lambda: longcat_flash.init_params(cfg, param_dtype=cfg.dtype)[1])
    leaves = jax.tree.leaves(tree)
    assert [sum(int(np.prod(x.shape)) for x in leaves),
            sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)] == [params, nbytes]
    assert round(params / 1e6, 2) == 5172.75 and nbytes == 10383495168 \
        and round(nbytes / GIB, 2) == 9.67
    sm = c["engine"]["state_manager"]
    row = -(-(C + R) // 128) * 128
    block = c["engine"]["kv_block_size"] * 2 * n * row * 2  # TWO latent layers a model layer
    assert (row, block) == (640, 1280 * 1024)
    pool = sm["memory_config"]["size"] * block
    assert round(pool / GIB, 2) == 2.5 and 0.76 < (nbytes + pool) / (16 * GIB) < 0.77
    for said in ("5172.75 M", "10,383,495,168 bytes", "9.67 GiB", "2.50 GiB", "76.1 %"):
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
    assert set(NEW_METRICS) <= traced
    assert {"moe_busy_pct", "moe_route_busy_pct", "attn_busy_pct", "attn_latent_proj_busy_pct",
            "dense_ffn_busy_pct", "unscoped_busy_pct", "device_idle_pct", "kv_blocks_peak_pct",
            "compiles_in_window", "step_device_any_p50_ms", "step_decode_p50_ms",
            "step_any_p50_ms", "chunk_launch_p50_ms", "sched_seqs_per_step",
            "idle_in_engine_pct", "seq_bucket_fill", "unembed_busy_pct",
            "idle_waiting_pct", "idle_in_host_stall_pct", "gc_pause_ms_per_s",
            "moe_local_assignment_share", "moe_banks_per_local_assignment",
            "moe_share_grouped_roofline", "kl_latent_busy_pct",
            "kl_latent_tiled_roofline"} <= traced
    assert not NOT_ITS & traced
    assert {m["name"] for m in harness.metrics_for(bench, CELL, False)} == \
        {"tpot_p50_ms", "setup_s"}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW_METRICS}
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        perf = f.read()
    for name in NEW_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
        assert entry["layer"] in layers and f"`{name}`" in perf
        with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{name}.json")) as f:
            assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "readers",
                                               f"{json.load(f)['reader']}.py"))


def test_a_program_without_the_family_exits_at_once_with_a_message():
    """The parent's tree plus this PR's benchmark files: the family module's
    import of the program's config fails, and the run exits in seconds with a
    sentence that says why, before any weight is made."""
    code = ("import sys\n"
            "sys.modules['deepspeed_tpu.models.longcat_flash'] = None\n"
            "from benchmark import harness\n"
            f"harness._load_module({tiny.REPO!r}, 'models', 'longcat_flash')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=tiny.REPO, capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=tiny.REPO, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0
    said = done.stderr.replace("\n", " ")
    assert "experts without a bank" in said and "two latent layers of the pool" in said
    assert "Nothing was measured" in said


def test_the_family_builds_the_programs_config_from_the_file(resolved):
    family = harness._load_module(tiny.REPO, "models", "longcat_flash")
    c = resolved[2]
    cfg = family.program_config(c)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_rank, cfg.first_expert_held) == \
        (512, 16, c["deployment_share"]["expert_rank"], 16 * c["deployment_share"]["expert_rank"])
    assert (cfg.num_layers, cfg.num_hidden_layers, cfg.vocab_size, cfg.router_outputs) == \
        (4, 4, 16384, 768)
    assert (cfg.moe_topk, cfg.routed_scaling_factor, cfg.expert_ffn_hidden_size,
            cfg.ffn_hidden_size, cfg.zero_expert_num) == (12, 6, 2048, 12288, 256)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_head_dim, cfg.latent_width, cfg.v_head_dim,
            cfg.num_attention_heads, cfg.rope_theta) == (512, 1536, 192, 576, 128, 64, 1e7)
    assert cfg.softmax_scale == 192**-0.5 and cfg.q_lora_scale == 2.0 \
        and cfg.kv_lora_scale == 12**0.5
    # the file records the init constants that are the benchmark's own: the program's
    gains = c["assumed"]["init_gains"]
    from deepspeed_tpu.models import longcat_flash
    assert (longcat_flash.QUERY_INIT_GAIN, longcat_flash.ROUTER_INIT_GAIN,
            longcat_flash.SELECT_BIAS_STD) == \
        (gains["query"], gains["router_init_gain"], gains["select_bias_std"])


# -------------------------------------------------------------- rehearsal ---
TINY = {
    "family": "longcat_flash", "mode": "serve", "torch_dtype": "float32",
    "num_layers": 2, "num_hidden_layers": 2, "hidden_size": 64, "vocab_size": 256,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "zero_expert_num": 8, "zero_expert_type": "identity", "moe_topk": 4,
    "routed_scaling_factor": 6, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "attention_method": "MLA", "attention_bias": False, "max_position_embeddings": 512,
    "deployment_share": {"chips_sharing_a_layer": 4, "routed_over": 16, "experts_held": 4,
                         "expert_rank": 1},
    "reference_pad_to": 96,
    "engine": {"kv_block_size": 8,
               "state_manager": {"memory_config": {"mode": "allocate", "size": 256},
                                 "max_context": 128, "max_ragged_batch_size": 32,
                                 "max_ragged_sequence_count": 8},
               "expert_parallel": {"capacity_factor": 4.0}},
    "serving": {"decode_chunk": 4, "queue_capacity": 1024},
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A throw-away benchmark root with the cell ``tiny-longcat-agent``."""
    root = tiny.make_root(tmp_path_factory.mktemp("longcat") / "root")
    tiny.write_json(os.path.join(root, "benchmark", "configs", "tiny-longcat.json"), TINY)
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", "agent32-closed.json")) as f:
        traffic = json.load(f)
    traffic.update(tiny._TIMES)
    traffic["params"].update(clients=4, requests_per_client=40,
                             prompt={"dist": "uniform", "min": 20, "max": 72},
                             output={"dist": "lognormal", "median": 16, "sigma": 0.25, "min": 8,
                                     "max": 24})
    tiny.write_json(os.path.join(root, "benchmark", "traffic", "tiny-agent.json"), traffic)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-longcat", "source": "none: a test preset",
                             "file": "benchmark/configs/tiny-longcat.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "tiny-longcat-agent", "config": "tiny-longcat",
                               "traffic": "tiny-agent", "chips": 1, "why": "CPU rehearsal"})
    own = {m["name"] for m in harness.metrics_for(harness._load_json(
        os.path.join(tiny.REPO, "BENCHMARK.json")), CELL, True)}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and ("tiny-mixtral-closed" in m["workloads"] or m["name"] in own) \
                and m["name"] not in NOT_ITS and "tiny-longcat-agent" not in m["workloads"]:
            m["workloads"].append("tiny-longcat-agent")
    tiny.write_json(path, bench)
    return root


def test_the_cell_rehearses_at_a_tiny_preset(tiny_root):
    """Two layers (four latent layers of the pool), 4 of 16 experts held beside
    8 identity experts, the check's four prompts prefilled together in shares
    of 8, through the harness's test-only entry: the family, the traffic, the
    new metric files and their readers all load, and the check holds prefill in
    chunks, ``put`` and ``decode_loop`` to the float32 reference."""
    out = io.StringIO()
    assert harness.run_cell(tiny_root, "tiny-longcat-agent", 2**31 + 62, 1.5, 1, rehearsal=True,
                            out=out) == 0  # traced: what an untraced run does, and the readers
    text = out.getvalue()
    line = tiny.last_line(text)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert text.count("-> ok") >= 4 and "WRONG" not in text
    assert line["metrics"]["cpu_rehearsal.compiles_in_window"]["value"] == 0
    # every metric the cell lists either read or said it had nothing to read off the chip
    for name in NEW_METRICS:
        assert f"cpu_rehearsal.{name}" in line["metrics"] \
            or f"metric {name}: nothing to read" in text


@pytest.mark.parametrize("control", ["no_identity", "second_half_first_cache", "drop_expert"])
def test_the_controls_run_through_the_harness_comparison_at_a_tiny_preset(tiny_root, capsys,
                                                                          control):
    """``benchmark/tools/controls_longcat.py`` on the tiny cell, float32: a
    control of each new mechanism reads false (the engine as built reads
    ``correct`` in the rehearsal above), and a control restores what it
    patched."""
    from benchmark.tools import controls_longcat
    rc = controls_longcat.main(["--workload", "tiny-longcat-agent", "--seed", str(2**31 + 62),
                                "--rehearsal", "1", "--root", tiny_root, "--controls", control])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    read = {name: c["correct"] for name, c in result["controls"].items()}
    # float32 on the CPU sees a dead bank of four; the chip's bf16 rows cannot see one of sixteen
    assert read == {control: False} and rc == 0
    from benchmark.tools import controls_latent
    from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
    from deepspeed_tpu.ops.pallas import latent_attention
    assert RaggedMoE._zero_term.__qualname__ == "RaggedMoE._zero_term"
    assert latent_attention.latent_paged_attention_xla.__module__ == latent_attention.__name__
    assert controls_latent.spoilt.__module__ == controls_latent.__name__
