"""The Kimi-Linear cell's files (PR 56): the configuration against the catalog
row and its own arithmetic (the bytes re-reckoned from the file are the tree
``init_params`` makes), the traffic and the metrics as the issue gives them,
the family module refuses a program without ``KimiLinearConfig`` at once, the
count the latent kernels' rooflines are held to, and the cell and its controls
rehearsed at a tiny preset. Every entry is found BY NAME: nothing here pins a
position, a count or a whole ``workloads`` list of ``BENCHMARK.json``."""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness, opcount
from benchmark.readers import trace_latent_rows_roofline
from tests.benchmark import tiny

CELL, CONFIG = "kimi-linear-longdoc-reason-closed", "kimi-linear-48b-a3b-serve-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
# the ``kl_`` names PR 56 had to give ten accepted readers a second time (the accepted metrics'
# lists were pinned to their cells by those cells' tests), and the accepted names that list this
# cell since PR 61: one name a reading. The latent kernels' two, whose count is new, and the
# latent layers' busy share keep names of their own; the latent pool's blocks are the generic
# ``kv_blocks_peak_pct``'s
FOLDED = {"kl_kda_busy_pct": "kda_busy_pct", "kl_kda_step_roofline": "kda_step_roofline",
          "kl_kda_chunk_roofline": "kda_chunk_roofline",
          "kl_latent_proj_busy_pct": "attn_latent_proj_busy_pct",
          "kl_moe_held_grouped_roofline": "moe_share_grouped_roofline",
          "kl_state_slots_peak_pct": "ssm_state_slots_peak_pct",
          "kl_kda_rows_in_place_share": "kda_rows_in_place_share",
          "kl_kda_chunk_in_kernel_share": "kda_chunk_in_kernel_share",
          "kl_chunk_launch_p50_ms": "chunk_launch_p50_ms",
          "kl_idle_in_chunk_run_pct": "idle_in_chunk_run_pct"}
NEW_METRICS = tuple(FOLDED.values()) + ("kl_latent_busy_pct", "kl_latent_token_roofline",
                                        "kl_latent_tiled_roofline")
# accepted metrics that would MISREAD this cell and are not its: ``unscoped_*`` name no ``kda``
# scope; the ``ssm_*`` / ``h1_*`` read Mamba-2 widths and scopes; ``paged_*`` a K/V kernel no
# layer runs; ``paged_latent_*`` / ``paged_index_roofline`` price every layer to ``index_topk``
# keys; ``moe_grouped_roofline`` / ``moe_banks_per_assignment`` every assignment the router made,
# four times what lands here; ``attn_gate_norm_busy_pct`` scopes no layer has
NOT_ITS = {"unscoped_busy_pct", "unscoped_hybrid_busy_pct", "ssm_rows_per_step", "ssm_busy_pct",
           "ssm_scan_roofline", "ssm_step_roofline", "ssm_in_place_row_share",
           "moe_relu2_grouped_roofline", "paged_attn_roofline", "paged_mixed_token_roofline",
           "paged_mixed_tiled_roofline", "paged_latent_token_roofline",
           "paged_latent_tiled_roofline", "paged_index_roofline", "index_selected_share",
           "moe_grouped_roofline", "moe_banks_per_assignment", "attn_gate_norm_busy_pct",
           "paged_attn_busy_pct", "paged_prefill_busy_pct"}
GIB = 2**30


@pytest.fixture(scope="module")
def resolved():
    return harness.resolve(tiny.REPO, CELL)


def test_the_cell_is_the_one_the_issue_names(resolved):
    bench, cell, config, traffic = resolved
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longdoc-reason-closed", 1) and len(cell["why"]) <= 200
    assert config["family"] == "kimi_linear" and config["mode"] == "serve"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(REDUCED) and config["reduced_from"] == REDUCED
    assert entry["source"] == config["source"] and entry["file"].endswith(f"{CONFIG}.json")
    assert len(entry["why"]) <= 200
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] == [CELL]
    engine = config["engine"]
    sm = engine["state_manager"]
    assert (engine["kv_block_size"], sm["max_context"], sm["max_ragged_batch_size"],
            sm["max_ragged_sequence_count"], sm["max_tracked_sequences"],
            sm["memory_config"]["size"], engine["expert_parallel"]["capacity_factor"],
            config["serving"]["decode_chunk"]) == (128, 32768, 256, 8, 64, 8192, 32, 8)
    p = traffic["params"]
    assert traffic["kind"] == "closed_clients" and (p["clients"], p["requests_per_client"]) == (8, 16)
    assert p["prompt"] == {"dist": "uniform", "min": 8192, "max": 16384}
    assert p["output"] == {"dist": "lognormal", "median": 1280, "sigma": 0.25, "min": 1024,
                           "max": 2048} and p["temperature"] == 0.0
    assert (traffic["lead_in_s"], traffic["drain_s"], traffic["trace_start_s"],
            traffic["trace_length_s"]) == (16.0, 6.0, 10.0, 4.0)
    assert p["prompt"]["max"] + p["output"]["max"] == 18432 <= sm["max_context"]
    assert config["reference_pad_to"] >= p["prompt"]["max"] + 8
    share = config["deployment_share"]
    assert (share["chips_sharing_a_layer"], share["routed_over"], share["experts_held"],
            share["expert_rank"], share["vocabulary_slices"], share["vocabulary_slice"]) == \
        (4, 256, 64, 0, 4, 0)
    assert share["experts_held"] == config["num_experts"]
    assert {"modelling_code", "init", "init_gains", "torch_dtype", "unused_keys"} <= \
        set(config["assumed"])
    assert "2510.26692" in config["assumed"]["modelling_code"]
    assert "FLOAT32" in config["assumed"]["torch_dtype"]
    assert "WHAT THE CUT DISTORTS" in config["deployment"]


def test_every_number_of_the_catalog_row_is_in_the_file_or_in_reduced(resolved):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog beside the model-configs guide is not here")
    config = resolved[2]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == config["source"])
    assert row["name"] == "Kimi-Linear-48B-A3B-Instruct" and len(row["config"]) >= 30
    for key, value in row["config"].items():
        if key in REDUCED:
            assert value == REDUCED[key] and config[key] != value
        else:
            assert config[key] == value, key  # the nested linear_attn_config whole, both lists
    # the layers served are two whole periods in the published order, the published 3 : 1
    linear, n = config["linear_attn_config"], config["num_hidden_layers"]
    served = ["kda" if i in linear["kda_layers"] else "mla" for i in range(1, n + 1)]
    assert all((i in linear["kda_layers"]) != (i in linear["full_attn_layers"])
               for i in range(1, row["layers"] + 1))
    assert served == ["kda", "kda", "kda", "mla"] * (n // 4) and n % 4 == 0
    assert config["first_k_dense_replace"] == 1 and n - 1 >= 3


def test_the_bytes_re_reckoned_from_the_file_are_the_tree_init_params_makes(resolved):
    """The issue's arithmetic, from the file's numbers alone, against the tree
    the program makes for the file (``jax.eval_shape``: nothing is allocated)."""
    import jax
    c = resolved[2]
    M, V, F, n = c["hidden_size"], c["vocab_size"], c["moe_intermediate_size"], c["num_hidden_layers"]
    lin = c["linear_attn_config"]
    H, D, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    W, E = H * D, c["reduced_from"]["num_experts"]
    C, N, R, Vd, A = (c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"], c["num_attention_heads"])
    kinds = ["kda" if i in lin["kda_layers"] else "mla" for i in range(1, n + 1)]
    expert = 3 * M * F
    moe = c["num_experts"] * expert + c["num_shared_experts"] * expert + M * E
    dense = 3 * M * c["intermediate_size"]
    kda = 4 * M * W + 2 * (M * D + D * W) + M * H + 3 * W * K
    mla = M * A * (N + R) + M * (C + R) + C * A * (N + Vd) + A * Vd * M
    assert [round(x / 1e6, 2) for x in (expert, moe, dense, kda, mla)] == \
        [7.08, 460.65, 63.70, 39.51, 29.11]
    # (parameters, bytes) of each part: bf16 but what the file's assumed.torch_dtype keeps float32
    small = W + H + D  # dt_bias, A_log, o_norm
    mixer = {"kda": (kda + small, 2 * (kda - 3 * W * K) + 4 * (3 * W * K + small)),
             "mla": (mla + C, 2 * mla + 4 * C)}
    ffn = {True: (dense, 2 * dense), False: (moe + E, 2 * (moe - M * E) + 4 * (M * E + E))}
    total = [2 * V * M + M, 2 * 2 * V * M + 4 * M]  # the ends: embedding, head, final norm
    for i, kind in enumerate(kinds):
        for part in (mixer[kind], ffn[i < c["first_k_dense_replace"]], (2 * M, 4 * 2 * M)):
            total = [t + p for t, p in zip(total, part)]
    family = harness._load_module(tiny.REPO, "models", "kimi_linear")
    cfg = family.program_config(c)
    from deepspeed_tpu.models import kimi_linear
    tree = jax.eval_shape(lambda: kimi_linear.init_params(cfg, param_dtype=cfg.dtype)[1])
    leaves = jax.tree.leaves(tree)
    assert [sum(int(np.prod(x.shape)) for x in leaves),
            sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)] == total
    assert round(total[0] / 1e6, 2) == 3772.37 and total[1] == 7553720064 \
        and round(total[1] / GIB, 2) == 7.03
    # the pools beside them
    sm = c["engine"]["state_manager"]
    slot = kinds.count("kda") * (4 * H * D * D + 2 * (K - 1) * 3 * W)
    row = -(-(C + R) // 128) * 128
    block = c["engine"]["kv_block_size"] * kinds.count("mla") * row * 2
    assert (row, block) == (640, 320 * 1024) and round(slot / 2**20, 2) == 12.42
    assert 4 * H * D * D == 2 * 2**20  # a layer's state a sequence: exactly 2 MiB
    pools = sm["max_tracked_sequences"] * slot + sm["memory_config"]["size"] * block
    assert round(sm["max_tracked_sequences"] * slot / GIB, 2) == 0.78
    assert round(sm["memory_config"]["size"] * block / GIB, 2) == 2.5
    assert 0.64 < (total[1] + pools) / (16 * GIB) < 0.65
    for said in ("3772.37 M", "7,553,720,064 bytes", "7.03 GiB", "12.42 MiB", "0.78 GiB",
                 "2.50 GiB", "64.4 %"):
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
    assert {"moe_busy_pct", "moe_route_busy_pct", "moe_shared_busy_pct", "attn_busy_pct",
            "dense_ffn_busy_pct", "device_idle_pct", "kv_blocks_peak_pct", "compiles_in_window",
            "serve_generated_tokens_per_s", "step_device_any_p50_ms", "step_decode_p50_ms",
            "sched_seqs_per_step", "idle_in_engine_pct", "idle_waiting_pct",
            "idle_in_host_stall_pct", "gc_pause_ms_per_s"} <= traced
    assert not NOT_ITS & traced
    assert not set(FOLDED) & traced
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


def test_the_roofline_count_prices_a_causal_row_and_reads_a_context_once():
    # one decode row at 12k of context in one latent layer: 12k rows of 640 lanes read, and each
    # of 32 heads a dot product over 640 lanes and an accumulation of 512
    flops, nbytes = trace_latent_rows_roofline.latent_work(12000, 12000, 32, 640, 512)
    assert (flops, nbytes) == (12000 * 32 * (640 + 512) * 2, 12000 * 1280)
    peaks = opcount.PEAKS["TPU v5 lite"]
    least, bound = opcount.roofline_seconds(flops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(18.75e-6, rel=0.01)
    # a 256-row chunk at 12k: 256 x ~12k causal rows computed, the context read ONCE: compute-bound
    flops, nbytes = trace_latent_rows_roofline.latent_work(256 * 12128, 12256, 32, 640, 512)
    assert opcount.roofline_seconds(flops, nbytes, peaks)[1] == "compute"
    # a program without the spans, a configuration without a latent row: nothing to read
    env = {"trace": None, "peaks": peaks, "config": {}}
    assert trace_latent_rows_roofline.read({"trace_slice": None}, {"grid": "token"}, env) is None


def test_a_program_without_the_family_exits_at_once_with_a_message():
    code = ("import sys\n"
            "sys.modules['deepspeed_tpu.models.kimi_linear'] = None\n"
            "from benchmark import harness\n"
            f"harness._load_module({tiny.REPO!r}, 'models', 'kimi_linear')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=tiny.REPO, capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=tiny.REPO, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0
    assert "a pool of latent rows a token and a pool of slots a sequence in one cache" \
        in done.stderr.replace("\n", " ")
    assert "Nothing was measured" in done.stderr.replace("\n", " ")


def test_the_family_builds_the_programs_config_from_the_file(resolved):
    family = harness._load_module(tiny.REPO, "models", "kimi_linear")
    cfg = family.program_config(resolved[2])
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_rank, cfg.first_expert_held) == \
        (256, 64, 0, 0)
    assert (cfg.num_hidden_layers, cfg.mla_here, cfg.kda_here, cfg.vocab_size) == \
        (8, (3, 7), (0, 1, 2, 4, 5, 6), 40960)
    assert (cfg.linear_num_heads, cfg.linear_head_dim, cfg.short_conv_kernel_size,
            cfg.kda_width, cfg.beta_scale) == (32, 128, 4, 4096, 1.0)
    assert (cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.moe_intermediate_size,
            cfg.n_shared_experts, cfg.norm_topk_prob, cfg.scoring_func, cfg.n_group) == \
        (8, 2.446, 1024, 1, True, "sigmoid", 1)
    assert (cfg.kv_lora_rank, cfg.qk_head_dim, cfg.latent_width, cfg.v_head_dim,
            cfg.q_lora_rank, cfg.max_position_embeddings) == (512, 192, 576, 128, None, 1048576)
    assert cfg.softmax_scale == 192**-0.5 and cfg.is_dense(0) and not cfg.is_dense(1)
    # the file records the one init gain that is this family's own
    from deepspeed_tpu.models import kimi_linear
    assert kimi_linear.QUERY_INIT_GAIN == resolved[2]["assumed"]["init_gains"]["query"]


# -------------------------------------------------------------- rehearsal ---
TINY = {
    "family": "kimi_linear", "mode": "serve", "torch_dtype": "float32",
    "num_hidden_layers": 4, "hidden_size": 64, "vocab_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "q_lora_rank": None, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
    "rope_scaling": None, "rope_theta": 10000,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 2,
                           "kda_layers": [1, 2, 5], "full_attn_layers": [3, 4]},
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 4,
    "num_shared_experts": 1, "num_experts_per_token": 4, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2.446, "first_k_dense_replace": 1, "rms_norm_eps": 1e-5,
    "model_max_length": 512,
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
    """A throw-away benchmark root with the cell ``tiny-kimi-reason``."""
    root = tiny.make_root(tmp_path_factory.mktemp("kimi") / "root")
    tiny.write_json(os.path.join(root, "benchmark", "configs", "tiny-kimi.json"), TINY)
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", "longdoc-reason-closed.json")) as f:
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
    bench["configs"].append({"name": "tiny-kimi", "source": "none: a test preset",
                             "file": "benchmark/configs/tiny-kimi.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "tiny-kimi-reason", "config": "tiny-kimi",
                               "traffic": "tiny-reason", "chips": 1, "why": "CPU rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "tiny-mixtral-closed" in m["workloads"] \
                and m["name"] not in NOT_ITS:
            m["workloads"].append("tiny-kimi-reason")
    tiny.write_json(path, bench)
    return root


def test_the_cell_rehearses_at_a_tiny_preset(tiny_root):
    """Two delta-rule layers (the first over a dense feed-forward) and two
    latent ones, 16-row visits under a 32-token budget (the check's four prompts
    prefilled together in shares of 8: every ``put`` is four segments), 4 of 16
    experts held, through the harness's test-only entry: the family, the
    traffic, the new metric files and readers all load, and the check holds
    prefill in chunks with the state and the latent rows carried, ``put`` and
    ``decode_loop`` to the float32 reference."""
    out = io.StringIO()
    assert harness.run_cell(tiny_root, "tiny-kimi-reason", 2**31 + 56, 1.5, 1, rehearsal=True,
                            out=out) == 0  # traced: what an untraced run does, and the readers
    text = out.getvalue()
    line = tiny.last_line(text)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert text.count("-> ok") >= 4 and "WRONG" not in text
    assert line["metrics"]["cpu_rehearsal.compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("control", ["no_conv_carry", "wrong_latent_layer"])
def test_the_controls_run_through_the_harness_comparison_at_a_tiny_preset(tiny_root, capsys,
                                                                          control):
    """``benchmark/tools/controls_kimi.py`` on the tiny cell, float32: a
    control of each mixer reads false (the engine as built reads ``correct`` in
    the rehearsal above; all six ran so on the chip, PR 56), and a control
    restores what it patched."""
    from benchmark.tools import controls_kimi
    rc = controls_kimi.main(["--workload", "tiny-kimi-reason", "--seed", str(2**31 + 56),
                             "--rehearsal", "1", "--root", tiny_root, "--controls", control])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    read = {name: c["correct"] for name, c in result["controls"].items()}
    assert read == {control: False} and rc == 0
    from deepspeed_tpu.inference.v2.model_implementations.deepseek_v32_v2 import DeepseekV32V2Model
    from deepspeed_tpu.inference.v2.model_implementations.kimi_linear_v2 import KimiLinearV2Model
    from deepspeed_tpu.inference.v2.modules import kda, ssm
    from deepspeed_tpu.models.kimi_linear import KimiLinearConfig
    from deepspeed_tpu.ops.pallas import latent_attention
    assert kda.scan_in_place.__module__ == kda.__name__
    assert ssm.conv_ragged.__module__ == ssm.__name__
    assert latent_attention.latent_paged_attention_xla.__module__ == latent_attention.__name__
    assert KimiLinearV2Model._write_rows is DeepseekV32V2Model._write_rows
    assert KimiLinearConfig.tiny().beta_scale == 1.0
