"""The Nemotron-3-Nano cell's files (PR 43): the configuration against the
catalog row and its own arithmetic, the traffic and the metrics as the issue
gives them, the family module refuses a program without ``NemotronHConfig`` at
once, the count the state-space rooflines are held to, and the cell and its
controls rehearsed at a tiny preset."""

import io
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark import harness, opcount
from benchmark.readers import (span_ssm_state, trace_hybrid_expert_roofline,
                               trace_hybrid_scope_busy, trace_ssm_roofline)
from tests.benchmark import tiny

CELL, CONFIG = "nemotron3-nano-reason-closed", "nemotron3-nano-serve-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072}
NEW_METRICS = ("ssm_busy_pct", "ssm_scan_roofline", "ssm_step_roofline",
               "ssm_state_slots_peak_pct", "ssm_rows_per_step", "moe_local_assignment_share",
               "moe_banks_per_local_assignment", "moe_relu2_grouped_roofline",
               "unscoped_hybrid_busy_pct", "chunk_launch_p50_ms",
               "chunk_round_trip_p50_ms", "idle_in_chunk_run_pct")
# the names PR 43 had to give five accepted readers a second time, and the accepted names
# that list this cell since PR 61: one name a reading
FOLDED = {"moe_held_assignment_share": "moe_local_assignment_share",
          "moe_banks_per_held_assignment": "moe_banks_per_local_assignment",
          "chunk_launch_hybrid_p50_ms": "chunk_launch_p50_ms",
          "chunk_round_trip_hybrid_p50_ms": "chunk_round_trip_p50_ms",
          "idle_in_chunk_run_hybrid_pct": "idle_in_chunk_run_pct"}
# readers that do not apply to this family as they are (``test_its_metrics_are_listed``)
NOT_ITS = {"paged_attn_roofline", "moe_share_grouped_roofline", "moe_grouped_roofline",
           "dense_ffn_busy_pct", "unscoped_busy_pct"}
GIB = 2**30


@pytest.fixture(scope="module")
def resolved():
    return harness.resolve(tiny.REPO, CELL)


def test_the_cell_is_the_one_the_issue_names(resolved):
    bench, cell, config, traffic = resolved
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason-closed", 1)
    assert config["family"] == "nemotron_h" and config["mode"] == "serve"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(REDUCED) and config["reduced_from"] == REDUCED
    assert entry["source"] == config["source"]
    sm = config["engine"]["state_manager"]
    assert (sm["max_context"], sm["max_ragged_batch_size"], sm["max_ragged_sequence_count"],
            sm["max_tracked_sequences"], config["serving"]["decode_chunk"]) == (4096, 256, 8, 128, 8)
    p = traffic["params"]
    assert traffic["kind"] == "closed_clients" and (p["clients"], p["requests_per_client"]) == (8, 16)
    assert p["prompt"] == {"dist": "uniform", "min": 1536, "max": 2560}
    assert p["prompt"]["max"] + p["output"]["max"] == 3840 <= sm["max_context"]
    assert config["reference_pad_to"] >= p["prompt"]["max"] + 8
    share = config["deployment_share"]
    assert (share["chips_sharing_a_layer"], share["routed_over"], share["experts_held"],
            share["expert_rank"], share["vocabulary_slices"], share["vocabulary_slice"]) == \
        (2, 128, 64, 0, 2, 0)
    assert share["experts_held"] == config["n_routed_experts"]
    assert {"no_rotary", "modelling_code", "init", "torch_dtype", "bank_lanes"} <= \
        set(config["assumed"])
    assert "transformers" in config["assumed"]["modelling_code"]
    assert "FLOAT32" in config["assumed"]["torch_dtype"]
    # the same traffic file as Trinity's cell: two 128-expert models under identical requests
    trinity = next(w for w in bench["workloads"] if w["name"] == "trinity-mini-reason-closed")
    assert trinity["traffic"] == cell["traffic"]


def test_every_number_of_the_catalog_row_is_in_the_file_or_in_reduced(resolved):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog beside the model-configs guide is not here")
    config = resolved[2]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == config["source"])
    assert row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16" and len(row["config"]) >= 45
    for key, value in row["config"].items():
        if key in REDUCED:
            assert value == REDUCED[key] and config[key] != value
        else:
            assert config[key] == value, key
    # the pattern is kept whole; the fourteen blocks served are its first two repeats
    served = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    assert served == "MEMEM*EMEMEM*E" == 2 * config["hybrid_override_pattern"][:7]
    assert (served.count("M"), served.count("E"), served.count("*")) == (6, 6, 2)


def test_the_bytes_re_reckoned_from_the_file_are_the_stated_ones(resolved):
    """The issue's arithmetic, from the file's numbers alone."""
    c = resolved[2]
    M, V = c["hidden_size"], c["vocab_size"]
    d_inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv_dim = d_inner + 2 * c["n_groups"] * c["ssm_state_size"]
    assert (d_inner, conv_dim) == (4096, 6144)
    mamba = M * (d_inner + conv_dim + c["mamba_num_heads"]) + d_inner * M \
        + conv_dim * (c["conv_kernel"] + 1) + 3 * c["mamba_num_heads"] + d_inner + M
    attn = 2 * M * c["num_attention_heads"] * c["head_dim"] \
        + 2 * M * c["num_key_value_heads"] * c["head_dim"] + M
    expert = 2 * M * c["moe_intermediate_size"]
    experts = c["n_routed_experts"] * expert + 2 * M * c["moe_shared_expert_intermediate_size"] \
        + M * c["reduced_from"]["n_routed_experts"] + c["reduced_from"]["n_routed_experts"] + M
    ends = 2 * V * M + M
    assert round(mamba / 1e6, 2) == 38.74 and round(attn / 1e6, 2) == 23.40
    assert round(expert / 1e6, 3) == 9.978 and round(experts / 1e6, 1) == 658.9
    assert round(ends / 1e6, 1) == 352.3
    total = 6 * mamba + 6 * experts + 2 * attn + ends
    assert round(total / 1e6) == 4585 and round(2 * total / GIB, 2) == 8.54
    # as the device holds them: an expert's width in whole lane tiles
    lanes = -(-c["moe_intermediate_size"] // 128) * 128
    held = total + 6 * c["n_routed_experts"] * 2 * M * (lanes - c["moe_intermediate_size"])
    assert lanes == 1920 and round(2 * held / GIB, 2) == 8.79
    # the pools beside them
    sm = c["engine"]["state_manager"]
    slot = 6 * (4 * c["mamba_num_heads"] * c["mamba_head_dim"] * c["ssm_state_size"]
                + 2 * (c["conv_kernel"] - 1) * conv_dim)
    block = c["engine"]["kv_block_size"] * 2 * 2 * c["num_key_value_heads"] * c["head_dim"] * 2
    assert block == 256 * 1024 and round(slot / 2**20, 1) == 12.2
    pools = sm["max_tracked_sequences"] * slot + sm["memory_config"]["size"] * block
    assert round(sm["max_tracked_sequences"] * slot / GIB, 2) == 1.53
    assert 0.69 < (2 * held + pools) / (16 * GIB) < 0.71
    for said in ("4585 M", "8.54 GiB", "8.79 GiB", "1.53 GiB", "69.6 %"):
        assert said in c["deployment"], said


def test_every_engine_key_says_why(resolved):
    config = resolved[2]
    assert {k for k in config if k.endswith("_why")} == {"engine_why", "serving_why"}
    engine = config["engine"]
    keys = {"kv_block_size"} | (set(engine["state_manager"]) - {"memory_config"}) \
        | {"memory_config"} | set(engine["expert_parallel"])
    assert keys | {"correct"} == set(config["engine_why"])
    assert set(config["serving"]) == set(config["serving_why"])
    assert all(len(why) > 40 for why in config["engine_why"].values())


def test_its_metrics_are_listed(resolved):
    bench = resolved[0]
    traced = {m["name"] for m in harness.metrics_for(bench, CELL, True)}
    assert set(NEW_METRICS) <= traced
    assert {"moe_busy_pct", "moe_route_busy_pct", "moe_shared_busy_pct", "attn_busy_pct",
            "paged_attn_busy_pct", "paged_prefill_busy_pct",
            "device_idle_pct", "kv_blocks_peak_pct", "compiles_in_window",
            "serve_generated_tokens_per_s", "step_device_any_p50_ms"} <= traced
    # readers that do not apply as they are: ``paged_attn_roofline`` prices attention in all
    # 14 blocks (runners/serve.py hands it num_hidden_layers) where 2 have it;
    # ``moe_share_grouped_roofline`` counts the expert layers as depth less
    # first_k_dense_replace and three matrices an expert; ``unscoped_busy_pct``'s pattern does
    # not name ``ssm`` and would count the Mamba-2 blocks twice (``unscoped_hybrid_busy_pct``)
    assert not NOT_ITS & traced
    assert set(FOLDED.values()) <= traced and not set(FOLDED) & traced
    assert {m["name"] for m in harness.metrics_for(bench, CELL, False)} == \
        {"tpot_p50_ms", "setup_s"}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW_METRICS}
    for name in NEW_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == "tpot_p50_ms"
        assert entry["layer"] in layers
        if name.endswith("_roofline"):
            assert entry["unit"] == "%" and entry["better"] == "higher"
        with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{name}.json")) as f:
            assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "readers",
                                               f"{json.load(f)['reader']}.py"))
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        perf = f.read()
    assert all(f"`{name}`" in perf for name in NEW_METRICS)


def test_the_roofline_count_prices_a_state_a_segment_and_the_recurrence_a_row():
    # one decode row through one block: its 2 MiB state read and written, 5 flop an element
    flops, nbytes = trace_ssm_roofline.ssm_work(1, 1, 64, 64, 8, 128)
    assert flops == 5 * 64 * 64 * 128
    assert nbytes == 2 * 4 * 64 * 64 * 128 + (4096 + 2048) * 2 + 4 * 64 + 4 * 4096
    # a 256-row chunk of ONE sequence reads and writes the state once, not 256 times
    flops, nbytes = trace_ssm_roofline.ssm_work(256, 1, 64, 64, 8, 128)
    assert flops == 256 * 5 * 64 * 64 * 128
    assert nbytes == 2 * 4 * 64 * 64 * 128 + 256 * ((4096 + 2048) * 2 + 4 * 64 + 4 * 4096)
    # a decode row is memory-bound on a v5e: 4 MiB at 819 GB/s, 5.1 us a row a block
    peaks = opcount.PEAKS["TPU v5 lite"]
    least, bound = opcount.roofline_seconds(*trace_ssm_roofline.ssm_work(1, 1, 64, 64, 8, 128),
                                            peaks)
    assert bound == "memory" and least == pytest.approx(5.15e-6, rel=0.01)


def test_a_program_without_the_family_exits_at_once_with_a_message():
    code = ("import sys\n"
            "sys.modules['deepspeed_tpu.models.nemotron_h'] = None\n"
            "from benchmark import harness\n"
            f"harness._load_module({tiny.REPO!r}, 'models', 'nemotron_h')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=tiny.REPO, capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=tiny.REPO, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0
    assert "cannot serve a model with state-space blocks" in done.stderr
    assert "Nothing was measured" in done.stderr


def test_the_family_builds_the_programs_config_from_the_file(resolved):
    family = harness._load_module(tiny.REPO, "models", "nemotron_h")
    cfg = family.program_config(resolved[2])
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_rank, cfg.first_expert_held) == \
        (128, 64, 0, 0)
    assert (cfg.num_hidden_layers, cfg.hybrid_override_pattern, cfg.vocab_size) == \
        (14, "MEMEM*EMEMEM*E", 65536)
    assert (cfg.d_inner, cfg.conv_dim, cfg.in_proj_width, cfg.bank_width) == \
        (4096, 6144, 10304, 1920)
    assert (cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.chunk_size) == (6, 2.5, 128)


# -------------------------------------------------------------- rehearsal ---
TINY = {
    "family": "nemotron_h", "mode": "serve", "torch_dtype": "float32",
    "hybrid_override_pattern": "MEM*EMEMEM*EME", "num_hidden_layers": 7, "hidden_size": 64,
    "vocab_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 8, "intermediate_size": 48, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96, "n_routed_experts": 4, "n_shared_experts": 1,
    "num_experts_per_tok": 3, "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "layer_norm_epsilon": 1e-5, "max_position_embeddings": 512,
    "deployment_share": {"chips_sharing_a_layer": 2, "routed_over": 8, "experts_held": 4,
                         "expert_rank": 1},
    "reference_pad_to": 96,
    "engine": {"kv_block_size": 8,
               "state_manager": {"memory_config": {"mode": "allocate", "size": 256},
                                 "max_context": 128, "max_ragged_batch_size": 32,
                                 "max_ragged_sequence_count": 8, "max_tracked_sequences": 12},
               "expert_parallel": {"capacity_factor": 4.0}},
    "serving": {"decode_chunk": 4, "queue_capacity": 1024},
}


def _tiny_root(tmp_path):
    """A throw-away benchmark root with the cell ``tiny-nemotron-reason``."""
    root = tiny.make_root(tmp_path / "root")
    tiny.write_json(os.path.join(root, "benchmark", "configs", "tiny-nemotron.json"), TINY)
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", "reason-closed.json")) as f:
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
    bench["configs"].append({"name": "tiny-nemotron", "source": "none: a test preset",
                             "file": "benchmark/configs/tiny-nemotron.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "tiny-nemotron-reason", "config": "tiny-nemotron",
                               "traffic": "tiny-reason", "chips": 1, "why": "CPU rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "tiny-mixtral-closed" in m["workloads"] \
                and m["name"] not in NOT_ITS:
            m["workloads"].append("tiny-nemotron-reason")
    tiny.write_json(path, bench)
    return root


def test_the_cell_rehearses_at_a_tiny_preset(tmp_path):
    """Two repeats of a tiny pattern, 8-token scan chunks under a 32-token
    budget (the check's four prompts prefilled together in shares of 8: every
    ``put`` is four segments), 4 of 8 experts held, through the harness's
    test-only entry: the family, the traffic, the new metric files and readers
    all load, and the check holds prefill in chunks with the state carried,
    ``put`` and ``decode_loop`` to the float32 reference."""
    root = _tiny_root(tmp_path)
    out = io.StringIO()
    assert harness.run_cell(root, "tiny-nemotron-reason", 2**31 + 43, 1.5, 1, rehearsal=True,
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
    """``benchmark/tools/controls_ssm.py`` on the tiny cell, float32: the engine as built
    reads ``correct``; a state or a convolution tail not carried from one ``put`` to the
    next reads false; what a bfloat16 state pool reads at the real sizes is the chip's to
    say (PERF.md section 6, PR 43); the exit code says whether every control was caught."""
    from benchmark.tools import controls_ssm
    root = _tiny_root(tmp_path)
    rc = controls_ssm.main(["--workload", "tiny-nemotron-reason", "--seed", str(2**31 + 43),
                            "--rehearsal", "1", "--root", root])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    read = {name: c["correct"] for name, c in result["controls"].items()}
    assert list(read) == ["baseline"] + list(controls_ssm.CONTROLS)
    assert read["baseline"] is True
    assert read["no_state_carry"] is False and read["no_conv_carry"] is False
    assert read["fp8_weights"] is False  # what holds the stated precision
    assert rc == (0 if read["state_bf16"] is False else 4)
    # a control restores what it patched
    from deepspeed_tpu.inference.v2.model_implementations import nemotron_h_v2 as served
    from deepspeed_tpu.inference.v2.modules import ssm
    assert served.ssm.scan_ragged is ssm.scan_ragged
    assert ssm.scan_ragged.__module__ == ssm.conv_ragged.__module__ == ssm.__name__
    assert isinstance(served.NemotronHV2Model.sequence_state, property)


def test_the_compilers_own_operations_on_a_state_are_in_the_time_the_roofline_prices(resolved):
    """Operations the compiler adds carry no ``op_name`` (here: a state gathered by a loop of
    slices, an ``in_proj`` kernel copied between memories, the conv pool re-laid; and a fused
    reduction that is nobody's): the first three are given the scope of the array they make, a
    state's the form of the scoped operation nearest in time; the last stays unscoped. So the
    step's roofline is least / (recurrence + gather), and the scope shares add up to the busy
    time."""
    config = resolved[2]
    step = "jit(_decode_loop_impl)/while/body/closed_call/ssm/step/mul:"
    scan = "jit(_forward_impl)/ssm/scan/dot_general:"
    gather = "%bitcast_dynamic-update-slice_fusion.16 = f32[8,64,64,128]{3,2,1,0:T(8,128)} fusion(%a)"
    gather_put = "%bitcast_dynamic-update-slice_fusion.9 = f32[8,64,64,128]{3,2,1,0:T(8,128)} fusion(%b)"
    copy = "%copy-done.37 = bf16[2688,10304]{0,1:T(8,128)(2,1)S(1)} copy-done(%copy-start.37)"
    tail = "%fusion.1033.remat = bf16[6,128,3,6144]{3,2,1,0} fusion(%c)"
    argmax = "%iota_reduce_fusion.3 = (bf16[8]{0}, s32[8]{0}) fusion(%d)"
    ops = [(0, 100, "%fusion.7 = f32[256,64,64]{2,1,0} fusion(%x)"),          # ssm/scan
           (100, 140, gather_put),                                             # nearest: the scan
           (1000, 1040, gather), (1040, 1140, "%fusion.8 = f32[8,64,64,128]{3,2,1,0} fusion(%y)"),
           (1140, 1150, copy), (1150, 1160, tail), (1160, 1200, argmax),
           (1000, 1040, "%while.42 = (s32[], f32[6,128,64,64,128]) while(%t)")]  # holds the gather
    scopes = {ops[0][2]: scan, ops[3][2]: step, ops[7][2]: step.replace("mul", "gather")}
    given = {}
    paths = trace_ssm_roofline.attributed(ops, scopes, config, given)
    assert [p[2] for p in paths] == [scan, "ssm/scan/unscoped", "ssm/step/unscoped", step,
                                     "ssm/in_proj/unscoped", "ssm/conv/unscoped", ""]
    assert given == pytest.approx({"ssm/scan/unscoped": 40e-9, "ssm/step/unscoped": 40e-9,
                                   "ssm/in_proj/unscoped": 10e-9, "ssm/conv/unscoped": 10e-9})
    # a float32 array of other dims, or the state's dims in bf16, is nobody's
    own = trace_ssm_roofline.own_arrays(config)
    assert trace_ssm_roofline._own_scope("%f.1 = f32[8,64,128]{2,1,0} fusion(%x)", own) is None
    assert trace_ssm_roofline._own_scope("%f.1 = bf16[8,64,64,128]{3,2,1,0} fusion(%x)", own) is None

    trace = SimpleNamespace(devices={0: ops}, host=[])
    slice_ = SimpleNamespace(began=0.0, ended=4.0, sync_clock=None)
    spans = [{"name": "decode_loop", "cat": "inference", "ts_us": 10, "dur_us": 5,
              "args": {"steps": 1, "ssm_tokens": 8 * 6, "ssm_segments": 8 * 6}}]
    run = {"trace_slice": slice_, "spans": spans, "t0": 0.0, "seconds": 45.0, "trace_path": None}
    logged = []
    env = {"trace": trace, "peaks": opcount.PEAKS["TPU v5 lite"], "config": config,
           "log": logged.append, "host_phases": ([], scopes)}
    least = 6 * opcount.roofline_seconds(*trace_ssm_roofline.ssm_work(8, 8, 64, 64, 8, 128),
                                         env["peaks"])[0]
    got = trace_ssm_roofline.read(run, {"pattern": "(^|/)ssm/step(/|$)", "kind": "step"}, env)
    assert got == pytest.approx(100.0 * least / 140e-9)  # 100 ns of recurrence + 40 of gather
    busy = trace_hybrid_scope_busy.read(run, {"pattern": "(^|/)ssm(/|$)"}, env)
    assert busy == pytest.approx(100.0 * 300 / 340)      # all but the argmax; the container apart
    rest = trace_hybrid_scope_busy.read(
        run, {"pattern": "(^|/)(attn|moe|mlp|embed|unembed|ssm)/", "invert": True}, env)
    assert rest == pytest.approx(100.0 * 40 / 340)
    assert any("iota_reduce_fusion" in line and "ssm/in_proj/unscoped" in line for line in logged)
    with open(os.path.join(tiny.REPO, "benchmark", "metrics", "unscoped_hybrid_busy_pct.json")) as f:
        assert json.load(f)["params"] == {"pattern": "(^|/)(attn|moe|mlp|embed|unembed|ssm)/",
                                          "invert": True}


def test_the_new_readers_find_nothing_on_a_program_without_the_family_and_do_not_raise(resolved):
    """On the parent the trace has no ``ssm`` scope and the spans none of the counts: each
    reader returns None (the metric is left out of the line), whatever the configuration."""
    config = resolved[2]
    trace = SimpleNamespace(devices={0: [(0, 1000, "fusion.1"), (1000, 3000, "grouped_matmul.3")]},
                            host=[])
    slice_ = SimpleNamespace(began=0.0, ended=4.0, sync_clock=None)
    spans = [{"name": "decode_loop", "cat": "inference", "ts_us": 10, "dur_us": 5,
              "args": {"steps": 8, "moe_path": "grouped", "moe_banks": 40,
                       "moe_assignments": 2048}}]
    run = {"trace_slice": slice_, "spans": spans, "t0": 0.0, "seconds": 45.0, "trace_path": None}
    env = {"trace": trace, "peaks": opcount.PEAKS["TPU v5 lite"], "config": config,
           "log": lambda message: None, "host_phases": ([], {})}
    for kind in ("scan", "step"):
        assert trace_ssm_roofline.read(run, {"pattern": f"(^|/)ssm/{kind}(/|$)", "kind": kind},
                                       env) is None
    for kind in ("slots_peak_pct", "rows_per_step"):
        assert span_ssm_state.read(run, {"kind": kind}, env) is None
    params = {"pattern": "^%?grouped_matmul", "moe_path": "grouped"}
    assert trace_hybrid_expert_roofline.read(run, params, env) is None
    # a configuration of another family has neither the pattern nor a state to price
    mellum = harness.resolve(tiny.REPO, "mellum2-repoctx-closed")[2]
    other = dict(env, config=mellum)
    assert trace_hybrid_expert_roofline.read(run, params, other) is None
    assert trace_ssm_roofline.read(run, {"pattern": "ssm", "kind": "step"}, other) is None
    for env_ in (env, other, dict(env, host_phases=([], {"fusion.1": "jit(f)/moe/experts/dot:"}))):
        assert trace_hybrid_scope_busy.read(run, {"pattern": "(^|/)ssm(/|$)"}, env_) is None
