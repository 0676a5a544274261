"""The Falcon-H1 cell's files (PR 47): the configuration against the catalog
row and its own arithmetic (which adds up to the tree ``init_params`` makes),
the traffic and the metrics as the issue gives them, the family module refuses
a program without ``FalconH1Config`` at once, the new readers (a piece of a
state is the mixers' time; the paged count at the configuration's ``head_dim``;
nothing to read and no raise on another program), and the cell and its
controls rehearsed at a tiny preset."""

import io
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark import harness, opcount
from benchmark.readers import (trace_h1_paged_roofline, trace_h1_scope_busy,
                               trace_h1_ssm_roofline, trace_ssm_roofline)
from tests.benchmark import tiny

CELL, CONFIG = "falcon-h1-34b-chat32-closed", "falcon-h1-34b-serve-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("h1_ssm_busy_pct", "h1_unscoped_busy_pct", "h1_ssm_step_roofline",
               "h1_ssm_scan_roofline", "h1_paged_token_roofline", "unembed_busy_pct",
               "seq_bucket_fill")
# accepted readers that do not read this configuration as it is: they take Nemotron's key
# names, a pattern of blocks, or ``hidden_size / heads`` (256) for the heads' width (128)
NOT_ITS = {"ssm_busy_pct", "ssm_step_roofline", "ssm_scan_roofline", "unscoped_hybrid_busy_pct",
           "ssm_rows_per_step", "paged_attn_roofline", "unscoped_busy_pct", "moe_busy_pct"}
GIB = 2**30


@pytest.fixture(scope="module")
def resolved():
    return harness.resolve(tiny.REPO, CELL)


def test_the_cell_is_the_one_the_issue_names(resolved):
    bench, cell, config, traffic = resolved
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "chat32-closed", 1)
    assert config["family"] == "falcon_h1" and config["mode"] == "serve"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] == list(config["reduced_from"])
    assert entry["source"] == config["source"] and "deployment_share" not in config
    sm = config["engine"]["state_manager"]
    assert (config["engine"]["kv_block_size"], sm["max_context"], sm["max_ragged_batch_size"],
            sm["max_ragged_sequence_count"], config["serving"]["decode_chunk"]) == \
        (128, 2048, 256, 32, 8)
    p = traffic["params"]
    assert traffic["kind"] == "closed_clients" and (p["clients"], p["requests_per_client"]) == (32, 24)
    assert p["prompt"] == {"dist": "uniform", "min": 256, "max": 768}
    assert p["output"] == {"dist": "lognormal", "median": 512, "sigma": 0.25, "min": 320,
                           "max": 832}
    assert p["temperature"] == 0.0 and p["clients"] == sm["max_ragged_sequence_count"]
    assert p["prompt"]["max"] + p["output"]["max"] == 1600 <= sm["max_context"]
    assert config["reference_pad_to"] >= p["prompt"]["max"] + 8
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", "reason-closed.json")) as f:
        reason = json.load(f)
    for key in ("lead_in_s", "drain_s", "trace_start_s", "trace_length_s"):
        assert traffic[key] == reason[key]
    assert {"modelling_code", "init", "torch_dtype", "max_context"} <= set(config["assumed"])
    assert "transformers" in config["assumed"]["modelling_code"]
    assert "memory" in config["assumed"]["modelling_code"]  # says plainly what it rests on
    assert "FLOAT32" in config["assumed"]["torch_dtype"]
    assert "WHAT THE CUT DISTORTS" in config["deployment"]


def test_every_key_of_the_catalog_row_is_in_the_file_and_only_the_depth_differs(resolved):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog beside the model-configs guide is not here")
    config = resolved[2]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == config["source"])
    assert row["name"] == "Falcon-H1-34B-Instruct" and len(row["config"]) == 42
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert value == config["reduced_from"][key] == 72 and 4 <= config[key] <= 6
        else:
            assert key in config and config[key] == value, key


def test_the_files_arithmetic_adds_up_to_the_tree_init_params_makes(resolved):
    """The deployment's parameter counts, from the file's numbers alone, and
    against the shapes ``init_params`` makes from the program's config (nothing
    is computed: ``jax.eval_shape``)."""
    import jax
    import numpy as np
    from deepspeed_tpu.models import falcon_h1
    c = resolved[2]
    M, V, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    d_inner, gn = c["mamba_d_ssm"], c["mamba_n_groups"] * c["mamba_d_state"]
    conv_dim = d_inner + 2 * gn
    assert (d_inner, conv_dim) == (4096, 5120) and d_inner == c["mamba_n_heads"] * c["mamba_d_head"]
    attn = 2 * M * c["num_attention_heads"] * c["head_dim"] \
        + 2 * M * c["num_key_value_heads"] * c["head_dim"]
    mamba = M * (d_inner + conv_dim + c["mamba_n_heads"]) + d_inner * M \
        + conv_dim * (c["mamba_d_conv"] + 1) + d_inner + 3 * c["mamba_n_heads"]
    mlp = 3 * M * c["intermediate_size"]
    layer = attn + mamba + mlp + 2 * M
    ends = 2 * V * M + M
    assert (round(attn / 1e6, 2), round(mamba / 1e6, 2), round(mlp / 1e6, 2)) == \
        (31.46, 68.35, 330.30)
    assert round(layer / 1e6, 2) == 430.12 and round(2 * layer / GIB, 3) == 0.801
    total = L * layer + ends
    family = harness._load_module(tiny.REPO, "models", "falcon_h1")
    cfg = family.program_config(c)
    tree = jax.eval_shape(lambda: falcon_h1.init_params(cfg, param_dtype=cfg.dtype)[1])
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree)) == total
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree["layers_0"])) == layer
    # the pools beside them
    sm = c["engine"]["state_manager"]
    slot = L * (4 * c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]
                + 2 * (c["mamba_d_conv"] - 1) * conv_dim)
    block = c["engine"]["kv_block_size"] * L * 2 * c["num_key_value_heads"] * c["head_dim"] * 2
    state, kv = sm["max_tracked_sequences"] * slot, sm["memory_config"]["size"] * block
    held = (2 * total + state + kv) / (16 * GIB)
    assert held >= 0.70
    if L == 6:
        assert round(total / 1e6, 1) == 5254.6 and round(2 * total / GIB, 2) == 9.79
        assert round(state / GIB, 2) == 1.51 and round(kv / GIB, 2) == 1.03
        for said in ("430.12 M", "0.801 GiB", "5254.6 M", "9.79 GiB", "1.51 GiB", "1.03 GiB",
                     "77.1 %"):
            assert said in c["deployment"], said
        assert round(100 * held, 1) == 77.1


def test_every_engine_key_says_why(resolved):
    config = resolved[2]
    assert {k for k in config if k.endswith("_why")} == {"engine_why", "serving_why"}
    engine = config["engine"]
    keys = {"kv_block_size", "memory_config"} | (set(engine["state_manager"]) - {"memory_config"})
    assert keys | {"correct"} == set(config["engine_why"])
    assert set(config["serving"]) == set(config["serving_why"])
    assert all(len(why) > 40 for why in config["engine_why"].values())
    assert "FALSE" in config["engine_why"]["correct"]  # the controls' readings


def test_its_metrics_are_listed_and_each_new_one_names_a_reader_that_exists(resolved):
    bench = resolved[0]
    traced = {m["name"] for m in harness.metrics_for(bench, CELL, True)}
    assert set(NEW_METRICS) <= traced
    assert {"attn_busy_pct", "paged_attn_busy_pct", "paged_prefill_busy_pct", "dense_ffn_busy_pct",
            "ssm_in_place_row_share", "device_idle_pct", "kv_blocks_peak_pct",
            "compiles_in_window", "serve_generated_tokens_per_s", "step_device_any_p50_ms",
            "sched_seqs_per_step", "step_decode_p50_ms", "idle_in_engine_pct"} <= traced
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


def test_a_program_without_the_family_exits_at_once_with_a_message():
    code = ("import sys\n"
            "sys.modules['deepspeed_tpu.models.falcon_h1'] = None\n"
            "from benchmark import harness\n"
            f"harness._load_module({tiny.REPO!r}, 'models', 'falcon_h1')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=tiny.REPO, capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=tiny.REPO, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0
    assert "a Mamba-2 mixer beside attention" in done.stderr
    assert "Nothing was measured" in done.stderr


def test_the_family_builds_the_programs_config_from_the_file(resolved):
    family = harness._load_module(tiny.REPO, "models", "falcon_h1")
    cfg = family.program_config(resolved[2])
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.hidden_size) == \
        (resolved[2]["num_hidden_layers"], 261120, 5120)
    assert (cfg.d_inner, cfg.conv_dim, cfg.in_proj_width) == (4096, 5120, 9248)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (20, 4, 128)
    assert cfg.rope_theta == 1e11 and cfg.ssm_multipliers[3] == 0.5
    assert len(cfg.ssm_multipliers) == 5 and len(cfg.mlp_multipliers) == 2
    hash(cfg)


# ---------------------------------------------------------------- readers ---
def test_a_piece_of_a_state_is_the_mixers_time_and_the_keys_are_mapped(resolved):
    """At 256 state columns the compiler re-lays the pool in two halves around a
    ``put`` program's update: ``f32[6,64,32,128,128]`` is a PIECE of a state and
    is given the form of the scoped operation nearest in time; the accepted
    reader alone would leave it under no scope. The least is ``ssm_work``
    (unchanged) at this family's widths."""
    config = resolved[2]
    mapped = trace_h1_ssm_roofline.hybrid_keys(config)
    L = config["num_hidden_layers"]
    assert (mapped["mamba_num_heads"], mapped["mamba_head_dim"], mapped["n_groups"],
            mapped["ssm_state_size"], mapped["conv_kernel"]) == (32, 128, 2, 256, 4)
    assert mapped["hybrid_override_pattern"] == "M" * L
    assert trace_h1_ssm_roofline.hybrid_keys({"hidden_size": 64}) is None
    scan = "jit(_forward_impl)/ssm/scan/dot_general:"
    step = "jit(_decode_loop_impl)/while/body/closed_call/ssm/step/mul:"
    half = f"%slice.7 = f32[{L},64,32,128,128]{{4,3,2,1,0:T(8,128)}} slice(%p)"
    whole = "%fusion.3 = f32[32,32,128,256]{3,2,1,0} fusion(%a)"
    other = "%fusion.9 = f32[32,32,64,128]{3,2,1,0} fusion(%b)"
    ops = [(0, 100, "%fusion.1 = f32[256,32,128]{2,1,0} fusion(%x)"), (100, 160, half),
           (160, 200, whole), (200, 230, other),
           (1000, 1100, "%ssm_step_in_place.1 = f32[32,32,1,128]{3,2,1,0} custom-call(%y)")]
    scopes = {ops[0][2]: scan, ops[4][2]: step}
    given = {}
    paths = trace_h1_ssm_roofline.attributed(ops, scopes, mapped, given)
    assert [p[2] for p in paths] == [scan, "ssm/scan/unscoped", "ssm/scan/unscoped", "", step]
    assert given == pytest.approx({"ssm/scan/unscoped": 100e-9})
    # the accepted attribution knows the whole state only
    assert [p[2] for p in trace_ssm_roofline.attributed(ops, scopes, mapped)][1] == ""

    trace = SimpleNamespace(devices={0: ops}, host=[])
    slice_ = SimpleNamespace(began=0.0, ended=4.0, sync_clock=None)
    spans = [{"name": "decode_loop", "cat": "inference", "ts_us": 10, "dur_us": 5,
              "args": {"steps": 1, "ssm_tokens": 32 * L, "ssm_segments": 32 * L}}]
    run = {"trace_slice": slice_, "spans": spans, "t0": 0.0, "seconds": 45.0, "trace_path": None}
    logged = []
    env = {"trace": trace, "peaks": opcount.PEAKS["TPU v5 lite"], "config": config,
           "log": logged.append, "host_phases": ([], scopes)}
    work = trace_ssm_roofline.ssm_work(32, 32, 32, 128, 2, 256)
    assert work[1] >= 32 * 2 * 4 * 2**20  # a 4 MiB state read and written a row
    least = L * opcount.roofline_seconds(*work, env["peaks"])[0]
    got = trace_h1_ssm_roofline.read(run, {"pattern": "(^|/)ssm/step(/|$)", "kind": "step"}, env)
    assert got == pytest.approx(100.0 * least / 100e-9)
    busy = trace_h1_scope_busy.read(run, {"pattern": "(^|/)ssm(/|$)"}, env)
    assert busy == pytest.approx(100.0 * 300 / 330)
    rest = trace_h1_scope_busy.read(
        run, {"pattern": "(^|/)(attn|mlp|embed|unembed|ssm)(/|$)", "invert": True}, env)
    assert rest == pytest.approx(100.0 * 30 / 330)
    assert any("ssm/scan/unscoped" in line for line in logged)


def test_the_paged_count_takes_the_heads_width_from_the_configuration(resolved):
    """Four sequences prefilled to 300 tokens outside the slice, then one decode
    step of theirs inside it: ``opcount.paged_attention`` of four queries at
    context 301, once a layer, at 20 heads over 4 of 128 (the runner's
    ``hidden_size / heads`` would price heads of 256)."""
    config = resolved[2]
    L = config["num_hidden_layers"]
    assert config["hidden_size"] // config["num_attention_heads"] == 256 != config["head_dim"]
    ops = [(0, 50_000, "%paged_attention_update.3 = bf16[32,20,128]{2,1,0} custom-call(%q)")]
    trace = SimpleNamespace(devices={0: ops}, host=[])
    slice_ = SimpleNamespace(began=1.0, ended=5.0, sync_clock=None)

    def step(name, ts, tokens):
        return [{"name": name, "cat": "serving", "ts_us": ts, "dur_us": 10,
                 "args": {"uid": u, "tokens": tokens}} for u in range(4)]

    spans = step("prefill", 5, 300) + step("decode", 2_000_000, 1)
    # what the runner hands the accepted reader: hidden_size / heads for the heads' width
    model = {"n_heads": 20, "n_kv_heads": 4, "head_dim": 256, "n_layers": L, "block_size": 128}
    run = {"trace_slice": slice_, "spans": spans, "t0": 0.0, "seconds": 45.0, "trace_path": None,
           "model": model}
    env = {"trace": trace, "peaks": opcount.PEAKS["TPU v5 lite"], "config": config,
           "log": lambda message: None}
    params = {"pattern": "paged_attention_update", "kernel_max_tokens": 32}
    work = opcount.paged_attention([[301]] * 4, 20, 4, 128, 128)
    least = L * opcount.roofline_seconds(*work, env["peaks"])[0]
    assert trace_h1_paged_roofline.read(run, params, env) == pytest.approx(100 * least / 50e-6)
    assert opcount.paged_attention([[301]] * 4, 20, 4, 256, 128)[1] > 1.9 * work[1]
    # a configuration without a head_dim of its own is the accepted reader's
    assert trace_h1_paged_roofline.read(run, params, dict(env, config={"engine": {}})) is None


def test_the_new_readers_find_nothing_on_a_program_without_the_family_and_do_not_raise(resolved):
    """On the parent the trace has no ``ssm`` scope and the spans none of the
    counts: each reader returns None (the metric is left out of the line)."""
    config = resolved[2]
    trace = SimpleNamespace(devices={0: [(0, 1000, "fusion.1"), (1000, 3000, "fusion.2")]},
                            host=[])
    slice_ = SimpleNamespace(began=0.0, ended=4.0, sync_clock=None)
    spans = [{"name": "decode_loop", "cat": "inference", "ts_us": 10, "dur_us": 5,
              "args": {"steps": 8}}]
    run = {"trace_slice": slice_, "spans": spans, "t0": 0.0, "seconds": 45.0, "trace_path": None}
    env = {"trace": trace, "peaks": opcount.PEAKS["TPU v5 lite"], "config": config,
           "log": lambda message: None, "host_phases": ([], {})}
    mistral = harness.resolve(tiny.REPO, "mistral-longdoc-closed")[2]
    for env_ in (env, dict(env, config=mistral),
                 dict(env, host_phases=([], {"fusion.1": "jit(f)/mlp/dot:"}))):
        for kind in ("scan", "step"):
            assert trace_h1_ssm_roofline.read(
                run, {"pattern": f"(^|/)ssm/{kind}(/|$)", "kind": kind}, dict(env_)) is None
        assert trace_h1_scope_busy.read(run, {"pattern": "(^|/)ssm(/|$)"}, dict(env_)) is None
        assert trace_h1_paged_roofline.read(
            run, {"pattern": "paged_attention_update", "kernel_max_tokens": 32},
            dict(env_)) is None
    for name in ("unembed_busy_pct", "seq_bucket_fill"):  # data files on accepted readers
        with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{name}.json")) as f:
            spec = json.load(f)
        reader = harness._load_module(tiny.REPO, "readers", spec["reader"])
        assert reader.read(run, spec["params"], dict(env)) is None


# -------------------------------------------------------------- rehearsal ---
TINY = {
    "family": "falcon_h1", "mode": "serve", "torch_dtype": "float32", "num_hidden_layers": 2,
    "hidden_size": 64, "intermediate_size": 96, "vocab_size": 256, "num_attention_heads": 5,
    "num_key_value_heads": 1, "head_dim": 16, "mamba_d_ssm": 48, "mamba_n_heads": 6,
    "mamba_d_head": 8, "mamba_n_groups": 2, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "mamba_expand": 2, "rms_norm_eps": 1e-5, "rope_theta": 1e11,
    "max_position_embeddings": 512, "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1.0,
    "attention_out_multiplier": 0.0375, "key_multiplier": 0.011048543456039804,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "reference_pad_to": 96,
    "engine": {"kv_block_size": 8,
               "state_manager": {"memory_config": {"mode": "allocate", "size": 256},
                                 "max_context": 128, "max_ragged_batch_size": 32,
                                 "max_ragged_sequence_count": 16, "max_tracked_sequences": 20}},
    "serving": {"decode_chunk": 4, "queue_capacity": 1024},
}


def _tiny_root(tmp_path):
    """A throw-away benchmark root with the cell ``tiny-falcon-h1-chat``."""
    root = tiny.make_root(tmp_path / "root")
    tiny.write_json(os.path.join(root, "benchmark", "configs", "tiny-falcon-h1.json"), TINY)
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
    bench["configs"].append({"name": "tiny-falcon-h1", "source": "none: a test preset",
                             "file": "benchmark/configs/tiny-falcon-h1.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "tiny-falcon-h1-chat", "config": "tiny-falcon-h1",
                               "traffic": "tiny-chat32", "chips": 1, "why": "CPU rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "tiny-mixtral-closed" in m["workloads"] \
                and m["name"] not in NOT_ITS:
            m["workloads"].append("tiny-falcon-h1-chat")
    tiny.write_json(path, bench)
    return root


def test_the_cell_rehearses_at_a_tiny_preset(tmp_path):
    """Two layers, 8-token scan chunks under a 32-token budget (the check's four
    prompts prefilled together in shares of 8: every ``put`` is four segments),
    ten clients in ONE sequence bucket of 16, five queries a K/V head, through
    the harness's test-only entry: the family, the traffic, the new metric
    files and readers all load, and the check holds prefill in chunks with both
    kinds of state carried, ``put`` and ``decode_loop`` to the float32
    reference. The harness guesses sequence buckets of 8 and 16 by the old
    rule; the guesses the program lacks are logged, not fatal."""
    root = _tiny_root(tmp_path)
    out = io.StringIO()
    assert harness.run_cell(root, "tiny-falcon-h1-chat", 2**31 + 47, 1.5, 1, rehearsal=True,
                            out=out) == 0  # traced: what an untraced run does, and the readers
    text = out.getvalue()
    line = tiny.last_line(text)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert text.count("-> ok") >= 4 and "WRONG" not in text
    assert line["metrics"]["cpu_rehearsal.compiles_in_window"]["value"] == 0
    assert "guessed buckets are not among the engine's programs" in text
    # every metric this cell brings reads only beside a chip's trace
    for name in NEW_METRICS:
        assert f"metric {name}: nothing to read, left out" in text


def test_the_controls_run_through_the_harness_comparison_at_a_tiny_preset(tmp_path, capsys):
    """``benchmark/tools/controls_h1.py`` on the tiny cell, float32, two seeds'
    worth of its loop cut to the controls that cost one engine each: the engine
    as built reads ``correct``; the state or the convolution's tail not carried,
    a multiplier dropped, a mixer left out of the sum each read false; the exit
    code says every control was caught; a control restores what it patched."""
    from benchmark.tools import controls_h1
    root = _tiny_root(tmp_path)
    rc = controls_h1.main(["--workload", "tiny-falcon-h1-chat", "--seeds", str(2**31 + 47),
                           "--rehearsal", "1", "--root", root])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    read = {name: c["correct"] for name, c in result["controls"].items()}
    assert list(read) == ["baseline"] + list(controls_h1.CONTROLS)
    assert read.pop("baseline") is True and not any(read.values()), read
    assert rc == 0 and result["controls"]["baseline"]["rows"] == 4 * 8
    assert result["controls"]["baseline"]["median_log2"] < result["tolerance_log2"] - 8
    from deepspeed_tpu.inference.v2.model_implementations import falcon_h1_v2, mamba2_base
    from deepspeed_tpu.inference.v2.modules import ssm
    assert ssm.scan_ragged.__module__ == ssm.conv_ragged.__module__ == ssm.__name__
    assert falcon_h1_v2.FalconH1V2Model._mamba_phase is mamba2_base.Mamba2Model._mamba_phase
    assert falcon_h1_v2.FalconH1V2Model._attn_phase.__qualname__.startswith("FalconH1V2Model")
