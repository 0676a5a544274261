"""The Granite 4.0-H cell's files (PR 65): the configuration against the catalog
row key by key but ``reduced`` and against its own arithmetic (the bytes
re-reckoned from the file are the tree ``init_params`` makes), the traffic and
the metrics as the issue gives them, the family module refuses a program
without ``GraniteMoeHybridConfig`` at once, the ``h1_*`` readers' count at nine
mixers in ten layers, the new metric's pattern, and the cell and its controls
rehearsed at a tiny preset. Every entry is found BY NAME: nothing here pins a
position, a count or a whole ``workloads`` list of ``BENCHMARK.json``."""

import io
import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness, opcount
from benchmark.readers import trace_h1_scope_busy, trace_h1_ssm_roofline, trace_ssm_roofline
from tests.benchmark import tiny

CELL, CONFIG = "granite-4.0-h-small-chat32-closed", "granite-4.0-h-small-serve-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 40, "num_local_experts": 72, "vocab_size": 100352}
NEW_METRIC = "ssm_proj_busy_pct"
# accepted metrics that would MISREAD this cell and are not its: ``h1_unscoped_busy_pct``'s
# pattern has no ``moe`` (it would call the experts unscoped) and ``unscoped_hybrid_busy_pct``
# / ``ssm_*`` take Nemotron's key names; ``paged_attn_roofline`` prices a K/V layer a model
# layer (one in ten keeps K/V) and ``h1_paged_token_roofline`` wants a ``head_dim`` key the
# source has not; ``moe_grouped_roofline`` / ``moe_banks_per_assignment`` price every choice
# the router made, twice what lands here; ``dense_ffn_busy_pct`` a scope no layer has
NOT_ITS = {"h1_unscoped_busy_pct", "unscoped_hybrid_busy_pct", "ssm_busy_pct",
           "ssm_step_roofline", "ssm_scan_roofline", "ssm_rows_per_step", "paged_attn_roofline",
           "h1_paged_token_roofline", "moe_grouped_roofline", "moe_banks_per_assignment",
           "moe_relu2_grouped_roofline", "dense_ffn_busy_pct", "unscoped_busy_pct",
           "hbm_peak_pct", "kda_busy_pct", "kl_latent_busy_pct"}
GIB = 2**30


@pytest.fixture(scope="module")
def resolved():
    return harness.resolve(tiny.REPO, CELL)


@pytest.fixture(scope="module")
def family():
    return harness._load_module(tiny.REPO, "models", "granitemoehybrid")


def test_the_cell_is_the_one_the_issue_names(resolved):
    bench, cell, config, traffic = resolved
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "chat32-closed", 1) \
        and len(cell["why"]) <= 200
    assert config["family"] == "granitemoehybrid" and config["mode"] == "serve"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(REDUCED) and config["reduced_from"] == REDUCED
    assert entry["source"] == config["source"] and entry["file"].endswith(f"{CONFIG}.json")
    assert len(entry["why"]) <= 200
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] == [CELL]
    engine = config["engine"]
    sm = engine["state_manager"]
    assert (engine["kv_block_size"], sm["max_context"], sm["max_ragged_batch_size"],
            sm["max_ragged_sequence_count"], sm["max_tracked_sequences"],
            sm["memory_config"]["size"], config["serving"]["decode_chunk"]) == \
        (128, 2048, 256, 32, 64, 1024, 8)
    p = traffic["params"]
    assert traffic["kind"] == "closed_clients" and (p["clients"], p["requests_per_client"]) == (32, 24)
    assert p["prompt"] == {"dist": "uniform", "min": 256, "max": 768}
    assert p["output"] == {"dist": "lognormal", "median": 512, "sigma": 0.25, "min": 320,
                           "max": 832} and p["temperature"] == 0.0
    assert p["prompt"]["max"] + p["output"]["max"] == 1600 <= sm["max_context"]
    assert config["reference_pad_to"] >= p["prompt"]["max"] + 8
    # a put step is ONE chunk of the ragged scan
    assert config["mamba_chunk_size"] == sm["max_ragged_batch_size"]
    share = config["deployment_share"]
    assert (share["chips_sharing_a_layer"], share["routed_over"], share["experts_held"],
            share["expert_rank"], share["vocabulary_slices"]) == (2, 72, 36, 0, 2)
    assert share["experts_held"] == config["num_local_experts"]
    assert {"modelling_code", "init", "init_gains", "torch_dtype", "tie_word_embeddings"} <= \
        set(config["assumed"])
    assert "WHAT THE CUT DISTORTS" in config["deployment"]
    assert "logits_scaling" in config["engine_why"]["correct"]


def test_the_file_is_the_catalog_row_key_by_key_but_reduced(resolved):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog beside the model-configs guide is not here")
    config = resolved[2]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == config["source"])
    assert row["name"] == "granite-4.0-h-small" and len(row["config"]) >= 33
    for key, value in row["config"].items():
        if key in REDUCED:
            assert value == REDUCED[key] and config[key] != value
        else:
            assert config[key] == value, key
    # layer_types is kept whole; the ten layers served are one whole period, 9 : 1 as 36 : 4
    served = config["layer_types"][:config["num_hidden_layers"]]
    assert len(config["layer_types"]) == 40 and config["layer_types"] == served * 4
    assert (served.count("mamba"), served.count("attention")) == (9, 1)
    # the floors: a whole period and four layers, 8+ experts, an eighth of the rows
    assert config["num_local_experts"] >= 8 and config["vocab_size"] * 8 >= row["vocab_size"]


def test_the_bytes_re_reckoned_from_the_file_are_the_tree_init_params_makes(resolved, family):
    """The issue's arithmetic, from the file's numbers alone, against the tree
    the program makes for the file (``jax.eval_shape``: nothing is allocated)."""
    import jax
    c = resolved[2]
    M, V, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    H, P, N, G, K = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                     c["mamba_n_groups"], c["mamba_d_conv"])
    D, conv = H * P, H * P + 2 * G * N
    assert D == c["mamba_expand"] * M and (D, conv, D + conv + H) == (8192, 8448, 16768)
    small = conv * K + conv + 3 * H + D  # float32: the convolution, dt_bias / A_log / D, the norm
    mamba = M * (D + conv + H) + D * M + small
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    head = M // heads
    attention = 2 * M * heads * head + 2 * M * kv * head
    held, F, Fs = c["num_local_experts"], c["intermediate_size"], c["shared_intermediate_size"]
    banks, router, shared = held * 3 * M * F, M * c["reduced_from"]["num_local_experts"], 3 * M * Fs
    ffn = banks + router + shared + 2 * M  # and the layer's two norms
    assert [round(x / 1e6, 2) for x in (mamba, attention, banks, router, shared, mamba + ffn,
                                        attention + ffn, V * M)] == \
        [102.29, 41.94, 339.74, 0.29, 18.87, 461.20, 400.86, 205.52]
    params = 9 * (mamba + ffn) + attention + ffn + V * M + M
    f32 = 9 * small + n * (router + 2 * M) + M
    cfg = family.program_config(c)
    from deepspeed_tpu.models import granitemoehybrid
    tree = jax.eval_shape(lambda: granitemoehybrid.init_params(cfg, param_dtype=cfg.dtype)[1])
    leaves = jax.tree.leaves(tree)
    assert [sum(int(np.prod(x.shape)) for x in leaves),
            sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)] == \
        [params, 2 * params + 2 * f32]
    nbytes = 2 * params + 2 * f32
    assert "lm_head" not in tree and round(params / 1e6, 2) == 4757.21 \
        and nbytes == 9521408512 and round(nbytes / GIB, 2) == 8.87
    sm = c["engine"]["state_manager"]
    from deepspeed_tpu.inference.v2.modules import ssm
    tails = ssm.conv_slot(K - 1, conv)
    slot = 9 * (H * P * N * 4 + int(np.prod(tails)) * 2)
    block = c["engine"]["kv_block_size"] * 1 * 2 * kv * head * 2  # ONE layer keeps K/V
    assert (tails, round(slot / 2**20, 2), block) == ((8, 3200), 36.44, 512 * 1024)
    pools = sm["max_tracked_sequences"] * slot + sm["memory_config"]["size"] * block
    assert round(sm["max_tracked_sequences"] * slot / GIB, 2) == 2.28
    assert 0.72 < (nbytes + pools) / (16 * GIB) < 0.73 and nbytes / (16 * GIB) > 0.55
    for said in ("4757.21 M", "9,521,408,512 bytes", "8.87 GiB", "2.28 GiB", "36.44 MiB",
                 "0.50 GiB", "72.8 %"):
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
    assert {NEW_METRIC, "h1_ssm_busy_pct", "h1_ssm_step_roofline", "h1_ssm_scan_roofline",
            "moe_busy_pct", "moe_route_busy_pct", "moe_shared_busy_pct",
            "moe_local_assignment_share", "moe_banks_per_local_assignment",
            "moe_rows_walked_share", "moe_share_grouped_roofline", "attn_busy_pct",
            "paged_attn_busy_pct", "unembed_busy_pct", "seq_bucket_fill",
            "ssm_in_place_row_share", "ssm_state_slots_peak_pct", "device_idle_pct",
            "kv_blocks_peak_pct", "compiles_in_window", "step_device_any_p50_ms",
            "step_decode_p50_ms", "sched_seqs_per_step", "idle_in_engine_pct",
            "idle_waiting_pct", "idle_in_host_stall_pct", "gc_pause_ms_per_s"} <= traced
    assert not NOT_ITS & traced
    assert {m["name"] for m in harness.metrics_for(bench, CELL, False)} == \
        {"tpot_p50_ms", "setup_s"}
    entry = next(m for m in bench["per_layer"] if m["name"] == NEW_METRIC)
    assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms" \
        and entry["source"] == "device_trace"
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] != NEW_METRIC}
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        assert f"`{NEW_METRIC}`" in f.read()
    with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{NEW_METRIC}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "trace_h1_scope_busy"  # a data file on an accepted reader


def test_a_program_without_the_family_exits_at_once_with_a_message():
    """The parent's tree plus this PR's benchmark files: the family module's
    import of the program's config fails, and the run exits in seconds with a
    sentence that says why, before any weight is made."""
    code = ("import sys\n"
            "sys.modules['deepspeed_tpu.models.granitemoehybrid'] = None\n"
            "from benchmark import harness\n"
            f"harness._load_module({tiny.REPO!r}, 'models', 'granitemoehybrid')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=tiny.REPO, capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=tiny.REPO, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0
    said = done.stderr.replace("\n", " ")
    assert "a Mamba-2 or a position-free softmax mixer AND routed experts" in said
    assert "a tied head" in said and "Nothing was measured" in said


def test_the_family_builds_the_programs_config_from_the_file(resolved, family):
    c = resolved[2]
    cfg = family.program_config(c)
    assert (cfg.num_local_experts, cfg.experts_held, cfg.expert_rank, cfg.first_expert_held) == \
        (72, 36, 0, 0)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.hidden_size, cfg.head_dim) == \
        (10, 50176, 4096, 128)
    assert cfg.layer_types == ("mamba", ) * 5 + ("attention", ) + ("mamba", ) * 4
    assert (cfg.layers_of("mamba"), cfg.layers_of("attention")) == \
        ((0, 1, 2, 3, 4, 6, 7, 8, 9), (5, ))
    assert (cfg.d_inner, cfg.conv_dim, cfg.in_proj_width, cfg.mamba_chunk_size) == \
        (8192, 8448, 16768, 256)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12, 0.0078125, 0.22, 16)
    assert cfg.query_scale == pytest.approx(128**0.5 / 128) and cfg.tie_word_embeddings
    hash(cfg)
    # the file records the init constants that are the benchmark's own: the program's
    gains = c["assumed"]["init_gains"]
    from deepspeed_tpu.models import granitemoehybrid
    assert (granitemoehybrid.EMBED_INIT_GAIN, granitemoehybrid.QUERY_INIT_GAIN,
            granitemoehybrid.ROUTER_INIT_GAIN) == (gains["embed"], gains["query"], gains["router"])


# ---------------------------------------------------------------- readers ---
def _reader_env(config, ops, scopes, logged=None):
    trace = SimpleNamespace(devices={0: ops}, host=[])
    return {"trace": trace, "peaks": opcount.PEAKS["TPU v5 lite"], "config": config,
            "log": (logged.append if logged is not None else lambda message: None),
            "host_phases": ([], scopes)}


def test_the_h1_readers_count_nine_mixers_in_ten_layers(resolved):
    """``trace_h1_ssm_roofline`` divides a span's counts by ``steps x
    num_hidden_layers`` (Falcon-H1 has a mixer a layer) and multiplies the
    least back by the same: ``ssm_work`` is linear in rows and segments, so at
    9 mixers in 10 layers the normalisation cancels and the least is nine
    mixers' work — held here by the number, at this family's widths."""
    config = resolved[2]
    mapped = trace_h1_ssm_roofline.hybrid_keys(config)
    assert (mapped["mamba_num_heads"], mapped["mamba_head_dim"], mapped["n_groups"],
            mapped["ssm_state_size"], mapped["conv_kernel"]) == (128, 64, 1, 128, 4)
    L, mixers, rows, steps = config["num_hidden_layers"], 9, 32, 8
    step = "jit(_decode_loop_impl)/while/body/closed_call/ssm/step/mul:"
    ops = [(0, 2_000_000, "%ssm_step_in_place.1 = f32[32,128,64]{2,1,0} custom-call(%y)")]
    slice_ = SimpleNamespace(began=0.0, ended=4.0, sync_clock=None)
    # what ``Mamba2Model.batch_counts`` puts on a chunk of 8 steps of 32 rows: x 9 mixers
    spans = [{"name": "decode_loop", "cat": "inference", "ts_us": 10, "dur_us": 5,
              "args": {"steps": steps, "ssm_tokens": steps * rows * mixers,
                       "ssm_segments": steps * rows * mixers}}]
    run = {"trace_slice": slice_, "spans": spans, "t0": 0.0, "seconds": 45.0, "trace_path": None}
    env = _reader_env(config, ops, {ops[0][2]: step})
    work = trace_ssm_roofline.ssm_work(rows, rows, 128, 64, 1, 128)
    assert work[1] >= rows * 2 * 4 * 2**20  # a 4 MiB state read and written a row
    least = steps * mixers * opcount.roofline_seconds(*work, env["peaks"])[0]
    got = trace_h1_ssm_roofline.read(run, {"pattern": "(^|/)ssm/step(/|$)", "kind": "step"}, env)
    assert got == pytest.approx(100.0 * least / 2e-3)
    # NOT ten layers' worth: the reading a mixer a layer would give is 10 / 9 of it
    wrong = steps * L * opcount.roofline_seconds(*work, env["peaks"])[0]
    assert got < 100.0 * wrong / 2e-3 * 0.95
    # the tiny widths of the tier-1 engine too: linear whatever the widths
    tiny_work = trace_ssm_roofline.ssm_work(9 * 5 / 10, 9 * 3 / 10, 16, 8, 1, 16)
    whole = trace_ssm_roofline.ssm_work(9 * 5, 9 * 3, 16, 8, 1, 16)
    assert [10 * w for w in tiny_work] == pytest.approx(list(whole))


def test_the_new_metric_reads_the_projections_apart_from_the_recurrence(resolved):
    """``ssm_proj_busy_pct``: ``in_proj`` and ``out_proj`` (the compiler's own
    prefetch of ``in_proj``'s kernel among them, given that scope by the array
    it makes), and neither the recurrence, the convolution, the gated norm nor
    the experts. On a program without the scope: nothing to read, no raise."""
    config = resolved[2]
    with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{NEW_METRIC}.json")) as f:
        spec = json.load(f)
    reader = harness._load_module(tiny.REPO, "readers", spec["reader"])
    loop = "jit(_decode_loop_impl)/while/body/closed_call/"
    named = [("in_proj", 300), ("conv", 50), ("step", 400), ("gate_norm", 30), ("out_proj", 200)]
    ops, scopes, t = [], {}, 0
    for i, (scope, ns) in enumerate(named):
        name = f"%fusion.{i} = bf16[32,4096]{{1,0}} fusion(%x{i})"
        ops.append((t, t + ns, name))
        scopes[name] = f"{loop}ssm/{scope}/dot_general:"
        t += ns
    prefetch = "%copy-done.1 = bf16[4096,16768]{1,0:T(8,128)(2,1)S(1)} copy-done(%c)"
    experts = "%grouped_matmul.1 = bf16[384,1536]{1,0} custom-call(%r)"
    ops += [(t, t + 20, prefetch), (t + 20, t + 1020, experts)]
    scopes[experts] = f"{loop}moe/experts/grouped_matmul:"
    run = {"trace_slice": None, "spans": [], "t0": 0.0, "seconds": 45.0, "trace_path": None}
    logged = []
    got = reader.read(run, spec["params"], _reader_env(config, ops, scopes, logged))
    assert got == pytest.approx(100.0 * (300 + 200 + 20) / 2000)
    whole = trace_h1_scope_busy.read(run, {"pattern": "(^|/)ssm(/|$)"},
                                     _reader_env(config, ops, scopes))
    assert whole == pytest.approx(100.0 * 1000 / 2000)
    rx = re.compile(spec["params"]["pattern"])
    assert [bool(rx.search(f"ssm/{s}")) for s, _ in named] == [True, False, False, False, True]
    # the parent: no ``ssm`` scope in the trace, or another family's configuration
    bare = [(0, 1000, "fusion.1"), (1000, 3000, "fusion.2")]
    assert reader.read(run, spec["params"], _reader_env(config, bare, {})) is None
    mistral = harness.resolve(tiny.REPO, "mistral-longdoc-closed")[2]
    assert reader.read(run, spec["params"], _reader_env(mistral, ops, scopes)) is None


# -------------------------------------------------------------- rehearsal ---
TINY = {
    "family": "granitemoehybrid", "mode": "serve", "torch_dtype": "float32",
    "num_hidden_layers": 4, "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "hidden_size": 64, "vocab_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_bias": False, "attention_multiplier": 0.125, "position_embedding_type": "nope",
    "mamba_n_heads": 16, "mamba_d_head": 8, "mamba_n_groups": 1, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_chunk_size": 8, "mamba_expand": 2, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "intermediate_size": 32, "shared_intermediate_size": 48,
    "num_local_experts": 4, "num_experts_per_tok": 3, "hidden_act": "silu",
    "embedding_multiplier": 12, "residual_multiplier": 0.22, "logits_scaling": 16,
    "normalization_function": "rmsnorm", "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "max_position_embeddings": 512, "rope_theta": 10000, "rope_scaling": None,
    "deployment_share": {"chips_sharing_a_layer": 2, "routed_over": 8, "experts_held": 4,
                         "expert_rank": 1},
    "reference_pad_to": 96,
    "engine": {"kv_block_size": 8,
               "state_manager": {"memory_config": {"mode": "allocate", "size": 128},
                                 "max_context": 128, "max_ragged_batch_size": 32,
                                 "max_ragged_sequence_count": 8, "max_tracked_sequences": 16},
               "expert_parallel": {"capacity_factor": 3.0}},
    "serving": {"decode_chunk": 4, "queue_capacity": 1024},
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A throw-away benchmark root with the cell ``tiny-granite-chat``."""
    root = tiny.make_root(tmp_path_factory.mktemp("granite") / "root")
    tiny.write_json(os.path.join(root, "benchmark", "configs", "tiny-granite.json"), TINY)
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", "chat32-closed.json")) as f:
        traffic = json.load(f)
    traffic.update(tiny._TIMES)
    traffic["params"].update(clients=4, requests_per_client=40,
                             prompt={"dist": "uniform", "min": 20, "max": 72},
                             output={"dist": "lognormal", "median": 16, "sigma": 0.25, "min": 8,
                                     "max": 24})
    tiny.write_json(os.path.join(root, "benchmark", "traffic", "tiny-chat.json"), traffic)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-granite", "source": "none: a test preset",
                             "file": "benchmark/configs/tiny-granite.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "tiny-granite-chat", "config": "tiny-granite",
                               "traffic": "tiny-chat", "chips": 1, "why": "CPU rehearsal"})
    own = {m["name"] for m in harness.metrics_for(harness._load_json(
        os.path.join(tiny.REPO, "BENCHMARK.json")), CELL, True)}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and ("tiny-mixtral-closed" in m["workloads"] or m["name"] in own) \
                and m["name"] not in NOT_ITS and "tiny-granite-chat" not in m["workloads"]:
            m["workloads"].append("tiny-granite-chat")
    tiny.write_json(path, bench)
    return root


def test_the_cell_rehearses_at_a_tiny_preset(tiny_root):
    """Four layers (three Mamba-2 mixers, one attention layer), 4 of 8 experts
    held, the check's four prompts prefilled together in shares of 8, through
    the harness's test-only entry: the family, the traffic, the new metric's
    file and its reader all load, and the check holds prefill in chunks,
    ``put`` and ``decode_loop`` to the float32 reference."""
    out = io.StringIO()
    assert harness.run_cell(tiny_root, "tiny-granite-chat", 2**31 + 65, 1.5, 1, rehearsal=True,
                            out=out) == 0  # traced: what an untraced run does, and the readers
    text = out.getvalue()
    line = tiny.last_line(text)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert text.count("-> ok") >= 4 and "WRONG" not in text
    assert line["metrics"]["cpu_rehearsal.compiles_in_window"]["value"] == 0
    assert f"cpu_rehearsal.{NEW_METRIC}" in line["metrics"] \
        or f"metric {NEW_METRIC}: nothing to read" in text


@pytest.mark.parametrize("control", ["no_state_carry", "softmax_scale"])
def test_the_controls_run_through_the_harness_comparison_at_a_tiny_preset(tiny_root, capsys,
                                                                          control):
    """``benchmark/tools/controls_granite.py`` on the tiny cell, float32: a
    control of the program and one of its configuration read false (the engine
    as built reads ``correct`` in the rehearsal above; the family's tier-1 has
    every control and every multiplier against the reference), and a control
    restores what it patched."""
    from benchmark.tools import controls_granite
    rc = controls_granite.main(["--workload", "tiny-granite-chat", "--seed", str(2**31 + 65),
                                "--rehearsal", "1", "--root", tiny_root, "--controls", control])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    read = {name: c["correct"] for name, c in result["controls"].items()}
    assert read == {control: False} and rc == 0
    from benchmark.tools import controls_latent
    from deepspeed_tpu.inference.v2.modules import ssm
    assert ssm.scan_in_place.__module__ == ssm.__name__ == ssm.conv_ragged.__module__
    assert controls_latent.spoilt.__module__ == controls_latent.__name__
