"""The Trinity-Mini cell's files (PR 34): the configuration against the catalog
row, the traffic and the metrics resolve, the family module refuses a program
without ``AfmoeConfig`` at once, the cell rehearses at a tiny preset, and the
reference's routing on fixtures."""

import io
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, harness, opcount
from benchmark.readers import trace_mixed_paged_roofline
from benchmark.references import afmoe as reference
from tests.benchmark import tiny

CELL, CONFIG = "trinity-mini-reason-closed", "trinity-mini-serve-1chip"
NEW_METRICS = ("moe_shared_busy_pct", "attn_gate_norm_busy_pct", "dense_ffn_busy_pct",
               "paged_mixed_token_roofline")
# what the window groups give back while a sequence DECODES past the window, and the
# throughput the decode steps deliver: read from the program's spans and the host's clock
RELEASE_METRICS = ("kv_window_released_blocks", "kv_full_layer_blocks_pct")
GENERATED = "serve_generated_tokens_per_s"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def resolved():
    return harness.resolve(tiny.REPO, CELL)


def test_the_cell_is_the_one_the_issue_names(resolved):
    bench, cell, config, traffic = resolved
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason-closed", 1)
    assert config["family"] == "afmoe" and config["mode"] == "serve"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers"]
    assert entry["source"] == config["source"]
    assert config["reduced_from"] == {"num_hidden_layers": 32, "num_dense_layers": 2}
    sm = config["engine"]["state_manager"]
    assert (config["engine"]["kv_block_size"], sm["max_context"], sm["max_ragged_batch_size"],
            sm["max_ragged_sequence_count"], config["serving"]["decode_chunk"]) == \
        (64, 4096, 256, 8, 8)
    # dropless: capacity = tokens
    assert config["engine"]["expert_parallel"]["capacity_factor"] == \
        config["num_experts"] / config["num_experts_per_tok"] == 16.0
    p = traffic["params"]
    assert traffic["kind"] == "closed_clients" and (p["clients"], p["requests_per_client"]) == (8, 16)
    assert p["prompt"] == {"dist": "uniform", "min": 1536, "max": 2560}
    assert p["output"] == {"dist": "lognormal", "median": 768, "sigma": 0.25, "min": 512,
                           "max": 1280}
    assert p["temperature"] == 0.0 and p["prompt"]["max"] + p["output"]["max"] == 3840 <= \
        sm["max_context"]
    assert (traffic["lead_in_s"], traffic["drain_s"], traffic["trace_start_s"],
            traffic["trace_length_s"]) == (8.0, 6.0, 10.0, 4.0)
    # every request ends past the window, so each crosses it while decoding
    assert p["prompt"]["min"] + p["output"]["min"] >= config["sliding_window"] > p["prompt"]["min"]
    assert {k for k in config if k.endswith("_why")} == {"engine_why", "serving_why"}
    assert {"modelling_code", "init", "max_context"} <= set(config["assumed"])
    assert "transformers" in config["assumed"]["modelling_code"]


def test_every_number_of_the_catalog_row_is_in_the_file_and_depth_is_the_only_cut(resolved):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog beside the model-configs guide is not here")
    config = resolved[2]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == config["source"])
    assert row["name"] == "Trinity-Mini" and len(row["config"]) >= 30
    differs = [k for k, v in row["config"].items() if config.get(k, "absent") != v]
    assert sorted(differs) == ["num_dense_layers", "num_hidden_layers"]
    assert len(config["layer_types"]) == 32
    # the guide's floors: a whole period and four layers after the leading dense ones,
    # at least 8 routed experts, the whole vocabulary
    served = config["layer_types"][:config["num_hidden_layers"]]
    experts = served[config["num_dense_layers"]:]
    assert config["num_dense_layers"] == 1 and len(experts) >= 4
    assert sorted(experts[:4]) == ["full_attention"] + ["sliding_attention"] * 3
    assert config["num_experts"] == 128 and config["vocab_size"] == 200192


def test_the_weights_and_the_pool_are_seventy_percent_of_the_chip(resolved):
    config = resolved[2]
    layers, h, d = config["num_hidden_layers"], config["hidden_size"], config["head_dim"]
    dense = config["num_dense_layers"]
    heads, kv = config["num_attention_heads"] * d, config["num_key_value_heads"] * d
    attention = 3 * h * heads + 2 * h * kv  # q, gate, o; k, v
    expert = 3 * h * config["moe_intermediate_size"]
    sparse = (config["num_experts"] + config["num_shared_experts"]) * expert
    weights = 2 * (layers * attention + dense * 3 * h * config["intermediate_size"]
                   + (layers - dense) * sparse + 2 * config["vocab_size"] * h)
    assert weights / 2**30 == pytest.approx(7.90, abs=0.01)
    # five layers in the pattern s,s,s,f,s are five groups: a block id holds ONE layer
    windows = trace_mixed_paged_roofline.layer_windows(config)
    assert windows == [2048, 2048, 2048, 0, 2048]
    block = 2 * kv * config["engine"]["kv_block_size"] * 2
    assert block == 128 * 1024
    pool = config["engine"]["state_manager"]["memory_config"]["size"] * block
    hbm = opcount.PEAKS["TPU v5 lite"]["hbm_bytes"]
    assert 0.68 <= (weights + pool) / hbm <= 0.72
    assert weights / hbm > 0.25  # the weights alone clear the floor of a cell's size


def test_the_programs_to_warm_are_thirty_five(resolved):
    from benchmark.runners import serve
    _, _, config, traffic = resolved
    forward, loops = serve.reachable_programs(config["engine"], config["serving"],
                                              traffic["params"])
    assert (len(forward), len(loops)) == (30, 5)
    assert {mb for _, _, mb in forward} == {4, 8, 16, 32, 64}
    # the check's prompts are the distribution's four mid-quantiles: two a side of the window
    rng = np.random.default_rng(0)
    from benchmark.traffic_kinds import _draw
    lengths = sorted(_draw.lengths(traffic["params"]["prompt"], serve.CHECK_PROMPTS, rng))
    assert lengths == [1664, 1920, 2176, 2432]


def test_its_metrics_are_listed_and_the_ones_that_misprice_it_are_not(resolved):
    bench = resolved[0]
    traced = {m["name"] for m in harness.metrics_for(bench, CELL, True)}
    assert set(NEW_METRICS) <= traced
    assert not {"paged_attn_roofline", "paged_window_tiled_roofline",
                "paged_window_token_roofline", "moe_rows_per_assignment"} & traced
    assert {"moe_busy_pct", "moe_route_busy_pct", "attn_busy_pct", "paged_prefill_busy_pct",
            "paged_attn_busy_pct", "step_decode_p50_ms", "step_any_p50_ms", "device_idle_pct",
            "kv_blocks_peak_pct", "compiles_in_window"} <= traced
    # ``serve_tokens_per_s`` is not judged here (PERF.md section 6, PR 34: ~38 prompts of ~2k
    # tokens a window, each counted whole when its first token arrives, spread it by 5 %
    # between seeds against the 4 % a new cell is admitted under), nor what moves it
    assert {m["name"] for m in harness.metrics_for(bench, CELL, False)} == \
        {"tpot_p50_ms", "setup_s"} and "hbm_peak_pct" not in traced
    added = NEW_METRICS + RELEASE_METRICS + (GENERATED, )
    assert set(added) <= traced
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in added}
    for name in added:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["layer"] in layers
        assert entry["moves"] == "tpot_p50_ms"
        assert entry["source"] == ("device_trace" if name in NEW_METRICS else
                                   "host_clock" if name == GENERATED else entry["source"])
        with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{name}.json")) as f:
            assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "readers",
                                               f"{json.load(f)['reader']}.py"))


def test_the_release_metrics_are_the_accepted_ones_with_this_cell_on_their_lists(resolved):
    """``kv_window_released_blocks`` and ``kv_full_layer_blocks_pct`` read this cell, whose
    window groups release inside ``decode_loop``, with the reader and the params they
    read their first cells with: one name a reading, the cells on its list (PR 61; PR 34
    had to bring each under a second name, because those cells' tests pinned the lists)."""
    bench = resolved[0]
    first = {"kv_window_released_blocks": "mistral-longdoc-closed",
             "kv_full_layer_blocks_pct": "mellum2-repoctx-closed"}
    for name in RELEASE_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert {first[name], CELL} <= set(entry["workloads"])
    assert not {"kv_window_groups_released_blocks", "kv_full_group_blocks_pct"} & \
        {m["name"] for m in bench["per_layer"]}


# ------------------------------------------------- generated tokens a second ---
def _request(prompt, first_s, window_tokens, ok=True):
    from benchmark import loadloop
    return loadloop.Request(index=0, due_s=0.0, prompt=np.zeros(prompt, np.int32),
                            max_new_tokens=64, first_s=first_s, window_tokens=window_tokens,
                            ok=ok)


@pytest.mark.parametrize("requests,want", [
    # the first token came inside the window: the prompt was counted with it, and goes
    ([_request(100, 0.5, 100 + 7)], 7 / 2.0),
    # it came in the lead-in: only generated tokens were ever counted
    ([_request(100, -0.3, 12)], 12 / 2.0),
    # it came after the window, or never: nothing was counted
    ([_request(100, 2.4, 0), _request(100, None, 0)], 0.0),
    # a failed request serves nothing, as in ``serve_tokens_per_s``
    ([_request(100, 0.5, 100 + 7, ok=False), _request(50, 1.0, 50 + 3)], 3 / 2.0),
    # a closed loop's window: lumps of prompts beside a steady stream of answers
    ([_request(2000, 0.1 * i, 2000 + 40) for i in range(5)] + [_request(1800, -1.0, 90)],
     (5 * 40 + 90) / 2.0),
])
def test_generated_throughput_leaves_the_prompts_out(requests, want):
    from benchmark.readers import serve_generated_throughput, serve_throughput
    from types import SimpleNamespace
    run = {"mode": "serve", "requests": requests, "seconds": 2.0}
    chip = {"trace": SimpleNamespace(devices={"/device:TPU:0": [object()]})}
    assert serve_generated_throughput.read(run, {}, chip) == pytest.approx(want)
    assert serve_throughput.read(run, {}, chip) >= want
    # nothing off the chip (the rehearsal's list of metrics is exact), nothing for training
    assert serve_generated_throughput.read(run, {}, {"trace": None}) is None
    assert serve_generated_throughput.read({"mode": "train"}, {}, chip) is None


# --------------------------------------------------------------- controls ---
def _tree(experts=16, hidden=8, width=4):
    rng = np.random.default_rng(0)
    def bank():
        return {"wi": jnp.asarray(rng.normal(size=(experts, hidden, 2 * width)), jnp.bfloat16),
                "wo": jnp.asarray(rng.normal(size=(experts, width, hidden)), jnp.bfloat16)}
    return {"embed_tokens": {"embedding": jnp.asarray(rng.normal(size=(32, hidden)),
                                                      jnp.bfloat16)},
            "norm": {"weight": jnp.ones((hidden, ), jnp.float32)},
            "layers_0": {"mlp": {"up_proj": {"kernel": jnp.asarray(
                rng.normal(size=(hidden, width)), jnp.bfloat16)}}},
            "layers_1": {"block_sparse_moe": {"gate": jnp.ones((hidden, experts), jnp.float32),
                                              "ExpertFFN_0": bank()}}}


@pytest.mark.parametrize("control", ["drop_1_in_8", "drop_expert", "wrong_bank", "fp8_banks",
                                     "fp8_weights"])
def test_a_control_spoils_the_banks_it_names_and_nothing_else(control):
    from benchmark.tools import controls
    def leaf(tree, path):
        for k in path:
            tree = tree[k]
        return np.asarray(tree, np.float32)
    bank, dense = ("layers_1", "block_sparse_moe", "ExpertFFN_0"), ("layers_0", "mlp", "up_proj",
                                                                    "kernel")
    params = _tree()
    wo, wi, up = leaf(params, bank + ("wo", )), leaf(params, bank + ("wi", )), leaf(params, dense)
    gate = params["layers_1"]["block_sparse_moe"]["gate"]
    spoilt = controls.spoil(params, control)  # consumes the tree: what it changes is donated
    wo2, wi2 = leaf(spoilt, bank + ("wo", )), leaf(spoilt, bank + ("wi", ))
    if control.startswith("drop"):
        dead = [0, 8] if control == "drop_1_in_8" else [0]
        assert not wo2[dead].any() and (wi2 == wi).all()
        alive = [e for e in range(16) if e not in dead]
        assert (wo2[alive] == wo[alive]).all()
    elif control == "wrong_bank":
        assert (wo2[1:] == wo[:-1]).all() and (wi2[0] == wi[-1]).all()
    else:
        # three bits of mantissa: off by at most 2^-4 of each weight under a scale an
        # expert, and not equal (bfloat16 has eight)
        for a, b in ((wo, wo2), (wi, wi2)):
            assert (a != b).any()
            top = np.abs(a).max(axis=(1, 2), keepdims=True)
            assert (np.abs(a - b) <= np.maximum(np.abs(a) * 2.0**-4, top * 2.0**-9) * 1.01).all()
    # the router and the norms stay float32 and untouched; the dense matrices change
    # only where every weight goes through fp8
    assert spoilt["layers_1"]["block_sparse_moe"]["gate"] is gate
    assert (leaf(spoilt, dense) != up).any() == (control == "fp8_weights")


# ------------------------------------------------------------ the refusal ---
def test_a_program_without_afmoe_config_exits_at_once_with_a_message():
    """The parent's tree under this PR's benchmark files: the family module is
    loaded in a process where ``deepspeed_tpu.models.afmoe`` cannot be
    imported, and exits before anything is made."""
    code = (
        "import sys, importlib.abc\n"
        "class Absent(importlib.abc.MetaPathFinder):\n"
        "    def find_spec(self, name, path, target=None):\n"
        "        if name == 'deepspeed_tpu.models.afmoe':\n"
        "            raise ImportError('No module named deepspeed_tpu.models.afmoe')\n"
        "sys.meta_path.insert(0, Absent())\n"
        "from benchmark import harness\n"
        f"harness._load_module({tiny.REPO!r}, 'models', 'afmoe')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=tiny.REPO, capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=tiny.REPO, JAX_PLATFORMS="cpu"))
    assert done.returncode == 1
    assert "no deepspeed_tpu.models.afmoe" in done.stderr and "Nothing was measured" in done.stderr
    assert "Traceback" not in done.stderr


def test_the_family_builds_the_programs_config_from_the_file(resolved):
    family = harness._load_module(tiny.REPO, "models", "afmoe")
    config = resolved[2]
    cfg = family.program_config(config)
    n = config["num_hidden_layers"]
    assert cfg.num_hidden_layers == n and cfg.layer_types == tuple(config["layer_types"][:n])
    assert (cfg.head_dim, cfg.hidden_size, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.num_shared_experts, cfg.num_dense_layers, cfg.vocab_size, cfg.sliding_window,
            cfg.route_scale, cfg.score_func, cfg.intermediate_size, cfg.moe_intermediate_size) == \
        (128, 2048, 128, 8, 1, 1, 200192, 2048, 2.826, "sigmoid", 6144, 1024)
    assert [cfg.window_of(i) for i in range(5)] == [2048, 2048, 2048, 0, 2048]
    assert [cfg.is_dense(i) for i in range(5)] == [True, False, False, False, False]
    assert cfg.dtype == jnp.bfloat16
    # every key of the catalog row that the program's config has reaches it
    assert set(family.MODEL_KEYS) <= set(config)


# ------------------------------------------------- the reference's routing ---
def _routing(scores_logits, bias, **kw):
    h = jnp.ones((1, len(scores_logits[0])), jnp.float32)
    gate = jnp.diag(jnp.asarray(scores_logits[0], jnp.float32))  # h @ gate = the logits
    kw = dict(dict(top_k=2, score_func="sigmoid", route_norm=True, route_scale=2.0), **kw)
    weights, gap = reference.routing(h, gate, bias, **kw)
    return np.asarray(weights)[0], float(gap[0])


def test_the_reference_picks_by_score_plus_bias_and_weighs_by_score():
    logits = [[2.0, 1.0, 0.5, 0.0]]
    s = 1 / (1 + np.exp(-np.asarray(logits[0])))
    w, gap = _routing(logits, None)
    np.testing.assert_allclose(w, [2 * s[0] / (s[0] + s[1]), 2 * s[1] / (s[0] + s[1]), 0, 0],
                               rtol=1e-6)
    # in router-logit units: the second and third logits are 0.5 apart
    assert gap == pytest.approx(0.5, rel=0.05)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.4])
    w, gap = _routing(logits, bias)
    np.testing.assert_allclose(w, [2 * s[0] / (s[0] + s[3]), 0, 0, 2 * s[3] / (s[0] + s[3])],
                               rtol=1e-6)
    # biased, the last expert takes the first place (0.5 + 0.4 = 0.9 over 0.881): the last
    # chosen is now expert 0 and the first left out expert 1, over the mean slope of the two
    slope = 0.5 * (s[0] * (1 - s[0]) + s[1] * (1 - s[1]))
    assert gap == pytest.approx((s[0] - s[1]) / slope, rel=1e-4)
    w, _ = _routing(logits, None, route_norm=False, route_scale=1.0)
    np.testing.assert_allclose(w, [s[0], s[1], 0, 0], rtol=1e-6)


def test_a_near_tie_is_a_toss_up_by_the_harness_rule_and_a_clear_choice_is_not():
    _, gap = _routing([[2.0, 1.0, 1.0 - 0.5 * check.ROUTING_TOSS_UP_GAP, 0.0]], None)
    assert gap < check.ROUTING_TOSS_UP_GAP
    _, gap = _routing([[2.0, 1.0, 1.0 - 2.0 * check.ROUTING_TOSS_UP_GAP, 0.0]], None)
    assert gap > check.ROUTING_TOSS_UP_GAP
    # softmax, no bias: the first order of mixtral's difference of the logs
    _, gap = _routing([[2.0, 1.0, 0.97, 0.0]], None, score_func="softmax")
    assert gap == pytest.approx(0.03, rel=0.05)


# -------------------------------------------------------------- rehearsal ---
TINY_TRINITY = {
    "family": "afmoe", "mode": "serve", "torch_dtype": "float32",
    "global_attn_every_n_layers": 4, "head_dim": 16, "hidden_act": "silu", "hidden_size": 48,
    "intermediate_size": 96, "layer_types": ["sliding_attention"] * 3 + ["full_attention"] +
    ["sliding_attention"] * 3 + ["full_attention"], "load_balance_coeff": 0.001,
    "max_position_embeddings": 512, "moe_intermediate_size": 32, "mup_enabled": True,
    "n_group": 1, "num_attention_heads": 4, "num_dense_layers": 1, "num_expert_groups": 1,
    "num_experts": 16, "num_experts_per_tok": 4, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "num_limited_groups": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 16,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True, "vocab_size": 256,
    "engine": {"kv_block_size": 4,
               "state_manager": {"memory_config": {"mode": "allocate", "size": 512},
                                 "max_context": 96, "max_ragged_batch_size": 32,
                                 "max_ragged_sequence_count": 8},
               "expert_parallel": {"capacity_factor": 4.0}},
    "serving": {"decode_chunk": 4, "queue_capacity": 1024},
}


def _tiny_root(tmp_path):
    """A throw-away benchmark root with the cell ``tiny-trinity-reason``."""
    root = tiny.make_root(tmp_path / "root")
    tiny.write_json(os.path.join(root, "benchmark", "configs", "tiny-trinity.json"), TINY_TRINITY)
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", "reason-closed.json")) as f:
        traffic = json.load(f)
    traffic.update(tiny._TIMES)
    traffic["params"].update(clients=3, requests_per_client=40,
                             prompt={"dist": "uniform", "min": 10, "max": 40},
                             output={"dist": "lognormal", "median": 16, "sigma": 0.25, "min": 8,
                                     "max": 24})
    tiny.write_json(os.path.join(root, "benchmark", "traffic", "tiny-reason.json"), traffic)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-trinity", "source": "none: a test preset",
                             "file": "benchmark/configs/tiny-trinity.json", "reduced": [],
                             "why": "CPU rehearsal"})
    bench["workloads"].append({"name": "tiny-trinity-reason", "config": "tiny-trinity",
                               "traffic": "tiny-reason", "chips": 1, "why": "CPU rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "tiny-mixtral-closed" in m["workloads"]:
            m["workloads"].append("tiny-trinity-reason")
    tiny.write_json(path, bench)

    return root


def test_the_cell_rehearses_at_a_tiny_preset(tmp_path):
    """Window 16 over 4-token blocks, prompts on both sides of it, answers that
    cross it in ``decode_loop``, top-4 of 16 beside a shared expert, through the
    harness's test-only entry: the family, the traffic, the new metric files
    and readers all load, and the check holds prefill in chunks, release in the
    window groups and decode to the float32 reference."""
    root = _tiny_root(tmp_path)
    for trace in (0, 1):
        out = io.StringIO()
        assert harness.run_cell(root, "tiny-trinity-reason", 2**31 + 34, 1.5, trace,
                                rehearsal=True, out=out) == 0
        text = out.getvalue()
        line = tiny.last_line(text)
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
        assert text.count("-> ok") >= 4 and "WRONG" not in text
    assert line["metrics"]["cpu_rehearsal.compiles_in_window"]["value"] == 0
    # every metric this cell brings reads only beside a chip's trace
    for name in NEW_METRICS + RELEASE_METRICS + (GENERATED, ):
        assert f"metric {name}: nothing to read, left out" in text


def test_the_controls_run_through_the_harness_comparison_at_a_tiny_preset(tmp_path, capsys):
    """``benchmark/tools/controls.py`` on the tiny cell: the weights as the seed makes them
    read ``correct``, every assignment through its neighbour's bank and every matrix
    through fp8 do not (what the smaller controls read is the chip's to say: PERF.md
    section 6, PR 34); the tool's exit code says whether every control was caught."""
    from benchmark.tools import controls
    root = _tiny_root(tmp_path)
    code = controls.main(["--workload", "tiny-trinity-reason", "--seed", str(2**31 + 34),
                          "--rehearsal", "1", "--root", root,
                          "--controls", "baseline,wrong_bank,fp8_weights,drop_expert"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    read = result["controls"]
    assert list(read) == ["baseline", "wrong_bank", "fp8_weights", "drop_expert"]
    assert read["baseline"]["correct"] is True
    assert read["wrong_bank"]["correct"] is False and read["fp8_weights"]["correct"] is False
    assert read["wrong_bank"]["worst_loose_log2"] > read["baseline"]["worst_loose_log2"] + 3
    assert read["baseline"]["tight_rows"] + read["baseline"]["loose_rows"] == 4 * 8
    assert code == (0 if not read["drop_expert"]["correct"] else 4)
    assert result["tolerance_log2"] == pytest.approx(np.log2(check.logit_rel_tol(5)))
