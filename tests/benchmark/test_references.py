"""The plain references against the repository's own training forwards, at tiny
sizes on the CPU, and the evidence that serving MoE at the default capacity
factor is not the published (dropless) Mixtral."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check
from benchmark.references import mistral as ref_mistral
from benchmark.references import mixtral as ref_mixtral

SIZES = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
             num_hidden_layers=2, rms_norm_eps=1e-5, vocab_size=256, max_position_embeddings=256)


def _ids(seed, n, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


@pytest.mark.parametrize("window", [0, 8])
def test_mistral_reference_matches_the_training_forward(window):
    from deepspeed_tpu.models import llama
    sizes = dict(SIZES, rope_theta=1e4, sliding_window=window, tie_word_embeddings=False)
    cfg = llama.LlamaConfig(dtype=jnp.float32, remat=False, model_type="mistral",
                            **{k: v for k, v in sizes.items()})
    _, params = llama.init_params(cfg, rng=jax.random.PRNGKey(0))
    ids = _ids(0, 24)
    theirs = llama.LlamaModel(cfg).apply({"params": params["model"]}, ids[None])[0]
    ours = ref_mistral.forward_logits(params, sizes, ids)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=2e-5)
    labels = _ids(1, 24)
    loss = float(llama.LlamaForCausalLM(cfg).apply({"params": params}, (ids[None], labels[None])))
    assert ref_mistral.next_token_loss(params, sizes, ids, labels, block=7) == \
        pytest.approx(loss, rel=1e-5)
    if window:
        # the window is not a no-op at this length: without it the logits move
        wide = ref_mistral.forward_logits(params, dict(sizes, sliding_window=0), ids)
        assert float(jnp.abs(wide - ours).max()) > 1e-3


def test_mistral_reference_walks_queries_in_blocks(monkeypatch):
    sizes = dict(SIZES, rope_theta=1e4, sliding_window=16)
    from deepspeed_tpu.models import llama
    cfg = llama.LlamaConfig(dtype=jnp.float32, remat=False, **{k: v for k, v in SIZES.items()})
    _, params = llama.init_params(cfg, rng=jax.random.PRNGKey(1))
    ids = _ids(2, 40)
    whole = np.asarray(ref_mistral.forward_logits(params, sizes, ids))
    monkeypatch.setattr(ref_mistral, "QUERY_BLOCK", 16)
    jax.clear_caches()  # the block size is read while tracing
    blocked = np.asarray(ref_mistral.forward_logits(params, sizes, ids))
    np.testing.assert_allclose(blocked, whole, atol=1e-5)


def _mixtral(experts=4, **kw):
    from deepspeed_tpu.models import mixtral
    sizes = dict(SIZES, rope_theta=1e6, num_local_experts=experts, num_experts_per_tok=2,
                 sliding_window=None)
    cfg = mixtral.MixtralConfig(dtype=jnp.float32, remat=False,
                                **{k: v for k, v in sizes.items() if k != "sliding_window"}, **kw)
    return mixtral, sizes, cfg


def test_mixtral_reference_matches_the_training_forward_where_nothing_drops():
    # capacity_factor = experts / top_k: the training MoE cannot drop either
    mixtral, sizes, cfg = _mixtral(capacity_factor=2.0)
    model, params = mixtral.init_params(cfg, rng=jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    ids, labels = _ids(0, 24), _ids(1, 24)
    loss = float(mixtral.MixtralForCausalLM(cfg, aux_loss_weight=0.0).apply(
        {"params": params}, (ids[None], labels[None])))
    logits = ref_mixtral.forward_logits(params, sizes, ids)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ours = float(-jnp.take_along_axis(logp, jnp.asarray(labels)[:, None], axis=-1).mean())
    assert ours == pytest.approx(loss, rel=2e-5)


def _serve_rows(cfg, params, capacity_factor, ids):
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    engine = build_engine(params, cfg, RaggedInferenceEngineConfig(
        kv_block_size=16, expert_parallel={"capacity_factor": capacity_factor},
        state_manager={"memory_config": {"mode": "allocate", "size": 32}, "max_context": 64,
                       "max_ragged_batch_size": 64, "max_ragged_sequence_count": 8}))
    try:
        # four sequences prefilled together: 4 x 16 = 64 tokens in one MoE call
        rows = np.asarray(engine.put([0, 1, 2, 3], [ids] * 4))
    finally:
        engine.close()
    return rows


def test_serving_moe_drops_at_the_default_capacity_factor_and_not_at_experts_over_top_k():
    """ISSUE 22, fact 2. 8 experts, top-2: at capacity_factor 2.0 an expert takes
    at most 64 x 2 / 8 x 2 = 32 of a 64-token batch's assignments. Four copies
    of one prompt route identically, so every expert some token chose is asked
    for a multiple of four and the popular ones overflow: the later copies lose
    an expert's contribution and their logits leave the reference by far more
    than the tolerance. At 8 / 2 = 4.0 the capacity is the token count, nothing
    can drop, and the same engine agrees."""
    mixtral, sizes, cfg = _mixtral(experts=8)
    _, params = mixtral.init_params(cfg, rng=jax.random.PRNGKey(3))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    ids = np.full(16, 7, np.int32)  # one token repeated: the router sends them one way
    ref = np.asarray(ref_mixtral.forward_logits(params, sizes, ids, rows=[15]))
    ref4 = np.repeat(ref, 4, axis=0)
    tol = check.logit_rel_tol(sizes["num_hidden_layers"])
    ok, detail = check.logits_close(ref4, _serve_rows(cfg, params, 4.0, ids), tol)
    assert ok, detail
    ok, detail = check.logits_close(ref4, _serve_rows(cfg, params, 2.0, ids), tol)
    print(detail)
    assert not ok, f"capacity_factor 2.0 was expected to drop: {detail}"


def test_logit_tolerance_catches_a_lower_precision():
    rng = np.random.default_rng(0)
    ref = rng.normal(0, 1.5, (6, 512)).astype(np.float32)
    tol = check.logit_rel_tol(3)
    assert 2.0**-6.3 < tol < 2.0**-6 and 2.0**-5.3 < check.logit_rel_tol(12) < 2.0**-5
    assert check.logits_close(ref, ref + rng.normal(0, 2**-9, ref.shape).astype(np.float32), tol)[0]
    # 2^-4 relative noise, as from 4-bit mantissas, is outside it
    noisy = ref * (1 + rng.normal(0, 2**-4, ref.shape).astype(np.float32))
    assert not check.logits_close(ref, noisy, tol)[0]
    assert not check.logits_close(ref, np.full_like(ref, np.nan), tol)[0]
    # one row off by 2^-5 of the largest logit: wrong, unless the reference says
    # that row's routing was a toss-up; and a toss-up does not excuse 2^-3
    off = ref.copy()
    off[2, 0] += 2.0**-5 * np.abs(ref).max()
    gaps = np.full(6, 1.0)
    assert not check.logits_close(ref, off, tol, routing_gaps=gaps)[0]
    gaps[2] = 2.0**-7
    assert check.logits_close(ref, off, tol, routing_gaps=gaps)[0]
    off[2, 0] += 2.0**-3 * np.abs(ref).max()
    assert not check.logits_close(ref, off, tol, routing_gaps=gaps)[0]
    assert check.token_decided(ref[0], int(ref[0].argmax()), tol)
    assert not check.token_decided(ref[0], int(ref[0].argmin()), tol)
    assert check.loss_close(10.8792, 10.8788)[0] and not check.loss_close(10.0, 10.05)[0]


def _serving_config_files():
    import glob
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    served = []
    for path in sorted(glob.glob(os.path.join(here, "benchmark", "configs", "*.json"))):
        with open(path) as f:
            if json.load(f).get("mode") == "serve":  # a training configuration compares losses
                served.append(path)
    return served


@pytest.mark.parametrize("path", _serving_config_files(), ids=lambda p: p.rsplit("/", 1)[-1][:-5])
def test_a_configurations_row_limits_are_its_own_or_the_rule(path):
    """A served configuration that states no ``check`` group is held as it always
    was (2^-7 x sqrt(layers), 4 x that at a toss-up, no median); one that states
    limits states all three with their reason, each between the readings PERF.md
    section 2 keeps: above the rule's tight limit (it was stated because an
    honest row passed that), under the 2^-4.5 the fp8 control's rows read."""
    import json
    with open(path) as f:
        config = json.load(f)
    limits = check.row_limits(config)
    rule = check.logit_rel_tol(config["num_hidden_layers"])
    stated = config.get("check")
    if stated is None:
        assert limits == {"tight": rule, "loose": rule * check.TOSS_UP_TOL_FACTOR, "median": None}
        return
    assert set(stated) == {"tight_row_log2", "loose_row_log2", "median_row_log2", "why"}
    assert limits == {"tight": 2.0**stated["tight_row_log2"], "loose": 2.0**stated["loose_row_log2"],
                      "median": 2.0**stated["median_row_log2"]}
    assert rule < limits["tight"] < 2.0**-4.5 and limits["tight"] < limits["loose"]
    assert limits["median"] < limits["tight"]


def test_the_median_row_catches_what_moves_every_row_and_the_worst_row_what_moves_one():
    """DeepSeek's readings (PERF.md section 2, PR 61) as rows: honest rows at
    2^-7 with one at 2^-5.8 are inside all three limits; every row at 2^-4.45,
    the fp8 control, is over the median's and the tight row's; one tight row at
    2^-4 (a mechanism gone) is over the tight row's alone; a toss-up row may
    read 2^-4 and not 2^-3.5."""
    limits = check.row_limits({"num_hidden_layers": 5,
                               "check": {"tight_row_log2": -5.0, "loose_row_log2": -3.84,
                                         "median_row_log2": -5.5}})
    honest = [(2.0**-7, i % 2 == 0) for i in range(32)]
    honest[5] = (2.0**-5.8, False)
    ok, compared = check.rows_compared(honest, limits)
    assert ok and list(compared) == ["worst_tight_row", "worst_loose_row", "median_row"]
    assert compared["worst_tight_row"] == [2.0**-5.8, 2.0**-5.0]
    assert compared["median_row"] == [2.0**-7, 2.0**-5.5]
    ok, compared = check.rows_compared([(2.0**-4.45, loose) for _, loose in honest], limits)
    over = {name for name, (v, limit) in compared.items() if v > limit}
    assert not ok and over == {"worst_tight_row", "median_row"}
    one = list(honest)
    one[5] = (2.0**-4, False)
    ok, compared = check.rows_compared(one, limits)
    assert not ok and compared["median_row"][0] <= compared["median_row"][1]
    one[5], one[6] = honest[5], (2.0**-4, True)
    assert check.rows_compared(one, limits)[0]
    one[6] = (2.0**-3.5, True)
    assert not check.rows_compared(one, limits)[0]
    # under the rule alone (no group stated) the same honest rows fail: 2^-5.8 is over 2^-5.84
    ok, compared = check.rows_compared(honest, check.row_limits({"num_hidden_layers": 5}))
    assert not ok and "median_row" not in compared


def test_logits_close_hands_out_each_rows_error_and_kind():
    rng = np.random.default_rng(1)
    ref = rng.normal(0, 1.5, (4, 64)).astype(np.float32)
    scale = float(np.abs(ref).max())
    off = ref.copy()
    off[1, 3] += 2.0**-6 * scale
    off[2, 3] += 2.0**-4 * scale
    rows = []
    ok, detail = check.logits_close(ref, off, 2.0**-5, routing_gaps=np.array([1, 1, 2.0**-7, 1]),
                                    loose_tol=2.0**-3.84, row_errors=rows)
    assert ok, detail
    assert [loose for _, loose in rows] == [False, False, True, False]
    assert rows[0][0] == 0 and rows[1][0] == pytest.approx(2.0**-6, rel=1e-3)
    assert rows[2][0] == pytest.approx(2.0**-4, rel=1e-3)
    assert not check.logits_close(ref, off, 2.0**-5, routing_gaps=np.array([1, 1, 2.0**-7, 1]),
                                  loose_tol=2.0**-4.5)[0]

