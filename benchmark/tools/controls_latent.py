#!/usr/bin/env python3
"""What a latent-attention cell's ``correct`` can see of the mechanisms its
family adds: the harness's own comparison (``runners/serve.py:correctness``,
the cell's four check prompts, the same reference rows) on an engine spoilt on
purpose, one mechanism at a time. The baseline must read ``correct: true``; a
control that reads true as well is something the cell's comparison cannot see
on the chip (exit code 4) and has to be held by a tier-1 test instead (the
configuration's ``engine_why.correct`` names it).

    python3 benchmark/tools/controls_latent.py --workload <cell> --seed <n>
        [--controls baseline,dense,recent,...]

The reference is computed ONCE, from the unspoilt weights and configuration.
Each control changes the program's configuration, its parameter tree, or one
function of the program's latent-attention path while its engine is built and
run (restored after):

- ``dense``: no selection: ``index_topk`` raised to ``max_context``, so every
  bucket attends to every earlier key.
- ``recent``: the most recent ``index_topk`` keys in place of the top ones: the
  indexer's scores replaced by the keys' positions.
- ``no_index_weights``: the per-head index weights w dropped (all ones).
- ``index_key_no_rope``: the index key cached without its rotary embedding
  (the queries keep theirs).
- ``latent_fp8``: the latent row rounded to float8 (e4m3, one scale a row) as
  it is written to the pool.
- ``index_fp8``: the same for the index key (the published indexer's own
  precision: the configuration's ``assumed.torch_dtype``).
- ``drop_expert``: ONE held expert's ``wo`` bank zero in every expert layer.
- ``no_group_limit``: the router picks its top-k over all the experts
  (``n_group`` = ``topk_group`` = 1).
- ``fp8_weights``: ``controls.py``'s own (every matrix of the model but the
  float32 router rounded to float8, a matrix or an expert a scale: the nearest
  precision below the configuration's bfloat16), which that tool cannot run
  on this family's tree (it finds the banks by Mixtral's names). Run last: it
  consumes a tree of its own, and two do not fit on the chip.

Prints one JSON line: per control ``correct``, the largest row error on the
rows held to the tight and to the loose tolerance, as log2 of the largest
logit, and ``compared_log2``: each number the comparison held beside its limit
(``check.rows_compared``; the median row where the configuration states one). Runs on the chip (``--rehearsal 1`` runs wherever JAX runs, for the
tests, and proves nothing about a chip). The comparison's own file,
``controls.py``, is read for its row parser and its float8 rounding.
"""

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROLS = ("dense", "recent", "no_index_weights", "index_key_no_rope", "latent_fp8",
            "index_fp8", "drop_expert", "no_group_limit", "fp8_weights")


@contextlib.contextmanager
def _patched(module, **functions):
    was = {name: getattr(module, name) for name in functions}
    for name, fn in functions.items():
        setattr(module, name, fn)
    try:
        yield
    finally:
        for name, fn in was.items():
            setattr(module, name, fn)


def _scores_patch(change):
    """Both arms' score functions with ``change(q_index, weights, scores)``
    applied: to the inputs (scores None) and to the result."""
    from deepspeed_tpu.ops.pallas import latent_attention as la

    def wrap(fn):
        def scored(q_index, weights, *rest, **kw):
            q_index, weights = change(q_index, weights, None)[:2]
            return change(q_index, weights, fn(q_index, weights, *rest, **kw))[2]
        return scored

    return _patched(la, latent_index_scores=wrap(la.latent_index_scores),
                    latent_index_scores_xla=wrap(la.latent_index_scores_xla))


def spoilt(control, cfg, params, max_context):
    """``(cfg, params, context manager)`` of a control. ``params`` is changed
    by copy of the dicts on the way to the one leaf (the leaf itself is made
    anew; the rest is shared)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_implementations import deepseek_v32_v2 as served
    from deepspeed_tpu.ops.pallas import latent_attention as la
    nothing = contextlib.nullcontext()
    if control == "baseline":
        return cfg, params, nothing
    if control == "dense":
        return dataclasses.replace(cfg, index_topk=max_context), params, nothing
    if control == "no_group_limit":
        return dataclasses.replace(cfg, n_group=1, topk_group=1), params, nothing
    if control == "recent":
        def by_position(q_index, weights, scores):
            if scores is None:
                return q_index, weights, None
            position = jnp.arange(scores.shape[1], dtype=jnp.float32)[None, :]
            return q_index, weights, jnp.where(scores > 0.5 * la.NEG_INF, position, scores)
        return cfg, params, _scores_patch(by_position)
    if control == "no_index_weights":
        return cfg, params, _scores_patch(
            lambda q_index, weights, scores: (q_index, jnp.ones_like(weights), scores))
    if control == "index_key_no_rope":
        rotate = served._rotate_half

        def queries_only(x, cos, sin):  # the key comes with ONE head, the queries with many
            return x if x.shape[1] == 1 else rotate(x, cos, sin)
        return cfg, params, _patched(served, _rotate_half=queries_only)
    if control in ("latent_fp8", "index_fp8"):
        from benchmark.tools.controls import _through_fp8
        write = served.DeepseekV32V2Model._write_rows
        latent = la.padded_width(cfg.latent_width)

        def through_fp8(self, pool, li, rows, batch):
            # the pools are told apart by their rows' width
            if (rows.shape[-1] == latent) == (control == "latent_fp8"):
                rows = _through_fp8(rows[:, None, :])[:, 0]
            return write(self, pool, li, rows, batch)
        return cfg, params, _patched(served.DeepseekV32V2Model, _write_rows=through_fp8)
    if control == "drop_expert":
        out = dict(params)
        for name, layer in params.items():
            if isinstance(layer, dict) and "experts" in layer.get("mlp", {}):
                bank = layer["mlp"]["experts"]
                dead = jax.jit(lambda wo: wo.at[0].set(0))(bank["wo"])
                out[name] = dict(layer, mlp=dict(layer["mlp"], experts=dict(bank, wo=dead)))
        return cfg, out, nothing
    raise ValueError(f"no control {control!r}; known: {CONTROLS}")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--controls", default="baseline," + ",".join(CONTROLS))
    parser.add_argument("--rehearsal", type=int, default=0)
    parser.add_argument("--root", default=ROOT)
    args = parser.parse_args(argv)
    t_start = time.perf_counter()

    def log(message):
        print(f"[{time.perf_counter() - t_start:7.1f}s] {message}", flush=True)

    from benchmark import check, harness
    from benchmark.tools.controls import _worst
    started = harness.start(args.root, args.workload, bool(args.rehearsal), log)
    if isinstance(started, int):
        return started
    _, cell, config, traffic, _ = started
    ctx = harness.make_ctx(args.root, args.workload, cell, config, traffic, args.seed, 0.0, 0, log)
    family = ctx["family"]

    import jax
    from benchmark.runners import serve
    from benchmark.traffic_kinds import _draw
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine

    cfg = family.program_config(config)
    params = family.serving_params(cfg, args.seed)
    jax.block_until_ready(params)
    # the cell's own check prompts: runners/serve.py:prepare draws them so
    rng = np.random.default_rng([args.seed, 0xc0de])
    lengths = _draw.lengths(traffic["params"]["prompt"], serve.CHECK_PROMPTS, rng)
    prompts = [_draw.tokens(rng, cfg.vocab_size, n) for n in lengths]
    feeds = [_draw.tokens(rng, cfg.vocab_size, serve.CHECK_STEPS) for _ in prompts]
    ref = serve.reference_rows(family, params, config, prompts, feeds)
    log(f"reference: prompts of {lengths.tolist()} tokens")

    engine_cfg = config["engine"]
    budget = engine_cfg["state_manager"]["max_ragged_batch_size"]
    loop_steps = config["serving"].get("decode_chunk", 1)
    result = {"workload": args.workload, "seed": args.seed,
              "tolerance_log2": float(np.log2(check.row_limits(config)["tight"])),
              "controls": {}}
    wanted = sorted(args.controls.split(","), key=lambda c: c == "fp8_weights")
    for control in wanted:
        if control == "fp8_weights":
            from benchmark.tools.controls import spoil
            params = None  # let go before the seed's weights are made again
            gc.collect()
            its_cfg, patch = cfg, contextlib.nullcontext()
            its_params = spoil(family.serving_params(cfg, args.seed), control)
            jax.block_until_ready(its_params)
        else:
            its_cfg, its_params, patch = spoilt(control, cfg, params,
                                                engine_cfg["state_manager"]["max_context"])
        lines = []

        def keep(message, lines=lines, control=control):
            lines.append(message)
            log(f"{control}: {message}")

        compared = {}
        with patch:
            engine = build_engine(its_params, its_cfg, RaggedInferenceEngineConfig(**engine_cfg))
            ok = serve.correctness(engine, family, config, budget, prompts, feeds, ref,
                                   loop_steps, keep, compared=compared)
            engine.close()
        del engine, its_params
        gc.collect()  # the engine sits in reference cycles, and its KV pool with it
        result["controls"][control] = dict(
            _worst(lines, ref, check.ROUTING_TOSS_UP_GAP), correct=bool(ok),
            compared_log2={name: [round(float(np.log2(max(v, 1e-12))), 2),
                                  round(float(np.log2(limit)), 2)]
                           for name, (v, limit) in compared.items()})
        log(f"{control}: correct={ok}")
    print(json.dumps(result), flush=True)
    want = {c: c == "baseline" for c in result["controls"]}
    return 0 if all(result["controls"][c]["correct"] == w for c, w in want.items()) else 4


if __name__ == "__main__":
    sys.exit(main())
