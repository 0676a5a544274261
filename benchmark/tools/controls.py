#!/usr/bin/env python3
"""What a sparse cell's ``correct`` can see of its routed experts: the
harness's own comparison (``runners/serve.py:correctness``, the cell's four
check prompts, the same reference rows) on an engine whose weights were spoilt
on purpose. The baseline, the weights as the seed makes them, must read
``correct: true``; a control that reads true as well is something the cell's
comparison cannot see on the chip (exit code 4), and has to be held elsewhere
(PERF.md section 6, PR 34 has this repo's readings and why).

    python3 benchmark/tools/controls.py --workload <cell> --seed <n>
        [--controls baseline,drop_1_in_8,drop_expert,wrong_bank,fp8_banks,fp8_weights]

The reference is computed ONCE, from the unspoilt weights; the engine is then
built from spoilt ones. Every control is a change of the parameter tree alone
(the program, the engine's settings and the comparison stay as the cell has
them), each applied to the seed's weights made anew:

- ``drop_1_in_8``: in every expert layer the ``wo`` bank of every 8th expert
  is zero: an assignment to one of them returns nothing, which is what a
  dropped assignment is. A token loses top_k / 8 assignments a layer on average
  (one of its eight at top-8).
- ``drop_expert``: the same for ONE expert a layer (expert 0): a token loses
  that assignment in a layer with probability top_k / experts.
- ``wrong_bank``: in every expert layer both banks are rolled by one expert:
  every assignment is computed by its neighbour's weights.
- ``fp8_banks``: both banks of every expert layer rounded to float8 (e4m3:
  three bits of mantissa), scaled an expert so that its largest weight is the
  format's largest number (weight-only fp8 as it is deployed): the nearest
  precision below the configuration's bfloat16, in the routed experts alone.
- ``fp8_weights``: every matrix of the model (embedding and head too; not the
  router, float32 by the model's statement) the same way, a matrix (a bank: an
  expert) a scale.

Prints one JSON line: per control ``correct`` and the largest row error on the
rows held to the tight and to the loose tolerance, as log2 of the largest
logit. Lives beside ``repeat.py``; runs on the chip (``--rehearsal 1`` runs
wherever JAX runs, for the tests, and proves nothing about a chip).
"""

import argparse
import gc
import json
import os
import re
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROLS = ("drop_1_in_8", "drop_expert", "wrong_bank", "fp8_banks", "fp8_weights")
FP8_MAX = 240.0  # the largest finite number of an IEEE-style e4m3 (float8_e4m3fn reaches 448)


def _banks(params):
    """``(layer name, ExpertFFN_0 subtree)`` of every layer that has routed
    experts, by the names Mixtral's, Mellum's and afmoe's trees share."""
    return [(name, layer["block_sparse_moe"]["ExpertFFN_0"])
            for name, layer in sorted(params.items())
            if isinstance(layer, dict) and "block_sparse_moe" in layer]


def _through_fp8(x):
    """``x`` [..., rows, columns] rounded to float8's precision (e4m3: four bits
    of exponent, three of mantissa) under one scale a leading index (an expert
    of a bank; a whole matrix), back in x's dtype. ``lax.reduce_precision``, not
    a pair of converts: a compiler may take ``f32 -> f8 -> f32`` out as
    redundant, and the TPU's did (the first run of these controls on the chip
    read the baseline's numbers to the digit)."""
    import jax
    import jax.numpy as jnp
    wide = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wide), axis=(-2, -1), keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (jax.lax.reduce_precision(wide / scale, exponent_bits=4, mantissa_bits=3)
            * scale).astype(x.dtype)


def spoil(params, control):
    """``params`` with ``control`` applied. CONSUMES ``params``: every leaf it
    changes is donated to the program that changes it (two copies of the banks
    do not fit beside each other on the chip the cell fills), so the caller
    hands over a tree it can make again from the seed."""
    import jax
    import jax.numpy as jnp
    fp8 = jax.jit(_through_fp8, donate_argnums=0)
    if control == "fp8_weights":

        def matrix(path, x):
            # the norms' gains and the selection bias have one axis; the router (``gate``,
            # a bare matrix beside the banks) is float32 by the model's own statement
            router = getattr(path[-1], "key", None) == "gate"
            return fp8(x) if x.ndim >= 2 and not router else x

        return jax.tree_util.tree_map_with_path(matrix, params)
    roll = jax.jit(lambda x: jnp.roll(x, 1, axis=0), donate_argnums=0)
    kill = jax.jit(lambda x, dead: jnp.where(dead[:, None, None], jnp.zeros((), x.dtype), x),
                   donate_argnums=0)
    out = dict(params)
    for name, bank in _banks(params):
        experts = bank["wo"].shape[0]
        if control in ("drop_1_in_8", "drop_expert"):
            dead = np.zeros(experts, bool)
            dead[::8 if control == "drop_1_in_8" else experts] = True
            new = {"wi": bank["wi"], "wo": kill(bank["wo"], jnp.asarray(dead))}
        elif control == "wrong_bank":
            new = {k: roll(bank[k]) for k in ("wi", "wo")}
        elif control == "fp8_banks":
            new = {k: fp8(bank[k]) for k in ("wi", "wo")}
        else:
            raise ValueError(f"no control {control!r}; known: {CONTROLS}")
        layer = dict(params[name])
        layer["block_sparse_moe"] = dict(layer["block_sparse_moe"], ExpertFFN_0=new)
        out[name] = layer
    return out


_ROW = re.compile(r"correct\[(\d+)\] prompt.*?largest logit \([^)]*\): \[([^\]]*)\]")


def _worst(lines, ref, toss_up_gap):
    """Largest row error on the rows held to the tight and to the loose
    tolerance, as log2 of the largest logit: the errors from the comparison's
    own log lines (``check.logits_close``'s detail), the rows' kind from the
    reference's routing gaps as the comparison reads them."""
    tight, loose = [], []
    for line in lines:
        m = _ROW.search(line)
        if not m:
            continue
        gaps = ref[int(m.group(1))][1]
        for j, err in enumerate(float(v) for v in m.group(2).split(",")):
            (loose if gaps is not None and gaps[j] < toss_up_gap else tight).append(err)
    return {"tight_rows": len(tight), "loose_rows": len(loose),
            "worst_tight_log2": max(tight, default=None),
            "worst_loose_log2": max(loose, default=None)}


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
    if not _banks(params):
        log("this configuration has no routed experts: nothing to spoil")
        return 1
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
    wanted = args.controls.split(",")
    if "baseline" in wanted:  # first, on the tree the reference was computed from
        wanted.remove("baseline")
        wanted.insert(0, "baseline")
    for control in wanted:
        # a control consumes its tree: each gets the seed's weights made again, and the
        # unspoilt tree is let go before the first of them (two do not fit on the chip)
        spoilt, params = params if control == "baseline" else None, None
        if spoilt is None:
            spoilt = spoil(family.serving_params(cfg, args.seed), control)
        jax.block_until_ready(spoilt)
        engine = build_engine(spoilt, cfg, RaggedInferenceEngineConfig(**engine_cfg))
        lines = []

        def keep(message, lines=lines):
            lines.append(message)
            log(f"{control}: {message}")

        ok = serve.correctness(engine, family, config, budget, prompts, feeds, ref, loop_steps,
                               keep)
        engine.close()
        del engine, spoilt
        gc.collect()  # the engine sits in reference cycles, and its KV pool with it
        log(f"{control}: {sum(a.nbytes for a in jax.live_arrays()) / 2**30:.2f} GiB of live "
            f"arrays after the engine was dropped")
        result["controls"][control] = dict(_worst(lines, ref, check.ROUTING_TOSS_UP_GAP),
                                           correct=bool(ok))
        log(f"{control}: correct={ok}")
    print(json.dumps(result), flush=True)
    want = {c: c == "baseline" for c in result["controls"]}
    return 0 if all(result["controls"][c]["correct"] == w for c, w in want.items()) else 4


if __name__ == "__main__":
    sys.exit(main())
