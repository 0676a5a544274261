#!/usr/bin/env python3
"""What the Falcon-H1 cell's ``correct`` can see of what its family adds: the
harness's own comparison (``runners/serve.py:correctness``, the cell's four
check prompts prefilled together in shares of a quarter of the token budget,
then fed through ``put`` and ``decode_loop``, against the same reference rows)
on an engine spoilt on purpose, one mechanism at a time. The baseline must read
``correct: true``; a control that reads true as well is something the cell's
comparison cannot see on the chip (exit code 4) and has to be held by a tier-1
test instead (the configuration's ``engine_why.correct`` names it).

    python3 benchmark/tools/controls_h1.py --workload <cell> --seeds <n>[,<n>...]
        [--controls baseline,no_state_carry,no_conv_carry,no_key_multiplier,
                    no_ssm_multipliers,no_attention,no_mamba,fp8_weights]

The reference is computed ONCE a seed, from the unspoilt weights and the
configuration as stated. Each control changes one thing of the program while
its engine is built and run (restored after):

- ``no_state_carry``: the Mamba-2 state NOT carried from one ``put`` to the
  next: every chunk of a prompt scans from zero.
- ``no_conv_carry``: the convolution's tail not carried: the first rows of
  every chunk see zeros where the last rows of the chunk before belong.
- ``no_key_multiplier`` / ``no_ssm_multipliers``: the engine built from a
  configuration with ``key_multiplier`` 1 / every ``ssm_multipliers`` entry 1.
- ``no_attention`` / ``no_mamba``: that mixer's output left out of the layers'
  sum (zeros in its place).
- ``fp8_weights``: ``controls.py``'s own (every matrix rounded to float8, a
  matrix a scale: the nearest precision below the configuration's bfloat16). It
  must read false: it is what holds the stated precision. Run last: it consumes
  a tree of its own, and two do not fit on the chip.

With several ``--seeds`` every seed runs the same controls (``--controls
baseline`` alone: the comparison on honest engines, seed after seed). Prints one
JSON line a seed: per control ``correct`` and the rows' errors as log2 of the
largest logit (worst and median; the model is dense, so every row is held to
the tight tolerance). Runs on the chip (``--rehearsal 1`` runs wherever JAX
runs, for the tests, and proves nothing about a chip).
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

CONTROLS = ("no_state_carry", "no_conv_carry", "no_key_multiplier", "no_ssm_multipliers",
            "no_attention", "no_mamba", "fp8_weights")


def spoilt(control, cfg):
    """``(context manager, the configuration the engine is built from)``."""
    import jax.numpy as jnp
    from benchmark.tools.controls_latent import _patched
    from deepspeed_tpu.inference.v2.model_implementations import falcon_h1_v2 as served
    from deepspeed_tpu.inference.v2.modules import ssm
    model = served.FalconH1V2Model
    if control in ("baseline", "fp8_weights"):  # the second spoils the tree, not the program
        return contextlib.nullcontext(), cfg
    if control == "no_key_multiplier":
        return contextlib.nullcontext(), dataclasses.replace(cfg, key_multiplier=1.0)
    if control == "no_ssm_multipliers":
        return contextlib.nullcontext(), dataclasses.replace(cfg, ssm_multipliers=(1.0, ) * 5)
    if control == "no_state_carry":
        scan = ssm.scan_ragged
        return _patched(ssm, scan_ragged=lambda x, dt, A, B, C, h0, *rest:
                        scan(x, dt, A, B, C, jnp.zeros_like(h0), *rest)), cfg
    if control == "no_conv_carry":
        conv = ssm.conv_ragged
        return _patched(ssm, conv_ragged=lambda xbc, w, b, tail, *rest:
                        conv(xbc, w, b, jnp.zeros_like(tail), *rest)), cfg
    if control == "no_attention":
        return _patched(model, _attn_phase=lambda self, ap, li, u, kv, attn_fn, batch:
                        (jnp.zeros_like(u), kv)), cfg
    if control == "no_mamba":
        return _patched(model, _mamba_phase=lambda self, mp, mi, h, pools, batch:
                        (jnp.zeros_like(h), tuple(pools))), cfg
    raise ValueError(f"no control {control!r}; known: {CONTROLS}")


def _rows(lines):
    """Every row's error, log2 of the largest logit, from the comparison's own
    log lines (``check.logits_close``'s detail)."""
    from benchmark.tools.controls import _ROW
    return [float(v) for line in lines for m in [_ROW.search(line)] if m
            for v in m.group(2).split(",")]


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="0")
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

    import jax
    from benchmark.runners import serve
    from benchmark.traffic_kinds import _draw
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine

    engine_cfg = config["engine"]
    budget = engine_cfg["state_manager"]["max_ragged_batch_size"]
    loop_steps = config["serving"].get("decode_chunk", 1)
    controls = sorted(args.controls.split(","), key=lambda c: c == "fp8_weights")
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.make_ctx(args.root, args.workload, cell, config, traffic, seed, 0.0, 0, log)
        family = ctx["family"]
        cfg = family.program_config(config)
        params = family.serving_params(cfg, seed)
        jax.block_until_ready(params)
        # the cell's own check prompts: runners/serve.py:prepare draws them so
        rng = np.random.default_rng([seed, 0xc0de])
        lengths = _draw.lengths(traffic["params"]["prompt"], serve.CHECK_PROMPTS, rng)
        prompts = [_draw.tokens(rng, cfg.vocab_size, n) for n in lengths]
        feeds = [_draw.tokens(rng, cfg.vocab_size, serve.CHECK_STEPS) for _ in prompts]
        ref = serve.reference_rows(family, params, config, prompts, feeds)
        log(f"seed {seed}: reference of prompts of {lengths.tolist()} tokens")
        result = {"workload": args.workload, "seed": seed, "controls": {},
                  "tolerance_log2": float(np.log2(
                      check.logit_rel_tol(config["num_hidden_layers"])))}
        for control in controls:
            if control == "fp8_weights":
                from benchmark.tools.controls import spoil
                params = None  # let go before the seed's weights are made again
                gc.collect()
                params = spoil(family.serving_params(cfg, seed), control)
                jax.block_until_ready(params)
            lines = []

            def keep(message, lines=lines, control=control):
                lines.append(message)
                log(f"{control}: {message}")

            patched, built_from = spoilt(control, cfg)
            with patched:
                engine = build_engine(params, built_from, RaggedInferenceEngineConfig(**engine_cfg))
                ok = serve.correctness(engine, family, config, budget, prompts, feeds, ref,
                                       loop_steps, keep)
                engine.close()
            del engine
            gc.collect()  # the engine sits in reference cycles, and its pools with it
            errors = _rows(lines)
            result["controls"][control] = {
                "correct": bool(ok), "rows": len(errors),
                "worst_log2": max(errors, default=None),
                "median_log2": float(np.median(errors)) if errors else None}
            log(f"seed {seed} {control}: correct={ok}")
            caught &= bool(ok) == (control == "baseline")
        print(json.dumps(result), flush=True)
        params = ref = None
        gc.collect()
    return 0 if caught else 4


if __name__ == "__main__":
    sys.exit(main())
