#!/usr/bin/env python3
"""What a state-space cell's ``correct`` can see of the per-sequence state its
family adds: the harness's own comparison (``runners/serve.py:correctness``,
the cell's four check prompts prefilled together in shares of a quarter of the
token budget, the same reference rows) on an engine spoilt on purpose, one
mechanism at a time. The baseline must read ``correct: true``; a control that
reads true as well is something the cell's comparison cannot see on the chip
(exit code 4) and has to be held by a tier-1 test instead (the configuration's
``engine_why.correct`` names it).

    python3 benchmark/tools/controls_ssm.py --workload <cell> --seed <n>
        [--controls baseline,state_bf16,no_state_carry,no_conv_carry,fp8_weights]

The reference is computed ONCE, from the unspoilt weights. Each control changes
one thing of the program's state-space path while its engine is built and run
(restored after):

- ``state_bf16``: the Mamba-2 state pool in bfloat16 where the configuration
  states float32: the state is rounded every time a step leaves it.
- ``no_state_carry``: the state NOT carried from one ``put`` to the next: every
  chunk of a prompt scans from zero (``decode_loop``'s recurrence still reads
  and writes its slot).
- ``no_conv_carry``: the convolution's tail not carried: the first rows of
  every chunk see zeros where the last rows of the chunk before belong.
- ``fp8_weights``: ``controls.py``'s own (every matrix of the model but the
  float32 router rounded to float8, a matrix or an expert a scale: the nearest
  precision below the configuration's bfloat16). It must read false: it is what
  holds the stated precision. Run last: it consumes a tree of its own, and two
  do not fit on the chip.

Prints one JSON line: per control ``correct`` and the largest row error on the
rows held to the tight and to the loose tolerance, as log2 of the largest
logit. Runs on the chip (``--rehearsal 1`` runs wherever JAX runs, for the
tests, and proves nothing about a chip). The row parser is ``controls.py``'s,
the patching ``controls_latent.py``'s.
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROLS = ("state_bf16", "no_state_carry", "no_conv_carry", "fp8_weights")


def spoilt(control):
    """The context manager of a control."""
    import jax.numpy as jnp
    from benchmark.tools.controls_latent import _patched
    from deepspeed_tpu.inference.v2.model_implementations import nemotron_h_v2 as served
    from deepspeed_tpu.inference.v2.modules import ssm
    if control in ("baseline", "fp8_weights"):  # the second spoils the tree, not the program
        return contextlib.nullcontext()
    if control == "state_bf16":
        stated = served.NemotronHV2Model.sequence_state

        def in_bf16(self):
            return tuple(spec.model_copy(update={"dtype": "bfloat16"}) if spec.name == "ssm"
                         else spec for spec in stated.fget(self))
        return _patched(served.NemotronHV2Model, sequence_state=property(in_bf16))
    if control == "no_state_carry":
        scan = ssm.scan_ragged
        return _patched(ssm, scan_ragged=lambda x, dt, A, B, C, h0, *rest:
                        scan(x, dt, A, B, C, jnp.zeros_like(h0), *rest))
    if control == "no_conv_carry":
        conv = ssm.conv_ragged
        return _patched(ssm, conv_ragged=lambda xbc, w, b, tail, *rest:
                        conv(xbc, w, b, jnp.zeros_like(tail), *rest))
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
    for control in sorted(args.controls.split(","), key=lambda c: c == "fp8_weights"):
        if control == "fp8_weights":
            from benchmark.tools.controls import spoil
            params = None  # let go before the seed's weights are made again
            gc.collect()
            params = spoil(family.serving_params(cfg, args.seed), control)
            jax.block_until_ready(params)
        lines = []

        def keep(message, lines=lines, control=control):
            lines.append(message)
            log(f"{control}: {message}")

        with spoilt(control):
            engine = build_engine(params, cfg, RaggedInferenceEngineConfig(**engine_cfg))
            ok = serve.correctness(engine, family, config, budget, prompts, feeds, ref,
                                   loop_steps, keep)
            engine.close()
        del engine
        gc.collect()  # the engine sits in reference cycles, and its pools with it
        result["controls"][control] = dict(_worst(lines, ref, check.ROUTING_TOSS_UP_GAP),
                                           correct=bool(ok))
        log(f"{control}: correct={ok}")
    print(json.dumps(result), flush=True)
    want = {c: c == "baseline" for c in result["controls"]}
    return 0 if all(result["controls"][c]["correct"] == w for c, w in want.items()) else 4


if __name__ == "__main__":
    sys.exit(main())
