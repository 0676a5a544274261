#!/usr/bin/env python3
"""The readings a served cell's row limits are set between (``check.row_limits``,
a configuration's ``check`` group): for each seed, every row of the harness's own
check (``runners/serve.py``: the cell's four check prompts and fed tokens, the
same reference, the same engine calls) with its error as a share of the largest
logit and whether the reference calls it a toss-up, from the weights as the seed
makes them and, for ``--control-seeds``, from the same weights through float8
(``controls.py``'s ``fp8_weights``: the nearest precision below bfloat16).

    python3 benchmark/tools/check_rows.py --workload <cell> --seeds <n>[,<n>...]
        [--control-seeds <n>[,<n>...]] [--out chiprun_out/rows]

One process for all the seeds (a seed costs its weights, its reference and one
engine: ~25 s on the chip for DeepSeek's cell, ~10 s more with the control).
Writes ``<out>/<cell>.<seed>.json`` and prints one JSON line a seed: the worst
tight row, the worst loose row and the median row, as log2, beside the limits
the configuration is held to now. Runs on the chip (``--rehearsal 1`` runs
wherever JAX runs, for the tests, and proves nothing about a chip).
"""

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _log2(v):
    return round(float(np.log2(max(v, 1e-12))), 2)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--out", default=os.path.join("chiprun_out", "rows"))
    parser.add_argument("--rehearsal", type=int, default=0)
    parser.add_argument("--root", default=ROOT)
    args = parser.parse_args(argv)
    t_start = time.perf_counter()

    def log(message):
        print(f"[{time.perf_counter() - t_start:7.1f}s] {message}", file=sys.stderr, flush=True)

    from benchmark import check, harness
    started = harness.start(args.root, args.workload, bool(args.rehearsal), log)
    if isinstance(started, int):
        return started
    _, cell, config, traffic, _ = started

    import jax
    from benchmark.runners import serve
    from benchmark.tools.controls import spoil
    from benchmark.traffic_kinds import _draw
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine

    out_dir = os.path.join(args.root, args.out)
    os.makedirs(out_dir, exist_ok=True)
    limits = check.row_limits(config)
    engine_cfg = config["engine"]
    budget = engine_cfg["state_manager"]["max_ragged_batch_size"]
    loop_steps = config["serving"].get("decode_chunk", 1)
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
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
        record = {"workload": args.workload, "seed": seed, "lengths": lengths.tolist(),
                  "limits_log2": {k: None if v is None else _log2(v) for k, v in limits.items()}}
        line = {"seed": seed}
        for side in ["seed_weights"] + (["fp8_weights"] if seed in control_seeds else []):
            if side == "fp8_weights":
                params = None  # let go before the seed's weights are made again
                gc.collect()
                its = spoil(family.serving_params(cfg, seed), side)
                jax.block_until_ready(its)
            else:
                its = params
            engine = build_engine(its, cfg, RaggedInferenceEngineConfig(**engine_cfg))
            got, _ = serve.engine_rows(engine, budget, prompts, feeds, loop_steps)
            engine.close()
            del engine, its
            gc.collect()  # the engine sits in reference cycles, and its KV pool with it
            rows = []
            for (r, gaps), g in zip(ref, got):
                check.logits_close(r[:g.shape[0]], g, limits["tight"],
                                   routing_gaps=None if gaps is None else gaps[:g.shape[0]],
                                   loose_tol=limits["loose"], row_errors=rows)
            err = np.asarray([e for e, _ in rows])
            loose = np.asarray([k for _, k in rows], bool)
            record[side] = {"error": err.tolist(), "loose": loose.tolist()}
            line[side] = {"rows": len(rows), "tight_rows": int((~loose).sum()),
                          "worst_tight_log2": _log2(err[~loose].max()) if (~loose).any() else None,
                          "worst_loose_log2": _log2(err[loose].max()) if loose.any() else None,
                          "median_log2": _log2(float(np.median(err)))}
        params = ref = None
        gc.collect()
        with open(os.path.join(out_dir, f"{args.workload}.{seed}.json"), "w") as f:
            json.dump(record, f)
        print(json.dumps(dict(line, limits_log2=record["limits_log2"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
