#!/usr/bin/env python3
"""What the SDAR cell's ``correct`` can see of what its family adds: the
harness's own comparison (``runners/serve_blocks.py:correctness``: the cell's
check prompts and its probe, every denoise forward of two blocks, every choice
of the timed block loop, the block after it) on an engine spoilt on purpose,
one mechanism at a time. The baseline must read ``correct: true``; a control
that reads true as well is something the cell's comparison cannot see on the
chip (exit code 4) and has to be held by a tier-1 test instead (the
configuration's ``engine_why.correct`` names it).

    python3 benchmark/tools/controls_sdar.py --workload <cell> --seeds <n>[,<n>...]
        [--controls baseline,causal_mask,no_commit,left_to_right,fp8_banks,fp8_weights]
        [--init attention_gain=<x>,expert_gain=<y>]

``--init`` reads the controls under another seeded init than the
configuration's ``assumed.init`` (how the two gains were chosen: the honest
reading and the banks-in-float8 reading at each).

Each control changes one thing of the program while its engine is built and
run (restored after):

- ``causal_mask``: the paged kernel's tile grid (and the XLA arm) called
  without the block mask: a row no longer sees the later rows of its block.
- ``no_commit``: the commit skipped — the block loop's commit forward leaves
  the pool as it is and the check's commit ``put`` only moves ``seen_tokens``:
  the K/V in place is the last denoise forward's, whose last-taken row was
  still fed the mask token.
- ``left_to_right``: rows unmasked left to right instead of by confidence, in
  the block loop's program.
- ``fp8_banks`` / ``fp8_weights``: ``controls.py``'s own (the routed experts'
  banks / every matrix rounded to float8, a matrix a scale: the nearest
  precision below the configuration's bfloat16). ``fp8_weights`` must read
  false: it is what holds the stated precision. Run last: each consumes a tree
  of its own, and two do not fit on the chip. On request, ``controls.py``'s
  ``wrong_bank`` (every assignment computed by its neighbour's weights) and
  ``drop_expert`` (ONE dead bank a layer) the same way.

Prints one JSON line a seed: per control ``correct``, the rows' errors as log2
of the largest logit (worst, median and the lowest decile the runner's part (iv)
holds) and the number of ``WRONG`` lines. Runs
on the chip (``--rehearsal 1`` runs wherever JAX runs, for the tests, and
proves nothing about a chip).
"""

import argparse
import contextlib
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

CONTROLS = ("causal_mask", "no_commit", "left_to_right", "fp8_banks", "fp8_weights")
# spoil the tree, not the program (controls.py's; the last two on request only)
_TREES = ("fp8_banks", "fp8_weights", "wrong_bank", "drop_expert")


class _CommitSkipped:
    """The engine, but a ``put`` that commits one finished block a sequence
    only moves ``seen_tokens``: the block's K/V stays the last denoise
    forward's."""

    def __init__(self, engine, block):
        self._engine, self._block = engine, block

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def put(self, uids, tokens, **kw):
        manager = self._engine._state_manager
        if all(np.size(t) == self._block and manager.get_sequence(u) is not None
               and manager.get_sequence(u).seen_tokens for u, t in zip(uids, tokens)):
            for u in uids:
                manager.get_sequence(u).pre_forward(self._block)
                manager.get_sequence(u).post_forward()
            return None
        return self._engine.put(uids, tokens, **kw)


@contextlib.contextmanager
def _all(*patches):
    with contextlib.ExitStack() as stack:
        for patch in patches:
            stack.enter_context(patch)
        yield


def spoilt(control, cfg):
    """``(context manager, what wraps the engine)``."""
    import jax
    import jax.numpy as jnp
    from benchmark.tools.controls_latent import _patched
    from deepspeed_tpu.inference.v2.model_implementations.transformer_base import (
        DSTransformerModelBase as base)
    from deepspeed_tpu.ops.pallas import paged_attention
    same = (lambda engine: engine)
    if control == "baseline" or control in _TREES:
        return contextlib.nullcontext(), same
    if control == "causal_mask":
        tiled, gather = paged_attention.paged_attention_prefill, base._gather_attention
        return _all(
            _patched(paged_attention, paged_attention_prefill=lambda *a, block=0, **kw:
                     tiled(*a, **kw)),
            _patched(base, _gather_attention=lambda self, *a, block=0, **kw:
                     gather(self, *a, **kw))), same
    if control == "no_commit":
        forward = base._forward_impl

        def skipped(self, params, cache, batch, rows="last"):
            if rows != "none":
                return forward(self, params, cache, batch, rows)
            grouped = self.moe_path(batch["tok_meta"].shape[1]) == "grouped"
            banks = (jnp.zeros((self.num_layers, ), jnp.int32), ) if grouped else ()
            return (None, cache, *banks)

        return _patched(base, _forward_impl=skipped), \
            (lambda engine: _CommitSkipped(engine, cfg.block_length))
    if control == "left_to_right":
        top_k, B = jax.lax.top_k, cfg.block_length

        def leftmost(x, k):
            if x.shape[-1] != B or k != B // cfg.denoising_steps:
                return top_k(x, k)  # the router's
            rank = -jnp.arange(B, dtype=x.dtype)
            return top_k(jnp.where(x >= 0, rank, -jnp.inf), k)

        return _patched(jax.lax, top_k=leftmost), same
    raise ValueError(f"no control {control!r}; known: {CONTROLS}")


_ROW = re.compile(r"correct\[\d+\] .*?largest logit \([^)]*\): \[([^\]]*)\]")


_BEST = re.compile(r"lowest decile of \d+ rows is off by 2\^(-?[\d.]+)")


def _rows(lines):
    """Every row's error, log2 of the largest logit, from the comparison's own
    log lines (``check.logits_close``'s detail)."""
    return [float(v) for line in lines for m in [_ROW.search(line)] if m
            for v in m.group(1).split(",")]


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="0")
    parser.add_argument("--controls", default="baseline," + ",".join(CONTROLS))
    parser.add_argument("--init", default="")
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
    if args.init:
        gains = {k: float(v) for k, v in (kv.split("=") for kv in args.init.split(","))}
        config["assumed"]["init"] = dict(config["assumed"]["init"], **gains)
        log(f"init: {config['assumed']['init']} (the configuration's, but {gains})")

    import jax
    from benchmark.runners import serve_blocks
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine

    controls = sorted(args.controls.split(","), key=lambda c: c in _TREES)  # stable: trees last
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.make_ctx(args.root, args.workload, cell, config, traffic, seed, 0.0, 0, log)
        family = ctx["family"]
        cfg = family.program_config(config)
        loop_blocks = config["serving"]["decode_chunk"] // cfg.block_length
        params = family.serving_params(cfg, seed, config["assumed"]["init"])
        jax.block_until_ready(params)
        # the cell's own check prompts: runners/serve_blocks.py:prepare draws them so
        prompts = serve_blocks.check_prompts(traffic["params"], cfg.vocab_size, cfg.block_length,
                                             np.random.default_rng([seed, 0xc0de]))
        log(f"seed {seed}: prompts of {[p.size for p in prompts]} tokens")
        result = {"workload": args.workload, "seed": seed, "controls": {},
                  "tolerance_log2": float(np.log2(
                      check.logit_rel_tol(config["num_hidden_layers"])))}
        for control in controls:
            if control in _TREES:
                from benchmark.tools.controls import spoil
                params = None  # let go before the seed's weights are made again
                gc.collect()
                params = spoil(family.serving_params(cfg, seed, config["assumed"]["init"]), control)
                jax.block_until_ready(params)
            lines = []

            def keep(message, lines=lines, control=control):
                lines.append(message)
                log(f"{control}: {message}")

            patched, wrap = spoilt(control, cfg)
            with patched:
                engine = build_engine(params, cfg, RaggedInferenceEngineConfig(**config["engine"]))
                records = serve_blocks.system_side(wrap(engine), family, config, prompts,
                                                   loop_blocks)
                engine.close()
            del engine
            gc.collect()  # the engine sits in reference cycles, and its pool with it
            if control in _TREES:
                # the reference reads the HONEST weights: two trees do not fit on the chip
                params = None
                gc.collect()
                params = family.serving_params(cfg, seed, config["assumed"]["init"])
            ok = serve_blocks.judge(*records, family, config, params, keep)
            errors = _rows(lines)
            result["controls"][control] = {
                "correct": bool(ok), "rows": len(errors),
                "wrong_lines": sum("WRONG" in line for line in lines),
                "worst_log2": max(errors, default=None),
                "median_log2": float(np.median(errors)) if errors else None,
                # what the runner's (iv) holds, unrounded, from its own line
                "best_rows_log2": next((float(m.group(1)) for line in lines
                                        for m in [_BEST.search(line)] if m), None)}
            log(f"seed {seed} {control}: correct={ok}")
            caught &= bool(ok) == (control == "baseline")
        print(json.dumps(result), flush=True)
        params = None
        gc.collect()
    return 0 if caught else 4


if __name__ == "__main__":
    sys.exit(main())
