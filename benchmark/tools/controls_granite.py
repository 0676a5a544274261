#!/usr/bin/env python3
"""What the Granite 4.0-H cell's ``correct`` can see of what its family adds:
the harness's own comparison (``runners/serve.py:correctness``, the cell's four
check prompts prefilled together in shares of a quarter of the token budget, the
same reference rows) on an engine spoilt on purpose, one mechanism at a time.
The baseline must read ``correct: true``; a control that reads true as well is
something the cell's comparison cannot see on the chip (exit code 4) and has to
be held by a tier-1 test instead (the configuration's ``engine_why.correct``
names it).

    python3 benchmark/tools/controls_granite.py --workload <cell> --seed <n>
        [--controls baseline,no_state_carry,no_conv_carry,residual_one,
                    softmax_scale,drop_expert,fp8_weights]

The reference is computed ONCE, from the unspoilt weights and the configuration
as stated. Each control changes one thing of the program, of its configuration
or of its tree while its engine is built and run (restored after):

- ``no_state_carry``: the Mamba-2 state NOT carried from one ``put`` to the
  next: every chunk of a prompt scans from zero (``decode_loop``'s recurrence
  still reads and writes its slot).
- ``no_conv_carry``: the convolution's tail not carried: the first rows of
  every chunk see zeros where the last rows of the chunk before belong.
- ``residual_one``: ``residual_multiplier`` 1.0 in place of 0.22, on both adds
  of every layer.
- ``softmax_scale``: the attention layer's scores times 1 / sqrt(head_dim) in
  place of ``attention_multiplier``.
- ``drop_expert``: ONE held expert's ``wo`` bank zero in every layer.
- ``fp8_weights``: ``controls.py``'s own (every matrix of the model but the
  float32 router rounded to float8: the nearest precision below the
  configuration's bfloat16). It must read false: it is what holds the stated
  precision. Run last: it consumes a tree of its own.

``logits_scaling`` has no control: a limit relative to the largest logit cannot
see a factor on every logit (tier-1 holds it, and ``embedding_multiplier``).

The run itself, its one JSON line and its exit code are ``controls_latent.py``'s
(the same comparison on the same kind of cell), handed this family's controls.
"""

import contextlib
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tools.controls_latent import spoilt as _latent_spoilt  # noqa: E402

CONTROLS = ("no_state_carry", "no_conv_carry", "residual_one", "softmax_scale", "drop_expert",
            "fp8_weights")


def spoilt(control, cfg, params, max_context):
    """``(cfg, params, context manager)`` of a control, as
    ``controls_latent.spoilt`` returns them."""
    import jax.numpy as jnp
    from benchmark.tools.controls_latent import _patched
    from deepspeed_tpu.inference.v2.modules import ssm
    nothing = contextlib.nullcontext()
    if control == "baseline":
        return cfg, params, nothing
    if control == "residual_one":
        return dataclasses.replace(cfg, residual_multiplier=1.0), params, nothing
    if control == "softmax_scale":
        return dataclasses.replace(cfg, attention_multiplier=cfg.head_dim**-0.5), params, nothing
    if control == "no_state_carry":
        scan = ssm.scan_in_place
        return cfg, params, _patched(
            ssm, scan_in_place=lambda pool, block, slot, live, started, *rest, **kw:
            scan(pool, block, slot, live, jnp.zeros_like(started), *rest, **kw))
    if control == "no_conv_carry":
        conv = ssm.conv_ragged
        return cfg, params, _patched(ssm, conv_ragged=lambda xbc, w, b, tail, *rest:
                                     conv(xbc, w, b, jnp.zeros_like(tail), *rest))
    if control == "drop_expert":  # the same tree names: ``layers_N.mlp.experts.wo``
        return _latent_spoilt(control, cfg, params, max_context)
    raise ValueError(f"no control {control!r}; known: {CONTROLS}")


def main(argv=None):
    from benchmark.tools import controls_latent
    with controls_latent._patched(controls_latent, spoilt=spoilt, CONTROLS=CONTROLS):
        return controls_latent.main(argv)


if __name__ == "__main__":
    sys.exit(main())
