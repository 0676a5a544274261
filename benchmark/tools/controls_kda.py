#!/usr/bin/env python3
"""What a delta-rule cell's ``correct`` can see of the mechanisms its family
adds: the harness's own comparison (``runners/serve.py:correctness``, the
cell's four check prompts prefilled together in shares of a quarter of the
token budget, the same reference rows) on an engine spoilt on purpose, one
mechanism at a time. The baseline must read ``correct: true``; a control that
reads true as well is something the cell's comparison cannot see on the chip
(exit code 4) and has to be held by a tier-1 test instead (the configuration's
``engine_why.correct`` names it).

    python3 benchmark/tools/controls_kda.py --workload <cell> --seed <n>
        [--controls baseline,no_state_carry,no_conv_carry,beta_one,no_decay,fp8_weights]

The reference is computed ONCE, from the unspoilt weights. Each control changes
one thing of the program's delta-rule path while its engine is built and run
(restored after):

- ``no_state_carry``: the matrix state NOT carried from one ``put`` to the
  next: every chunk of a prompt scans from zero (``decode_loop``'s recurrence
  still reads and writes its slot).
- ``no_conv_carry``: the three convolutions' tails not carried: the first rows
  of every chunk see zeros where the last rows of the chunk before belong.
- ``beta_one``: beta without its factor 2 (``kda_allow_neg_eigval``): the
  correction at half its strength, no eigenvalue below zero.
- ``no_decay``: the decay dropped (alpha = 1): the state forgets nothing.
- ``state_bf16``: the state pool in bfloat16 where the configuration states
  float32 (off the kernel's shape rule: the recurrence between the slot
  copies). Not among the default controls: listed to be READ, a rounding once a
  step over 128 x 128 a head may well pass, and tier-1 holds the float32 pool.
- ``fp8_weights``: ``controls.py``'s own (every matrix of the model but the
  float32 router rounded to float8: the nearest precision below the
  configuration's bfloat16). It must read false: it is what holds the stated
  precision. Run last: it consumes a tree of its own.

The run itself, its one JSON line and its exit code are ``controls_ssm.py``'s
(the same comparison on the same kind of cell), handed this family's controls.
"""

import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROLS = ("no_state_carry", "no_conv_carry", "beta_one", "no_decay", "fp8_weights")


def spoilt(control):
    """The context manager of a control."""
    import jax.numpy as jnp
    from benchmark.tools.controls_latent import _patched
    from deepspeed_tpu.inference.v2.model_implementations import solar_open2_v2 as served
    from deepspeed_tpu.inference.v2.modules import kda, ssm
    from deepspeed_tpu.models.solar_open2 import SolarOpen2Config
    if control in ("baseline", "fp8_weights"):  # the second spoils the tree, not the program
        return contextlib.nullcontext()
    if control == "no_state_carry":
        scan = kda.scan_in_place
        return _patched(kda, scan_in_place=lambda pool, block, slot, live, started, *rest, **kw:
                        scan(pool, block, slot, live, jnp.zeros_like(started), *rest, **kw))
    if control == "no_conv_carry":
        conv = ssm.conv_ragged
        return _patched(ssm, conv_ragged=lambda x, w, b, tail, *rest:
                        conv(x, w, b, jnp.zeros_like(tail), *rest))
    if control == "beta_one":
        return _patched(SolarOpen2Config, beta_scale=property(lambda self: 1.0))
    if control == "no_decay":
        decay = kda.decay
        return _patched(kda, decay=lambda *a, **kw: jnp.zeros_like(decay(*a, **kw)))
    if control == "state_bf16":
        stated = served.SolarOpen2V2Model.sequence_state

        def in_bf16(self):
            return tuple(spec.model_copy(update={"dtype": "bfloat16"}) if spec.name == "kda"
                         else spec for spec in stated.fget(self))
        return _patched(served.SolarOpen2V2Model, sequence_state=property(in_bf16))
    raise ValueError(f"no control {control!r}; known: {CONTROLS + ('state_bf16', )}")


def main(argv=None):
    from benchmark.tools import controls_ssm
    from benchmark.tools.controls_latent import _patched
    with _patched(controls_ssm, spoilt=spoilt, CONTROLS=CONTROLS):
        return controls_ssm.main(argv)


if __name__ == "__main__":
    sys.exit(main())
