#!/usr/bin/env python3
"""What the Kimi-Linear cell's ``correct`` can see of its two mixers and of the
cache that holds both: the harness's own comparison
(``runners/serve.py:correctness``, the cell's four check prompts prefilled
together in shares of a quarter of the token budget, the same reference rows)
on an engine spoilt on purpose, one mechanism at a time. The baseline must read
``correct: true``; a control that reads true as well is something the cell's
comparison cannot see on the chip (exit code 4) and has to be held by a tier-1
test instead (the configuration's ``engine_why.correct`` names it).

    python3 benchmark/tools/controls_kimi.py --workload <cell> --seed <n>
        [--controls baseline,no_state_carry,no_conv_carry,beta_two,k_r_rotated,
                    wrong_latent_layer,fp8_weights]

The reference is computed ONCE, from the unspoilt weights. Each control changes
one thing of the program while its engine is built and run (restored after):

- ``no_state_carry``: the delta rule's matrix state NOT carried from one
  ``put`` to the next: every chunk of a prompt scans from zero
  (``decode_loop``'s recurrence still reads and writes its slot).
- ``no_conv_carry``: the three convolutions' tails not carried: the first rows
  of every chunk see zeros where the last rows of the chunk before belong.
- ``beta_two``: beta WITH Solar Open 2's factor 2 (``kda_allow_neg_eigval``,
  which this configuration has not): the correction at twice its strength.
- ``k_r_rotated``: the shared key's 64 dims rotated by position as they are
  cached (DeepSeek's rotary at ``rope_theta``; the queries stay as they are):
  what ``mla_use_nope`` leaves out, put back.
- ``wrong_latent_layer``: a latent layer reads the OTHER latent layer's rows of
  the pool (its own are still written where they belong).
- ``state_bf16``: the state pool in bfloat16 where the configuration states
  float32. Not among the default controls, which must each read false: it reads
  TRUE at the timed sizes (PR 56, seed 3000005681: -7.1 / -6.5 beside the
  baseline's -7.1 / -6.5), as Solar's does; tier-1 alone holds the float32 pool.
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

CONTROLS = ("no_state_carry", "no_conv_carry", "beta_two", "k_r_rotated", "wrong_latent_layer",
            "fp8_weights")


def spoilt(control):
    """The context manager of a control."""
    import jax.numpy as jnp
    from benchmark.tools.controls_latent import _patched
    from deepspeed_tpu.inference.v2.model_implementations import kimi_linear_v2 as served
    from deepspeed_tpu.inference.v2.model_implementations.deepseek_v32_v2 import _rotate_pairs
    from deepspeed_tpu.inference.v2.modules import kda, ssm
    from deepspeed_tpu.models.kimi_linear import KimiLinearConfig
    from deepspeed_tpu.models.mellum import rotary_cos_sin
    from deepspeed_tpu.ops.pallas import latent_attention
    model = served.KimiLinearV2Model
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
    if control == "beta_two":
        return _patched(KimiLinearConfig, beta_scale=property(lambda self: 2.0))
    if control == "k_r_rotated":
        write = model._write_rows

        def rotated(self, pool, li, rows, batch):
            cfg = self._config
            C, R = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            cos, sin = rotary_cos_sin({"rope_type": "default", "rope_theta": cfg.rope_theta},
                                      batch["token_pos"], R)
            k_r = _rotate_pairs(rows[:, None, C:C + R], cos[:, None, :], sin[:, None, :])[:, 0]
            return write(self, pool, li, rows.at[:, C:C + R].set(k_r), batch)
        return _patched(model, _write_rows=rotated)
    if control == "wrong_latent_layer":
        kernel, plain = (latent_attention.latent_paged_attention,
                         latent_attention.latent_paged_attention_xla)

        def other(attend):
            return lambda q, pool, li, *rest, **kw: attend(q, pool, (li + 1) % pool.shape[0],
                                                           *rest, **kw)
        return _patched(latent_attention, latent_paged_attention=other(kernel),
                        latent_paged_attention_xla=other(plain))
    if control == "state_bf16":
        stated = model.sequence_state

        def in_bf16(self):
            return tuple(spec.model_copy(update={"dtype": "bfloat16"}) if spec.name == "kda"
                         else spec for spec in stated.fget(self))
        return _patched(model, sequence_state=property(in_bf16))
    raise ValueError(f"no control {control!r}; known: {CONTROLS + ('state_bf16', )}")


def main(argv=None):
    from benchmark.tools import controls_ssm
    from benchmark.tools.controls_latent import _patched
    with _patched(controls_ssm, spoilt=spoilt, CONTROLS=CONTROLS):
        return controls_ssm.main(argv)


if __name__ == "__main__":
    sys.exit(main())
