#!/usr/bin/env python3
"""What the LongCat-Flash cell's ``correct`` can see of what its family adds:
the harness's own comparison (``runners/serve.py:correctness``, the cell's four
check prompts prefilled together in shares of a quarter of the token budget, the
same reference rows) on an engine spoilt on purpose, one mechanism at a time.
The baseline must read ``correct: true``; a control that reads true as well is
something the cell's comparison cannot see on the chip (exit code 4) and has to
be held by a tier-1 test instead (the configuration's ``engine_why.correct``
names it).

    python3 benchmark/tools/controls_longcat.py --workload <cell> --seed <n>
        [--controls baseline,no_identity,branch_early,no_kv_lora_scale,
                    second_half_first_cache,drop_expert,fp8_weights]

The reference is computed ONCE, from the unspoilt weights. Each control changes
one thing of the program or of its tree while its engine is built and run
(restored after):

- ``no_identity``: the experts without a bank return nothing: their weights are
  still chosen and counted, ``h`` times their sum is not added.
- ``branch_early``: the routed branch added where it is computed, after the
  first dense half, so that the second attention and the second dense half read
  it; the layer's output has it once either way.
- ``no_kv_lora_scale``: ``mla_scale_kv_lora`` dropped: the normed latent cached
  and expanded without its ``(hidden / kv_lora_rank)^1/2``.
- ``second_half_first_cache``: the second half's attention reads the FIRST
  half's latent layer of the pool (its own rows are still written where they
  belong).
- ``drop_expert``: ONE held expert's ``wo`` bank zero in every layer.
- ``fp8_weights``: ``controls.py``'s own (every matrix of the model but the
  float32 router rounded to float8: the nearest precision below the
  configuration's bfloat16). It must read false: it is what holds the stated
  precision. Run last: it consumes a tree of its own.

The run itself, its one JSON line and its exit code are ``controls_latent.py``'s
(the same comparison on the same kind of cell), handed this family's controls.
"""

import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROLS = ("no_identity", "branch_early", "no_kv_lora_scale", "second_half_first_cache",
            "drop_expert", "fp8_weights")


def _branch_early(self, params, li, x, cache, attn_fn, batch):
    """``LongcatFlashV2Model.layer_forward`` with the routed branch joined to
    the stream beside the first dense half."""
    from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _root
    lp = _root(params)[f"layers_{li}"]
    (latent_pool, ) = cache
    x, h, latent_pool = self._half(lp, li, 0, x, latent_pool, batch)
    routed = self._routed_beside_shared(li, h, lp["mlp"]["gate"], lp["mlp"]["experts"],
                                        lp["mlp"]["e_score_correction_bias"], None, batch)
    x = x + self._dense(h, lp["mlps_0"]) + routed
    x, h, latent_pool = self._half(lp, li, 1, x, latent_pool, batch)
    return x + self._dense(h, lp["mlps_1"]), (latent_pool, )


def spoilt(control, cfg, params, max_context):
    """``(cfg, params, context manager)`` of a control, as
    ``controls_latent.spoilt`` returns them."""
    import jax
    import jax.numpy as jnp
    from benchmark.tools.controls_latent import _patched
    from deepspeed_tpu.inference.v2.model_implementations import longcat_flash_v2 as served
    from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
    from deepspeed_tpu.models.longcat_flash import LongcatFlashConfig
    from deepspeed_tpu.ops.pallas import latent_attention
    if control == "baseline":
        return cfg, params, contextlib.nullcontext()
    if control == "drop_expert":  # the dicts on the way to the one leaf copied, the rest shared
        out = dict(params)
        for name, layer in params.items():
            if isinstance(layer, dict) and "experts" in layer.get("mlp", {}):
                bank = layer["mlp"]["experts"]
                dead = jax.jit(lambda wo: wo.at[0].set(0))(bank["wo"])
                out[name] = dict(layer, mlp=dict(layer["mlp"], experts=dict(bank, wo=dead)))
        return cfg, out, contextlib.nullcontext()
    if control == "no_identity":
        return cfg, params, _patched(
            RaggedMoE, _zero_term=lambda self, h, *chosen: jnp.zeros(h.shape, jnp.float32))
    if control == "branch_early":
        return cfg, params, _patched(served.LongcatFlashV2Model, layer_forward=_branch_early)
    if control == "no_kv_lora_scale":
        return cfg, params, _patched(LongcatFlashConfig, kv_lora_scale=property(lambda self: 1.0))
    if control == "second_half_first_cache":
        kernel, plain = (latent_attention.latent_paged_attention,
                         latent_attention.latent_paged_attention_xla)

        def first_half(attend):
            return lambda q, pool, li, *rest, **kw: attend(q, pool, li - li % 2, *rest, **kw)
        return cfg, params, _patched(latent_attention,
                                     latent_paged_attention=first_half(kernel),
                                     latent_paged_attention_xla=first_half(plain))
    raise ValueError(f"no control {control!r}; known: {CONTROLS}")


def main(argv=None):
    from benchmark.tools import controls_latent
    with controls_latent._patched(controls_latent, spoilt=spoilt, CONTROLS=CONTROLS):
        return controls_latent.main(argv)


if __name__ == "__main__":
    sys.exit(main())
