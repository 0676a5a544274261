"""LongCat-Flash family (``model_type="longcat_flash"``: the language model of
LongCat-Flash-Omni), served as one chip's share of a layer that thirty-two
chips share: two latent attentions and two dense halves a layer (two latent
layers of the pool a model layer), one routed branch that is computed in the
first half and added at the end of the second, a softmax router over 768
outputs of which 512 are SwiGLU experts (this chip holds 16) and 256 are
identity experts that have no bank; a slice of the vocabulary. From a
configuration file to the program's own objects.

The program's ``LongcatFlashConfig`` is imported before anything else: a program
without it (no expert without a bank, no layer that holds two latent layers of
the pool, no routed branch carried past a half-layer) cannot serve this family,
and a run of its cell exits here, in seconds, before any weight is made.

The configuration file states the experts HELD as ``n_routed_experts`` (a
reduced key) and the experts routed over under ``deployment_share``; the
program's config takes them the other way round (``n_routed_experts`` the
router's outputs that have banks, ``experts_held`` the banks'). The init's
constants that are the benchmark's own guesses are the program's
(``models/longcat_flash.py``'s three module constants); the file repeats them
under ``assumed.init_gains`` with the readings taken at other values.

The cold run's clock, as ``models/deepseek_v32.py``: weights made layer by
layer on the device; the reference on ids padded to ONE length
(``reference_pad_to``).
"""

from types import SimpleNamespace

try:
    from deepspeed_tpu.models.longcat_flash import LongcatFlashConfig
except ImportError as e:
    raise SystemExit(
        f"benchmark: this program has no deepspeed_tpu.models.longcat_flash ({e}): it cannot "
        f"serve a model whose router's outputs include experts without a bank, whose layer "
        f"holds two latent layers of the pool, and whose routed branch is added a half-layer "
        f"after it is computed. Nothing was measured.")

from benchmark import interval_lookup
from benchmark.references import longcat_flash as plain_reference

# what the file states another way round, or under ``assumed``
_OWN = {"n_routed_experts", "experts_held", "expert_rank", "dtype", "model_type"}

interval_lookup.install()


def program_config(sizes):
    import dataclasses

    import jax.numpy as jnp
    share = sizes["deployment_share"]
    # every key of the catalog row the program's config has a field for
    stated = {f.name for f in dataclasses.fields(LongcatFlashConfig)} - _OWN
    return LongcatFlashConfig(
        dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")),
        n_routed_experts=share["routed_over"], experts_held=sizes["n_routed_experts"],
        expert_rank=share["expert_rank"], **{k: sizes[k] for k in stated if k in sizes})


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed, layer by layer."""
    import jax
    from deepspeed_tpu.models import longcat_flash
    return longcat_flash.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)[1]


def _forward_logits_padded(params, sizes, ids, rows=None, routing_gaps=None):
    """``references/longcat_flash.py:forward_logits`` of ``ids`` padded with
    token 0 to ``reference_pad_to``: the same rows (every layer is causal), and
    one compilation for the four prompts of a check."""
    import numpy as np
    ids = np.asarray(ids)
    padded = np.zeros(max(ids.size, int(sizes.get("reference_pad_to", 0))), ids.dtype)
    padded[:ids.size] = ids
    return plain_reference.forward_logits(params, sizes, padded,
                                          rows=np.arange(ids.size) if rows is None else rows,
                                          routing_gaps=routing_gaps)


reference = SimpleNamespace(forward_logits=_forward_logits_padded)  # named for the harness
