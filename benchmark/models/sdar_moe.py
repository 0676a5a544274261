"""SDAR family (``model_type="sdar_moe"``: SDAR-30B-A3B-Chat): generation by
diffusion over blocks — attention under a block mask, a decode step that
rewrites a block of rows and commits its K/V once, several tokens a sequence a
step — on softmax top-8 of 128 experts. From a configuration file to the
program's own objects.

The program's ``SdarMoeConfig`` is imported before anything else: a program
without it cannot serve this family, and a run of its cell exits here, in
seconds, before any weight is made.

The cold run's clock, as ``models/falcon_h1.py``: weights made on the device
layer by layer; the reference on ids padded to ONE length
(``reference_pad_to``, a multiple of the block: the padding starts at a block's
edge, so no row asked for sees it).
"""

from types import SimpleNamespace

try:
    from deepspeed_tpu.models.sdar_moe import SdarMoeConfig
except ImportError as e:
    raise SystemExit(
        f"benchmark: this program has no deepspeed_tpu.models.sdar_moe ({e}): it cannot serve a "
        f"model that generates by diffusion over blocks (attention under a block mask, a step "
        f"that rewrites a block of rows, commits its K/V once and hands over several tokens a "
        f"sequence). Nothing was measured.")

from benchmark import interval_lookup
from benchmark.references import sdar_moe as plain_reference

# what the file states in another form, or not at all
_OWN = {"dtype", "model_type"}

interval_lookup.install()


def program_config(sizes):
    import dataclasses

    import jax.numpy as jnp
    # every key of the catalog row the program's config has a field for, and the
    # four generation settings the published config.json has no key for
    stated = {f.name for f in dataclasses.fields(SdarMoeConfig)} - _OWN
    given = dict(sizes, **plain_reference.generation(sizes))
    return SdarMoeConfig(dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")),
                         **{k: given[k] for k in stated if k in given})


def serving_params(cfg, seed, init):
    """bf16 weights made on the device from the seed, layer by layer. ``init``
    is the configuration's ``assumed.init``: what the seeded model's two
    branches are multiplied by (``assumed.why.init`` says why)."""
    import jax
    from deepspeed_tpu.models import sdar_moe
    return sdar_moe.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype,
                                attention_gain=init["attention_gain"],
                                expert_gain=init["expert_gain"])[1]


def _forward_logits_padded(params, sizes, ids, rows=None, routing_gaps=None, flags=None):
    """``references/sdar_moe.py:forward_logits`` of ``ids`` (whole blocks)
    padded with token 0 to ``reference_pad_to``: the same rows, and one
    compilation for every state of a check."""
    import numpy as np
    ids = np.asarray(ids)
    block = int(plain_reference.generation(sizes)["block_length"])
    if ids.size % block:
        raise ValueError(f"{ids.size} ids are not whole blocks of {block}: the padding would "
                         f"be seen")
    n = max(ids.size, int(sizes.get("reference_pad_to", 0)))
    padded = np.zeros(n, ids.dtype)
    padded[:ids.size] = ids
    marks = np.zeros(n, bool)
    if flags is not None:
        marks[:ids.size] = flags
    return plain_reference.forward_logits(params, sizes, padded,
                                          rows=np.arange(ids.size) if rows is None else rows,
                                          routing_gaps=routing_gaps, flags=marks)


reference = SimpleNamespace(forward_logits=_forward_logits_padded,  # named for the harness
                            generation=plain_reference.generation,
                            confidence=plain_reference.confidence,
                            most_confident=plain_reference.most_confident)
