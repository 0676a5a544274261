"""Falcon-H1 family (``model_type="falcon_h1"``: Falcon-H1-34B-Instruct): a
Mamba-2 mixer and an attention mixer side by side in EVERY layer (paged K/V and
a per-sequence state in each), a dense gated feed-forward, fourteen forward
multipliers, the whole vocabulary. From a configuration file to the program's
own objects.

The program's ``FalconH1Config`` is imported before anything else: a program
without it cannot serve this family, and a run of its cell exits here, in
seconds, before any weight is made.

The cold run's clock, as ``models/nemotron_h.py``: weights made on the device
(the two 261120 x 5120 matrices a block of rows at a time); the reference on
ids padded to ONE length (``reference_pad_to``).
"""

from types import SimpleNamespace

try:
    from deepspeed_tpu.models.falcon_h1 import FalconH1Config
except ImportError as e:
    raise SystemExit(
        f"benchmark: this program has no deepspeed_tpu.models.falcon_h1 ({e}): it cannot serve "
        f"a model whose every layer runs a Mamba-2 mixer beside attention (paged K/V and a "
        f"per-sequence state in each layer, the family's forward multipliers). Nothing was "
        f"measured.")

from benchmark import interval_lookup
from benchmark.references import falcon_h1 as plain_reference

# what the file states in another form, or not at all
_OWN = {"dtype", "model_type"}

interval_lookup.install()


def program_config(sizes):
    import dataclasses

    import jax.numpy as jnp
    # every key of the catalog row the program's config has a field for
    stated = {f.name for f in dataclasses.fields(FalconH1Config)} - _OWN
    return FalconH1Config(dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")),
                          **{k: sizes[k] for k in stated if k in sizes})


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed, layer by layer."""
    import jax
    from deepspeed_tpu.models import falcon_h1
    return falcon_h1.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)[1]


def _forward_logits_padded(params, sizes, ids, rows=None, routing_gaps=None):
    """``references/falcon_h1.py:forward_logits`` of ``ids`` padded with token 0
    to ``reference_pad_to``: the same rows (both mixers are causal), and one
    compilation for the four prompts of a check."""
    import numpy as np
    ids = np.asarray(ids)
    padded = np.zeros(max(ids.size, int(sizes.get("reference_pad_to", 0))), ids.dtype)
    padded[:ids.size] = ids
    return plain_reference.forward_logits(params, sizes, padded,
                                          rows=np.arange(ids.size) if rows is None else rows,
                                          routing_gaps=routing_gaps)


reference = SimpleNamespace(forward_logits=_forward_logits_padded)  # named for the harness
