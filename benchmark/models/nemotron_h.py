"""Nemotron-H family (``model_type="nemotron_h"``: Nemotron-3-Nano-30B-A3B),
served as one chip's share of a layer that two chips share: Mamba-2 blocks
whose state is a SEQUENCE's (a per-sequence state group beside the K/V array),
relu-squared experts of which this chip holds 64 of 128, attention blocks
without position encoding, a slice of the vocabulary. From a configuration
file to the program's own objects.

The program's ``NemotronHConfig`` is imported before anything else: a program
without it (no per-sequence state group in its cache manager, no ragged scan
that carries a state from one step to the next) cannot serve this family, and a
run of its cell exits here, in seconds, before any weight is made.

The configuration file states the experts HELD as ``n_routed_experts`` (a
reduced key) and the experts routed over under ``deployment_share``; the
program's config takes them the other way round. ``hybrid_override_pattern``
is kept whole in the file; the program is given its first
``num_hidden_layers`` characters.

The cold run's clock, as ``models/deepseek_v32.py``: weights made block by
block on the device; the reference on ids padded to ONE length
(``reference_pad_to``).
"""

from types import SimpleNamespace

try:
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig
except ImportError as e:
    raise SystemExit(
        f"benchmark: this program has no deepspeed_tpu.models.nemotron_h ({e}): it cannot "
        f"serve a model with state-space blocks, whose state is a sequence's and not a row a "
        f"token (a per-sequence state group beside the K/V array, a ragged scan that carries "
        f"its state from step to step). Nothing was measured.")

from benchmark import interval_lookup
from benchmark.references import nemotron_h as plain_reference

# what the file states another way round, or not at all
_OWN = {"hybrid_override_pattern", "n_routed_experts", "experts_held", "expert_rank", "dtype",
        "model_type"}

interval_lookup.install()


def program_config(sizes):
    import dataclasses

    import jax.numpy as jnp
    share = sizes["deployment_share"]
    # every key of the catalog row the program's config has a field for
    stated = {f.name for f in dataclasses.fields(NemotronHConfig)} - _OWN
    return NemotronHConfig(
        dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")),
        hybrid_override_pattern=sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]],
        n_routed_experts=share["routed_over"], experts_held=sizes["n_routed_experts"],
        expert_rank=share["expert_rank"], **{k: sizes[k] for k in stated if k in sizes})


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed, block by block."""
    import jax
    from deepspeed_tpu.models import nemotron_h
    return nemotron_h.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)[1]


def _forward_logits_padded(params, sizes, ids, rows=None, routing_gaps=None):
    """``references/nemotron_h.py:forward_logits`` of ``ids`` padded with token
    0 to ``reference_pad_to``: the same rows (every block is causal), and one
    compilation for the four prompts of a check."""
    import numpy as np
    ids = np.asarray(ids)
    padded = np.zeros(max(ids.size, int(sizes.get("reference_pad_to", 0))), ids.dtype)
    padded[:ids.size] = ids
    return plain_reference.forward_logits(params, sizes, padded,
                                          rows=np.arange(ids.size) if rows is None else rows,
                                          routing_gaps=routing_gaps)


reference = SimpleNamespace(forward_logits=_forward_logits_padded)  # named for the harness
