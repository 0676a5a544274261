"""Granite 4.0-H family (``model_type="granitemoehybrid"``: Granite-4.0-H-Small),
served as one chip's share of a layer that two chips share: EVERY layer a mixer
(Mamba-2, whose state is a SEQUENCE's, in nine layers of ten; softmax attention
without position encoding in the tenth) and then top-10 of 72 routed experts
(this chip holds 36) beside a shared one, four scalar multipliers, a tied head
over a slice of the vocabulary. From a configuration file to the program's own
objects.

The program's ``GraniteMoeHybridConfig`` is imported before anything else: a
program without it (no layer that runs a state-space or softmax mixer AND
routed experts, no softmax scale of the model's own, no tied head) cannot serve
this family, and a run of its cell exits here, in seconds, before any weight is
made.

The configuration file states the experts HELD as ``num_local_experts`` (a
reduced key) and the experts routed over under ``deployment_share``; the
program's config takes them the other way round. ``layer_types`` is kept whole
in the file; the program is given its first ``num_hidden_layers`` entries. The
init's constants that are the benchmark's own guesses are the program's
(``models/granitemoehybrid.py``'s three module constants); the file repeats
them under ``assumed.init_gains`` with the readings taken at other values.

The cold run's clock, as ``models/nemotron_h.py``: weights made layer by layer
on the device; the reference on ids padded to ONE length (``reference_pad_to``).
"""

from types import SimpleNamespace

try:
    from deepspeed_tpu.models.granitemoehybrid import GraniteMoeHybridConfig
except ImportError as e:
    raise SystemExit(
        f"benchmark: this program has no deepspeed_tpu.models.granitemoehybrid ({e}): it cannot "
        f"serve a model whose every layer runs a Mamba-2 or a position-free softmax mixer AND "
        f"routed experts beside a shared one, under a softmax scale, two residual multipliers "
        f"and a tied head of the model's own. Nothing was measured.")

from benchmark import interval_lookup
from benchmark.references import granitemoehybrid as plain_reference

# what the file states another way round, or not at all
_OWN = {"layer_types", "num_local_experts", "experts_held", "expert_rank", "dtype", "model_type"}

interval_lookup.install()


def program_config(sizes):
    import dataclasses

    import jax.numpy as jnp
    share = sizes["deployment_share"]
    # every key of the catalog row the program's config has a field for
    stated = {f.name for f in dataclasses.fields(GraniteMoeHybridConfig)} - _OWN
    return GraniteMoeHybridConfig(
        dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")),
        layer_types=tuple(sizes["layer_types"][:sizes["num_hidden_layers"]]),
        num_local_experts=share["routed_over"], experts_held=sizes["num_local_experts"],
        expert_rank=share["expert_rank"], **{k: sizes[k] for k in stated if k in sizes})


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed, layer by layer."""
    import jax
    from deepspeed_tpu.models import granitemoehybrid
    return granitemoehybrid.init_params(cfg, rng=jax.random.PRNGKey(seed),
                                        param_dtype=cfg.dtype)[1]


def _forward_logits_padded(params, sizes, ids, rows=None, routing_gaps=None):
    """``references/granitemoehybrid.py:forward_logits`` of ``ids`` padded with
    token 0 to ``reference_pad_to``: the same rows (every mixer is causal), and
    one compilation for the four prompts of a check."""
    import numpy as np
    ids = np.asarray(ids)
    padded = np.zeros(max(ids.size, int(sizes.get("reference_pad_to", 0))), ids.dtype)
    padded[:ids.size] = ids
    return plain_reference.forward_logits(params, sizes, padded,
                                          rows=np.arange(ids.size) if rows is None else rows,
                                          routing_gaps=routing_gaps)


reference = SimpleNamespace(forward_logits=_forward_logits_padded)  # named for the harness
