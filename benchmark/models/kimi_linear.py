"""Kimi Linear family (``model_type="kimi_linear"``: Kimi-Linear-48B-A3B),
served as one chip's share of a layer that four chips share: three layers in
four mix by a gated delta rule whose state is a SEQUENCE's (a 128 x 128 matrix
a head in a per-sequence state group), the fourth by latent attention without
positions over a paged pool of latent rows, both in ONE cache; a leading dense
layer, then SwiGLU experts of which this chip holds 64 of 256 beside a shared
one; a slice of the vocabulary. From a configuration file to the program's own
objects.

The program's ``KimiLinearConfig`` is imported before anything else: a program
without it (no cache that keeps latent rows and per-sequence slots side by
side) cannot serve this family, and a run of its cell exits here, in seconds,
before any weight is made.

The configuration file states the experts HELD as ``num_experts`` (a reduced
key) and the experts routed over under ``deployment_share``; the program's
config takes them the other way round, under this repository's names
(``models/kimi_linear.py``'s docstring pairs them with the published keys).
``linear_attn_config`` is the row's nested group, copied whole, both lists of
layers whole; the program reads those up to ``num_hidden_layers``.

The cold run's clock, as ``models/deepseek_v32.py``: weights made layer by
layer on the device; the reference on ids padded to ONE length
(``reference_pad_to``).
"""

from types import SimpleNamespace

try:
    from deepspeed_tpu.models.kimi_linear import KimiLinearConfig
except ImportError as e:
    raise SystemExit(
        f"benchmark: this program has no deepspeed_tpu.models.kimi_linear ({e}): it cannot "
        f"serve a model that mixes by latent attention in one layer of four and by a gated "
        f"delta rule in the other three (a pool of latent rows a token and a pool of slots a "
        f"sequence in one cache, under one block table and one admission). Nothing was "
        f"measured.")

from benchmark import interval_lookup
from benchmark.references import kimi_linear as plain_reference

# the program's field -> the published key, where they differ
PUBLISHED = {"n_routed_experts": "num_experts", "num_experts_per_tok": "num_experts_per_token",
             "n_shared_experts": "num_shared_experts", "norm_topk_prob": "moe_renormalize",
             "scoring_func": "moe_router_activation_func", "n_group": "num_expert_group",
             "max_position_embeddings": "model_max_length"}
# what the file states another way round, nested, or under ``assumed``
_OWN = {"n_routed_experts", "experts_held", "expert_rank", "dtype", "model_type", "kda_layers",
        "full_attn_layers", "linear_num_heads", "linear_head_dim", "short_conv_kernel_size",
        "kda_chunk"}

interval_lookup.install()


def program_config(sizes):
    import dataclasses

    import jax.numpy as jnp
    share, linear = sizes["deployment_share"], sizes["linear_attn_config"]
    # every key of the catalog row the program's config has a field for
    stated = {f.name for f in dataclasses.fields(KimiLinearConfig)} - _OWN
    given = {name: sizes[PUBLISHED.get(name, name)] for name in stated
             if PUBLISHED.get(name, name) in sizes}
    return KimiLinearConfig(
        dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")),
        kda_layers=tuple(linear["kda_layers"]), full_attn_layers=tuple(linear["full_attn_layers"]),
        linear_num_heads=linear["num_heads"], linear_head_dim=linear["head_dim"],
        short_conv_kernel_size=linear["short_conv_kernel_size"],
        n_routed_experts=share["routed_over"], experts_held=sizes["num_experts"],
        expert_rank=share["expert_rank"], **given)


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed, layer by layer."""
    import jax
    from deepspeed_tpu.models import kimi_linear
    return kimi_linear.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)[1]


def _forward_logits_padded(params, sizes, ids, rows=None, routing_gaps=None):
    """``references/kimi_linear.py:forward_logits`` of ``ids`` padded with
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
