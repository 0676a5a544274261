"""Solar Open 2 family (``model_type="solar_open2"``: Solar-Open2-250B), served
as one chip's share of a layer that eight chips share: three layers in four
mix by a gated delta rule whose state is a SEQUENCE's (a 128 x 128 matrix a
head in a per-sequence state group beside the K/V array), the fourth by gated
softmax attention without position encoding; SwiGLU experts of which this
chip holds 40 of 320 beside a shared one, a slice of the vocabulary. From a
configuration file to the program's own objects.

The program's ``SolarOpen2Config`` is imported before anything else: a program
without it (no delta-rule mixer, no kernel that updates a matrix state in its
slot) cannot serve this family, and a run of its cell exits here, in seconds,
before any weight is made.

The configuration file states the experts HELD as ``n_routed_experts`` (a
reduced key) and the experts routed over under ``deployment_share``; the
program's config takes them the other way round. ``gqa_layers`` is kept whole
in the file; the program reads those below ``num_hidden_layers``.
``linear_attn_config`` is the row's nested group, copied whole; the program's
config names its keys ``linear_*`` / ``short_conv_kernel_size``.

The cold run's clock, as ``models/deepseek_v32.py``: weights made layer by
layer on the device; the reference on ids padded to ONE length
(``reference_pad_to``).
"""

from types import SimpleNamespace

try:
    from deepspeed_tpu.models.solar_open2 import SolarOpen2Config
except ImportError as e:
    raise SystemExit(
        f"benchmark: this program has no deepspeed_tpu.models.solar_open2 ({e}): it cannot "
        f"serve a model with gated delta-rule layers, whose state is a matrix a head a "
        f"sequence (a per-sequence state group beside the K/V array, a recurrence that "
        f"updates it in its slot and a chunked form that carries it from step to step). "
        f"Nothing was measured.")

from benchmark import interval_lookup
from benchmark.references import solar_open2 as plain_reference

# what the file states another way round, nested, or not at all
_OWN = {"n_routed_experts", "experts_held", "expert_rank", "dtype", "model_type",
        "linear_num_heads", "linear_head_dim", "linear_num_kv_heads", "short_conv_kernel_size",
        "kda_chunk"}

interval_lookup.install()


def program_config(sizes):
    import dataclasses

    import jax.numpy as jnp
    share, linear = sizes["deployment_share"], sizes["linear_attn_config"]
    # every key of the catalog row the program's config has a field for
    stated = {f.name for f in dataclasses.fields(SolarOpen2Config)} - _OWN
    return SolarOpen2Config(
        dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")),
        linear_num_heads=linear["num_heads"], linear_head_dim=linear["head_dim"],
        linear_num_kv_heads=linear["num_kv_heads"],
        short_conv_kernel_size=linear["short_conv_kernel_size"],
        n_routed_experts=share["routed_over"], experts_held=sizes["n_routed_experts"],
        expert_rank=share["expert_rank"],
        **{k: tuple(sizes[k]) if k == "gqa_layers" else sizes[k] for k in stated if k in sizes})


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed, layer by layer."""
    import jax
    from deepspeed_tpu.models import solar_open2
    return solar_open2.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)[1]


def _forward_logits_padded(params, sizes, ids, rows=None, routing_gaps=None, **variant):
    """``references/solar_open2.py:forward_logits`` of ``ids`` padded with
    token 0 to ``reference_pad_to``: the same rows (every layer is causal), and
    one compilation for the four prompts of a check."""
    import numpy as np
    ids = np.asarray(ids)
    padded = np.zeros(max(ids.size, int(sizes.get("reference_pad_to", 0))), ids.dtype)
    padded[:ids.size] = ids
    return plain_reference.forward_logits(params, sizes, padded,
                                          rows=np.arange(ids.size) if rows is None else rows,
                                          routing_gaps=routing_gaps, **variant)


reference = SimpleNamespace(forward_logits=_forward_logits_padded)  # named for the harness
