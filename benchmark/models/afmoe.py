"""Afmoe family (``model_type="afmoe"``: Trinity-Mini): sigmoid-scored top-8 of
128 experts beside a shared expert, a leading dense layer, gated attention with
q/k norm, rotary window layers and position-free full layers under four norms a
layer. From a configuration file to the program's own objects.

The program's ``AfmoeConfig`` is imported before anything else: a program
without it (no ``deepspeed_tpu.models.afmoe``: no shared expert, no score
function but softmax, no dense layer in a stack of expert layers) cannot serve
this family, and a run of its cell exits here, in seconds, before any weight is
made.

How the family keeps a cold run inside the driver's 360 s (PERF.md section 6,
PR 25 / PR 26 / PR 30): the weights are made layer by layer on the device (the
program's initialiser compiles one layer once a kind of layer, and draws from
the device's own generator); the reference runs on token ids padded to ONE
length, the engine's ``max_context`` (each of its jitted parts is compiled once
a layer type, not once a prompt length; the model is causal, so the rows asked
for do not see the padding); and the traced slice's idle gaps are labelled by
bisection, ``interval_lookup.install()``: this family's steps take under ~12 ms
and a slice holds hundreds of them.
"""

from types import SimpleNamespace

try:
    from deepspeed_tpu.models.afmoe import AfmoeConfig
except ImportError as e:
    raise SystemExit(
        f"benchmark: this program has no deepspeed_tpu.models.afmoe ({e}): it cannot serve a "
        f"model with a shared expert beside sigmoid-scored routed ones, a dense layer in front "
        f"of the expert layers, gated attention with q/k norm or position-free full layers. "
        f"Nothing was measured.")

from benchmark import interval_lookup
from benchmark.references import afmoe as plain_reference

MODEL_KEYS = ("vocab_size", "hidden_size", "head_dim", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "intermediate_size", "moe_intermediate_size",
              "num_dense_layers", "num_experts", "num_experts_per_tok", "num_shared_experts",
              "score_func", "route_norm", "route_scale", "n_group", "topk_group",
              "num_expert_groups", "num_limited_groups", "sliding_window",
              "global_attn_every_n_layers", "rope_theta", "rope_scaling",
              "max_position_embeddings", "rms_norm_eps", "hidden_act", "mup_enabled",
              "tie_word_embeddings", "load_balance_coeff", "use_grouped_mm")

interval_lookup.install()


def program_config(sizes):
    """The first ``num_hidden_layers`` entries of the published ``layer_types``:
    the configuration keeps the list whole."""
    import jax.numpy as jnp
    return AfmoeConfig(dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")),
                       layer_types=tuple(sizes["layer_types"][:sizes["num_hidden_layers"]]),
                       **{k: sizes[k] for k in MODEL_KEYS})


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed, layer by layer."""
    import jax
    from deepspeed_tpu.models import afmoe
    return afmoe.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)[1]


def _forward_logits_padded(params, sizes, ids, rows=None, routing_gaps=None):
    """``references/afmoe.py:forward_logits`` of ``ids`` padded with token 0 to
    the configuration's ``max_context``: the same rows, and one compilation for
    the four prompts of a check."""
    import numpy as np
    ids = np.asarray(ids)
    padded = np.zeros(max(ids.size, sizes["engine"]["state_manager"]["max_context"]), ids.dtype)
    padded[:ids.size] = ids
    return plain_reference.forward_logits(params, sizes, padded,
                                          rows=np.arange(ids.size) if rows is None else rows,
                                          routing_gaps=routing_gaps)


reference = SimpleNamespace(forward_logits=_forward_logits_padded)  # named for the harness
