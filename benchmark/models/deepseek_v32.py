"""DeepSeek-V3.2 family (``model_type="deepseek_v32"``), served as one chip's
share of a layer that sixteen chips share: a latent cache with absorbed decode,
a learned index of keys that selects what attention reads, group-limited
sigmoid routing over 256 experts of which this chip holds 16, a slice of the
vocabulary. From a configuration file to the program's own objects.

The program's ``DeepseekV32Config`` is imported before anything else: a program
without it (no latent KV group, no selection inside paged attention, no group
limit, no layer that holds a share of its experts) cannot serve this family,
and a run of its cell exits here, in seconds, before any weight is made.

The configuration file states the experts HELD as ``n_routed_experts`` (a
reduced key) and the experts routed over under ``deployment_share``; the
program's config takes them the other way round (``n_routed_experts`` the
router's outputs, ``experts_held`` the banks').

The cold run's clock, as ``models/afmoe.py``: weights made layer by layer on
the device; the reference on ids padded to ONE length (``reference_pad_to``:
the traffic's longest prompt + the fed tokens, not ``max_context``: the
reference's attention is quadratic); the slice's idle gaps labelled by
bisection.
"""

from types import SimpleNamespace

try:
    from deepspeed_tpu.models.deepseek_v32 import DeepseekV32Config
except ImportError as e:
    raise SystemExit(
        f"benchmark: this program has no deepspeed_tpu.models.deepseek_v32 ({e}): it cannot "
        f"serve a model with a latent KV cache, a learned selection of keys inside paged "
        f"attention, a group limit on its routing, or a layer that holds a share of its "
        f"experts. Nothing was measured.")

from benchmark import interval_lookup
from benchmark.references import deepseek_v32 as plain_reference

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
              "intermediate_size", "moe_intermediate_size", "first_k_dense_replace",
              "num_experts_per_tok", "n_group", "topk_group", "n_shared_experts",
              "routed_scaling_factor", "scoring_func", "topk_method", "norm_topk_prob",
              "rope_theta", "rope_scaling", "max_position_embeddings", "rms_norm_eps",
              "hidden_act", "tie_word_embeddings", "attention_bias", "num_nextn_predict_layers",
              "ep_size", "moe_layer_freq")

interval_lookup.install()


def program_config(sizes):
    import jax.numpy as jnp
    share = sizes["deployment_share"]
    return DeepseekV32Config(dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")),
                             n_routed_experts=share["routed_over"],
                             experts_held=sizes["n_routed_experts"],
                             expert_rank=share["expert_rank"],
                             **{k: sizes[k] for k in MODEL_KEYS if k in sizes})


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed, layer by layer."""
    import jax
    from deepspeed_tpu.models import deepseek_v32
    return deepseek_v32.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)[1]


def _forward_logits_padded(params, sizes, ids, rows=None, routing_gaps=None):
    """``references/deepseek_v32.py:forward_logits`` of ``ids`` padded with
    token 0 to ``reference_pad_to``: the same rows (the model is causal), and
    one compilation for the four prompts of a check."""
    import numpy as np
    ids = np.asarray(ids)
    padded = np.zeros(max(ids.size, int(sizes.get("reference_pad_to", 0))), ids.dtype)
    padded[:ids.size] = ids
    return plain_reference.forward_logits(params, sizes, padded,
                                          rows=np.arange(ids.size) if rows is None else rows,
                                          routing_gaps=routing_gaps)


reference = SimpleNamespace(forward_logits=_forward_logits_padded)  # named for the harness
