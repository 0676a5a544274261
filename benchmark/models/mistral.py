"""Mistral family (Llama code path, ``model_type="mistral"``): from a
configuration file to the program's own objects."""

from benchmark.references import mistral as reference  # noqa: F401  (named for the harness)

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
              "rms_norm_eps", "rope_theta", "tie_word_embeddings")


def program_config(sizes, **overrides):
    """``overrides`` come from the configuration file's ``program`` group: what
    the program needs beyond the published keys (flash attention, remat), and
    the one published key it cannot take as declared on the training path
    (``sliding_window``; the configuration file says why)."""
    from deepspeed_tpu.models.llama import LlamaConfig
    kw = {k: sizes[k] for k in MODEL_KEYS}
    import jax.numpy as jnp
    kw.update(model_type="mistral", sliding_window=int(sizes.get("sliding_window") or 0),
              dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")))
    kw.update(overrides)
    return LlamaConfig(**kw)


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed, in one jitted call."""
    import jax
    from deepspeed_tpu.models import llama
    return llama.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)[1]


def training_module(cfg):
    from deepspeed_tpu.models import llama
    return llama.LlamaForCausalLM(cfg)
