"""Mixtral family: from a configuration file to the program's own objects."""

from benchmark.references import mixtral as reference  # noqa: F401  (named for the harness)

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "num_local_experts",
              "num_experts_per_tok", "max_position_embeddings", "rms_norm_eps", "rope_theta")


def program_config(sizes):
    from deepspeed_tpu.models.mixtral import MixtralConfig
    if sizes.get("sliding_window"):
        raise ValueError("the program's Mixtral has no sliding window")
    import jax.numpy as jnp
    return MixtralConfig(dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")),
                         **{k: sizes[k] for k in MODEL_KEYS})


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed, in one jitted call."""
    import jax
    from deepspeed_tpu.models import mixtral
    return mixtral.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)[1]
