"""SDAR sparse block-diffusion LM (``model_type="sdar_moe"``: SDAR-30B-A3B-Chat),
SERVING ONLY.

Source: ``huggingface.co/JetLM/SDAR-30B-A3B-Chat`` ``config.json``; what the
configuration has no key for (marked +) is the family's public modelling and
generation code, built on Qwen3-MoE's. A pre-norm decoder whose every layer is

- grouped-query attention (``head_dim`` its own key: 32 heads of 128 over a
  2048-wide stream) with + an RMS norm over each head of q and of k, rotary
  embedding over the whole head (``rope_theta``, no scaling), and + the BLOCK
  mask: key j is visible to query i iff ``j // B <= i // B`` — inside a block of
  ``block_length`` = B positions every position sees every other, across
  blocks attention is causal, and blocks are counted from position 0 of the
  sequence, prompt included;
- ``num_experts`` routed SwiGLU experts (``moe_intermediate_size``), softmax
  scores in float32, the ``num_experts_per_tok`` largest renormalised over the
  chosen (``norm_topk_prob``); every layer is sparse (``decoder_sparse_step``
  1, ``mlp_only_layers`` empty), there is no shared expert, and
  ``intermediate_size`` is used by no layer.

Row i of the logits scores the token AT position i (no shift). + Generation
is by diffusion over blocks (``inference/v2/model_implementations/
transformer_base.py``, "block steps"): a block's rows start as the mask token,
``denoising_steps`` forwards rewrite the block under the block mask, after each
the ``block_length / denoising_steps`` most confident masked rows take their
greedy token (``remasking_strategy`` ``low_confidence_static``), and one more
forward commits the finished block's K/V. ``block_length``,
``denoising_steps``, ``remasking_strategy`` and ``mask_token_id`` are the
generation script's arguments, not ``config.json``'s.

There is no training module. :func:`init_params` makes the parameter tree the
serving model (``sdar_moe_v2.py``) reads, named as the Mixtral tree is.
Refused rather than served wrong: a ``sliding_window``, a ``rope_scaling``,
tied embeddings, attention biases, dense layers among the sparse ones, another
strategy, a block that the paged kernel's tile cannot hold whole.
"""

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_STRATEGIES = ("low_confidence_static", )


@dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    head_dim: int = 128
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    attention_bias: bool = False
    hidden_act: str = "silu"
    max_position_embeddings: int = 32768
    max_window_layers: int = 48
    rms_norm_eps: float = 1e-6
    rope_scaling: Optional[dict] = None
    rope_theta: float = 1000000.0
    sliding_window: Optional[int] = None
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False
    # generation (the published scripts' arguments)
    block_length: int = 4
    denoising_steps: int = 4
    remasking_strategy: str = "low_confidence_static"
    mask_token_id: int = 151669
    dtype: jnp.dtype = jnp.bfloat16
    model_type: str = "sdar_moe"

    def __post_init__(self):
        from deepspeed_tpu.ops.pallas.paged_attention import TQ
        object.__setattr__(self, "mlp_only_layers", tuple(self.mlp_only_layers or ()))
        # one layer type, for the glue that reads a config layer by layer
        object.__setattr__(self, "layer_types", ("full_attention", ) * self.num_hidden_layers)
        # refuse what is not implemented rather than serve wrong logits
        if self.sliding_window or self.use_sliding_window:
            raise NotImplementedError(
                f"sliding_window {self.sliding_window!r} (use_sliding_window "
                f"{self.use_sliding_window}): a window beside the block mask is not implemented")
        if self.rope_scaling:
            raise NotImplementedError(f"rope_scaling {self.rope_scaling!r} is not implemented")
        if self.tie_word_embeddings:
            raise NotImplementedError("tied embeddings are not implemented")
        if self.attention_bias:
            raise NotImplementedError("attention biases are not implemented")
        if self.mlp_only_layers or self.decoder_sparse_step != 1:
            raise NotImplementedError(
                f"mlp_only_layers {list(self.mlp_only_layers)} / decoder_sparse_step "
                f"{self.decoder_sparse_step}: every layer of this tree is sparse (a stack with "
                f"dense layers among its expert layers is models/afmoe.py's)")
        if self.hidden_act != "silu":
            raise NotImplementedError(f"hidden_act {self.hidden_act!r}: only 'silu'")
        if self.remasking_strategy not in _STRATEGIES:
            raise NotImplementedError(
                f"remasking_strategy {self.remasking_strategy!r}: only {_STRATEGIES} (the "
                f"dynamic threshold makes a block's forwards depend on its logits)")
        B = self.block_length
        if B < 1 or B & (B - 1) or TQ % B:
            raise ValueError(f"block_length {B}: a power of two that divides the paged kernel's "
                             f"tile of {TQ} rows (paged_attention.TQ), so that no block "
                             f"straddles two tiles")
        if self.denoising_steps < 1 or B % self.denoising_steps:
            raise ValueError(f"denoising_steps {self.denoising_steps} does not divide "
                             f"block_length {B}: a step unmasks block_length / denoising_steps "
                             f"rows")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} outside the vocabulary of "
                             f"{self.vocab_size}")
        if not 0 < self.num_experts_per_tok <= self.num_experts:
            raise ValueError(f"num_experts_per_tok {self.num_experts_per_tok} of "
                             f"{self.num_experts} experts")

    def window_of(self, li: int) -> int:
        """No layer has a sliding window (one is refused)."""
        return 0

    def rope_of(self, layer_type: str) -> dict:
        return {"rope_type": "default", "rope_theta": float(self.rope_theta)}

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=48, head_dim=16, num_hidden_layers=3,
                    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
                    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
                    max_position_embeddings=512, mask_token_id=255)
        base.update(kw)
        return SdarMoeConfig(**base)


# --------------------------------------------------------------- parameters --
def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def _layer(cfg: SdarMoeConfig, key, dtype, attention_gain, expert_gain):
    """As ``models/mellum.py``'s layer (every kernel normal with variance 1 /
    fan_in, the two projections that write into the residual stream scaled by
    1 / sqrt(2 x layers): without it a top-8 model with random weights is
    chaotic in its routing, PERF.md section 6, PR 30) plus the q/k norms'
    gains, 1; ``o_proj`` times ``attention_gain`` and the experts' ``wo``
    times ``expert_gain`` (:func:`init_params`)."""
    M, D, F, E = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size, cfg.num_experts
    H, KVH = cfg.num_attention_heads, cfg.num_key_value_heads
    k = jax.random.split(key, 7)
    ones = jnp.ones((M, ), jnp.float32)
    into_stream = 2.0 * cfg.num_hidden_layers
    return {
        "input_layernorm": {"weight": ones},
        "self_attn": {"q_proj": {"kernel": _normal(k[0], (M, H * D), M, dtype)},
                      "k_proj": {"kernel": _normal(k[1], (M, KVH * D), M, dtype)},
                      "v_proj": {"kernel": _normal(k[2], (M, KVH * D), M, dtype)},
                      "o_proj": {"kernel": _normal(k[3], (H * D, M),
                                                   H * D * into_stream / attention_gain**2, dtype)},
                      "q_norm": {"weight": jnp.ones((D, ), jnp.float32)},
                      "k_norm": {"weight": jnp.ones((D, ), jnp.float32)}},
        "post_attention_layernorm": {"weight": ones},
        "block_sparse_moe": {
            "gate": _normal(k[4], (M, E), M, jnp.float32),
            "ExpertFFN_0": {"wi": _normal(k[5], (E, M, 2 * F), M, dtype),
                            "wo": _normal(k[6], (E, F, M), F * into_stream / expert_gain**2,
                                          dtype)}},
    }


def _ends(cfg: SdarMoeConfig, key, dtype):
    k = jax.random.split(key, 2)
    M, V = cfg.hidden_size, cfg.vocab_size
    return {"embed_tokens": {"embedding": _normal(k[0], (V, M), 1.0, dtype)},
            "norm": {"weight": jnp.ones((M, ), jnp.float32)},
            "lm_head": {"kernel": _normal(k[1], (M, V), M, dtype)}}


def init_params(cfg: SdarMoeConfig, rng=None, param_dtype=None, attention_gain=1.0,
                expert_gain=1.0):
    """Random parameters, made on the device as ``models/mellum.py`` makes
    them: the two ends by one jitted program, the layers by ONE one-layer
    program run once a layer with the key folded with the layer's index, the
    bits from the device's own generator (``rbg``). Returns ``(None,
    params)``.

    ``attention_gain`` / ``expert_gain`` multiply the two projections that
    write into the residual stream (``o_proj``, the experts' ``wo``). At 1 a
    seeded model's branches are small beside the embedding it started from: its
    attention is FLAT (q . k of random rows), so its output is the mean of the
    n visible value rows, 1 / sqrt(n) of one. Rows that are all fed the SAME
    token — a block step's masked rows — then stay alike through every layer
    and route alike; a caller who wants them told apart by their contexts, as
    a trained model's are, asks for a larger attention branch."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seed_words = jnp.ravel(jax.random.key_data(rng)).astype(jnp.uint32)
    rng = jax.random.wrap_key_data(jnp.resize(seed_words, (4, )), impl="rbg")
    dtype = param_dtype or jnp.float32
    params = jax.jit(_ends, static_argnums=(0, 2))(cfg, jax.random.fold_in(rng, 2**31 - 1), dtype)
    layer = jax.jit(_layer, static_argnums=(0, 2, 3, 4))
    for i in range(cfg.num_hidden_layers):
        params[f"layers_{i}"] = layer(cfg, jax.random.fold_in(rng, i), dtype,
                                      float(attention_gain), float(expert_gain))
    return None, params
