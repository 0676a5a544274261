"""DeepSeek-V3.2 sparse causal LM (``model_type="deepseek_v32"``), SERVING
ONLY, and served as ONE CHIP'S SHARE of a deployment that shares each layer
over several chips.

Source: ``huggingface.co/deepseek-ai/DeepSeek-V3.2`` ``config.json``; what the
configuration has no key for (marked +) is the family's public reference code
(``inference/model.py`` of the model's repository). A pre-norm decoder whose
every layer is

- **latent attention** (MLA): queries through a ``q_lora_rank`` bottleneck
  with + an RMS norm, ``num_attention_heads`` heads of ``qk_nope_head_dim`` +
  ``qk_rope_head_dim``; keys and values through ONE ``kv_lora_rank`` latent a
  token (+ RMS-normed) beside one rotary key of ``qk_rope_head_dim`` shared by
  every head. The cache keeps the latent and the rotary key, not K and V.
  Rotary embedding is YaRN with unscaled cos / sin; + ``mscale`` squared goes
  into the softmax scale; + the rotary pairs are interleaved (2i, 2i + 1);
- a **learned index of keys**: ``index_n_heads`` heads of ``index_head_dim``
  from the query bottleneck score every earlier key's cached index key (+ a
  LayerNorm with bias on the key, + rotary on the first ``qk_rope_head_dim``
  dims of both in half-split pairs, + ReLU, + a per-head weight from the
  layer's input times ``index_n_heads^-1/2 index_head_dim^-1/2``), and
  attention runs over the ``index_topk`` keys of largest score;
- a feed-forward that is a dense SwiGLU (``intermediate_size``) in the first
  ``first_k_dense_replace`` layers and after them ``n_routed_experts`` routed
  SwiGLU experts (``moe_intermediate_size``) beside ``n_shared_experts``
  always-on ones: sigmoid scores in float32, a selection bias, + the group
  limit (``n_group`` groups scored by the sum of their top-2 of score + bias,
  the best ``topk_group`` kept), the ``num_experts_per_tok`` largest of score +
  bias among the kept groups, weights = the chosen SCORES renormalised
  (``norm_topk_prob``) times ``routed_scaling_factor``.

**The share.** ``experts_held`` < ``n_routed_experts`` says that this chip
holds experts ``expert_rank * experts_held ..`` of the ``n_routed_experts`` the
router scores: the banks are ``[experts_held, ...]``, the router keeps its
``n_routed_experts`` outputs and the renormalisation runs over all the chosen,
held here or not; the layer computes its own experts' part and the shared
expert. ``vocab_size`` may be a slice of the vocabulary: ids, logits and
sampling then run over the slice.

Left out, and why it changes no next-token logit: ``num_nextn_predict_layers``
(a draft module for a step that yields more than one token), the indexer's
Hadamard rotation of q and k (orthogonal: it exists for FP8 rounding),
``ep_size`` / ``moe_layer_freq`` (1). Refused rather than served wrong: another
``scoring_func`` / ``topk_method`` / ``hidden_act``, tied embeddings, an
attention bias, a ``rope_scaling`` that is not YaRN.

There is no training module. :func:`init_params` makes the tree the serving
model (``inference/v2/model_implementations/deepseek_v32_v2.py``) reads.
"""

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp


def _freeze(d):
    return None if d is None else tuple(sorted(d.items()))


@dataclass(frozen=True)
class DeepseekV32Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    num_key_value_heads: int = 128  # carried: every head reads the one latent
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    first_k_dense_replace: int = 3
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    rope_scaling: Optional[tuple] = None  # the dict's items, sorted (hashable)
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    # carried for the record; none changes a next-token logit
    num_nextn_predict_layers: int = 1
    ep_size: int = 1
    moe_layer_freq: int = 1
    # the share of the deployment this chip holds (None: every routed expert)
    experts_held: Optional[int] = None
    expert_rank: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    model_type: str = "deepseek_v32"

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling", _freeze(self.rope_scaling))
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        rope = dict(self.rope_scaling or ())
        if rope and rope.get("type", rope.get("rope_type")) != "yarn":
            raise NotImplementedError(f"rope_scaling {rope!r}: only yarn (or none)")
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise NotImplementedError(
                f"scoring_func {self.scoring_func!r} / topk_method {self.topk_method!r}: only "
                f"sigmoid scores with the bias-corrected group-limited top-k (noaux_tc)")
        if self.hidden_act != "silu":
            raise NotImplementedError(f"hidden_act {self.hidden_act!r}: only 'silu'")
        if self.tie_word_embeddings or self.attention_bias:
            raise NotImplementedError("tied embeddings / attention biases are not implemented")
        n = self.num_hidden_layers
        if not 0 <= self.first_k_dense_replace < n:
            raise ValueError(f"first_k_dense_replace {self.first_k_dense_replace} of {n} layers: "
                             f"the dense layers lead and an expert layer follows them")
        E, G = self.n_routed_experts, self.n_group
        if E % G or not 1 <= self.topk_group <= G:
            raise ValueError(f"{E} experts in {G} groups, {self.topk_group} kept")
        if self.num_experts_per_tok > self.topk_group * (E // G):
            raise ValueError("num_experts_per_tok exceeds the experts of the kept groups")
        if E % self.experts_held or not 0 <= self.expert_rank < E // self.experts_held:
            raise ValueError(f"a share of {self.experts_held} experts, rank {self.expert_rank}, "
                             f"does not divide {E} routed experts")

    # ---------------------------------------------------------------- shape --
    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """The cached latent row: ``kv_lora_rank`` + the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_dense(self, li: int) -> bool:
        return li < self.first_k_dense_replace

    @property
    def first_expert_held(self) -> int:
        return self.expert_rank * self.experts_held

    # --------------------------------------------------------------- rotary --
    def rope(self) -> dict:
        """The rotary parameters as ``models/mellum.py:rotary_cos_sin`` takes
        them: cos / sin are NOT scaled (``attention_factor`` 1); the YaRN
        ``mscale`` goes into :attr:`softmax_scale`."""
        rope = dict(self.rope_scaling or ())
        if not rope:
            return {"rope_type": "default", "rope_theta": self.rope_theta}
        return {"rope_type": "yarn", "rope_theta": self.rope_theta, "factor": rope["factor"],
                "original_max_position_embeddings": rope["original_max_position_embeddings"],
                "beta_fast": rope.get("beta_fast", 32), "beta_slow": rope.get("beta_slow", 1),
                "attention_factor": 1.0}

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim^-1/2`` times ``mscale``^2, ``mscale`` = 0.1 x
        ``mscale_all_dim`` x ln(factor) + 1 where the context is extended."""
        scale = self.qk_head_dim**-0.5
        rope = dict(self.rope_scaling or ())
        if rope and self.max_position_embeddings > rope["original_max_position_embeddings"]:
            m = 0.1 * float(rope.get("mscale_all_dim", 0) or 0) * math.log(rope["factor"]) + 1.0
            scale *= m * m
        return scale

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
                    num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, index_n_heads=8, index_head_dim=16,
                    index_topk=32, intermediate_size=96, moe_intermediate_size=32,
                    first_k_dense_replace=1, n_routed_experts=16, num_experts_per_tok=4,
                    n_group=4, topk_group=2, max_position_embeddings=512,
                    rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
                                  "mscale": 1, "mscale_all_dim": 1,
                                  "original_max_position_embeddings": 64})
        base.update(kw)
        return DeepseekV32Config(**base)


# --------------------------------------------------------------- parameters --
# The selection bias at init, in score units. At the top-8-of-256 cut a sigmoid
# score's slope is ~0.12, so a bias of 0.02 is 0.17 router-logit units and makes
# an expert 1.45 x as likely to be chosen: the share of the assignments that
# lands on the 16 experts ONE chip holds then swings +-12 % with the seed, and
# the chip's decode step with it (7.18-7.52 ms, read on the chip: PERF.md
# section 6, PR 40). A published model's bias is trained to BALANCE the load; a
# tenth of that spread still reorders near-ties (the bias picks, it does not
# weigh) and leaves every chip its sixteenth.
SELECT_BIAS_STD = 0.002


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def _swiglu(key, hidden, width, out_fan, dtype):
    k = jax.random.split(key, 3)
    return {"gate_proj": {"kernel": _normal(k[0], (hidden, width), hidden, dtype)},
            "up_proj": {"kernel": _normal(k[1], (hidden, width), hidden, dtype)},
            "down_proj": {"kernel": _normal(k[2], (width, hidden), out_fan, dtype)}}


def routed_out_scale(cfg: DeepseekV32Config) -> float:
    """The ROUTED experts' ``wo`` over the shared expert's ``down_proj``:
    1.5 / top-k, ``models/afmoe.py:routed_out_scale``'s argument (sigmoid
    scores renormalised over the chosen weigh them alike, so a k-th-against-
    (k+1)-th flip between a bf16 system and a float32 reference swaps a whole
    expert; the routed sum is as large as a comparison's loose tolerance lets a
    flip be, and no larger)."""
    return min(1.0, 1.5 / cfg.num_experts_per_tok)


def _layer(cfg: DeepseekV32Config, dense: bool, key, dtype):
    """Every kernel normal with variance 1 / fan_in (of ONE expert, for the
    banks); the projections that write into the residual stream (``wo``, the
    ``down_proj``s, the experts' ``wo``) times 1 / sqrt(2 x layers) as GPT-2
    and Megatron initialise them (the model is pre-norm: nothing re-norms a
    branch's output); the routed experts' ``wo`` also times
    :func:`routed_out_scale`; the selection bias normal x ``SELECT_BIAS_STD``; the norms'
    gains 1, the index key's LayerNorm bias 0."""
    M, H = cfg.hidden_size, cfg.num_attention_heads
    QL, KL = cfg.q_lora_rank, cfg.kv_lora_rank
    NH, DI = cfg.index_n_heads, cfg.index_head_dim
    k = jax.random.split(key, 16)
    ones = lambda n: jnp.ones((n, ), jnp.float32)  # noqa: E731
    into_stream = 2.0 * cfg.num_hidden_layers
    layer = {
        "input_layernorm": {"weight": ones(M)},
        "post_attention_layernorm": {"weight": ones(M)},
        "self_attn": {
            "wq_a": {"kernel": _normal(k[0], (M, QL), M, dtype)},
            "q_norm": {"weight": ones(QL)},
            "wq_b": {"kernel": _normal(k[1], (QL, H * cfg.qk_head_dim), QL, dtype)},
            "wkv_a": {"kernel": _normal(k[2], (M, cfg.latent_width), M, dtype)},
            "kv_norm": {"weight": ones(KL)},
            "wkv_b": {"kernel": _normal(k[3], (KL, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                                        KL, dtype)},
            "wo": {"kernel": _normal(k[4], (H * cfg.v_head_dim, M),
                                     H * cfg.v_head_dim * into_stream, dtype)},
            "indexer": {
                "wq_b": {"kernel": _normal(k[5], (QL, NH * DI), QL, dtype)},
                "wk": {"kernel": _normal(k[6], (M, DI), M, dtype)},
                "k_norm": {"weight": ones(DI), "bias": jnp.zeros((DI, ), jnp.float32)},
                "weights_proj": {"kernel": _normal(k[7], (M, NH), M, dtype)}}},
    }
    if dense:
        layer["mlp"] = _swiglu(k[8], M, cfg.intermediate_size,
                               cfg.intermediate_size * into_stream, dtype)
        return layer
    E, El, F = cfg.n_routed_experts, cfg.experts_held, cfg.moe_intermediate_size
    layer["mlp"] = {
        "gate": _normal(k[9], (M, E), M, jnp.float32),
        "e_score_correction_bias": SELECT_BIAS_STD * jax.random.normal(k[10], (E, ), jnp.float32),
        "experts": {"wi": _normal(k[11], (El, M, 2 * F), M, dtype),
                    "wo": _normal(k[12], (El, F, M), F * into_stream / routed_out_scale(cfg)**2,
                                  dtype)}}
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        layer["mlp"]["shared_experts"] = _swiglu(k[13], M, Fs, Fs * into_stream, dtype)
    return layer


def _ends(cfg: DeepseekV32Config, key, dtype):
    k = jax.random.split(key, 2)
    M, V = cfg.hidden_size, cfg.vocab_size
    return {"embed_tokens": {"embedding": _normal(k[0], (V, M), 1.0, dtype)},
            "norm": {"weight": jnp.ones((M, ), jnp.float32)},
            "lm_head": {"kernel": _normal(k[1], (M, V), M, dtype)}}


def init_params(cfg: DeepseekV32Config, rng=None, param_dtype=None):
    """Random parameters, made on the device as ``models/afmoe.py`` makes
    them: the ends by one jitted program, the layers by one program a KIND of
    layer run once a layer with the key folded with the layer's index, the
    bits from the device's own generator. The expert banks hold
    ``experts_held`` experts: a share is initialised as a share. Returns
    ``(None, params)``."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seed_words = jnp.ravel(jax.random.key_data(rng)).astype(jnp.uint32)
    rng = jax.random.wrap_key_data(jnp.resize(seed_words, (4, )), impl="rbg")
    dtype = param_dtype or jnp.float32
    params = jax.jit(_ends, static_argnums=(0, 2))(cfg, jax.random.fold_in(rng, 2**31 - 1), dtype)
    layer = jax.jit(_layer, static_argnums=(0, 1, 3))
    for i in range(cfg.num_hidden_layers):
        params[f"layers_{i}"] = layer(cfg, cfg.is_dense(i), jax.random.fold_in(rng, i), dtype)
    return None, params
