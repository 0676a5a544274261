"""Moonshot Kimi Linear causal LM (``model_type="kimi_linear"``:
Kimi-Linear-48B-A3B), SERVING ONLY, and served as ONE CHIP'S SHARE of a
deployment that shares each layer over several chips.

Source: ``huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct``
``config.json``; what the configuration has no key for (marked +) is the Kimi
Linear report (arXiv:2510.26692) and its public ``fla`` layer, as remembered.
Every layer is pre-norm, ``x <- x + mixer(RMSNorm(x))``, ``x <- x +
ffn(RMSNorm(x))``; there is NO position encoding anywhere (``mla_use_nope``).
The mixer is set by the layer's PUBLISHED (1-based) index:

- a layer in ``kda_layers``, **gated delta-rule linear attention** ("KDA"):
  ``models/solar_open2.py``'s linear mixer word for word (the same report's
  layer) at ``linear_num_heads`` heads of ``linear_head_dim``, with **beta =
  sigmoid(w_b x)**: no factor 2 (the configuration has no
  ``kda_allow_neg_eigval``);
- a layer in ``full_attn_layers``, **latent attention (MLA) without
  positions**: a FULL-RANK query (``q_lora_rank`` null) of
  ``num_attention_heads`` heads of ``qk_nope_head_dim`` + ``qk_rope_head_dim``;
  keys and values through ONE ``kv_lora_rank`` latent a token (+ RMS-normed)
  beside one key of ``qk_rope_head_dim`` shared by every head, carried
  UN-ROTATED in query and key; logits times ``(qk_nope_head_dim +
  qk_rope_head_dim)^-1/2``. The cache keeps the latent and the shared key, not
  K and V (``models/deepseek_v32.py``'s latent row, the rotation left out);
- the feed-forward: a dense SwiGLU (``intermediate_size``) in the first
  ``first_k_dense_replace`` layers, after them ``n_routed_experts`` SwiGLU
  experts of ``moe_intermediate_size`` beside ``n_shared_experts`` shared
  ones; sigmoid scores in float32, the ``num_experts_per_tok`` largest of score
  + ``e_score_correction_bias`` (one expert group: no group limit), weights the
  chosen SCORES renormalised (``norm_topk_prob``) times
  ``routed_scaling_factor``.

A final RMSNorm and an untied head. The fields carry this repository's names;
the published keys are ``num_experts`` (``n_routed_experts``),
``num_experts_per_token``, ``num_shared_experts``, ``moe_renormalize``
(``norm_topk_prob``), ``moe_router_activation_func`` (``scoring_func``),
``num_expert_group`` (``n_group``), ``model_max_length``
(``max_position_embeddings``), and ``linear_attn_config``'s four.

**The share.** ``experts_held`` < ``n_routed_experts``: this chip holds experts
``expert_rank * experts_held ..`` of those the router scores, as
``models/deepseek_v32.py`` says it. ``vocab_size`` may be a slice.

Refused rather than served wrong: rotary latent attention (``mla_use_nope``
false, a ``rope_scaling``), a query bottleneck (``q_lora_rank``), another
router activation, a group limit (``num_expert_group`` > 1), tied embeddings,
a layer that is in neither list or in both.

There is no training module. :func:`init_params` makes the tree the serving
model (``inference/v2/model_implementations/kimi_linear_v2.py``) reads.
"""

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# the delta-rule mixer, the experts and the ends are initialised as Solar Open 2's
# (the same report's layer; ``models/deepseek_v32.py``'s arguments for the experts)
from deepspeed_tpu.models.solar_open2 import _ends, _experts, _kda, _normal, _swiglu


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    # the mixer of each layer, by its PUBLISHED index (the first layer is 1)
    kda_layers: Tuple[int, ...] = tuple(i for i in range(1, 27) if i % 4)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    # latent attention (the layers in full_attn_layers)
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    rope_scaling: Optional[dict] = None
    # gated delta-rule linear attention (the layers in kda_layers): linear_attn_config's keys
    linear_num_heads: int = 32
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    # feed-forward
    intermediate_size: int = 9216
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.446
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 1048576
    # carried for the record; none changes a next-token logit
    num_key_value_heads: int = 32
    head_dim: int = 72
    rope_theta: float = 10000.0
    moe_layer_freq: int = 1
    use_grouped_topk: bool = True
    num_nextn_predict_layers: int = 0
    # + the served chunked form's chunk (rows a visit): the program's, not the model's
    kda_chunk: int = 64
    # the share of the deployment this chip holds (None: every routed expert)
    experts_held: Optional[int] = None
    expert_rank: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    model_type: str = "kimi_linear"

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        for name in ("kda_layers", "full_attn_layers"):
            object.__setattr__(self, name, tuple(int(i) for i in getattr(self, name)))
        # refuse what is not implemented rather than serve wrong logits
        if not self.mla_use_nope or self.rope_scaling:
            raise NotImplementedError(
                f"mla_use_nope {self.mla_use_nope} / rope_scaling {self.rope_scaling!r}: the "
                f"published model rotates nothing, and no rotary latent layer is implemented here")
        if self.q_lora_rank is not None:
            raise NotImplementedError(f"q_lora_rank {self.q_lora_rank}: only the full-rank query")
        if self.scoring_func != "sigmoid":
            raise NotImplementedError(f"moe_router_activation_func {self.scoring_func!r}: only "
                                      f"'sigmoid'")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(f"num_expert_group {self.n_group} / topk_group "
                                      f"{self.topk_group}: only one expert group (no group limit)")
        if self.hidden_act != "silu" or self.moe_layer_freq != 1:
            raise NotImplementedError(f"hidden_act {self.hidden_act!r} / moe_layer_freq "
                                      f"{self.moe_layer_freq}: only 'silu' and experts in every "
                                      f"layer past the dense ones")
        if self.tie_word_embeddings:
            raise NotImplementedError("tied embeddings are not implemented")
        n = self.num_hidden_layers
        for i in range(1, n + 1):
            if (i in self.kda_layers) == (i in self.full_attn_layers):
                raise NotImplementedError(
                    f"layer {i} of {n} is in {'both' if i in self.kda_layers else 'neither'} of "
                    f"kda_layers {self.kda_layers} and full_attn_layers {self.full_attn_layers}")
        if not 0 <= self.first_k_dense_replace < n:
            raise ValueError(f"first_k_dense_replace {self.first_k_dense_replace} of {n} layers: "
                             f"the dense layers lead and an expert layer follows them")
        E = self.n_routed_experts
        if not 0 < self.num_experts_per_tok <= E:
            raise ValueError(f"num_experts_per_token {self.num_experts_per_tok} of {E} experts")
        if E % self.experts_held or not 0 <= self.expert_rank < E // self.experts_held:
            raise ValueError(f"a share of {self.experts_held} experts, rank {self.expert_rank}, "
                             f"does not divide {E} routed experts")

    # ---------------------------------------------------------------- shape --
    def is_kda(self, li: int) -> bool:
        """Layer ``li`` (counted from 0, as the program counts) mixes by the delta rule."""
        return li + 1 in self.kda_layers

    def is_dense(self, li: int) -> bool:
        return li < self.first_k_dense_replace

    @property
    def kda_here(self) -> Tuple[int, ...]:
        """The delta-rule layers among this model's ``num_hidden_layers`` (the
        lists are kept whole where the depth is cut), counted from 0: a layer's
        index in the state pools is its ordinal here."""
        return tuple(li for li in range(self.num_hidden_layers) if self.is_kda(li))

    @property
    def mla_here(self) -> Tuple[int, ...]:
        """The latent layers, counted from 0: a layer's index in the latent
        pool is its ordinal here."""
        return tuple(li for li in range(self.num_hidden_layers) if not self.is_kda(li))

    @property
    def kda_width(self) -> int:
        return self.linear_num_heads * self.linear_head_dim

    @property
    def beta_scale(self) -> float:
        """beta = sigmoid(w_b x): the configuration has no ``kda_allow_neg_eigval``."""
        return 1.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """The cached latent row: ``kv_lora_rank`` + the shared un-rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim**-0.5

    @property
    def first_expert_held(self) -> int:
        return self.expert_rank * self.experts_held

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4, kda_layers=(1, 2, 3, 5),
                    full_attn_layers=(4, 8), num_attention_heads=4, num_key_value_heads=4,
                    head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16, linear_num_heads=2, linear_head_dim=128, intermediate_size=96,
                    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4,
                    kda_chunk=16, max_position_embeddings=512)
        base.update(kw)
        return KimiLinearConfig(**base)


# --------------------------------------------------------------- parameters --
# the standard deviation of a latent layer's logits at initialisation (:func:`_mla`)
QUERY_INIT_GAIN = 2.5


def _mla(cfg: KimiLinearConfig, key, dtype, into_stream):
    """``models/deepseek_v32.py``'s latent layer without the query bottleneck
    and the indexer, under the published names. With every kernel at variance 1
    / fan_in a logit is ~N(0, 1) and the softmax over n keys averages n / e of
    them: ``q_proj`` times ``QUERY_INIT_GAIN`` g makes it N(0, g^2), n / e^(g^2)
    keys (16-32 of 8-16 k), as peaked as a trained layer's."""
    M, H, C = cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank
    k = jax.random.split(key, 4)
    return {"q_proj": {"kernel": _normal(k[0], (M, H * cfg.qk_head_dim),
                                         M / QUERY_INIT_GAIN**2, dtype)},
            "kv_a_proj_with_mqa": {"kernel": _normal(k[1], (M, cfg.latent_width), M, dtype)},
            "kv_a_layernorm": {"weight": jnp.ones((C, ), jnp.float32)},
            "kv_b_proj": {"kernel": _normal(k[2], (C, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                                            C, dtype)},
            "o_proj": {"kernel": _normal(k[3], (H * cfg.v_head_dim, M),
                                         H * cfg.v_head_dim * into_stream, dtype)}}


def _layer(cfg: KimiLinearConfig, kda: bool, dense: bool, key, dtype):
    """``models/solar_open2.py:_layer``'s rule: every kernel normal with
    variance 1 / fan_in; what writes into the residual stream (``o_proj``, the
    ``down_proj``s, the experts' ``wo``) times 1 / sqrt(2 x layers); the routed
    experts' ``wo`` also times 1.5 / top-k; the selection bias normal x 0.002;
    the delta rule's ``A_log`` / ``dt_bias`` as Mamba-2 publishes them; the
    norms' gains 1."""
    into_stream = 2.0 * cfg.num_hidden_layers
    k = jax.random.split(key, 2)
    ones = jnp.ones((cfg.hidden_size, ), jnp.float32)
    mixer = (_kda if kda else _mla)(cfg, k[0], dtype, into_stream)
    ffn = (_swiglu(k[1], cfg.hidden_size, cfg.intermediate_size,
                   cfg.intermediate_size * into_stream, dtype) if dense
           else _experts(cfg, k[1], dtype, into_stream))
    return {"input_layernorm": {"weight": ones}, "post_attention_layernorm": {"weight": ones},
            "linear_attn" if kda else "self_attn": mixer, "mlp": ffn}


def init_params(cfg: KimiLinearConfig, rng=None, param_dtype=None):
    """Random parameters, made on the device as ``models/afmoe.py`` makes
    them: the ends by one jitted program, the layers by one program a KIND of
    layer (mixer x feed-forward) run once a layer with the key folded with the
    layer's index. The expert banks hold ``experts_held`` experts: a share is
    initialised as a share. Returns ``(None, params)``."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seed_words = jnp.ravel(jax.random.key_data(rng)).astype(jnp.uint32)
    rng = jax.random.wrap_key_data(jnp.resize(seed_words, (4, )), impl="rbg")
    dtype = param_dtype or jnp.float32
    params = jax.jit(_ends, static_argnums=(0, 2))(cfg, jax.random.fold_in(rng, 2**31 - 1), dtype)
    layer = jax.jit(_layer, static_argnums=(0, 1, 2, 4))
    for i in range(cfg.num_hidden_layers):
        params[f"layers_{i}"] = layer(cfg, cfg.is_kda(i), cfg.is_dense(i),
                                      jax.random.fold_in(rng, i), dtype)
    return None, params
