"""Upstage Solar Open 2 causal LM (``model_type="solar_open2"``:
Solar-Open2-250B), SERVING ONLY, and served as ONE CHIP'S SHARE of a deployment
that shares each layer over several chips.

Source: ``huggingface.co/upstage/Solar-Open2-250B`` ``config.json``; what the
configuration has no key for (marked +) is the Kimi Linear report
(arXiv:2510.26692) and its public ``fla`` layer for the linear mixer, and Solar
Open's public GLM-4.5-style MoE code for the experts, as remembered. Every
layer is pre-norm, ``x <- x + mixer(RMSNorm(x))``, ``x <- x + moe(RMSNorm(x))``;
there is NO position encoding anywhere (``use_rope`` false). The mixer is set
by the layer's index:

- a layer in ``gqa_layers``, **softmax attention**: grouped-query, causal, no
  rotary, + no q/k norm, with an output gate (``use_gqa_gate``): ``W_o [ attn
  (.) sigmoid(W_gate x) ]``, + the gate element-wise, hidden -> heads x head_dim;
- every other layer, **gated delta-rule linear attention** ("KDA"),
  ``linear_attn_config``: H heads of d_k = d_v = ``head_dim``; ``q, k, v =
  silu(conv(W x))``, each through its own causal depthwise convolution of
  ``short_conv_kernel_size`` taps without a bias; a head's q and k L2-normed,
  q also times d_k^-1/2; a decay a CHANNEL ``g = -exp(A_log[h]) softplus(W_f^
  W_fv x + dt_bias)`` through a rank-``head_dim`` pair (``kda_use_full_proj``
  false), ``alpha = exp(g)``; ``beta = 2 sigmoid(w_b x)`` a head
  (``kda_allow_neg_eigval``: the 2); a float32 state ``S`` in ``R^{d_k x d_v}`` a
  head: ``S~ = diag(alpha) S``, ``S = S~ + beta k (v - S~^T k)^T``, ``o = S^T q``;
  out ``W_o [ RMSNorm_head(o) w (.) sigmoid(W_g^ W_gv x) ]``
  (``modules/kda.py``);
- the feed-forward of EVERY layer (``first_k_dense_replace`` 0):
  ``n_routed_experts`` SwiGLU experts of ``moe_intermediate_size`` beside
  ``n_shared_experts`` shared ones; sigmoid scores in float32, the
  ``num_experts_per_tok`` largest of score + ``e_score_correction_bias``,
  weights the chosen SCORES renormalised (``norm_topk_prob``) times
  ``routed_scaling_factor``.

A final RMSNorm and an untied head.

**The share.** ``experts_held`` < ``n_routed_experts``: this chip holds experts
``expert_rank * experts_held ..`` of those the router scores, as
``models/deepseek_v32.py`` says it. ``vocab_size`` may be a slice.

Refused rather than served wrong: rotary embeddings (``use_rope``), the full
decay projection (``kda_use_full_proj``), fewer K/V heads in the linear mixer
than heads (``num_kv_heads``), leading dense layers, tied embeddings.

There is no training module. :func:`init_params` makes the tree the serving
model (``inference/v2/model_implementations/solar_open2_v2.py``) reads.
"""

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    # softmax attention (the layers in gqa_layers)
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    gqa_interval: int = 3
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    use_gqa_gate: bool = True
    use_rope: bool = False
    # gated delta-rule linear attention (every other layer): linear_attn_config's keys
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    linear_num_kv_heads: Optional[int] = None
    short_conv_kernel_size: int = 4
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    # experts
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 1048576
    # carried for the record; none changes a next-token logit
    intermediate_size: int = 10240
    partial_rotary_factor: float = 1.0
    rope_theta: float = 10000.0
    # + the served chunked form's chunk (rows a visit): the program's, not the model's
    kda_chunk: int = 64
    # the share of the deployment this chip holds (None: every routed expert)
    experts_held: Optional[int] = None
    expert_rank: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    model_type: str = "solar_open2"

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        object.__setattr__(self, "gqa_layers", tuple(int(i) for i in self.gqa_layers))
        # refuse what is not implemented rather than serve wrong logits
        if self.use_rope:
            raise NotImplementedError("use_rope: the published model applies no position "
                                      "encoding, and none is implemented")
        if self.kda_use_full_proj:
            raise NotImplementedError("kda_use_full_proj: only the low-rank decay projection "
                                      "(hidden -> head_dim -> heads x head_dim)")
        if self.linear_num_kv_heads not in (None, self.linear_num_heads):
            raise NotImplementedError(f"linear_attn_config.num_kv_heads "
                                      f"{self.linear_num_kv_heads}: every linear head has its "
                                      f"own key and value")
        if self.first_k_dense_replace:
            raise NotImplementedError(f"first_k_dense_replace {self.first_k_dense_replace}: "
                                      f"every layer's feed-forward is the experts'")
        if self.tie_word_embeddings:
            raise NotImplementedError("tied embeddings are not implemented")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads over "
                             f"{self.num_key_value_heads} K/V heads")
        E = self.n_routed_experts
        if not 0 < self.num_experts_per_tok <= E:
            raise ValueError(f"num_experts_per_tok {self.num_experts_per_tok} of {E} experts")
        if E % self.experts_held or not 0 <= self.expert_rank < E // self.experts_held:
            raise ValueError(f"a share of {self.experts_held} experts, rank {self.expert_rank}, "
                             f"does not divide {E} routed experts")

    # ---------------------------------------------------------------- shape --
    def is_gqa(self, li: int) -> bool:
        return li in self.gqa_layers

    @property
    def gqa_here(self) -> Tuple[int, ...]:
        """The softmax layers among this model's ``num_hidden_layers`` (the key
        is kept whole where the depth is cut): a layer's index in the K/V array
        is its ordinal here."""
        return tuple(i for i in self.gqa_layers if i < self.num_hidden_layers)

    @property
    def kda_here(self) -> Tuple[int, ...]:
        """The linear layers, in order: a layer's index in the state pools is
        its ordinal here."""
        return tuple(i for i in range(self.num_hidden_layers) if i not in self.gqa_layers)

    @property
    def kda_width(self) -> int:
        return self.linear_num_heads * self.linear_head_dim

    @property
    def beta_scale(self) -> float:
        return 2.0 if self.kda_allow_neg_eigval else 1.0

    @property
    def first_expert_held(self) -> int:
        return self.expert_rank * self.experts_held

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, gqa_layers=(0, ), linear_num_heads=2,
                    linear_head_dim=128, moe_intermediate_size=32, n_routed_experts=16,
                    num_experts_per_tok=4, kda_chunk=16, max_position_embeddings=512)
        base.update(kw)
        return SolarOpen2Config(**base)


# --------------------------------------------------------------- parameters --
# The selection bias at init, in score units: ``models/deepseek_v32.py``'s
# argument (a trained bias balances the load; a tenth of Trinity's spread still
# reorders near-ties and leaves each chip its share).
SELECT_BIAS_STD = 0.002
# the range the decay's step is drawn in: Mamba-2's published initialisation,
# which the public delta-rule layers share
DT_MIN, DT_MAX = 1e-3, 1e-1


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def routed_out_scale(cfg: SolarOpen2Config) -> float:
    """The ROUTED experts' ``wo`` over the shared expert's ``down_proj``:
    1.5 / top-k, ``models/afmoe.py:routed_out_scale``'s argument."""
    return min(1.0, 1.5 / cfg.num_experts_per_tok)


def _kda(cfg: SolarOpen2Config, key, dtype, into_stream):
    """``A_log`` = log of uniform(1, 16) a head, ``dt_bias`` the inverse
    softplus of a step drawn log-uniformly in ``[DT_MIN, DT_MAX]`` a channel."""
    M, H, D, W, K = (cfg.hidden_size, cfg.linear_num_heads, cfg.linear_head_dim, cfg.kda_width,
                     cfg.short_conv_kernel_size)
    k = jax.random.split(key, 14)
    dt = jnp.exp(jax.random.uniform(k[12], (W, ), jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    out = {f"{n}_proj": {"kernel": _normal(k[i], (M, W), M, dtype)} for i, n in enumerate("qkv")}
    out.update({f"{n}_conv1d": {"kernel": _normal(k[3 + i], (W, K), K, jnp.float32)}
                for i, n in enumerate("qkv")})
    out.update({
        "f_a_proj": {"kernel": _normal(k[6], (M, D), M, dtype)},
        "f_b_proj": {"kernel": _normal(k[7], (D, W), D, dtype)},
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k[13], (H, ), jnp.float32, 1.0, 16.0)),
        "b_proj": {"kernel": _normal(k[8], (M, H), M, dtype)},
        "g_a_proj": {"kernel": _normal(k[9], (M, D), M, dtype)},
        "g_b_proj": {"kernel": _normal(k[10], (D, W), D, dtype)},
        "o_norm": {"weight": jnp.ones((D, ), jnp.float32)},
        "o_proj": {"kernel": _normal(k[11], (W, M), W * into_stream, dtype)},
    })
    return out


def _gqa(cfg: SolarOpen2Config, key, dtype, into_stream):
    M, H, KVH, D = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    k = jax.random.split(key, 5)
    out = {"q_proj": {"kernel": _normal(k[0], (M, H * D), M, dtype)},
           "k_proj": {"kernel": _normal(k[1], (M, KVH * D), M, dtype)},
           "v_proj": {"kernel": _normal(k[2], (M, KVH * D), M, dtype)},
           "o_proj": {"kernel": _normal(k[3], (H * D, M), H * D * into_stream, dtype)}}
    if cfg.use_gqa_gate:
        out["gate_proj"] = {"kernel": _normal(k[4], (M, H * D), M, dtype)}
    return out


def _swiglu(key, hidden, width, out_fan, dtype):
    k = jax.random.split(key, 3)
    return {"gate_proj": {"kernel": _normal(k[0], (hidden, width), hidden, dtype)},
            "up_proj": {"kernel": _normal(k[1], (hidden, width), hidden, dtype)},
            "down_proj": {"kernel": _normal(k[2], (width, hidden), out_fan, dtype)}}


def _experts(cfg: SolarOpen2Config, key, dtype, into_stream):
    M, E, El, F = (cfg.hidden_size, cfg.n_routed_experts, cfg.experts_held,
                   cfg.moe_intermediate_size)
    k = jax.random.split(key, 5)
    out = {"gate": _normal(k[0], (M, E), M, jnp.float32),
           "e_score_correction_bias": SELECT_BIAS_STD * jax.random.normal(k[1], (E, ), jnp.float32),
           "experts": {"wi": _normal(k[2], (El, M, 2 * F), M, dtype),
                       "wo": _normal(k[3], (El, F, M),
                                     F * into_stream / routed_out_scale(cfg)**2, dtype)}}
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        out["shared_experts"] = _swiglu(k[4], M, Fs, Fs * into_stream, dtype)
    return out


def _layer(cfg: SolarOpen2Config, gqa: bool, key, dtype):
    """Every kernel normal with variance 1 / fan_in (of ONE expert, for the
    banks; the convolutions' of their taps); the projections that write into
    the residual stream (both ``o_proj``s, the ``down_proj``, the experts'
    ``wo``) times 1 / sqrt(2 x layers) as GPT-2 and Megatron initialise them;
    the routed experts' ``wo`` also times :func:`routed_out_scale`; the
    selection bias normal x ``SELECT_BIAS_STD``; the norms' gains 1."""
    into_stream = 2.0 * cfg.num_hidden_layers
    k = jax.random.split(key, 2)
    ones = jnp.ones((cfg.hidden_size, ), jnp.float32)
    mixer = (_gqa if gqa else _kda)(cfg, k[0], dtype, into_stream)
    return {"input_layernorm": {"weight": ones}, "post_attention_layernorm": {"weight": ones},
            "self_attn" if gqa else "linear_attn": mixer,
            "mlp": _experts(cfg, k[1], dtype, into_stream)}


def _ends(cfg: SolarOpen2Config, key, dtype):
    k = jax.random.split(key, 2)
    M, V = cfg.hidden_size, cfg.vocab_size
    return {"embed_tokens": {"embedding": _normal(k[0], (V, M), 1.0, dtype)},
            "norm": {"weight": jnp.ones((M, ), jnp.float32)},
            "lm_head": {"kernel": _normal(k[1], (M, V), M, dtype)}}


def init_params(cfg: SolarOpen2Config, rng=None, param_dtype=None):
    """Random parameters, made on the device as ``models/afmoe.py`` makes
    them: the ends by one jitted program, the layers by one program a KIND of
    layer run once a layer with the key folded with the layer's index. The
    expert banks hold ``experts_held`` experts: a share is initialised as a
    share. Returns ``(None, params)``."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seed_words = jnp.ravel(jax.random.key_data(rng)).astype(jnp.uint32)
    rng = jax.random.wrap_key_data(jnp.resize(seed_words, (4, )), impl="rbg")
    dtype = param_dtype or jnp.float32
    params = jax.jit(_ends, static_argnums=(0, 2))(cfg, jax.random.fold_in(rng, 2**31 - 1), dtype)
    layer = jax.jit(_layer, static_argnums=(0, 1, 3))
    for i in range(cfg.num_hidden_layers):
        params[f"layers_{i}"] = layer(cfg, cfg.is_gqa(i), jax.random.fold_in(rng, i), dtype)
    return None, params
