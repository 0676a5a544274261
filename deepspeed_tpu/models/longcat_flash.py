"""Meituan LongCat-Flash language model (``model_type="longcat_flash"``: the
text decoder of LongCat-Flash-Omni), SERVING ONLY, and served as ONE CHIP'S
SHARE of a deployment that shares each layer over several chips.

Source: ``huggingface.co/meituan-longcat/LongCat-Flash-Omni`` ``config.json``
(the language model's keys; the audio / vision encoders and the codec decoder
are not served: traffic is text ids); what the configuration has no key for
(marked +) is the family's public modelling code as remembered. A pre-norm
decoder whose every layer is TWO half-layers and ONE routed branch (``n`` an
RMS norm with a gain of its own at each use):

    x1 = x  + A0(n(x))
    h  = n(x1)
    m  = MoE(h)                  + computed here, added at the END of the layer
    x2 = x1 + F0(h)
    x3 = x2 + A1(n(x2))
    y  = x3 + F1(n(x3)) + m

- ``A``: **latent attention** (MLA) with rotary: ``c_q = n(W_qa x)``, ``q =
  W_qb c_q x (hidden / q_lora_rank)^1/2`` (``mla_scale_q_lora``) ->
  ``num_attention_heads`` heads of ``qk_nope_head_dim`` + ``qk_rope_head_dim``;
  ``[c, k_r] = W_kva x``, ``c_kv = n(c) x (hidden / kv_lora_rank)^1/2``
  (``mla_scale_kv_lora``), keys and values of every head from ``c_kv``; + rotary
  on the shared key's dims of query and key by INTERLEAVED pairs,
  ``rope_theta``, no scaling; + softmax scale ``qk_head_dim^-1/2``. The cache
  keeps ``[c_kv (scaled), rot(k_r)]`` a token a HALF-layer: a model layer
  holds two latent layers of the pool (cache index ``2 l + half``);
- ``F``: a dense SwiGLU ``ffn_hidden_size`` wide;
- ``MoE``: ``p = softmax(W_r h)`` in float32 over ``n_routed_experts +
  zero_expert_num`` outputs; the ``moe_topk`` largest of ``p + b``
  (``e_score_correction_bias``: it picks and does not weigh); weights
  ``routed_scaling_factor x p[chosen]``, + NOT renormalised; an output ``e <
  n_routed_experts`` is a SwiGLU expert ``expert_ffn_hidden_size`` wide, an
  output behind them an expert WITHOUT A BANK that returns ``h``
  (``zero_expert_type`` ``identity``). No shared expert.

A final RMSNorm and an untied head.

**The share.** ``experts_held`` < ``n_routed_experts``: this chip holds experts
``expert_rank * experts_held ..`` of the ``n_routed_experts`` that have banks;
the router keeps all its outputs, the identity experts are no chip's to hold
and are computed whole where the token lives. ``vocab_size`` may be a slice.

Refused rather than served wrong: an ``attention_method`` that is not MLA, a
``zero_expert_type`` that is not ``identity``, an attention bias.

There is no training module (as ``models/deepseek_v32.py`` has none).
:func:`init_params` makes the tree the serving model
(``inference/v2/model_implementations/longcat_flash_v2.py``) reads.
"""

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

# the draws, the dense SwiGLU and the ends are initialised as DeepSeek-V3.2's
from deepspeed_tpu.models.deepseek_v32 import _ends, _normal, _swiglu


@dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    attention_method: str = "MLA"
    attention_bias: bool = False
    # the share of the deployment this chip holds (None: every routed expert)
    experts_held: Optional[int] = None
    expert_rank: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    model_type: str = "longcat_flash"

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        if self.attention_method != "MLA" or self.attention_bias:
            raise NotImplementedError(f"attention_method {self.attention_method!r} / an "
                                      f"attention bias: only latent attention without biases")
        if self.zero_expert_num and self.zero_expert_type != "identity":
            raise NotImplementedError(f"zero_expert_type {self.zero_expert_type!r}: an expert "
                                      f"without a bank returns its input ('identity')")
        E = self.n_routed_experts
        if E % self.experts_held or not 0 <= self.expert_rank < E // self.experts_held:
            raise ValueError(f"a share of {self.experts_held} experts, rank {self.expert_rank}, "
                             f"does not divide {E} routed experts")
        if not 1 <= self.moe_topk <= E:
            raise ValueError(f"moe_topk {self.moe_topk} of {E} routed experts")

    # ------------------------------------------- the names the engine reads --
    @property
    def num_hidden_layers(self) -> int:
        return self.num_layers

    @property
    def num_key_value_heads(self) -> int:
        return self.num_attention_heads  # carried: every head reads the one latent

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """The cached latent row: ``kv_lora_rank`` + the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim**-0.5

    @property
    def q_lora_scale(self) -> float:
        return (self.hidden_size / self.q_lora_rank)**0.5 if self.mla_scale_q_lora else 1.0

    @property
    def kv_lora_scale(self) -> float:
        return (self.hidden_size / self.kv_lora_rank)**0.5 if self.mla_scale_kv_lora else 1.0

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def first_expert_held(self) -> int:
        return self.expert_rank * self.experts_held

    def rope(self) -> dict:
        """The rotary parameters as ``models/mellum.py:rotary_cos_sin`` takes them."""
        return {"rope_type": "default", "rope_theta": self.rope_theta}

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, ffn_hidden_size=96, expert_ffn_hidden_size=32,
                    num_layers=2, num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48,
                    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16, n_routed_experts=16,
                    zero_expert_num=8, moe_topk=4, max_position_embeddings=512, rope_theta=1e4)
        base.update(kw)
        return LongcatFlashConfig(**base)


# --------------------------------------------------------------- parameters --
# the standard deviation of an attention logit at initialisation (:func:`_mla`)
QUERY_INIT_GAIN = 2.5
# the standard deviation of a router logit, and of the selection bias (:func:`_layer`)
ROUTER_INIT_GAIN = 2.0
SELECT_BIAS_STD = 2e-4


def _mla(cfg: LongcatFlashConfig, key, dtype, into_stream):
    """``models/deepseek_v32.py``'s latent layer under the published names. The
    two published factors (``q_lora_scale`` = 2, ``kv_lora_scale`` = 12^1/2)
    multiply what ``q_b_proj`` and ``kv_b_proj`` read: those two kernels are
    drawn that much smaller, so that queries, keys and values have the
    variance the fan-in rule gives them without the factors (a trained model's
    weights have learnt their factor; a fan-in draw under it would make every
    logit ~N(0, 80^2 / 192) and the softmax one key). ``q_b_proj`` then times
    ``QUERY_INIT_GAIN`` g: a logit is ~N(0, g^2), as peaked as a trained
    layer's (``models/kimi_linear.py``'s argument)."""
    M, H, QL, KL = cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    k = jax.random.split(key, 5)
    return {"q_a_proj": {"kernel": _normal(k[0], (M, QL), M, dtype)},
            "q_a_layernorm": {"weight": jnp.ones((QL, ), jnp.float32)},
            "q_b_proj": {"kernel": _normal(k[1], (QL, H * cfg.qk_head_dim),
                                           QL * (cfg.q_lora_scale / QUERY_INIT_GAIN)**2, dtype)},
            "kv_a_proj_with_mqa": {"kernel": _normal(k[2], (M, cfg.latent_width), M, dtype)},
            "kv_a_layernorm": {"weight": jnp.ones((KL, ), jnp.float32)},
            "kv_b_proj": {"kernel": _normal(k[3], (KL, H * (cfg.qk_nope_head_dim
                                                             + cfg.v_head_dim)),
                                            KL * cfg.kv_lora_scale**2, dtype)},
            "o_proj": {"kernel": _normal(k[4], (H * cfg.v_head_dim, M),
                                         H * cfg.v_head_dim * into_stream, dtype)}}


def _layer(cfg: LongcatFlashConfig, key, dtype):
    """Every kernel normal with variance 1 / fan_in (of ONE expert, for the
    banks; :func:`_mla` for the two it draws smaller); the projections that
    write into the residual stream (``o_proj``, the ``down_proj``s, the experts'
    ``wo``) times 1 / sqrt(4 x layers): a layer is two half-layers of two
    branches each (pre-norm: nothing re-norms a branch's output); the router
    times ``ROUTER_INIT_GAIN`` g (its logits ~N(0, g^2): with the plain rule
    the softmax over 768 is flat, the twelve chosen carry 0.12 of the mass and
    the routed branch vanishes beside two dense halves); the selection bias
    normal x ``SELECT_BIAS_STD``; the norms' gains 1."""
    M, F = cfg.hidden_size, cfg.expert_ffn_hidden_size
    k = jax.random.split(key, 8)
    ones = lambda: jnp.ones((M, ), jnp.float32)  # noqa: E731
    into_stream = 4.0 * cfg.num_layers
    layer = {"mlp": {
        "gate": _normal(k[4], (M, cfg.router_outputs), M / ROUTER_INIT_GAIN**2, jnp.float32),
        "e_score_correction_bias":
        SELECT_BIAS_STD * jax.random.normal(k[5], (cfg.router_outputs, ), jnp.float32),
        "experts": {"wi": _normal(k[6], (cfg.experts_held, M, 2 * F), M, dtype),
                    "wo": _normal(k[7], (cfg.experts_held, F, M), F * into_stream, dtype)}}}
    for half in (0, 1):
        layer[f"input_layernorm_{half}"] = {"weight": ones()}
        layer[f"post_attention_layernorm_{half}"] = {"weight": ones()}
        layer[f"self_attn_{half}"] = _mla(cfg, k[half], dtype, into_stream)
        layer[f"mlps_{half}"] = _swiglu(k[2 + half], M, cfg.ffn_hidden_size,
                                        cfg.ffn_hidden_size * into_stream, dtype)
    return layer


def init_params(cfg: LongcatFlashConfig, rng=None, param_dtype=None):
    """Random parameters, made on the device as ``models/deepseek_v32.py``
    makes them: the ends by one jitted program, the layers by one program run
    once a layer with the key folded with the layer's index, the bits from the
    device's own generator. The expert banks hold ``experts_held`` experts: a
    share is initialised as a share. Returns ``(None, params)``."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seed_words = jnp.ravel(jax.random.key_data(rng)).astype(jnp.uint32)
    rng = jax.random.wrap_key_data(jnp.resize(seed_words, (4, )), impl="rbg")
    dtype = param_dtype or jnp.float32
    params = jax.jit(_ends, static_argnums=(0, 2))(cfg, jax.random.fold_in(rng, 2**31 - 1), dtype)
    layer = jax.jit(_layer, static_argnums=(0, 2))
    for i in range(cfg.num_layers):
        params[f"layers_{i}"] = layer(cfg, jax.random.fold_in(rng, i), dtype)
    return None, params
