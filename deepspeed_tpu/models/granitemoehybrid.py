"""IBM Granite 4.0-H hybrid causal LM (``model_type="granitemoehybrid"``:
Granite-4.0-H-Small, 32B-A9B), SERVING ONLY, and served as ONE CHIP'S SHARE of
a deployment that shares each layer over several chips.

Source: ``huggingface.co/ibm-granite/granite-4.0-h-small`` ``config.json``;
what the configuration has no key for (marked +) is the family's public
modelling code, ``transformers`` ``models/granitemoehybrid`` (and Bamba's
mixer, which it takes). EVERY layer is a mixer and then a routed + shared
feed-forward, under two norms and two SCALED residuals; four scalar
multipliers stand in the forward pass:

    x = embed[ids] x embedding_multiplier
    layer l, of the kind ``layer_types[l]``:
      h = rms(x; input_layernorm)
      ``mamba``, Mamba-2 (``d_inner`` = ``mamba_n_heads x mamba_d_head``):
        [z | xBC | dt] = h W_in, in that order +; a causal depthwise
        convolution of ``mamba_d_conv`` taps over xBC with a bias, then silu;
        dt = softplus(dt + dt_bias), nothing clamped +; a = -exp(A_log); the
        state a head S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t in float32,
        y_t = S_t C_t + D x_t; the gate BEFORE the norm +: rms_grouped(y
        silu(z)) g over ``mamba_n_groups`` groups; m = . W_out
      ``attention``: grouped-query, no bias, causal, NO position encoding
        (``position_embedding_type`` nope), the softmax scale
        ``attention_multiplier`` and NOT 1 / sqrt(head_dim)
      x = x + residual_multiplier x m
      f = rms(x; post_attention_layernorm)
      r = f W_r in float32 (``num_local_experts`` outputs, no bias); the
      ``num_experts_per_tok`` largest are chosen and weighed by a softmax over
      THEIR logits +; the routed sum of gated SwiGLU experts
      ``intermediate_size`` wide, beside one shared expert
      ``shared_intermediate_size`` wide on every token
      x = x + residual_multiplier x (routed + shared)
    logits = rms(x; norm) embed^T / logits_scaling     (``tie_word_embeddings``)

**The share.** ``experts_held`` < ``num_local_experts``: this chip holds experts
``expert_rank * experts_held ..`` of those the router scores, as
``models/deepseek_v32.py`` says it. ``vocab_size`` may be a slice.

Refused rather than served wrong, each by its name: a
``position_embedding_type`` other than ``nope``, any bias but the
convolution's, ``tie_word_embeddings`` false (the published model ties; no
second matrix is made or read), a layer type outside ``mamba`` /
``attention``, another activation than silu, another norm than rmsnorm.

There is no training module. :func:`init_params` makes the tree the serving
model (``inference/v2/model_implementations/granitemoehybrid_v2.py``) reads.
"""

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

MAMBA, ATTENTION = "mamba", "attention"


@dataclass(frozen=True)
class GraniteMoeHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ((MAMBA, ) * 5 + (ATTENTION, ) + (MAMBA, ) * 4) * 4
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_bias: bool = False
    attention_multiplier: float = 0.0078125
    position_embedding_type: str = "nope"
    # Mamba-2
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # the routed experts beside the shared one
    intermediate_size: int = 768
    shared_intermediate_size: int = 1536
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    hidden_act: str = "silu"
    # the three other multipliers, the norms, the head
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    normalization_function: str = "rmsnorm"
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 131072
    # carried for the record: nothing is rotated
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    # the share of the deployment this chip holds (None: every routed expert)
    experts_held: Optional[int] = None
    expert_rank: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    model_type: str = "granitemoehybrid"

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.num_local_experts)
        # a configuration file's list: a static argument of the jitted initialisers has to hash
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers, "
                             f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = sorted(set(self.layer_types) - {MAMBA, ATTENTION})
        if unknown:
            raise NotImplementedError(f"layer_types {unknown}: a layer's mixer is 'mamba' "
                                      f"(Mamba-2) or 'attention'")
        # refuse what is not implemented rather than serve wrong logits
        if self.position_embedding_type != "nope":
            raise NotImplementedError(
                f"position_embedding_type {self.position_embedding_type!r}: the attention "
                f"layers apply no position encoding ('nope'); the Mamba-2 layers carry the order")
        if self.attention_bias or self.mamba_proj_bias:
            raise NotImplementedError("attention_bias / mamba_proj_bias: the only bias that is "
                                      "implemented is the convolution's (mamba_conv_bias)")
        if not self.tie_word_embeddings:
            raise NotImplementedError(
                "tie_word_embeddings false: the head IS the embedding (the published model "
                "ties them); an untied head is not implemented")
        if self.hidden_act != "silu" or self.normalization_function != "rmsnorm":
            raise NotImplementedError(f"hidden_act {self.hidden_act!r} / normalization_function "
                                      f"{self.normalization_function!r}: only silu and rmsnorm")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.hidden_size:
            raise ValueError(f"{self.mamba_n_heads} Mamba heads of {self.mamba_d_head} are not "
                             f"mamba_expand x hidden_size = {self.mamba_expand * self.hidden_size}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(f"{self.mamba_n_heads} Mamba heads in {self.mamba_n_groups} groups")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads over "
                             f"{self.num_key_value_heads} K/V heads")
        E = self.num_local_experts
        if not 0 < self.num_experts_per_tok <= E:
            raise ValueError(f"num_experts_per_tok {self.num_experts_per_tok} of {E} experts")
        if E % self.experts_held or not 0 <= self.expert_rank < E // self.experts_held:
            raise ValueError(f"a share of {self.experts_held} experts, rank {self.expert_rank}, "
                             f"does not divide {E} routed experts")

    # ---------------------------------------------------------------- shape --
    def layers_of(self, kind: str):
        """The layers whose mixer is of one kind, in order: a layer's cache
        index is its ordinal here."""
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def query_scale(self) -> float:
        """``attention_multiplier`` over the 1 / sqrt(head_dim) that the
        attention kernels apply: what the queries are multiplied by."""
        return self.attention_multiplier * math.sqrt(self.head_dim)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """The convolution's channels: x, B and C side by side."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_width(self) -> int:
        return self.d_inner + self.conv_dim + self.mamba_n_heads

    @property
    def first_expert_held(self) -> int:
        return self.expert_rank * self.experts_held

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                    layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA), num_attention_heads=4,
                    num_key_value_heads=2, attention_multiplier=0.125, mamba_n_heads=16,
                    mamba_d_head=8, mamba_n_groups=1, mamba_d_state=16, mamba_chunk_size=8,
                    intermediate_size=32, shared_intermediate_size=48, num_local_experts=8,
                    num_experts_per_tok=3, max_position_embeddings=512)
        base.update(kw)
        return GraniteMoeHybridConfig(**base)


# --------------------------------------------------------------- parameters --
# Mamba-2's published initialisation of dt_bias: the inverse softplus of a step
# drawn log-uniformly in [DT_MIN, DT_MAX] and floored (config.json has no range)
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
# THE BENCHMARK'S OWN GUESSES (a trained model's weights have learnt their place
# behind the multipliers; a seeded draw has to be given one):
# the standard deviation of a row of the embedding TIMES embedding_multiplier. The
# head is the same matrix: a token's own logit stands this x sqrt(hidden) over
# the others' spread once the layers have written a variance of ~1 into the
# stream; at 1 the one logit is 45 x the rest and a limit relative to the largest
# logit sees nothing of the layers, at 1/8 it is 8 spreads, twice the largest of
# the others (:func:`_ends`)
EMBED_INIT_GAIN = 1.0 / 8.0
# the standard deviation of an attention logit (``models/kimi_linear.py``'s
# constant and argument): under the plain rule ``attention_multiplier`` = 1/128
# leaves a logit ~N(0, 0.09^2), the softmax an average over every key and the
# branch nothing
QUERY_INIT_GAIN = 2.5
# the standard deviation of a router logit (``models/longcat_flash.py``'s constant
# and argument): the ten chosen are weighed by a softmax over themselves, and at
# 2 the first carries ~0.27 and the tenth ~0.02, a trained router's order of
# mass, so a toss-up between the tenth and the eleventh moves a fiftieth of an
# expert and the rows' errors are the arithmetic's, not a cascade of flips
ROUTER_INIT_GAIN = 2.0


def _normal(key, shape, fan_in, dtype, behind=1.0):
    """Normal with variance 1 / fan_in AFTER the multiplier ``behind`` which the
    kernel stands (``models/falcon_h1.py``'s rule)."""
    return (jax.random.normal(key, shape, jnp.float32)
            / (behind * math.sqrt(fan_in))).astype(dtype)


def _mamba(cfg: GraniteMoeHybridConfig, key, dtype, into_stream):
    """``A_log`` = log of uniform(1, 16), ``dt_bias`` through ``DT_MIN`` /
    ``DT_MAX`` / ``DT_FLOOR``, ``D`` = 1 (Mamba-2's published initialisation);
    the convolution as ``models/nemotron_h.py`` has it."""
    M, H = cfg.hidden_size, cfg.mamba_n_heads
    k = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(k[3], (H, ), jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return {
        "in_proj": {"kernel": _normal(k[0], (M, cfg.in_proj_width), M, dtype)},
        "conv1d": {"kernel": _normal(k[1], (cfg.conv_dim, cfg.mamba_d_conv), cfg.mamba_d_conv,
                                     jnp.float32),
                   "bias": 0.1 * jax.random.normal(k[2], (cfg.conv_dim, ), jnp.float32)},
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k[4], (H, ), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((H, ), jnp.float32),
        "norm": {"weight": jnp.ones((cfg.d_inner, ), jnp.float32)},
        "out_proj": {"kernel": _normal(k[5], (cfg.d_inner, M), cfg.d_inner * into_stream, dtype,
                                       cfg.residual_multiplier)},
    }


def _attention(cfg: GraniteMoeHybridConfig, key, dtype, into_stream):
    M, H, KVH, D = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    k = jax.random.split(key, 4)
    return {"q_proj": {"kernel": _normal(k[0], (M, H * D), M, dtype,
                                         cfg.query_scale / QUERY_INIT_GAIN)},
            "k_proj": {"kernel": _normal(k[1], (M, KVH * D), M, dtype)},
            "v_proj": {"kernel": _normal(k[2], (M, KVH * D), M, dtype)},
            "o_proj": {"kernel": _normal(k[3], (H * D, M), H * D * into_stream, dtype,
                                         cfg.residual_multiplier)}}


def _experts(cfg: GraniteMoeHybridConfig, key, dtype, into_stream):
    M, F, Fs = cfg.hidden_size, cfg.intermediate_size, cfg.shared_intermediate_size
    k = jax.random.split(key, 6)
    rm = cfg.residual_multiplier
    return {"gate": _normal(k[0], (M, cfg.num_local_experts), M / ROUTER_INIT_GAIN**2,
                            jnp.float32),
            "experts": {"wi": _normal(k[1], (cfg.experts_held, M, 2 * F), M, dtype),
                        "wo": _normal(k[2], (cfg.experts_held, F, M), F * into_stream, dtype, rm)},
            "shared_experts": {
                "gate_proj": {"kernel": _normal(k[3], (M, Fs), M, dtype)},
                "up_proj": {"kernel": _normal(k[4], (M, Fs), M, dtype)},
                "down_proj": {"kernel": _normal(k[5], (Fs, M), Fs * into_stream, dtype, rm)}}}


def _layer(cfg: GraniteMoeHybridConfig, kind: str, key, dtype):
    """Every kernel drawn so that KERNEL x ITS MULTIPLIER has variance 1 /
    fan_in (of ONE expert, for the banks): the four projections that write into
    the stream (``out_proj`` / ``o_proj``, the shared ``down_proj``, the
    experts' ``wo``) stand behind ``residual_multiplier`` and further times
    1 / sqrt(2 x layers), two branches a layer; ``q_proj`` behind the query's
    scale, times ``QUERY_INIT_GAIN``; the router times ``ROUTER_INIT_GAIN``, in
    float32; the norms' gains 1."""
    into_stream = 2.0 * cfg.num_hidden_layers
    k = jax.random.split(key, 2)
    ones = jnp.ones((cfg.hidden_size, ), jnp.float32)
    mixer = (_mamba if kind == MAMBA else _attention)(cfg, k[0], dtype, into_stream)
    return {"input_layernorm": {"weight": ones}, "post_attention_layernorm": {"weight": ones},
            "mamba" if kind == MAMBA else "self_attn": mixer,
            "mlp": _experts(cfg, k[1], dtype, into_stream)}


def _ends(cfg: GraniteMoeHybridConfig, key, dtype):
    """The embedding, which is the head too, and the final norm. A row is drawn
    ``EMBED_INIT_GAIN`` / ``embedding_multiplier``."""
    M, V = cfg.hidden_size, cfg.vocab_size
    return {"embed_tokens": {"embedding": _normal(key, (V, M), 1.0, dtype,
                                                  cfg.embedding_multiplier / EMBED_INIT_GAIN)},
            "norm": {"weight": jnp.ones((M, ), jnp.float32)}}


def init_params(cfg: GraniteMoeHybridConfig, rng=None, param_dtype=None):
    """Random parameters, made on the device as ``models/nemotron_h.py`` makes
    them: the ends by one jitted program, the layers by one program a KIND of
    mixer run once a layer with the key folded with the layer's index. The
    expert banks hold ``experts_held`` experts: a share is initialised as a
    share. Returns ``(None, params)``."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seed_words = jnp.ravel(jax.random.key_data(rng)).astype(jnp.uint32)
    rng = jax.random.wrap_key_data(jnp.resize(seed_words, (4, )), impl="rbg")
    dtype = param_dtype or jnp.float32
    params = jax.jit(_ends, static_argnums=(0, 2))(cfg, jax.random.fold_in(rng, 2**31 - 1), dtype)
    layer = jax.jit(_layer, static_argnums=(0, 1, 3))
    for i, kind in enumerate(cfg.layer_types):
        params[f"layers_{i}"] = layer(cfg, kind, jax.random.fold_in(rng, i), dtype)
    return None, params
