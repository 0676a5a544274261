"""TII Falcon-H1 hybrid causal LM (``model_type="falcon_h1"``:
Falcon-H1-34B-Instruct), SERVING ONLY.

Source: ``huggingface.co/tiiuae/Falcon-H1-34B-Instruct`` ``config.json``; what
the configuration has no key for (marked +) is the family's public modelling
code, ``transformers`` ``models/falcon_h1``. Every layer runs an attention
mixer and a Mamba-2 mixer SIDE BY SIDE on the same normed rows and adds both
to the stream, then a gated feed-forward; fourteen scalar MULTIPLIERS stand in
the forward pass (``*_multiplier``, ``ssm_multipliers``, ``mlp_multipliers``):

    h = embed[ids] x embedding_multiplier
    u = rms(h; input_layernorm)
    Mamba-2 (``d_inner`` = ``mamba_d_ssm``, NOT ``mamba_expand x hidden_size``;
    ``mamba_n_heads x mamba_d_head`` of it):
      p = (u x ssm_in_multiplier) W_in, columns + [z | xBC | dt], each block of
      columns z, x, B, C, dt times its entry of ``ssm_multipliers`` +; a causal
      depthwise convolution of ``mamba_d_conv`` taps over xBC with a bias, then
      silu; dt = softplus(dt + dt_bias), nothing clamped +; the state a head
      S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t in float32, a = -exp(A_log),
      y_t = S_t C_t + D x_t; the gate BEFORE the grouped norm
      (``mamba_norm_before_gate`` false): rms_grouped(y silu(z)) g over
      ``mamba_n_groups`` groups; mamba = (y W_out) x ssm_out_multiplier
    attention, on the same u:
      a = u x attention_in_multiplier; q, k, v = a Wq, (a Wk) x key_multiplier +
      (before the rotary embedding), a Wv; rotate-half rotary over the whole
      head at ``rope_theta``; causal grouped-query softmax;
      attn = (. Wo) x attention_out_multiplier
    h = h + mamba + attn
    f = rms(h; pre_ff_layernorm)
    h = h + ((f W_up) silu((f W_gate) x mlp_multipliers[0])) W_down x mlp_multipliers[1]
    logits = (rms(h; final_layernorm) W_head) x lm_head_multiplier      (untied)

Refused rather than served wrong: the norm before the gate, no grouped norm,
no feed-forward, any bias but the convolution's, tied embeddings, a rotary
scaling, ``attn_layer_indices``, another activation than silu.

There is no training module. :func:`init_params` makes the tree the serving
model (``inference/v2/model_implementations/falcon_h1_v2.py``) reads.
"""

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_hidden_layers: int = 72
    intermediate_size: int = 21504
    # attention
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    attention_bias: bool = False
    attn_layer_indices: Optional[tuple] = None
    rope_theta: float = 1e11
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 262144
    # Mamba-2
    mamba_d_ssm: Optional[int] = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_norm_before_gate: bool = False
    mamba_rms_norm: bool = True
    mamba_use_mlp: bool = True
    # the feed-forward and the rest
    hidden_act: str = "silu"
    mlp_bias: bool = False
    projectors_bias: bool = False
    mlp_expansion_factor: int = 8
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    num_logits_to_keep: int = 1
    # the fourteen multipliers
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: Tuple[float, ...] = (0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                                          0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369, 0.011160714285714284)
    # where the seeded dt_bias is drawn (Mamba-2's published initialisation;
    # the configuration carries no range)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    dtype: jnp.dtype = jnp.bfloat16
    model_type: str = "falcon_h1"

    def __post_init__(self):
        # a configuration file's lists: a static argument of the jitted
        # initialisers has to hash
        object.__setattr__(self, "ssm_multipliers", tuple(float(m) for m in self.ssm_multipliers))
        object.__setattr__(self, "mlp_multipliers", tuple(float(m) for m in self.mlp_multipliers))
        # 100000000000, as the file writes it, is no int32: a float from here on
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has an entry each for z, x, B, C and dt, "
                             "mlp_multipliers one for the gate and one for the output")
        # refuse what is not implemented rather than serve wrong logits
        if self.mamba_norm_before_gate or not self.mamba_rms_norm:
            raise NotImplementedError("only the gate BEFORE a grouped RMS norm is implemented "
                                      "(mamba_norm_before_gate false, mamba_rms_norm true)")
        if not self.mamba_use_mlp:
            raise NotImplementedError("mamba_use_mlp false: a layer without its feed-forward")
        if self.hidden_act != "silu":
            raise NotImplementedError(f"hidden_act {self.hidden_act!r}: only 'silu'")
        if self.attention_bias or self.mamba_proj_bias or self.mlp_bias or self.projectors_bias:
            raise NotImplementedError(
                "attention_bias / mamba_proj_bias / mlp_bias / projectors_bias: the only bias "
                "that is implemented is the convolution's (mamba_conv_bias)")
        if self.tie_word_embeddings:
            raise NotImplementedError("tied embeddings are not implemented")
        if self.rope_scaling:
            raise NotImplementedError(f"rope_scaling {self.rope_scaling!r} is not implemented")
        if self.attn_layer_indices is not None:
            raise NotImplementedError("attn_layer_indices: every layer holds both mixers")
        if self.d_inner != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(f"d_inner {self.d_inner} is not {self.mamba_n_heads} heads of "
                             f"{self.mamba_d_head}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(f"{self.mamba_n_heads} Mamba heads in {self.mamba_n_groups} groups")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads over "
                             f"{self.num_key_value_heads} K/V heads")

    # ---------------------------------------------------------------- shape --
    @property
    def d_inner(self) -> int:
        """``mamba_d_ssm`` where the configuration states it (34B: 4096, not
        ``mamba_expand x hidden_size`` = 10240)."""
        if self.mamba_d_ssm is not None:
            return self.mamba_d_ssm
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self) -> int:
        """The convolution's channels: x, B and C side by side."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_width(self) -> int:
        return self.d_inner + self.conv_dim + self.mamba_n_heads

    @property
    def in_proj_columns(self) -> Tuple[Tuple[int, float], ...]:
        """``in_proj``'s output columns, in order: (width, its entry of
        ``ssm_multipliers``) for z, x, B, C and dt."""
        gn = self.mamba_n_groups * self.mamba_d_state
        return tuple(zip((self.d_inner, self.d_inner, gn, gn, self.mamba_n_heads),
                         self.ssm_multipliers))

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3, intermediate_size=96,
                    num_attention_heads=5, num_key_value_heads=1, head_dim=16, mamba_d_ssm=48,
                    mamba_n_heads=6, mamba_d_head=8, mamba_n_groups=2, mamba_d_state=16,
                    mamba_chunk_size=8, max_position_embeddings=512)
        base.update(kw)
        return FalconH1Config(**base)


# --------------------------------------------------------------- parameters --
def _normal(key, shape, fan_in, dtype, behind=1.0):
    """Normal with variance 1 / fan_in AFTER the multiplier ``behind`` which the
    kernel stands: standard deviation 1 / (behind sqrt(fan_in))."""
    return (jax.random.normal(key, shape, jnp.float32)
            / (behind * math.sqrt(fan_in))).astype(dtype)


def _mamba(cfg: FalconH1Config, key, dtype, into_stream):
    """``A_log`` = log of uniform(1, 16), ``dt_bias`` the inverse softplus of a
    step drawn log-uniformly in ``[time_step_min, time_step_max]`` and floored
    at ``time_step_floor``, ``D`` = 1 (Mamba-2's published initialisation);
    the convolution as ``models/nemotron_h.py`` has it. ``in_proj``'s column
    blocks each answer their own entry of ``ssm_multipliers`` (and all of them
    ``ssm_in_multiplier``)."""
    M, H = cfg.hidden_size, cfg.mamba_n_heads
    k = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(k[3], (H, ), jnp.float32)
                 * (math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
                 + math.log(cfg.time_step_min))
    dt = jnp.maximum(dt, cfg.time_step_floor)
    columns = jnp.concatenate([jnp.full((width, ), cfg.ssm_in_multiplier * m, jnp.float32)
                               for width, m in cfg.in_proj_columns])
    in_proj = jax.random.normal(k[0], (M, cfg.in_proj_width), jnp.float32) \
        / (columns[None, :] * math.sqrt(M))
    return {
        "in_proj": {"kernel": in_proj.astype(dtype)},
        "conv1d": {"kernel": _normal(k[1], (cfg.conv_dim, cfg.mamba_d_conv), cfg.mamba_d_conv,
                                     jnp.float32),
                   "bias": 0.1 * jax.random.normal(k[2], (cfg.conv_dim, ), jnp.float32)},
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k[4], (H, ), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((H, ), jnp.float32),
        "norm": {"weight": jnp.ones((cfg.d_inner, ), jnp.float32)},
        "out_proj": {"kernel": _normal(k[5], (cfg.d_inner, M), cfg.d_inner * into_stream, dtype,
                                       cfg.ssm_out_multiplier)},
    }


def _attention(cfg: FalconH1Config, key, dtype, into_stream):
    M, H, KVH, D = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    k = jax.random.split(key, 4)
    a_in = cfg.attention_in_multiplier
    return {"q_proj": {"kernel": _normal(k[0], (M, H * D), M, dtype, a_in)},
            "k_proj": {"kernel": _normal(k[1], (M, KVH * D), M, dtype, a_in * cfg.key_multiplier)},
            "v_proj": {"kernel": _normal(k[2], (M, KVH * D), M, dtype, a_in)},
            "o_proj": {"kernel": _normal(k[3], (H * D, M), H * D * into_stream, dtype,
                                         cfg.attention_out_multiplier)}}


def _feed_forward(cfg: FalconH1Config, key, dtype, into_stream):
    M, F = cfg.hidden_size, cfg.intermediate_size
    k = jax.random.split(key, 3)
    gate_m, down_m = cfg.mlp_multipliers
    return {"gate_proj": {"kernel": _normal(k[0], (M, F), M, dtype, gate_m)},
            "up_proj": {"kernel": _normal(k[1], (M, F), M, dtype)},
            "down_proj": {"kernel": _normal(k[2], (F, M), F * into_stream, dtype, down_m)}}


def _layer(cfg: FalconH1Config, key, dtype):
    """Every kernel drawn so that KERNEL x ITS MULTIPLIER has variance 1 /
    fan_in: with plain 1 / fan_in weights the multipliers (made for trained
    weights) would leave each branch a few hundredths of the stream, and a
    comparison of logits blind to the layers. The three projections that write
    into the stream (``out_proj``, ``o_proj``, ``down_proj``) further times
    1 / sqrt(3 x layers); the norms' gains 1."""
    into_stream = 3.0 * cfg.num_hidden_layers
    k = jax.random.split(key, 3)
    M = cfg.hidden_size
    return {"input_layernorm": {"weight": jnp.ones((M, ), jnp.float32)},
            "mamba": _mamba(cfg, k[0], dtype, into_stream),
            "self_attn": _attention(cfg, k[1], dtype, into_stream),
            "pre_ff_layernorm": {"weight": jnp.ones((M, ), jnp.float32)},
            "feed_forward": _feed_forward(cfg, k[2], dtype, into_stream)}


def _by_rows(key, shape, fan_in, dtype, behind, blocks=8):
    """:func:`_normal` drawn ``blocks`` row blocks at a time: the float32 draw of
    a 261120 x 5120 matrix is 5 GiB beside its bf16 copy, of a block 0.7."""
    if shape[0] % blocks:
        return _normal(key, shape, fan_in, dtype, behind)
    block = (shape[0] // blocks, ) + tuple(shape[1:])
    out = jax.lax.map(lambda k: _normal(k, block, fan_in, dtype, behind),
                      jax.random.split(key, blocks))
    return out.reshape(shape)


def _embedding(cfg: FalconH1Config, key, dtype):
    return _by_rows(key, (cfg.vocab_size, cfg.hidden_size), 1.0, dtype, cfg.embedding_multiplier)


def _head(cfg: FalconH1Config, key, dtype):
    M = cfg.hidden_size
    return _by_rows(key, (M, cfg.vocab_size), M, dtype, cfg.lm_head_multiplier)


def init_params(cfg: FalconH1Config, rng=None, param_dtype=None):
    """Random parameters, made on the device as ``models/nemotron_h.py`` makes
    them: the embedding and the head by one jitted program each, the layers by
    one program run once a layer with the key folded with the layer's index.
    Returns ``(None, params)``."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seed_words = jnp.ravel(jax.random.key_data(rng)).astype(jnp.uint32)
    rng = jax.random.wrap_key_data(jnp.resize(seed_words, (4, )), impl="rbg")
    dtype = param_dtype or jnp.float32
    ends = jax.random.split(jax.random.fold_in(rng, 2**31 - 1), 2)
    params = {
        "embed_tokens": {"embedding": jax.jit(_embedding, static_argnums=(0, 2))(cfg, ends[0],
                                                                                 dtype)},
        "final_layernorm": {"weight": jnp.ones((cfg.hidden_size, ), jnp.float32)},
        "lm_head": {"kernel": jax.jit(_head, static_argnums=(0, 2))(cfg, ends[1], dtype)}}
    layer = jax.jit(_layer, static_argnums=(0, 2))
    for i in range(cfg.num_hidden_layers):
        params[f"layers_{i}"] = layer(cfg, jax.random.fold_in(rng, i), dtype)
    return None, params
