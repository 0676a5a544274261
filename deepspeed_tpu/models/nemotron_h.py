"""NVIDIA Nemotron-H hybrid causal LM (``model_type="nemotron_h"``:
Nemotron-3-Nano-30B-A3B), SERVING ONLY, and served as ONE CHIP'S SHARE of a
deployment that shares each layer over several chips.

Source: ``huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``
``config.json``; what the configuration has no key for (marked +) is the
family's public modelling code, ``transformers`` ``models/nemotron_h``. Every
block is ``x <- x + mixer(RMSNorm(x))``: ONE norm and ONE mixer a block, the
mixer set by the block's character in ``hybrid_override_pattern``:

- ``M``, **Mamba-2**: ``[z | xBC | dt] = u W_in`` (widths ``d_inner`` |
  ``d_inner + 2 n_groups ssm_state_size`` | ``mamba_num_heads``; ``d_inner`` =
  ``mamba_num_heads x mamba_head_dim``); a causal depthwise convolution of
  ``conv_kernel`` taps over ``xBC`` with a bias, then silu; ``[x | B | C]`` of
  it, head h reading group ``h // (heads / n_groups)``; ``dt = softplus(dt +
  dt_bias)``, ``a = -exp(A_log)``; a state a head ``h_t = exp(dt_t a) h_{t-1} +
  dt_t x_t (x) B_t`` in float32, ``y_t = h_t C_t + D x_t``; + the gate BEFORE
  the norm, ``RMSNorm_grouped(y silu(z)) g`` over ``n_groups`` groups; ``out =
  y W_out``. ``time_step_min / max / floor`` are initialisation ranges (the
  seeded ``dt_bias`` is drawn through them); + nothing is clamped;
- ``E``, **experts**: ``n_routed_experts`` ungated experts ``relu(x W_up)^2
  W_down`` (``mlp_hidden_act`` relu2) beside one shared expert of
  ``moe_shared_expert_intermediate_size``; sigmoid scores in float32, the
  ``num_experts_per_tok`` largest of score + ``e_score_correction_bias``,
  weights the chosen SCORES renormalised (``norm_topk_prob``) times
  ``routed_scaling_factor``;
- ``*``, **attention**: grouped-query, ``head_dim`` its own key, no bias,
  causal, + NO rotary embedding (the family's code applies none: ``rope_theta``
  and ``partial_rotary_factor`` are unread);
- ``-``, a dense relu2 feed-forward of ``intermediate_size``.

A final RMSNorm and an untied head.

**The share.** ``experts_held`` < ``n_routed_experts``: this chip holds experts
``expert_rank * experts_held ..`` of those the router scores, as
``models/deepseek_v32.py`` says it. ``vocab_size`` may be a slice.

**The banks' lanes.** An expert's intermediate width is held in whole 128-lane
tiles (:attr:`NemotronHConfig.bank_width`: 1856 -> 1920), the padding zero: a
device array's minor dimension is tiled so anyway, and the grouped matmul
(``ops/pallas/grouped_matmul.py:lane_padded``) takes whole lane tiles.
``relu(0)^2 = 0`` times a zero row of ``W_down``: no logit changes. Banks that
arrive at the published width (a checkpoint's) are padded once where the
serving model is built (``RaggedMoE.banks_in_lane_tiles``).

Refused rather than served wrong: another ``mamba_hidden_act`` /
``mlp_hidden_act``, any bias but the convolution's, a group limit (``n_group``
> 1), tied embeddings, a sliding window.

There is no training module. :func:`init_params` makes the tree the serving
model (``inference/v2/model_implementations/nemotron_h_v2.py``) reads.
"""

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

MAMBA, EXPERTS, ATTENTION, MLP = "M", "E", "*", "-"


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    sliding_window: Optional[int] = None
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    mamba_hidden_act: str = "silu"
    mamba_proj_bias: bool = False
    use_conv_bias: bool = True
    use_bias: bool = False
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts and the dense feed-forward
    intermediate_size: int = 1856
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 262144
    # carried for the record; none changes a next-token logit
    expand: int = 2
    norm_eps: float = 1e-5
    num_logits_to_keep: int = 1
    partial_rotary_factor: float = 1.0
    rope_theta: float = 10000.0
    rescale_prenorm_residual: bool = True
    residual_in_fp32: bool = False
    use_mamba_kernels: bool = True
    # the share of the deployment this chip holds (None: every routed expert)
    experts_held: Optional[int] = None
    expert_rank: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    model_type: str = "nemotron_h"

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        n, pattern = self.num_hidden_layers, self.hybrid_override_pattern
        if len(pattern) != n:
            raise ValueError(f"hybrid_override_pattern names {len(pattern)} blocks, "
                             f"num_hidden_layers is {n}")
        unknown = sorted(set(pattern) - {MAMBA, EXPERTS, ATTENTION, MLP})
        if unknown:
            raise ValueError(f"hybrid_override_pattern {unknown}: a block is M (Mamba-2), "
                             f"E (experts), * (attention) or - (a dense feed-forward)")
        # refuse what is not implemented rather than serve wrong logits
        if self.mamba_hidden_act != "silu":
            raise NotImplementedError(f"mamba_hidden_act {self.mamba_hidden_act!r}: only 'silu'")
        if self.mlp_hidden_act != "relu2":
            raise NotImplementedError(f"mlp_hidden_act {self.mlp_hidden_act!r}: only 'relu2'")
        if self.attention_bias or self.mamba_proj_bias or self.mlp_bias or self.use_bias:
            raise NotImplementedError(
                "attention_bias / mamba_proj_bias / mlp_bias / use_bias: the only bias that is "
                "implemented is the convolution's (use_conv_bias)")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                f"n_group {self.n_group} / topk_group {self.topk_group}: a group limit on this "
                f"family's routing is not implemented (the published model has none)")
        if self.tie_word_embeddings:
            raise NotImplementedError("tied embeddings are not implemented")
        if self.sliding_window:
            raise NotImplementedError(f"sliding_window {self.sliding_window}: the attention "
                                      f"blocks see every earlier key")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"{self.mamba_num_heads} Mamba heads in {self.n_groups} groups")
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
    def layers_of(self, kind: str):
        """The blocks of one kind, in order: a block's cache index is its
        ordinal here."""
        return tuple(i for i, c in enumerate(self.hybrid_override_pattern) if c == kind)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """The convolution's channels: x, B and C side by side."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def in_proj_width(self) -> int:
        return self.d_inner + self.conv_dim + self.mamba_num_heads

    @property
    def bank_width(self) -> int:
        """An expert's intermediate width as the banks hold it: whole lane
        tiles, the grouped kernel's rule (``RaggedMoE.banks_in_lane_tiles``
        pads a checkpoint's banks to it where the serving model is built)."""
        from deepspeed_tpu.ops.pallas.grouped_matmul import lane_padded
        return lane_padded(self.moe_intermediate_size)

    @property
    def first_expert_held(self) -> int:
        return self.expert_rank * self.experts_held

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=7,
                    hybrid_override_pattern="MEM*EME", num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
                    n_groups=2, ssm_state_size=16, chunk_size=8, intermediate_size=48,
                    moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
                    n_routed_experts=8, num_experts_per_tok=3, max_position_embeddings=512)
        base.update(kw)
        return NemotronHConfig(**base)


# --------------------------------------------------------------- parameters --
# The selection bias at init, in score units: ``models/deepseek_v32.py``'s
# argument (a trained bias balances the load; a tenth of Trinity's spread still
# reorders near-ties and leaves each of the two chips its half).
SELECT_BIAS_STD = 0.002


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def routed_out_scale(cfg: NemotronHConfig) -> float:
    """The ROUTED experts' ``wo`` over the shared expert's ``down_proj``:
    1.5 / top-k, ``models/afmoe.py:routed_out_scale``'s argument."""
    return min(1.0, 1.5 / cfg.num_experts_per_tok)


def _mamba(cfg: NemotronHConfig, key, dtype, into_stream):
    """``A_log`` = log of uniform(1, 16), ``dt_bias`` the inverse softplus of a
    step drawn log-uniformly in ``[time_step_min, time_step_max]`` and floored
    at ``time_step_floor``, ``D`` = 1: the published initialisation."""
    M, H = cfg.hidden_size, cfg.mamba_num_heads
    k = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(k[3], (H, ), jnp.float32)
                 * (math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
                 + math.log(cfg.time_step_min))
    dt = jnp.maximum(dt, cfg.time_step_floor)
    return {
        "in_proj": {"kernel": _normal(k[0], (M, cfg.in_proj_width), M, dtype)},
        "conv1d": {"kernel": _normal(k[1], (cfg.conv_dim, cfg.conv_kernel), cfg.conv_kernel,
                                     jnp.float32),
                   "bias": 0.1 * jax.random.normal(k[2], (cfg.conv_dim, ), jnp.float32)},
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k[4], (H, ), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((H, ), jnp.float32),
        "norm": {"weight": jnp.ones((cfg.d_inner, ), jnp.float32)},
        "out_proj": {"kernel": _normal(k[5], (cfg.d_inner, M), cfg.d_inner * into_stream, dtype)},
    }


def _relu2_mlp(key, hidden, width, into_stream, dtype):
    k = jax.random.split(key, 2)
    return {"up_proj": {"kernel": _normal(k[0], (hidden, width), hidden, dtype)},
            "down_proj": {"kernel": _normal(k[1], (width, hidden), width * into_stream, dtype)}}


def _experts(cfg: NemotronHConfig, key, dtype, into_stream):
    M, E, El = cfg.hidden_size, cfg.n_routed_experts, cfg.experts_held
    F, Fb = cfg.moe_intermediate_size, cfg.bank_width
    k = jax.random.split(key, 5)
    lanes = (jnp.arange(Fb) < F)  # the banks' padding lanes hold zeros
    wi = _normal(k[2], (El, M, Fb), M, dtype) * lanes[None, None, :].astype(dtype)
    wo = _normal(k[3], (El, Fb, M), F * into_stream / routed_out_scale(cfg)**2, dtype) \
        * lanes[None, :, None].astype(dtype)
    out = {"gate": _normal(k[0], (M, E), M, jnp.float32),
           "e_score_correction_bias": SELECT_BIAS_STD * jax.random.normal(k[1], (E, ), jnp.float32),
           "experts": {"wi": wi, "wo": wo}}
    if cfg.n_shared_experts:
        Fs = cfg.moe_shared_expert_intermediate_size * cfg.n_shared_experts
        out["shared_experts"] = _relu2_mlp(k[4], M, Fs, into_stream, dtype)
    return out


def _attention(cfg: NemotronHConfig, key, dtype, into_stream):
    M, H, KVH, D = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    k = jax.random.split(key, 4)
    return {"q_proj": {"kernel": _normal(k[0], (M, H * D), M, dtype)},
            "k_proj": {"kernel": _normal(k[1], (M, KVH * D), M, dtype)},
            "v_proj": {"kernel": _normal(k[2], (M, KVH * D), M, dtype)},
            "o_proj": {"kernel": _normal(k[3], (H * D, M), H * D * into_stream, dtype)}}


def _layer(cfg: NemotronHConfig, kind: str, key, dtype):
    """Every kernel normal with variance 1 / fan_in (of ONE expert, for the
    banks); the projections that write into the residual stream times 1 /
    sqrt(blocks) (``rescale_prenorm_residual``: one mixer a block); the routed
    experts' ``wo`` also times :func:`routed_out_scale`; the norms' gains 1."""
    into_stream = float(cfg.num_hidden_layers)
    if kind == MAMBA:
        mixer = _mamba(cfg, key, dtype, into_stream)
    elif kind == EXPERTS:
        mixer = _experts(cfg, key, dtype, into_stream)
    elif kind == ATTENTION:
        mixer = _attention(cfg, key, dtype, into_stream)
    else:
        mixer = _relu2_mlp(key, cfg.hidden_size, cfg.intermediate_size, into_stream, dtype)
    return {"norm": {"weight": jnp.ones((cfg.hidden_size, ), jnp.float32)}, "mixer": mixer}


def _ends(cfg: NemotronHConfig, key, dtype):
    k = jax.random.split(key, 2)
    M, V = cfg.hidden_size, cfg.vocab_size
    return {"embed_tokens": {"embedding": _normal(k[0], (V, M), 1.0, dtype)},
            "norm_f": {"weight": jnp.ones((M, ), jnp.float32)},
            "lm_head": {"kernel": _normal(k[1], (M, V), M, dtype)}}


def init_params(cfg: NemotronHConfig, rng=None, param_dtype=None):
    """Random parameters, made on the device as ``models/afmoe.py`` makes
    them: the ends by one jitted program, the blocks by one program a KIND of
    block run once a block with the key folded with the block's index. The
    expert banks hold ``experts_held`` experts: a share is initialised as a
    share. Returns ``(None, params)``."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seed_words = jnp.ravel(jax.random.key_data(rng)).astype(jnp.uint32)
    rng = jax.random.wrap_key_data(jnp.resize(seed_words, (4, )), impl="rbg")
    dtype = param_dtype or jnp.float32
    params = jax.jit(_ends, static_argnums=(0, 2))(cfg, jax.random.fold_in(rng, 2**31 - 1), dtype)
    layer = jax.jit(_layer, static_argnums=(0, 1, 3))
    for i, kind in enumerate(cfg.hybrid_override_pattern):
        params[f"layers_{i}"] = layer(cfg, kind, jax.random.fold_in(rng, i), dtype)
    return None, params
