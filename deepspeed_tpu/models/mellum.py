"""Mellum-2 style sparse causal LM (``model_type="mellum"``), SERVING ONLY.

Source: ``huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct`` ``config.json``.
A pre-norm decoder whose every layer is grouped-query attention (``head_dim`` is
its own key: heads x head_dim need not equal ``hidden_size``) followed by a
sparse SwiGLU feed-forward, top-``num_experts_per_tok`` of ``num_experts``,
renormalised over the chosen experts when ``norm_topk_prob``. ``layer_types``
says, layer by layer, whether attention sees every earlier key
(``full_attention``) or the last ``sliding_window`` of them
(``sliding_attention``); each of the two has its own rotary table
(``rope_parameters``: ``default``, or ``yarn`` with its scaling folded in).

There is no training module: a training forward at top-k > 2 is the sharded MoE
layer's (ROADMAP D7). :func:`init_params` makes the parameter tree the serving
model (``inference/v2/model_implementations/mellum_v2.py``) reads, named as the
Mixtral tree is (``block_sparse_moe.{gate, ExpertFFN_0.{wi, wo}}`` with
``wi`` = (gate | up) side by side), so AutoTP placement and the MoE scope read
it unchanged.

What the published model has and this does not: q/k normalisation has no key in
the configuration and is not applied; the multi-token-prediction head is not
served.
"""

import math
from dataclasses import dataclass, field
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

FULL, SLIDING = "full_attention", "sliding_attention"
_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)
_ROPE_TYPES = ("default", "yarn")


def _freeze(tree):
    """Nested dicts -> nested sorted tuples: a frozen dataclass must hash."""
    if isinstance(tree, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in tree.items()))
    return tree


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    head_dim: int = 128
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    # one entry a layer; shorter configurations take a prefix of the period
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    # {layer type: {"rope_type", "rope_theta", yarn's keys}}, frozen by __post_init__
    rope_parameters: Tuple = field(default_factory=lambda: _freeze({
        FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
               "original_max_position_embeddings": 8192, "beta_fast": 32.0, "beta_slow": 1.0,
               "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000.0}}))
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    model_type: str = "mellum"

    def __post_init__(self):
        n = self.num_hidden_layers
        layer_types = tuple(self.layer_types) or tuple(_PERIOD[i % 4] for i in range(n))
        mlp_types = tuple(self.mlp_layer_types) or ("sparse", ) * n
        object.__setattr__(self, "layer_types", layer_types)
        object.__setattr__(self, "mlp_layer_types", mlp_types)
        if isinstance(self.rope_parameters, dict):
            object.__setattr__(self, "rope_parameters", _freeze(self.rope_parameters))
        if len(layer_types) != n or len(mlp_types) != n:
            raise ValueError(f"layer_types / mlp_layer_types must name {n} layers, got "
                             f"{len(layer_types)} / {len(mlp_types)}")
        # refuse what is not implemented rather than serve wrong logits
        unknown = sorted(set(layer_types) - {FULL, SLIDING})
        if unknown:
            raise ValueError(f"layer_types {unknown}: only {FULL!r} and {SLIDING!r} are served")
        if set(mlp_types) != {"sparse"}:
            raise NotImplementedError(
                f"mlp_layer_types {sorted(set(mlp_types))}: a Mellum stack is 'sparse' layers "
                f"only (its parameter tree has no dense feed-forward; a model with dense layers "
                f"in front of its expert layers is models/afmoe.py's)")
        if self.tie_word_embeddings or self.attention_bias:
            raise NotImplementedError("tied embeddings / attention biases are not implemented")
        if SLIDING in layer_types and self.sliding_window <= 0:
            raise ValueError("sliding_attention layers need sliding_window > 0")
        if not 0 < self.num_experts_per_tok <= self.num_experts:
            raise ValueError(f"num_experts_per_tok {self.num_experts_per_tok} of "
                             f"{self.num_experts} experts")
        for kind in set(layer_types):
            rope_type = self.rope_of(kind).get("rope_type", "default")
            if rope_type not in _ROPE_TYPES:
                raise NotImplementedError(f"rope_type {rope_type!r} ({kind}): only "
                                          f"{_ROPE_TYPES} are implemented")

    def rope_of(self, layer_type: str) -> Dict:
        groups = dict(self.rope_parameters)
        if layer_type not in groups:
            raise ValueError(f"rope_parameters has no entry for {layer_type!r}")
        return dict(groups[layer_type])

    def window_of(self, li: int) -> int:
        """Layer ``li``'s sliding window in tokens; 0 = every earlier key."""
        return self.sliding_window if self.layer_types[li] == SLIDING else 0

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=48, head_dim=16, num_hidden_layers=4,
                    num_attention_heads=4, num_key_value_heads=2, num_experts=8,
                    num_experts_per_tok=2, moe_intermediate_size=32, sliding_window=16,
                    max_position_embeddings=512)
        base.update(kw)
        return MellumConfig(**base)


# ------------------------------------------------------------------- rotary --
def rope_inv_freq(rope: Dict, head_dim: int):
    """``(inv_freq [head_dim / 2] float64 numpy, attention_factor)`` of one
    ``rope_parameters`` entry. ``yarn`` (arXiv:2309.00071, as the public
    implementation computes it): dimensions that turn more than ``beta_fast``
    times over the original context keep their frequency, those that turn less
    than ``beta_slow`` times are divided by ``factor``, and a linear ramp joins
    them; cos and sin are scaled by ``attention_factor``."""
    import numpy as np
    theta = float(rope["rope_theta"])
    half = head_dim // 2
    inv_freq = theta**(-np.arange(half, dtype=np.float64) * 2.0 / head_dim)
    if rope.get("rope_type", "default") == "default":
        return inv_freq, 1.0
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def turns_to_dim(turns):
        return head_dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_to_dim(float(rope.get("beta_fast", 32.0)))), 0)
    high = min(math.ceil(turns_to_dim(float(rope.get("beta_slow", 1.0)))), head_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq / factor * ramp + inv_freq * (1.0 - ramp), float(attention_factor)


def rotary_cos_sin(rope: Dict, positions, head_dim: int):
    """``(cos, sin)`` ``[len(positions), head_dim / 2]`` float32 at integer
    ``positions``, with the attention factor folded in (the angles are float32
    products, as the training models' tables are)."""
    inv_freq, scale = rope_inv_freq(rope, head_dim)
    angles = jnp.asarray(positions).astype(jnp.float32)[:, None] * \
        jnp.asarray(inv_freq, jnp.float32)[None, :]
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


# --------------------------------------------------------------- parameters --
def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def _layer(cfg: MellumConfig, key, dtype):
    """Every kernel normal with variance 1 / fan_in (of ONE expert, for the
    banks), and the two projections that write into the residual stream
    (``o_proj``, the experts' ``wo``) scaled by 1 / sqrt(2 x layers) as GPT-2 and
    Megatron initialise them. Without that scaling a top-8-of-64 model with
    random weights is chaotic in its routing: an expert's output is of the
    stream's own size, one flipped 8th-against-9th choice moves the hidden state
    by 2^-5 of it, which flips further choices in the layers above (read on the
    chip at 4 layers, bf16 against the float32 reference: logits off by 2^-3.6 of
    the largest in the rows that flipped, 2^-5.0 with the scaling, 2^-7.5 in the
    rows that did not; PERF.md section 6, PR 30)."""
    M, D, F, E = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size, cfg.num_experts
    H, KVH = cfg.num_attention_heads, cfg.num_key_value_heads
    k = jax.random.split(key, 7)
    ones = jnp.ones((M, ), jnp.float32)
    into_stream = 2.0 * cfg.num_hidden_layers  # fan_in x this: std / sqrt(2 x layers)
    return {
        "input_layernorm": {"weight": ones},
        "self_attn": {"q_proj": {"kernel": _normal(k[0], (M, H * D), M, dtype)},
                      "k_proj": {"kernel": _normal(k[1], (M, KVH * D), M, dtype)},
                      "v_proj": {"kernel": _normal(k[2], (M, KVH * D), M, dtype)},
                      "o_proj": {"kernel": _normal(k[3], (H * D, M), H * D * into_stream, dtype)}},
        "post_attention_layernorm": {"weight": ones},
        "block_sparse_moe": {
            "gate": _normal(k[4], (M, E), M, jnp.float32),
            "ExpertFFN_0": {"wi": _normal(k[5], (E, M, 2 * F), M, dtype),
                            "wo": _normal(k[6], (E, F, M), F * into_stream, dtype)}},
    }


def _ends(cfg: MellumConfig, key, dtype):
    k = jax.random.split(key, 2)
    M, V = cfg.hidden_size, cfg.vocab_size
    return {"embed_tokens": {"embedding": _normal(k[0], (V, M), 1.0, dtype)},
            "norm": {"weight": jnp.ones((M, ), jnp.float32)},
            "lm_head": {"kernel": _normal(k[1], (M, V), M, dtype)}}


def init_params(cfg: MellumConfig, rng=None, param_dtype=None):
    """Random parameters, made on the device: embedding, final norm and head by
    one jitted program, the layers by ONE one-layer program run once a layer
    with the key folded with the layer's index (a 12-layer tree costs the
    compilations of a 1-layer one). The bits come from the device's own
    generator (``rbg`` keys derived from ``rng``): threefry over 4 GiB of
    normals is most of a cold start's 25 s here. Returns ``(None, params)``:
    the other models' ``(module, params)`` with no training module to give."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seed_words = jnp.ravel(jax.random.key_data(rng)).astype(jnp.uint32)
    rng = jax.random.wrap_key_data(jnp.resize(seed_words, (4, )), impl="rbg")
    dtype = param_dtype or jnp.float32
    params = jax.jit(_ends, static_argnums=(0, 2))(cfg, jax.random.fold_in(rng, 2**31 - 1), dtype)
    layer = jax.jit(_layer, static_argnums=(0, 2))
    for i in range(cfg.num_hidden_layers):
        params[f"layers_{i}"] = layer(cfg, jax.random.fold_in(rng, i), dtype)
    return None, params
