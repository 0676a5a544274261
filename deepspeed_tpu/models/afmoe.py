"""Arcee "afmoe" sparse causal LM (``model_type="afmoe"``: Trinity-Mini /
Trinity-Nano), SERVING ONLY.

Source: ``huggingface.co/arcee-ai/Trinity-Mini`` ``config.json``; what the
configuration has no key for (marked +) is the family's public modelling code,
``transformers`` ``models/afmoe``. A decoder whose every layer is

- grouped-query attention (``head_dim`` its own key) with + an RMS norm over
  each head of q and of k, + rotary embedding on the ``sliding_attention``
  layers ONLY (a ``full_attention`` layer sees every earlier key and carries no
  position encoding), and + an output gate ``sigmoid(h Wg)`` on the heads'
  output before ``o_proj``;
- a feed-forward that is a dense SwiGLU (``intermediate_size``) in the first
  ``num_dense_layers`` layers and, after them, ``num_experts`` routed SwiGLU
  experts (``moe_intermediate_size``) beside ``num_shared_experts`` always-on
  ones. The router scores by ``score_func`` in float32, picks the
  ``num_experts_per_tok`` largest of score + a per-expert selection bias, and
  weights the chosen experts by their SCORES (not the biased ones),
  renormalised over the chosen (``route_norm``) and times ``route_scale``;
- + four norms: each branch is normed going in and coming out
  (``input_layernorm`` / ``post_attention_layernorm`` around attention,
  ``pre_mlp_layernorm`` / ``post_mlp_layernorm`` around the feed-forward).

The embedding is multiplied by sqrt(``hidden_size``) (``mup_enabled``).

There is no training module (a training forward at top-k > 2 is the sharded
MoE layer's, ROADMAP D7). :func:`init_params` makes the parameter tree the
serving model (``inference/v2/model_implementations/afmoe_v2.py``) reads; the
routed experts are named as Mixtral's are (``block_sparse_moe.{gate,
ExpertFFN_0.{wi, wo}}``, ``wi`` = (gate | up) side by side), beside them
``expert_bias`` and ``shared_experts``; a dense layer's ``mlp`` is Llama's.

The group limit (``n_group`` / ``topk_group``; 1 in the published model) is
``RaggedMoE._choose``'s. Refused rather than served wrong: a ``rope_scaling``,
tied embeddings, another activation than ``silu``.
"""

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

FULL, SLIDING = "full_attention", "sliding_attention"
_SCORE_FUNCS = ("sigmoid", "softmax")


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    head_dim: int = 128
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_dense_layers: int = 2
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    n_group: int = 1
    topk_group: int = 1
    num_expert_groups: int = 1
    num_limited_groups: int = 1
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    # one entry a layer; empty = every ``global_attn_every_n_layers``-th layer full
    layer_types: Tuple[str, ...] = ()
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    mup_enabled: bool = True
    tie_word_embeddings: bool = False
    # carried for the record; neither changes a serving forward
    load_balance_coeff: float = 0.001
    use_grouped_mm: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    model_type: str = "afmoe"

    def __post_init__(self):
        n, every = self.num_hidden_layers, self.global_attn_every_n_layers
        layer_types = tuple(self.layer_types) or tuple(
            FULL if (i + 1) % every == 0 else SLIDING for i in range(n))
        object.__setattr__(self, "layer_types", layer_types)
        if len(layer_types) != n:
            raise ValueError(f"layer_types must name {n} layers, got {len(layer_types)}")
        unknown = sorted(set(layer_types) - {FULL, SLIDING})
        if unknown:
            raise ValueError(f"layer_types {unknown}: only {FULL!r} and {SLIDING!r} are served")
        # refuse what is not implemented rather than serve wrong logits
        if {self.num_expert_groups, self.num_limited_groups} != {1}:
            raise NotImplementedError(
                "expert groups by num_expert_groups / num_limited_groups other than 1 are not "
                "implemented: the group limit is n_group / topk_group")
        if self.num_experts % self.n_group or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(f"{self.num_experts} experts in {self.n_group} groups, "
                             f"{self.topk_group} kept")
        if self.score_func not in _SCORE_FUNCS:
            raise NotImplementedError(f"score_func {self.score_func!r}: only {_SCORE_FUNCS}")
        if self.rope_scaling:
            raise NotImplementedError(f"rope_scaling {self.rope_scaling!r} is not implemented")
        if self.tie_word_embeddings:
            raise NotImplementedError("tied embeddings are not implemented")
        if self.hidden_act != "silu":
            raise NotImplementedError(f"hidden_act {self.hidden_act!r}: only 'silu'")
        if SLIDING in layer_types and self.sliding_window <= 0:
            raise ValueError("sliding_attention layers need sliding_window > 0")
        if not 0 <= self.num_dense_layers < n:
            raise ValueError(f"num_dense_layers {self.num_dense_layers} of {n} layers: the dense "
                             f"layers lead and at least one expert layer follows them")
        if not 0 < self.num_experts_per_tok <= self.num_experts:
            raise ValueError(f"num_experts_per_tok {self.num_experts_per_tok} of "
                             f"{self.num_experts} experts")

    def window_of(self, li: int) -> int:
        """Layer ``li``'s sliding window in tokens; 0 = every earlier key."""
        return self.sliding_window if self.layer_types[li] == SLIDING else 0

    def rope_of(self, layer_type: str) -> Optional[dict]:
        """The rotary parameters of a layer of ``layer_type``; None where it
        carries no position encoding (a full-attention layer)."""
        if layer_type != SLIDING:
            return None
        return {"rope_type": "default", "rope_theta": self.rope_theta}

    def is_dense(self, li: int) -> bool:
        """Layer ``li``'s feed-forward is the dense SwiGLU, not the experts."""
        return li < self.num_dense_layers

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=48, head_dim=16, num_hidden_layers=5,
                    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
                    moe_intermediate_size=32, num_dense_layers=1, num_experts=8,
                    num_experts_per_tok=2, sliding_window=16, max_position_embeddings=512)
        base.update(kw)
        return AfmoeConfig(**base)


# --------------------------------------------------------------- parameters --
def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def branch_gain(cfg: AfmoeConfig) -> float:
    """What the two norms that write into the residual stream
    (``post_attention_layernorm``, ``post_mlp_layernorm``) are initialised to:
    1 / sqrt(2 x layers), the analogue of the scaled output projections of
    ``models/mellum.py``. A branch's output is re-normed here, so scaling the
    whole branch (``o_proj``, a dense layer's ``down_proj``) would change
    nothing: the gain is the place. What the re-norm does NOT undo is the
    SHARE of a branch that one part of it has: :func:`routed_out_scale`."""
    return 1.0 / math.sqrt(2.0 * cfg.num_hidden_layers)


def routed_out_scale(cfg: AfmoeConfig) -> float:
    """What the ROUTED experts' ``wo`` is initialised at, over the shared
    expert's ``down_proj``: 1.5 / top-k (3/16 at top-8).

    Sigmoid scores renormalised over the chosen k weigh them alike (0.33-0.37
    each at top-8 and ``route_scale`` 2.826; the chosen scores all lie in 0.8-
    0.95), so a flipped k-th-against-(k+1)-th choice, which a bf16 system and a
    float32 reference make differently, both rightly, on one row in six,
    swaps a whole expert: HALF the routed sum's size, whatever the init, and
    re-norming the branch does not shrink that share. The routed sum is
    therefore as large as the loosest tolerance a comparison gives such a row
    allows a flip to be, with room, and no larger (PERF.md section 6, PR 34:
    the chip's readings at 1/8, 3/16 and 1/4, and what a comparison of logits
    can and cannot see of the routed experts at that size)."""
    return min(1.0, 1.5 / cfg.num_experts_per_tok)


def _swiglu(key, hidden, width, dtype):
    k = jax.random.split(key, 3)
    return {"gate_proj": {"kernel": _normal(k[0], (hidden, width), hidden, dtype)},
            "up_proj": {"kernel": _normal(k[1], (hidden, width), hidden, dtype)},
            "down_proj": {"kernel": _normal(k[2], (width, hidden), width, dtype)}}


def _layer(cfg: AfmoeConfig, dense: bool, key, dtype):
    """Every kernel normal with variance 1 / fan_in (of ONE expert, for the
    banks), the selection bias normal x 0.02 (so that the largest of score +
    bias are not always the largest scores), the norms' gains 1 but for the two
    that write into the stream (:func:`branch_gain`), and the ROUTED experts'
    ``wo`` times :func:`routed_out_scale`."""
    M, D, E = cfg.hidden_size, cfg.head_dim, cfg.num_experts
    H, KVH, F = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.moe_intermediate_size
    k = jax.random.split(key, 10)
    ones = jnp.ones((M, ), jnp.float32)
    out = ones * branch_gain(cfg)
    layer = {
        "input_layernorm": {"weight": ones},
        "self_attn": {"q_proj": {"kernel": _normal(k[0], (M, H * D), M, dtype)},
                      "k_proj": {"kernel": _normal(k[1], (M, KVH * D), M, dtype)},
                      "v_proj": {"kernel": _normal(k[2], (M, KVH * D), M, dtype)},
                      "gate_proj": {"kernel": _normal(k[3], (M, H * D), M, dtype)},
                      "o_proj": {"kernel": _normal(k[4], (H * D, M), H * D, dtype)},
                      "q_norm": {"weight": jnp.ones((D, ), jnp.float32)},
                      "k_norm": {"weight": jnp.ones((D, ), jnp.float32)}},
        "post_attention_layernorm": {"weight": out},
        "pre_mlp_layernorm": {"weight": ones},
        "post_mlp_layernorm": {"weight": out},
    }
    if dense:
        layer["mlp"] = _swiglu(k[5], M, cfg.intermediate_size, dtype)
        return layer
    layer["block_sparse_moe"] = {
        "gate": _normal(k[5], (M, E), M, jnp.float32),
        "expert_bias": 0.02 * jax.random.normal(k[6], (E, ), jnp.float32),
        "ExpertFFN_0": {"wi": _normal(k[7], (E, M, 2 * F), M, dtype),
                        "wo": _normal(k[8], (E, F, M), F / routed_out_scale(cfg)**2, dtype)}}
    if cfg.num_shared_experts:
        layer["block_sparse_moe"]["shared_experts"] = _swiglu(k[9], M, F * cfg.num_shared_experts,
                                                              dtype)
    return layer


def _ends(cfg: AfmoeConfig, key, dtype):
    """The embedding with variance 1 / hidden where it is multiplied by
    sqrt(hidden) going in (``mup_enabled``): the stream starts at the size the
    branches add to it."""
    k = jax.random.split(key, 2)
    M, V = cfg.hidden_size, cfg.vocab_size
    return {"embed_tokens": {"embedding": _normal(k[0], (V, M), M if cfg.mup_enabled else 1.0,
                                                  dtype)},
            "norm": {"weight": jnp.ones((M, ), jnp.float32)},
            "lm_head": {"kernel": _normal(k[1], (M, V), M, dtype)}}


def init_params(cfg: AfmoeConfig, rng=None, param_dtype=None):
    """Random parameters, made on the device as ``models/mellum.py`` makes
    them: embedding, final norm and head by one jitted program, the layers by
    one program a KIND of layer (dense, sparse) run once a layer with the key
    folded with the layer's index, the bits from the device's own generator
    (``rbg``). Returns ``(None, params)``: the other models' ``(module,
    params)`` with no training module to give."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seed_words = jnp.ravel(jax.random.key_data(rng)).astype(jnp.uint32)
    rng = jax.random.wrap_key_data(jnp.resize(seed_words, (4, )), impl="rbg")
    dtype = param_dtype or jnp.float32
    params = jax.jit(_ends, static_argnums=(0, 2))(cfg, jax.random.fold_in(rng, 2**31 - 1), dtype)
    layer = jax.jit(_layer, static_argnums=(0, 1, 3))
    for i in range(cfg.num_hidden_layers):
        params[f"layers_{i}"] = layer(cfg, cfg.is_dense(i), jax.random.fold_in(rng, i), dtype)
    return None, params
