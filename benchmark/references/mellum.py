"""Plain reference for Mellum2-12B-A2.5B-Instruct (the public ``config.json``,
huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct): a pre-norm decoder whose
every layer is grouped-query attention (32 query heads of 128 over 4 KV heads;
``head_dim`` is its own key, 32 x 128 is not the hidden size) and a sparse
SwiGLU feed-forward: softmax over 64 experts in float32, the 8 largest kept and
renormalised to sum to 1 (``norm_topk_prob``), no shared expert, dropless.
``layer_types`` says layer by layer what attention sees and how positions are
encoded:

- ``sliding_attention``: keys ``j`` with ``i - sliding_window < j <= i``; rotary
  ``default``: ``inv_freq_m = theta^(-2m / head_dim)``;
- ``full_attention``: every key ``j <= i``; rotary ``yarn``: frequencies that turn
  more than ``beta_fast`` times over ``original_max_position_embeddings`` are
  kept, those that turn less than ``beta_slow`` times are divided by ``factor``,
  a linear ramp over the dimension index joins them, and cos and sin are both
  multiplied by ``attention_factor`` (so q.k grows by its square).

Same form as ``references/mixtral.py``: float32, "highest" precision, no
kernels, no cache, no batching, one sequence, one jitted call per layer part,
attention in blocks of queries (a 16k-token prompt's scores do not fit whole),
the experts one at a time over every token weighted by the routing weight (0
where the token did not choose the expert). Independent of the code under test:
it reads the parameter tree by its names only (Mixtral's: ``block_sparse_moe.gate``
[hidden, E], ``ExpertFFN_0.wi`` [E, hidden, 2 x ffn] = (gate | up), ``wo``
[E, ffn, hidden]).

Departures from the published description, which the configuration lists under
``assumed``: the configuration has no key for q/k normalisation and none is
applied; the multi-token-prediction head is not part of the forward.
"""

import functools
import math

import jax
import jax.numpy as jnp

from .mistral import _f32, embed, head, rms_norm
from .mixtral import expert, routing

QUERY_BLOCK = 512
FULL, SLIDING = "full_attention", "sliding_attention"


def rotary_frequencies(rope, head_dim):
    """``(inv_freq [head_dim / 2] as a tuple, attention_factor)`` of one
    ``rope_parameters`` entry, in Python floats."""
    theta, half = float(rope["rope_theta"]), head_dim // 2
    plain = [theta**(-2.0 * m / head_dim) for m in range(half)]
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return tuple(plain), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r} is not in this reference")
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def dimension_turning(times):
        return head_dim * math.log(original / (times * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dimension_turning(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dimension_turning(float(rope["beta_slow"]))), head_dim - 1)
    span = (high - low) or 0.001
    scaled = []
    for m, f in enumerate(plain):
        ramp = min(max((m - low) / span, 0.0), 1.0)
        scaled.append(f / factor * ramp + f * (1.0 - ramp))
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return tuple(scaled), float(attention_factor)


def rotary(x, positions, inv_freq, scale):
    """x: [S, H, D]; rotates the pairs (x[i], x[i + D/2]); cos and sin carry
    ``scale``."""
    d = x.shape[-1]
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = (jnp.cos(angles) * scale)[:, None, :], (jnp.sin(angles) * scale)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, p, *, n_heads, n_kv_heads, head_dim, inv_freq, rope_scale, window):
    """Causal grouped-query attention of one sequence x: [S, hidden]. The
    queries are walked in blocks (``lax.map``: one small program, and a block's
    [heads, block, keys] scores are the largest temporary); a block under a
    window is given only the ``window + block`` keys that end where it ends,
    any other block every key. The mask is computed from the true positions
    either way."""
    s = x.shape[0]
    pos = jnp.arange(s)
    q = (x @ _f32(p["q_proj"]["kernel"])).reshape(s, n_heads, head_dim)
    k = (x @ _f32(p["k_proj"]["kernel"])).reshape(s, n_kv_heads, head_dim)
    v = (x @ _f32(p["v_proj"]["kernel"])).reshape(s, n_kv_heads, head_dim)
    q, k = rotary(q, pos, inv_freq, rope_scale), rotary(k, pos, inv_freq, rope_scale)
    group = n_heads // n_kv_heads
    block = min(QUERY_BLOCK, s)
    n_blocks = -(-s // block)
    q = jnp.pad(q, ((0, n_blocks * block - s), (0, 0), (0, 0)))
    span = min(s, window + block) if window else s

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block).reshape(block, n_kv_heads, group,
                                                                   head_dim)
        qpos = jnp.minimum(start + jnp.arange(block), s - 1)  # rows past the end repeat the last
        first = jnp.clip(start + block - span, 0, s - span)
        kb = jax.lax.dynamic_slice_in_dim(k, first, span)
        vb = jax.lax.dynamic_slice_in_dim(v, first, span)
        kpos = first + jnp.arange(span)
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, kb) / jnp.sqrt(jnp.float32(head_dim))
        visible = kpos[None, :] <= qpos[:, None]
        if window:
            visible &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, vb).reshape(block, n_heads * head_dim)

    out = jax.lax.map(one_block, jnp.arange(n_blocks) * block).reshape(-1, n_heads * head_dim)
    return out[:s] @ _f32(p["o_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim", "inv_freq",
                                             "rope_scale", "window", "eps"))
def attention_part(x, p, *, n_heads, n_kv_heads, head_dim, inv_freq, rope_scale, window, eps):
    with jax.default_matmul_precision("highest"):
        return x + attention(rms_norm(x, p["input_layernorm"]["weight"], eps), p["self_attn"],
                             n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
                             inv_freq=inv_freq, rope_scale=rope_scale, window=window)


def layer_settings(sizes, layer_type):
    """The attention settings of one layer type of a configuration file."""
    if layer_type not in (FULL, SLIDING):
        raise ValueError(f"layer type {layer_type!r} is not in this reference")
    inv_freq, scale = rotary_frequencies(sizes["rope_parameters"][layer_type], sizes["head_dim"])
    return dict(n_heads=sizes["num_attention_heads"], n_kv_heads=sizes["num_key_value_heads"],
                head_dim=sizes["head_dim"], inv_freq=inv_freq, rope_scale=scale,
                window=int(sizes["sliding_window"]) if layer_type == SLIDING else 0,
                eps=float(sizes["rms_norm_eps"]))


def layer(x, p, settings, *, top_k, norm_topk_prob, gaps):
    x = attention_part(x, p, **settings)
    moe = p["block_sparse_moe"]
    h, weights, gap = routing(x, p["post_attention_layernorm"]["weight"], moe["gate"],
                              top_k=top_k, eps=settings["eps"])
    if not norm_topk_prob:
        raise ValueError("norm_topk_prob false is not in this reference")
    gaps.append(gap)
    bank = moe["ExpertFFN_0"]
    for e in range(bank["wi"].shape[0]):
        x = x + expert(h, bank["wi"][e], bank["wo"][e], weights[:, e])
    return x


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None):
    """Float32 logits of one sequence; ``rows`` picks positions (default all).
    A list passed as ``routing_gaps`` receives one entry: per picked position,
    the smallest gap over the layers between the last expert chosen and the
    first left out (8th against 9th), in router-logit units."""
    if set(sizes["mlp_layer_types"][:sizes["num_hidden_layers"]]) != {"sparse"}:
        raise ValueError("only sparse feed-forward layers are in this reference")
    x = embed(params["embed_tokens"]["embedding"], jnp.asarray(ids, jnp.int32))
    gaps = []
    for i in range(sizes["num_hidden_layers"]):
        x = layer(x, params[f"layers_{i}"], layer_settings(sizes, sizes["layer_types"][i]),
                  top_k=sizes["num_experts_per_tok"], norm_topk_prob=sizes["norm_topk_prob"],
                  gaps=gaps)
    smallest = jnp.min(jnp.stack(gaps), axis=0)
    if rows is not None:
        x, smallest = x[jnp.asarray(rows)], smallest[jnp.asarray(rows)]
    if routing_gaps is not None:
        routing_gaps.append(smallest)
    return head(x, params["norm"]["weight"], params["lm_head"]["kernel"],
                eps=float(sizes["rms_norm_eps"]))
