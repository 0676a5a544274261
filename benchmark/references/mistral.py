"""Plain reference for Mistral-7B-v0.1 (arXiv:2310.06825; the public
``config.json`` and ``modeling_mistral.py``): pre-norm decoder, RMSNorm,
grouped-query attention with rotary embeddings (split-half convention) and a
causal sliding-window mask, SwiGLU feed-forward, untied output head.

Straight ``jax.numpy`` in float32 at "highest" matmul precision: no kernels, no
cache, no batching, one sequence at a time. Independent of the code under
test: it only reads the parameter tree by its published names (``q_proj`` ...
``down_proj``; kernels stored [in, out]).

Departures from a one-shot forward, neither of which changes the mathematics:
each layer is one jitted call that casts that layer's weights to float32 (the
whole model in float32 beside the served copy does not fit a 16 GB chip), and
attention walks the queries in blocks so the score matrix stays small.
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(weight)


def rotary(x, positions, theta):
    """x: [S, H, D]; rotates the pairs (x[i], x[i + D/2])."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta**(jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, p, *, n_heads, n_kv_heads, head_dim, theta, window):
    """Causal grouped-query attention of one sequence x: [S, hidden]."""
    s = x.shape[0]
    pos = jnp.arange(s)
    q = (x @ _f32(p["q_proj"]["kernel"])).reshape(s, n_heads, head_dim)
    k = (x @ _f32(p["k_proj"]["kernel"])).reshape(s, n_kv_heads, head_dim)
    v = (x @ _f32(p["v_proj"]["kernel"])).reshape(s, n_kv_heads, head_dim)
    q, k = rotary(q, pos, theta), rotary(k, pos, theta)
    group = n_heads // n_kv_heads
    outs = []
    for start in range(0, s, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK].reshape(-1, n_kv_heads, group, head_dim)
        qpos = pos[start:start + QUERY_BLOCK]
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) / jnp.sqrt(jnp.float32(head_dim))
        visible = pos[None, :] <= qpos[:, None]
        if window:
            visible &= pos[None, :] > qpos[:, None] - window
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", probs, v).reshape(-1, n_heads * head_dim))
    return jnp.concatenate(outs, axis=0) @ _f32(p["o_proj"]["kernel"])


def swiglu(x, p):
    gate = x @ _f32(p["gate_proj"]["kernel"])
    up = x @ _f32(p["up_proj"]["kernel"])
    return (jax.nn.silu(gate) * up) @ _f32(p["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim", "theta",
                                             "window", "eps"))
def layer(x, p, *, n_heads, n_kv_heads, head_dim, theta, window, eps):
    with jax.default_matmul_precision("highest"):
        x = x + attention(rms_norm(x, p["input_layernorm"]["weight"], eps), p["self_attn"],
                          n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
                          theta=theta, window=window)
        return x + swiglu(rms_norm(x, p["post_attention_layernorm"]["weight"], eps), p["mlp"])


@jax.jit
def embed(table, ids):
    return _f32(table)[ids]


@functools.partial(jax.jit, static_argnames=("eps", ))
def head(x, norm_weight, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, norm_weight, eps) @ _f32(lm_head)


def attention_sizes(sizes):
    """The attention settings of a configuration file, as the layer wants them."""
    n_heads = sizes["num_attention_heads"]
    return dict(n_heads=n_heads, n_kv_heads=sizes["num_key_value_heads"],
                head_dim=sizes.get("head_dim") or sizes["hidden_size"] // n_heads,
                theta=float(sizes["rope_theta"]), window=int(sizes.get("sliding_window") or 0),
                eps=float(sizes["rms_norm_eps"]))


def forward_hidden(params, sizes, ids, layer_fn=layer):
    """Final hidden states [S, hidden] of one sequence of token ids."""
    tree = params["model"] if "model" in params else params
    x = embed(tree["embed_tokens"]["embedding"], jnp.asarray(ids, jnp.int32))
    kw = attention_sizes(sizes)
    for i in range(sizes["num_hidden_layers"]):
        x = layer_fn(x, tree[f"layers_{i}"], **kw)
    return x, tree


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None):
    """Float32 logits of one sequence; ``rows`` picks positions (default all).
    ``routing_gaps`` is the sparse models' and stays untouched: a dense model
    routes nothing."""
    x, tree = forward_hidden(params, sizes, ids)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(x, tree["norm"]["weight"], tree["lm_head"]["kernel"],
                eps=float(sizes["rms_norm_eps"]))


def next_token_loss(params, sizes, ids, labels, block=1024):
    """Mean cross-entropy of ``labels`` [S] given ``ids`` [S], over positions
    whose label is not -100. The head is applied in blocks of positions."""
    x, tree = forward_hidden(params, sizes, ids)
    labels = jnp.asarray(labels, jnp.int32)
    total, count = 0.0, 0
    for start in range(0, x.shape[0], block):
        logits = head(x[start:start + block], tree["norm"]["weight"], tree["lm_head"]["kernel"],
                      eps=float(sizes["rms_norm_eps"]))
        lab = labels[start:start + block]
        valid = lab != -100
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, jnp.where(valid, lab, 0)[:, None], axis=-1)[:, 0]
        total += float(-(picked * valid).sum())
        count += int(valid.sum())
    return total / max(count, 1)
