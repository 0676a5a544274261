"""Plain reference for Trinity-Mini (the public ``config.json``,
huggingface.co/arcee-ai/Trinity-Mini, ``model_type`` ``afmoe``; what the
configuration has no key for, marked + below, is the family's public modelling
code, ``transformers`` ``models/afmoe``, and the configuration file lists it
under ``assumed``):

    x = E[ids] * sqrt(hidden)                                    (mup_enabled)
    each layer l of type t = layer_types[l]:
      h = rms(x; input_layernorm)
      q = rms_head(h Wq; q_norm)   k = rms_head(h Wk; k_norm)   v = h Wv       (+)
      sliding_attention: q, k = rope(q, k; rope_theta, default)
      full_attention:    q, k as they are: no position encoding              (+)
      a = softmax(q k^T / sqrt(head_dim), causal, the last sliding_window keys
                  on a sliding layer) v                        (GQA 32 : 4)
      a = a * sigmoid(h Wg)                                                  (+)
      x = x + rms(a Wo; post_attention_layernorm)                            (+)
      h2 = rms(x; pre_mlp_layernorm)
      l < num_dense_layers:  m = swiglu(h2; mlp)               (intermediate_size)
      else: s = sigmoid(h2 Wr) in float32; sel = the num_experts_per_tok largest
            of s + expert_bias; w = s[sel] / (sum s[sel] + 1e-20) * route_scale
            m = sum_k w_k swiglu(h2; expert sel_k) + swiglu(h2; shared_experts)
      x = x + rms(m; post_mlp_layernorm)                                     (+)
    logits = rms(x; norm) W_head

``n_group`` = ``topk_group`` = 1: the grouped top-k is the identity.

Same form as ``references/mellum.py``: float32, "highest" precision, no
kernels, no cache, no batching, one sequence (its norm, rotary table, SwiGLU and
head are ``references/mistral.py``'s); one jitted call a layer part;
attention in blocks of queries; the experts one at a time over every token
(``fori_loop``: one expert of the bank is in float32 at a time) weighted by the
routing weight, 0 where the token did not choose the expert. Independent of the
code under test: it reads the parameter tree by its names only.
"""

import functools
import math

import jax
import jax.numpy as jnp

from .mistral import _f32, head, rms_norm, rotary, swiglu

QUERY_BLOCK = 512
FULL, SLIDING = "full_attention", "sliding_attention"


def attention(h, p, *, n_heads, n_kv_heads, head_dim, theta, rotate, window, eps):
    """Gated causal grouped-query attention of one sequence h: [S, hidden],
    before ``o_proj``'s norm. The queries are walked in blocks; a block under a
    window is given only the ``window + block`` keys that end where it ends."""
    s = h.shape[0]
    pos = jnp.arange(s)
    q = (h @ _f32(p["q_proj"]["kernel"])).reshape(s, n_heads, head_dim)
    k = (h @ _f32(p["k_proj"]["kernel"])).reshape(s, n_kv_heads, head_dim)
    v = (h @ _f32(p["v_proj"]["kernel"])).reshape(s, n_kv_heads, head_dim)
    q, k = rms_norm(q, p["q_norm"]["weight"], eps), rms_norm(k, p["k_norm"]["weight"], eps)
    if rotate:
        q, k = rotary(q, pos, theta), rotary(k, pos, theta)
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
    out = out[:s] * jax.nn.sigmoid(h @ _f32(p["gate_proj"]["kernel"]))
    return out @ _f32(p["o_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim", "theta",
                                             "rotate", "window", "eps"))
def attention_part(x, p, *, eps, **settings):
    with jax.default_matmul_precision("highest"):
        a = attention(rms_norm(x, p["input_layernorm"]["weight"], eps), p["self_attn"], eps=eps,
                      **settings)
        return x + rms_norm(a, p["post_attention_layernorm"]["weight"], eps)


@functools.partial(jax.jit, static_argnames=("eps", ))
def dense_part(x, p, *, eps):
    with jax.default_matmul_precision("highest"):
        m = swiglu(rms_norm(x, p["pre_mlp_layernorm"]["weight"], eps), p["mlp"])
        return x + rms_norm(m, p["post_mlp_layernorm"]["weight"], eps)


def routing(h, gate, bias, *, top_k, score_func, route_norm, route_scale):
    """Routing weights [S, E] (0 where the token did not choose the expert) and
    each token's routing gap: how far the last expert chosen is ahead of the
    first one left out, in ROUTER-LOGIT units (the harness's toss-up rule,
    ``benchmark/check.py``). The choice is by score + bias; a small move d of
    both experts' logits moves that difference by at most d x (the two scores'
    slopes, added), so the gap in logit units is the difference over the mean
    slope: s (1 - s) for sigmoid, p (1 - p) ~ p for a softmax over many experts
    (where, without a bias, this is the first order of ``references/
    mixtral.py``'s difference of the logs)."""
    logits = h @ _f32(gate)
    scores = jax.nn.sigmoid(logits) if score_func == "sigmoid" else jax.nn.softmax(logits, -1)
    keys = scores + (0.0 if bias is None else _f32(bias))
    ranked, chosen = jax.lax.top_k(keys, top_k + 1)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)  # the scores, not the biased keys
    last, first_out = picked[:, top_k - 1], picked[:, top_k]
    if score_func == "sigmoid":
        slope = 0.5 * (last * (1.0 - last) + first_out * (1.0 - first_out))
    else:
        slope = 0.5 * (last + first_out)
    gap = (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.maximum(slope, 1e-30)
    top_w, top_e = picked[:, :top_k], chosen[:, :top_k]
    if route_norm:
        top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * route_scale
    weights = jnp.zeros_like(scores).at[jnp.arange(scores.shape[0])[:, None], top_e].set(top_w)
    return weights, gap


@functools.partial(jax.jit, static_argnames=("top_k", "score_func", "route_norm", "route_scale",
                                             "eps"))
def sparse_part(x, p, *, top_k, score_func, route_norm, route_scale, eps):
    """``(x + rms(routed + shared), gap)``. The routed experts run one at a time
    over every token: ``fori_loop`` slices one expert's two banks out of the
    served (bf16) tree and casts that slice alone."""
    moe = p["block_sparse_moe"]
    bank = moe["ExpertFFN_0"]
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, p["pre_mlp_layernorm"]["weight"], eps)
        weights, gap = routing(h, moe["gate"], moe.get("expert_bias"), top_k=top_k,
                               score_func=score_func, route_norm=route_norm,
                               route_scale=route_scale)

        def one_expert(e, m):
            gate, up = jnp.split(h @ _f32(bank["wi"][e]), 2, axis=-1)
            out = (jax.nn.silu(gate) * up) @ _f32(bank["wo"][e])
            return m + out * weights[:, e][:, None]

        m = jax.lax.fori_loop(0, bank["wi"].shape[0], one_expert, jnp.zeros_like(x))
        if "shared_experts" in moe:
            m = m + swiglu(h, moe["shared_experts"])
        return x + rms_norm(m, p["post_mlp_layernorm"]["weight"], eps), gap


@functools.partial(jax.jit, static_argnames=("scale", ))
def embed(table, ids, *, scale):
    return _f32(table[ids]) * scale


def _refuse(sizes):
    if {sizes.get(k, 1) for k in ("n_group", "topk_group", "num_expert_groups",
                                   "num_limited_groups")} != {1}:
        raise ValueError("grouped top-k over expert groups is not in this reference")
    if sizes.get("rope_scaling") or sizes.get("tie_word_embeddings"):
        raise ValueError("rope_scaling / tied embeddings are not in this reference")
    if sizes.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {sizes['hidden_act']!r} is not in this reference")


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None):
    """Float32 logits of one sequence; ``rows`` picks positions (default all).
    A list passed as ``routing_gaps`` receives one entry: per picked position,
    the smallest routing gap over the expert layers (see ``routing``)."""
    _refuse(sizes)
    eps, n = float(sizes["rms_norm_eps"]), sizes["num_hidden_layers"]
    scale = math.sqrt(sizes["hidden_size"]) if sizes.get("mup_enabled", True) else 1.0
    x = embed(params["embed_tokens"]["embedding"], jnp.asarray(ids, jnp.int32), scale=scale)
    gaps = []
    for i in range(n):
        kind = sizes["layer_types"][i]
        if kind not in (FULL, SLIDING):
            raise ValueError(f"layer type {kind!r} is not in this reference")
        p = params[f"layers_{i}"]
        x = attention_part(x, p, n_heads=sizes["num_attention_heads"],
                           n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
                           theta=float(sizes["rope_theta"]), rotate=kind == SLIDING,
                           window=int(sizes["sliding_window"]) if kind == SLIDING else 0, eps=eps)
        if i < sizes["num_dense_layers"]:
            x = dense_part(x, p, eps=eps)
        else:
            x, gap = sparse_part(x, p, top_k=sizes["num_experts_per_tok"],
                                 score_func=sizes.get("score_func", "sigmoid"),
                                 route_norm=bool(sizes.get("route_norm", True)),
                                 route_scale=float(sizes.get("route_scale", 1.0)), eps=eps)
            gaps.append(gap)
    smallest = jnp.min(jnp.stack(gaps), axis=0)
    if rows is not None:
        x, smallest = x[jnp.asarray(rows)], smallest[jnp.asarray(rows)]
    if routing_gaps is not None:
        routing_gaps.append(smallest)
    return head(x, params["norm"]["weight"], params["lm_head"]["kernel"], eps=eps)
