"""Plain reference for Solar-Open2-250B (the public ``config.json``,
huggingface.co/upstage/Solar-Open2-250B, ``model_type`` ``solar_open2``), as
ONE CHIP'S SHARE of a layer that several chips share (``deployment_share``).
What the configuration has no key for, marked + below, is the Kimi Linear
report (arXiv:2510.26692) and its public ``fla`` layer for the linear mixer,
and Solar Open's public GLM-4.5-style MoE code for the experts, as remembered
(no network here); the configuration file lists it under
``assumed.modelling_code``. No position encoding anywhere (``use_rope`` false).

    x = E[ids]
    each layer l:
      u = rms(x; input_layernorm)                                (rms_norm_eps)
      l in gqa_layers (softmax attention, 64 query / 8 K/V heads of 128):
        q, k, v = u Wq, u Wk, u Wv;  no rotary, no q/k norm                (+)
        a = softmax(q k^T / sqrt(128), causal) v                  (GQA 64 : 8)
        m = [a (.) sigmoid(u W_gate)] Wo      (use_gqa_gate; element-wise, +)
      every other layer (the gated delta rule, "KDA": H = 64 heads, d_k = d_v =
      128, linear_attn_config):
        q, k, v = silu(conv4(u Wq)), silu(conv4(u Wk)), silu(conv4(u Wv))
                  (causal, depthwise, short_conv_kernel_size = 4 taps, no bias,
                   rows before 0 are 0; num_kv_heads null: 64 heads each)  (+)
        a head:  q <- q / |q| x 128^-1/2;  k <- k / |k|   (|.| = sqrt(sum + 1e-6)) (+)
        g_t = -exp(A_log[h]) softplus(u W_fv W_f^ + dt_bias)  in R^128 a head
              (kda_use_full_proj false: 4096 -> 128 -> 8192);  alpha_t = exp(g_t)
        beta_t = 2 sigmoid(u w_b[h])             (kda_allow_neg_eigval: the 2)
        S~  = diag(alpha_t) S_{t-1}              S in R^{128 x 128} a head,
        S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T         float32, S_{-1} = 0,
        o_t = S_t^T q_t                                  TOKEN BY TOKEN
        m = [rms_head(o_t; o_norm) (.) sigmoid(u W_gv W_g^)] Wo   (rank 128, +)
      x = x + m
      u = rms(x; post_attention_layernorm)
      s = sigmoid(u W_r) in float32 over ALL routed experts; sel = the
          num_experts_per_tok largest of s + e_score_correction_bias;
          w = s[sel] / sum s[sel] x routed_scaling_factor  (norm_topk_prob)
      x = x + sum over the sel HELD HERE of w swiglu_e(u) + swiglu(u; shared)
    logits = rms(x; norm) W_head                  (the slice of the vocabulary)

``first_k_dense_replace`` = 0: every layer's feed-forward is the experts', and
``intermediate_size`` is unread. The routed sum is over the experts this chip
holds (the banks' leading dimension; the first is ``deployment_share.expert_rank
x experts_held``): what the other chips' experts would add is left out, as the
served layer leaves it out.

Float32, "highest" precision, no kernels, no cache, no batching, one sequence.
The delta rule is the recurrence as written, one ``lax.scan`` step a token:
independent of the chunked form under test. One jitted call a layer part;
attention in blocks of queries; the experts one at a time over every token. It
reads the parameter tree by its names only. ``variant`` is the controls' (and
tier-1's): the same layer with one piece of the mathematics left out, to show
that the comparison sees the piece.
"""

import functools

import jax
import jax.numpy as jnp

from .mistral import _f32, embed, head, rms_norm, swiglu
from .nemotron_h import routing

QUERY_BLOCK = 256
L2_EPS = 1e-6


def _conv_silu(x, w):
    """x [S, C] through a causal depthwise convolution w [C, K], then silu."""
    S, K = x.shape[0], w.shape[1]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[j:j + S] * w[None, :, j] for j in range(K)))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda(u, p, *, heads, head_dim, eps, beta_scale, variant=""):
    """The gated delta-rule mixer of one sequence u: [S, hidden], from zero
    state. ``variant``: ``no_decay`` (alpha = 1) or ``beta_one`` (beta without
    its factor) leave a piece out."""
    S = u.shape[0]
    shape = (S, heads, head_dim)
    q, k, v = (_conv_silu(u @ _f32(p[f"{n}_proj"]["kernel"]),
                          _f32(p[f"{n}_conv1d"]["kernel"])).reshape(shape) for n in "qkv")
    q, k = _l2(q) * head_dim**-0.5, _l2(k)
    f = (u @ _f32(p["f_a_proj"]["kernel"])) @ _f32(p["f_b_proj"]["kernel"])
    g = -jnp.exp(_f32(p["A_log"]))[None, :, None] \
        * jax.nn.softplus(f + _f32(p["dt_bias"])[None, :]).reshape(shape)
    alpha = jnp.ones_like(g) if variant == "no_decay" else jnp.exp(g)
    beta = (1.0 if variant == "beta_one" else beta_scale) \
        * jax.nn.sigmoid(u @ _f32(p["b_proj"]["kernel"]))  # [S, heads]

    def token(state, row):
        q_t, k_t, v_t, a_t, b_t = row
        state = state * a_t[:, :, None]
        held = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - held))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((heads, head_dim, head_dim), jnp.float32),
                        (q, k, v, alpha, beta))
    o = o * jax.lax.rsqrt(jnp.square(o).mean(axis=-1, keepdims=True) + eps) \
        * _f32(p["o_norm"]["weight"])[None, None, :]
    gate = jax.nn.sigmoid((u @ _f32(p["g_a_proj"]["kernel"])) @ _f32(p["g_b_proj"]["kernel"]))
    return (o.reshape(S, heads * head_dim) * gate) @ _f32(p["o_proj"]["kernel"])


def attention(u, p, *, n_heads, n_kv_heads, head_dim):
    """Causal grouped-query attention of one sequence, no position encoding,
    the heads' output gated element-wise from the layer's input."""
    s = u.shape[0]
    q = (u @ _f32(p["q_proj"]["kernel"])).reshape(s, n_heads, head_dim)
    k = (u @ _f32(p["k_proj"]["kernel"])).reshape(s, n_kv_heads, head_dim)
    v = (u @ _f32(p["v_proj"]["kernel"])).reshape(s, n_kv_heads, head_dim)
    group = n_heads // n_kv_heads
    block = min(QUERY_BLOCK, s)
    n_blocks = -(-s // block)
    q = jnp.pad(q, ((0, n_blocks * block - s), (0, 0), (0, 0)))
    kpos = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block).reshape(block, n_kv_heads, group,
                                                                   head_dim)
        qpos = start + jnp.arange(block)
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) / jnp.sqrt(jnp.float32(head_dim))
        scores = jnp.where((kpos[None, :] <= qpos[:, None])[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, v).reshape(block, n_heads * head_dim)

    out = jax.lax.map(one_block, jnp.arange(n_blocks) * block).reshape(-1, n_heads * head_dim)[:s]
    if "gate_proj" in p:
        out = out * jax.nn.sigmoid(u @ _f32(p["gate_proj"]["kernel"]))
    return out @ _f32(p["o_proj"]["kernel"])


def experts(u, moe, *, top_k, norm, scale, first_held):
    """``(held routed + shared, gap)``; the held SwiGLU experts one at a time."""
    bank = moe["experts"]
    held = bank["wi"].shape[0]
    weights, gap = routing(u, moe["gate"], moe["e_score_correction_bias"], top_k=top_k,
                           norm=norm, scale=scale, first_held=first_held, held=held)

    def one_expert(e, m):
        gate, up = jnp.split(u @ _f32(bank["wi"][e]), 2, axis=-1)
        return m + ((jax.nn.silu(gate) * up) @ _f32(bank["wo"][e])) * weights[:, e][:, None]

    m = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(u))
    if "shared_experts" in moe:
        m = m + swiglu(u, moe["shared_experts"])
    return m, gap


@functools.partial(jax.jit, static_argnames=("gqa", "eps", "settings", "variant"))
def mixer_part(x, p, *, gqa, eps, settings, variant=""):
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["input_layernorm"]["weight"], eps)
        if gqa:
            return x + attention(u, p["self_attn"], **dict(settings))
        return x + kda(u, p["linear_attn"], eps=eps, variant=variant, **dict(settings))


@functools.partial(jax.jit, static_argnames=("eps", "settings"))
def experts_part(x, p, *, eps, settings):
    with jax.default_matmul_precision("highest"):
        m, gap = experts(rms_norm(x, p["post_attention_layernorm"]["weight"], eps), p["mlp"],
                         **dict(settings))
        return x + m, gap


def _refuse(sizes):
    if sizes.get("use_rope") or sizes.get("tie_word_embeddings"):
        raise ValueError("rotary embeddings / tied embeddings are not in this reference")
    if sizes.get("kda_use_full_proj") or sizes.get("first_k_dense_replace"):
        raise ValueError("the full decay projection / leading dense layers are not in this "
                         "reference")
    linear = sizes["linear_attn_config"]
    if linear.get("num_kv_heads") not in (None, linear["num_heads"]):
        raise ValueError("fewer K/V heads than heads in the linear mixer are not in this reference")


def layer_settings(sizes):
    """What each part of a layer reads of the configuration, hashable."""
    share = sizes.get("deployment_share") or {}
    linear = sizes["linear_attn_config"]
    return {
        "gqa": (("n_heads", sizes["num_attention_heads"]),
                ("n_kv_heads", sizes["num_key_value_heads"]), ("head_dim", sizes["head_dim"])),
        "kda": (("heads", linear["num_heads"]), ("head_dim", linear["head_dim"]),
                ("beta_scale", 2.0 if sizes.get("kda_allow_neg_eigval", True) else 1.0)),
        "experts": (("top_k", sizes["num_experts_per_tok"]),
                    ("norm", bool(sizes.get("norm_topk_prob", True))),
                    ("scale", float(sizes.get("routed_scaling_factor", 1.0))),
                    ("first_held", share.get("expert_rank", 0) * share.get("experts_held", 0))),
    }


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None, variant=""):
    """Float32 logits of one sequence; ``rows`` picks positions (default all).
    A list passed as ``routing_gaps`` receives one entry: per picked position,
    the smallest routing gap over the layers (``references/nemotron_h.py:
    routing``)."""
    _refuse(sizes)
    eps, n = float(sizes["rms_norm_eps"]), sizes["num_hidden_layers"]
    settings = layer_settings(sizes)
    x = embed(params["embed_tokens"]["embedding"], jnp.asarray(ids, jnp.int32))
    gaps = []
    for i in range(n):
        gqa = i in sizes["gqa_layers"]
        x = mixer_part(x, params[f"layers_{i}"], gqa=gqa, eps=eps,
                       settings=settings["gqa" if gqa else "kda"], variant="" if gqa else variant)
        x, gap = experts_part(x, params[f"layers_{i}"], eps=eps, settings=settings["experts"])
        gaps.append(gap)
    smallest = jnp.min(jnp.stack(gaps), axis=0)
    if rows is not None:
        x, smallest = x[jnp.asarray(rows)], smallest[jnp.asarray(rows)]
    if routing_gaps is not None:
        routing_gaps.append(smallest)
    return head(x, params["norm"]["weight"], params["lm_head"]["kernel"], eps=eps)
