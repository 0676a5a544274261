"""Plain reference for Kimi-Linear-48B-A3B-Instruct (the public ``config.json``,
huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct, ``model_type``
``kimi_linear``), as ONE CHIP'S SHARE of a layer that several chips share
(``deployment_share``). What the configuration has no key for, marked + below,
is the Kimi Linear report (arXiv:2510.26692) and its public ``fla`` layer, as
remembered (no network here); the configuration file lists it under
``assumed.modelling_code``. No position encoding in either mixer
(``mla_use_nope``); layers are named by their PUBLISHED index, the first is 1.

    x = E[ids]
    each layer l = 1 ..:
      u = rms(x; input_layernorm)                               (rms_norm_eps 1e-5)
      l in linear_attn_config.kda_layers (the gated delta rule, "KDA": H = 32
      heads, d_k = d_v = 128): ``references/solar_open2.py``'s mixer word for
      word (the same report's layer: q, k, v = silu(conv4(u W)), q and k
      L2-normed, q x 128^-1/2, the decay a channel through a rank-128 pair, a
      float32 state S in R^{128 x 128} a head updated TOKEN BY TOKEN, a gated
      per-head RMS norm before o_proj), with
        beta_t = sigmoid(u w_b[h])          (NO factor 2: the configuration has
                                             no kda_allow_neg_eigval)       (+)
      l in linear_attn_config.full_attn_layers (latent attention, 32 heads):
        q = u W_q -> 32 x (128 + 64) = [q_n | q_r]          (q_lora_rank null)
        [c | k_r] = u W_kv_a -> 512 + 64;  c <- rms(c; kv_a_layernorm)      (+)
        [k_n | v] = c W_kv_b -> 32 x (128 + 128)
        head i, key s <= t:  logit = (q_n,i(t) . k_n,i(s) + q_r,i(t) . k_r(s))
                                     x 192^-1/2
                             NO rotation of q_r or k_r (mla_use_nope; rope_theta
                             and head_dim 72 are carried and unread)
        m = W_o concat_i softmax(logit) v_i             UN-absorbed: K and V of
                                                        every head are made here
      x = x + m
      u = rms(x; post_attention_layernorm)
      l <= first_k_dense_replace (1):  x = x + swiglu(u; 9216)
      else: s = sigmoid(u W_g) in float32 over ALL routed experts; sel = the
          num_experts_per_token largest of s + e_score_correction_bias
          (num_expert_group 1, topk_group 1: no group limit);
          w = s[sel] / sum s[sel] x routed_scaling_factor      (moe_renormalize)
        x = x + sum over the sel HELD HERE of w swiglu_e(u) + swiglu(u; shared)
    logits = rms(x; norm) W_head                  (the slice of the vocabulary)

The routed sum is over the experts this chip holds (the banks' leading
dimension; the first is ``deployment_share.expert_rank x experts_held``): what
the other chips' experts would add is left out, as the served layer leaves it
out.

Float32, "highest" precision, no kernels, no cache, no batching, one sequence.
The delta rule is the recurrence as written, one ``lax.scan`` step a token;
attention in blocks of ``QUERY_BLOCK`` queries against every key (18k tokens:
32 x 256 x 18k float32 logits a block); the experts one at a time over every
token. One jitted call a layer part. It reads the parameter tree by its names
only.
"""

import functools

import jax
import jax.numpy as jnp

from .mistral import _f32, embed, head, rms_norm, swiglu
from .solar_open2 import experts, kda

QUERY_BLOCK = 256


def latent_attention(u, p, *, n_heads, nope, rope, rank, v_dim, eps):
    """Causal latent attention of one sequence, no position encoding, K and V
    of every head made from the latent (nothing absorbed)."""
    s = u.shape[0]
    q = (u @ _f32(p["q_proj"]["kernel"])).reshape(s, n_heads, nope + rope)
    kv = u @ _f32(p["kv_a_proj_with_mqa"]["kernel"])
    c, k_r = rms_norm(kv[:, :rank], p["kv_a_layernorm"]["weight"], eps), kv[:, rank:]
    kv_b = (c @ _f32(p["kv_b_proj"]["kernel"])).reshape(s, n_heads, nope + v_dim)
    k_n, v = kv_b[..., :nope], kv_b[..., nope:]
    block = min(QUERY_BLOCK, s)
    n_blocks = -(-s // block)
    q = jnp.pad(q, ((0, n_blocks * block - s), (0, 0), (0, 0)))
    kpos = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        qpos = start + jnp.arange(block)
        scores = (jnp.einsum("qhd,thd->hqt", qb[..., :nope], k_n)
                  + jnp.einsum("qhr,tr->hqt", qb[..., nope:], k_r)) * (nope + rope)**-0.5
        scores = jnp.where((kpos[None, :] <= qpos[:, None])[None], scores, -jnp.inf)
        return jnp.einsum("hqt,thv->qhv", jax.nn.softmax(scores, axis=-1), v) \
            .reshape(block, n_heads * v_dim)

    out = jax.lax.map(one_block, jnp.arange(n_blocks) * block).reshape(-1, n_heads * v_dim)[:s]
    return out @ _f32(p["o_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("linear", "eps", "settings"))
def mixer_part(x, p, *, linear, eps, settings):
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["input_layernorm"]["weight"], eps)
        if linear:
            return x + kda(u, p["linear_attn"], eps=eps, **dict(settings))
        return x + latent_attention(u, p["self_attn"], eps=eps, **dict(settings))


@functools.partial(jax.jit, static_argnames=("dense", "eps", "settings"))
def ffn_part(x, p, *, dense, eps, settings):
    """``(x + feed-forward, routing gap a row)``; a dense layer routes nothing
    and its gap is infinite."""
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        if dense:
            return x + swiglu(u, p["mlp"]), jnp.full((x.shape[0], ), jnp.inf)
        m, gap = experts(u, p["mlp"], **dict(settings))
        return x + m, gap


def _refuse(sizes):
    if not sizes.get("mla_use_nope", True) or sizes.get("rope_scaling") \
            or sizes.get("q_lora_rank") is not None:
        raise ValueError("rotary latent attention / a query bottleneck are not in this reference")
    if sizes.get("moe_router_activation_func", "sigmoid") != "sigmoid" \
            or sizes.get("num_expert_group", 1) != 1 or sizes.get("tie_word_embeddings"):
        raise ValueError("another router activation / a group limit / tied embeddings are not "
                         "in this reference")


def layer_settings(sizes):
    """What each part of a layer reads of the configuration, hashable."""
    share = sizes.get("deployment_share") or {}
    linear = sizes["linear_attn_config"]
    return {
        "kda": (("heads", linear["num_heads"]), ("head_dim", linear["head_dim"]),
                ("beta_scale", 1.0)),
        "mla": (("n_heads", sizes["num_attention_heads"]), ("nope", sizes["qk_nope_head_dim"]),
                ("rope", sizes["qk_rope_head_dim"]), ("rank", sizes["kv_lora_rank"]),
                ("v_dim", sizes["v_head_dim"])),
        "experts": (("top_k", sizes["num_experts_per_token"]),
                    ("norm", bool(sizes.get("moe_renormalize", True))),
                    ("scale", float(sizes.get("routed_scaling_factor", 1.0))),
                    ("first_held", share.get("expert_rank", 0) * share.get("experts_held", 0))),
    }


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None):
    """Float32 logits of one sequence; ``rows`` picks positions (default all).
    A list passed as ``routing_gaps`` receives one entry: per picked position,
    the smallest routing gap over the layers (``references/nemotron_h.py:
    routing``)."""
    _refuse(sizes)
    eps, n = float(sizes["rms_norm_eps"]), sizes["num_hidden_layers"]
    settings, linear = layer_settings(sizes), sizes["linear_attn_config"]
    x = embed(params["embed_tokens"]["embedding"], jnp.asarray(ids, jnp.int32))
    gaps = []
    for i in range(n):  # the published index is i + 1
        if (i + 1 in linear["kda_layers"]) == (i + 1 in linear["full_attn_layers"]):
            raise ValueError(f"layer {i + 1} is in both or neither of kda_layers and "
                             f"full_attn_layers")
        kda_layer = i + 1 in linear["kda_layers"]
        x = mixer_part(x, params[f"layers_{i}"], linear=kda_layer, eps=eps,
                       settings=settings["kda" if kda_layer else "mla"])
        x, gap = ffn_part(x, params[f"layers_{i}"], dense=i < sizes["first_k_dense_replace"],
                          eps=eps, settings=settings["experts"])
        gaps.append(gap)
    smallest = jnp.min(jnp.stack(gaps), axis=0)
    if rows is not None:
        x, smallest = x[jnp.asarray(rows)], smallest[jnp.asarray(rows)]
    if routing_gaps is not None:
        routing_gaps.append(smallest)
    return head(x, params["norm"]["weight"], params["lm_head"]["kernel"], eps=eps)
