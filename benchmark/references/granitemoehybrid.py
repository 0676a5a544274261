"""Plain reference for Granite-4.0-H-Small (the public ``config.json``,
huggingface.co/ibm-granite/granite-4.0-h-small, ``model_type``
``granitemoehybrid``; what the configuration has no key for, marked + below, is
the family's public modelling code, ``transformers`` ``models/granitemoehybrid``
with Bamba's mixer, and the configuration file lists it under ``assumed``), as
ONE CHIP'S SHARE of a layer that several chips share (``deployment_share``):

    x = E[ids] x embedding_multiplier
    each layer l, of the kind k = layer_types[l]:
      h = rms(x; input_layernorm)                                (rms_norm_eps)
      mamba:     references/nemotron_h.py:mamba at this family's widths
                 (8192 | 8192 + 2 x 128 | 128; ONE group: the gated norm is
                 over all 8192), token by token, float32, from zero state (+)
      attention: q, k, v = h Wq, h Wk, h Wv; NO position encoding
                 m = softmax(q k^T x attention_multiplier, causal) v Wo
                 (GQA 32 : 8; the scale is the multiplier, NOT 1/sqrt(128))
      x = x + residual_multiplier x m
      f = rms(x; post_attention_layernorm)
      r = f W_r (float32, num_local_experts outputs); sel = the
      num_experts_per_tok largest of r; w = softmax(r[sel])               (+)
      m = sum over the sel HELD HERE of w (silu(f Wi[:, :F]) (f Wi[:, F:])) Wo
          + (silu(f Wg) (f Wu)) Wd                     (the shared expert, once)
      x = x + residual_multiplier x m
    logits = rms(x; norm) E^T / logits_scaling                  (the tied head)

The routed sum is over the experts this chip holds (the banks' leading
dimension; the first is ``deployment_share.expert_rank x experts_held``): what
the other chips' experts would add is left out, as the served layer leaves it
out; the softmax runs over all the chosen, held here or not.

Float32, "highest" precision, no kernels, no cache, no batching, one sequence.
One jitted call a half-layer; attention in blocks of queries; the experts one at
a time over every token. It reads the parameter tree by its names only.
"""

import functools

import jax
import jax.numpy as jnp

from .mistral import _f32, rms_norm, swiglu
from .nemotron_h import mamba

QUERY_BLOCK = 512


def attention(u, p, *, n_heads, n_kv_heads, head_dim, scale):
    """Causal grouped-query attention of one sequence, no position encoding,
    the scores times ``scale``."""
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
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) * scale
        scores = jnp.where((kpos[None, :] <= qpos[:, None])[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, v).reshape(block, n_heads * head_dim)

    out = jax.lax.map(one_block, jnp.arange(n_blocks) * block).reshape(-1, n_heads * head_dim)
    return out[:s] @ _f32(p["o_proj"]["kernel"])


def routing(f, gate, *, top_k, first_held, held):
    """``(weights [S, held], gap [S])``: each position's routing weight of each
    expert held here (0 where it did not choose it) and its toss-up gap that
    matters here, in router-logit units — the choice is of the logits
    themselves, so the gap is their difference (``references/nemotron_h.py``'s
    rule: a flip between the last expert chosen and the first left out counts
    only where one of the two is held here)."""
    S = f.shape[0]
    r = f @ _f32(gate)
    ranked, order = jax.lax.top_k(r, top_k + 1)
    w = jax.nn.softmax(ranked[:, :top_k], axis=-1)
    everywhere = jnp.zeros_like(r).at[jnp.arange(S)[:, None], order[:, :top_k]].set(w)

    def here(e):
        return (e >= first_held) & (e < first_held + held)

    gap = jnp.where(here(order[:, top_k - 1]) | here(order[:, top_k]),
                    ranked[:, top_k - 1] - ranked[:, top_k], jnp.inf)
    return everywhere[:, first_held:first_held + held], gap


def experts(f, mp, *, top_k, first_held):
    """``(held routed + shared, gap)``; the held experts one at a time."""
    bank = mp["experts"]
    held = bank["wi"].shape[0]
    weights, gap = routing(f, mp["gate"], top_k=top_k, first_held=first_held, held=held)

    def one_expert(e, m):
        gate, up = jnp.split(f @ _f32(bank["wi"][e]), 2, axis=-1)
        return m + ((jax.nn.silu(gate) * up) @ _f32(bank["wo"][e])) * weights[:, e][:, None]

    m = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(f))
    return m + swiglu(f, mp["shared_experts"]), gap


@functools.partial(jax.jit, static_argnames=("kind", "eps", "residual", "settings"))
def mixer_part(x, p, *, kind, eps, residual, settings):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, p["input_layernorm"]["weight"], eps)
        if kind == "mamba":
            m = mamba(h, p["mamba"], eps=eps, **dict(settings))
        else:
            m = attention(h, p["self_attn"], **dict(settings))
        return x + residual * m


@functools.partial(jax.jit, static_argnames=("eps", "residual", "settings"))
def experts_part(x, p, *, eps, residual, settings):
    with jax.default_matmul_precision("highest"):
        m, gap = experts(rms_norm(x, p["post_attention_layernorm"]["weight"], eps), p["mlp"],
                         **dict(settings))
        return x + residual * m, gap


@functools.partial(jax.jit, static_argnames=("scale", ))
def embed(table, ids, *, scale):
    return _f32(table[ids]) * scale


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def tied_head(x, norm_weight, table, *, eps, scaling):
    """``rms(x) E^T / scaling``: the head is the embedding."""
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("sm,vm->sv", rms_norm(x, norm_weight, eps), _f32(table)) / scaling


def _refuse(sizes):
    if sizes.get("position_embedding_type", "nope") != "nope":
        raise ValueError("a position encoding is not in this reference")
    if not sizes.get("tie_word_embeddings", True):
        raise ValueError("an untied head is not in this reference")
    if sizes.get("attention_bias") or sizes.get("mamba_proj_bias"):
        raise ValueError("projection biases are not in this reference")
    if sizes.get("hidden_act", "silu") != "silu" \
            or sizes.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise ValueError("another activation than silu / another norm than rmsnorm is not in "
                         "this reference")


def layer_settings(sizes):
    """What each part of a layer reads of the configuration, hashable."""
    share = sizes.get("deployment_share") or {}
    n_heads = sizes["num_attention_heads"]
    return {
        "mamba": (("heads", sizes["mamba_n_heads"]), ("head_dim", sizes["mamba_d_head"]),
                  ("groups", sizes["mamba_n_groups"]), ("state", sizes["mamba_d_state"])),
        "attention": (("n_heads", n_heads), ("n_kv_heads", sizes["num_key_value_heads"]),
                      ("head_dim", sizes["hidden_size"] // n_heads),
                      ("scale", float(sizes["attention_multiplier"]))),
        "experts": (("top_k", sizes["num_experts_per_tok"]),
                    ("first_held", share.get("expert_rank", 0) * share.get("experts_held", 0))),
    }


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None):
    """Float32 logits of one sequence; ``rows`` picks positions (default all).
    A list passed as ``routing_gaps`` receives one entry: per picked position,
    the smallest routing gap over the layers (:func:`routing`)."""
    _refuse(sizes)
    eps, residual = float(sizes["rms_norm_eps"]), float(sizes["residual_multiplier"])
    settings = layer_settings(sizes)
    table = params["embed_tokens"]["embedding"]
    x = embed(table, jnp.asarray(ids, jnp.int32), scale=float(sizes["embedding_multiplier"]))
    gaps = []
    for i, kind in enumerate(sizes["layer_types"][:sizes["num_hidden_layers"]]):
        p = params[f"layers_{i}"]
        x = mixer_part(x, p, kind=kind, eps=eps, residual=residual, settings=settings[kind])
        x, gap = experts_part(x, p, eps=eps, residual=residual, settings=settings["experts"])
        gaps.append(gap)
    smallest = jnp.min(jnp.stack(gaps), axis=0)
    if rows is not None:
        x, smallest = x[jnp.asarray(rows)], smallest[jnp.asarray(rows)]
    if routing_gaps is not None:
        routing_gaps.append(smallest)
    return tied_head(x, params["norm"]["weight"], table, eps=eps,
                     scaling=float(sizes["logits_scaling"]))
