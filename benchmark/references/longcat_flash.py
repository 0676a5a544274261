"""Plain reference for the language model of LongCat-Flash-Omni (the public
``config.json``, huggingface.co/meituan-longcat/LongCat-Flash-Omni; what the
configuration has no key for, marked + below, is the family's public modelling
code AS REMEMBERED — no network here — and the configuration file lists it
under ``assumed.modelling_code``), as ONE CHIP'S SHARE of a layer that several
chips share (``deployment_share``). ``n`` is an RMS norm (``rms_norm_eps``)
with a gain of its own at each use:

    x = E[ids]
    each layer l, halves i = 0, 1:
      x1 = x  + A0(n(x))
      h  = n(x1)
      m  = MoE(h)                 (+) computed here, added at the END
      x2 = x1 + F0(h)             F_i: swiglu, ffn_hidden_size wide
      x3 = x2 + A1(n(x2))
      y  = x3 + F1(n(x3)) + m
    A(u), positions p = 0..S-1:
      c_q = n(u W_qa);  q = c_q W_qb x (hidden / q_lora_rank)^1/2          (mla_scale_q_lora)
            -> [heads, nope | rope];  q_rope = rope(q_rope, p)
      [c | k_r] = u W_kva;  c_kv = n(c) x (hidden / kv_lora_rank)^1/2      (mla_scale_kv_lora)
      k_r = rope(k_r, p)          the shared key is NOT scaled             (+)
      [k_nope | v] = c_kv W_kvb -> [heads, nope | v]       (EXPANDED: no absorption)
      rope: INTERLEAVED pairs (2i, 2i+1), theta = rope_theta, no scaling   (+)
      a = softmax over s <= t of (q_nope k_nope + q_rope k_r) x (nope + rope)^-1/2
      A = concat(a v) W_o
    MoE(h):
      p = softmax(h W_r) in float32 over n_routed_experts + zero_expert_num outputs
      sel = the moe_topk largest of p + e_score_correction_bias            (+)
      w = routed_scaling_factor x p[sel]          NOT renormalised         (+)
      m = sum over the sel HELD HERE of w swiglu_e(h)   (expert_ffn_hidden_size)
        + h x sum of w over the sel >= n_routed_experts  (zero_expert_type identity)
    logits = n(x) W_head                           (the slice of the vocabulary)

The routed sum is over the experts this chip holds (the banks' leading
dimension; the first is ``deployment_share.expert_rank x experts_held``): what
the other chips' experts would add is left out, as the served layer leaves it
out. The identity experts are no chip's to hold: they are HERE for every token,
whole, whatever the share.

``routing_gaps``: per position the smallest, over the layers, of the gap
between the last output chosen and the first left out (of ``p + bias``, brought
back to router-logit units by the softmax's slope ``p (1 - p)`` at the two),
counted only where the toss-up would move something HERE: one of the two is an
expert held here or an identity expert — the one difference from
``references/nemotron_h.py:routing``, whose every expert has a bank: an
identity expert is here for every token, so a toss-up that moves one in or out
makes the row loose just as one that moves a held expert does. Where BOTH are
identity experts the swap moves ``h`` times the difference of two weights that
are equal at a toss-up: nothing, and it does not count.

Float32, "highest" precision, no kernels, no cache, no absorption, no batching,
one sequence. One jitted call a layer part; one matrix upcast at a time (the
served bf16 tree stays resident); attention in blocks of ``QUERY_BLOCK``
queries against every key; the held experts one at a time over every token. It
reads the parameter tree by its names only.
"""

import functools

import jax
import jax.numpy as jnp

from .deepseek_v32 import rope_interleaved
from .mistral import _f32, embed, head, rms_norm, swiglu

QUERY_BLOCK = 256


def latent_attention(u, p, angles, *, n_heads, nope, rope, rank, v_dim, q_scale, kv_scale, eps):
    """Causal latent attention of one sequence, K and V of every head made
    from the latent (nothing absorbed)."""
    s = u.shape[0]
    c_q = rms_norm(u @ _f32(p["q_a_proj"]["kernel"]), p["q_a_layernorm"]["weight"], eps)
    q = ((c_q @ _f32(p["q_b_proj"]["kernel"])) * q_scale).reshape(s, n_heads, nope + rope)
    q_n, q_r = q[..., :nope], rope_interleaved(q[..., nope:], angles)
    kv = u @ _f32(p["kv_a_proj_with_mqa"]["kernel"])
    c_kv = rms_norm(kv[:, :rank], p["kv_a_layernorm"]["weight"], eps) * kv_scale
    k_r = rope_interleaved(kv[:, rank:], angles)
    kv_b = (c_kv @ _f32(p["kv_b_proj"]["kernel"])).reshape(s, n_heads, nope + v_dim)
    k_n, v = kv_b[..., :nope], kv_b[..., nope:]
    block = min(QUERY_BLOCK, s)
    n_blocks = -(-s // block)
    pad = ((0, n_blocks * block - s), (0, 0), (0, 0))
    q_n, q_r = jnp.pad(q_n, pad), jnp.pad(q_r, pad)
    kpos = jnp.arange(s)

    def one_block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_n, start, block)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, block)
        qpos = start + jnp.arange(block)
        scores = (jnp.einsum("qhd,thd->hqt", qn, k_n)
                  + jnp.einsum("qhr,tr->hqt", qr, k_r)) * (nope + rope)**-0.5
        scores = jnp.where((kpos[None, :] <= qpos[:, None])[None], scores, -jnp.inf)
        return jnp.einsum("hqt,thv->qhv", jax.nn.softmax(scores, axis=-1), v) \
            .reshape(block, n_heads * v_dim)

    out = jax.lax.map(one_block, jnp.arange(n_blocks) * block).reshape(-1, n_heads * v_dim)[:s]
    return out @ _f32(p["o_proj"]["kernel"])


def routing(h, gate, bias, *, top_k, scale, routed, first_held, held):
    """``(weights [S, held], identity weight [S], gap [S])``: each position's
    routing weight of each expert held here (0 where it did not choose it), the
    summed weight of the identity experts it chose, and its toss-up gap that
    matters here, in router-logit units (see the module)."""
    S = h.shape[0]
    p = jax.nn.softmax(h @ _f32(gate), axis=-1)  # over EVERY output, float32
    ranked, order = jax.lax.top_k(p + _f32(bias), top_k + 1)
    chosen = order[:, :top_k]
    w = jnp.take_along_axis(p, chosen, axis=1) * scale  # the bias picks, it does not weigh
    everywhere = jnp.zeros_like(p).at[jnp.arange(S)[:, None], chosen].set(w)

    def here(e):
        return (e >= first_held) & (e < first_held + held)

    def slope_at(e):  # of the softmax, to bring a gap in probabilities back to router logits
        v = jnp.take_along_axis(p, e[:, None], axis=1)[:, 0]
        return v * (1.0 - v)

    last, first_out = order[:, top_k - 1], order[:, top_k]
    gap = (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.maximum(
        0.5 * (slope_at(last) + slope_at(first_out)), 1e-30)
    moves = (here(last) | here(first_out) | (last >= routed) | (first_out >= routed)) \
        & ~((last >= routed) & (first_out >= routed))
    return (everywhere[:, first_held:first_held + held], everywhere[:, routed:].sum(axis=-1),
            jnp.where(moves, gap, jnp.inf))


def moe(h, mp, *, top_k, scale, zero, first_held):
    """``(the held experts' part + the identity experts' part, gap)`` of the
    routed branch over ``h`` [S, hidden]; the held experts one at a time."""
    bank = mp["experts"]
    held = bank["wi"].shape[0]
    weights, identity, gap = routing(h, mp["gate"], mp["e_score_correction_bias"], top_k=top_k,
                                     scale=scale, routed=mp["gate"].shape[1] - zero,
                                     first_held=first_held, held=held)

    def one_expert(e, m):
        gate, up = jnp.split(h @ _f32(bank["wi"][e]), 2, axis=-1)
        return m + ((jax.nn.silu(gate) * up) @ _f32(bank["wo"][e])) * weights[:, e][:, None]

    m = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(h))
    return m + h * identity[:, None], gap


@functools.partial(jax.jit, static_argnames=("half", "eps", "settings"))
def attention_part(x, p, angles, *, half, eps, settings):
    """``(x + A_half(n(x)), n of that)``: the half's stream and what its
    feed-forward (and, in the first half, the routed branch) reads."""
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p[f"input_layernorm_{half}"]["weight"], eps)
        x = x + latent_attention(u, p[f"self_attn_{half}"], angles, eps=eps, **dict(settings))
        return x, rms_norm(x, p[f"post_attention_layernorm_{half}"]["weight"], eps)


@functools.partial(jax.jit, static_argnames=("settings", ))
def routed_part(h, mp, *, settings):
    with jax.default_matmul_precision("highest"):
        return moe(h, mp, **dict(settings))


@jax.jit
def dense_part(x, h, mp):
    with jax.default_matmul_precision("highest"):
        return x + swiglu(h, mp)


def _refuse(sizes):
    if sizes.get("attention_method", "MLA") != "MLA" or sizes.get("attention_bias"):
        raise ValueError("another attention than MLA / an attention bias is not in this reference")
    if sizes.get("zero_expert_num") and sizes.get("zero_expert_type", "identity") != "identity":
        raise ValueError(f"zero_expert_type {sizes['zero_expert_type']!r} is not in this reference")


def layer_settings(sizes):
    """What each part of a layer reads of the configuration, hashable."""
    share = sizes.get("deployment_share") or {}
    M = sizes["hidden_size"]
    return {
        "mla": (("n_heads", sizes["num_attention_heads"]), ("nope", sizes["qk_nope_head_dim"]),
                ("rope", sizes["qk_rope_head_dim"]), ("rank", sizes["kv_lora_rank"]),
                ("v_dim", sizes["v_head_dim"]),
                ("q_scale", (M / sizes["q_lora_rank"])**0.5
                 if sizes.get("mla_scale_q_lora", True) else 1.0),
                ("kv_scale", (M / sizes["kv_lora_rank"])**0.5
                 if sizes.get("mla_scale_kv_lora", True) else 1.0)),
        "moe": (("top_k", sizes["moe_topk"]), ("scale", float(sizes["routed_scaling_factor"])),
                ("zero", int(sizes.get("zero_expert_num", 0))),
                ("first_held", int(share.get("expert_rank", 0)) * int(sizes["n_routed_experts"]))),
    }


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None):
    """Float32 logits of one sequence; ``rows`` picks positions (default all).
    A list passed as ``routing_gaps`` receives one entry: per picked position,
    the smallest routing gap over the layers (:func:`routing`)."""
    _refuse(sizes)
    eps, settings = float(sizes["rms_norm_eps"]), layer_settings(sizes)
    ids = jnp.asarray(ids, jnp.int32)
    rope = int(sizes["qk_rope_head_dim"])
    inv_freq = float(sizes["rope_theta"])**(-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angles = jnp.arange(ids.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    x = embed(params["embed_tokens"]["embedding"], ids)
    gaps = []
    for i in range(sizes["num_layers"]):
        p = params[f"layers_{i}"]
        x, h = attention_part(x, p, angles, half=0, eps=eps, settings=settings["mla"])
        m, gap = routed_part(h, p["mlp"], settings=settings["moe"])
        x = dense_part(x, h, p["mlps_0"])
        x, h = attention_part(x, p, angles, half=1, eps=eps, settings=settings["mla"])
        x = dense_part(x, h, p["mlps_1"]) + m
        gaps.append(gap)
    smallest = jnp.min(jnp.stack(gaps), axis=0)
    if rows is not None:
        x, smallest = x[jnp.asarray(rows)], smallest[jnp.asarray(rows)]
    if routing_gaps is not None:
        routing_gaps.append(smallest)
    return head(x, params["norm"]["weight"], params["lm_head"]["kernel"], eps=eps)
