"""Plain reference for DeepSeek-V3.2 (the public ``config.json``,
huggingface.co/deepseek-ai/DeepSeek-V3.2, ``model_type`` ``deepseek_v32``; what
the configuration has no key for, marked + below, is the family's public
reference code, ``inference/model.py`` of the model's repository, and the
configuration file lists it under ``assumed``), for ONE CHIP'S SHARE of the
routed experts and a slice of the vocabulary:

    x = E[ids]
    each layer, positions p = 0..S-1:
      h   = rms(x; input_layernorm)
      c_q = rms(h Wq_a; q_norm)                                              (+)
      q   = c_q Wq_b -> [heads, nope | rope];  q_rope = rope(q_rope, p)
      [c_kv | k_rope] = h Wkv_a;  c_kv = rms(c_kv; kv_norm);  k_rope = rope(k_rope, p)
      [k_nope | v] = c_kv Wkv_b -> [heads, nope | v]      (EXPANDED: no absorption)
      rope = YaRN(factor, beta_fast, beta_slow, original_max_position_embeddings),
             cos / sin unscaled, INTERLEAVED pairs (2i, 2i+1)                (+)
      scale = (nope + rope)^-1/2 x (0.1 mscale_all_dim ln factor + 1)^2      (+)
      indexer:  q_I = c_q W^I_qb -> [index heads, index dim]
                k_I = layer_norm(h W^I_k; weight, bias)                      (+)
                rope on the first qk_rope_head_dim dims of both, HALF-SPLIT
                pairs (i, i + rope/2)                                        (+)
                w   = h W^I_w x index_heads^-1/2 x index_dim^-1/2            (+)
                I(t, s) = sum_j w[t, j] relu(q_I[t, j] . k_I[s]),  s <= t    (+)
                S_t = the index_topk keys of largest I(t, .) among s <= t
                      (an explicit mask; every s <= t while t + 1 <= index_topk)
      a   = softmax over s in S_t of (q_nope k_nope + q_rope k_rope) scale;  o = a v
      x   = x + concat(o) Wo
      h2  = rms(x; post_attention_layernorm)
      l < first_k_dense_replace:  x = x + swiglu(h2; mlp)
      else: s = sigmoid(h2 Wg) in float32 over ALL routed experts;  c = s + bias
            group score = sum of the top-2 of c in each of n_group groups    (+)
            keep the topk_group best groups;  E = top-k of c in the kept groups
            w_e = s_e / sum_E s x routed_scaling_factor    (over all of E)
            x = x + sum over e in E HELD HERE of w_e swiglu_e(h2) + swiglu(h2; shared)
    logits = rms(x; norm) W_head                      (the slice of the vocabulary)

Float32, "highest" precision, no kernel, no cache, no absorption, one sequence;
one jitted call a layer part; one matrix upcast at a time (the served bf16 tree
stays resident); attention and the indexer in blocks of queries; the held
experts one at a time over every token. Independent of the code under test: it
reads the parameter tree by its names only.

``routing_gaps``: per position the smallest, over the expert layers, of the
expert gap (k-th against (k+1)-th of c among the kept groups) and the group gap
(``topk_group``-th against the next group score), in router-logit units, each
counted only where the toss-up would move an expert HELD HERE in or out: the
k-th or (k+1)-th expert is held here; the held experts' group is among the
kept groups or the first one out.

The selection is a discontinuity too, of another kind. A query's index scores
lie ~2^-11 of their spread apart at the ``index_topk``-th place of a few
thousand keys, and the served hidden states reach the indexer with ~2^-8 of
rounding: in EVERY row a handful of keys within ``SELECTION_BAND`` of the
threshold sit on the other side of it in the system, both sides rightly, and
the gap between the two keys at the threshold says nothing about which rows
that moves. What says it is the WEIGHT attention gives a key of the band: with
random index weights nothing ties a key's index score to its attention score,
so one row in a few dozen has a band key that carries several percent of the
layer's output. ``selection_swing``: per position, the largest over the layers
and over the band's keys of |what moving that ONE key in or out of the
selection adds to the stream| / |the stream|: its softmax share (of the
selected keys' sum, would-be for a key outside) times its distance from the
heads' outputs, through ``wo``. A row whose swing is over ``SELECTION_SWING``
is returned in ``routing_gaps`` as a toss-up (gap 0): held to the loose
tolerance as a row at a routing toss-up is. ``selection_gaps``: the indexer's
own gap (``index_topk``-th against the next score), for the tests.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .mistral import _f32, head, rms_norm, swiglu

QUERY_BLOCK = 128
NEG = -1e30
# half-width of the band of keys round a query's threshold that the system may
# select otherwise, as a share of the spread (standard deviation over the keys it
# may see) of the query's index scores: ~3 x the 2^-8 of rounding the served
# hidden states carry into the indexer's q, k and w
SELECTION_BAND = 2.0**-6
# a band key that moves the stream by more than this share of it makes the row a
# toss-up. Read on the chip (PERF.md section 6, PR 40: error, gap and swing of
# 1,248 rows of 39 seeds): every row without a routing toss-up that read over
# 2^-6.3 of the largest logit had a swing over 2^-7, and the rows under it stay
# 0.46 bits inside the tight tolerance at 5 layers (check.logit_rel_tol, 2^-5.84);
# at 2^-8 the room is the same and two runs in 54 had no tight row left
SELECTION_SWING = 2.0**-7


def yarn_inv_freq(dim, theta, scaling):
    """float64 ``[dim / 2]``: the family's ``precompute_freqs_cis``."""
    freqs = theta**(-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return freqs
    factor, original = float(scaling["factor"]), float(scaling["original_max_position_embeddings"])

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    smooth = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return freqs / factor * (1.0 - smooth) + freqs * smooth


def rope_interleaved(x, angles):
    """x: [S, ..., D] rotated in the pairs (2i, 2i+1); angles: [S, D/2]."""
    shape = x.shape
    x = x.reshape(*shape[:-1], shape[-1] // 2, 2)
    extra = (1, ) * (x.ndim - 3)
    cos = jnp.cos(angles).reshape(shape[0], *extra, -1)
    sin = jnp.sin(angles).reshape(shape[0], *extra, -1)
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(shape)


def rope_half(x, angles):
    """x: [S, ..., D] rotated in the pairs (i, i + D/2)."""
    extra = (1, ) * (x.ndim - 2)
    cos = jnp.cos(angles).reshape(x.shape[0], *extra, -1)
    sin = jnp.sin(angles).reshape(x.shape[0], *extra, -1)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer_norm(x, p, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = jnp.square(x - mean).mean(axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(p["weight"]) + _f32(p["bias"])


def _shape(sizes):
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "kv_lora_rank", "index_n_heads", "index_head_dim", "index_topk")
    return tuple(int(sizes[k]) for k in keys)


def softmax_scale(sizes):
    scale = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"])**-0.5
    scaling = sizes.get("rope_scaling")
    if scaling and sizes["max_position_embeddings"] > scaling["original_max_position_embeddings"]:
        m = 0.1 * float(scaling.get("mscale_all_dim", 0)) * math.log(scaling["factor"]) + 1.0
        scale *= m * m
    return scale


@functools.partial(jax.jit, static_argnames=("shape", "scale", "eps"))
def attention_part(x, p, angles, *, shape, scale, eps):
    """``(x + attention, selection gap [S], selection swing [S])`` of one
    sequence x: [S, hidden]."""
    H, N, R, V, C, NH, DI, topk = shape
    S = x.shape[0]
    ap, ip = p["self_attn"], p["self_attn"]["indexer"]
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, p["input_layernorm"]["weight"], eps)
        c_q = rms_norm(h @ _f32(ap["wq_a"]["kernel"]), ap["q_norm"]["weight"], eps)
        q = (c_q @ _f32(ap["wq_b"]["kernel"])).reshape(S, H, N + R)
        q = jnp.concatenate([q[..., :N], rope_interleaved(q[..., N:], angles)], axis=-1)
        kv = h @ _f32(ap["wkv_a"]["kernel"])
        c_kv = rms_norm(kv[:, :C], ap["kv_norm"]["weight"], eps)
        k_rope = rope_interleaved(kv[:, C:], angles)
        expanded = (c_kv @ _f32(ap["wkv_b"]["kernel"])).reshape(S, H, N + V)
        k = jnp.concatenate([expanded[..., :N],
                             jnp.broadcast_to(k_rope[:, None, :], (S, H, R))], axis=-1)
        v = expanded[..., N:]
        q_i = (c_q @ _f32(ip["wq_b"]["kernel"])).reshape(S, NH, DI)
        q_i = jnp.concatenate([rope_half(q_i[..., :R], angles), q_i[..., R:]], axis=-1)
        k_i = layer_norm(h @ _f32(ip["wk"]["kernel"]), ip["k_norm"], eps)
        k_i = jnp.concatenate([rope_half(k_i[:, :R], angles), k_i[:, R:]], axis=-1)
        w_i = (h @ _f32(ip["weights_proj"]["kernel"])) * (NH**-0.5 * DI**-0.5)

        block = min(QUERY_BLOCK, S)
        n_blocks = -(-S // block)
        pad = n_blocks * block - S

        def padded(a):
            return jnp.pad(a, ((0, pad), ) + ((0, 0), ) * (a.ndim - 1)) \
                .reshape(n_blocks, block, *a.shape[1:])

        key_pos = jnp.arange(S)[None, :]

        def one_block(args):
            q_b, qi_b, w_b, start = args
            pos = start + jnp.arange(block)[:, None]
            causal = key_pos <= pos  # [block, S]
            index = (jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi_b, k_i)) * w_b[:, :, None]).sum(1)
            index = jnp.where(causal, index, NEG)
            gap = jnp.full((block, ), jnp.inf)
            keep, band = causal, None
            if S > topk:
                best, chosen = jax.lax.top_k(index, topk + 1)
                selected = jnp.zeros((block, S), bool) \
                    .at[jnp.arange(block)[:, None], chosen[:, :topk]].set(True)
                keep = causal & selected
                selects = pos[:, 0] + 1 > topk
                gap = jnp.where(selects, best[:, topk - 1] - best[:, topk], jnp.inf)
                seen = causal.sum(axis=1)
                mean = jnp.where(causal, index, 0.0).sum(axis=1) / seen
                spread = jnp.sqrt(jnp.where(causal, jnp.square(index - mean[:, None]), 0.0)
                                  .sum(axis=1) / seen)
                threshold = 0.5 * (best[:, topk - 1] + best[:, topk])
                band = causal & selects[:, None] & \
                    (jnp.abs(index - threshold[:, None]) < SELECTION_BAND * spread[:, None])
            logits = jnp.einsum("thd,shd->ths", q_b, k) * scale
            kept = jnp.where(keep[:, None, :], logits, NEG)
            probs = jax.nn.softmax(kept, axis=-1)
            o = jnp.einsum("ths,shv->thv", probs, v)
            moved = jnp.zeros((block, ))
            if band is not None:
                # |share x (v_s - o)| over the heads, its cross term left out
                share = jnp.square(jnp.exp(jnp.where(band[:, None, :], logits, NEG)
                                           - jax.nn.logsumexp(kept, axis=-1)[..., None]))
                moved = jnp.einsum("ths,sh->ts", share, jnp.square(v).sum(-1)) + \
                    jnp.einsum("ths,th->ts", share, jnp.square(o).sum(-1))
                moved = jnp.sqrt(moved.max(axis=1) / jnp.square(o).sum((1, 2)))
            return o, gap, moved

        out, gap, moved = jax.lax.map(one_block, (padded(q), padded(q_i), padded(w_i),
                                                  jnp.arange(n_blocks) * block))
        out = out.reshape(n_blocks * block, H * V)[:S] @ _f32(ap["wo"]["kernel"])
        x = x + out
        swing = moved.reshape(-1)[:S] * jnp.linalg.norm(out, axis=-1) / jnp.linalg.norm(x, axis=-1)
        return x, gap.reshape(-1)[:S], swing


@functools.partial(jax.jit, static_argnames=("eps", ))
def dense_part(x, p, *, eps):
    with jax.default_matmul_precision("highest"):
        return x + swiglu(rms_norm(x, p["post_attention_layernorm"]["weight"], eps), p["mlp"])


def routing(h, gate, bias, *, top_k, n_group, topk_group, scale, first_held, held):
    """``(weights [S, held], gap [S])``: each position's routing weight of
    each expert held here (0 where it did not choose it), and its smallest
    toss-up gap that matters here, in router-logit units."""
    S = h.shape[0]
    s = jax.nn.sigmoid(h @ _f32(gate))  # [S, E], float32
    E = s.shape[1]
    c = s + _f32(bias)
    size = E // n_group
    group_score = jax.lax.top_k(c.reshape(S, n_group, size), 2)[0].sum(-1)  # [S, groups]
    ranked_groups, group_order = jax.lax.top_k(group_score, n_group)
    kept = jnp.zeros((S, n_group), bool) \
        .at[jnp.arange(S)[:, None], group_order[:, :topk_group]].set(True)
    masked = jnp.where(jnp.repeat(kept, size, axis=1), c, -jnp.inf)
    ranked, order = jax.lax.top_k(masked, top_k + 1)
    chosen = order[:, :top_k]
    picked = jnp.take_along_axis(s, chosen, axis=1)
    w = picked / picked.sum(axis=-1, keepdims=True) * scale
    everywhere = jnp.zeros_like(s).at[jnp.arange(S)[:, None], chosen].set(w)
    weights = everywhere[:, first_held:first_held + held]

    def here(e):
        return (e >= first_held) & (e < first_held + held)

    def slope_at(e):  # of the sigmoid, to bring a gap in scores back to router logits
        v = jnp.take_along_axis(s, e[:, None], axis=1)[:, 0]
        return v * (1.0 - v)

    last, first_out = order[:, top_k - 1], order[:, top_k]
    expert_gap = (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.maximum(
        0.5 * (slope_at(last) + slope_at(first_out)), 1e-30)
    expert_gap = jnp.where(here(last) | here(first_out), expert_gap, jnp.inf)
    gap = expert_gap
    if topk_group < n_group:
        # a group score is a sum of two scores: its slope is about twice one's
        ours = first_held // size
        ours_rank = jnp.argmax(group_order == ours, axis=1)
        group_gap = (ranked_groups[:, topk_group - 1] - ranked_groups[:, topk_group]) / \
            jnp.maximum(slope_at(last) + slope_at(first_out), 1e-30)
        gap = jnp.minimum(gap, jnp.where(ours_rank <= topk_group, group_gap, jnp.inf))
    return weights, gap


@functools.partial(jax.jit, static_argnames=("top_k", "n_group", "topk_group", "scale",
                                             "first_held", "eps"))
def sparse_part(x, p, *, top_k, n_group, topk_group, scale, first_held, eps):
    """``(x + held routed + shared, gap)``; the held experts one at a time."""
    moe = p["mlp"]
    bank = moe["experts"]
    held = bank["wi"].shape[0]
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        weights, gap = routing(h, moe["gate"], moe["e_score_correction_bias"], top_k=top_k,
                               n_group=n_group, topk_group=topk_group, scale=scale,
                               first_held=first_held, held=held)

        def one_expert(e, m):
            gate, up = jnp.split(h @ _f32(bank["wi"][e]), 2, axis=-1)
            out = (jax.nn.silu(gate) * up) @ _f32(bank["wo"][e])
            return m + out * weights[:, e][:, None]

        m = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(x))
        if "shared_experts" in moe:
            m = m + swiglu(h, moe["shared_experts"])
        return x + m, gap


@jax.jit
def embed(table, ids):
    return _f32(table[ids])


def _refuse(sizes):
    if sizes.get("scoring_func", "sigmoid") != "sigmoid" or \
            sizes.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("only sigmoid scores with the group-limited top-k are in this reference")
    if sizes.get("tie_word_embeddings") or sizes.get("attention_bias"):
        raise ValueError("tied embeddings / attention biases are not in this reference")
    if sizes.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {sizes['hidden_act']!r} is not in this reference")


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None, selection_gaps=None,
                   selection_swings=None):
    """Float32 logits of one sequence; ``rows`` picks positions (default all).
    A list passed as ``routing_gaps`` / ``selection_gaps`` / ``selection_swings``
    receives one entry: per picked position, the smallest gap (the largest
    swing) over the layers (see the module); ``routing_gaps`` reads 0 where
    the selection's swing makes the row a toss-up."""
    _refuse(sizes)
    eps, n = float(sizes["rms_norm_eps"]), sizes["num_hidden_layers"]
    share = sizes.get("deployment_share") or {}
    held = int(sizes["n_routed_experts"])
    first_held = int(share.get("expert_rank", 0)) * held
    ids = jnp.asarray(ids, jnp.int32)
    inv_freq = yarn_inv_freq(int(sizes["qk_rope_head_dim"]), float(sizes["rope_theta"]),
                             sizes.get("rope_scaling"))
    angles = jnp.arange(ids.shape[0], dtype=jnp.float32)[:, None] * \
        jnp.asarray(inv_freq, jnp.float32)[None, :]
    x = embed(params["embed_tokens"]["embedding"], ids)
    gaps, picks, swings = [], [], []
    for i in range(n):
        p = params[f"layers_{i}"]
        x, pick, swing = attention_part(x, p, angles, shape=_shape(sizes),
                                        scale=softmax_scale(sizes), eps=eps)
        picks.append(pick)
        swings.append(swing)
        if i < sizes["first_k_dense_replace"]:
            x = dense_part(x, p, eps=eps)
        else:
            x, gap = sparse_part(x, p, top_k=sizes["num_experts_per_tok"],
                                 n_group=sizes["n_group"], topk_group=sizes["topk_group"],
                                 scale=float(sizes["routed_scaling_factor"]),
                                 first_held=first_held, eps=eps)
            gaps.append(gap)
    smallest, pick = jnp.min(jnp.stack(gaps), axis=0), jnp.min(jnp.stack(picks), axis=0)
    swing = jnp.max(jnp.stack(swings), axis=0)
    if rows is not None:
        rows = jnp.asarray(rows)
        x, smallest, pick, swing = x[rows], smallest[rows], pick[rows], swing[rows]
    if routing_gaps is not None:
        routing_gaps.append(jnp.where(swing > SELECTION_SWING, 0.0, smallest))
    if selection_gaps is not None:
        selection_gaps.append(pick)
    if selection_swings is not None:
        selection_swings.append(swing)
    return head(x, params["norm"]["weight"], params["lm_head"]["kernel"], eps=eps)
