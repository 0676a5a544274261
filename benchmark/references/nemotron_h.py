"""Plain reference for Nemotron-3-Nano-30B-A3B (the public ``config.json``,
huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type``
``nemotron_h``; what the configuration has no key for, marked + below, is the
family's public modelling code, ``transformers`` ``models/nemotron_h``, and the
configuration file lists it under ``assumed``), as ONE CHIP'S SHARE of a layer
that several chips share (``deployment_share``):

    x = E[ids]
    each block l, of the kind k = hybrid_override_pattern[l]:
      u = rms(x; norm_l)                                  (layer_norm_epsilon)
      M: [z | xBC | dt] = u W_in          (4096 | 4096 + 2 x 8 x 128 | 64)
         xBC_t = silu(sum_j w[:, j] xBC_{t-3+j} + b)      (causal, depthwise;
                                                           rows before 0 are 0)
         [x | B | C] = xBC;  x -> [64 heads, 64];  B, C -> [8 groups, 128];
                                               head h reads group h // 8
         D_t = softplus(dt_t + dt_bias);  a = -exp(A_log)                  (+)
         h_t = exp(D_t a) h_{t-1} + D_t x_t (x) B_t        TOKEN BY TOKEN,
         y_t = h_t C_t + D x_t                             float32, h_{-1} = 0
         m = rms_grouped(y silu(z); 8 groups) g W_out      (gate BEFORE norm +)
      E: s = sigmoid(u W_r) in float32; sel = the num_experts_per_tok largest
         of s + e_score_correction_bias; w = s[sel] / sum s[sel] x
         routed_scaling_factor; m = sum over the sel HELD HERE of
         w relu(u W_up)^2 W_down + relu(u W_up')^2 W_down' (the shared expert)
      *: q, k, v = u Wq, u Wk, u Wv; NO position encoding                  (+)
         m = softmax(q k^T / sqrt(head_dim), causal) v Wo       (GQA 32 : 2)
      -: m = relu(u W_up)^2 W_down
      x = x + m
    logits = rms(x; norm_f) W_head

``n_group`` = ``topk_group`` = 1: no group limit. The routed sum is over the
experts this chip holds (the banks' leading dimension; the first is
``deployment_share.expert_rank x experts_held``): what the other chips' experts
would add is left out, as the served layer leaves it out. The banks hold an
expert's width in whole lane tiles, the padding zero
(``deepspeed_tpu/models/nemotron_h.py``): read as they are.

Float32, "highest" precision, no kernels, no cache, no batching, one sequence.
The state-space scan is the recurrence as written, one ``lax.scan`` step a
token: independent of the chunked form under test. One jitted call a block;
attention in blocks of queries; the experts one at a time over every token.
It reads the parameter tree by its names only.
"""

import functools

import jax
import jax.numpy as jnp

from .mistral import _f32, embed, head, rms_norm

QUERY_BLOCK = 512


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba(u, p, *, heads, head_dim, groups, state, eps):
    """The Mamba-2 mixer of one sequence u: [S, hidden], from zero state."""
    S = u.shape[0]
    d_inner, gn = heads * head_dim, groups * state
    zxbcdt = u @ _f32(p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    w, b = _f32(p["conv1d"]["kernel"]), _f32(p["conv1d"]["bias"])  # [C, K], [C]
    K = w.shape[1]
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[j:j + S] * w[None, :, j] for j in range(K)) + b[None, :])
    x, B, C = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
    x = x.reshape(S, heads, head_dim)
    B = jnp.repeat(B.reshape(S, groups, state), heads // groups, axis=1)  # head h: group h // r
    C = jnp.repeat(C.reshape(S, groups, state), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"])[None, :])  # [S, heads]
    a = -jnp.exp(_f32(p["A_log"]))

    def token(h, row):
        x_t, B_t, C_t, dt_t = row
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, C_t)

    _, y = jax.lax.scan(token, jnp.zeros((heads, head_dim, state), jnp.float32), (x, B, C, dt))
    y = (y + _f32(p["D"])[None, :, None] * x).reshape(S, d_inner)
    g = (y * jax.nn.silu(z)).reshape(S, groups, d_inner // groups)
    g = g * jax.lax.rsqrt(jnp.square(g).mean(axis=-1, keepdims=True) + eps)
    return (g.reshape(S, d_inner) * _f32(p["norm"]["weight"])) @ _f32(p["out_proj"]["kernel"])


def attention(u, p, *, n_heads, n_kv_heads, head_dim):
    """Causal grouped-query attention of one sequence, no position encoding."""
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

    out = jax.lax.map(one_block, jnp.arange(n_blocks) * block).reshape(-1, n_heads * head_dim)
    return out[:s] @ _f32(p["o_proj"]["kernel"])


def routing(u, gate, bias, *, top_k, norm, scale, first_held, held):
    """``(weights [S, held], gap [S])``: each position's routing weight of each
    expert held here (0 where it did not choose it) and its toss-up gap that
    matters here, in router-logit units (``references/deepseek_v32.py``'s rule:
    a flip between the last expert chosen and the first left out counts only
    where one of the two is held here)."""
    S = u.shape[0]
    s = jax.nn.sigmoid(u @ _f32(gate))
    ranked, order = jax.lax.top_k(s + _f32(bias), top_k + 1)
    chosen = order[:, :top_k]
    picked = jnp.take_along_axis(s, chosen, axis=1)
    w = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) if norm else picked
    everywhere = jnp.zeros_like(s).at[jnp.arange(S)[:, None], chosen].set(w * scale)

    def here(e):
        return (e >= first_held) & (e < first_held + held)

    def slope_at(e):  # of the sigmoid, to bring a gap in scores back to router logits
        v = jnp.take_along_axis(s, e[:, None], axis=1)[:, 0]
        return v * (1.0 - v)

    last, first_out = order[:, top_k - 1], order[:, top_k]
    gap = (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.maximum(
        0.5 * (slope_at(last) + slope_at(first_out)), 1e-30)
    gap = jnp.where(here(last) | here(first_out), gap, jnp.inf)
    return everywhere[:, first_held:first_held + held], gap


def experts(u, moe, *, top_k, norm, scale, first_held):
    """``(held routed + shared, gap)``; the held experts one at a time."""
    bank = moe["experts"]
    held = bank["wi"].shape[0]
    weights, gap = routing(u, moe["gate"], moe["e_score_correction_bias"], top_k=top_k,
                           norm=norm, scale=scale, first_held=first_held, held=held)

    def one_expert(e, m):
        return m + (relu2(u @ _f32(bank["wi"][e])) @ _f32(bank["wo"][e])) * weights[:, e][:, None]

    m = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(u))
    if "shared_experts" in moe:
        m = m + mlp(u, moe["shared_experts"])
    return m, gap


def mlp(u, p):
    return relu2(u @ _f32(p["up_proj"]["kernel"])) @ _f32(p["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("kind", "eps", "settings"))
def block(x, p, *, kind, eps, settings):
    """``(x + mixer(rms(x)), gap or None)`` for one block of ``kind``."""
    settings = dict(settings)
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["norm"]["weight"], eps)
        gap = None
        if kind == "M":
            m = mamba(u, p["mixer"], eps=eps, **settings)
        elif kind == "E":
            m, gap = experts(u, p["mixer"], **settings)
        elif kind == "*":
            m = attention(u, p["mixer"], **settings)
        else:
            m = mlp(u, p["mixer"])
        return x + m, gap


def _refuse(sizes):
    if {sizes.get("n_group", 1), sizes.get("topk_group", 1)} != {1}:
        raise ValueError("a group limit on the routing is not in this reference")
    if sizes.get("tie_word_embeddings") or sizes.get("sliding_window"):
        raise ValueError("tied embeddings / a sliding window are not in this reference")
    if sizes.get("mamba_hidden_act", "silu") != "silu" or sizes.get("mlp_hidden_act") != "relu2":
        raise ValueError("activations other than silu (Mamba) and relu2 are not in this reference")
    if any(sizes.get(k) for k in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias")):
        raise ValueError("projection biases are not in this reference")


def block_settings(sizes):
    """What each kind of block reads of the configuration, hashable."""
    share = sizes.get("deployment_share") or {}
    return {
        "M": (("heads", sizes["mamba_num_heads"]), ("head_dim", sizes["mamba_head_dim"]),
              ("groups", sizes["n_groups"]), ("state", sizes["ssm_state_size"])),
        "E": (("top_k", sizes["num_experts_per_tok"]),
              ("norm", bool(sizes.get("norm_topk_prob", True))),
              ("scale", float(sizes.get("routed_scaling_factor", 1.0))),
              ("first_held", share.get("expert_rank", 0) * share.get("experts_held", 0))),
        "*": (("n_heads", sizes["num_attention_heads"]),
              ("n_kv_heads", sizes["num_key_value_heads"]), ("head_dim", sizes["head_dim"])),
        "-": (),
    }


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None):
    """Float32 logits of one sequence; ``rows`` picks positions (default all).
    A list passed as ``routing_gaps`` receives one entry: per picked position,
    the smallest routing gap over the expert blocks (see ``routing``)."""
    _refuse(sizes)
    eps, n = float(sizes["layer_norm_epsilon"]), sizes["num_hidden_layers"]
    settings = block_settings(sizes)
    x = embed(params["embed_tokens"]["embedding"], jnp.asarray(ids, jnp.int32))
    gaps = []
    for i, kind in enumerate(sizes["hybrid_override_pattern"][:n]):
        x, gap = block(x, params[f"layers_{i}"], kind=kind, eps=eps, settings=settings[kind])
        if gap is not None:
            gaps.append(gap)
    smallest = jnp.min(jnp.stack(gaps), axis=0) if gaps else None
    if rows is not None:
        x = x[jnp.asarray(rows)]
        smallest = None if smallest is None else smallest[jnp.asarray(rows)]
    if routing_gaps is not None and smallest is not None:
        routing_gaps.append(smallest)
    return head(x, params["norm_f"]["weight"], params["lm_head"]["kernel"], eps=eps)
