"""Plain reference for Falcon-H1-34B-Instruct (the public ``config.json``,
huggingface.co/tiiuae/Falcon-H1-34B-Instruct, ``model_type`` ``falcon_h1``;
what the configuration has no key for, marked + below, is the family's public
modelling code, ``transformers`` ``models/falcon_h1``, and the configuration
file lists it under ``assumed.modelling_code``):

    h = E[ids] embedding_multiplier
    each layer:
      u = rms(h; input_layernorm)                                (rms_norm_eps)
      -- Mamba-2 (d_inner = mamba_d_ssm = mamba_n_heads x mamba_d_head)
      p = (u ssm_in_multiplier) W_in;  columns [z | xBC | dt] +,  xBC = [x | B | C]
      p = p (.) m:  ssm_multipliers[0] on z, [1] on x, [2] on B, [3] on C, [4] on dt +
      xBC_t = silu(sum_j w[:, j] xBC_{t-3+j} + b)     (causal, depthwise; rows
                                                       before 0 are 0)
      x -> [heads, d_head];  B, C -> [groups, d_state];  head j reads group
                                                         j // (heads / groups)
      D_t = softplus(dt_t + dt_bias) (no clamp +);  a = -exp(A_log)
      S_t = exp(D_t a) S_{t-1} + D_t x_t (x) B_t       TOKEN BY TOKEN, float32,
      y_t = S_t C_t + D x_t                            S_{-1} = 0
      mamba = rms_grouped(y silu(z); groups) g W_out ssm_out_multiplier
                                                      (gate BEFORE the norm +)
      -- attention, on the SAME u
      a = u attention_in_multiplier
      q, k, v = a Wq, (a Wk) key_multiplier (before the rotary embedding +), a Wv
      q, k = rope(q), rope(k)          (whole head, rope_theta, rotate-half)
      attn = softmax(q k^T / sqrt(head_dim), causal) v Wo attention_out_multiplier
      h = h + mamba + attn
      -- feed-forward
      f = rms(h; pre_ff_layernorm)
      h = h + ((f W_up) (.) silu((f W_gate) mlp_multipliers[0])) W_down mlp_multipliers[1]
    logits = (rms(h; final_layernorm) W_head) lm_head_multiplier       (untied)

Float32, "highest" precision, no kernels, no cache, no batching, one sequence.
The state-space scan is the recurrence as written, one ``lax.scan`` step a
token: independent of the chunked form under test. One jitted call a layer
(that layer's weights cast to float32 inside it); the embedding gathered
before it is cast and the head applied a block of the vocabulary's columns at
a time, so that neither 261120 x 5120 matrix exists in float32. It reads the
parameter tree by its names only.
"""

import functools

import jax
import jax.numpy as jnp

from .mistral import _f32, rms_norm, rotary

HEAD_BLOCKS = 8  # column blocks of the head: 1/8 of it in float32 at a time


def mamba(u, p, c):
    """The Mamba-2 mixer of one sequence u: [S, hidden], from zero state."""
    S = u.shape[0]
    heads, d_head, groups, state = c["heads"], c["d_head"], c["groups"], c["state"]
    d_inner, gn = heads * d_head, groups * state
    proj = (u * c["ssm_in"]) @ _f32(p["in_proj"]["kernel"])
    mz, mx, mb, mc, mdt = c["ssm_m"]
    m = jnp.concatenate([jnp.full((d_inner, ), mz), jnp.full((d_inner, ), mx),
                         jnp.full((gn, ), mb), jnp.full((gn, ), mc), jnp.full((heads, ), mdt)])
    proj = proj * m.astype(jnp.float32)[None, :]
    z, xbc, dt = jnp.split(proj, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    w, b = _f32(p["conv1d"]["kernel"]), _f32(p["conv1d"]["bias"])  # [C, K], [C]
    K = w.shape[1]
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[j:j + S] * w[None, :, j] for j in range(K)) + b[None, :])
    x, B, C = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
    x = x.reshape(S, heads, d_head)
    B = jnp.repeat(B.reshape(S, groups, state), heads // groups, axis=1)  # head j: group j // r
    C = jnp.repeat(C.reshape(S, groups, state), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"])[None, :])  # [S, heads]
    a = -jnp.exp(_f32(p["A_log"]))

    def token(h, row):
        x_t, B_t, C_t, dt_t = row
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, C_t)

    _, y = jax.lax.scan(token, jnp.zeros((heads, d_head, state), jnp.float32), (x, B, C, dt))
    y = (y + _f32(p["D"])[None, :, None] * x).reshape(S, d_inner)
    g = (y * jax.nn.silu(z)).reshape(S, groups, d_inner // groups)
    g = g * jax.lax.rsqrt(jnp.square(g).mean(axis=-1, keepdims=True) + c["eps"])
    return ((g.reshape(S, d_inner) * _f32(p["norm"]["weight"])) @ _f32(p["out_proj"]["kernel"])) \
        * c["ssm_out"]


def attention(u, p, c):
    """Causal grouped-query attention of one sequence, rotary over the whole
    head, the keys scaled before it."""
    s = u.shape[0]
    n_heads, n_kv, d = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    a = u * c["attn_in"]
    q = (a @ _f32(p["q_proj"]["kernel"])).reshape(s, n_heads, d)
    k = ((a @ _f32(p["k_proj"]["kernel"])) * c["key_m"]).reshape(s, n_kv, d)
    v = (a @ _f32(p["v_proj"]["kernel"])).reshape(s, n_kv, d)
    pos = jnp.arange(s)
    q, k = rotary(q, pos, c["theta"]), rotary(k, pos, c["theta"])
    qg = q.reshape(s, n_kv, n_heads // n_kv, d)
    scores = jnp.einsum("qkgd,tkd->kgqt", qg, k) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where((pos[None, :] <= pos[:, None])[None, None], scores, -jnp.inf)
    out = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, axis=-1), v)
    return (out.reshape(s, n_heads * d) @ _f32(p["o_proj"]["kernel"])) * c["attn_out"]


def feed_forward(f, p, c):
    gate_m, down_m = c["mlp_m"]
    gate = (f @ _f32(p["gate_proj"]["kernel"])) * gate_m
    up = f @ _f32(p["up_proj"]["kernel"])
    return ((up * jax.nn.silu(gate)) @ _f32(p["down_proj"]["kernel"])) * down_m


@functools.partial(jax.jit, static_argnames=("settings", ))
def layer(h, p, *, settings):
    c = dict(settings)
    with jax.default_matmul_precision("highest"):
        u = rms_norm(h, p["input_layernorm"]["weight"], c["eps"])
        h = h + mamba(u, p["mamba"], c) + attention(u, p["self_attn"], c)
        return h + feed_forward(rms_norm(h, p["pre_ff_layernorm"]["weight"], c["eps"]),
                                p["feed_forward"], c)


@functools.partial(jax.jit, static_argnames=("scale", ))
def embed(table, ids, *, scale):
    return _f32(table[ids]) * scale


@functools.partial(jax.jit, static_argnames=("eps", "scale"))
def head(x, norm_weight, lm_head, *, eps, scale):
    """``(rms(x) W_head) scale``, a block of W_head's columns at a time."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, norm_weight, eps)
        M, V = lm_head.shape
        blocks = HEAD_BLOCKS if V % HEAD_BLOCKS == 0 else 1
        width = V // blocks

        def one(i):
            return x @ _f32(jax.lax.dynamic_slice(lm_head, (0, i * width), (M, width)))

        out = jax.lax.map(one, jnp.arange(blocks))  # [blocks, rows, width]
        return out.transpose(1, 0, 2).reshape(x.shape[0], V) * scale


def _refuse(sizes):
    if sizes.get("mamba_norm_before_gate") or not sizes.get("mamba_rms_norm", True):
        raise ValueError("only the gate before a grouped RMS norm is in this reference")
    if not sizes.get("mamba_use_mlp", True) or sizes.get("hidden_act", "silu") != "silu":
        raise ValueError("a layer without its feed-forward / another activation than silu is "
                         "not in this reference")
    if any(sizes.get(k) for k in ("attention_bias", "mamba_proj_bias", "mlp_bias",
                                  "projectors_bias", "tie_word_embeddings", "rope_scaling")):
        raise ValueError("projection biases, tied embeddings and rotary scaling are not in "
                         "this reference")
    if sizes.get("attn_layer_indices") is not None:
        raise ValueError("attn_layer_indices: every layer holds both mixers in this reference")


def layer_settings(sizes):
    """What a layer reads of the configuration, hashable."""
    d_inner = sizes.get("mamba_d_ssm") or sizes["mamba_expand"] * sizes["hidden_size"]
    heads, d_head = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    if heads * d_head != d_inner:
        raise ValueError(f"d_inner {d_inner} is not {heads} heads of {d_head}")
    return (("heads", heads), ("d_head", d_head), ("groups", sizes["mamba_n_groups"]),
            ("state", sizes["mamba_d_state"]), ("eps", float(sizes["rms_norm_eps"])),
            ("ssm_in", float(sizes["ssm_in_multiplier"])),
            ("ssm_m", tuple(float(m) for m in sizes["ssm_multipliers"])),
            ("ssm_out", float(sizes["ssm_out_multiplier"])),
            ("n_heads", sizes["num_attention_heads"]),
            ("n_kv_heads", sizes["num_key_value_heads"]), ("head_dim", sizes["head_dim"]),
            ("theta", float(sizes["rope_theta"])),
            ("attn_in", float(sizes["attention_in_multiplier"])),
            ("key_m", float(sizes["key_multiplier"])),
            ("attn_out", float(sizes["attention_out_multiplier"])),
            ("mlp_m", tuple(float(m) for m in sizes["mlp_multipliers"])))


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None):
    """Float32 logits of one sequence; ``rows`` picks positions (default all).
    ``routing_gaps`` is the sparse models' and stays untouched: this model
    routes nothing."""
    _refuse(sizes)
    settings = layer_settings(sizes)
    tree = params["model"] if "model" in params else params
    h = embed(tree["embed_tokens"]["embedding"], jnp.asarray(ids, jnp.int32),
              scale=float(sizes["embedding_multiplier"]))
    for i in range(sizes["num_hidden_layers"]):
        h = layer(h, tree[f"layers_{i}"], settings=settings)
    if rows is not None:
        h = h[jnp.asarray(rows)]
    return head(h, tree["final_layernorm"]["weight"], tree["lm_head"]["kernel"],
                eps=float(sizes["rms_norm_eps"]), scale=float(sizes["lm_head_multiplier"]))
