"""Plain reference for SDAR-30B-A3B-Chat (the public ``config.json``,
huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``model_type`` ``sdar_moe``): a
pre-norm decoder whose every layer is grouped-query attention (32 query heads
of 128 over 4 K/V heads; ``head_dim`` is its own key), an RMS norm over each
head of q and of k, rotary embedding over the whole head, and a sparse SwiGLU
feed-forward: softmax over 128 experts in float32, the 8 largest kept and
renormalised (``norm_topk_prob``), no shared expert, dropless. Attention is
under the BLOCK mask: key j is visible to query i iff ``j // B <= i // B``
(``B`` = ``block_length``, blocks counted from position 0). Row i of the logits
scores the token AT position i.

``generate`` is the family's block-diffusion generation, greedy, with the
static schedule (``low_confidence_static``): prefill of the prompt's whole
blocks; then, block by block, denoise forwards of the whole sequence with the
block's untaken rows fed the mask token, after each of which the ``B /
denoising_steps`` masked rows with the most confident greedy token take it;
the block is then part of the sequence (the system's commit).

Same form as ``references/mellum.py``: float32, "highest" precision, no
kernels, no cache, no batching, one sequence, one jitted call per layer part,
attention in blocks of queries, the experts one at a time over every token
(``fori_loop`` slices one expert's banks out of the served tree and casts that
slice alone, so the reference fits beside 8 GiB of bf16 weights), the head on
the rows asked for. Independent of the code under test: it reads the parameter
tree by its names only.

Departures from the published code, which the configuration lists under
``assumed``: whether a row is masked is a FLAG beside the row (``flags``), never
recovered by comparing ids with ``mask_token_id`` — the benchmark's prompts are
drawn from the whole vocabulary, the mask id included; the bf16 weights are
read in float32; q/k norm a head is Qwen3-MoE's (the config has no key for
it); ties in confidence go to the earlier row.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .mistral import _f32, head, rms_norm, rotary

QUERY_BLOCK = 512
GENERATION_KEYS = ("block_length", "denoising_steps", "remasking_strategy", "mask_token_id")


def generation(sizes):
    """The four generation settings of a configuration file: under ``assumed``
    (the published ``config.json`` has no key for them), else at the top."""
    assumed = sizes.get("assumed") or {}
    out = {k: assumed[k] if k in assumed else sizes[k] for k in GENERATION_KEYS}
    if out["remasking_strategy"] != "low_confidence_static":
        raise ValueError(f"remasking_strategy {out['remasking_strategy']!r} is not in this "
                         f"reference")
    return out


def attention(x, p, *, n_heads, n_kv_heads, head_dim, theta, block, eps):
    """Grouped-query attention of one sequence x: [S, hidden] under the block
    mask; the queries are walked in blocks (``lax.map``)."""
    s = x.shape[0]
    pos = jnp.arange(s)
    q = (x @ _f32(p["q_proj"]["kernel"])).reshape(s, n_heads, head_dim)
    k = (x @ _f32(p["k_proj"]["kernel"])).reshape(s, n_kv_heads, head_dim)
    v = (x @ _f32(p["v_proj"]["kernel"])).reshape(s, n_kv_heads, head_dim)
    q, k = rms_norm(q, p["q_norm"]["weight"], eps), rms_norm(k, p["k_norm"]["weight"], eps)
    q, k = rotary(q, pos, theta), rotary(k, pos, theta)
    group = n_heads // n_kv_heads
    rows = min(QUERY_BLOCK, s)
    n_blocks = -(-s // rows)
    q = jnp.pad(q, ((0, n_blocks * rows - s), (0, 0), (0, 0)))

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows).reshape(rows, n_kv_heads, group,
                                                                  head_dim)
        qpos = jnp.minimum(start + jnp.arange(rows), s - 1)  # rows past the end repeat the last
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) / jnp.sqrt(jnp.float32(head_dim))
        visible = pos[None, :] // block <= qpos[:, None] // block
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, v).reshape(rows, n_heads * head_dim)

    out = jax.lax.map(one_block, jnp.arange(n_blocks) * rows).reshape(-1, n_heads * head_dim)
    return out[:s] @ _f32(p["o_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim", "theta",
                                             "block", "eps"))
def attention_part(x, p, *, eps, **settings):
    with jax.default_matmul_precision("highest"):
        return x + attention(rms_norm(x, p["input_layernorm"]["weight"], eps), p["self_attn"],
                             eps=eps, **settings)


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def sparse_part(x, p, *, top_k, eps):
    """``(x + routed experts, gap)``: softmax over every expert in float32, the
    ``top_k`` largest renormalised; the gap between the last expert chosen and
    the first left out in router-logit units (``references/mixtral.py``)."""
    moe = p["block_sparse_moe"]
    bank = moe["ExpertFFN_0"]
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        probs = jax.nn.softmax(h @ _f32(moe["gate"]), axis=-1)
        ranked, top_e = jax.lax.top_k(probs, top_k + 1)
        gap = jnp.log(ranked[:, top_k - 1]) - jnp.log(ranked[:, top_k])
        top_p = ranked[:, :top_k] / ranked[:, :top_k].sum(axis=-1, keepdims=True)
        weights = jnp.zeros_like(probs).at[jnp.arange(probs.shape[0])[:, None],
                                           top_e[:, :top_k]].set(top_p)

        def one_expert(e, m):
            gate, up = jnp.split(h @ _f32(bank["wi"][e]), 2, axis=-1)
            return m + ((jax.nn.silu(gate) * up) @ _f32(bank["wo"][e])) * weights[:, e][:, None]

        return x + jax.lax.fori_loop(0, bank["wi"].shape[0], one_expert, jnp.zeros_like(x)), gap


@jax.jit
def embed(table, ids):
    return _f32(table[ids])


def _refuse(sizes):
    for key in ("sliding_window", "rope_scaling", "tie_word_embeddings", "attention_bias",
                "mlp_only_layers", "use_sliding_window"):
        if sizes.get(key):
            raise ValueError(f"{key} {sizes[key]!r} is not in this reference")
    if sizes.get("decoder_sparse_step", 1) != 1 or not sizes.get("norm_topk_prob", True):
        raise ValueError("dense layers / unnormalised top-k weights are not in this reference")
    if sizes.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {sizes['hidden_act']!r} is not in this reference")


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None, flags=None):
    """Float32 logits of one sequence under the block mask; ``rows`` picks
    positions (default all). ``flags`` (one a position, default none): the
    position is fed the mask token's embedding, whatever its id. A list passed
    as ``routing_gaps`` receives one entry: per picked position, the smallest
    routing gap over the layers."""
    _refuse(sizes)
    gen = generation(sizes)
    eps = float(sizes["rms_norm_eps"])
    ids = jnp.asarray(ids, jnp.int32)
    if flags is not None:
        ids = jnp.where(jnp.asarray(flags, bool), jnp.int32(gen["mask_token_id"]), ids)
    x = embed(params["embed_tokens"]["embedding"], ids)
    gaps = []
    for i in range(sizes["num_hidden_layers"]):
        p = params[f"layers_{i}"]
        x = attention_part(x, p, n_heads=sizes["num_attention_heads"],
                           n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
                           theta=float(sizes["rope_theta"]), block=int(gen["block_length"]),
                           eps=eps)
        x, gap = sparse_part(x, p, top_k=sizes["num_experts_per_tok"], eps=eps)
        gaps.append(gap)
    smallest = jnp.min(jnp.stack(gaps), axis=0)
    if rows is not None:
        x, smallest = x[jnp.asarray(rows)], smallest[jnp.asarray(rows)]
    if routing_gaps is not None:
        routing_gaps.append(smallest)
    return head(x, params["norm"]["weight"], params["lm_head"]["kernel"], eps=eps)


def confidence(logits):
    """``(x0, c)`` of rows of float32 logits: the greedy token and its softmax
    probability."""
    logits = jnp.asarray(logits, jnp.float32)
    return (np.asarray(jnp.argmax(logits, axis=-1)),
            np.asarray(jnp.max(jax.nn.softmax(logits, axis=-1), axis=-1)))


def most_confident(conf, masked, n):
    """The ``n`` masked rows with the largest confidence, ties to the earlier
    row (fewer where fewer are masked)."""
    order = sorted(np.flatnonzero(masked), key=lambda j: (-float(conf[j]), j))
    return order[:n]


def generate(params, sizes, prompt, max_new_tokens, pad_to=64):
    """Greedy block-diffusion generation: ``(ids, steps)``, each
    ``[max_new_tokens]`` — the tokens at positions ``len(prompt) ..`` and the
    denoise step of its block at which each took its token. The sequence is
    padded with token 0 to a multiple of ``pad_to`` (a multiple of the block:
    padding starts at a block's edge, so no row asked for sees it), which keeps
    the compilations few."""
    gen = generation(sizes)
    B, n_steps = int(gen["block_length"]), int(gen["denoising_steps"])
    prompt = np.asarray(prompt, np.int64).reshape(-1)
    whole = prompt.size // B * B
    seq = list(prompt[:whole])  # what the system has committed
    known = list(prompt[whole:])  # the first block's given rows
    out_ids, out_steps = [], []
    while len(out_ids) < max_new_tokens:
        block = known + [0] * (B - len(known))
        masked = np.array([False] * len(known) + [True] * (B - len(known)))
        taken = np.full(B, -1, np.int64)
        for step in range(n_steps):
            if not masked.any():
                break  # the system runs the forward all the same; nothing is taken
            ids = np.asarray(seq + block, np.int64)
            flags = np.concatenate([np.zeros(len(seq), bool), masked])
            padded = -(-ids.size // pad_to) * pad_to
            logits = forward_logits(params, sizes, np.pad(ids, (0, padded - ids.size)),
                                    rows=np.arange(len(seq), ids.size),
                                    flags=np.pad(flags, (0, padded - ids.size)))
            x0, conf = confidence(logits)
            for j in most_confident(conf, masked, B // n_steps):
                block[j], masked[j], taken[j] = int(x0[j]), False, step
        first = len(known)  # rows of the prompt are not generated tokens
        out_ids += block[first:]
        out_steps += list(taken[first:])
        seq += block
        known = []
    return (np.asarray(out_ids[:max_new_tokens], np.int64),
            np.asarray(out_steps[:max_new_tokens], np.int64))
