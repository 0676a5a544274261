"""Plain reference for Mixtral-8x7B-v0.1 (arXiv:2401.04088; the public
``config.json`` and ``modeling_mixtral.py``): Mistral's attention block (no
window declared) with the feed-forward replaced by 8 SwiGLU experts, of which
each token takes the 2 with the largest router probability, weighted by those
probabilities renormalised over the chosen two. Dropless: every token reaches
both of its experts whatever the others chose.

Same form as ``references/mistral.py``: float32, "highest" precision, one
sequence, one jitted call per layer part, and the experts one at a time so that
a single float32 expert (0.7 GB) is the largest temporary. Every expert is
applied to every token and weighted by the routing weight, which is 0 for the
tokens that did not choose it: plain, and exact.

The parameter tree is the program's: ``block_sparse_moe.gate`` [hidden, E],
``ExpertFFN_0.wi`` [E, hidden, 2 x ffn] holding (gate | up) side by side
(Mixtral's w1 | w3) and ``ExpertFFN_0.wo`` [E, ffn, hidden] (w2).
"""

import functools

import jax
import jax.numpy as jnp

from . import mistral
from .mistral import _f32, rms_norm


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim", "theta",
                                             "window", "eps"))
def attention_part(x, p, *, n_heads, n_kv_heads, head_dim, theta, window, eps):
    with jax.default_matmul_precision("highest"):
        return x + mistral.attention(rms_norm(x, p["input_layernorm"]["weight"], eps),
                                     p["self_attn"], n_heads=n_heads, n_kv_heads=n_kv_heads,
                                     head_dim=head_dim, theta=theta, window=window)


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def routing(x, norm_weight, gate, *, top_k, eps):
    """Normalised input h [S, hidden] and routing weights [S, E]: softmax over
    all experts, the top_k largest kept and renormalised to sum to 1."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, norm_weight, eps)
        probs = jax.nn.softmax(h @ _f32(gate), axis=-1)
    ranked, top_e = jax.lax.top_k(probs, top_k + 1)
    top_p, top_e = ranked[:, :top_k], top_e[:, :top_k]
    # how far the last expert chosen is ahead of the first one left out, in
    # router-logit units: where this is tiny, rounding decides the choice
    gap = jnp.log(ranked[:, top_k - 1]) - jnp.log(ranked[:, top_k])
    top_p = top_p / top_p.sum(axis=-1, keepdims=True)
    weights = jnp.zeros_like(probs).at[jnp.arange(probs.shape[0])[:, None], top_e].set(top_p)
    return h, weights, gap


@jax.jit
def expert(h, wi, wo, weight):
    """One SwiGLU expert over every token, scaled by that expert's routing
    weight [S] (0 where the token did not choose it)."""
    with jax.default_matmul_precision("highest"):
        gate, up = jnp.split(h @ _f32(wi), 2, axis=-1)
        return ((jax.nn.silu(gate) * up) @ _f32(wo)) * weight[:, None]


def layer(x, p, *, top_k, gaps=None, **attn):
    x = attention_part(x, p, **attn)
    moe = p["block_sparse_moe"]
    h, weights, gap = routing(x, p["post_attention_layernorm"]["weight"], moe["gate"],
                              top_k=top_k, eps=attn["eps"])
    if gaps is not None:
        gaps.append(gap)
    bank = moe["ExpertFFN_0"]
    for e in range(bank["wi"].shape[0]):
        x = x + expert(h, bank["wi"][e], bank["wo"][e], weights[:, e])
    return x


def forward_logits(params, sizes, ids, rows=None, routing_gaps=None):
    """Float32 logits of one sequence; ``rows`` picks positions (default all).
    A list passed as ``routing_gaps`` receives one entry: per picked position,
    the smallest routing gap over the layers (see ``routing``)."""
    gaps = []
    layer_fn = functools.partial(layer, top_k=sizes["num_experts_per_tok"], gaps=gaps)
    x, tree = mistral.forward_hidden(params, sizes, ids, layer_fn=layer_fn)
    smallest = jnp.min(jnp.stack(gaps), axis=0)
    if rows is not None:
        x, smallest = x[jnp.asarray(rows)], smallest[jnp.asarray(rows)]
    if routing_gaps is not None:
        routing_gaps.append(smallest)
    return mistral.head(x, tree["norm"]["weight"], tree["lm_head"]["kernel"],
                        eps=float(sizes["rms_norm_eps"]))
