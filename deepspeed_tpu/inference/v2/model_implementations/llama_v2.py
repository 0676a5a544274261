"""Llama ragged inference model.

Reference: ``deepspeed/inference/v2/model_implementations/llama_v2/model.py``
(LlamaV2InferenceModel — per-layer qkv → blocked-kv rotary → blocked flash attn →
gated MLP over the ragged batch).

Consumes the TRAINING param tree of :class:`deepspeed_tpu.models.llama.LlamaModel`
verbatim (``{"model": {embed_tokens, layers_i{self_attn,mlp,*layernorm}, norm},
lm_head}``) so inference logits are testable bit-for-bit against the training
forward — the reference needs a LayerContainer mapping step instead
(``layer_container_base.py:164``); a functional pytree makes it a no-op.

Every phase runs under a ``jax.named_scope`` (``embed``, ``attn``, ``mlp``,
``unembed``; no layer index, so a kind of operation is one row whatever its
layer): metadata only, carried into the device trace with each operation.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.transformer_base import (
    DSTransformerModelBase, _rms, _root, scaled_dot)
from deepspeed_tpu.models.llama import LlamaConfig, rotary_embedding


def _rotate_half(x, cos, sin):
    """x: [T, H, D]; cos, sin: [T, 1, D/2]; rotates the pairs (x[i], x[i + D/2])."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _swiglu(h, mp):
    """The dense SwiGLU feed-forward of ``h`` [T, M] over one ``{gate_proj,
    up_proj, down_proj}`` subtree: a Llama layer's ``mlp``, and any model's
    always-on (dense or shared) expert."""
    gate = h @ mp["gate_proj"]["kernel"].astype(h.dtype)
    up = h @ mp["up_proj"]["kernel"].astype(h.dtype)
    return (jax.nn.silu(gate) * up) @ mp["down_proj"]["kernel"].astype(h.dtype)


def _rotary_at(x, pos, cos_tab, sin_tab):
    """x: [T, H, D] with per-token absolute positions [T]."""
    return _rotate_half(x, cos_tab[pos][:, None, :], sin_tab[pos][:, None, :])


class PositionFreeGQA:
    """The softmax layers of the hybrids whose other mixers carry the order
    (``nemotron_h_v2.py``, ``solar_open2_v2.py``): grouped-query, causal, no
    position encoding, no norm or residual of its own (the caller's)."""

    # what the queries are multiplied by where the model's softmax scale is not
    # the kernels' 1 / sqrt(head_dim) (its scale over theirs), on the q
    # projection's float32 product; None: nothing, the projection as it is
    query_scale = None

    @jax.named_scope("attn")
    def _attn_phase(self, ap, ai, h, kv, attn_fn):
        """Softmax layer ``ai`` (its ordinal: its layer of the K/V array) over
        the normed rows ``h``; where the tree has a ``gate_proj``, the heads'
        output gated from the layer's input."""
        T = h.shape[0]
        H, KVH, D = self.num_heads, self.num_kv_heads, self.head_dim
        if self.query_scale is None:
            q = h @ ap["q_proj"]["kernel"].astype(h.dtype)
        else:
            q = scaled_dot(h, ap["q_proj"]["kernel"], self.query_scale)
        q = q.reshape(T, H, D)
        k = (h @ ap["k_proj"]["kernel"].astype(h.dtype)).reshape(T, KVH, D)
        v = (h @ ap["v_proj"]["kernel"].astype(h.dtype)).reshape(T, KVH, D)
        out, kv = attn_fn(q, k, v, kv, ai)
        out = out.reshape(T, H * D).astype(h.dtype)
        if "gate_proj" in ap:
            with jax.named_scope("gate"):
                out = out * jax.nn.sigmoid(h @ ap["gate_proj"]["kernel"].astype(h.dtype))
        return out @ ap["o_proj"]["kernel"].astype(h.dtype), kv


class LlamaV2Model(DSTransformerModelBase):

    def __init__(self, params, config: LlamaConfig, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager)
        self._rope = self._build_rope(engine_config.state_manager.max_context)

    def _build_rope(self, max_context):
        """The rotary tables, built once to ``max_context``: here one ``(cos,
        sin)`` pair for every layer."""
        return rotary_embedding(max_context, self.head_dim, self._config.rope_theta, jnp.float32)

    def _rotate(self, li, x, pos):
        """Layer ``li``'s rotary embedding of x ``[T, H, D]`` at positions ``pos``."""
        return _rotary_at(x, pos, *self._rope)

    @property
    def head_dim(self):
        """The config's own ``head_dim`` where it has one (heads x head_dim
        need not be ``hidden_size``), else ``hidden_size / heads``."""
        return (getattr(self._config, "head_dim", None)
                or self._config.hidden_size // self._config.num_attention_heads)

    # --------------------------------------------------------------- phases --
    @jax.named_scope("attn")
    def _attn_phase(self, params, li, x, cache, attn_fn, batch):
        cfg = self._config
        lp = _root(params)[f"layers_{li}"]
        H, KVH, D = self.num_heads, self.num_kv_heads, self.head_dim
        h = _rms(x, lp["input_layernorm"]["weight"], cfg.rms_norm_eps)
        ap = lp["self_attn"]

        def lin(p, width):  # qwen2-style optional q/k/v biases
            out = h @ p["kernel"].astype(h.dtype)
            if "bias" in p:
                out = out + p["bias"].astype(h.dtype)
            return out.reshape(-1, width, D)

        q = lin(ap["q_proj"], H)
        k = lin(ap["k_proj"], KVH)
        v = lin(ap["v_proj"], KVH)
        if "q_norm" in ap:  # an RMS norm over each head of q and of k
            with jax.named_scope("qk_norm"):
                q = _rms(q, ap["q_norm"]["weight"], cfg.rms_norm_eps)
                k = _rms(k, ap["k_norm"]["weight"], cfg.rms_norm_eps)
        pos = batch["token_pos"]
        q = self._rotate(li, q, pos)
        k = self._rotate(li, k, pos)
        out, cache = attn_fn(q, k, v, cache, li)
        out = out.reshape(x.shape[0], H * D)
        if "gate_proj" in ap:  # an output gate on the heads' output, from the layer's input
            with jax.named_scope("gate"):
                out = out * jax.nn.sigmoid(h @ ap["gate_proj"]["kernel"].astype(h.dtype))
        return x + self._attn_out(lp, out @ ap["o_proj"]["kernel"].astype(h.dtype)), cache

    def _attn_out(self, lp, y):
        """The attention branch's output ``y`` as it is added to the residual
        stream: as it is (these models' ``post_attention_layernorm`` is the
        feed-forward's pre-norm); a model that norms each branch coming out as
        well as going in applies that norm here."""
        return y

    @jax.named_scope("mlp")
    def _ffn_phase(self, params, li, x):
        cfg = self._config
        lp = _root(params)[f"layers_{li}"]
        h = _rms(x, lp["post_attention_layernorm"]["weight"], cfg.rms_norm_eps)
        return x + _swiglu(h, lp["mlp"])

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        x, cache = self._attn_phase(params, li, x, cache, attn_fn, batch)
        return self._ffn_phase(params, li, x), cache

    @property
    def attention_window(self):
        """Sliding attention window (mistral); 0/None = full causal."""
        return getattr(self._config, "sliding_window", 0) or 0


class MistralV2Model(LlamaV2Model):
    """Reference: inference/v2/model_implementations/mistral — llama
    architecture + sliding-window attention. The window is the config's
    ``sliding_window`` (``attention_window``); every attention arm masks it, the
    Pallas kernel also walks only the window's blocks, and the KV pool takes
    back each block the window has passed (``maybe_free_kv``), so contexts up to
    ``max_context`` are served at the window's cost and memory."""


class Qwen2V2Model(LlamaV2Model):
    """Reference: inference/v2/model_implementations/qwen — llama architecture
    + q/k/v projection biases (handled generically by ``_attn_phase``)."""
