"""Ragged inference for the configurable decoder family (OPT / Falcon / Phi).

Reference: ``deepspeed/inference/v2/model_implementations/{opt,falcon,phi}``
(one directory per model in the reference; one parameterized implementation
here — the axes are position encoding, residual topology, norm, activation,
MQA — see ``models/decoder.py``). Consumes the training pytree verbatim so
logits are testable against the training forward.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _root, rotary_embedding
from deepspeed_tpu.inference.v2.model_implementations.transformer_base import \
    DSTransformerModelBase
from deepspeed_tpu.models.decoder import DecoderConfig, _act


def _ln(x, p, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * p["scale"] + p["bias"]).astype(x.dtype)


def _linear(h, p):
    out = h @ p["kernel"].astype(h.dtype)
    if "bias" in p:
        out = out + p["bias"].astype(h.dtype)
    return out


def _rotary_at_partial(x, pos, cos_tab, sin_tab, pct, interleaved=False):
    if pct <= 0.0:
        return x
    D = x.shape[-1]
    rot = int(round(D * pct)) // 2 * 2
    cos = cos_tab[pos][:, None, :]
    sin = sin_tab[pos][:, None, :]
    xr = x[..., :rot]
    if interleaved:  # gptj: adjacent (even, odd) pairs rotate together
        x1 = xr[..., 0::2]
        x2 = xr[..., 1::2]
        rotated = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1) \
            .reshape(xr.shape)
    else:            # llama/neox half-split
        x1, x2 = jnp.split(xr, 2, axis=-1)
        rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), x[..., rot:]], axis=-1)


class DecoderV2Model(DSTransformerModelBase):

    def __init__(self, params, config: DecoderConfig, engine_config, state_manager=None):
        if config.pos_embed == "alibi" or config.embed_layernorm:
            # BEFORE super(): the base may quantize the whole tree first
            raise NotImplementedError(
                f"inference-v2 DecoderV2Model does not serve {config.model_type!r}: "
                "ALiBi biases are not implemented in the paged attention paths — "
                "use the v1 engine (init_inference over the converted checkpoint)")
        super().__init__(params, config, engine_config, state_manager)
        if config.pos_embed == "rotary":
            D = config.hidden_size // config.num_attention_heads
            rot = int(round(D * config.rotary_pct)) // 2 * 2
            self._cos, self._sin = rotary_embedding(engine_config.state_manager.max_context,
                                                    rot, config.rope_theta, jnp.float32)

    @property
    def head_dim(self):
        return self._config.hidden_size // self._config.num_attention_heads

    # --------------------------------------------------------------- phases --
    @jax.named_scope("embed")
    def _add_positions(self, params, x, batch):
        cfg = self._config
        if cfg.pos_embed != "learned":
            return x
        wpe = _root(params)["embed_positions"]["embedding"]
        pos = batch["token_pos"] + cfg.learned_pos_offset
        return x + wpe[pos].astype(x.dtype)

    @jax.named_scope("unembed")
    def unembed(self, params, x):
        r = _root(params)
        x = _ln(x, r["final_layer_norm"], self._config.layer_norm_eps)
        logits = x @ r["lm_head"]["kernel"].astype(x.dtype)
        if "bias" in r["lm_head"]:  # gptj's biased head
            logits = logits + r["lm_head"]["bias"].astype(x.dtype)
        return logits

    def _attn(self, params, li, h, cache, attn_fn, batch):
        cfg = self._config
        ap = _root(params)[f"layers_{li}"]["self_attn"]
        H, KVH, D = self.num_heads, self.num_kv_heads, self.head_dim
        q = _linear(h, ap["q_proj"]).reshape(-1, H, D)
        k = _linear(h, ap["k_proj"]).reshape(-1, KVH, D)
        v = _linear(h, ap["v_proj"]).reshape(-1, KVH, D)
        if cfg.pos_embed == "rotary":
            pos = batch["token_pos"]
            q = _rotary_at_partial(q, pos, self._cos, self._sin, cfg.rotary_pct,
                                   cfg.rotary_interleaved)
            k = _rotary_at_partial(k, pos, self._cos, self._sin, cfg.rotary_pct,
                                   cfg.rotary_interleaved)
        out, cache = attn_fn(q, k, v, cache, li)
        return _linear(out.reshape(h.shape[0], H * D), ap["out_proj"]), cache

    def _mlp(self, params, li, h):
        cfg = self._config
        mp = _root(params)[f"layers_{li}"]["mlp"]
        act = _act(cfg)  # shared table: unknown activations fail loudly
        return _linear(act(_linear(h, mp["fc1"])), mp["fc2"])

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        cfg = self._config
        lp = _root(params)[f"layers_{li}"]
        if li == 0:
            x = self._add_positions(params, x, batch)
        # the norms and residual adds take the scope of the phase they feed
        if cfg.parallel_residual:
            with jax.named_scope("attn"):
                h = _ln(x, lp["input_layernorm"], cfg.layer_norm_eps)
                attn_out, cache = self._attn(params, li, h, cache, attn_fn, batch)
            with jax.named_scope("mlp"):
                hm = _ln(x, lp["post_attention_layernorm"], cfg.layer_norm_eps) \
                    if cfg.parallel_mlp_norm else h
                return x + attn_out + self._mlp(params, li, hm), cache
        with jax.named_scope("attn"):
            h = _ln(x, lp["input_layernorm"], cfg.layer_norm_eps)
            attn_out, cache = self._attn(params, li, h, cache, attn_fn, batch)
            x = x + attn_out
        with jax.named_scope("mlp"):
            h = _ln(x, lp["post_attention_layernorm"], cfg.layer_norm_eps)
            return x + self._mlp(params, li, h), cache
