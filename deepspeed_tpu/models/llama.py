"""Llama-family causal LM (the flagship training model).

Role in the framework: the reference exercises Llama-2 through DeepSpeed-Chat SFT
(BASELINE.md north-star: Llama-2-7B ZeRO-3 bf16) and through inference policies
(``deepspeed/inference/v2/model_implementations/llama_v2``). This is the TPU-native
equivalent model implementation: flax, bf16 matmuls on the MXU, GQA, RoPE, SwiGLU,
``jax.checkpoint`` rematerialization, Megatron-style TP sharding specs over the
``model`` mesh axis, and Ulysses sequence parallelism over the ``seq`` axis.
"""

from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.sequence.layer import DistributedAttention
from deepspeed_tpu.utils import groups


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = True
    # "nothing": recompute everything (min memory); "dots": save matmul outputs,
    # recompute elementwise only (cheap recompute — the usual transformer policy)
    remat_policy: str = "nothing"
    sequence_parallel: bool = False
    use_flash_attention: bool = False
    # llama-family deltas: qwen2 adds q/k/v biases; internlm biases the output
    # projection too; mistral masks beyond a sliding attention window
    attention_bias: bool = False
    attention_out_bias: bool = False
    sliding_window: int = 0  # 0 = disabled
    model_type: str = "llama"

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=128,
                    remat=False)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def llama2_7b(**kw):
        return LlamaConfig(**kw)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("weight", nn.initializers.ones, (x.shape[-1], ), jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(x.dtype)


def rotary_embedding(seq_len, head_dim, theta=10000.0, dtype=jnp.float32):
    inv_freq = 1.0 / (theta**(jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rotary(x, cos, sin):
    # x: [B, S, H, D]; rotate pairs (x1, x2) per the Llama convention
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def causal_attention(q, k, v, scale, window: int = 0):
    """Plain XLA attention [B,S,H,D]; fused/flash variant in ops/pallas.
    ``window`` > 0 masks keys older than the sliding window (mistral)."""
    B, S, H, D = q.shape
    _, _, KVH, _ = k.shape
    if KVH != H:  # GQA: repeat kv heads
        rep = H // KVH
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    pos = jnp.arange(S)
    mask = pos[None, :] <= pos[:, None]
    if window > 0:
        mask &= pos[None, :] > pos[:, None] - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_causal_attention(q, k, v, scale):
    """Pallas flash attention over [B, S, H, D], one kernel instance per device.

    The SPMD partitioner cannot split a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so on a multi-device mesh the call is
    shard_mapped: sequences over the data-parallel axes, heads over ``seq``
    (where Ulysses puts them) and ``model``. Attention is independent per
    (sequence, head), so any such split is exact; an axis that does not divide
    its dimension is left out and that dimension stays whole on every device."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    attn = partial(flash_attention, scale=scale, causal=True)
    mesh = groups.get_mesh() if groups.mesh_is_initialized() else None
    # inside someone else's shard_map (the pipeline engine) the program is
    # already per-device
    if mesh is None or mesh.size == 1 or jax.sharding.get_abstract_mesh().manual_axes:
        return attn(q, k, v)

    def dividing(axes, *sizes):
        picked, n = [], 1
        for ax in axes:
            if mesh.shape[ax] > 1 and all(s % (n * mesh.shape[ax]) == 0 for s in sizes):
                picked.append(ax)
                n *= mesh.shape[ax]
        return tuple(picked) or None

    spec = P(dividing(groups.DATA_PARALLEL_AXES, q.shape[0]), None,
             dividing((groups.SEQ_AXIS, groups.MODEL_AXIS), q.shape[2], k.shape[2]), None)
    return jax.shard_map(attn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.cfg
        H, KVH = cfg.num_attention_heads, cfg.num_key_value_heads
        D = cfg.hidden_size // H
        qkv_dense = partial(nn.Dense, use_bias=cfg.attention_bias, dtype=cfg.dtype)
        q = qkv_dense(H * D, name="q_proj")(x).reshape(*x.shape[:-1], H, D)
        k = qkv_dense(KVH * D, name="k_proj")(x).reshape(*x.shape[:-1], KVH, D)
        v = qkv_dense(KVH * D, name="v_proj")(x).reshape(*x.shape[:-1], KVH, D)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)

        if cfg.use_flash_attention:
            assert cfg.sliding_window == 0, "flash path has no sliding-window mask yet"
            attn = partial(flash_causal_attention, scale=1.0 / (D**0.5))
        else:
            attn = partial(causal_attention, scale=1.0 / (D**0.5), window=cfg.sliding_window)
        if cfg.sequence_parallel:
            # Ulysses: all-to-all seq→heads around full-sequence local attention
            attn = DistributedAttention(attn)
        out = attn(q, k, v)
        out = out.reshape(*x.shape[:-1], H * D)
        o_dense = partial(nn.Dense, use_bias=cfg.attention_out_bias, dtype=cfg.dtype)
        return o_dense(cfg.hidden_size, name="o_proj")(out)


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        gate = dense(cfg.intermediate_size, name="gate_proj")(x)
        up = dense(cfg.intermediate_size, name="up_proj")(x)
        return dense(cfg.hidden_size, name="down_proj")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin):
        x = x + LlamaAttention(self.cfg, name="self_attn")(RMSNorm(self.cfg.rms_norm_eps,
                                                                   name="input_layernorm")(x), cos, sin)
        x = x + LlamaMLP(self.cfg, name="mlp")(RMSNorm(self.cfg.rms_norm_eps,
                                                        name="post_attention_layernorm")(x))
        return x


class LlamaModel(nn.Module):
    """Returns logits [B, S, V]."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="embed_tokens")(input_ids)
        S = input_ids.shape[1]
        D = cfg.hidden_size // cfg.num_attention_heads
        cos, sin = rotary_embedding(S, D, cfg.rope_theta, jnp.float32)

        block = LlamaBlock
        if cfg.remat:
            # activation recomputation: keep only block boundaries
            # (reference activation_checkpointing/checkpointing.py role)
            assert cfg.remat_policy in ("nothing", "dots"), cfg.remat_policy
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat_policy == "dots" else jax.checkpoint_policies.nothing_saveable)
            block = nn.remat(LlamaBlock, policy=policy)
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"layers_{i}")(x, cos, sin)

        x = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype, name="lm_head")(x)
        return logits


class LlamaForCausalLM(nn.Module):
    """Loss module: batch = (input_ids, labels); -100 labels are masked."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, batch):
        input_ids, labels = batch
        logits = LlamaModel(self.cfg, name="model")(input_ids)
        return cross_entropy_loss(logits, labels)


def cross_entropy_loss(logits, labels, ignore_index=-100):
    valid = labels != ignore_index
    labels_safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels_safe[..., None], axis=-1)[..., 0]
    return -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1)


@lru_cache(maxsize=32)
def _jitted_init(model, batch_size, seq_len, param_dtype, mesh, specs_fn):

    def init(rng):
        # the forward pass flax runs to discover the parameters is dead code
        # under jit: only the initializers survive into the program
        ids = jnp.zeros((batch_size, seq_len), jnp.int32)
        params = model.init(rng, (ids, ids))["params"]
        if param_dtype is None:
            return params
        return jax.tree.map(
            lambda x: x.astype(param_dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params)

    out_shardings = None
    if mesh is not None:
        specs = specs_fn(jax.eval_shape(init, jax.random.PRNGKey(0)))
        out_shardings = jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), specs)
    return jax.jit(init, out_shardings=out_shardings)


def random_params(model, rng, batch_size, seq_len, param_dtype=None, mesh=None,
                  specs_fn=None):
    """Random parameters of a loss module over ``(input_ids, labels)`` batches.

    Asked for nothing else, this is flax's eager ``model.init`` (float32 Dense
    kernels; what the tiny models of the tests use, at no compile).

    Asked for a dtype or a mesh, the parameters are made ON THE DEVICE by one
    jitted program — the way to build a full-width tree (Llama-2-7B,
    Mixtral-8x7B), which must never exist as an eagerly initialized float32 copy
    on the host or on the first device. ``param_dtype`` casts every floating leaf
    inside the program (serving keeps weights in the compute dtype at rest).
    ``mesh`` places each leaf as ``specs_fn(abstract_params)`` says, each device
    generating only its own shard; the values do not depend on the placement
    (jax's threefry is partitionable), so one seed gives one model on one chip
    and on four."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if param_dtype is None and mesh is None:
        ids = jnp.zeros((batch_size, seq_len), jnp.int32)
        return model.init(rng, (ids, ids))["params"]
    return _jitted_init(model, batch_size, seq_len, param_dtype, mesh, specs_fn)(rng)


def init_params(cfg: LlamaConfig, rng=None, batch_size=1, seq_len=None, param_dtype=None,
                mesh=None):
    """``(model, params)`` with random weights; see :func:`random_params`.
    With ``mesh``, leaves are placed by :func:`llama_param_specs`."""
    model = LlamaForCausalLM(cfg)
    S = seq_len or min(cfg.max_position_embeddings, 16)
    return model, random_params(model, rng, batch_size, S, param_dtype, mesh, llama_param_specs)


def llama_param_specs(params, model_axis=groups.MODEL_AXIS):
    """Megatron-style TP placement over the ``model`` axis, derived structurally
    by AutoTP: column-parallel q/k/v/gate/up (+embed, lm_head), row-parallel
    o_proj/down_proj (reference module_inject/auto_tp.py:188)."""
    from deepspeed_tpu.module_inject.auto_tp import auto_tp_specs
    return auto_tp_specs(params, model_axis=model_axis)
