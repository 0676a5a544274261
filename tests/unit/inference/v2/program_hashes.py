"""The serving programs of a tiny Mixtral (top-2 of 4, no window) and a tiny
Mistral (one window for every layer) engine, as text: what
``test_one_group_programs.py`` hashes. One group, ``top_k`` 2 and one block
table are the programs the benchmark's cells run, so the layer groups, the
general top-k and the per-layer window must leave their traces as they were."""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig, MemoryConfig)
from deepspeed_tpu.utils import groups

BUCKETS = ((8, 8, 4), (128, 8, 8))


def _engine(kind, kernel):
    groups.initialize_mesh(force=True)
    if kind == "mixtral":
        from deepspeed_tpu.models.mixtral import MixtralConfig, init_params
        cfg = MixtralConfig.tiny(dtype=jnp.float32, hidden_size=256, num_attention_heads=2,
                                 num_key_value_heads=1)
    else:
        from deepspeed_tpu.models.llama import LlamaConfig, init_params
        cfg = LlamaConfig.tiny(dtype=jnp.float32, hidden_size=256, num_attention_heads=2,
                               num_key_value_heads=1, sliding_window=24, model_type="mistral")
    _, params = init_params(cfg)
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=24),
                               max_context=128, max_ragged_batch_size=128,
                               max_ragged_sequence_count=8)
    return build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=16, use_paged_kernel=kernel))


def _stable(jaxpr):
    """The jaxpr's text with what varies run to run blanked: addresses, and the
    order in which a frozenset of mesh axes prints."""
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    return re.sub(r"frozenset\(\{([^}]*)\}\)",
                  lambda m: "frozenset({" + ", ".join(sorted(m.group(1).split(", "))) + "})", text)


def decode_loop_hash(model, n_steps=4):
    """sha256 of a served model's traced ``decode_loop`` program (:func:`_stable`)
    at its default bucket: what a PR that must leave that program alone pins."""
    loop = functools.partial(model._decode_loop_impl, n_steps=n_steps)
    jaxpr = jax.make_jaxpr(loop)(model._params, model.state_manager.kv_cache.cache,
                                 model._synthetic_batch(None))
    return hashlib.sha256(_stable(jaxpr).encode()).hexdigest()


def traced_program_texts():
    """``{name: jaxpr text}`` (addresses blanked) of each forward bucket and one
    decode loop, on the gather arm and on the kernel arm."""
    out = {}
    for kind in ("mixtral", "mistral"):
        for kernel in (False, True):
            engine = _engine(kind, kernel)
            model = engine.model
            cache = model.state_manager.kv_cache.cache
            arm = "kernel" if kernel else "gather"
            for bucket in BUCKETS:
                dev = model._synthetic_batch(bucket)
                jaxpr = jax.make_jaxpr(model._forward_impl)(model._params, cache, dev)
                out[f"{kind}.{arm}.forward.{'x'.join(map(str, bucket))}"] = _stable(jaxpr)
            dev = model._synthetic_batch(BUCKETS[0])
            jaxpr = jax.make_jaxpr(lambda p, c, d: model._decode_loop_impl(p, c, d, n_steps=4))(
                model._params, cache, dev)
            out[f"{kind}.{arm}.decode_loop"] = _stable(jaxpr)
            engine.close()
    return out
