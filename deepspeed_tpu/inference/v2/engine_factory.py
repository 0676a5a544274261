"""Engine construction + generation driver.

Reference: ``deepspeed/inference/v2/engine_factory.py`` (build_hf_engine:66 picks an
InferenceV2Policy by HF ``model_type``). Here model classes consume the training
pytree directly, so the "policy" is a config-type → model-class dispatch.

The decode loop (``generate``) is the serving-side driver the reference leaves to
MII: continuous-batching greedy/temperature sampling over ``engine.put()``.
"""

from typing import Dict, List, Optional, Sequence

import numpy as np

from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2


def build_engine(params, model_config, engine_config: Optional[RaggedInferenceEngineConfig] = None):
    """Build an InferenceEngineV2 for a training param tree + model config;
    the model class resolves through the policy registry (reference
    engine_factory.py:66-120 model_type dispatch)."""
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for

    if engine_config is None:
        engine_config = RaggedInferenceEngineConfig()
    model = model_cls_for(model_config)(params, model_config, engine_config)
    return InferenceEngineV2(model, engine_config)


def build_engine_from_ds_checkpoint(path: str,
                                    engine_config: Optional[RaggedInferenceEngineConfig] = None):
    """Rebuild an engine from an ``InferenceEngineV2.serialize`` directory
    (reference engine_factory.py:29) — the inference-checkpoint round-trip.
    The config is JSON (never pickle: a checkpoint directory must not be an
    arbitrary-code-execution vector) and its class is restricted to this
    package's model configs."""
    import importlib
    import json
    import os

    import jax.numpy as jnp

    with open(os.path.join(path, "ds_model_config.json")) as f:
        cfg_doc = json.load(f)
    mod_name, _, cls_name = cfg_doc["config_class"].rpartition(".")
    if not mod_name.startswith("deepspeed_tpu."):
        raise ValueError(f"refusing to import config class from {mod_name!r} "
                         "(only deepspeed_tpu model configs are loadable)")
    cfg_cls = getattr(importlib.import_module(mod_name), cls_name)

    def dec(v):
        if isinstance(v, dict) and "__dtype__" in v:
            # restore the jnp SCALAR TYPE (jnp.float32), not np.dtype: they
            # compare equal but models may branch on the exact object
            return getattr(jnp, v["__dtype__"], jnp.dtype(v["__dtype__"]))
        return v

    model_config = cfg_cls(**{k: dec(v) for k, v in cfg_doc["fields"].items()})
    with open(os.path.join(path, "metadata_rank0.json")) as f:
        meta = json.load(f)
    params: Dict = {}
    with np.load(os.path.join(path, "params_rank0.npz")) as z:
        for i, m in enumerate(meta):
            arr = z[f"p{i}"]
            if str(arr.dtype) != m["dtype"]:  # stored as a uint view (bf16)
                arr = jnp.asarray(arr).view(jnp.dtype(m["dtype"]))
            node = params
            keys = m["path"].split("/")
            for k in keys[:-1]:
                node = node.setdefault(k, {})
            node[keys[-1]] = jnp.asarray(arr).reshape(m["shape"])
    return build_engine(params, model_config, engine_config)


def build_hf_engine(path: str, engine_config: Optional[RaggedInferenceEngineConfig] = None):
    """Load an HF checkpoint directory and build an engine (reference
    engine_factory.py:66); a directory written by ``engine.serialize`` routes
    to the DS-checkpoint loader (reference :84 ds_model_config detection)."""
    import os

    if os.path.exists(os.path.join(path, "ds_model_config.json")):
        return build_engine_from_ds_checkpoint(path, engine_config)
    if os.path.exists(os.path.join(path, "ds_model_config.pkl")):
        raise ValueError(
            f"{path} is a LEGACY pickle-format DS checkpoint; the format was "
            "retired (pickle in a checkpoint is an arbitrary-code-execution "
            "vector). Re-serialize the engine with the current code to get "
            "the JSON-config format.")
    from deepspeed_tpu.inference.checkpoint import load_hf_checkpoint

    params, model_config = load_hf_checkpoint(path)
    return build_engine(params, model_config, engine_config)


def generate(engine: InferenceEngineV2,
             prompts: Sequence[Sequence[int]],
             max_new_tokens: int = 16,
             temperature: float = 0.0,
             eos_token_id: Optional[int] = None,
             seed: int = 0,
             decode_chunk: int = 1) -> List[List[int]]:
    """Synchronous continuous-batching decode: a thin wrapper over the serving
    scheduler (``deepspeed_tpu/serving``), so Dynamic SplitFuse admission —
    chunked prefill under the token budget, decode-first batching, KV-pressure
    shrink/evict — exists in exactly one place. Greedy when ``temperature == 0``.

    ``decode_chunk`` > 1 runs decode-only batches in chunks of K steps through
    the engine's on-device ``decode_loop`` (one dispatch per chunk instead of
    one per token); eos is checked between chunks, so a finished sequence
    over-generates up to K-1 discarded tokens before its KV blocks recycle —
    the standard chunked-serving tradeoff of host-RTT against speculative
    compute. The fast path is greedy-only: with ``temperature > 0`` each
    request (seeded ``seed + index``) takes the step-by-step path, its tokens
    drawn on the device from its own positional stream
    (``inference/v2/sampling.py``), so concurrent requests stay independently
    reproducible; greedy output is identical either way.
    """
    from deepspeed_tpu.serving.config import ServingConfig
    from deepspeed_tpu.serving.request import RequestState
    from deepspeed_tpu.serving.scheduler import ServingScheduler

    if len(prompts) == 0:
        return []
    # an engine already serving keeps its scheduler (requests just join the
    # live batch mix); otherwise a temporary one owns the engine for this
    # call and is driven INLINE — no background thread, the caller's thread
    # ticks the scheduler until every request finishes
    scheduler = engine.serving_scheduler
    own_scheduler = scheduler is None
    if own_scheduler:
        scheduler = ServingScheduler(
            engine,
            ServingConfig(queue_capacity=len(prompts), decode_chunk=decode_chunk,
                          default_max_new_tokens=max_new_tokens),
            start=False)
    requests = []
    try:
        for i, p in enumerate(prompts):
            requests.append(scheduler.submit(p, max_new_tokens=max_new_tokens,
                                             temperature=temperature,
                                             eos_token_id=eos_token_id, seed=seed + i))
        if own_scheduler:
            while not all(req.finished for req in requests):
                scheduler.step()
        outputs = []
        for req in requests:
            tokens = req.result()  # raises RuntimeError when the request FAILED
            if req.state is not RequestState.DONE:
                # reachable through a shared scheduler: its default deadline,
                # or a concurrent stop()/engine.close(), can cut the request
                raise RuntimeError(f"generate(): request finished {req.state.name} "
                                   f"after {len(tokens)} of {max_new_tokens} tokens")
            outputs.append(tokens)
        return outputs
    except BaseException:
        # a failed submit (queue full on a shared scheduler) or a failed
        # request must not orphan the rest: nobody will consume them
        for req in requests:
            req.cancel()
        raise
    finally:
        if own_scheduler:
            scheduler.stop(drain=False)
