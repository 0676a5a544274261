"""The serving programs' named scopes: present in the lowered ``put`` programs
(``op_name`` metadata, which the TPU's trace carries as each operation's
``tf_op``), and metadata only — the logits are bitwise what the same code
gives with every scope turned off."""

import contextlib
import importlib.util
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig, MemoryConfig)
from deepspeed_tpu.models import mixtral
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
from deepspeed_tpu.utils import groups

V2 = "deepspeed_tpu.inference.v2."


def _engine_config(**kw):
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=32),
                               max_context=128)
    return RaggedInferenceEngineConfig(state_manager=mgr, kv_block_size=16, **kw)


@pytest.fixture(scope="module")
def models():
    groups.destroy_mesh()
    llama_cfg = LlamaConfig.tiny(dtype=jnp.float32)
    llama = {"model": LlamaModel(llama_cfg).init(jax.random.PRNGKey(0),
                                                 jnp.zeros((1, 8), jnp.int32))["params"]}
    mixtral_cfg = mixtral.MixtralConfig.tiny(dtype=jnp.float32)
    return {"llama": (llama_cfg, llama),
            "mixtral": (mixtral_cfg, mixtral.init_params(mixtral_cfg, rng=jax.random.PRNGKey(0))[1])}


@pytest.fixture(scope="module")
def lowered(models):
    """The lowered text, with debug info, of each family's ``put`` program on
    each attention arm, and Mixtral's ``decode_loop``."""
    out = {}
    for family, (cfg, params) in models.items():
        for arm, kernel in (("gather", False), ("kernel", True)):
            engine = build_engine(params, cfg, _engine_config(use_paged_kernel=kernel))
            out[family, arm] = engine.lower_forward().as_text(debug_info=True)
            if family == "mixtral" and arm == "gather":
                out[family, "loop"] = engine.lower_decode_loop(2).as_text(debug_info=True)
            if family == "llama" and arm == "kernel":
                # a prefill bucket (64 tokens, 8 sequences, 4 blocks): the query-tiled grid
                out[family, "kernel-prefill"] = engine.lower_forward((64, 8, 4)).as_text(
                    debug_info=True)
            engine.close()
    return out


@pytest.mark.parametrize("family,arm,scope", [
    ("llama", "gather", "embed"), ("llama", "gather", "attn"), ("llama", "gather", "attn/kv_write"),
    ("llama", "gather", "attn/gather"), ("llama", "kernel", "attn/paged_kernel"),
    ("llama", "kernel-prefill", "attn/paged_kernel"),
    ("llama", "gather", "mlp"), ("llama", "gather", "unembed"),
    ("mixtral", "gather", "attn"), ("mixtral", "gather", "moe/route"),
    ("mixtral", "gather", "moe/dispatch"), ("mixtral", "gather", "moe/experts"),
    ("mixtral", "gather", "moe/combine"), ("mixtral", "gather", "unembed"),
    ("mixtral", "loop", "moe/experts"), ("mixtral", "loop", "attn/gather"),
])
def test_put_program_carries_the_scope(lowered, family, arm, scope):
    # "jit(_forward_impl)/moe/route/top_k"; inside a scan's body the path is
    # relative ("moe/route/top_k") and the call site supplies the rest
    assert re.search(rf'[/"]{scope}/', lowered[family, arm]), \
        f"no operation of the {family} {arm} program is under {scope}"


def _operations(text):
    """``text`` without its SOURCE locations (``loc("/.../file.py":line...)``): a
    jitted helper (``jax.nn.silu``) is traced once a shape a process, and its
    cached trace carries the file of whoever called it FIRST — after another
    test file on the same worker that is ``deepspeed_tpu/moe/layer.py``, whose
    path holds "/moe/" (the one red test of the driver's whole runs to PR 61).
    What is asked here is the operations' scope paths."""
    return re.sub(r'loc\("[^"]*\.py":[^)]*\)', "", text)


def test_scopes_name_no_layer_and_dense_models_have_no_moe(lowered):
    lowered = {key: _operations(text) for key, text in lowered.items()}
    assert "/moe/" not in lowered["llama", "gather"] and "/mlp/" not in lowered["mixtral", "gather"]
    assert "/attn/gather/" not in lowered["llama", "kernel"]
    # a prefill bucket on the kernel arm: the tiled kernel, no scatter, no gather
    prefill = lowered["llama", "kernel-prefill"]
    # (interpret mode inlines the kernel: its body's locations name the function)
    assert "_tiled_kernel" in prefill and "_tiled_kernel" not in lowered["llama", "kernel"]
    assert "/attn/gather/" not in prefill and "/attn/kv_write/" not in prefill
    assert "layers_0/" not in lowered["mixtral", "gather"]  # one row a kind, whatever the layer


def _layer_operations(model, one_token):
    """The scope path of every operation of the model's LAYERS (the embedding,
    the head and the batch's unpacking left out): the layers alone, lowered with
    debug info over the model's own synthetic batch, as a ``put`` runs them or
    (``one_token``) as a ``decode_loop`` step does."""
    from functools import partial
    from tests.unit.inference.v2.family_pins import _NOT_A_SCOPE, _OP_PATH
    dev = model._synthetic_batch(model._bucket_of(model._synthetic_batch(None)))
    fields = model._unpack_batch(dict(dev, one_token_rows=one_token))
    fields = {k: v for k, v in fields.items() if k != "one_token_rows"}

    def layers(params, x, cache, fields):
        batch = dict(fields, one_token_rows=one_token, moe_banks=[])
        attn = partial(model._paged_attention, batch=batch)
        for li in range(model.num_layers):
            x, cache = model.layer_forward(params, li, x, cache, attn, batch)
        return x, cache

    x = jnp.zeros((dev["tok_meta"].shape[1], model.config.hidden_size), model.config.dtype)
    text = jax.jit(layers).lower(model._params, x, model.state_manager.kv_cache.cache,
                                 fields).as_text(debug_info=True)
    # an operation's own name starts at the jitted function (``jit(layers)/ssm/conv/add``);
    # inside a jitted helper's body (``jnp.einsum``) a name is relative and its call carries
    # the scope; argument names and call-stack frames are locations too, and no operation's
    paths = set()
    for path in _OP_PATH.findall(text):
        if path.startswith("jit(layers)/"):
            paths.add("/".join(p for p in path.split("/")[:-1] if not _NOT_A_SCOPE.match(p)))
    return paths


def test_every_operation_of_a_granite_layer_is_under_a_scope_the_metrics_read():
    """Granite 4.0-H (PR 65): a layer is a mixer and then the experts, and
    every operation of either form of a layer — a ``put``'s scan, a chunk's
    recurrence — lies under ``ssm``, ``attn`` or ``moe``; each scope the
    per-layer metrics read is there; and the new metric's pattern, READ FROM
    its file, takes the two projections and nothing else."""
    import json
    import os
    from deepspeed_tpu.models import granitemoehybrid as gm
    from tests.unit.inference.v2 import test_granitemoehybrid as t
    cfg = gm.GraniteMoeHybridConfig.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1)
    engine = t.engine_of(cfg, gm.init_params(cfg, rng=jax.random.PRNGKey(3))[1])
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))))
    with open(os.path.join(repo, "benchmark", "metrics", "ssm_proj_busy_pct.json")) as f:
        projections = re.compile(json.load(f)["params"]["pattern"])
    layer_scope = re.compile(r"(^|/)(ssm|attn|moe)(/|$)")
    for one_token, form in ((False, "scan"), (True, "step")):
        paths = _layer_operations(engine.model, one_token)
        nobodys = sorted(p for p in paths if not layer_scope.search(p))
        assert not nobodys, nobodys
        for scope in ("ssm/in_proj", "ssm/conv", f"ssm/{form}", "ssm/gate_norm", "ssm/out_proj",
                      "attn", "moe/route", "moe/dispatch", "moe/experts", "moe/combine",
                      "moe/shared"):
            assert any(re.search(rf"(^|/){scope}(/|$)", p) for p in paths), (form, scope)
        other = "step" if form == "scan" else "scan"
        assert not any(re.search(rf"(^|/)ssm/{other}(/|$)", p) for p in paths)
        taken = {p for p in paths if projections.search(p)}
        assert taken and all(re.search(r"(^|/)ssm/(in_proj|out_proj)(/|$)", p) for p in taken)
        assert not any(re.search(r"(^|/)(moe|attn)(/|$)", p) for p in taken)
    engine.close()
    # the ends, in the whole program's text
    whole = engine.model.lower_forward().as_text(debug_info=True)
    assert re.search(r'[/"]embed/', whole) and re.search(r'[/"]unembed/', whole)


class _NoScope(contextlib.ContextDecorator):

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _unscoped_model_classes(monkeypatch):
    """The model modules executed once more, privately, with
    ``jax.named_scope`` a no-op: the same code without its scopes."""
    monkeypatch.setattr(jax, "named_scope", lambda name: _NoScope())
    private = {}
    for name in ("modules.moe", "model_implementations.transformer_base",
                 "model_implementations.llama_v2", "model_implementations.mixtral_v2"):
        spec = importlib.util.find_spec(V2 + name)
        module = importlib.util.module_from_spec(spec)
        monkeypatch.setitem(sys.modules, V2 + name, module)
        spec.loader.exec_module(module)
        private[name.rsplit(".", 1)[1]] = module
    return {"llama": private["llama_v2"].LlamaV2Model, "mixtral": private["mixtral_v2"].MixtralV2Model}


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_logits_are_bitwise_what_they_are_without_scopes(models, family, monkeypatch):
    cfg, params = models[family]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in (9, 5)]
    step = [np.array([7], np.int32), np.array([11], np.int32)]

    def run(engine):
        first = np.asarray(engine.put([0, 1], prompts))
        second = np.asarray(engine.put([0, 1], step))
        looped = np.asarray(engine.decode_loop([0, 1], step, 3))
        engine.close()
        return first, second, looped

    scoped = run(build_engine(params, cfg, _engine_config()))
    model_cls = _unscoped_model_classes(monkeypatch)[family]
    config = _engine_config()
    plain_engine = InferenceEngineV2(model_cls(params, cfg, config), config)
    assert "/attn/" not in plain_engine.lower_forward().as_text(debug_info=True)
    plain = run(plain_engine)
    for a, b in zip(scoped, plain):
        np.testing.assert_array_equal(a, b)
