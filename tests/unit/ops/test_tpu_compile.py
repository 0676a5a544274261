"""What the chip's COMPILER accepts, asked without a chip.

The TPU compiler is installed in the sandbox and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). Interpret-mode kernel
tests cannot see what it refuses: a slice off the tiling, too much VMEM, a
program that does not fit 16 GB, a Mosaic kernel the partitioner is asked to
split. These compile the main paths' kernels and two whole serving programs at
the shapes ``chip_smoke.py`` runs. Nothing executes, so nothing here is a result
or a time — only "the compiler did not refuse".
"""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described v5e 2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"the v5e topology cannot be described here: {e}")
    return topo.devices


@pytest.fixture(autouse=True)
def compile_for_tpu(monkeypatch):
    """The code under test asks ``jax.default_backend()`` to choose between the
    Mosaic kernel and interpret mode; here it is compiled for the TPU. And a
    compile for a described device must not go through the persistent cache
    (it can be written but not read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sizes():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    return chip_smoke.ServeSizes(), chip_smoke.TrainSizes()


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes \
        + m.temp_size_in_bytes


def _kernel_calls(text, name):
    """The ``tpu_custom_call``s of a compiled program's text that run the kernel
    called ``name``."""
    return [line for line in text.splitlines() if "tpu_custom_call" in line and name in line]


def _call_sites(text, name):
    """The calls of the jitted function ``name`` (any of its traces: ``name``,
    ``name_123``) in a LOWERED program's text: what was traced, before the
    compiler inlines anything."""
    return len(re.findall(rf"call @{name}(?:_\d+)?\(", text))


# serve-phase geometry: Mixtral-8x7B attention, the engine's default 64-token blocks
H, KVH, D, BS = 32, 8, 128, 64


@pytest.mark.parametrize("tokens,max_blocks", [(8, 4), (32, 16), (64, 16), (256, 16)],
                         ids=["decode-bucket", "largest-token-grid-bucket",
                              "smallest-tiled-bucket", "rag-chunk-bucket"])
def test_paged_attention_update_compiles(v5e, sizes, tokens, max_blocks):
    """Both grids of the paged kernel at the benchmark configuration's head
    shapes: per token up to 32 tokens, query-tiled above."""
    from deepspeed_tpu.ops.pallas import paged_attention
    serve, _ = sizes
    one = SingleDeviceSharding(v5e[0])
    on = functools.partial(_on, one)
    tiled = tokens > paged_attention.TOKEN_GRID_MAX
    seqs = 16 if tiled else 8

    def step(q, k, v, cache, *meta):
        update = paged_attention.paged_attention_prefill if tiled \
            else paged_attention.paged_attention_update
        return update(q, k, v, cache, 1, *meta)

    meta = (on((seqs, ), jnp.int32), ) * 3 if tiled else \
        (on((tokens, ), jnp.int32), on((tokens, ), jnp.int32), on((tokens, ), jnp.bool_))
    compiled = jax.jit(step, donate_argnums=(3, )).lower(
        on((tokens, H, D), jnp.bfloat16), on((tokens, KVH, D), jnp.bfloat16),
        on((tokens, KVH, D), jnp.bfloat16),
        on((serve.layers, 2, serve.kv_blocks, KVH, BS, D), jnp.bfloat16),
        on((seqs, max_blocks), jnp.int32), *meta).compile()
    # ONE kernel a layer, whatever arms and phases it holds inside
    assert len(_kernel_calls(compiled.as_text(), "paged_attention_prefill" if tiled
                             else "paged_attention_update")) == 1


def test_flash_forward_and_backward_compile(v5e, sizes):
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    _, train = sizes
    one = SingleDeviceSharding(v5e[0])
    x = _on(one, (1, train.seq_len, 32, D), jnp.bfloat16)
    lse = _on(one, (32, train.seq_len, 1), jnp.float32)

    fwd = jax.jit(functools.partial(fa._flash_fwd_pallas, scale=D**-0.5, causal=True,
                                    save_lse=True)).lower(x, x, x).compile()
    assert "flash_attention_fwd" in fwd.as_text()
    bwd = jax.jit(functools.partial(fa._flash_bwd_pallas, scale=D**-0.5, causal=True)) \
        .lower(x, x, x, x, x, lse).compile().as_text()
    assert "flash_attention_bwd_dkv" in bwd and "flash_attention_bwd_dq" in bwd


def test_flash_under_a_data_parallel_mesh_compiles(v5e, sizes):
    """The partitioner refuses to split a Mosaic kernel; the model's attention
    must hand each device its own sequences (shard_map) for ZeRO-3 over
    ``data=4`` to compile at all."""
    from deepspeed_tpu.models.llama import flash_causal_attention
    from deepspeed_tpu.utils import groups
    _, train = sizes
    mesh = groups.set_mesh(Mesh(np.array(v5e).reshape(1, 4, 1, 1, 1, 1), groups.MESH_AXES))
    x = _on(NamedSharding(mesh, P(groups.DATA_AXIS)), (4, train.seq_len, 32, D), jnp.bfloat16)

    def loss(q, k, v):
        return flash_causal_attention(q, k, v, D**-0.5).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 3


@pytest.fixture(scope="module")
def serve_model(sizes):
    """The serve phase's model over ``jax.eval_shape``d parameters (6.3 GB of
    bf16 weights that are never made)."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import mixtral
    serve, _ = sizes
    cfg = mixtral.MixtralConfig(num_hidden_layers=serve.layers)
    abstract = jax.eval_shape(lambda: mixtral.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=serve.max_context,
                                           max_ragged_batch_size=serve.token_budget,
                                           max_ragged_sequence_count=8), kv_block_size=BS)
    return model_cls_for(cfg)(abstract, cfg, engine_config), abstract


def _serve_args(device, sizes, abstract, bucket):
    serve, _ = sizes
    one = SingleDeviceSharding(device)
    tokens, seqs, max_blocks = bucket
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = _on(one, (serve.layers, 2, serve.kv_blocks, KVH, BS, D), jnp.bfloat16)
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (seqs, 4 + max_blocks), jnp.int32)}
    return one, params, cache, batch


def _pool_sized_results(text, pool_shape):
    """``copy`` / ``slice`` / ``bitcast-slice fusion`` instructions of a compiled
    program whose result has the KV pool's shape or one layer's K or V plane's:
    what the XLA gather arm costs a step (PERF.md §6, PR 24), and the kernel
    arm, which aliases the pool through, must not."""
    import re
    plane = tuple(pool_shape[2:])
    shapes = {",".join(map(str, shape)) for shape in (pool_shape, plane, (1, 1) + plane)}
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = \w+\[([\d,]*)\][^ ]* (\w[\w-]*)\(", line)
        if m and m.group(2) in shapes and (
                m.group(3) in ("copy", "slice", "dynamic-slice")
                or (m.group(3) == "fusion" and re.match(r"(copy|slice)", m.group(1)))):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("bucket,kernel", [((8, 8, 4), "paged_attention_update"),
                                           ((128, 8, 4), "paged_attention_prefill")],
                         ids=["decode-bucket", "prefill-bucket"])
def test_serve_put_program_fits_one_chip(v5e, sizes, serve_model, bucket, kernel):
    serve, _ = sizes
    model, abstract = serve_model
    _, params, cache, batch = _serve_args(v5e[0], sizes, abstract, bucket)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and kernel in text  # what heuristics.py chose
    assert _device_bytes(compiled) < HBM_BYTES
    # the pool is aliased through every layer's kernel: the compiler neither
    # copies it nor cuts a plane out of it
    assert not _pool_sized_results(text, cache.shape)


def test_gather_arm_program_copies_the_pool(v5e, sizes, serve_model):
    """The same prefill bucket on the XLA gather arm, as a check OF the check
    above: the compiler answers its scatter and gather with pool-sized copies
    (what every step over 32 tokens paid before PR 24)."""
    import copy
    model, abstract = serve_model
    gather = copy.copy(model)
    gather._engine_config = model._engine_config.model_copy(update={"use_paged_kernel": False})
    _, params, cache, batch = _serve_args(v5e[0], sizes, abstract, (128, 8, 4))
    text = jax.jit(gather._forward_impl, donate_argnums=(1, )).lower(
        params, cache, batch).compile().as_text()
    assert "tpu_custom_call" not in text
    assert _pool_sized_results(text, cache.shape)


def test_serve_decode_loop_program_fits_one_chip(v5e, sizes, serve_model):
    serve, _ = sizes
    model, abstract = serve_model
    one, params, cache, batch = _serve_args(v5e[0], sizes, abstract, (8, 8, 4))
    loop = functools.partial(model._decode_loop_impl, n_steps=serve.decode_chunk)
    compiled = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch).compile()
    assert "paged_attention_update" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("tree", [False, True], ids=["chains", "branching"])
def test_the_verify_step_keeps_the_kernel_for_chains(v5e, sizes, serve_model, tree):
    """Why the one verify step has two programs (PR 28): a batch of chains
    carries no ``tree_meta`` and attends through the paged kernel, the pool
    aliased through, exactly as ``put`` at that bucket; the ancestor mask has
    no kernel arm, and the compiler answers its scatter and gather with
    pool-sized copies. Sending chains through it would cost them the kernel."""
    model, abstract = serve_model
    one, params, cache, batch = _serve_args(v5e[0], sizes, abstract, (32, 8, 4))
    if tree:
        batch["tree_meta"] = _on(one, (2, 32), jnp.int32)
    verify = functools.partial(model._verify_impl, greedy=True)
    compiled = jax.jit(verify, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text and "paged_attention_update" in text) == (not tree)
    assert bool(_pool_sized_results(text, cache.shape)) == tree
    assert _device_bytes(compiled) < HBM_BYTES


# ---- a sliding-window model at the benchmark cell's shapes (PR 26) -----------
WINDOW, WINDOW_MAX_BLOCKS, WINDOW_POOL_BLOCKS, WINDOW_LAYERS = 4096, 128, 7104, 5


@pytest.mark.parametrize("tokens", [8, 256], ids=["token-grid", "tile-grid"])
def test_paged_attention_with_a_window_compiles(v5e, tokens):
    """Both grids with ``window`` 4096 over a block table of 8192 tokens."""
    from deepspeed_tpu.ops.pallas import paged_attention
    on = functools.partial(_on, SingleDeviceSharding(v5e[0]))
    tiled = tokens > paged_attention.TOKEN_GRID_MAX

    def step(q, k, v, cache, *meta):
        update = paged_attention.paged_attention_prefill if tiled \
            else paged_attention.paged_attention_update
        return update(q, k, v, cache, 1, *meta, window=WINDOW)

    meta = (on((8, ), jnp.int32), ) * 3 if tiled else \
        (on((tokens, ), jnp.int32), on((tokens, ), jnp.int32), on((tokens, ), jnp.bool_))
    compiled = jax.jit(step, donate_argnums=(3, )).lower(
        on((tokens, H, D), jnp.bfloat16), on((tokens, KVH, D), jnp.bfloat16),
        on((tokens, KVH, D), jnp.bfloat16),
        on((2, 2, 256, KVH, BS, D), jnp.bfloat16),
        on((8, WINDOW_MAX_BLOCKS), jnp.int32), *meta).compile()
    # ONE kernel a layer, whatever arms and phases it holds inside
    assert len(_kernel_calls(compiled.as_text(), "paged_attention_prefill" if tiled
                             else "paged_attention_update")) == 1


@pytest.fixture(scope="module")
def window_model():
    """``mistral-7b-serve-1chip``: Mistral-7B-v0.1 widths, 5 layers, window
    4096, contexts to 8192, over ``jax.eval_shape``d parameters."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import llama
    cfg = llama.LlamaConfig(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                            num_hidden_layers=WINDOW_LAYERS, num_attention_heads=32,
                            num_key_value_heads=8, rope_theta=10000.0,
                            max_position_embeddings=32768, model_type="mistral",
                            sliding_window=WINDOW, remat=False)
    abstract = jax.eval_shape(lambda: llama.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=WINDOW_MAX_BLOCKS * BS,
                                           max_ragged_batch_size=256,
                                           max_ragged_sequence_count=8), kv_block_size=BS)
    model = model_cls_for(cfg)(abstract, cfg, engine_config)
    assert model.attention_window == WINDOW
    return model, abstract


def _window_args(device, abstract, bucket):
    one = SingleDeviceSharding(device)
    tokens, seqs, max_blocks = bucket
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = _on(one, (WINDOW_LAYERS, 2, WINDOW_POOL_BLOCKS, KVH, BS, D), jnp.bfloat16)
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (seqs, 4 + max_blocks), jnp.int32)}
    return one, params, cache, batch


@pytest.mark.parametrize("bucket,kernel", [((8, 8, 128), "paged_attention_update"),
                                           ((256, 8, 128), "paged_attention_prefill")],
                         ids=["decode-bucket", "chunk-bucket"])
def test_window_model_put_program_fits_one_chip(v5e, window_model, bucket, kernel):
    """A window model takes the kernel like any other, and its 8.7 GiB pool is
    aliased through: one pool-sized copy would not fit beside it."""
    model, abstract = window_model
    assert model.attention_arm(bucket[0]) in ("paged_token", "paged_tiled")
    _, params, cache, batch = _window_args(v5e[0], abstract, bucket)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and kernel in text
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _pool_sized_results(text, cache.shape)


def test_window_model_decode_loop_program_fits_one_chip(v5e, window_model):
    model, abstract = window_model
    one, params, cache, batch = _window_args(v5e[0], abstract, (8, 8, 128))
    loop = functools.partial(model._decode_loop_impl, n_steps=8)
    compiled = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "paged_attention_update" in text
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _pool_sized_results(text, cache.shape)


# ---- window and full layers side by side, top-8 of 64 (PR 30) ----------------
MELLUM_LAYERS, MELLUM_POOL_BLOCKS, MELLUM_MAX_BLOCKS = 8, 17408, 256


@pytest.fixture(scope="module")
def mellum_model():
    """``mellum2-12b-a2.5b-serve-1chip``: Mellum-2's published widths, two
    periods of its layer pattern, contexts to 16384, over ``jax.eval_shape``d
    parameters."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import mellum
    cfg = mellum.MellumConfig(num_hidden_layers=MELLUM_LAYERS)
    abstract = jax.eval_shape(lambda: mellum.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=MELLUM_MAX_BLOCKS * BS,
                                           max_ragged_batch_size=256,
                                           max_ragged_sequence_count=8), kv_block_size=BS,
        expert_parallel={"capacity_factor": 8.0})
    model = model_cls_for(cfg)(abstract, cfg, engine_config)
    assert model.group_windows == (1024, 1024, 1024, 0) and model.head_dim == 128
    return model, abstract


def _mellum_args(device, model, abstract, bucket):
    one = SingleDeviceSharding(device)
    tokens, seqs, max_blocks = bucket
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = _on(one, (MELLUM_LAYERS // model.kv_groups, 2, MELLUM_POOL_BLOCKS, 4, BS, 128),
                jnp.bfloat16)
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (seqs, 4 + model.kv_groups * max_blocks), jnp.int32)}
    return one, params, cache, batch


@pytest.mark.parametrize("bucket,kernel", [((8, 8, 256), "paged_attention_update"),
                                           ((256, 8, 256), "paged_attention_prefill")],
                         ids=["decode-bucket", "chunk-bucket"])
def test_mellum_put_program_fits_one_chip(v5e, mellum_model, bucket, kernel):
    """Two kernel variants a program (window 1024 and none), each layer on its
    group's table and cache layer; 7.1 GiB of weights beside a 4.25 GiB pool
    that is aliased through."""
    model, abstract = mellum_model
    assert model.attention_arm(bucket[0]) in ("paged_token", "paged_tiled")
    _, params, cache, batch = _mellum_args(v5e[0], model, abstract, bucket)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and kernel in text
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _pool_sized_results(text, cache.shape)
    # the full chunk routes by sorting (PR 32): the grouped-matmul kernel with
    # the layer's bank an operand and no [tokens, experts, capacity] mask; the
    # decode bucket keeps the masks
    grouped = model.moe_path(bucket[0]) == "grouped"
    assert grouped == (bucket[0] == 256)
    assert ("grouped_matmul" in text) == grouped
    assert (f"[{bucket[0]},64,{bucket[0]}]" in text) != grouped


def test_mellum_decode_loop_program_fits_one_chip(v5e, mellum_model):
    model, abstract = mellum_model
    one, params, cache, batch = _mellum_args(v5e[0], model, abstract, (8, 8, 256))
    loop = functools.partial(model._decode_loop_impl, n_steps=8)
    compiled = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "paged_attention_update" in text
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _pool_sized_results(text, cache.shape)


# ---- the grouped matmul's ring of bank tiles, within the VMEM it states (PR 60) ----
@pytest.mark.parametrize("rows,G,K,N,out_dtype", [
    (2048, 64, 2304, 1792, jnp.bfloat16), (2048, 64, 896, 2304, jnp.float32),
    (128, 128, 2048, 2048, jnp.bfloat16), (128, 128, 1024, 2048, jnp.float32)],
    ids=["mellum-chunk-gate-up", "mellum-chunk-down", "trinity-decode-gate-up",
         "trinity-decode-down"])
def test_the_grouped_matmul_compiles_within_the_vmem_it_states(v5e, rows, G, K, N, out_dtype):
    """The bank stays in HBM and the kernel holds a ring of THREE bank tiles
    (interpret mode cannot see a buffer that does not fit): what the compiler
    says the kernel uses is no more than ``ring_vmem_bytes`` states, and where
    that passes three quarters of the compiler's own 16 MiB the call asks for
    the stated bytes and a quarter (``paged_attention.vmem_params``)."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm
    from deepspeed_tpu.ops.pallas.paged_attention import SCOPED_VMEM_BYTES
    on = functools.partial(_on, SingleDeviceSharding(v5e[0]))

    def projection(rows, bank, sizes):
        return gm.grouped_matmul(rows, bank, sizes, out_dtype)

    text = jax.jit(projection).lower(on((rows, K), jnp.bfloat16), on((G, K, N), jnp.bfloat16),
                                     on((G, ), jnp.int32)).compile().as_text()
    call, = _kernel_calls(text, "grouped_matmul")
    tn = gm.column_tile(K, N, 2)
    stated = gm.ring_vmem_bytes(K, tn, 2, jnp.dtype(out_dtype).itemsize)
    vmem = r'scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"'
    used, = re.findall('"used_' + vmem, call)
    asked = re.findall('"' + vmem, call)
    assert gm.RING_DEPTH * K * tn * 2 < int(used) <= stated
    if stated > SCOPED_VMEM_BYTES * 3 // 4:
        assert [int(a) for a in asked] == [stated * 5 // 4]
    else:
        assert not asked


# ---- sigmoid top-8 of 128 beside a shared expert, five layer groups (PR 34) ----
TRINITY_LAYERS, TRINITY_POOL_BLOCKS, TRINITY_MAX_BLOCKS = 5, 26624, 64


@pytest.fixture(scope="module")
def trinity_model():
    """``trinity-mini-serve-1chip``: Trinity-Mini's published widths, one dense
    layer and four expert layers in the pattern s, s, s, f, s, contexts to
    4096, over ``jax.eval_shape``d parameters."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import afmoe
    cfg = afmoe.AfmoeConfig(num_hidden_layers=TRINITY_LAYERS, num_dense_layers=1,
                            layer_types=afmoe.AfmoeConfig().layer_types[:TRINITY_LAYERS])
    abstract = jax.eval_shape(lambda: afmoe.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=TRINITY_MAX_BLOCKS * BS,
                                           max_ragged_batch_size=256,
                                           max_ragged_sequence_count=8), kv_block_size=BS,
        expert_parallel={"capacity_factor": 16.0})
    model = model_cls_for(cfg)(abstract, cfg, engine_config)
    assert model.group_windows == (2048, 2048, 2048, 0, 2048) and model.head_dim == 128
    return model, abstract


def _trinity_args(device, model, abstract, bucket):
    one = SingleDeviceSharding(device)
    tokens, seqs, max_blocks = bucket
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = _on(one, (1, 2, TRINITY_POOL_BLOCKS, 4, BS, 128), jnp.bfloat16)
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (seqs, 4 + model.kv_groups * max_blocks), jnp.int32)}
    return one, params, cache, batch


@pytest.mark.parametrize("bucket,kernel", [((8, 8, 64), "paged_attention_update"),
                                           ((32, 8, 64), "paged_attention_update"),
                                           ((256, 8, 64), "paged_attention_prefill")],
                         ids=["decode-bucket", "tail-bucket", "chunk-bucket"])
def test_trinity_put_program_fits_one_chip(v5e, trinity_model, bucket, kernel):
    """7.9 GiB of weights beside a 3.25 GiB pool that is aliased through, five
    block tables a sequence; the full chunk routes by sorting over 128 groups
    and so does the decode bucket (64 assignments in one row tile, at most 64
    of the 128 banks visited: PR 35), the 32-token tail of a prompt keeps the
    ``[32, 128, 32]`` masks."""
    model, abstract = trinity_model
    assert model.attention_arm(bucket[0]) in ("paged_token", "paged_tiled")
    _, params, cache, batch = _trinity_args(v5e[0], model, abstract, bucket)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and kernel in text
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _pool_sized_results(text, cache.shape)
    grouped = model.moe_path(bucket[0]) == "grouped"
    assert grouped == (bucket[0] != 32)
    assert ("grouped_matmul" in text) == grouped
    assert (f"[{bucket[0]},128,{bucket[0]}]" in text) != grouped


def test_trinity_decode_loop_program_fits_one_chip(v5e, trinity_model):
    """The grouped kernel with its dynamic visit count inside the loop's scan."""
    model, abstract = trinity_model
    one, params, cache, batch = _trinity_args(v5e[0], model, abstract, (8, 8, 64))
    loop = functools.partial(model._decode_loop_impl, n_steps=8)
    compiled = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "paged_attention_update" in text and "grouped_matmul" in text
    assert "[8,128,8]" not in text  # no mask of the capacity path
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _pool_sized_results(text, cache.shape)


# ---- deepseek-v32-serve-1chip: the latent cache's kernels and whole programs (PR 40) ----
DEEPSEEK_POOL_BLOCKS, DEEPSEEK_BLOCK = 2752, 128


@pytest.fixture(scope="module")
def deepseek_model():
    """``deepseek-v32-serve-1chip``: DeepSeek-V3.2's published widths, one dense
    layer and four expert layers, 16 of the 256 routed experts held, an eighth
    of the vocabulary, contexts to 8192, over ``jax.eval_shape``d parameters."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import deepseek_v32
    cfg = deepseek_v32.DeepseekV32Config(
        num_hidden_layers=5, first_k_dense_replace=1, vocab_size=16160, experts_held=16,
        expert_rank=5, rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                                     "original_max_position_embeddings": 4096})
    abstract = jax.eval_shape(lambda: deepseek_v32.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=8192, max_ragged_batch_size=256,
                                           max_ragged_sequence_count=8),
        kv_block_size=DEEPSEEK_BLOCK, use_paged_kernel=True,
        expert_parallel={"capacity_factor": 32.0})
    model = model_cls_for(cfg)(abstract, cfg, engine_config)
    assert model.kv_state_widths == (640, 128) and model.min_table_bucket == 64
    return model, abstract


def _deepseek_args(device, model, abstract, bucket):
    one = SingleDeviceSharding(device)
    tokens, seqs, max_blocks = bucket
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = tuple(_on(one, (5, DEEPSEEK_POOL_BLOCKS, DEEPSEEK_BLOCK, width), jnp.bfloat16)
                  for width in model.kv_state_widths)
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (seqs, 4 + max_blocks), jnp.int32)}
    return one, params, cache, batch


def _walks_a_window(text, layers, window, width=6144, banks="16,(6144,4096|2048,6144)"):
    """A share's row window in a compiled program (PR 64): a loop an expert
    layer whose body calls the grouped matmul over ``window`` sorted rows (the
    second projection's float32 ``[window, width]``) and no call over more (a
    bucket of one row tile has no window and no loop of its own: ``window`` is
    then the tile), and no copy of a bank (``banks``: their shapes) into a loop."""
    kernels = {int(m) for m in re.findall(rf"= f32\[(\d+),{width}\]\S* custom-call\(.*grouped_matmul",
                                          text)}
    bank_copies = [line for line in text.splitlines()
                   if re.search(rf"= bf16\[{banks}\]\S* copy\(", line)]
    return (len(re.findall(r" while\(", text)) >= layers and " conditional(" not in text
            and kernels == {window} and not bank_copies)


def _latent_pool_copies(text):
    """``copy`` instructions whose result is a whole latent or index pool."""
    import re
    return [line.strip()[:160] for line in text.splitlines()
            if re.search(rf"= bf16\[5,{DEEPSEEK_POOL_BLOCKS},{DEEPSEEK_BLOCK},\d+\]\S* copy\(", line)]


@pytest.mark.parametrize("bucket,kernel,selects", [
    ((8, 8, 64), "latent_paged_attention_token", True),
    ((256, 8, 64), "latent_paged_attention_tiled", True),
    ((256, 8, 16), "latent_paged_attention_tiled", False)],
    ids=["decode-bucket", "chunk-bucket", "chunk-under-index-topk"])
def test_deepseek_put_program_fits_one_chip(v5e, deepseek_model, bucket, kernel, selects):
    """8.65 GiB of weights beside two pools (2.52 GiB) that the scatters update in
    place and the kernels read: no copy of a pool; every bucket routes by sorting
    over the 16 held banks; a table of no more than ``index_topk`` keys carries no
    indexer scores."""
    model, abstract = deepseek_model
    assert model.attention_arm(bucket[0]) == kernel.replace("_paged_attention", "")
    assert model.selects(bucket[2]) == selects and model.moe_path(bucket[0]) == "grouped"
    _, params, cache, batch = _deepseek_args(v5e[0], model, abstract, bucket)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and kernel in text and "grouped_matmul" in text
    assert ("latent_index_scores" in text) == selects
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _latent_pool_copies(text)
    # 16 of 256 experts held: a 256-token bucket walks its 2,048 sorted rows 512 at a time as far
    # as what landed reaches; the decode bucket's 128 rows are one tile, walked whole as before
    assert _walks_a_window(text, layers=4, window=512 if bucket[0] == 256 else 128, width=7168,
                           banks="16,(7168,4096|2048,7168)")


def test_deepseek_decode_loop_program_fits_one_chip(v5e, deepseek_model):
    model, abstract = deepseek_model
    one, params, cache, batch = _deepseek_args(v5e[0], model, abstract, (8, 8, 64))
    loop = functools.partial(model._decode_loop_impl, n_steps=8)
    compiled = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "latent_paged_attention_token" in text and "latent_index_scores" in text
    assert "grouped_matmul" in text
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _latent_pool_copies(text)


# ---- nemotron3-nano-serve-1chip: a per-sequence state group beside K/V (PR 43) ----
NEMOTRON_SLOTS, NEMOTRON_BLOCKS, NEMOTRON_BLOCK = 128, 1536, 128
NEMOTRON_TAILS = (8, 2304)  # the 3 x 6144 convolution tails a sequence, folded (ssm.conv_slot)


@pytest.fixture(scope="module")
def nemotron_model():
    """``nemotron3-nano-serve-1chip``: Nemotron-3-Nano-30B-A3B's published
    widths, the pattern's first 14 blocks (6 Mamba-2, 6 expert, 2 attention), 64
    of the 128 routed experts held, half the vocabulary, contexts to 4096, over
    ``jax.eval_shape``d parameters."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import nemotron_h
    cfg = nemotron_h.NemotronHConfig(num_hidden_layers=14,
                                     hybrid_override_pattern="MEMEM*EMEMEM*E",
                                     vocab_size=65536, experts_held=64, expert_rank=0)
    abstract = jax.eval_shape(lambda: nemotron_h.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=4096, max_ragged_batch_size=256,
                                           max_ragged_sequence_count=8,
                                           max_tracked_sequences=NEMOTRON_SLOTS),
        kv_block_size=NEMOTRON_BLOCK, use_paged_kernel=True,
        expert_parallel={"capacity_factor": 22.0})
    model = model_cls_for(cfg)(abstract, cfg, engine_config)
    assert model.num_kv_layers == 2 and model.min_table_bucket == 32
    assert [(s.name, s.layers, s.shape, s.dtype) for s in model.sequence_state] == [
        ("ssm", 6, (64, 64, 128), "float32"), ("conv", 6, NEMOTRON_TAILS, "bfloat16")]
    return model, abstract


def _nemotron_args(device, model, abstract, bucket):
    one = SingleDeviceSharding(device)
    tokens, seqs, max_blocks = bucket
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = (_on(one, (2, 2, NEMOTRON_BLOCKS, 2, NEMOTRON_BLOCK, 128), jnp.bfloat16),
             _on(one, (6, NEMOTRON_SLOTS, 64, 64, 128), jnp.float32),
             _on(one, (6, NEMOTRON_SLOTS) + NEMOTRON_TAILS, jnp.bfloat16))
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (seqs, 4 + max_blocks + 1), jnp.int32)}
    return one, params, cache, batch


def _state_sized_results(text, rows, per_row=64 * 64 * 128, pool=(6, NEMOTRON_SLOTS)):
    """Instructions whose result holds a Mamba-2 state a ROW of the batch
    (``rows`` x ``per_row`` float32 or more: Nemotron's 64 x 64 x 128 unless
    told) and is no pool (leading dimensions ``pool``): the two forms keep a
    state a sequence, never a state a token."""
    import re
    out = []
    for line in text.splitlines():
        m = re.search(r"= f32\[([\d,]+)\]", line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        if tuple(dims[:2]) == tuple(pool):
            continue  # the pool itself: _pool_shaped_results' to judge
        if int(np.prod(dims)) >= rows * per_row:
            out.append(line.strip()[:160])
    return out


def _pool_shaped_results(text, pool):
    """Instructions with a result (or a member of a tuple result) that has the
    state pool's leading dimensions ``pool`` = (mixers, slots, heads, head) in
    float32 — the pool, or a piece of it whatever its last dimension — and that
    are not the pool passing through: parameters, tuples and their elements,
    bitcasts, a loop that carries it, a kernel's result that IS its operand
    (aliased: ``ssm_step_in_place``, ``ssm_store_in_place``, ``kda_step_in_place``,
    ``kda_chunk_in_place``) and the update of
    one slot in place (a ``dynamic-update-slice``, alone or the root of its
    fusion). XLA cuts a gather of rows above 2 MiB by first slicing its operand:
    a pass over the whole pool a mixer that PR 47's check, which skipped
    pool-shaped results as "updated in place", could not see (PERF.md section 6,
    PR 48)."""
    import re
    roots = dict(re.findall(r"^%(\S+) \([^\n]*\{\n(?:(?!^\}).*\n)*?\s*ROOT %\S+ = \S+ ([a-z][a-z\-]*)\(",
                            text, flags=re.M))
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z\-]*)\(", line)
        if not m or m.group(2) in ("parameter", "tuple", "get-tuple-element", "bitcast", "while",
                                   "dynamic-update-slice"):
            continue
        shapes = [tuple(int(d) for d in dims.split(","))
                  for dims in re.findall(r"f32\[([\d,]+)\]", m.group(1))]
        if not any(shape[:len(pool)] == tuple(pool) and len(shape) == len(pool) + 1 for shape in shapes):
            continue
        if m.group(2) == "custom-call" and "output_to_operand_aliasing" in line \
                and re.search(r"(ssm_(store|step)|kda_(step|chunk))_in_place", line):
            continue
        called = re.search(r"calls=%(\S+?)[,\s}]", line)
        if m.group(2) == "fusion" and called and roots.get(called.group(1)) == "dynamic-update-slice":
            continue
        out.append(line.strip()[:200])
    return out


def _conv_pool_results(text, pool):
    """Instructions of a compiled program whose result (or a member of a tuple
    result) has the CONV pool's shape ``pool`` in bfloat16, in any layout and
    memory space, and that are not the pool passing through (parameters, tuples
    and their elements, bitcasts, a loop that carries it) or the slot-copy
    kernel's result that IS its operand (``ssm_store_in_place``, aliased). Until
    PR 53 the pool was ``[mixers, slots, 3, C]``: XLA re-laid it around every
    chunk, carried it into vector memory and back twice a step
    (``fusion.*.remat_(un)compressed``: 6.2 % of Nemotron's device time) and
    scattered into it a block (PERF.md section 6, PR 53)."""
    import re
    dims = ",".join(str(d) for d in pool)
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z\-]*)\(", line)
        if not m or m.group(2) in ("parameter", "tuple", "get-tuple-element", "bitcast", "while"):
            continue
        if f"bf16[{dims}]" not in m.group(1):
            continue
        if m.group(2) == "custom-call" and "output_to_operand_aliasing" in line \
                and "ssm_store_in_place" in line:
            continue
        out.append(line.strip()[:200])
    return out


def _tails_by_the_kernels(text, mixers, scope="ssm/conv"):
    """One ``ssm_load`` and one ``ssm_store_in_place`` a mixer, under
    ``ssm/conv`` (the convolution's scope: nothing of it under ``ssm/step`` or
    ``ssm/scan``, whose rooflines count the recurrence's and the scan's work),
    or under the ``scope`` another family's convolution has."""
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    loads = [line for line in calls if "/ssm_load/pallas_call" in line]
    stores = [line for line in calls if "/ssm_store_in_place/pallas_call" in line]
    return len(loads) == len(stores) == mixers \
        and all(f"/{scope}/ssm_" in line for line in loads + stores)


def _step_states(text, seqs, heads, head, state, groups):
    """Float32 results shaped like the step's states, ``[seqs, heads, head,
    state]`` or the scan's ``[seqs, groups, heads / groups, head, state]``,
    whatever follows ``seqs``' place: since PR 49 a ``put`` step's scan visits a
    state in its slot, and the one in hand is ``[1, ...]``."""
    import re
    forms = (f"{seqs},{heads},{head},{state}", f"{seqs},{groups},{heads // groups},{head},{state}")
    return [line.strip()[:160] for line in text.splitlines()
            if any(re.search(rf"= \(?f32\[{form}\]", line) for form in forms)]


def _scans_in_the_pool(text, mixers):
    """The ``put`` program's scan by segment: one ``ssm_step_in_place`` a mixer
    (the one-row segments) and one loop of visits a mixer, under ``ssm/scan``
    (the scope the scan's roofline readers sum), and neither slot-copy kernel
    under that scope (since PR 53 they move the convolution's tails, under
    ``ssm/conv``: ``_tails_by_the_kernels``)."""
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "ssm/scan/" in line and "ssm_step_in_place" in line]
    loops = [line for line in text.splitlines()
             if " while(" in line and 'op_name="jit(_forward_impl)/ssm/scan/while"' in line]
    copies = [line for line in text.splitlines() if "tpu_custom_call" in line
              and ("/ssm/scan/ssm_load/" in line or "/ssm/scan/ssm_store_in_place/" in line)]
    return len(kernels) == mixers and len(loops) == mixers and not copies


@pytest.mark.parametrize("bucket,kernel,scope", [
    ((8, 8, 32), "paged_attention_update", "ssm/scan"),
    ((256, 8, 32), "paged_attention_prefill", "ssm/scan")],
    ids=["decode-bucket", "chunk-bucket"])
def test_nemotron_put_program_fits_one_chip(v5e, nemotron_model, bucket, kernel, scope):
    """8.6 GiB of weights beside the K/V array and the two state pools (1.5 GiB
    of float32 state in 128 slots): both grids of the paged kernel at 32 query
    heads over 2 K/V heads, the grouped matmul over the 64 held banks at the
    banks' 1920 lanes, the chunked scan at the published widths with the state
    a SEQUENCE (nothing 9 x a state, let alone 256)."""
    model, abstract = nemotron_model
    assert model.moe_path(bucket[0]) == "grouped"
    _, params, cache, batch = _nemotron_args(v5e[0], model, abstract, bucket)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and kernel in text and "grouped_matmul" in text
    assert scope in text and "ssm/step" not in text
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _state_sized_results(text, rows=9)
    assert _scans_in_the_pool(text, mixers=6)
    assert not _pool_shaped_results(text, (6, NEMOTRON_SLOTS, 64, 64))
    assert not _step_states(text, 8, 64, 64, 128, 8)
    assert _tails_by_the_kernels(text, mixers=6)
    assert not _conv_pool_results(text, (6, NEMOTRON_SLOTS) + NEMOTRON_TAILS)
    out = jax.eval_shape(model._forward_impl, params, cache, batch)
    assert [(c.shape, c.dtype) for c in out[1]] == [(c.shape, c.dtype) for c in cache]


def test_nemotron_decode_loop_program_fits_one_chip(v5e, nemotron_model):
    """The recurrence inside ``decode_loop``'s scan: the pools ride in the
    carry and come out in the shapes and dtypes they went in. Since PR 44 a
    block's recurrence is ONE kernel over the pool itself, under ``ssm/step``:
    no row's state exists outside the pool (the gather was 8 of them). Since PR
    53 the convolution's tails leave and enter their slots by the slot-copy
    kernels: no operation of the chunk makes an array of the conv pool's shape
    but the kernels' aliased pool (it was re-laid around the chunk and carried
    through vector memory twice a step)."""
    model, abstract = nemotron_model
    one, params, cache, batch = _nemotron_args(v5e[0], model, abstract, (8, 8, 32))
    loop = functools.partial(model._decode_loop_impl, n_steps=8)
    compiled = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "paged_attention_update" in text and "grouped_matmul" in text
    assert "ssm/step" in text and "ssm/scan" not in text
    kernels = _kernel_calls(text, "ssm_step_in_place")
    assert len(kernels) == 6 and all("ssm/step" in line for line in kernels), kernels
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _state_sized_results(text, rows=8)
    assert _tails_by_the_kernels(text, mixers=6)
    assert not _conv_pool_results(text, (6, NEMOTRON_SLOTS) + NEMOTRON_TAILS)
    out = jax.eval_shape(loop, params, cache, batch)
    assert [(c.shape, c.dtype) for c in out[1]] == [(c.shape, c.dtype) for c in cache]


# ---- falcon-h1-34b-serve-1chip: K/V AND a per-sequence state in every layer (PR 47) ----
FALCON_LAYERS, FALCON_SLOTS, FALCON_BLOCKS, FALCON_BLOCK, FALCON_SEQS = 6, 64, 704, 128, 32
FALCON_TAILS = (8, 1920)  # the 3 x 5120 convolution tails a sequence, folded (ssm.conv_slot)


@pytest.fixture(scope="module")
def falcon_h1_model():
    """``falcon-h1-34b-serve-1chip``: Falcon-H1-34B-Instruct's published widths
    and whole vocabulary, 6 of 72 layers, contexts to 2048, 32 sequences a
    step, over ``jax.eval_shape``d parameters."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import falcon_h1
    cfg = falcon_h1.FalconH1Config(num_hidden_layers=FALCON_LAYERS)
    abstract = jax.eval_shape(lambda: falcon_h1.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=2048, max_ragged_batch_size=256,
                                           max_ragged_sequence_count=FALCON_SEQS,
                                           max_tracked_sequences=FALCON_SLOTS),
        kv_block_size=FALCON_BLOCK, use_paged_kernel=True)
    model = model_cls_for(cfg)(abstract, cfg, engine_config)
    assert model.num_kv_layers == FALCON_LAYERS and model.min_table_bucket == 16
    assert model.min_sequence_bucket == FALCON_SEQS
    assert [(s.name, s.layers, s.shape, s.dtype) for s in model.sequence_state] == [
        ("ssm", FALCON_LAYERS, (32, 128, 256), "float32"),
        ("conv", FALCON_LAYERS, FALCON_TAILS, "bfloat16")]
    return model, abstract


def _falcon_h1_args(device, abstract, bucket):
    one = SingleDeviceSharding(device)
    tokens, seqs, max_blocks = bucket
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = (_on(one, (FALCON_LAYERS, 2, FALCON_BLOCKS, 4, FALCON_BLOCK, 128), jnp.bfloat16),
             _on(one, (FALCON_LAYERS, FALCON_SLOTS, 32, 128, 256), jnp.float32),
             _on(one, (FALCON_LAYERS, FALCON_SLOTS) + FALCON_TAILS, jnp.bfloat16))
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (seqs, 4 + max_blocks + 1), jnp.int32)}
    return params, cache, batch


def _falcon_state_sized_results(text, rows):
    return _state_sized_results(text, rows, per_row=32 * 128 * 256,
                                pool=(FALCON_LAYERS, FALCON_SLOTS))


@pytest.mark.parametrize("bucket,kernel", [((32, 32, 16), "paged_attention_update"),
                                           ((256, 32, 16), "paged_attention_prefill")],
                         ids=["smallest-bucket", "chunk-bucket"])
def test_falcon_h1_put_program_fits_one_chip(v5e, falcon_h1_model, bucket, kernel):
    """9.8 GiB of weights beside 1 GiB of K/V and 1.5 GiB of float32 state in 64
    slots: both grids of the paged kernel at FIVE query heads a K/V head, the
    chunked scan at (heads, head, state) = (32, 128, 256) with the state a
    SEQUENCE (32 of them read from their slots, never a state a token), every
    layer writing its own layer of the K/V array and its own slot pools: a
    state scanned IN its slot (the one-row segments by the step kernel over the
    pool itself, a longer one a visit a chunk), nothing in the program shaped
    like the pool or a piece of it (PR 48) or like the step's 32 states (PR 49)."""
    model, abstract = falcon_h1_model
    assert model._synthetic_batch()["seq_meta"].shape[0] == FALCON_SEQS
    params, cache, batch = _falcon_h1_args(v5e[0], abstract, bucket)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and kernel in text
    assert "ssm/scan" in text and "ssm/step" not in text
    # 12.4 GiB: PR 47's gather held a second pool's worth of temporaries (1.65 GiB: 14.0 in
    # all), PR 48's copy of the step's 32 states and its re-laying 0.31 (12.7); a state
    # visited in its slot leaves under 0.1 GiB of temporaries
    assert _device_bytes(compiled) < 0.79 * HBM_BYTES
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * 2**30
    assert not _falcon_state_sized_results(text, rows=4 * FALCON_SEQS)
    assert _scans_in_the_pool(text, mixers=FALCON_LAYERS)
    assert not _pool_shaped_results(text, (FALCON_LAYERS, FALCON_SLOTS, 32, 128))
    assert not _step_states(text, FALCON_SEQS, 32, 128, 256, 2)
    assert _tails_by_the_kernels(text, mixers=FALCON_LAYERS)
    assert not _conv_pool_results(text, (FALCON_LAYERS, FALCON_SLOTS) + FALCON_TAILS)
    out = jax.eval_shape(model._forward_impl, params, cache, batch)
    assert [(c.shape, c.dtype) for c in out[1]] == [(c.shape, c.dtype) for c in cache]


def test_falcon_h1_decode_loop_program_fits_one_chip(v5e, falcon_h1_model):
    """The real-width ``decode_loop`` program of 32 rows: one ``ssm_step_in_place``
    a layer at (32, 128, 256, 2) over the pool itself (no row's state outside
    it), the per-token paged kernel at five queries a K/V head, and the pools
    handed back in the types they came in."""
    model, abstract = falcon_h1_model
    params, cache, batch = _falcon_h1_args(v5e[0], abstract, (32, 32, 16))
    loop = functools.partial(model._decode_loop_impl, n_steps=8)
    compiled = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "paged_attention_update" in text and "ssm/scan" not in text
    kernels = _kernel_calls(text, "ssm_step_in_place")
    assert len(kernels) == FALCON_LAYERS and all("ssm/step" in line for line in kernels), kernels
    assert _device_bytes(compiled) < 0.95 * HBM_BYTES
    assert not _falcon_state_sized_results(text, rows=8)
    assert _tails_by_the_kernels(text, mixers=FALCON_LAYERS)
    assert not _conv_pool_results(text, (FALCON_LAYERS, FALCON_SLOTS) + FALCON_TAILS)
    out = jax.eval_shape(loop, params, cache, batch)
    assert [(c.shape, c.dtype) for c in out[1]] == [(c.shape, c.dtype) for c in cache]


# ---- sdar-30b-a3b-serve-1chip: the block mask on the tile grid and the block loop (PR 50) ----
SDAR_LAYERS, SDAR_POOL_BLOCKS, SDAR_MAX_BLOCKS, SDAR_SEQS = 7, 2304, 32, 32


@pytest.mark.parametrize("tokens,block", [(128, 4), (256, 4), (128, 16)],
                         ids=["block-step-of-32-sequences", "prompt-chunk-bucket",
                              "a-block-of-16"])
def test_the_tile_grid_under_a_block_mask_compiles(v5e, tokens, block):
    """``paged_attention_prefill`` with ``block`` 4 at SDAR's heads (32 queries
    over 4 K/V heads of 128: eight a K/V head, so a few-row pass walks 32 rows
    a K/V head) at the cell's two token buckets — 128 rows are a block step of
    32 sequences, every pass the few-row arm's — and with a block of 16 (128
    rows a K/V head, placed by one select); ONE kernel still. The token grid
    refuses the same mask by name before anything is lowered."""
    from deepspeed_tpu.ops.pallas import paged_attention
    on = functools.partial(_on, SingleDeviceSharding(v5e[0]))

    def step(q, k, v, cache, *meta):
        return paged_attention.paged_attention_prefill(q, k, v, cache, 1, *meta, block=block)

    compiled = jax.jit(step, donate_argnums=(3, )).lower(
        on((tokens, 32, 128), jnp.bfloat16), on((tokens, 4, 128), jnp.bfloat16),
        on((tokens, 4, 128), jnp.bfloat16), on((2, 2, 256, 4, BS, 128), jnp.bfloat16),
        on((SDAR_SEQS, SDAR_MAX_BLOCKS), jnp.int32), *(on((SDAR_SEQS, ), jnp.int32), ) * 3).compile()
    assert len(_kernel_calls(compiled.as_text(), "paged_attention_prefill")) == 1
    with pytest.raises(ValueError, match="per-token grid.*block mask"):
        paged_attention.paged_attention_update(
            jnp.zeros((8, 32, 128)), jnp.zeros((8, 4, 128)), jnp.zeros((8, 4, 128)),
            jnp.zeros((1, 2, 4, 4, BS, 128)), 0, jnp.zeros((8, 4), jnp.int32),
            jnp.zeros(8, jnp.int32), jnp.zeros(8, jnp.int32), jnp.ones(8, bool), block=4)


@pytest.fixture(scope="module")
def sdar_model():
    """``sdar-30b-a3b-serve-1chip``: SDAR-30B-A3B-Chat's published widths at seven
    layers, contexts to 2048, one sequence bucket of 32, over
    ``jax.eval_shape``d parameters."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import sdar_moe
    cfg = sdar_moe.SdarMoeConfig(num_hidden_layers=SDAR_LAYERS)
    abstract = jax.eval_shape(lambda: sdar_moe.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=SDAR_MAX_BLOCKS * BS,
                                           max_ragged_batch_size=256,
                                           max_ragged_sequence_count=SDAR_SEQS),
        kv_block_size=BS, expert_parallel={"capacity_factor": 16.0})
    model = model_cls_for(cfg)(abstract, cfg, engine_config)
    assert model.attention_block == 4 and model.head_dim == 128 and model.group_windows == (0, )
    assert (model.min_sequence_bucket, model.min_table_bucket, model.min_token_bucket) == \
        (SDAR_SEQS, SDAR_MAX_BLOCKS, 128)
    return model, abstract


def _sdar_args(device, model, abstract, tokens):
    one = SingleDeviceSharding(device)
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = _on(one, (SDAR_LAYERS, 2, SDAR_POOL_BLOCKS, 4, BS, 128), jnp.bfloat16)
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (SDAR_SEQS, 4 + SDAR_MAX_BLOCKS), jnp.int32)}
    return one, params, cache, batch


@pytest.mark.parametrize("tokens", [128, 256], ids=["block-step-bucket", "chunk-bucket"])
def test_sdar_put_program_fits_one_chip(v5e, sdar_model, tokens):
    """9.3 GiB of weights beside a 2 GiB pool aliased through; EVERY bucket on
    the tile grid (a block step of 128 rows too) and routed by sorting."""
    model, abstract = sdar_model
    assert model.attention_arm(tokens) == model.attention_arm(8) == "paged_tiled"
    _, params, cache, batch = _sdar_args(v5e[0], model, abstract, tokens)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "paged_attention_prefill" in text and "paged_attention_update" not in text
    assert model.moe_path(tokens) == "grouped" and "grouped_matmul" in text
    assert _device_bytes(compiled) < 0.85 * HBM_BYTES
    assert not _pool_sized_results(text, cache.shape)


def test_sdar_block_loop_program_fits_one_chip(v5e, sdar_model):
    """Two blocks a chunk, nine forwards: a scan of four denoise forwards
    (every row unembedded, the unmasking's ``top_k``), the FUSED forward of 256
    rows (the first block's commit beside the second's first denoise forward,
    the head on the second's 128 rows) with three denoise forwards behind it,
    and the last block's commit forward with no head; ids and int8 steps a row
    of ``[32, 8]``. The forward is TRACED three times, not four: the B-row
    denoise forward's two uses are one jit's (a kernel's trace is part of every
    warm start: PERF.md section 6, PR 51), so the lowered program holds three
    forwards' kernel call sites."""
    model, abstract = sdar_model
    one, params, cache, batch = _sdar_args(v5e[0], model, abstract, 128)
    forward = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).as_text()
    batch["masked"] = _on(one, (128, ), jnp.int32)
    loop = functools.partial(model._block_loop_impl, n_blocks=2)
    lowered = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch)
    # (``_projection``: the jit around the ``grouped_matmul`` kernel, a call a projection;
    # nothing reads the stream behind the tail commit's last attention: that layer's experts
    # are not in the program)
    for kernel, dead in (("paged_attention_prefill", 0), ("_projection", 1)):
        a_forward = _call_sites(forward, kernel)
        assert a_forward >= SDAR_LAYERS
        assert _call_sites(lowered.as_text(), kernel) == \
            3 * a_forward - dead * a_forward // SDAR_LAYERS, kernel
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "paged_attention_prefill" in text and "grouped_matmul" in text
    assert "paged_attention_update" not in text
    out = jax.eval_shape(loop, params, cache, batch)
    assert (out[0].shape, out[0].dtype, out[1].shape, out[1].dtype) == \
        ((32, 8), jnp.int32, (32, 8), jnp.int8)
    # the banks each block's forwards read and the kernel's visits of them, a layer
    assert out[4].shape == (2, SDAR_LAYERS, 2)
    assert _device_bytes(compiled) < 0.85 * HBM_BYTES
    assert not _pool_sized_results(text, cache.shape)
    # one block: no fused forward, the program it always was (a scan of four and a commit)
    one_block = jax.jit(functools.partial(model._block_loop_impl, n_blocks=1),
                        donate_argnums=(1, )).lower(params, cache, batch).as_text()
    assert _call_sites(one_block, "paged_attention_prefill") == \
        2 * _call_sites(forward, "paged_attention_prefill")


# ---- solar-open2-250b-serve-1chip: a delta-rule state of 4 MiB a sequence a layer (PR 54) ----
SOLAR_SLOTS, SOLAR_BLOCKS, SOLAR_BLOCK, SOLAR_TABLE = 128, 4096, 128, 64
SOLAR_TAILS = (8, 9216)  # q, k and v's 3 x 24576 convolution tails a sequence, folded


@pytest.fixture(scope="module")
def solar_model():
    """``solar-open2-250b-serve-1chip``: Solar-Open2-250B's published widths, one
    period of four layers (GQA, KDA, KDA, KDA), 40 of the 320 routed experts
    held, an eighth of the vocabulary, contexts to 8192, over
    ``jax.eval_shape``d parameters."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import solar_open2
    cfg = solar_open2.SolarOpen2Config(num_hidden_layers=4, vocab_size=24576, experts_held=40,
                                       expert_rank=0)
    abstract = jax.eval_shape(lambda: solar_open2.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=8192, max_ragged_batch_size=256,
                                           max_ragged_sequence_count=8,
                                           max_tracked_sequences=SOLAR_SLOTS),
        kv_block_size=SOLAR_BLOCK, use_paged_kernel=True,
        expert_parallel={"capacity_factor": 40.0})
    model = model_cls_for(cfg)(abstract, cfg, engine_config)
    assert model.num_kv_layers == 1 and model.min_table_bucket == SOLAR_TABLE
    assert [(s.name, s.layers, s.shape, s.dtype) for s in model.sequence_state] == [
        ("kda", 3, (64, 128, 128), "float32"), ("conv", 3, SOLAR_TAILS, "bfloat16")]
    return model, abstract


def _solar_args(device, abstract, tokens):
    one = SingleDeviceSharding(device)
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = (_on(one, (1, 2, SOLAR_BLOCKS, 8, SOLAR_BLOCK, 128), jnp.bfloat16),
             _on(one, (3, SOLAR_SLOTS, 64, 128, 128), jnp.float32),
             _on(one, (3, SOLAR_SLOTS) + SOLAR_TAILS, jnp.bfloat16))
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (8, 4 + SOLAR_TABLE + 1), jnp.int32)}
    return params, cache, batch


def _solar_states(text):
    """Float32 results that hold MORE than one delta-rule state (``[..., 64,
    128, 128]`` with two or more ahead of it) and are no pool: both forms keep
    a state a sequence in its slot, and the one in hand is ``[1, 1, ...]``."""
    import re
    out = []
    for line in text.splitlines():
        for dims in re.findall(r"= \(?f32\[([\d,]+),64,128,128\]", line):
            ahead = [int(d) for d in dims.split(",")]
            if ahead != [3, SOLAR_SLOTS] and int(np.prod(ahead)) > 1:
                out.append(line.strip()[:160])
    return out


def _solar_no_pass_over_a_pool(text):
    """No result shaped like the state pool or a piece of it but the kernel's
    aliased pool and a slot's update in place (``_pool_shaped_results``), none
    shaped like the conv pool but the slot-copy kernel's aliased pool, and the
    tails moved by the two kernels under ``kda/conv``."""
    return not _pool_shaped_results(text, (3, SOLAR_SLOTS, 64, 128)) \
        and not _conv_pool_results(text, (3, SOLAR_SLOTS) + SOLAR_TAILS) \
        and _tails_by_the_kernels(text, mixers=3, scope="kda/conv")


def test_solar_put_program_fits_one_chip(v5e, solar_model):
    """The chunk bucket's ``put`` program: 6.2 GiB of weights beside 2 GiB of
    K/V and 1.6 GiB of state in 128 slots of 3 x 4 MiB. The tile grid of the
    paged kernel at eight query heads a K/V head, the grouped matmul over the
    40 held SwiGLU banks, and the scan by segment under ``kda/scan``: one
    ``kda_step_in_place`` a delta-rule layer (the one-row segments) and, since
    PR 55, ONE ``kda_chunk_in_place`` a layer for the step's visits of the
    chunked form, the pool aliased through both and no loop of visits left: a
    state is visited IN its slot (nothing 2 x a state, let alone 256, and no
    whole state outside the kernels). The compiler granting the kernel its
    vector memory is what compiling says."""
    model, abstract = solar_model
    assert model.moe_path(256) == "grouped"
    params, cache, batch = _solar_args(v5e[0], abstract, 256)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "paged_attention_prefill" in text and "grouped_matmul" in text
    assert "kda/scan" in text and "kda/step" not in text and "attn/gate" in text
    for name in ("kda_step_in_place", "kda_chunk_in_place"):
        # by the call's own name: the pool one kernel leaves is the other's operand
        kernels = [line for line in _kernel_calls(text, name) if line.strip().startswith(f"%{name}")]
        assert len(kernels) == 3 and all("kda/scan" in line for line in kernels), (name, kernels)
        assert all("output_to_operand_aliasing" in line for line in kernels), (name, kernels)
    assert not [line for line in text.splitlines() if " while(" in line and "kda/scan" in line]
    whole_states = [line.strip()[:160] for line in text.splitlines()
                    if re.search(r"= \(?f32\[(1,)*64,128,128\]", line)]
    assert not whole_states, whole_states
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _solar_states(text)
    assert _solar_no_pass_over_a_pool(text)
    out = jax.eval_shape(model._forward_impl, params, cache, batch)
    assert [(c.shape, c.dtype) for c in out[1]] == [(c.shape, c.dtype) for c in cache]


def test_solar_decode_loop_program_fits_one_chip(v5e, solar_model):
    """The recurrence inside ``decode_loop``'s scan: ONE ``kda_step_in_place`` a
    delta-rule layer over the pool itself, under ``kda/step`` (no row's 4 MiB
    state outside the pool: a gather of rows above 2 MiB slices the whole
    pool), the per-token paged kernel at eight queries a K/V head, and the
    pools handed back in the types they came in."""
    model, abstract = solar_model
    assert model.moe_path(8) == "grouped"
    params, cache, batch = _solar_args(v5e[0], abstract, 8)
    loop = functools.partial(model._decode_loop_impl, n_steps=8)
    compiled = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "paged_attention_update" in text and "grouped_matmul" in text
    assert "kda/step" in text and "kda/scan" not in text
    kernels = _kernel_calls(text, "kda_step_in_place")
    assert len(kernels) == 3 and all("kda/step" in line for line in kernels), kernels
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert not _solar_states(text)
    assert _solar_no_pass_over_a_pool(text)
    out = jax.eval_shape(loop, params, cache, batch)
    assert [(c.shape, c.dtype) for c in out[1]] == [(c.shape, c.dtype) for c in cache]


# ---- kimi-linear-48b-a3b-serve-1chip: a latent pool and a slot pool in ONE cache (PR 56) ----
KIMI_SLOTS, KIMI_BLOCKS, KIMI_BLOCK, KIMI_TABLE = 64, 8192, 128, 256
KIMI_TAILS = (8, 4608)  # q, k and v's 3 x 12288 convolution tails a sequence, folded


@pytest.fixture(scope="module")
def kimi_model():
    """``kimi-linear-48b-a3b-serve-1chip``: Kimi-Linear-48B-A3B's published
    widths, two periods of four layers (KDA over a dense SwiGLU, KDA, KDA, MLA,
    KDA, KDA, KDA, MLA), 64 of the 256 routed experts held, a quarter of the
    vocabulary, contexts to 32768 = a 256-entry table, over ``jax.eval_shape``d
    parameters."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import kimi_linear
    cfg = kimi_linear.KimiLinearConfig(num_hidden_layers=8, vocab_size=40960, experts_held=64,
                                       expert_rank=0)
    abstract = jax.eval_shape(lambda: kimi_linear.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=32768, max_ragged_batch_size=256,
                                           max_ragged_sequence_count=8,
                                           max_tracked_sequences=KIMI_SLOTS),
        kv_block_size=KIMI_BLOCK, use_paged_kernel=True,
        expert_parallel={"capacity_factor": 32.0})
    model = model_cls_for(cfg)(abstract, cfg, engine_config)
    assert model.num_kv_layers == 2 and model.min_table_bucket == KIMI_TABLE
    assert model.kv_state_widths == (640, )
    assert [(s.name, s.layers, s.shape, s.dtype) for s in model.sequence_state] == [
        ("kda", 6, (32, 128, 128), "float32"), ("conv", 6, KIMI_TAILS, "bfloat16")]
    return model, abstract


def _kimi_args(device, abstract, tokens):
    one = SingleDeviceSharding(device)
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = ((_on(one, (2, KIMI_BLOCKS, KIMI_BLOCK, 640), jnp.bfloat16), ),
             _on(one, (6, KIMI_SLOTS, 32, 128, 128), jnp.float32),
             _on(one, (6, KIMI_SLOTS) + KIMI_TAILS, jnp.bfloat16))
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (8, 4 + KIMI_TABLE + 1), jnp.int32)}
    return params, cache, batch


def _kimi_pools_in_place(text):
    """No result shaped like the state pool or a piece of it but the kernels'
    aliased pool (a slot is exactly 2 MiB: the size XLA's gather rule turns
    on), none shaped like the conv pool, the tails moved by the two slot-copy
    kernels under ``kda/conv``, and no copy of the 2.5 GiB latent pool."""
    import re
    copies = [line for line in text.splitlines()
              if re.search(rf"= bf16\[2,{KIMI_BLOCKS},{KIMI_BLOCK},640\]\S* copy\(", line)]
    return not copies and not _pool_shaped_results(text, (6, KIMI_SLOTS, 32, 128)) \
        and not _conv_pool_results(text, (6, KIMI_SLOTS) + KIMI_TAILS) \
        and _tails_by_the_kernels(text, mixers=6, scope="kda/conv")


def _kimi_same_cache(out, cache):
    flat = jax.tree.leaves(out)
    return [(c.shape, c.dtype) for c in flat] == [(c.shape, c.dtype) for c in jax.tree.leaves(cache)] \
        and jax.tree.structure(out) == jax.tree.structure(cache)


def test_kimi_put_program_fits_one_chip(v5e, kimi_model):
    """The chunk bucket's ``put`` program: 7.0 GiB of weights beside 2.5 GiB of
    latent rows and 0.8 GiB of state in 64 slots of 6 x 2 MiB, in one cache
    pytree ``((latent, ), state, conv)``. The tiled latent grid against a
    256-entry table in the two latent layers, the grouped matmul over the 64
    held banks of width 1024 in seven layers, and in the six delta-rule layers
    Solar Open 2's scan by segment at 32 heads (one ``kda_step_in_place`` and
    one ``kda_chunk_in_place`` a layer, the pool aliased through both)."""
    model, abstract = kimi_model
    assert model.moe_path(256) == "grouped" and model.attention_arm(256) == "latent_tiled"
    params, cache, batch = _kimi_args(v5e[0], abstract, 256)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert len([line for line in _kernel_calls(text, "latent_paged_attention_tiled")
                if "attn/latent_kernel" in line]) == 2
    assert "grouped_matmul" in text and "kda/scan" in text and "kda/step" not in text
    assert "latent_index_scores" not in text and "paged_attention_prefill" not in text
    for name in ("kda_step_in_place", "kda_chunk_in_place"):
        kernels = [line for line in _kernel_calls(text, name) if line.strip().startswith(f"%{name}")]
        assert len(kernels) == 6 and all("kda/scan" in line for line in kernels), (name, kernels)
        assert all("output_to_operand_aliasing" in line for line in kernels), (name, kernels)
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert _kimi_pools_in_place(text)
    assert _kimi_same_cache(jax.eval_shape(model._forward_impl, params, cache, batch)[1], cache)


def test_kimi_decode_loop_program_fits_one_chip(v5e, kimi_model):
    """``decode_loop``'s scan: the per-token latent grid walking a 256-entry
    table in two layers, ONE ``kda_step_in_place`` a delta-rule layer over the
    pool itself, and the pytree handed back in the types it came in."""
    model, abstract = kimi_model
    assert model.moe_path(8) == "grouped" and model.attention_arm(8) == "latent_token"
    params, cache, batch = _kimi_args(v5e[0], abstract, 8)
    loop = functools.partial(model._decode_loop_impl, n_steps=8)
    compiled = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert len([line for line in _kernel_calls(text, "latent_paged_attention_token")
                if "attn/latent_kernel" in line]) == 2
    assert "grouped_matmul" in text and "kda/step" in text and "kda/scan" not in text
    kernels = _kernel_calls(text, "kda_step_in_place")
    assert len(kernels) == 6 and all("kda/step" in line for line in kernels), kernels
    assert _device_bytes(compiled) < 0.8 * HBM_BYTES
    assert _kimi_pools_in_place(text)
    assert _kimi_same_cache(jax.eval_shape(loop, params, cache, batch)[1], cache)


# ---- longcat-flash-omni-serve-1chip: two latent layers of the pool a model layer (PR 62) ----
LONGCAT_BLOCKS, LONGCAT_BLOCK, LONGCAT_TABLE, LONGCAT_SEQS = 2048, 128, 64, 32


@pytest.fixture(scope="module")
def longcat_model():
    """``longcat-flash-omni-serve-1chip``: LongCat-Flash-Omni's published widths
    (64 heads of 128 + 64 over a 512 + 64 latent, dense halves of 12288, experts
    of 2048), 4 of 28 layers = EIGHT latent layers of the pool, 16 of the 512
    routed experts held beside 256 identity experts, an eighth of the
    vocabulary, contexts to 8192 = a 64-entry table, ONE sequence bucket of 32,
    over ``jax.eval_shape``d parameters."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import longcat_flash
    cfg = longcat_flash.LongcatFlashConfig(num_layers=4, vocab_size=16384, experts_held=16,
                                           expert_rank=11)
    abstract = jax.eval_shape(lambda: longcat_flash.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=8192, max_ragged_batch_size=256,
                                           max_ragged_sequence_count=LONGCAT_SEQS),
        kv_block_size=LONGCAT_BLOCK, use_paged_kernel=True,
        expert_parallel={"capacity_factor": 43.0})
    model = model_cls_for(cfg)(abstract, cfg, engine_config)
    assert model.num_kv_layers == 8 and model.min_table_bucket == LONGCAT_TABLE
    assert model.min_sequence_bucket == LONGCAT_SEQS and model.kv_state_widths == (640, )
    assert model.moe_count_names[-1] == "moe_assignments_zero"
    return model, abstract


def _longcat_args(device, abstract, tokens):
    one = SingleDeviceSharding(device)
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = (_on(one, (8, LONGCAT_BLOCKS, LONGCAT_BLOCK, 640), jnp.bfloat16), )
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (LONGCAT_SEQS, 4 + LONGCAT_TABLE), jnp.int32)}
    return params, cache, batch


def _longcat_pool_in_place(text):
    """No copy of the 2.5 GiB latent pool."""
    import re
    return not [line for line in text.splitlines()
                if re.search(rf"= bf16\[8,{LONGCAT_BLOCKS},{LONGCAT_BLOCK},640\]\S* copy\(", line)]


@pytest.mark.parametrize("tokens", [256, 32])
def test_longcat_put_program_fits_one_chip(v5e, longcat_model, tokens):
    """A ``put`` program (the 256-token chunk bucket on the tiled latent grid
    at 64 query heads; the 32-token bucket, a decode row a sequence, on the
    token grid): 9.7 GiB of weights beside 2.5 GiB of latent rows, one latent
    kernel a HALF-layer, the grouped matmul over the 16 held banks ``[16, 6144,
    2 x 2048]`` / ``[16, 2048, 6144]`` once a layer, and the identity experts'
    term under ``moe/zero``."""
    model, abstract = longcat_model
    grid = "latent_tiled" if tokens > 32 else "latent_token"
    assert model.moe_path(tokens) == "grouped" and model.attention_arm(tokens) == grid
    params, cache, batch = _longcat_args(v5e[0], abstract, tokens)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert len([line for line in _kernel_calls(text, grid.replace("latent_", "latent_paged_attention_"))
                if "attn/latent_kernel" in line]) == 8
    assert "grouped_matmul" in text and "moe/zero" in text and "mlp" in text
    assert "latent_index_scores" not in text and "paged_attention_prefill" not in text
    assert _device_bytes(compiled) < 0.85 * HBM_BYTES
    assert _longcat_pool_in_place(text)
    out = jax.eval_shape(model._forward_impl, params, cache, batch)
    assert [(c.shape, c.dtype) for c in out[1]] == [(c.shape, c.dtype) for c in cache]
    # banks, local assignments, visits, rows walked, identity choices a layer
    assert out[2].shape == (4, 5)
    assert _walks_a_window(text, layers=4, window=256 if tokens == 256 else 128)


def test_longcat_decode_loop_program_fits_one_chip(v5e, longcat_model):
    """``decode_loop``'s scan at 32 rows a step: the per-token latent grid
    walking a 64-entry table in eight latent layers, the pool handed back in
    the type it came in, the counts a step a layer beside the tokens."""
    model, abstract = longcat_model
    params, cache, batch = _longcat_args(v5e[0], abstract, 32)
    loop = functools.partial(model._decode_loop_impl, n_steps=8)
    compiled = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert len([line for line in _kernel_calls(text, "latent_paged_attention_token")
                if "attn/latent_kernel" in line]) == 8
    assert "grouped_matmul" in text and "moe/zero" in text
    assert _device_bytes(compiled) < 0.85 * HBM_BYTES
    assert _longcat_pool_in_place(text)
    out = jax.eval_shape(loop, params, cache, batch)
    assert [(c.shape, c.dtype) for c in out[1]] == [(c.shape, c.dtype) for c in cache]
    assert out[2].shape == (8, 4, 5)
    assert _walks_a_window(text, layers=4, window=128)  # inside the scan


# ---- the tiled latent grid's tile, by the heads a call sees (PR 63) ----
@pytest.mark.parametrize("fixture, heads, tile", [
    ("kimi_model", 32, 32), ("longcat_model", 64, 16), ("deepseek_model", 128, 8)])
def test_a_latent_tile_is_1024_mxu_rows_whatever_the_head_count(request, fixture, heads, tile):
    """The three families' ``put`` programs above compile the tile this rule
    gives them (and the one-token pass's deeper buffer beside it); the arm a
    bucket takes is the bucket's alone, as it was."""
    from deepspeed_tpu.ops.pallas import latent_attention as la
    model = request.getfixturevalue(fixture)[0]
    assert model._config.num_attention_heads == heads and tile * heads == la.TILE_ROWS == 1024
    for bucket in (8, 16, 32):
        assert la.tile_tokens(bucket, heads) == la.tile_tokens(bucket) == 1
        assert model.attention_arm(bucket) == "latent_token"
    for bucket in (64, 128, 256):
        assert la.tile_tokens(bucket, heads) == tile and la.tile_tokens(bucket) == 8
        assert model.attention_arm(bucket) == "latent_tiled"
    # 8 tokens the floor; a bucket the rule's tile does not divide takes the next that does
    assert la.tile_tokens(256, 2 * heads) == max(tile // 2, 8) and la.tile_tokens(256, 512) == 8
    assert la.tile_tokens(48, heads) == min(tile, 16)


# ---- granite-4.0-h-small-serve-1chip: a Mamba-2 or softmax mixer AND experts a layer (PR 65) ----
GRANITE_LAYERS, GRANITE_SLOTS, GRANITE_BLOCKS, GRANITE_BLOCK, GRANITE_SEQS = 10, 64, 1024, 128, 32
GRANITE_MIXERS, GRANITE_VOCAB = 9, 50176
GRANITE_TAILS = (8, 3200)  # the 3 x 8448 convolution tails a sequence, folded (ssm.conv_slot)


@pytest.fixture(scope="module")
def granite_model():
    """``granite-4.0-h-small-serve-1chip``: Granite-4.0-H-Small's published
    widths, the first period of ``layer_types`` (9 Mamba-2 layers, 1 attention
    layer), 36 of the 72 routed experts held, half the vocabulary, contexts to
    2048, 32 sequences a step, over ``jax.eval_shape``d parameters."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations.registry import model_cls_for
    from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
    from deepspeed_tpu.models import granitemoehybrid as granite
    whole = granite.GraniteMoeHybridConfig()
    cfg = granite.GraniteMoeHybridConfig(num_hidden_layers=GRANITE_LAYERS,
                                         layer_types=whole.layer_types[:GRANITE_LAYERS],
                                         vocab_size=GRANITE_VOCAB, experts_held=36, expert_rank=0)
    abstract = jax.eval_shape(lambda: granite.init_params(cfg, param_dtype=cfg.dtype)[1])
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(max_context=2048, max_ragged_batch_size=256,
                                           max_ragged_sequence_count=GRANITE_SEQS,
                                           max_tracked_sequences=GRANITE_SLOTS),
        kv_block_size=GRANITE_BLOCK, use_paged_kernel=True,
        expert_parallel={"capacity_factor": 7.2})
    model = model_cls_for(cfg)(abstract, cfg, engine_config)
    assert model.num_kv_layers == 1 and model.min_table_bucket == 16
    assert model.min_sequence_bucket == GRANITE_SEQS
    assert [(s.name, s.layers, s.shape, s.dtype) for s in model.sequence_state] == [
        ("ssm", GRANITE_MIXERS, (128, 64, 128), "float32"),
        ("conv", GRANITE_MIXERS, GRANITE_TAILS, "bfloat16")]
    return model, abstract


def _granite_args(device, abstract, tokens):
    one = SingleDeviceSharding(device)
    params = jax.tree.map(lambda leaf: _on(one, leaf.shape, leaf.dtype), abstract)
    cache = (_on(one, (1, 2, GRANITE_BLOCKS, 8, GRANITE_BLOCK, 128), jnp.bfloat16),
             _on(one, (GRANITE_MIXERS, GRANITE_SLOTS, 128, 64, 128), jnp.float32),
             _on(one, (GRANITE_MIXERS, GRANITE_SLOTS) + GRANITE_TAILS, jnp.bfloat16))
    batch = {"tok_meta": _on(one, (4, tokens), jnp.int32),
             "seq_meta": _on(one, (GRANITE_SEQS, 4 + 16 + 1), jnp.int32)}
    return params, cache, batch


def _granite_tied_head(compiled, params):
    """The program holds NO second ``[50176, 4096]`` array: the tied head reads
    the embedding where it lies. Nothing makes an array of its shape, either
    way round, but what passes the parameter through (tuples, a loop's carry,
    bitcasts and the compiler's ``bitcast_fusion``s, which copy nothing); the
    program's temporaries are under half of the table's 0.38 GiB; and the tree
    has no ``lm_head``."""
    import re
    table = re.compile(rf"bf16\[(?:{GRANITE_VOCAB},4096|4096,{GRANITE_VOCAB})\]")
    made = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z\-]*)\(", line)
        if not m or not table.search(m.group(1)) or m.group(2) in (
                "parameter", "tuple", "get-tuple-element", "bitcast", "while"):
            continue
        if m.group(2) == "fusion" and "calls=%bitcast_fusion" in line:
            continue
        made.append(line.strip()[:300])
    return not made and "lm_head" not in params \
        and compiled.memory_analysis().temp_size_in_bytes < 0.19 * 2**30


def _granite_one_grouped_shape(text):
    """One grouped-matmul shape a projection a program: every call reads a bank
    of the 36 held, ``[36, 4096, 1536]`` going in and ``[36, 768, 4096]`` coming
    out, and the ten layers' calls are alike."""
    import re
    calls = _kernel_calls(text, "grouped_matmul")
    banks = {m for line in calls for m in re.findall(r"bf16\[36,(\d+,\d+)\]", line)}
    return len(calls) == 2 * GRANITE_LAYERS and banks == {"4096,1536", "768,4096"}


@pytest.mark.parametrize("tokens,kernel", [(32, "paged_attention_update"),
                                           (256, "paged_attention_prefill")],
                         ids=["smallest-bucket", "chunk-bucket"])
def test_granite_put_program_fits_one_chip(v5e, granite_model, tokens, kernel):
    """8.9 GiB of weights beside 0.5 GiB of K/V in ONE layer and 2.3 GiB of
    float32 state in 64 slots of nine mixers: the chunked scan at (heads, head,
    state, groups) = (128, 64, 128, 1) with the state a SEQUENCE, scanned in
    its slot; ten grouped matmuls over the 36 held banks at 768 lanes; one
    paged layer; the head the embedding itself."""
    model, abstract = granite_model
    assert model.moe_path(tokens) == "grouped"
    params, cache, batch = _granite_args(v5e[0], abstract, tokens)
    compiled = jax.jit(model._forward_impl, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and kernel in text
    assert "ssm/scan" in text and "ssm/step" not in text
    assert _device_bytes(compiled) < 0.85 * HBM_BYTES
    assert _granite_tied_head(compiled, abstract)
    assert _granite_one_grouped_shape(text)
    assert not _state_sized_results(text, rows=4 * GRANITE_SEQS, per_row=128 * 64 * 128,
                                    pool=(GRANITE_MIXERS, GRANITE_SLOTS))
    assert _scans_in_the_pool(text, mixers=GRANITE_MIXERS)
    assert not _pool_shaped_results(text, (GRANITE_MIXERS, GRANITE_SLOTS, 128, 64))
    assert not _step_states(text, GRANITE_SEQS, 128, 64, 128, 1)
    assert _tails_by_the_kernels(text, mixers=GRANITE_MIXERS)
    assert not _conv_pool_results(text, (GRANITE_MIXERS, GRANITE_SLOTS) + GRANITE_TAILS)
    out = jax.eval_shape(model._forward_impl, params, cache, batch)
    assert [(c.shape, c.dtype) for c in out[1]] == [(c.shape, c.dtype) for c in cache]


def test_granite_decode_loop_program_fits_one_chip(v5e, granite_model):
    """The real-width ``decode_loop`` program of 32 rows: one
    ``ssm_step_in_place`` a Mamba-2 layer at (128, 64, 128, 1) over the pool
    itself (no ``[rows, H, P, N]`` state outside it), the per-token paged kernel
    in the one attention layer, one grouped-matmul shape, the head the
    embedding, and the pools handed back in the types they came in."""
    model, abstract = granite_model
    params, cache, batch = _granite_args(v5e[0], abstract, GRANITE_SEQS)
    loop = functools.partial(model._decode_loop_impl, n_steps=8)
    compiled = jax.jit(loop, donate_argnums=(1, )).lower(params, cache, batch).compile()
    text = compiled.as_text()
    assert "paged_attention_update" in text and "ssm/scan" not in text
    kernels = _kernel_calls(text, "ssm_step_in_place")
    assert len(kernels) == GRANITE_MIXERS and all("ssm/step" in line for line in kernels), kernels
    assert _device_bytes(compiled) < 0.85 * HBM_BYTES
    assert _granite_tied_head(compiled, abstract)
    assert _granite_one_grouped_shape(text)
    assert not _state_sized_results(text, rows=8, per_row=128 * 64 * 128,
                                    pool=(GRANITE_MIXERS, GRANITE_SLOTS))
    assert _tails_by_the_kernels(text, mixers=GRANITE_MIXERS)
    assert not _conv_pool_results(text, (GRANITE_MIXERS, GRANITE_SLOTS) + GRANITE_TAILS)
    out = jax.eval_shape(loop, params, cache, batch)
    assert [(c.shape, c.dtype) for c in out[1]] == [(c.shape, c.dtype) for c in cache]
