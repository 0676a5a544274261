"""Flagship program builders for the perf gates.

Each builder constructs a SMALL but structurally faithful instance of one
flagship computation — same code paths, same jit sites, same sharding
machinery as production, shrunk to tier-1 size — and returns its
``jax.stages.Lowered`` via the engines' official lowering hooks
(``lower_train_batch`` / ``lower_forward`` / ``lower_decode_loop``), never
by reaching into private jit caches.

Determinism contract: builders must produce the same program every call on
the same jax install (fixed shapes, fixed configs, fixed seeds), because the
extracted stats are diffed against checked-in budget files. The gate
environment pins ``JAX_PLATFORMS=cpu`` and
``--xla_force_host_platform_device_count=8`` (tests/conftest.py already
does; ``bin/dstpu_perfgate`` re-asserts it).
"""

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import numpy as np

# gate-standard shapes (small enough for tier-1, big enough that remat /
# quantization / cache structure actually shows in the numbers)
TRAIN_B, TRAIN_S, TRAIN_GAS = 8, 64, 2
FLASH_B, FLASH_S, FLASH_H, FLASH_D = 1, 128, 4, 32
DECODE_STEPS = 8
PREFIX_TOKENS, SUFFIX_TOKENS = 192, 24
KV_BLOCK = 16
SPEC_DRAFT_K = 3  # verify feed width 1+k pads into the smallest token bucket
SPEC_TREE_NODES = 8  # token-tree feed (root + draft branches) at the smallest bucket


@dataclass
class BuiltProgram:
    name: str
    lowered: Any                       # jax.stages.Lowered
    analytic_flops: Optional[float] = None
    meta: Dict[str, Any] = field(default_factory=dict)
    # optional comparison programs for structural (non-budget) assertions,
    # e.g. the bf16 twin of the int4 program
    comparisons: Dict[str, Any] = field(default_factory=dict)


def _flops_per_token(cfg, n_params, S):
    """The PaLM-appendix convention: 6*(N - N_embed) dense fwd+bwd +
    12*L*S*H attention per token."""
    return 6.0 * (n_params - cfg.vocab_size * cfg.hidden_size) \
        + 12.0 * cfg.num_hidden_layers * S * cfg.hidden_size


def build_train_engine(dtype=None):
    """Tiny ZeRO-3 training engine on the full 8-way data mesh, params
    force-sharded (persistence threshold 0) so the gathered/reduced
    collectives exist to be budgeted. Shared with the gate-sensitivity tests
    (the f32-upcast regression is built here too)."""
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.utils import groups

    groups.initialize_mesh(force=True)
    cfg = llama.LlamaConfig.tiny(remat=True, remat_policy="dots",
                                 dtype=dtype if dtype is not None else jnp.bfloat16)
    model, params = llama.init_params(cfg, batch_size=TRAIN_B, seq_len=TRAIN_S)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": TRAIN_B,
                "gradient_accumulation_steps": TRAIN_GAS,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 3,
                                      "stage3_param_persistence_threshold": 0},
                "bf16": {"enabled": True}})
    return engine, cfg


def train_batch_example(cfg):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(TRAIN_B * TRAIN_GAS, TRAIN_S + 1),
                       dtype=np.int64)
    return (ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32))


def _build_zero3_train_batch() -> BuiltProgram:
    import jax

    from deepspeed_tpu.utils import groups

    engine, cfg = build_train_engine()
    lowered = engine.lower_train_batch(batch=train_batch_example(cfg))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(engine.params))
    dp = groups.get_data_parallel_world_size()
    tokens_per_partition = TRAIN_B * TRAIN_GAS * TRAIN_S / dp
    return BuiltProgram(
        name="zero3_train_batch", lowered=lowered,
        # cost_analysis reports per-partition numbers, so the analytic model
        # flops are per-partition tokens too
        analytic_flops=tokens_per_partition * _flops_per_token(cfg, n_params, TRAIN_S),
        meta={"B": TRAIN_B, "S": TRAIN_S, "gas": TRAIN_GAS, "zero_stage": 3,
              "data_parallel": dp, "n_params": n_params})


def _build_flash_fwd_bwd() -> BuiltProgram:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, D = FLASH_B, FLASH_S, FLASH_H, FLASH_D
    mk = lambda s: jax.random.normal(jax.random.PRNGKey(s), (B, S, H, D), jnp.bfloat16)
    q, k, v = mk(1), mk(2), mk(3)
    scale = 1.0 / (D**0.5)

    def loss(q, k, v):
        return (flash_attention(q, k, v, scale=scale, causal=True)
                .astype(jnp.float32) ** 2).mean()

    fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    # fwd ~4*S^2*D mult-adds per head (*2 flops), bwd ~2.5x fwd; causal not
    # discounted — the repo-wide convention
    analytic = 2.0 * 4.0 * B * H * S * S * D * 3.5
    return BuiltProgram(name="flash_attention_fwd_bwd", lowered=fn.lower(q, k, v),
                        analytic_flops=analytic,
                        meta={"B": B, "S": S, "H": H, "D": D, "causal": True,
                              "note": "pallas interpret-mode lowering on cpu"})


def build_v2_engine(quant_bits: Optional[int] = None, blocks: int = 64,
                    max_context: int = 256):
    """Tiny ragged inference engine (shared by the decode / int4 / prefix
    programs and the sensitivity tests)."""
    from deepspeed_tpu.inference.v2.config_v2 import (QuantizationConfig,
                                                      RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.utils import groups

    groups.initialize_mesh(force=True)
    cfg = llama.LlamaConfig.tiny()
    _, params = llama.init_params(cfg, seq_len=16)
    mgr = DSStateManagerConfig(
        memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=blocks),
        max_context=max_context, max_ragged_batch_size=512,
        max_ragged_sequence_count=8)
    eng_cfg = RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=KV_BLOCK,
        quantization=QuantizationConfig(enabled=quant_bits is not None,
                                        bits=quant_bits or 8,
                                        min_size=1024))
    return build_engine(params, cfg, eng_cfg), cfg


def _build_paged_decode_step() -> BuiltProgram:
    engine, _ = build_v2_engine()
    return BuiltProgram(name="paged_decode_step",
                        lowered=engine.lower_decode_loop(DECODE_STEPS),
                        meta={"n_steps": DECODE_STEPS, "kv_block_size": KV_BLOCK})


def _build_spec_verify_step() -> BuiltProgram:
    """The speculative verify step over chains (the causal program): one
    ragged forward scoring a next-input token plus SPEC_DRAFT_K drafts per
    sequence (every position unembedded). Built at the smallest pad bucket —
    the same bucket a single-token decode forward runs in, which IS the
    speculative claim: 1+k verified positions for the dispatch cost of one
    step."""
    engine, _ = build_v2_engine()
    return BuiltProgram(
        name="spec_verify_step", lowered=engine.lower_verify(),
        meta={"draft_tokens": SPEC_DRAFT_K, "feed_width": 1 + SPEC_DRAFT_K,
              "kv_block_size": KV_BLOCK,
              "note": "all-position unembed over the smallest decode bucket"},
        comparisons={"single_token_forward": engine.lower_forward()})


def _build_spec_tree_verify() -> BuiltProgram:
    """The same verify step over a branching batch: one ragged forward
    scoring a whole draft TREE (root + branching candidates) under the
    tree-attention mask with the per-query virtual-KV gather, in its greedy
    variant — per-node ids cross the host boundary, not a ``[T, vocab]``
    f32 logits block. Built at the smallest pad bucket; the comparisons ARE
    the tree-speculation claim: verifying up to SPEC_TREE_NODES tree nodes
    costs a budgeted multiple of ONE single-token forward at the same
    bucket — nowhere near node-count sequential steps — and stays in the
    causal verify program's weight class despite the mask and gather."""
    engine, _ = build_v2_engine()
    return BuiltProgram(
        name="spec_tree_verify",
        lowered=engine.lower_verify(tree=True, greedy=True),
        meta={"tree_nodes": SPEC_TREE_NODES, "kv_block_size": KV_BLOCK,
              "greedy": True,
              "note": "tree-attention mask + per-query virtual KV at the "
                      "smallest decode bucket; greedy returns per-node ids"},
        comparisons={"single_token_forward": engine.lower_forward(),
                     "linear_verify": engine.lower_verify()})


def _build_int4_decode_matmul() -> BuiltProgram:
    engine, _ = build_v2_engine(quant_bits=4)
    bf16_engine, _ = build_v2_engine(quant_bits=None)
    return BuiltProgram(
        name="int4_decode_matmul", lowered=engine.lower_forward(),
        meta={"bits": 4, "note": "decode-bucket forward, weights packed int4"},
        comparisons={"bf16_forward": bf16_engine.lower_forward()})


def _suffix_bucket():
    """The (T, S, MB) bucket the ragged wrapper pads a SUFFIX-only prefill
    into, with the block table still spanning the whole (cached) prefix —
    exactly the program shape a prefix-cache hit executes."""
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import to_padded
    total_blocks = -(-(PREFIX_TOKENS + SUFFIX_TOKENS) // KV_BLOCK)
    MB = 4
    while MB < total_blocks:
        MB *= 2
    return (to_padded(SUFFIX_TOKENS), 8, MB)


def _build_prefix_suffix_prefill() -> BuiltProgram:
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import to_padded

    engine, _ = build_v2_engine(blocks=64, max_context=256)
    suffix_bucket = _suffix_bucket()
    full_bucket = (to_padded(PREFIX_TOKENS + SUFFIX_TOKENS), 8, suffix_bucket[2])
    return BuiltProgram(
        name="prefix_suffix_prefill", lowered=engine.lower_forward(suffix_bucket),
        meta={"prefix_tokens": PREFIX_TOKENS, "suffix_tokens": SUFFIX_TOKENS,
              "suffix_bucket": list(suffix_bucket), "full_bucket": list(full_bucket)},
        comparisons={"full_prompt_prefill": engine.lower_forward(full_bucket)})


FLAGSHIP_PROGRAMS: Dict[str, Callable[[], BuiltProgram]] = {
    "zero3_train_batch": _build_zero3_train_batch,
    "flash_attention_fwd_bwd": _build_flash_fwd_bwd,
    "paged_decode_step": _build_paged_decode_step,
    "spec_verify_step": _build_spec_verify_step,
    "spec_tree_verify": _build_spec_tree_verify,
    "int4_decode_matmul": _build_int4_decode_matmul,
    "prefix_suffix_prefill": _build_prefix_suffix_prefill,
}


def build_program(name: str) -> BuiltProgram:
    try:
        builder = FLAGSHIP_PROGRAMS[name]
    except KeyError:
        raise KeyError(f"unknown flagship program {name!r}; "
                       f"known: {sorted(FLAGSHIP_PROGRAMS)}") from None
    return builder()
