"""Benchmark harness: one process, on the chip or not at all.

The process that measures is the process that holds the chip. It refuses to
start where ``jax.devices()[0].platform`` is not ``tpu`` (a number from a CPU
never appears under a device metric's name), takes the chip's peak from
``deepspeed_tpu/perf/chip_specs.py`` by the ``device_kind`` the device reports
(an unknown kind is an error, not a default), lets a failing leg fail the run,
and names platform, kind and device count in the one JSON line it prints.

What the legs measure and how (this methodology is the benchmark issue's
business, ROADMAP S0, and is left as it was):
- Headline: 530M-param Llama training step, ZeRO-3 semantics, bf16 + fp32
  master, B=8 GAS=8 S=1024, remat=dots — ``vs_baseline`` = MFU / 0.45 (the
  BASELINE.json north star is ZeRO-3 Llama SFT at >=45% MFU).
- Long-seq flash leg: S=4096 Pallas flash fwd+bwd vs dense.
- Inference: prefill + on-device decode_loop, Pallas paged kernel vs XLA
  gather (two-point differenced: chain data, difference two N's, barrier via a
  host float() fetch).
- Block-sparse attention at 8k seq; evoformer at AF2 MSA shapes.

``bench.py --microbench`` runs ONLY the on-device kernel suite (paged-
attention decode, int4 unpack, block-sparse, evoformer), two-point differenced
like the decode loop.

FLOPs model: 6*(N - N_embed) dense (fwd+bwd) + 12*L*S*H attention per token
(PaLM-appendix MFU convention, causal not discounted; embedding lookup
excluded).
"""

import json
import os
import sys
import time


def _llama_530m(llama, jnp, S, **kw):
    """The 530M bench model (largest Llama-class fitting one 16 GB chip with
    fp32 master + Adam moments)."""
    return llama.LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5376,
                             num_hidden_layers=8, num_attention_heads=16,
                             num_key_value_heads=16, max_position_embeddings=S,
                             dtype=jnp.bfloat16, **kw)


def _flops_per_token(cfg, n_params, S):
    """PaLM-appendix MFU convention: 6*(N - N_embed) dense fwd+bwd +
    12*L*S*H attention per token (causal not discounted; embed lookup free)."""
    return 6.0 * (n_params - cfg.vocab_size * cfg.hidden_size) \
        + 12.0 * cfg.num_hidden_layers * S * cfg.hidden_size


def _bench_attn_compare(llama, groups, jnp, peak, B, S, GAS):
    """Dense vs Pallas-flash training comparison at one (B, S, GAS) shape —
    two-point differenced per leg; flash_speedup is the ratio. Reused by the
    S=4096 long-seq leg AND the S=1024 headline-shape leg (the headline
    itself now trains with flash; this keeps the dense path selectable and
    measured for the same shape)."""
    import jax
    import numpy as np
    import deepspeed_tpu

    out = {}
    for flash in (False, True):
        groups.initialize_mesh(force=True)
        cfg = _llama_530m(llama, jnp, S, remat=True, remat_policy="dots",
                          use_flash_attention=flash)
        model, params = llama.init_params(cfg, batch_size=B, seq_len=S)
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": B, "gradient_accumulation_steps": GAS,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                    "zero_optimization": {"stage": 3}, "bf16": {"enabled": True}})
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, size=(B * GAS, S + 1), dtype=np.int64)
        batch = (ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32))
        for _ in range(2):
            float(eng.train_batch(batch=batch))
        t0 = time.perf_counter()
        loss = None
        for _ in range(4):
            loss = eng.train_batch(batch=batch)
        float(loss)
        dt = (time.perf_counter() - t0) / 4
        tps = B * GAS * S / dt
        out["flash" if flash else "dense"] = {
            "tokens_per_sec": round(tps, 1),
            "mfu": round(tps * _flops_per_token(cfg, n_params, S) / peak, 4)}
        del eng, params
    out["flash_speedup"] = round(out["flash"]["tokens_per_sec"] /
                                 max(out["dense"]["tokens_per_sec"], 1e-9), 2)
    out["seq"] = S
    return out


def _bench_long_seq(llama, groups, jnp, peak):
    """Long-sequence training leg (VERDICT r3 #10): S=4096, Pallas flash
    attention vs dense — flash must win (dense OOMs outright at 8k on 16 GB)."""
    return _bench_attn_compare(llama, groups, jnp, peak, B=1, S=4096, GAS=4)


def _bench_headline_attention(llama, groups, jnp, peak):
    """Flash vs dense at the HEADLINE shape (S=1024) — the differenced
    justification for the headline leg running on the flash kernel (ROADMAP
    item 1's oldest unpaid debt). GAS shrunk from the headline's 8 to keep
    the comparison leg short; per-token step time is GAS-independent."""
    return _bench_attn_compare(llama, groups, jnp, peak, B=8, S=1024, GAS=2)


def _bench_inference(llama, groups, jnp):
    """Inference legs (VERDICT r4 #1): prefill tokens/s + decode tokens/s at
    long context, Pallas paged-attention kernel vs the XLA gather path.

    Methodology (per-call host timing of repeated identical programs measures
    dispatch latency, not the device):
    - prefill: warm puts differenced ((t(2 puts) - t(1 put)) / CTX) so the
      per-dispatch RTT cancels;
    - decode: the engine's on-device ``decode_loop`` (one dispatch runs N
      greedy steps as a lax.scan), two-point differenced between N1 and N2
      steps — device-bound, elision-proof (metadata advances every call).
    """
    import jax
    import numpy as np
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)

    groups.initialize_mesh(force=True)
    MAXCTX, CTX = 4096, 3500
    N1, N2 = 16, 112
    cfg = _llama_530m(llama, jnp, MAXCTX)
    _, params = llama.init_params(cfg, seq_len=16)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, CTX)

    out = {"context": CTX, "decode_method": f"on-device decode_loop, (t({N2})-t({N1}))/{N2 - N1}"}
    # paged leg = auto mode (the deployment config): XLA-gather prefill +
    # Pallas-kernel decode buckets; forcing the kernel for a 3.5k prefill
    # would serialize 3.5k per-token programs nobody would ship
    for kernel, key in ((False, "xla_gather"), (None, "paged_kernel")):
        mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                              size=2048),
                                   max_context=MAXCTX, max_ragged_batch_size=4096,
                                   max_ragged_sequence_count=8)
        eng = build_engine(params, cfg,
                           RaggedInferenceEngineConfig(state_manager=mgr, kv_block_size=16,
                                                       use_paged_kernel=kernel))
        t0 = time.perf_counter()
        pre = eng.put([0], [prompt])
        jax.block_until_ready(pre)
        prefill_compile_sec = time.perf_counter() - t0  # cold: includes compile

        # warm prefill, RTT-differenced: time 1 blocked put, then 2 puts with a
        # SINGLE sync (the dispatches pipeline; the cache chains them on
        # device) — the difference is one put's device time, RTT cancelled
        t0 = time.perf_counter()
        jax.block_until_ready(eng.put([1], [prompt]))
        t_one = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.put([2], [prompt])
        jax.block_until_ready(eng.put([3], [prompt]))
        t_two = time.perf_counter() - t0
        prefill_tps = CTX / max(t_two - t_one, 1e-9)
        if t_two <= t_one:  # timing noise — fall back to the single-put number
            prefill_tps = CTX / t_one

        # decode: device-side loop on uid 0 (context CTX and growing)
        first = np.asarray([int(np.argmax(np.asarray(pre)[0]))], np.int32)
        t0 = time.perf_counter()
        toks = eng.decode_loop([0], [first], N1)   # compiles the N1 program
        nxt = toks[:, -1]
        t_c1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        toks = eng.decode_loop([0], [nxt], N2)     # compiles the N2 program
        nxt = toks[:, -1]
        decode_compile_sec = t_c1 + time.perf_counter() - t0
        t0 = time.perf_counter()
        toks = eng.decode_loop([0], [nxt], N1)
        nxt = toks[:, -1]
        t_n1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        toks = eng.decode_loop([0], [nxt], N2)
        t_n2 = time.perf_counter() - t0
        if t_n2 > t_n1:
            decode_tps = (N2 - N1) / (t_n2 - t_n1)
            step_ms = 1e3 * (t_n2 - t_n1) / (N2 - N1)
        else:  # timing noise — fall back to the (RTT-inclusive) whole-call rate
            decode_tps = N2 / t_n2
            step_ms = 1e3 * t_n2 / N2
        out[key] = {"prefill_tokens_per_sec": round(prefill_tps, 1),
                    "decode_tokens_per_sec": round(decode_tps, 1),
                    "decode_step_ms": round(step_ms, 3),
                    "prefill_compile_sec": round(prefill_compile_sec, 1),
                    "decode_compile_sec": round(decode_compile_sec, 1)}
        del eng
    out["kernel_decode_speedup"] = round(
        out["paged_kernel"]["decode_tokens_per_sec"] /
        max(out["xla_gather"]["decode_tokens_per_sec"], 1e-9), 2)
    return out


def _bench_prefix_cache(llama, groups, jnp):
    """Automatic prefix-cache leg: cold vs warm TTFT on a shared-prefix batch
    (the shared-system-prompt workload). Both phases pay the identical fixed
    per-request cost — scheduler dispatch, the single-step forward producing
    the first token, sampling — so differencing warm from cold (the two-point
    trick at request granularity) isolates exactly the prefill the cache
    eliminated. Warmup requests absorb compiles before either phase is timed.
    """
    import numpy as np
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)
    from deepspeed_tpu.serving import PrefixCacheConfig, ServingConfig, ServingScheduler

    groups.initialize_mesh(force=True)
    MAXCTX, PREFIX, SUFFIX, K = 4096, 3456, 64, 4
    cfg = _llama_530m(llama, jnp, MAXCTX)
    _, params = llama.init_params(cfg, seq_len=16)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, PREFIX)

    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                          size=4096),
                               max_context=MAXCTX, max_ragged_batch_size=4096,
                               max_ragged_sequence_count=8)
    eng = build_engine(params, cfg,
                       RaggedInferenceEngineConfig(state_manager=mgr, kv_block_size=16))
    sched = ServingScheduler(eng, ServingConfig(
        prefix_cache=PrefixCacheConfig(enabled=True)))

    def ttft(prefix):
        prompt = np.concatenate([prefix,
                                 rng.integers(0, cfg.vocab_size, SUFFIX)])
        req = sched.submit(prompt.tolist(), max_new_tokens=2)
        req.result(timeout=600)
        return req.ttft_s, req.cached_tokens

    try:
        # warmup: compile every bucket both phases touch AND publish the
        # shared prefix (the first shared-prefix request is the publisher)
        ttft(rng.integers(0, cfg.vocab_size, PREFIX))
        ttft(shared)
        cold = [ttft(rng.integers(0, cfg.vocab_size, PREFIX))[0] for _ in range(K)]
        warm_pairs = [ttft(shared) for _ in range(K)]
        warm = [t for t, _ in warm_pairs]
        cached = [c for _, c in warm_pairs]
    finally:
        sched.stop(drain=False)
        del eng
    cold_ms = 1e3 * float(np.median(cold))
    warm_ms = 1e3 * float(np.median(warm))
    return {"prefix_tokens": PREFIX, "suffix_tokens": SUFFIX, "requests_per_phase": K,
            "cold_ttft_ms": round(cold_ms, 2), "warm_ttft_ms": round(warm_ms, 2),
            "ttft_saved_ms": round(cold_ms - warm_ms, 2),
            "ttft_speedup": round(cold_ms / max(warm_ms, 1e-9), 2),
            "cached_tokens_per_hit": int(np.median(cached))}


def _bench_speculative_decode(llama, groups, jnp):
    """Speculative-decoding leg: a repeated (templated-workload shape) prompt
    decoded spec-on vs spec-off through the serving scheduler. Two-point
    differenced like the decode-loop leg: each arm times a warm N1-token and
    a warm N2-token request, so (t2 - t1)/(N2 - N1) isolates the marginal
    per-token cost (ITL) and cancels the shared fixed cost — dispatch, the
    prefix-hit admission, the single prefill step. Warmup requests absorb
    compiles (including every verify-feed bucket) before either arm is
    timed. Reports accepted-tokens-per-step, acceptance rate, and the ITL
    delta/speedup. The third arm runs ``drafter="auto"`` (tree verify, a
    fresh learned head racing prompt-lookup): on this templated workload
    arbitration should settle on prompt-lookup — the reported
    ``winning_drafter`` shows auto finds the right drafter instead of
    taxing the win the trie already delivers."""
    import numpy as np
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)
    from deepspeed_tpu.serving import (PrefixCacheConfig, ServingConfig,
                                       ServingScheduler, SpeculativeConfig)

    groups.initialize_mesh(force=True)
    MAXCTX, PROMPT, N1, N2, K = 2048, 512, 16, 112, 4
    cfg = _llama_530m(llama, jnp, MAXCTX)
    _, params = llama.init_params(cfg, seq_len=16)
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, PROMPT).tolist()

    out = {"prompt_tokens": PROMPT, "n1": N1, "n2": N2, "max_draft_tokens": K}
    arms = (("spec_off", dict(enabled=False)),
            ("spec_on", dict(enabled=True, max_draft_tokens=K)),
            ("spec_auto", dict(enabled=True, drafter="auto",
                               max_draft_tokens=K)))
    for key, spec_kw in arms:
        mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                              size=512),
                                   max_context=MAXCTX, max_ragged_batch_size=2048,
                                   max_ragged_sequence_count=8)
        eng = build_engine(params, cfg,
                           RaggedInferenceEngineConfig(state_manager=mgr,
                                                       kv_block_size=16))
        sched = ServingScheduler(eng, ServingConfig(
            prefix_cache=PrefixCacheConfig(enabled=True),
            speculative=SpeculativeConfig(**spec_kw)))

        def gen(n):
            req = sched.submit(prompt, max_new_tokens=n)
            req.result(timeout=600)
            return req

        try:
            gen(N2)            # publisher: full history lands in the trie
            gen(N1)
            gen(N2)            # warm the exact timed shapes and programs
            t0 = time.perf_counter()
            gen(N1)
            t_n1 = time.perf_counter() - t0
            t0 = time.perf_counter()
            r2 = gen(N2)
            t_n2 = time.perf_counter() - t0
            winner = None
            if key == "spec_auto":
                doc = sched.stats()["speculative"]
                ew = {n: d["ewma"] for n, d in doc["drafters"].items()
                      if d["ewma"] is not None}
                winner = max(ew, key=ew.get) if ew else None
        finally:
            sched.stop(drain=False)
            del eng
        itl_ms = (1e3 * (t_n2 - t_n1) / (N2 - N1) if t_n2 > t_n1
                  else 1e3 * t_n2 / N2)  # timing noise: whole-call fallback
        dispatches = max(1, r2.decode_steps) + 1  # + the prefill-hit dispatch
        out[key] = {"itl_ms": round(itl_ms, 3),
                    "decode_steps": r2.decode_steps,
                    "tokens_per_step": round(N2 / dispatches, 2),
                    "accept_rate": (round(r2.spec_accepted / r2.spec_drafted, 3)
                                    if r2.spec_drafted else None)}
        if key == "spec_auto":
            out[key]["winning_drafter"] = winner
    out["accepted_tokens_per_step"] = out["spec_on"]["tokens_per_step"]
    out["itl_saved_ms"] = round(out["spec_off"]["itl_ms"]
                                - out["spec_on"]["itl_ms"], 3)
    out["itl_speedup"] = round(out["spec_off"]["itl_ms"]
                               / max(out["spec_on"]["itl_ms"], 1e-9), 2)
    return out


def _bench_int4_weights(llama, groups, jnp):
    """ZeRO-Inference weight-quantization leg (VERDICT r5 ask #5): decode
    throughput with bf16 vs int8 vs int4 weights — weight-only quantization
    pays off when decode is weight-bandwidth-bound."""
    import numpy as np
    from deepspeed_tpu.inference.v2.config_v2 import (QuantizationConfig,
                                                      RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)

    groups.initialize_mesh(force=True)
    MAXCTX, CTX = 2048, 512
    N1, N2 = 16, 112
    cfg = _llama_530m(llama, jnp, MAXCTX)
    _, params = llama.init_params(cfg, seq_len=16)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, CTX)

    out = {"context": CTX}
    for bits, key in ((None, "bf16"), (8, "int8"), (4, "int4")):
        mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                                              size=512),
                                   max_context=MAXCTX, max_ragged_batch_size=2048,
                                   max_ragged_sequence_count=8)
        eng = build_engine(params, cfg,
                           RaggedInferenceEngineConfig(
                               state_manager=mgr, kv_block_size=16,
                               quantization=QuantizationConfig(enabled=bits is not None,
                                                               bits=bits or 8)))
        pre = eng.put([0], [prompt])
        first = np.asarray([int(np.argmax(np.asarray(pre)[0]))], np.int32)
        nxt = eng.decode_loop([0], [first], N1)[:, -1]   # compile N1
        nxt = eng.decode_loop([0], [nxt], N2)[:, -1]     # compile N2
        t0 = time.perf_counter()
        nxt = eng.decode_loop([0], [nxt], N1)[:, -1]
        t_n1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        toks = eng.decode_loop([0], [nxt], N2)
        t_n2 = time.perf_counter() - t0
        if t_n2 > t_n1:
            tps = (N2 - N1) / (t_n2 - t_n1)
        else:
            tps = N2 / t_n2
        out[key] = {"decode_tokens_per_sec": round(tps, 1)}
        del eng
    out["int4_vs_bf16"] = round(out["int4"]["decode_tokens_per_sec"] /
                                max(out["bf16"]["decode_tokens_per_sec"], 1e-9), 2)
    return out


def _bench_sparse_attention(jnp):
    """Block-sparse attention leg (VERDICT r4 #4): 8k sequence — where dense
    S² scores OOM on 16 GB — BigBird layouts at two densities; fwd+bwd time
    must scale with layout density. Timing: chained on-device scans, two-point
    differenced, with a host value fetch as the barrier."""
    import jax
    from deepspeed_tpu.ops.pallas.block_sparse_attention import block_sparse_attention
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import BigBirdSparsityConfig

    B, H, S, D, LB = 1, 16, 8192, 128, 64
    N1, N2 = 2, 10
    mk = lambda s: jax.jit(lambda k: jax.random.normal(k, (B, H, S, D), jnp.bfloat16))(
        jax.random.PRNGKey(s))
    q, k, v = mk(1), mk(2), mk(3)

    def leg(nrand, nwin):
        layout = BigBirdSparsityConfig(num_heads=H, block=LB, num_random_blocks=nrand,
                                       num_sliding_window_blocks=nwin,
                                       num_global_blocks=1).make_layout(S)

        def loss(q, k, v):
            return (block_sparse_attention(q, k, v, layout, LB).astype(jnp.float32) ** 2).mean()

        def make(n):
            @jax.jit
            def scan_fn(q, k, v):
                def body(x, _):
                    l, gq = jax.value_and_grad(loss)(x, k, v)
                    return x + gq.astype(x.dtype) * 1e-4, l
                return jax.lax.scan(body, q, None, length=n)
            return scan_fn

        f1, f2 = make(N1), make(N2)
        x, ls = f1(q, k, v)
        float(ls[-1])
        x, ls = f2(x, k, v)
        float(ls[-1])

        def t(f):
            nonlocal x
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                x, ls = f(x, k, v)
                float(ls[-1])  # host fetch = true barrier
                best = min(best, time.perf_counter() - t0)
            return best

        ms = (t(f2) - t(f1)) / (N2 - N1) * 1e3
        return {"density": round(float(layout.mean()), 4), "fwd_bwd_ms": round(ms, 2)}

    lo = leg(1, 3)
    hi = leg(4, 9)
    return {"seq": S, "layout": "bigbird", "low": lo, "high": hi,
            "time_ratio": round(hi["fwd_bwd_ms"] / max(lo["fwd_bwd_ms"], 1e-9), 2),
            "density_ratio": round(hi["density"] / lo["density"], 2)}


def _bench_evoformer(jnp, peak):
    """Evoformer attention at MSA-realistic shapes (VERDICT r4 #10): fwd+bwd
    time and achieved FLOP/s for the XLA-fused einsum formulation, with and
    without remat — the measured justification for not hand-writing the
    reference's 15k-LoC CUTLASS tier. Two-point differenced scans, host-fetch
    barrier."""
    import jax
    from deepspeed_tpu.ops.evoformer import DS4Sci_EvoformerAttention

    B, N, S, H, D = 1, 128, 256, 4, 32  # MSA row-attention shape (AF2)
    key = jax.random.PRNGKey(0)
    mk = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)
    q = mk(0, (B, N, S, H, D))
    k = mk(1, (B, N, S, H, D))
    v = mk(2, (B, N, S, H, D))
    b1 = mk(3, (B, N, 1, 1, S))
    b2 = mk(4, (B, 1, H, S, S))

    def one(remat):
        attn = DS4Sci_EvoformerAttention
        if remat:
            attn = jax.checkpoint(lambda *a: DS4Sci_EvoformerAttention(a[0], a[1], a[2],
                                                                       biases=[a[3], a[4]]))
            loss0 = lambda q: (attn(q, k, v, b1, b2).astype(jnp.float32) ** 2).mean()
        else:
            loss0 = lambda q: (attn(q, k, v, biases=[b1, b2]).astype(jnp.float32) ** 2).mean()

        def make(n):
            @jax.jit
            def f(q):
                def body(x, _):
                    l, g = jax.value_and_grad(loss0)(x)
                    return x + g.astype(x.dtype) * 1e-4, l
                return jax.lax.scan(body, q, None, length=n)
            return f

        f1, f2 = make(2), make(10)
        x, ls = f1(q)
        float(ls[-1])
        x, ls = f2(x)
        float(ls[-1])

        def t(f, x):
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                x, ls = f(x)
                float(ls[-1])
                best = min(best, time.perf_counter() - t0)
            return best, x

        ta, x = t(f1, x)
        tb, x = t(f2, x)
        return (tb - ta) / 8

    plain = one(False)
    remat = one(True)
    # fwd 4*N*H*S^2*D mults-adds *2, bwd ~2.5x
    flops = 2 * 4 * B * N * H * S * S * D * 3.5
    return {"shape": f"B{B} N{N} S{S} H{H} D{D}", "fwd_bwd_ms": round(plain * 1e3, 2),
            "achieved_tflops": round(flops / plain / 1e12, 1),
            "peak_fraction": round(flops / plain / peak, 3),
            "remat_fwd_bwd_ms": round(remat * 1e3, 2),
            "remat_time_ratio": round(remat / max(plain, 1e-12), 2)}


def _microbench_paged_decode(jnp, T=8, H=16, KVH=16, D=128, bs=16, S=8, MB=64,
                             N1=4, N2=20):
    """Kernel-level paged-attention decode microbench: the Pallas fused
    KV-insert + blocked-attention kernel vs nothing else — one decode batch
    (8 sequences x 1 token, 1k context each at the default shape) applied in
    a chained on-device scan, two-point differenced with a host-fetch
    barrier (the decode-loop methodology at kernel granularity). Shapes are
    overridable so the CPU interpret-mode smoke test stays cheap."""
    import jax
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention_update

    NB = S * MB + 1                    # +1: the drop-mode scatter target
    key = jax.random.PRNGKey(0)
    mk = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)
    q = mk(0, (T, H, D))
    k_new = mk(1, (T, KVH, D))
    v_new = mk(2, (T, KVH, D))
    cache = mk(3, (1, 2, NB, KVH, bs, D))
    table = jnp.arange(S * MB, dtype=jnp.int32).reshape(S, MB)
    token_seq = jnp.arange(T, dtype=jnp.int32)
    token_pos = jnp.full((T, ), MB * bs - 1, jnp.int32)
    token_valid = jnp.ones((T, ), bool)

    def make(n):
        @jax.jit
        def f(q, cache):
            def body(carry, _):
                qq, cache = carry
                out, cache = paged_attention_update(qq, k_new, v_new, cache, 0, table,
                                                    token_seq, token_pos, token_valid)
                # chain through q so the scan cannot be elided or reordered
                return (q + out * jnp.bfloat16(1e-3), cache), out[0, 0, 0]
            (_, cache), outs = jax.lax.scan(body, (q, cache), None, length=n)
            return cache, outs[-1]
        return f

    f1, f2 = make(N1), make(N2)
    cache, o = f1(q, cache)
    float(o)
    cache, o = f2(q, cache)
    float(o)

    def t(f, cache):
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            cache, o = f(q, cache)
            float(o)  # host fetch = true barrier
            best = min(best, time.perf_counter() - t0)
        return best, cache

    ta, cache = t(f1, cache)
    tb, cache = t(f2, cache)
    ms = (tb - ta) / (N2 - N1) * 1e3
    return {"seqs": S, "context": MB * bs, "heads": H, "head_dim": D,
            "kernel_step_ms": round(ms, 4),
            "tokens_per_sec": round(T / max(ms / 1e3, 1e-9), 1)}


def _microbench_int4_unpack(jnp, K=4096, N=4096, N1=8, N2=40):
    """Int4 unpack on the decode critical path: x[1,K] @ W[K,N] with W held
    bf16 vs packed-int4 (dequantized inside the jit, as the engine does) —
    the weight-bandwidth story isolated from the rest of the model. Chained
    scans, two-point differenced."""
    import jax
    from deepspeed_tpu.inference.v2.quantization import (_quantize_leaf_int4,
                                                         dequantize_tree)
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (K, N), jnp.bfloat16)
    x0 = jax.random.normal(jax.random.fold_in(key, 1), (1, K), jnp.bfloat16)
    packed = jax.jit(_quantize_leaf_int4)(w)

    def make(n, weights):
        @jax.jit
        def f(x):
            def body(x, _):
                y = x @ dequantize_tree(weights)   # [1, N] (N == K chains back)
                # renormalize so the chain neither explodes nor denorms
                x = (y / (jnp.abs(y).max() + 1e-6)).astype(jnp.bfloat16)
                return x, y[0, 0]
            x, ys = jax.lax.scan(body, x, None, length=n)
            return x, ys[-1]
        return f

    out = {"K": K, "N": N}
    for name, weights in (("bf16", w), ("int4", packed)):
        f1, f2 = make(N1, weights), make(N2, weights)
        x, y = f1(x0)
        float(y)
        x, y = f2(x)
        float(y)

        def t(f, x):
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                x, y = f(x)
                float(y)
                best = min(best, time.perf_counter() - t0)
            return best, x

        ta, x = t(f1, x)
        tb, x = t(f2, x)
        out[name] = {"matmul_us": round((tb - ta) / (N2 - N1) * 1e6, 2)}
    out["int4_speedup"] = round(out["bf16"]["matmul_us"] /
                                max(out["int4"]["matmul_us"], 1e-9), 2)
    return out


def _microbench_legs(jnp, peak):
    """The --microbench kernel suite: two-point differenced on-device kernel
    timings (paged decode, int4 unpack, block-sparse, evoformer) that accrue
    automatically whenever a chip is reachable."""
    return (
        ("paged_decode", lambda: _microbench_paged_decode(jnp)),
        ("int4_unpack", lambda: _microbench_int4_unpack(jnp)),
        ("sparse_attention", lambda: _bench_sparse_attention(jnp)),
        ("evoformer", lambda: _bench_evoformer(jnp, peak)),
    )


def _run_microbench(jnp, peak):
    """``bench.py --microbench``: the kernel-level suite only."""
    extra = {"mode": "microbench"}
    for name, fn in _microbench_legs(jnp, peak):
        extra[name] = fn()
    return {"metric": "paged_decode_kernel_step_ms",
            "value": float(extra["paged_decode"]["kernel_step_ms"]), "unit": "ms",
            "vs_baseline": 0.0, "extra": extra}


def _run_bench(jnp, peak):
    """The headline training leg, then every other leg; an exception in any of
    them is the run's."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.utils import groups

    B, S, GAS, STAGE = 8, 1024, 8, 3
    # the headline leg trains on the Pallas flash kernel (ROADMAP item 1);
    # DSTPU_BENCH_ATTENTION=dense selects the dense path for A/B runs, and
    # the headline_attention leg measures both at this shape regardless
    attention = os.environ.get("DSTPU_BENCH_ATTENTION", "flash")
    cfg = _llama_530m(llama, jnp, S, remat=True, remat_policy="dots",
                      use_flash_attention=(attention != "dense"))
    steps, warmup = 12, 3

    model, params = llama.init_params(cfg, batch_size=B, seq_len=S)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

    groups.initialize_mesh(force=True)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": B,
            "gradient_accumulation_steps": GAS,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": STAGE},
            "bf16": {"enabled": True},
        })

    # Pre-generate host batches (the input pipeline must not sit inside the
    # measured loop; train_batch's device_put overlaps the previous step's
    # compute because dispatch is async).
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(8):
        ids = rng.integers(0, cfg.vocab_size, size=(B * GAS, S + 1), dtype=np.int64)
        batches.append((ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)))

    for i in range(warmup):
        float(engine.train_batch(batch=batches[i % len(batches)]))  # host fetch = true barrier

    # Two-point measurement: total(N) = N*step + fixed. The steps chain through the
    # donated params, so ONE final scalar fetch forces the whole chain; differencing
    # two N's cancels the fixed dispatch latency and async-dispatch skew.
    def run(n):
        t0 = time.perf_counter()
        loss = None
        for i in range(n):
            loss = engine.train_batch(batch=batches[i % len(batches)])
        float(loss)
        return time.perf_counter() - t0, loss

    n1 = max(2, steps // 4)
    t1, _ = run(n1)
    t2, loss = run(steps)
    step_time = (t2 - t1) / (steps - n1)
    if step_time <= 0:  # timing noise — fall back to plain avg
        step_time = t2 / steps
    tokens_per_sec = B * GAS * S / step_time
    mfu = tokens_per_sec * _flops_per_token(cfg, n_params, S) / peak

    extra = {
        "mfu": round(mfu, 4),
        "n_params": n_params,
        "batch": B,
        "gas": GAS,
        "seq": S,
        "zero_stage": STAGE,
        "attention": cfg.use_flash_attention and "flash" or "dense",
        "loss_final": float(loss),
    }

    # free the training engine's HBM before the other legs
    del engine, params
    legs = (
        ("headline_attention", lambda: _bench_headline_attention(llama, groups, jnp, peak)),
        ("long_seq_train", lambda: _bench_long_seq(llama, groups, jnp, peak)),
        ("microbench_paged_decode", lambda: _microbench_paged_decode(jnp)),
        ("microbench_int4_unpack", lambda: _microbench_int4_unpack(jnp)),
        ("inference", lambda: _bench_inference(llama, groups, jnp)),
        ("prefix_cache", lambda: _bench_prefix_cache(llama, groups, jnp)),
        ("speculative_decode", lambda: _bench_speculative_decode(llama, groups, jnp)),
        ("int4_weights", lambda: _bench_int4_weights(llama, groups, jnp)),
        ("sparse_attention", lambda: _bench_sparse_attention(jnp)),
        ("evoformer", lambda: _bench_evoformer(jnp, peak)),
    )
    for name, fn in legs:
        extra[name] = fn()
    return {"metric": "llama_train_tokens_per_sec_per_chip",
            "value": round(tokens_per_sec, 1), "unit": "tokens/s",
            "vs_baseline": round(mfu / 0.45, 4), "extra": extra}


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"bench.py: jax.devices()[0].platform is {platform!r}, not 'tpu': a device "
              f"metric is measured on the device or not at all", file=sys.stderr)
        return 1

    import jax.numpy as jnp

    from deepspeed_tpu.perf.chip_specs import chip_spec_for_device_kind
    from deepspeed_tpu.utils.jax_platform import enable_compile_cache
    enable_compile_cache()
    peak = chip_spec_for_device_kind(devices[0].device_kind).peak_bf16_flops
    result = _run_microbench(jnp, peak) if "--microbench" in argv else _run_bench(jnp, peak)
    result["device"] = {"platform": platform, "kind": devices[0].device_kind,
                        "count": len(devices)}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
