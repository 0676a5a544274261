#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives the two main paths once, through the entry points a user
calls, at the published widths of one model each (depth cut, weights random
from ``--seed``):

- ``serve``: Mixtral-8x7B widths → ``build_engine`` → ``ServingScheduler`` →
  ``ServingServer`` → a few requests over HTTP, then the Pallas-kernel path
  against the XLA-gather path on the same prompts;
- ``train``: Llama-2-7B widths → ``deepspeed_tpu.initialize`` (ZeRO-3, bf16,
  AdamW, flash attention) → a few ``train_batch`` steps and one checkpoint
  round trip.

With ``--chips 4`` it runs INSTEAD the two paths that exist only across chips,
each against the same model on one device: expert-parallel serving (two experts
a chip) and ZeRO-3 training over ``data=4``.

Nothing here is a benchmark: seconds are printed as set-up information, split
into compile and run. Every phase raises on failure; the last line of output is
the device the run happened on, and is printed only when every phase passed.
The script refuses to run where ``jax.devices()[0].platform`` is not ``tpu``.
"""

import argparse
import contextlib
import gc
import json
import shutil
import sys
import tempfile
import threading
import time
import urllib.request
from dataclasses import dataclass

import numpy as np


# ----------------------------------------------------------------- sizes ----
@dataclass(frozen=True)
class ServeSizes:
    """What the serve phase runs. The defaults are the chip run; the CPU
    rehearsal (tests/unit/test_chip_smoke.py) shrinks them."""
    layers: int = 2            # of 32: 2 layers are 6.3 GB of bf16 weights
    kv_blocks: int = 2048      # x 64 tokens = 131k tokens of KV pool (1.07 GB at 2 layers)
    max_context: int = 1024
    token_budget: int = 128    # ragged batch tokens: prompts longer than this are chunk-prefilled
    new_tokens: int = 32
    decode_chunk: int = 8
    short_prompt: int = 40
    long_prompt: int = 200     # > token_budget, and prompt + new tokens stay inside 4 blocks
    compare_steps: int = 8
    # the sliding-window check: Mistral-7B widths with the window cut to 512 so
    # that a prompt of window + two feeds + 40 stays inside max_context
    window: int = 512
    window_prompt: int = 808


@dataclass(frozen=True)
class TrainSizes:
    layers: int = 1            # of 32: fp32 master + Adam moments + grads are 18 B/param
    seq_len: int = 2048
    batch: int = 4             # sequences per step (the global batch on any mesh)
    steps: int = 4
    lr: float = 1e-3
    # the rehearsal's tiny matrices sit under ZeRO-3's default persistence
    # threshold (kept whole on every device); it lowers the threshold to shard them
    zero_optimization: tuple = (("stage", 3), )


# tolerances, each with its reason -------------------------------------------
# Serving logits are float32 outputs of bf16 matmuls (8 bits of mantissa). Two
# correct programs for the same model differ where they round: the gather path
# casts attention scores and probabilities to bf16 and the kernel keeps them in
# float32; expert parallelism sums the same products in another order. A few
# roundings of 2^-8 relative on activations of the logits' own magnitude: allow
# 2^-6 of the largest logit (the first chip run measured 2^-7.7, kernel against
# gather). A wrong mask, a wrong block or a dropped token is off by the size of
# the logits themselves, two orders of magnitude above this.
LOGIT_REL_TOL = 2.0**-6
# Training on data=4 changes the order in which gradients and the loss mean are
# summed, nothing else — but Adam divides each gradient by its own magnitude, so
# a rounding-sized difference in a near-zero gradient becomes a full-sized step
# for that element. First-step losses agree to float32 rounding; later steps
# drift by parts in a thousand. A wrong shard or a missed reduction changes the
# loss by parts in ten.
LOSS_REL_TOL = 2e-2


# ------------------------------------------------------------- instruments --
class CompileMeter:
    """Counts what JAX reports about compilation while the script runs: seconds
    in the backend compiler, programs compiled, persistent-cache hits/misses."""

    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()  # the scheduler thread compiles too
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == self._BACKEND_COMPILE:
            with self._lock:
                self.seconds += seconds
                self.programs += 1

    def _on_event(self, event, **_):
        if event in (self._HIT, self._MISS):
            with self._lock:
                if event == self._HIT:
                    self.hits += 1
                else:
                    self.misses += 1

    def snapshot(self):
        with self._lock:
            return (self.seconds, self.programs, self.hits, self.misses)

    @contextlib.contextmanager
    def step(self, name):
        """Time one step of a phase and print it split into compile and run."""
        before, t0 = self.snapshot(), time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        secs, programs, hits, misses = (a - b for a, b in zip(self.snapshot(), before))
        print(f"  [{name}] {wall:.1f}s = compile {secs:.1f}s ({programs} programs; persistent "
              f"cache {hits} hit, {misses} miss) + run {max(0.0, wall - secs):.1f}s", flush=True)


def _report_peak(phase):
    import jax
    stats = jax.devices()[0].memory_stats()  # None on the CPU
    if stats:
        peak = stats["peak_bytes_in_use"]
        print(f"  [{phase}] peak bytes in use on device 0 since the process started: {peak} "
              f"({peak / 2**30:.2f} GiB)")


def check_logits_close(what, ref, other, rel_tol=LOGIT_REL_TOL):
    """``other`` reproduces ``ref`` (rows of float32 logits): finite, within the
    tolerance, and the same greedy token wherever ``ref``'s own top-2 margin is
    outside it (inside it, either token is a right answer)."""
    ref, other = np.asarray(ref, np.float32), np.asarray(other, np.float32)
    if not (np.isfinite(ref).all() and np.isfinite(other).all()):
        raise AssertionError(f"{what}: non-finite logits")
    tol = rel_tol * float(np.abs(ref).max())
    worst = float(np.abs(ref - other).max())
    if worst > tol:
        raise AssertionError(f"{what}: logits differ by {worst:.4g}, tolerance {tol:.4g}")
    top2 = np.sort(ref, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > 2 * tol
    same = ref.argmax(-1) == other.argmax(-1)
    if not same[decided].all():
        raise AssertionError(f"{what}: greedy tokens differ where the margin is outside the tolerance")
    print(f"  [{what}] max |dlogit| {worst:.4g} (tolerance {tol:.4g}); greedy tokens equal at "
          f"{int(same.sum())}/{same.size} positions, {int((~decided).sum())} inside the margin")


def greedy_chain(engine, uid, prompt, steps, feed=None, budget=None):
    """Prefill ``prompt`` (in chunks of ``budget`` tokens, if given) and decode
    ``steps`` tokens one ``put`` at a time. Returns (tokens fed back, logits
    [1 + steps, vocab]: the last prefill chunk's row first). With ``feed`` the
    next token is taken from it (teacher forcing: the same inputs on two engines,
    so every position compares), otherwise it is this engine's own argmax."""
    prompt = np.asarray(prompt)
    for at in range(0, prompt.size, budget or prompt.size):
        row = np.asarray(engine.put([uid], [prompt[at:at + (budget or prompt.size)]]))[0]
    rows = [row]
    tokens = []
    for j in range(steps):
        nxt = int(feed[j]) if feed is not None else int(rows[-1].argmax())
        tokens.append(nxt)
        rows.append(np.asarray(engine.put([uid], [np.asarray([nxt])]))[0])
    engine.flush(uid)
    return tokens, np.stack(rows)


def _prompts(seed, vocab, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).tolist() for n in lengths]


def _engine_config(sizes, **kw):
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)
    return RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(
            memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=sizes.kv_blocks),
            max_context=sizes.max_context, max_ragged_batch_size=sizes.token_budget,
            max_ragged_sequence_count=8),
        kv_block_size=64, **kw)


# ------------------------------------------------------------------- serve --
def _http_json(url, body=None, timeout=900):
    req = urllib.request.Request(url, data=None if body is None else json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _http_sse(url, body, timeout=900):
    """POST a streaming request; returns (tokens streamed one by one, final doc)."""
    req = urllib.request.Request(url, data=json.dumps({**body, "stream": True}).encode(),
                                 headers={"Content-Type": "application/json"})
    streamed, final = [], None
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for line in resp:
            if not line.startswith(b"data: "):
                continue  # keepalive comments, blank separators
            doc = json.loads(line[6:])
            if doc.get("done"):
                final = doc
            else:
                streamed.append(doc["token"])
    return streamed, final


def _check_done(what, doc, n_tokens):
    if doc is None or doc["state"] != "DONE" or len(doc["tokens"]) != n_tokens:
        raise AssertionError(f"{what}: expected DONE with {n_tokens} tokens, got "
                             f"{doc and {k: doc[k] for k in ('state', 'n_tokens', 'error')}}")


def serve_phase(meter, seed, sizes=ServeSizes(), config=None, window_config=None):
    """Mixtral widths through engine → scheduler → HTTP server; kernel path
    against gather path; then :func:`window_check`. ``config`` and
    ``window_config`` replace the published-width configs (the CPU rehearsal
    passes tiny ones)."""
    import jax

    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.modules.heuristics import attention_implementation
    from deepspeed_tpu.models import mixtral
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler, ServingServer

    cfg = config or mixtral.MixtralConfig(num_hidden_layers=sizes.layers)
    print(f"phase serve: Mixtral widths hidden={cfg.hidden_size} ffn={cfg.intermediate_size} "
          f"heads={cfg.num_attention_heads}/{cfg.num_key_value_heads} experts="
          f"{cfg.num_local_experts} top-{cfg.num_experts_per_tok} vocab={cfg.vocab_size}; "
          f"depth cut to {cfg.num_hidden_layers} layers; KV pool {sizes.kv_blocks} blocks x 64 "
          f"tokens, max_context {sizes.max_context}, token budget {sizes.token_budget}", flush=True)

    with meter.step("weights: bf16, made on the device from the seed"):
        _, params = mixtral.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)
        jax.block_until_ready(params)
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    print(f"  weights: {n_bytes} bytes ({n_bytes / 2**30:.2f} GiB)")

    engine_config = _engine_config(sizes)
    engine = build_engine(params, cfg, engine_config)
    scheduler = ServingScheduler(engine, ServingConfig(decode_chunk=sizes.decode_chunk,
                                                       default_max_new_tokens=sizes.new_tokens))
    server = ServingServer(scheduler).start()
    gen = server.url + "/v1/generate"
    short, long_, pair_a, pair_b, streamed_prompt = _prompts(
        seed, cfg.vocab_size,
        (sizes.short_prompt, sizes.long_prompt, sizes.short_prompt, sizes.short_prompt + 9,
         sizes.short_prompt))
    n = sizes.new_tokens
    try:
        health = _http_json(server.url + "/healthz")
        if health.get("status") not in ("ok", "starting"):
            raise AssertionError(f"/healthz answered {health}")

        with meter.step("request: short prompt, greedy (prefill, then decode_loop chunks)"):
            served_short = _http_json(gen, {"prompt": short, "max_new_tokens": n})
        _check_done("short prompt", served_short, n)

        with meter.step(f"request: {len(long_)}-token prompt, chunk-prefilled under the "
                        f"{sizes.token_budget}-token budget"):
            doc = _http_json(gen, {"prompt": long_, "max_new_tokens": n})
        _check_done("long prompt", doc, n)

        with meter.step("requests: two arriving together"):
            docs = [None, None]

            def post(i, prompt):
                docs[i] = _http_json(gen, {"prompt": prompt, "max_new_tokens": n})

            threads = [threading.Thread(target=post, args=(i, p))
                       for i, p in enumerate((pair_a, pair_b))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
        for i, doc in enumerate(docs):
            _check_done(f"concurrent request {i}", doc, n)

        # sampled: a request with a temperature keeps its own seeded stream on
        # the host, so it decodes step by step (put), never in decode_loop
        with meter.step("request: streamed over SSE, sampled (step decode)"):
            streamed, final = _http_sse(gen, {"prompt": streamed_prompt, "max_new_tokens": n,
                                              "temperature": 0.8, "seed": seed})
        _check_done("streamed request", final, n)
        if streamed != final["tokens"]:
            raise AssertionError("streamed tokens differ from the final document's")

        stats = _http_json(server.url + "/v1/stats")
        if _http_json(server.url + "/healthz").get("status") != "ok":
            raise AssertionError("/healthz is not ok after serving")
        if not stats:
            raise AssertionError("/v1/stats answered nothing")
    finally:
        server.stop(drain=False)  # stops the scheduler too

    # which programs ran, and which attention each bucket took
    programs = engine.lowerable_callables()
    for kind in ("forward", "decode_loop"):
        # a forward program is keyed by its (tokens, sequences, blocks) bucket,
        # a decode loop by (bucket, steps, sampled)
        took = [f"{key}→" + attention_implementation(
            engine.model, engine_config, (key if kind == "forward" else key[0])[0])
            for key in sorted(programs[kind])]
        print(f"  programs[{kind}]: " + "; ".join(took))
        if not took:
            raise AssertionError(f"no {kind} program ran")

    # the decode bucket: one token a sequence, padded to (8 tokens, 8 sequences, 4 blocks)
    decode_bucket = (8, 8, 4)
    if decode_bucket not in programs["forward"]:
        raise AssertionError(f"the step-decode bucket {decode_bucket} never ran")
    took = attention_implementation(engine.model, engine_config, decode_bucket[0])
    if took == "paged_token":
        text = engine.lower_forward(decode_bucket).compile().as_text()  # the jit's own cache
        if "tpu_custom_call" not in text or "paged_attention_update" not in text:
            raise AssertionError("heuristics chose paged_token but the decode program holds no "
                                 "Pallas kernel")
        print(f"  decode bucket {decode_bucket}: {text.count('tpu_custom_call')} tpu_custom_call "
              f"(paged_attention_update) in the compiled program")
    elif jax.default_backend() == "tpu":
        raise AssertionError(f"on a TPU the decode bucket must take the kernel, took {took}")

    # kernel path against gather path: same weights, same inputs
    reference = build_engine(params, cfg, _engine_config(sizes, use_paged_kernel=False))
    with meter.step("gather-path engine: prefill"):
        _, g_prefill = greedy_chain(reference, 100, short, 0)
    with meter.step("kernel path vs gather path, teacher-forced step decode"):
        k_tokens, k_logits = greedy_chain(engine, 101, short, sizes.compare_steps)
        _, g_logits = greedy_chain(reference, 101, short, sizes.compare_steps, feed=k_tokens)
    check_logits_close("prefill, kernel vs gather", k_logits[:1], g_prefill)
    check_logits_close("kernel vs gather", k_logits, g_logits)
    # the tokens the server sent are this engine's greedy chain (decode_loop
    # chunks and single steps are two programs around one kernel) — up to the
    # first position whose margin is inside the tolerance, where they may part
    tol = LOGIT_REL_TOL * float(np.abs(k_logits).max())
    for j, (served, stepped) in enumerate(zip(served_short["tokens"], k_tokens)):
        if served != stepped:
            top2 = np.sort(k_logits[j])[-2:]
            if top2[1] - top2[0] > 2 * tol:
                raise AssertionError(f"served token {j} is {served}, step decode says {stepped}")
            break
    engine.close()
    reference.close()
    del engine, reference, scheduler, server, params
    gc.collect()
    window_check(meter, seed, sizes, config=window_config)
    _report_peak("serve")


def window_check(meter, seed, sizes=ServeSizes(), config=None):
    """A sliding-window model past its window: the kernel (tile grid for the
    prompt's chunks, token grid for the decode steps, the pool releasing blocks
    as the window passes them) against the gather arm, same weights and inputs.
    Mistral-7B-v0.1 widths, one layer; the window is ``sizes.window``, not the
    published 4096, so that the check stays inside ``sizes.max_context``."""
    import jax

    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.models import llama

    cfg = config or llama.LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336, num_hidden_layers=1,
        num_attention_heads=32, num_key_value_heads=8, rope_theta=10000.0,
        max_position_embeddings=32768, model_type="mistral", sliding_window=sizes.window,
        remat=False)
    window, n = cfg.sliding_window, sizes.window_prompt
    print(f"  window model: Mistral widths hidden={cfg.hidden_size} heads="
          f"{cfg.num_attention_heads}/{cfg.num_key_value_heads}, {cfg.num_hidden_layers} layer, "
          f"window {window}; a {n}-token prompt in chunks of {sizes.token_budget}", flush=True)
    if n <= window + sizes.token_budget:
        raise AssertionError("the window check's prompt does not pass the window")
    with meter.step("window model weights"):
        _, params = llama.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)
        jax.block_until_ready(params)
    (prompt, ) = _prompts(seed + 1, cfg.vocab_size, (n, ))
    engine = build_engine(params, cfg, _engine_config(sizes))
    reference = build_engine(params, cfg, _engine_config(sizes, use_paged_kernel=False))
    arms = {engine.model.attention_arm(t) for t in (8, sizes.token_budget)}
    if jax.default_backend() == "tpu" and arms != {"paged_token", "paged_tiled"}:
        raise AssertionError(f"on a TPU a window model must take the kernel, took {arms}")
    free = engine.free_blocks
    with meter.step("window model: kernel path vs gather path past the window"):
        k_tokens, k_logits = greedy_chain(engine, 200, prompt, sizes.compare_steps,
                                          budget=sizes.token_budget)
        _, g_logits = greedy_chain(reference, 200, prompt, sizes.compare_steps, feed=k_tokens,
                                   budget=sizes.token_budget)
    check_logits_close("window model, kernel vs gather", k_logits, g_logits)
    # the chain flushed its sequence: the pool is whole, and the window gave blocks back on the way
    if engine.released_blocks <= 0 or engine.free_blocks != free:
        raise AssertionError(f"rolling release: {engine.released_blocks} blocks released, "
                             f"{free - engine.free_blocks} still held after the flush")
    print(f"  window model: {engine.released_blocks} blocks released as the window passed "
          f"(its context spans {-(-(n + sizes.compare_steps) // 64)})")
    engine.close()
    reference.close()


# ------------------------------------------------------------------- train --
def _train_config(sizes, micro):
    return {"train_micro_batch_size_per_gpu": micro, "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": sizes.lr}},
            "zero_optimization": dict(sizes.zero_optimization), "bf16": {"enabled": True}}


def _train_batch(seed, vocab, sizes):
    ids = np.random.default_rng(seed).integers(0, vocab, size=(sizes.batch, sizes.seq_len + 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


def _llama_config(sizes):
    from deepspeed_tpu.models import llama
    return llama.LlamaConfig(num_hidden_layers=sizes.layers, use_flash_attention=True)


def _zero3_engine(cfg, sizes, seed, batch, data_parallel):
    """The ZeRO-3 engine on the CURRENT global mesh, parameters born sharded
    (``example_batch``: one jitted init straight into each device's shard)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import llama

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=llama.LlamaForCausalLM(cfg), config=_train_config(sizes, sizes.batch // data_parallel),
        example_batch=batch, rng_seed=seed)
    return engine


def _check_flash_kernels(text):
    missing = [k for k in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                           "flash_attention_bwd_dq") if k not in text]
    if missing or "tpu_custom_call" not in text:
        raise AssertionError(f"the compiled train step lacks Pallas kernels: {missing}")


def train_phase(meter, seed, sizes=TrainSizes(), config=None):
    """Llama widths through ``initialize`` (ZeRO-3, bf16, AdamW, flash)."""
    import jax

    cfg = config or _llama_config(sizes)
    print(f"phase train: Llama widths hidden={cfg.hidden_size} ffn={cfg.intermediate_size} "
          f"heads={cfg.num_attention_heads} vocab={cfg.vocab_size} flash={cfg.use_flash_attention}; "
          f"depth cut to {cfg.num_hidden_layers} layer(s); {sizes.batch} x {sizes.seq_len} tokens "
          f"a step", flush=True)
    batch = _train_batch(seed, cfg.vocab_size, sizes)
    with meter.step("initialize: ZeRO-3 engine, fp32 master + Adam moments born on the device"):
        engine = _zero3_engine(cfg, sizes, seed, batch, data_parallel=1)
        jax.block_until_ready((engine.params, engine.opt_state))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(engine.params))
    print(f"  parameters: {n_params}")

    losses = []
    for i in range(sizes.steps):
        with meter.step(f"train_batch {i}"):
            losses.append(float(engine.train_batch(batch=batch)))
    print(f"  losses on the repeated batch: {losses}")
    if not np.isfinite(losses).all() or not losses[-1] < losses[0]:
        raise AssertionError(f"loss must be finite and fall on a repeated batch: {losses}")

    if jax.default_backend() == "tpu":
        text = engine.lower_train_batch(batch=batch).compile().as_text()  # the jit's own cache
        _check_flash_kernels(text)
        print(f"  train step: {text.count('tpu_custom_call')} tpu_custom_call (flash forward, "
              f"dK/dV and dQ backward) in the compiled program")

    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        with meter.step("save_checkpoint"):
            engine.save_checkpoint(ckpt, tag="smoke")
        with meter.step("train_batch after the save"):
            after_save = float(engine.train_batch(batch=batch))
        with meter.step("load_checkpoint"):
            engine.load_checkpoint(ckpt, tag="smoke")
        with meter.step("train_batch after the load"):
            after_load = float(engine.train_batch(batch=batch))
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    # the restored state IS the saved state, and the step is deterministic
    if after_load != after_save:
        raise AssertionError(f"checkpoint round trip: next-step loss {after_load} != {after_save}")
    print(f"  checkpoint round trip reproduces the next step's loss: {after_save}")
    engine.destroy()
    _report_peak("train")


# -------------------------------------------------------------- four chips --
def _quarter_everywhere(what, array, n):
    """``array`` is split over ``n`` devices: a 1/n shard on each, no device
    holding the whole."""
    shards = array.addressable_shards
    devices = {s.device for s in shards}
    if len(devices) != n or any(s.data.nbytes * n != array.nbytes for s in shards):
        raise AssertionError(f"{what}: expected 1/{n} of {array.nbytes} bytes on each of {n} "
                             f"devices, got {[(s.device.id, s.data.nbytes) for s in shards]}")


def ep_serve_phase(meter, seed, sizes=ServeSizes(), config=None, devices=None):
    """Expert-parallel serving over ``len(devices)`` chips (default: all) against
    the same model on ONE device, same seed, at a capacity factor where neither
    side can drop a token — so any difference is a fault, not capacity."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.inference.v2.config_v2 import DeepSpeedEPConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.models import mixtral
    from deepspeed_tpu.utils import groups

    devices = list(devices or jax.devices())
    ep = len(devices)
    cfg = config or mixtral.MixtralConfig(num_hidden_layers=sizes.layers)
    # capacity = tokens x top_k / experts x factor; at factor experts/top_k it is
    # the token count itself, and no expert can be offered more than that
    no_drop = cfg.num_local_experts / cfg.num_experts_per_tok
    print(f"phase ep-serve: Mixtral widths, {cfg.num_hidden_layers} layers, "
          f"{cfg.num_local_experts // ep} experts a chip on {ep} chips vs one device; "
          f"capacity factor {no_drop} (no token can be dropped)", flush=True)
    prompts = _prompts(seed, cfg.vocab_size, (sizes.short_prompt, sizes.short_prompt + 9))

    def run(mesh_devices, expert_parallel, feed):
        mesh = groups.initialize_mesh(expert_parallel_size=len(mesh_devices) if expert_parallel else 1,
                                      data_parallel_size=1, devices=mesh_devices, force=True)
        _, params = mixtral.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype,
                                        mesh=mesh)
        engine = build_engine(params, cfg, _engine_config(
            sizes, expert_parallel=DeepSpeedEPConfig(enabled=expert_parallel,
                                                     replica_num=len(mesh_devices),
                                                     capacity_factor=no_drop)))
        out = [greedy_chain(engine, uid, p, sizes.compare_steps,
                            feed=None if feed is None else feed[uid][0])
               for uid, p in enumerate(prompts)]
        if expert_parallel:
            # the stacked expert banks are the model's only 3-D leaves: wi and wo a layer
            banks = [leaf for leaf in engine.model.flattened_params() if leaf.ndim == 3]
            if len(banks) != 2 * cfg.num_hidden_layers:
                raise AssertionError(f"expected 2 expert banks a layer, found {len(banks)}")
            for bank in banks:
                _quarter_everywhere(f"expert bank {bank.shape}", bank, ep)
            cache = engine.model.state_manager.kv_cache.cache
            if not cache.sharding.is_equivalent_to(NamedSharding(mesh, P()), cache.ndim) \
                    or len(cache.addressable_shards) != ep:
                raise AssertionError(f"KV cache is not replicated on the mesh: {cache.sharding}")
            text = engine.lower_forward((8, 8, 4)).compile().as_text()
            n_a2a = text.count(" all-to-all(") + text.count(" all-to-all-start(")
            if n_a2a != 2 * cfg.num_hidden_layers:
                raise AssertionError(f"expected 2 all-to-alls a layer in the decode step, "
                                     f"found {n_a2a}")
            print(f"  expert banks: 1/{ep} on each of {ep} devices; KV cache replicated on the "
                  f"mesh by intent; decode step holds {n_a2a} all-to-alls "
                  f"({cfg.num_hidden_layers} layers x dispatch + return)")
        engine.close()
        del engine, params
        gc.collect()  # the next mesh needs this one's device memory
        return out

    with meter.step("one device"):
        one = run(devices[:1], False, None)
    with meter.step(f"{ep} devices, expert parallel, fed the one-device tokens"):
        many = run(devices, True, one)
    for uid in range(len(prompts)):
        check_logits_close(f"ep x{ep} vs one device, prompt {uid}", one[uid][1], many[uid][1])
    groups.destroy_mesh()
    _report_peak("ep-serve")


def zero3_phase(meter, seed, sizes=TrainSizes(), config=None, devices=None):
    """ZeRO-3 training over ``data=len(devices)`` against the same model on one
    device: same seed, same global batch."""
    import jax

    from deepspeed_tpu.utils import groups

    devices = list(devices or jax.devices())
    dp = len(devices)
    cfg = config or _llama_config(sizes)
    print(f"phase zero3: Llama widths, {cfg.num_hidden_layers} layer(s), data={dp} vs one device; "
          f"{sizes.batch} x {sizes.seq_len} tokens a step", flush=True)
    batch = _train_batch(seed, cfg.vocab_size, sizes)

    def run(mesh_devices):
        n = len(mesh_devices)
        groups.initialize_mesh(devices=mesh_devices, force=True)
        engine = _zero3_engine(cfg, sizes, seed, batch, data_parallel=n)
        losses = [float(engine.train_batch(batch=batch)) for _ in range(sizes.steps)]
        if n > 1:
            leaves = [(jax.tree_util.keystr(path), leaf) for tree in (engine.params, engine.opt_state)
                      for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
                      if getattr(leaf, "ndim", 0) >= 2]
            for name, leaf in leaves:
                _quarter_everywhere(name, leaf, n)
            text = engine.lower_train_batch(batch=batch).compile().as_text()
            # parameters are gathered and gradients reduced onto their shards.
            # The TPU compiler fuses the all-reduce and the shard's slice into
            # one reduce-scatter ("all-reduce-scatter"); the CPU compiler
            # leaves the two apart
            found = {c: text.count(c) for c in ("all-gather", "reduce-scatter", "all-reduce")}
            if not found["all-gather"] or not (found["reduce-scatter"] or found["all-reduce"]):
                raise AssertionError(f"the ZeRO-3 step lacks its collectives: {found}")
            if jax.default_backend() == "tpu":
                _check_flash_kernels(text)
            print(f"  {len(leaves)} parameter and optimizer matrices: 1/{n} on each of {n} "
                  f"devices; mentions in the compiled step: {found}")
        engine.destroy()
        del engine
        gc.collect()  # the next mesh needs this one's device memory
        return losses

    with meter.step("one device"):
        one = run(devices[:1])
    with meter.step(f"data={dp}"):
        many = run(devices)
    print(f"  losses, one device: {one}\n  losses, data={dp}:    {many}")
    if not (np.isfinite(one).all() and np.isfinite(many).all()):
        raise AssertionError("non-finite loss")
    worst = max(abs(a - b) / abs(a) for a, b in zip(one, many))
    if worst > LOSS_REL_TOL:
        raise AssertionError(f"per-step loss differs by {worst:.3g} relative, "
                             f"tolerance {LOSS_REL_TOL}")
    print(f"  per-step loss agrees within {worst:.3g} relative (tolerance {LOSS_REL_TOL})")
    groups.destroy_mesh()
    _report_peak("zero3")


# -------------------------------------------------------------------- main --
def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0, help="fixes weights, prompts and batches")
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4: run only the sharded paths and their one-device comparisons")
    args = parser.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: jax.devices()[0].platform is {platform!r}, not 'tpu': this script "
              f"proves the system on the chip and does not run anywhere else", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax reports {len(devices)} devices",
              file=sys.stderr)
        return 1

    from deepspeed_tpu.utils.jax_platform import enable_compile_cache
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}; seed {args.seed}; "
          f"persistent compile cache at {enable_compile_cache()}", flush=True)
    meter = CompileMeter()
    t0 = time.perf_counter()
    for phase in ((serve_phase, train_phase) if args.chips == 1 else (ep_serve_phase, zero3_phase)):
        phase(meter, args.seed)
        gc.collect()  # engines hold reference cycles; the next phase needs the device memory
    secs, programs, hits, misses = meter.snapshot()
    print(f"chip_smoke: every phase passed in {time.perf_counter() - t0:.1f}s (set-up, not a "
          f"benchmark): compile {secs:.1f}s over {programs} programs, persistent cache "
          f"{hits} hit / {misses} miss")
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
