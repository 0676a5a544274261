"""Runner for ``"mode": "serve"`` configurations: the program's engine and
scheduler, in process, under a traffic kind of the loop family
(``initial`` / ``on_finish``).

Requests go through ``ServingScheduler.submit`` and come back through each
request's token stream; the HTTP front end is not in the path (PERF.md, Open
questions: ``*-http``). Everything taken from the program is public API:
``build_engine``, ``engine.put / decode_loop / flush / free_blocks /
lowerable_callables``, ``ServingScheduler.submit / stats / stop``.
"""

import json
import os
import queue
import time

import numpy as np

from benchmark import check, instruments, loadloop
from benchmark.traffic_kinds import _draw

CHECK_PROMPTS = 4
CHECK_STEPS = 8


# ------------------------------------------------------------- buckets ------
# The program pads each batch to a (tokens, sequences, blocks) bucket and
# compiles one program per bucket. These three functions GUESS the set to warm
# from the cell's parameters, by today's padding rule
# (inference/v2/ragged/ragged_wrapper.py). Nothing depends on the guess being
# right: a program the traffic meets and the warm-up did not is counted in
# ``compiles_in_window`` and written to the cell's ``learned`` file, which the
# next run warms; a guessed bucket the program no longer has costs one warm-up
# call that lands in another bucket.
def _pad_tokens(n):
    if n <= 64:
        p = 8
        while p < n:
            p *= 2
        return p
    return -(-n // 128) * 128


def _pow2(n, minimum=4):
    p = minimum
    while p < n:
        p *= 2
    return p


def reachable_programs(engine_cfg, serving_cfg, traffic_params):
    """Every (program, bucket) the cell's traffic can reach: forward buckets
    ``(T, S, MB)`` and, where some request is greedy and ``decode_chunk`` > 1,
    decode loops ``((S, S, MB), K, False)``."""
    sm = engine_cfg["state_manager"]
    budget, max_seqs = sm["max_ragged_batch_size"], sm["max_ragged_sequence_count"]
    block = engine_cfg["kv_block_size"]
    longest = min(sm["max_context"],
                  traffic_params["prompt"]["max"] + traffic_params["output"]["max"])
    tokens = sorted({_pad_tokens(n) for n in range(1, budget + 1)})
    seqs = sorted({max(8, -(-n // 8) * 8) for n in range(1, max_seqs + 1)})
    blocks = sorted({_pow2(n) for n in range(1, -(-longest // block) + 1)})
    forward = [(t, s, mb) for mb in blocks for s in seqs for t in tokens if t >= s]
    loops = []
    k = serving_cfg.get("decode_chunk", 1)
    if k > 1 and float(traffic_params.get("temperature", 0.0)) <= 0.0:
        loops = [((_pad_tokens(s), s, mb), k, False) for mb in blocks for s in seqs]
    return forward, loops


class Warmer:
    """Runs each program once through ``engine.put`` / ``engine.decode_loop``
    with batches built to land in its bucket. Sequence 0 is kept and grown so
    that its block count sets MB; the others are fresh fillers, flushed after
    every call."""

    def __init__(self, engine, engine_cfg, vocab_size):
        self.engine, self.vocab = engine, vocab_size
        self.block = engine_cfg["kv_block_size"]
        self.budget = engine_cfg["state_manager"]["max_ragged_batch_size"]
        self.long_uid, self.long_len = 10**6, 0
        self._next_uid = 10**6 + 1
        self.rng = np.random.default_rng(0)

    def _toks(self, n):
        return self.rng.integers(0, self.vocab, n).astype(np.int32)

    def _grow_long(self, mb, room):
        """Bring the kept sequence to where ``room`` more tokens leave its block
        count in (mb/2, mb] (or <= 4 for the smallest bucket)."""
        low = 0 if mb <= 4 else (mb // 2) * self.block + 1
        target = max(low - room, 0)
        if -(-(self.long_len + room) // self.block) > mb:  # past this bucket: start over
            self.close()
            self.long_uid, self.long_len = self._fillers(1)[0], 0
        while self.long_len < target:
            n = min(self.budget, target - self.long_len)
            self.engine.put([self.long_uid], [self._toks(n)])
            self.long_len += n

    def _fillers(self, n):
        uids = list(range(self._next_uid, self._next_uid + n))
        self._next_uid += n
        return uids

    def forward(self, bucket):
        t, s, mb = bucket
        n_seqs = min(s, t)
        self._grow_long(mb, 1)
        fill = self._fillers(n_seqs - 1)
        share = [(t - 1) // max(1, len(fill))] * len(fill)
        for i in range((t - 1) - sum(share)):
            share[i] += 1
        self.engine.put([self.long_uid] + fill, [self._toks(1)] + [self._toks(n) for n in share])
        self.long_len += 1
        for uid in fill:
            self.engine.flush(uid)

    def decode_loop(self, key):
        (t, s, mb), k, _sampled = key
        self._grow_long(mb, k)
        fill = self._fillers(s - 1)
        self.engine.decode_loop([self.long_uid] + fill, [self._toks(1) for _ in range(s)], k)
        self.long_len += k
        for uid in fill:
            self.engine.flush(uid)

    def sequence_counts(self, max_seqs):
        """``forward`` returns ``logits[:n]`` for the n live sequences, sliced on
        the device: one more tiny program per distinct n. Meet each n once."""
        for n in range(1, max_seqs + 1):
            fill = self._fillers(n)
            self.engine.put(fill, [self._toks(1) for _ in fill])
            for uid in fill:
                self.engine.flush(uid)

    def close(self):
        if self.long_len:
            self.engine.flush(self.long_uid)


def warm(engine, engine_cfg, vocab_size, forward, loops, log):
    warmer = Warmer(engine, engine_cfg, vocab_size)
    # ascending MB, so that the kept sequence only grows
    work = sorted([(b[2], 0, b) for b in forward] + [(k[0][2], 1, k) for k in loops])
    t0 = time.perf_counter()
    for _, is_loop, key in work:
        (warmer.decode_loop if is_loop else warmer.forward)(key)
    warmer.sequence_counts(engine_cfg["state_manager"]["max_ragged_sequence_count"])
    warmer.close()
    ran = engine.lowerable_callables()
    missing = [k for _, is_loop, k in work
               if k not in ran.get("decode_loop" if is_loop else "forward", {})]
    if missing:
        log(f"warm-up: {len(missing)} guessed buckets are not among the engine's programs "
            f"(the padding rule has moved?): {missing[:5]}")
    log(f"warm-up: {len(work)} programs ({len(forward)} forward, {len(loops)} decode_loop) in "
        f"{time.perf_counter() - t0:.1f}s")


def _program_keys(engine):
    ran = engine.lowerable_callables()
    return {"forward": sorted(ran.get("forward", {})),
            "decode_loop": sorted(ran.get("decode_loop", {}), key=repr)}


# ---------------------------------------------------------- correctness -----
def reference_rows(family, params, sizes, prompts, feeds):
    """For each prompt, the reference's float32 logits after the prompt and
    after each fed token: rows [1 + len(feed), vocab]; and, from a sparse
    model's reference, each row's smallest routing gap (else None)."""
    out = []
    for prompt, feed in zip(prompts, feeds):
        ids = np.concatenate([prompt, feed])
        rows = np.arange(prompt.size - 1, ids.size)
        gaps = []
        logits = family.reference.forward_logits(params, sizes, ids, rows=rows, routing_gaps=gaps)
        out.append((np.asarray(logits), np.asarray(gaps[0]) if gaps else None))
    return out


def engine_rows(engine, budget, prompts, feeds, loop_steps):
    """The same positions through the engine: the prompts prefilled TOGETHER in
    chunks under the token budget, then the fed tokens one ``put`` at a time for
    all sequences at once. With ``loop_steps`` the last fed token goes through
    ``decode_loop`` instead, and its first generated token is returned too."""
    uids = list(range(len(prompts)))
    fed = [0] * len(prompts)
    rows = [[] for _ in prompts]
    share = max(1, budget // len(prompts))
    while any(f < p.size for f, p in zip(fed, prompts)):
        batch = [(u, prompts[u][fed[u]:fed[u] + share]) for u in uids if fed[u] < prompts[u].size]
        logits = np.asarray(engine.put([u for u, _ in batch], [t for _, t in batch]))
        for (u, t), row in zip(batch, logits):
            fed[u] += t.size
            if fed[u] == prompts[u].size:
                rows[u].append(row)
    n_put = feeds[0].size - (1 if loop_steps else 0)
    for j in range(n_put):
        logits = np.asarray(engine.put(uids, [f[j:j + 1] for f in feeds]))
        for u, row in zip(uids, logits):
            rows[u].append(row)
    looped = None
    if loop_steps:
        looped = np.asarray(engine.decode_loop(uids, [f[-1:] for f in feeds], loop_steps))[:, 0]
    for u in uids:
        engine.flush(u)
    return [np.stack(r) for r in rows], looped


def correctness(engine, family, sizes, budget, prompts, feeds, ref, loop_steps, log,
                compared=None):
    """The check's rows through ``engine`` against ``ref``: every row inside its
    limit (``check.row_limits``: the configuration's own where it states them),
    the median row inside its where there is one, ``decode_loop``'s first token
    a right one. A dict passed as ``compared`` receives each number compared
    beside its limit."""
    got, looped = engine_rows(engine, budget, prompts, feeds, loop_steps)
    ok = True
    limits = check.row_limits(sizes)
    rel_tol, row_errors = limits["tight"], []
    for i, ((r, gaps), g) in enumerate(zip(ref, got)):
        same, detail = check.logits_close(r[:g.shape[0]], g, rel_tol,
                                          routing_gaps=None if gaps is None
                                          else gaps[:g.shape[0]],
                                          loose_tol=limits["loose"], row_errors=row_errors)
        log(f"correct[{i}] prompt of {prompts[i].size} tokens + {g.shape[0] - 1} fed: {detail}"
            f" -> {'ok' if same else 'WRONG'}")
        ok &= same
        if looped is not None:
            toss_up = gaps is not None and gaps[-1] < check.ROUTING_TOSS_UP_GAP
            hit = check.token_decided(r[-1], looped[i], scale=float(np.abs(r).max()),
                                      rel_tol=limits["loose"] if toss_up else rel_tol)
            log(f"correct[{i}] decode_loop's first token {int(looped[i])} against the reference's "
                f"last row -> {'ok' if hit else 'WRONG'}")
            ok &= hit
    inside, numbers = check.rows_compared(row_errors, limits)
    log("correct: " + "; ".join(f"{name} 2^{np.log2(max(v, 1e-12)):.2f} (limit 2^{np.log2(limit):.2f})"
                                for name, (v, limit) in numbers.items())
        + f" over {len(row_errors)} rows -> {'ok' if inside else 'WRONG'}")
    if compared is not None:
        compared.update(numbers)
    return ok and inside


# ------------------------------------------------------------- the system ---
class SchedulerSystem:
    """The load loop's view of ``ServingScheduler``."""

    def __init__(self, scheduler, engine, capacity_blocks):
        self.scheduler, self.engine, self.capacity = scheduler, engine, capacity_blocks

    def submit(self, req):
        return self.scheduler.submit(req.prompt, max_new_tokens=req.max_new_tokens,
                                     temperature=req.temperature, seed=req.seed)

    def poll(self, handle):
        n = 0
        while True:
            try:
                tok = handle.stream.get(timeout=0)
            except queue.Empty:
                return n, False
            if tok is None:
                return n, True
            n += 1

    def outcome(self, handle):
        state = handle.state.name
        return state == "DONE", f"{state} {getattr(handle, 'error', None) or ''}".strip()

    def sample(self):
        return {"kv_blocks_used": self.capacity - self.engine.free_blocks}


# ---------------------------------------------------------------- run -------
def prepare(ctx):
    """Weights from the seed, the reference, the engine, the correctness check
    and the warm-up: everything a window needs, once. Returns the prepared
    state ``measure`` takes."""
    import jax

    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine

    config, log, family = ctx["config"], ctx["log"], ctx["family"]
    params_doc = ctx["traffic"]["params"]
    seed = ctx["seed"]
    cfg = family.program_config(config)

    t = time.perf_counter()
    before = ctx["meter"].snapshot()
    params = family.serving_params(cfg, seed)
    jax.block_until_ready(params)
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    log(f"weights: {n_bytes / 2**30:.2f} GiB in {time.perf_counter() - t:.1f}s "
        f"({ctx['meter'].compiled_since(before)} programs compiled)")

    # the reference first: the KV pool is not there yet, so its float32
    # temporaries have the room
    rng = np.random.default_rng([seed, 0xc0de])
    lengths = _draw.lengths(params_doc["prompt"], CHECK_PROMPTS, rng)
    prompts = [_draw.tokens(rng, cfg.vocab_size, n) for n in lengths]
    feeds = [_draw.tokens(rng, cfg.vocab_size, CHECK_STEPS) for _ in prompts]
    t = time.perf_counter()
    ref = reference_rows(family, params, config, prompts, feeds)
    log(f"reference: {CHECK_PROMPTS} prompts of {lengths.tolist()} tokens in "
        f"{time.perf_counter() - t:.1f}s")

    engine_cfg = config["engine"]
    engine = build_engine(params, cfg, RaggedInferenceEngineConfig(**engine_cfg))
    capacity = engine.free_blocks
    budget = engine_cfg["state_manager"]["max_ragged_batch_size"]
    forward, loops = reachable_programs(engine_cfg, config["serving"], params_doc)
    learned_path = os.path.join(ctx["state_dir"], f"{ctx['workload']}.programs.json")
    if os.path.exists(learned_path):
        with open(learned_path) as f:
            learned = json.load(f)
        forward = sorted(set(forward) | {tuple(b) for b in learned.get("forward", [])})
        loops = sorted(set(loops) | {(tuple(k[0]), k[1], k[2])
                                     for k in learned.get("decode_loop", [])}, key=repr)
    t = time.perf_counter()
    compared = {}
    correct = correctness(engine, family, config, budget, prompts, feeds, ref,
                          config["serving"].get("decode_chunk", 1) if loops else 0, log,
                          compared=compared)
    log(f"correctness through the engine in {time.perf_counter() - t:.1f}s")
    warm(engine, engine_cfg, cfg.vocab_size, forward, loops, log)
    return {"engine": engine, "cfg": cfg, "correct": bool(correct), "compared": compared,
            "capacity": capacity,
            "warmed": _program_keys(engine), "forward": forward, "loops": loops,
            "learned_path": learned_path}


def measure(ctx, prepared, traffic_doc, seconds, slice_=None):
    """One window of ``traffic_doc`` through a scheduler of its own."""
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler

    engine, cfg = prepared["engine"], prepared["cfg"]
    scheduler = ServingScheduler(engine, ServingConfig(**ctx["config"]["serving"]))
    system = SchedulerSystem(scheduler, engine, prepared["capacity"])
    lead = float(traffic_doc["lead_in_s"])
    traffic = ctx["traffic_kind"].Traffic(traffic_doc["params"], ctx["seed"], seconds, lead,
                                          cfg.vocab_size)
    try:
        # the lead-in is set-up: time 0 of the window is lead_in_s from now
        if slice_ is not None:
            slice_.arm(time.perf_counter() + lead)
        # builds are counted from the start of the lead-in: a program first met
        # there was missed by the warm-up just as one met in the window
        before = ctx["meter"].snapshot()
        requests, samples, t0 = loadloop.run(
            system, traffic, seconds=seconds, lead_in_s=lead,
            drain_s=float(traffic_doc["drain_s"]),
            annotate=instruments.annotate if ctx["trace"] else None)
        after = ctx["meter"].snapshot()
        counters = scheduler.stats()["counters"]
    finally:
        scheduler.stop(drain=False)
    return {"requests": requests, "judged": loadloop.measured(requests, seconds),
            "samples": samples, "t0": t0, "seconds": seconds, "counters": counters,
            "builds_in_window": after["programs"] - before["programs"]}


def run(ctx):
    """``ctx``: what the harness resolved (config, traffic, seed, seconds,
    trace, state_dir, log, meter, family, traffic_kind). Returns the
    run record the metric readers take their numbers from."""
    traffic_doc, log, seconds = ctx["traffic"], ctx["log"], ctx["seconds"]
    with instruments.telemetry_spans(ctx["trace"]) as spans:
        prepared = prepare(ctx)
        slice_ = None
        if ctx["trace"]:
            slice_ = instruments.TraceSlice(ctx["trace_dir"], traffic_doc["trace_start_s"],
                                            traffic_doc["trace_length_s"])
        if spans is not None:
            spans.clear()
        window = measure(ctx, prepared, traffic_doc, seconds, slice_)
        trace_path = slice_.finish() if slice_ is not None else None
        span_rows = spans.export_since(0)["spans"] if spans is not None else []

    # what ran that the warm-up had not: remember it for this cell's next run
    engine, cfg, warmed = prepared["engine"], prepared["cfg"], prepared["warmed"]
    ran = _program_keys(engine)
    new = {kind: [k for k in ran[kind] if k not in warmed[kind]] for kind in ran}
    if any(new.values()):
        log(f"programs first met after warm-up: {new}")
        merged = {"forward": sorted(set(prepared["forward"]) | set(new["forward"])),
                  "decode_loop": sorted(set(prepared["loops"]) | set(new["decode_loop"]),
                                        key=repr)}
        with open(prepared["learned_path"], "w") as f:
            json.dump(merged, f)

    judged = window["judged"]
    latest = max(window["requests"], key=lambda r: r.sent_s - r.due_s)
    log(f"generator: the latest request ({latest.index}) was due at {latest.due_s:.3f}s and sent "
        f"{(latest.sent_s - latest.due_s) * 1e3:.1f} ms later; longest submit call "
        f"{max(r.submit_ms for r in window['requests']):.1f} ms; scheduler counters "
        f"{ {k: v for k, v in window['counters'].items() if v} }")
    bad = [r for r in judged if loadloop.failed(r)]
    for r in bad[:5]:
        log(f"failed request {r.index}: {r.detail or 'no first token before the drain ended'}")
    engine.close()
    return dict(
        window, mode="serve", correct=prepared["correct"], compared=prepared["compared"],
        attempted=len(judged),
        failed=len(bad), spans=span_rows, kv_capacity_blocks=prepared["capacity"], trace_path=trace_path, trace_slice=slice_,
        model={"n_heads": cfg.num_attention_heads, "n_kv_heads": cfg.num_key_value_heads,
               "head_dim": cfg.hidden_size // cfg.num_attention_heads,
               "n_layers": cfg.num_hidden_layers,
               "block_size": ctx["config"]["engine"]["kv_block_size"]})
