"""Runner for ``"mode": "serve_blocks"`` configurations: a model that generates
by diffusion over blocks (``engine.model.attention_block`` = B), through the
program's engine and scheduler, in process, under a traffic kind of the loop
family. ``runners/serve.py``'s window (``measure``), its view of the scheduler
(``SchedulerSystem``) and its record, around a ``prepare`` of this runner's:
the comparison that decides ``correct`` for a step that yields a BLOCK of
tokens, and a warm-up of the three programs such a cell's traffic reaches.

Everything taken from the program is public API: ``build_engine``,
``engine.put / block_forward / dispatch_block_loop / flush / free_blocks /
lowerable_callables``, ``ServingScheduler.submit / stats / stop``.

``correct``. Four prompts whose lengths are 0, 1, 2 and 3 mod B, prefilled
TOGETHER in shares of 64 tokens, and a fifth of B + 2 tokens, the PROBE. The
probe is what makes the mask visible: behind a prompt of hundreds of tokens the
three later rows of a row's block are a hundredth of what it attends to, and a
causal mask in their place moves its logits by less than bf16 does; behind six
tokens they are a third. Every prompt is held twice, under two uids:

(ii) THE LOOP. One copy runs ``CHECK_CHUNKS`` chunks of the TIMED program
    (``dispatch_block_loop`` of ``decode_chunk / B`` blocks, the second chunk
    behind the first as in the window; the first block part given). It hands
    back ids, the denoise step at which each position took its token, and the
    confidences its choices were made on. The choice of rows is held EXACTLY,
    on the loop's own numbers: behind each denoise forward the rows taken are
    the ``B / denoising_steps`` still-masked rows of largest confidence, ties
    to the earlier row. And the numbers are held to the reference's: each
    masked row's log-confidence within twice the row's logit tolerance of the
    reference's for that state (below), each token by ``check.token_decided``.
(i) THE WALK. The other copy goes through the same blocks a forward at a time
    (``block_forward`` + the commit ``put``), teacher-forced on the loop's
    choices, so that it stands in every state the loop stood in: every denoise
    forward's B rows of logits against the reference's ``forward_logits`` of
    the whole sequence as it then stands (``check.logits_close`` at
    ``check.logit_rel_tol(layers)``, the routing toss-up rule).
(iii) One more ``block_forward`` of the block after the loop's last, on the
    LOOP's copy: its commits left the K/V a block-masked prefill would have.
(iv) THE BEST ROWS. ``logits_close`` holds every row to a tolerance that has
    to let a routing toss-up pass (four times the tight one, for 19 rows in 20
    at top-8 of 128 over seven layers), and a toss-up moves a row by more than
    a lower precision of the experts' banks alone does. But a toss-up moves
    SOME rows, and only for the worse, where a precision moves EVERY row: the
    rows that agree best have no toss-up in them, and their error is the
    arithmetic's floor. The check's rows' lowest decile (``BEST_ROWS``) of the
    row's error (its largest, as a share of the reference's largest logit) is
    held to ``BEST_ROWS_TOL``, set between the honest reading and the reading
    with the banks rounded to float8 (the configuration's
    ``engine_why.correct`` has both). The median is logged beside it: it
    moves with the toss-ups, by a third of itself from seed to seed.

THE LOG-CONFIDENCE'S TOLERANCE. A row's log-confidence is ``max - logsumexp``
of its logits. Where every logit of a row may be off by t (the row's
tolerance), the max moves by at most t and the logsumexp by at most t: the
log-confidence by at most 2 t. Since the loop's rows are first by its own
confidences, exactly, the reference's log-confidence of a row it took is
within 4 t of the reference's best masked row's; the log counts how many
choices were not the reference's own first.
"""

import time

import numpy as np

from benchmark import check, instruments, loadloop
from benchmark.runners import serve
from benchmark.traffic_kinds import _draw

CHECK_PROMPTS = 4
CHECK_SHARE = 64  # prompt tokens a sequence a prefill step of the check
PROBE_EXTRA = 2  # the probe's tokens past one block
CHECK_CHUNKS = 2  # chunks of the timed program a prompt
LOGCONF_TOL_FACTOR = 2.0  # see THE LOG-CONFIDENCE'S TOLERANCE
# (iv): the rows' lowest decile of the row's error as a share of the largest
# logit, between its two readings at the configuration's init (chip, PR 50, seven
# seeds, 308 rows a run): honest 2^-7.56 .. 2^-7.36, the banks through float8
# 2^-6.90 .. 2^-6.67 (0.63-0.76 above the same seed's honest reading)
BEST_ROWS = 0.1
BEST_ROWS_TOL = 2.0**-7.12


# ------------------------------------------------------------- the check ----
def check_prompts(params_doc, vocab_size, block, rng):
    """Four prompts at the length distribution's 1/8 .. 7/8 quantiles (the
    stratified draw), moved down so that prompt i's length is i mod ``block``:
    all four residues, so the part-given first block is in every check; and
    the probe, one block and ``PROBE_EXTRA`` tokens."""
    lengths = _draw.lengths(params_doc["prompt"], CHECK_PROMPTS, rng)
    lengths = [int(n) - (int(n) - i) % block for i, n in enumerate(sorted(lengths))]
    return [_draw.tokens(rng, vocab_size, n) for n in lengths + [block + PROBE_EXTRA]]


class _Checker:
    """The reference's logits of a block of one sequence's state, and the
    tolerances a row of them is held to."""

    def __init__(self, family, params, sizes):
        self.family, self.params, self.sizes = family, params, sizes
        self.rel_tol = check.logit_rel_tol(sizes["num_hidden_layers"])
        self.calls = 0

    def rows(self, committed, block, masked):
        """``(logits [B, vocab], routing gaps [B])`` of the rows of ``block``
        (``masked`` rows fed the mask token) behind ``committed``."""
        ids = np.concatenate([committed, block])
        flags = np.concatenate([np.zeros(committed.size, bool), masked])
        gaps = []
        logits = self.family.reference.forward_logits(
            self.params, self.sizes, ids, rows=np.arange(committed.size, ids.size),
            routing_gaps=gaps, flags=flags)
        self.calls += 1
        return np.asarray(logits), np.asarray(gaps[0])

    def tol_of(self, gaps):
        """The relative tolerance a row: loose at a routing toss-up."""
        return np.where(np.asarray(gaps) < check.ROUTING_TOSS_UP_GAP,
                        self.rel_tol * check.TOSS_UP_TOL_FACTOR, self.rel_tol)


def _first_blocks(prompts, block):
    whole = [p.size // block * block for p in prompts]
    blocks = [np.concatenate([p[w:], np.zeros(block - (p.size - w), p.dtype)])
              for p, w in zip(prompts, whole)]
    masked = [np.arange(block) >= p.size - w for p, w in zip(prompts, whole)]
    return whole, blocks, masked


def _prefill(engine, uids, prompts, whole, share):
    """The prompts' whole blocks under ``uids``: the probe's one block first,
    alone, then the others together in shares of ``share`` tokens."""
    engine.put(uids[CHECK_PROMPTS:], [p[:w] for p, w in zip(prompts[CHECK_PROMPTS:],
                                                            whole[CHECK_PROMPTS:])])
    fed = [0] * CHECK_PROMPTS
    while any(f < w for f, w in zip(fed, whole)):
        batch = [(i, prompts[i][fed[i]:min(fed[i] + share, whole[i])])
                 for i in range(CHECK_PROMPTS) if fed[i] < whole[i]]
        engine.put([uids[i] for i, _ in batch], [t for _, t in batch])
        for i, t in batch:
            fed[i] += t.size


def system_side(engine, family, sizes, prompts, loop_blocks):
    """Everything the check asks of the ENGINE, in order, and nothing of the
    reference's weights: ``(forwards, loops)``. ``loops``: one a prompt —
    ``(prompt index, the first block's given flags, the loop's steps, its
    confidences)``; ``forwards``: one a denoise forward a prompt of the walk
    and of part (iii) — ``(label, prompt index, committed ids, block, flags,
    the system's logits [B, vocab], took)``, ``took`` what the LOOP took behind
    that forward: ``(rows, tokens, confidences [B])``, or None where it chose
    nothing. The prompts' shares of a prefill step are ``CHECK_SHARE`` tokens,
    or what the token budget leaves each in whole blocks."""
    gen = family.reference.generation(sizes)
    B, n_steps = int(gen["block_length"]), int(gen["denoising_steps"])
    n = len(prompts)
    uids, twins = list(range(n)), list(range(n, 2 * n))
    whole, first, first_masked = _first_blocks(prompts, B)
    budget = sizes["engine"]["state_manager"]["max_ragged_batch_size"]
    share = min(CHECK_SHARE, budget // CHECK_PROMPTS // B * B)
    _prefill(engine, uids, prompts, whole, share)
    _prefill(engine, twins, prompts, whole, share)

    # (ii) the timed program, chunk behind chunk
    blocks, masked, out = first, first_masked, []
    for _ in range(CHECK_CHUNKS):
        chunk = engine.dispatch_block_loop(uids, blocks, masked, loop_blocks)
        out.append((np.asarray(chunk.fetch()), np.asarray(chunk.steps),
                    np.asarray(chunk.confidences)))
        blocks, masked = [np.zeros(B, np.int32)] * n, [np.ones(B, bool)] * n
    ids, steps, conf = (np.concatenate(a, axis=1) for a in zip(*out))
    loops = [(u, ~first_masked[u], steps[u], conf[u]) for u in uids]

    # (i) the walk: the same blocks a forward at a time, in the loop's states
    committed = [p[:w].astype(np.int64) for p, w in zip(prompts, whole)]
    forwards = []
    for b in range(ids.shape[1] // B):
        block_ids, taken = ids[:, b * B:(b + 1) * B], steps[:, b * B:(b + 1) * B]
        for step in range(n_steps):
            flags = taken >= step
            if not flags.any():
                break
            feed = np.where(flags, 0, block_ids)
            logits = np.asarray(engine.block_forward(twins, list(feed), list(flags)))
            for u in uids:
                rows = np.flatnonzero(taken[u] == step)
                if flags[u].any():
                    forwards.append((f"block {b} denoise forward {step}", u, committed[u],
                                     feed[u], flags[u], logits[u],
                                     (rows, block_ids[u][rows], conf[u, b, step])))
        engine.put(twins, list(block_ids))  # the commit
        committed = [np.concatenate([c, row]) for c, row in zip(committed, block_ids)]

    # (iii) the K/V the LOOP's commits left, read by one more denoise forward
    after = [np.zeros(B, np.int32)] * n, [np.ones(B, bool)] * n
    logits = np.asarray(engine.block_forward(uids, *after))
    forwards += [("the block after the loop", u, committed[u], after[0][u], after[1][u],
                  logits[u], None) for u in uids]
    for u in uids + twins:
        engine.flush(u)
    return forwards, loops


def _steps_are_the_schedules(given, steps, B, n_steps):
    """Each block's steps: -1 where the row was given (the first block's
    alone), and ``B / n_steps`` of the other rows a denoise step from 0."""
    take = B // n_steps
    for b in range(steps.size // B):
        taken = steps[b * B:(b + 1) * B]
        was_given = given if b == 0 else np.zeros(B, bool)
        if ((taken < 0) != was_given).any() or \
                sorted(taken[taken >= 0]) != [i // take for i in range(int((~was_given).sum()))]:
            return f"block {b}: steps {taken.tolist()}"
    return None


def judge(forwards, loops, family, sizes, params, log):
    """``system_side``'s records against the reference computed from
    ``params``; True where every row, token, choice and the best rows held."""
    gen = family.reference.generation(sizes)
    B, n_steps = int(gen["block_length"]), int(gen["denoising_steps"])
    take = B // n_steps
    ref = _Checker(family, params, sizes)
    ok = True
    for u, given, steps, _ in loops:
        wrong = _steps_are_the_schedules(given, steps, B, n_steps)
        if wrong:
            log(f"correct[{u}] loop {wrong} are not {take} masked rows a denoise step -> WRONG")
            ok = False

    errors, tight, choices, not_first, own = [], [], 0, 0, 0
    for label, u, committed, block, flags, got, took in forwards:
        want, gaps = ref.rows(committed, block, flags)
        same, detail = check.logits_close(want, got, ref.rel_tol, routing_gaps=gaps)
        log(f"correct[{u}] {label} ({int(flags.sum())} rows masked) behind {committed.size} "
            f"tokens: {detail} -> {'ok' if same else 'WRONG'}")
        ok &= same
        scale = float(np.abs(want).max())
        errors += list(np.abs(want - np.asarray(got, np.float32)).max(axis=-1) / scale)
        tight += list(np.asarray(gaps) >= check.ROUTING_TOSS_UP_GAP)
        if took is None:
            continue
        rows, tokens, conf = took
        own += int(sum(int(t) == int(np.argmax(got[j])) for j, t in zip(rows, tokens)))
        masked = np.flatnonzero(flags)
        # the loop's rule on the loop's own numbers, exactly
        rule = sorted(masked, key=lambda j: (-float(conf[j]), j))[:take]
        # and its numbers against the reference's
        tol = ref.tol_of(gaps) * scale
        logconf = -np.log(np.exp(want - want.max(-1, keepdims=True)).sum(-1))
        off = np.abs(np.log(np.maximum(conf[masked], 1e-38)) - logconf[masked])
        near = bool((off <= LOGCONF_TOL_FACTOR * tol[masked]).all())
        decided = all(check.token_decided(want[j], t, rel_tol=float(tol[j]) / scale, scale=scale)
                      for j, t in zip(rows, tokens))
        choices += len(rows)
        not_first += int(sum(logconf[j] < logconf[masked].max() for j in rows))
        ruled = sorted(rows) == sorted(rule)
        if not (ruled and near and decided):
            log(f"correct[{u}] loop, behind {label}: took rows {[int(j) for j in rows]} with "
                f"tokens {[int(t) for t in tokens]}; its rule on its own confidences "
                f"{np.round(conf, 6).tolist()} takes {[int(j) for j in sorted(rule)]} -> "
                f"{'ok' if ruled else 'WRONG'}; its log-confidences off the reference's by "
                f"{np.round(off, 4).tolist()} of "
                f"{np.round(LOGCONF_TOL_FACTOR * tol[masked], 4).tolist()} -> "
                f"{'ok' if near else 'WRONG'}; tokens {'ok' if decided else 'WRONG'}")
        ok &= ruled and near and decided
    errors, tight = np.asarray(errors), np.asarray(tight, bool)
    best = float(np.quantile(errors, BEST_ROWS))
    held = best <= BEST_ROWS_TOL
    log(f"correct: the lowest decile of {errors.size} rows is off by 2^{np.log2(best):.2f} of "
        f"the largest logit (limit 2^{np.log2(BEST_ROWS_TOL):.2f}) -> {'ok' if held else 'WRONG'}; "
        f"the median row by 2^{np.log2(np.median(errors)):.2f}, the worst by "
        f"2^{np.log2(errors.max()):.2f}, the worst of the {int(tight.sum())} rows at no routing "
        f"toss-up by 2^{np.log2(errors[tight].max(initial=1e-12)):.2f}")
    log(f"correct: the block loop's {choices} choices over {len(loops)} prompts are its rule's "
        f"on its own confidences, and those the reference's of each state; {not_first} took a "
        f"row the reference ranks behind its first; {own} of its tokens are the walk's own "
        f"greedy token of that row; {ref.calls} reference forwards")
    return bool(ok and held)


def correctness(engine, family, sizes, params, prompts, loop_blocks, log):
    """The parts of the module's docstring: the engine's side first
    (``system_side``), then the reference's (``judge``)."""
    forwards, loops = system_side(engine, family, sizes, prompts, loop_blocks)
    return judge(forwards, loops, family, sizes, params, log)


# ------------------------------------------------------------- warm-up ------
def _program_keys(engine):
    ran = engine.lowerable_callables()
    return {kind: sorted(ran.get(kind, {}), key=repr)
            for kind in ("forward", "decode_loop", "block_forward", "block_loop")}


def warm(engine, engine_cfg, vocab_size, block, loop_blocks, log):
    """Every program the traffic reaches: a ``put`` of each token bucket a
    prompt's share of the budget lands in (one sequence bucket, one table
    bucket: the model's), and the block loop of ``loop_blocks`` blocks."""
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import token_buckets
    rng = np.random.default_rng(0)
    budget = engine_cfg["state_manager"]["max_ragged_batch_size"]
    least = engine.model.min_token_bucket
    t0 = time.perf_counter()
    uid = 10**6
    for tokens in token_buckets(budget, least):
        engine.put([uid], [rng.integers(0, vocab_size, tokens).astype(np.int32)])
        engine.flush(uid)
        uid += 1
    engine.put([uid], [rng.integers(0, vocab_size, block).astype(np.int32)])
    engine.block_loop([uid], [np.zeros(block, np.int32)], [np.ones(block, bool)], loop_blocks)
    engine.flush(uid)
    log(f"warm-up: put at {token_buckets(budget, least)} tokens and a block loop of "
        f"{loop_blocks} blocks in {time.perf_counter() - t0:.1f}s; programs "
        f"{ {k: v for k, v in _program_keys(engine).items() if v} }")


# ---------------------------------------------------------------- run -------
def prepare(ctx):
    """Weights from the seed, the engine, the correctness check and the
    warm-up. Returns the prepared state ``serve.measure`` takes."""
    import jax

    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine

    config, log, family = ctx["config"], ctx["log"], ctx["family"]
    params_doc, seed = ctx["traffic"]["params"], ctx["seed"]
    cfg = family.program_config(config)
    B = cfg.block_length
    loop_blocks = config["serving"]["decode_chunk"] // B

    t = time.perf_counter()
    before = ctx["meter"].snapshot()
    params = family.serving_params(cfg, seed, config["assumed"]["init"])
    jax.block_until_ready(params)
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    log(f"weights: {n_bytes / 2**30:.2f} GiB in {time.perf_counter() - t:.1f}s "
        f"({ctx['meter'].compiled_since(before)} programs compiled)")

    engine_cfg = config["engine"]
    engine = build_engine(params, cfg, RaggedInferenceEngineConfig(**engine_cfg))
    capacity = engine.free_blocks
    prompts = check_prompts(params_doc, cfg.vocab_size, B, np.random.default_rng([seed, 0xc0de]))
    log(f"check: {len(prompts)} prompts of {[p.size for p in prompts]} tokens "
        f"({[p.size % B for p in prompts[:CHECK_PROMPTS]]} mod {B}, and the probe)")
    t = time.perf_counter()
    correct = correctness(engine, family, config, params, prompts, loop_blocks, log)
    log(f"correctness through the engine in {time.perf_counter() - t:.1f}s")
    warm(engine, engine_cfg, cfg.vocab_size, B, loop_blocks, log)
    return {"engine": engine, "cfg": cfg, "correct": correct, "capacity": capacity,
            "warmed": _program_keys(engine)}


def run(ctx):
    """``serve.run`` around this runner's ``prepare``: the same window, the
    same record (``mode`` ``"serve"``: what the readers ask for), ``model.
    head_dim`` the configuration's own."""
    traffic_doc, log, seconds = ctx["traffic"], ctx["log"], ctx["seconds"]
    with instruments.telemetry_spans(ctx["trace"]) as spans:
        prepared = prepare(ctx)
        slice_ = None
        if ctx["trace"]:
            slice_ = instruments.TraceSlice(ctx["trace_dir"], traffic_doc["trace_start_s"],
                                            traffic_doc["trace_length_s"])
        if spans is not None:
            spans.clear()
        window = serve.measure(ctx, prepared, traffic_doc, seconds, slice_)
        trace_path = slice_.finish() if slice_ is not None else None
        span_rows = spans.export_since(0)["spans"] if spans is not None else []

    engine, cfg, warmed = prepared["engine"], prepared["cfg"], prepared["warmed"]
    ran = _program_keys(engine)
    new = {kind: [k for k in ran[kind] if k not in warmed[kind]] for kind in ran}
    if any(new.values()):
        log(f"programs first met after warm-up: {new}")
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
        window, mode="serve", correct=prepared["correct"], attempted=len(judged),
        failed=len(bad), spans=span_rows, kv_capacity_blocks=prepared["capacity"],
        trace_path=trace_path, trace_slice=slice_,
        model={"n_heads": cfg.num_attention_heads, "n_kv_heads": cfg.num_key_value_heads,
               "head_dim": cfg.head_dim, "n_layers": cfg.num_hidden_layers,
               "block_size": ctx["config"]["engine"]["kv_block_size"],
               "attention_block": cfg.block_length})
