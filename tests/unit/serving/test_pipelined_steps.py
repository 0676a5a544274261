"""The scheduler's one-deep pipeline: a step — a ``put`` step or a
``decode_loop`` chunk — is dispatched before the step before it is fetched, its
decode rows fed from that step's device ids (a chunk's: its last row): at once
behind a step whose plan was closed to arrivals, at that step's commit time —
its predicted end less the host's lead — behind one whose plan was open;
anything that needs token values or an idle engine fetches the step in flight
first. Whatever a tick does, a request's tokens are the same.
"""

import threading
import time

import numpy as np
import pytest

import jax.monitoring

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2.scheduling_utils import SchedulingError, SchedulingResult
from deepspeed_tpu.serving import (RequestState, ServingConfig, ServingScheduler,
                                   SpeculativeConfig)

MAX_STEPS = 600
# four prompts over a 16-token budget: every step that feeds a prompt is full
WORK = [(40, 6), (23, 9), (70, 4), (9, 12)]
CLOSED = dict(max_ragged_batch_size=16, max_ragged_sequence_count=8)
OPEN = dict(max_ragged_batch_size=512, max_ragged_sequence_count=16)
# chunks of four steps under a cap of two sequences: two decoding requests are
# a closed chunk plan. A finishes by length inside the second chunk, C's prompt
# then rides beside B's decode row (a put step), then B and C decode together
CHUNKED = ServingConfig(decode_chunk=4)
TWO_SEQS = dict(max_ragged_batch_size=64, max_ragged_sequence_count=2)
CHUNK_WORK = [(9, 6), (11, 17), (7, 9)]


def _run_until(sched, pred, max_steps=MAX_STEPS):
    for _ in range(max_steps):
        if pred():
            return
        sched.step()
    raise AssertionError(f"predicate not reached in {max_steps} steps")


def _prompts(cfg, work=WORK, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n, _ in work]


def _submit_all(sched, cfg, temperature=0.0, work=WORK, **kw):
    return [sched.submit(p, max_new_tokens=m, temperature=temperature, seed=7 + i, **kw)
            for i, (p, (_, m)) in enumerate(zip(_prompts(cfg, work), work))]


def _drain_every_step(sched):
    """Make ``sched`` the draining scheduler the references come from: no
    step's duration is ever known, so each is fetched in its own tick."""
    sched._predicted_s = lambda key: None
    return sched


def _serve(make_engine, cfg, temperature=0.0, serving=None, work=WORK, drained=False, **mgr):
    engine = make_engine(**mgr)
    start = engine.free_blocks
    sched = ServingScheduler(engine, serving or ServingConfig(), start=False)
    if drained:
        _drain_every_step(sched)
    reqs = _submit_all(sched, cfg, temperature, work)
    _run_until(sched, lambda: all(r.finished for r in reqs))
    counters = sched.stats()["counters"]
    sched.stop(drain=False)
    assert engine.free_blocks == start
    return [list(r.tokens) for r in reqs], counters


def _counted(counters):
    return counters["pipelined_steps"] + sum(
        v for k, v in counters.items() if k.startswith("drained_steps_"))


@pytest.fixture(scope="module")
def reference(llama_setup):
    """The streams of WORK through a scheduler whose plans all stay open and
    that drains every step."""
    return {}


def _reference(reference, make_engine, cfg, temperature):
    if temperature not in reference:
        tokens, counters = _serve(make_engine, cfg, temperature, drained=True, **OPEN)
        assert counters["pipelined_steps"] == counters["open_behind_steps"] == 0
        assert counters["drained_steps_open"] == counters["put_steps"] == counters["batches"]
        reference[temperature] = tokens
    return reference[temperature]


# ------------------------------------------------------------ same streams --
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_closed_plans_pipeline_and_the_streams_are_the_open_schedulers(
        make_engine, llama_setup, reference, temperature):
    cfg, _, _ = llama_setup
    tokens, counters = _serve(make_engine, cfg, temperature, **CLOSED)
    assert tokens == _reference(reference, make_engine, cfg, temperature)
    assert [len(t) for t in tokens] == [m for _, m in WORK]
    assert counters["pipelined_steps"] >= 5
    assert counters["overrun_rows"] == 0
    # every step is counted once: fetched behind its successor, or why not
    assert _counted(counters) == counters["batches"] == counters["put_steps"]


def test_the_sequence_cap_closes_a_plan_as_the_token_budget_does(make_engine, llama_setup):
    """Two sampled requests under a cap of two sequences: every decode step
    holds both, no third could join, and the steps go behind one another."""
    cfg, _, _ = llama_setup
    work = [(5, 10), (7, 10)]
    got, counters = _serve(make_engine, cfg, 0.8, work=work, max_ragged_batch_size=64,
                           max_ragged_sequence_count=2)
    want, _ = _serve(make_engine, cfg, 0.8, work=work, drained=True, **OPEN)
    assert got == want
    assert counters["pipelined_steps"] >= 8 and counters["open_behind_steps"] == 0


# ----------------------------------------------------- the recording engine --
class _Ids:
    """A step's device ids; fetching them is logged."""

    def __init__(self, ids, n, log):
        self.ids, self.n, self.log = ids, n, log

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.n))
        return np.asarray(self.ids)


class _Chunk:
    """A chunk in flight; fetching it, and handing on its last row, is logged."""

    def __init__(self, chunk, n, uids, log):
        self.chunk, self.n, self.uids, self.log = chunk, n, uids, log

    @property
    def ids(self):
        return _Ids(self.chunk.ids, self.n, self.log)

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.n))
        return self.chunk.fetch()


class _RecordingEngine:
    """The engine, with every ``put_draw``, every ``dispatch_decode_loop`` and
    every fetch of what they returned logged in the order the scheduler made
    them; steps are numbered in dispatch order, whatever their kind."""

    def __init__(self, engine):
        self.__dict__["_engine"] = engine
        self.__dict__["log"] = []
        self.__dict__["chained"] = {}

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def __setattr__(self, name, value):
        setattr(self._engine, name, value)

    def _dispatched(self, kind, uids, kw):
        n = sum(1 for e in self.log if e[0] != "fetch") + 1
        if kw.get("prev") is not None:
            ids, index = kw["prev"]
            self.chained[n] = (ids.n, dict(zip(uids, index)))
            kw["prev"] = (ids.ids, index)
        self.log.append((kind, n))
        return n

    def put_draw(self, uids, tokens, *draw, **kw):
        n = self._dispatched("put_draw", uids, kw)
        return _Ids(self._engine.put_draw(uids, tokens, *draw, **kw), n, self.log)

    def dispatch_decode_loop(self, uids, tokens, n_steps, **kw):
        if self.__dict__.get("refuse_chunks"):
            raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
        n = self._dispatched("decode_loop", uids, kw)
        return _Chunk(self._engine.dispatch_decode_loop(uids, tokens, n_steps, **kw), n,
                      list(uids), self.log)


def test_a_pipelined_tick_dispatches_the_next_step_before_it_fetches_the_last(
        make_engine, llama_setup, reference):
    cfg, _, _ = llama_setup
    engine = _RecordingEngine(make_engine(**CLOSED))
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    reqs = _submit_all(sched, cfg)
    _run_until(sched, lambda: all(r.finished for r in reqs))
    sched.stop(drain=False)
    assert [list(r.tokens) for r in reqs] == _reference(reference, make_engine, cfg, 0.0)
    log = engine.log
    at = {e: i for i, e in enumerate(log)}
    assert sorted(n for kind, n in log if kind == "fetch") == \
        sorted(n for kind, n in log if kind == "put_draw")  # each step fetched once
    behind = [n for n in engine.chained if at[("put_draw", n)] < at[("fetch", n - 1)]]
    assert len(behind) == sched.stats()["counters"]["pipelined_steps"] >= 5
    for n in behind:
        # ... and fetched right after: the host emits step n-1 under step n
        assert log[at[("put_draw", n)] + 1] == ("fetch", n - 1)
    assert all(fed == n - 1 for n, (fed, _) in engine.chained.items())


def test_a_prompts_last_chunk_in_flight_feeds_its_first_decode_row_from_the_device(
        make_engine, llama_setup):
    """One request, prompt of two full chunks, under a budget of 16: the step
    after the final chunk is the request's first decode row; it is dispatched
    with the first token still on the device."""
    cfg, _, _ = llama_setup
    engine = _RecordingEngine(make_engine(max_ragged_batch_size=16, max_ragged_sequence_count=1))
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    prompt = _prompts(cfg, [(32, 0)])[0]
    req = sched.submit(prompt, max_new_tokens=5, temperature=0.8, seed=3)
    sched.step()   # chunk 1: closed (16 tokens), left in flight
    sched.step()   # chunk 2 behind it; chunk 1 fetched
    assert req.state is RequestState.DECODE and req.tokens == [] and req._pending == 1
    sched.step()   # the decode row, behind chunk 2: its input is chunk 2's id 0
    assert engine.chained[3] == (2, {req.uid: 0})
    assert engine.chained[2] == (1, {req.uid: -1})   # a chunk takes the host's ids
    assert len(req.tokens) == 1 and req._pending == 1
    _run_until(sched, lambda: req.finished)
    sched.stop(drain=False)

    plain = _drain_every_step(ServingScheduler(make_engine(**OPEN), ServingConfig(), start=False))
    ref = plain.submit(prompt, max_new_tokens=5, temperature=0.8, seed=3)
    _run_until(plain, lambda: ref.finished)
    plain.stop(drain=False)
    assert list(req.tokens) == list(ref.tokens) and len(req.tokens) == 5


# ------------------------------------------------------- chunks in flight --
def _serve_recorded(make_engine, cfg, serving, work, submit=None, **mgr):
    """``work`` through a recording engine: the streams, the counters, the
    recorder, and the uid of each request."""
    engine = _RecordingEngine(make_engine(**mgr))
    start = engine.free_blocks
    sched = ServingScheduler(engine, serving, start=False)
    reqs = (submit or _submit_all)(sched, cfg, work=work)
    _run_until(sched, lambda: all(r.finished for r in reqs))
    counters = sched.stats()["counters"]
    sched.stop(drain=False)
    assert engine.free_blocks == start
    return [list(r.tokens) for r in reqs], counters, engine, [r.uid for r in reqs]


@pytest.fixture(scope="module")
def chunked(llama_setup):
    """CHUNK_WORK under CHUNKED: through the draining scheduler (plans that
    stay open: every chunk fetched in its own tick) and with chunks in flight."""
    return {}


def _chunked(chunked, make_engine, cfg):
    if not chunked:
        want, drained = _serve(make_engine, cfg, serving=CHUNKED, work=CHUNK_WORK, drained=True,
                               **OPEN)
        assert drained["pipelined_steps"] == drained["pipelined_chunks"] == 0
        assert drained["batches"] - drained["put_steps"] >= 4   # it did run chunks
        chunked.update(want=want, run=_serve_recorded(make_engine, cfg, CHUNKED, CHUNK_WORK,
                                                      **TWO_SEQS))
    return chunked["want"], chunked["run"]


@pytest.mark.parametrize("before, after", [("decode_loop", "decode_loop"),
                                           ("put_draw", "decode_loop"),
                                           ("decode_loop", "put_draw")],
                         ids=["chunk->chunk", "put->chunk", "chunk->put"])
def test_a_step_goes_behind_a_chunk_and_a_chunk_behind_a_step_with_the_same_streams(
        make_engine, llama_setup, chunked, before, after):
    """Request by request the streams are the draining scheduler's; and the
    transition named happened: the successor was dispatched before its
    predecessor was fetched, fed from the predecessor's device ids, and the
    predecessor was fetched right after."""
    cfg, _, _ = llama_setup
    want, (tokens, counters, engine, _) = _chunked(chunked, make_engine, cfg)
    assert tokens == want and [len(t) for t in tokens] == [m for _, m in CHUNK_WORK]
    log = engine.log
    at = {e: i for i, e in enumerate(log)}
    kind = {n: k for k, n in log if k != "fetch"}
    behind = [n for n in engine.chained
              if (kind[n - 1], kind[n]) == (before, after)
              and at[(kind[n], n)] < at[("fetch", n - 1)]]
    assert behind, log
    for n in behind:
        assert log[at[(kind[n], n)] + 1] == ("fetch", n - 1)
        fed_from, rows = engine.chained[n]
        assert fed_from == n - 1 and any(r >= 0 for r in rows.values())
    assert counters["overrun_rows"] == 0
    assert _counted(counters) == counters["batches"]
    chunks = [n for n in kind if kind[n] == "decode_loop"]
    assert counters["batches"] - counters["put_steps"] == len(chunks)
    assert counters["pipelined_chunks"] == sum(
        1 for n in chunks if n in engine.chained and at[("decode_loop", n)] < at[("fetch", n - 1)])
    assert counters["pipelined_chunks"] >= 3


def test_a_member_that_finishes_by_length_inside_a_chunk_is_not_in_the_next(
        make_engine, llama_setup, chunked):
    """A needs 6 tokens: one from its prompt's step, four from the first
    chunk, one from the second. The second chunk is dispatched with the first
    in flight and holds A (5 counted of 6); the step after it does not, though
    nothing of the second chunk has been fetched when it is built — and no row
    ran for a request that had ended."""
    cfg, _, _ = llama_setup
    _, (tokens, counters, engine, uids) = _chunked(chunked, make_engine, cfg)
    a = uids[0]
    kind = {n: k for k, n in engine.log if k != "fetch"}
    with_a = [n for n, (_, rows) in engine.chained.items() if a in rows]
    assert [kind[n] for n in with_a] == ["decode_loop", "decode_loop"]
    last = with_a[-1]
    assert kind[last + 1] == "put_draw" and a not in engine.chained[last + 1][1]
    at = {e: i for i, e in enumerate(engine.log)}
    assert at[("put_draw", last + 1)] < at[("fetch", last)]
    assert len(tokens[0]) == 6 and counters["overrun_rows"] == 0


def test_a_finished_members_kv_is_freed_when_its_chunk_is_fetched(make_engine, llama_setup):
    cfg, _, _ = llama_setup
    engine = make_engine(**TWO_SEQS)
    sched = ServingScheduler(engine, CHUNKED, start=False)
    a, b = _submit_all(sched, cfg, work=CHUNK_WORK[:2])
    _run_until(sched, lambda: a.finished)
    assert a.finish_reason == "length" and len(a.tokens) == 6
    sm = engine._state_manager
    assert sm.get_sequence(a.uid) is None and sm.get_sequence(b.uid) is not None
    _run_until(sched, lambda: b.finished)
    sched.stop(drain=False)
    assert sm.n_tracked_sequences == 0


def test_a_chunk_the_pool_has_no_room_for_runs_as_a_put_step_behind_the_step_in_flight(
        make_engine, llama_setup, chunked):
    """``SchedulingError`` from the chunk's dispatch (K steps a member do not
    fit the KV pool) leaves nothing changed: the same plan goes as a ``put``
    step, behind the step in flight as it would have."""
    cfg, _, _ = llama_setup
    want, _ = _chunked(chunked, make_engine, cfg)
    engine = _RecordingEngine(make_engine(**TWO_SEQS))
    engine.__dict__["refuse_chunks"] = True
    sched = ServingScheduler(engine, CHUNKED, start=False)
    reqs = _submit_all(sched, cfg, work=CHUNK_WORK)
    _run_until(sched, lambda: all(r.finished for r in reqs))
    counters = sched.stats()["counters"]
    sched.stop(drain=False)
    assert [list(r.tokens) for r in reqs] == want
    assert all(k != "decode_loop" for k, _ in engine.log)
    assert counters["put_steps"] == counters["batches"] and counters["pipelined_chunks"] == 0
    assert counters["pipelined_steps"] >= 10 and _counted(counters) == counters["batches"]


def test_a_pool_too_tight_for_a_chunk_falls_back_for_real(make_engine, llama_setup):
    """Two blocks of 16, a sequence each: a chunk of 8 from position 11 would
    need a second block a member. Every decode step is a ``put`` step, and the
    streams are those of a pool with room."""
    cfg, _, _ = llama_setup
    work = [(10, 6), (9, 6)]
    serving = ServingConfig(decode_chunk=8)
    want, roomy = _serve(make_engine, cfg, serving=serving, work=work, **TWO_SEQS)
    assert roomy["batches"] > roomy["put_steps"]
    got, tight = _serve(make_engine, cfg, serving=serving, work=work, num_blocks=2, **TWO_SEQS)
    assert got == want and tight["batches"] == tight["put_steps"] == 6
    assert tight["pipelined_steps"] >= 4


# ------------------------------------------------ ends counted one step early --
@pytest.mark.parametrize("end", ["length", "context"])
def test_a_request_whose_token_in_flight_is_its_last_is_not_in_the_next_plan(
        make_engine, llama_setup, end):
    cfg, _, _ = llama_setup
    mgr = dict(max_ragged_batch_size=64, max_ragged_sequence_count=2)
    if end == "context":
        mgr["max_context"] = 16   # prompts of 9 and 11: cut at 16 positions
    engine = _RecordingEngine(make_engine(**mgr))
    start = engine.free_blocks
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    work = [(9, 6), (11, 30 if end == "context" else 9)]
    reqs = _submit_all(sched, cfg, 0.8, work)
    _run_until(sched, lambda: all(r.finished for r in reqs))
    counters = sched.stats()["counters"]
    sched.stop(drain=False)
    assert engine.free_blocks == start
    assert counters["pipelined_steps"] >= 3 and counters["overrun_rows"] == 0
    assert counters["device_draws"] == sum(len(r.tokens) for r in reqs)  # no surplus row ran
    if end == "length":
        assert [len(r.tokens) for r in reqs] == [6, 9]
        assert all(r.finish_reason == "length" for r in reqs)
    else:
        # positions 0..15 hold the prompt and all but the last generated token
        assert [len(r.tokens) for r in reqs] == [6, 16 - 11 + 1]
        assert reqs[1].finish_reason == "context"
    ref = _drain_every_step(ServingScheduler(
        make_engine(**dict(OPEN, **{k: v for k, v in mgr.items() if k == "max_context"})),
        ServingConfig(), start=False))
    want = _submit_all(ref, cfg, 0.8, work)
    _run_until(ref, lambda: all(r.finished for r in want))
    ref.stop(drain=False)
    assert [list(r.tokens) for r in reqs] == [list(r.tokens) for r in want]


# ------------------------------------------- ends the host cannot count ahead --
@pytest.mark.parametrize("chunk", [0, 4], ids=["put_row", "chunk_row"])
@pytest.mark.parametrize("end", ["eos", "cancel", "deadline"])
def test_a_row_in_flight_for_a_request_that_ended_is_discarded_never_streamed(
        make_engine, llama_setup, end, chunk):
    """``chunk``: the row in flight is a ``put`` step's (sampled requests), or
    a ``decode_loop`` chunk's four tokens (greedy ones): the eos falls inside
    one chunk with the next already dispatched, the cancel and the deadline
    between two fetches."""
    cfg, _, _ = llama_setup
    mgr = dict(max_ragged_batch_size=64, max_ragged_sequence_count=2)
    work = [(9, 12), (11, 12)]
    temperature = 0.0 if chunk else 0.8
    serving = ServingConfig(decode_chunk=chunk) if chunk else ServingConfig()
    full, _ = _serve(make_engine, cfg, temperature, serving=serving, work=work, **mgr)

    engine = make_engine(**mgr)
    start = engine.free_blocks
    sched = ServingScheduler(engine, serving, start=False)
    kw = {}
    cut = 5
    if end == "eos":
        # the first token of request 0's stream that did not occur before it
        cut = next(i for i, t in enumerate(full[0]) if i >= 3 and t not in full[0][:i])
        kw["eos_token_id"] = full[0][cut]
    prompts = _prompts(cfg, work)
    a = sched.submit(prompts[0], max_new_tokens=12, temperature=temperature, seed=7, **kw)
    b = sched.submit(prompts[1], max_new_tokens=12, temperature=temperature, seed=8)
    if end == "eos":
        _run_until(sched, lambda: a.finished)
        assert a.finish_reason == "eos" and list(a.tokens) == full[0][:cut + 1]
    else:
        _run_until(sched, lambda: len(a.tokens) == cut and sched._inflight is not None
                   and a.uid in sched._inflight.row_of)
        if end == "cancel":
            a.cancel()
        else:
            a.deadline = time.monotonic() - 1.0
        _run_until(sched, lambda: a.finished)
        assert a.state is (RequestState.CANCELLED if end == "cancel" else RequestState.TIMED_OUT)
        assert list(a.tokens) == full[0][:cut]   # the token in flight was never streamed
    streamed = len(a.tokens)
    _run_until(sched, lambda: b.finished)
    assert len(a.tokens) == streamed and list(a.stream) == list(a.tokens)
    assert list(b.tokens) == full[1]              # its batch-mate never noticed
    counters = sched.stats()["counters"]
    assert counters["overrun_rows"] >= 1
    sched.stop(drain=False)
    assert engine.free_blocks == start


# ------------------------------------------------------------ drain reasons --
# four sequences a step and four requests: the decode plans are closed while
# all four decode, and every prompt chunk fills the token budget
FOUR_SEQS = dict(max_ragged_batch_size=16, max_ragged_sequence_count=4)
FOUR_SHORT = [(9, 10), (11, 13), (7, 18), (12, 16)]


def _drain_case(reason, chunk):
    """A configuration and workload under which a step in flight — with
    ``chunk``, a ``decode_loop`` chunk of that many steps — meets ``reason``."""
    if reason == "verify":
        return dict(serving=ServingConfig(speculative=SpeculativeConfig(
            enabled=True, max_draft_tokens=3)), mgr=CLOSED, temperature=0.0)
    if reason == "pressure":
        # 8 blocks of 16 under three 60-token prompts, two sequences a step:
        # a plan behind a step in flight would have to evict
        return dict(serving=ServingConfig(decode_chunk=chunk), temperature=0.0,
                    mgr=dict(num_blocks=8, max_context=128, max_ragged_batch_size=16,
                             max_ragged_sequence_count=2),
                    work=[(60, 8), (60, 8), (60, 8)])
    if chunk > 1:
        return dict(serving=ServingConfig(decode_chunk=chunk), mgr=FOUR_SEQS, work=FOUR_SHORT,
                    temperature=0.0)
    return dict(serving=ServingConfig(), mgr=CLOSED, temperature=0.8)


# (a verify step behind a chunk: these greedy streams repeat, so a request
# that decodes always has a draft and no tick of a speculating scheduler is a
# chunk; the rule that drains for it is the build's, whatever is in flight)
@pytest.mark.parametrize("reason, chunk", [
    ("open", 1), ("verify", 1), ("pressure", 1), ("control", 1), ("stop", 1),
    ("open", 4), ("pressure", 4), ("control", 4), ("stop", 4)],
    ids=lambda v: {1: "put_steps", 4: "chunks"}.get(v, v))
def test_each_drain_reason_fires_where_it_should_and_the_stream_is_unchanged(
        make_engine, llama_setup, reason, chunk):
    cfg, _, _ = llama_setup
    case = _drain_case(reason, chunk)
    work = case.get("work", WORK)
    open_mgr = dict(OPEN, **{k: v for k, v in case["mgr"].items()
                             if k in ("max_context", )})
    want, _ = _serve(make_engine, cfg, case["temperature"], work=work, drained=True,
                     serving=ServingConfig(decode_chunk=chunk), **open_mgr)

    engine = make_engine(**case["mgr"])
    start = engine.free_blocks
    sched = ServingScheduler(engine, case["serving"], start=False)
    reqs = _submit_all(sched, cfg, case["temperature"], work)
    ran = []
    def in_flight():
        step = sched._inflight
        return step is not None and (chunk == 1 or step.loop_steps == chunk)

    if reason == "control":
        _run_until(sched, in_flight)
        box = {"done": threading.Event(), "result": None, "error": None}
        # as a handler thread queues it (serving/scheduler.py:_call_on_loop)
        sched._control.append((lambda: ran.append(sched._inflight), box))
        sched.step()
        assert box["done"].is_set() and ran == [None]   # it ran beside an idle engine
        # a manually stepped scheduler runs a control call inline: same rule
        _run_until(sched, in_flight)
        assert sched._call_on_loop(lambda: sched._inflight) is None
    if reason == "stop":
        _run_until(sched, lambda: in_flight()
                   and sched.stats()["counters"]["pipelined_steps"] >= 1)
        sched.stop(drain=True, timeout=60.0)
    else:
        _run_until(sched, lambda: all(r.finished for r in reqs))
    counters = sched.stats()["counters"]
    sched.stop(drain=False)
    assert engine.free_blocks == start
    assert [list(r.tokens) for r in reqs] == want
    assert counters["pipelined_steps"] >= 1, counters
    assert counters[f"drained_steps_{reason}"] >= (2 if reason == "control" else 1), counters
    assert counters["overrun_rows"] == 0
    assert _counted(counters) == counters["batches"]
    if chunk > 1:
        assert counters["batches"] - counters["put_steps"] - counters["spec_steps"] >= 1
        if reason != "pressure":   # its chunks hold one sequence of two: open plans
            assert counters["pipelined_chunks"] >= 1, counters
    if reason == "pressure":
        assert counters["evictions"] >= 1


def test_kill_drops_the_step_in_flight_without_waiting_for_the_device(make_engine, llama_setup):
    cfg, _, _ = llama_setup
    engine = make_engine(**CLOSED)
    start = engine.free_blocks
    sched = ServingScheduler(engine, ServingConfig(), start=False)
    reqs = _submit_all(sched, cfg)
    _run_until(sched, lambda: sched._inflight is not None)
    sched.kill("test")
    assert sched._inflight is None and all(r.state is RequestState.FAILED for r in reqs)
    assert sched.stats()["counters"]["drained_steps_control"] == 1
    assert engine.free_blocks == start


# ------------------------------------------- an open plan, just in time --
STEP_S, HOST_S, FETCH_S = 0.010, 0.001, 0.0003


class _SimulatedDevice(_RecordingEngine):
    """The recording engine on a simulated clock, which the scheduler is given
    too: a dispatch costs the host ``HOST_S``, a step takes the device
    ``STEP_S`` from when it is dispatched or the step before it ends, and a
    fetch returns ``FETCH_S`` after its step ended. Time moves for nothing
    else but the scheduler's own waits (``pause``); ``on_dispatch`` runs at
    the start of every dispatch."""

    def __init__(self, engine):
        super().__init__(engine)
        self.__dict__.update(t=0.0, free_at=0.0, starts={}, ends={}, members={}, keys={},
                             pauses=[], on_dispatch=None, on_pause=None)

    def attach(self, sched):
        sched._now = lambda: self.t
        sched._pause = self.pause
        return sched

    def pause(self, seconds):
        self.pauses.append(seconds)
        self.__dict__["t"] = self.t + seconds
        if self.on_pause is not None:
            self.on_pause()

    def _dispatched(self, kind, uids, kw):
        if self.on_dispatch is not None:
            self.on_dispatch()
        n = super()._dispatched(kind, uids, kw)
        self.__dict__["t"] = self.t + HOST_S
        self.starts[n] = max(self.t, self.free_at)
        self.__dict__["free_at"] = self.ends[n] = self.starts[n] + STEP_S
        self.members[n] = list(uids)
        return n

    def put_draw(self, uids, tokens, *draw, **kw):
        ids = super().put_draw(uids, tokens, *draw, **kw)
        self.keys[ids.n] = self._engine.last_step_key
        return _SimulatedIds(ids, self)


class _SimulatedIds(_Ids):
    def __init__(self, ids, device):
        super().__init__(ids.ids, ids.n, ids.log)
        self.device = device

    def __array__(self, dtype=None, copy=None):
        device = self.device
        device.__dict__["t"] = max(device.t, device.ends[self.n]) + FETCH_S
        return super().__array__(dtype, copy)


def _simulated(make_engine, **mgr):
    device = _SimulatedDevice(make_engine(**mgr))
    return device, device.attach(ServingScheduler(device, ServingConfig(), start=False))


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_open_plans_go_behind_the_step_in_flight_and_the_streams_are_the_drained_schedulers(
        make_engine, llama_setup, reference, temperature):
    """Every plan is open (a budget of 512 tokens, a cap of 16 sequences). The
    first step of each program is fetched in its own tick — that is how its
    duration is first observed —, every other step is dispatched behind the
    step in flight after a wait for that step's commit time, and the
    simulated device goes from one into the next."""
    cfg, _, _ = llama_setup
    device, sched = _simulated(make_engine, **OPEN)
    reqs = _submit_all(sched, cfg, temperature)
    _run_until(sched, lambda: all(r.finished for r in reqs))
    counters = sched.stats()["counters"]
    sched.stop(drain=False)
    assert [list(r.tokens) for r in reqs] == _reference(reference, make_engine, cfg, temperature)
    steps = counters["put_steps"]
    programs = len(set(device.keys.values()))
    # one `open` drain a program and one when the last step left nothing to plan
    assert counters["drained_steps_open"] == programs + 1
    assert counters["open_behind_steps"] == counters["pipelined_steps"] == steps - programs - 1
    assert _counted(counters) == counters["batches"] == steps and counters["overrun_rows"] == 0
    at = {e: i for i, e in enumerate(device.log)}
    behind = [n for n in device.chained if at[("put_draw", n)] < at[("fetch", n - 1)]]
    assert len(behind) == counters["open_behind_steps"] >= 8
    # the wait was made of slices no longer than a tick, and it was worth it:
    # behind a step in flight the device idles at most the fetch's latency
    # (the first observation of a program holds it), and not at all once a
    # period behind another step has been observed
    assert device.pauses and max(device.pauses) <= sched._config.scheduler_tick_s + 1e-12
    idle = [device.starts[n] - device.ends[n - 1] for n in behind]
    assert max(idle) <= FETCH_S + 1e-9 and idle[-1] == 0.0
    assert counters["late_commits"] == 0


@pytest.mark.parametrize("arrives", ["before_the_commit", "after_the_commit"])
def test_an_arrival_before_the_commit_time_is_in_the_next_step_one_after_it_in_the_step_after(
        make_engine, llama_setup, arrives):
    cfg, _, _ = llama_setup
    device, sched = _simulated(make_engine, **OPEN)
    a = sched.submit(_prompts(cfg, [(9, 0)])[0], max_new_tokens=40, temperature=0.8, seed=1)
    _run_until(sched, lambda: sched.stats()["counters"]["open_behind_steps"] >= 3)
    late = []

    def arrive():
        if not late:
            late.append(sched.submit(_prompts(cfg, [(12, 0)], seed=5)[0], max_new_tokens=4,
                                     temperature=0.8, seed=2))

    last = max(device.members)
    if arrives == "before_the_commit":
        device.__dict__["on_pause"] = arrive      # inside the wait: the plan is not built yet
    else:
        device.__dict__["on_dispatch"] = arrive   # the plan is built and on its way
    sched.step()
    b, = late
    assert device.members[last + 1] == ([a.uid, b.uid] if arrives == "before_the_commit"
                                        else [a.uid])
    sched.step()
    assert device.members[last + 2] == [a.uid, b.uid]
    _run_until(sched, lambda: a.finished and b.finished)
    sched.stop(drain=False)
    assert len(a.tokens) == 40 and len(b.tokens) == 4


def test_a_closed_plan_in_flight_never_waits(make_engine, llama_setup):
    cfg, _, _ = llama_setup
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    device, sched = _simulated(make_engine, max_ragged_batch_size=64, max_ragged_sequence_count=2)
    reqs = _submit_all(sched, cfg, 0.8, [(5, 10), (7, 10)])
    _run_until(sched, lambda: all(r.finished for r in reqs))
    counters = sched.stats()["counters"]
    sched.stop(drain=False)
    assert counters["pipelined_steps"] >= 8
    assert counters["open_behind_steps"] == counters["late_commits"] == 0 and device.pauses == []
    spans = telemetry.get_span_recorder().export_since(0)["spans"]
    assert not [s for s in spans if s["name"] == "commit_wait"]
    ticks = [s["args"] for s in spans if s["cat"] == "sched" and s["name"] == "tick"
             and s["args"]["kind"] == "put"]
    assert sum(t["pipelined"] for t in ticks) >= 8
    assert all(t["predicted_us"] == 0 and not t["open_behind"] for t in ticks)


def test_a_program_with_no_observation_drains_open_once_then_never(make_engine, llama_setup):
    cfg, _, _ = llama_setup
    device, sched = _simulated(make_engine, **OPEN)
    asked = []
    commit_wait = sched._commit_wait

    def logged(spans):
        out = commit_wait(spans)
        asked.append((sched._inflight.key, out))
        return out

    sched._commit_wait = logged
    reqs = _submit_all(sched, cfg, 0.8)
    _run_until(sched, lambda: all(r.finished for r in reqs))
    sched.stop(drain=False)
    unknown = [key for key, out in asked if out == "open"]
    assert len(unknown) == len(set(unknown)) == len(set(device.keys.values())) >= 2
    for key in set(unknown):
        first = asked.index((key, "open"))
        assert all(out is None for k, out in asked[first + 1:] if k == key)
    assert all(out in (None, "open") for _, out in asked)


def test_a_late_commit_lengthens_a_period_and_not_the_predicted_duration(
        make_engine, llama_setup):
    cfg, _, _ = llama_setup
    device, sched = _simulated(make_engine, **OPEN)
    req = sched.submit(_prompts(cfg, [(9, 0)])[0], max_new_tokens=40, temperature=0.8, seed=1)
    _run_until(sched, lambda: sched.stats()["counters"]["open_behind_steps"] >= 4)
    key = sched._inflight.key
    assert sched._predicted_s(key) == pytest.approx(STEP_S, abs=1e-9)
    # the host oversleeps by a whole step, once: the step after is dispatched
    # onto a device that has been idle, and its period reads that much longer
    device.__dict__["on_pause"] = lambda: (device.__dict__.update(t=device.t + STEP_S,
                                                                  on_pause=None))
    sched.step()
    sched.step()
    assert max(sched._periods[key]) > 1.15 * STEP_S
    assert sched._predicted_s(key) == pytest.approx(STEP_S, abs=1e-9)
    # a tick that starts after its commit time commits at once
    late = sched.stats()["counters"]["late_commits"]
    device.__dict__["t"] = device.t + 2 * STEP_S
    pauses = len(device.pauses)
    sched.step()
    assert sched.stats()["counters"]["late_commits"] == late + 1 and len(device.pauses) == pauses
    _run_until(sched, lambda: req.finished)
    sched.stop(drain=False)
    assert sched._predicted_s(key) == pytest.approx(STEP_S, abs=1e-9)


@pytest.mark.parametrize("how", ["stop", "kill", "control", "cancel"])
def test_the_wait_ends_within_a_tick_on(make_engine, llama_setup, how):
    """A running scheduler that believes its step in flight has a minute to
    go: each of the four is served at once, not when the minute is over."""
    cfg, _, _ = llama_setup
    engine = make_engine(**OPEN)
    sched = ServingScheduler(engine, ServingConfig(), start=True)
    predicted = sched._predicted_s
    sched._predicted_s = lambda key: None if predicted(key) is None else 60.0
    waits = []
    pause = sched._pause
    sched._pause = lambda s: (waits.append(s), pause(s))
    req = sched.submit(_prompts(cfg, [(9, 0)])[0], max_new_tokens=200, temperature=0.8, seed=1)
    deadline = time.monotonic() + 60
    while len(waits) < 3 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(waits) >= 3 and not req.finished   # it is inside the wait
    t0 = time.monotonic()
    if how == "stop":
        sched.stop(drain=False)
    elif how == "kill":
        sched.kill("test")
    elif how == "control":
        assert sched._call_on_loop(lambda: sched._inflight, timeout=30.0) is None
    else:
        req.cancel()
        while not req.finished and time.monotonic() < t0 + 30:
            time.sleep(0.001)
        assert req.state is RequestState.CANCELLED
    assert time.monotonic() - t0 < 10.0
    assert max(waits) <= sched._config.scheduler_tick_s
    sched.stop(drain=False)


# ----------------------------------------------------- programs and compiles --
@pytest.mark.parametrize("mgr", [CLOSED, OPEN], ids=["closed_plans", "open_plans"])
def test_pipelining_builds_no_program_after_the_scheduler_is_constructed(
        make_engine, llama_setup, mgr):
    """The merge in front of a chained step is compiled when the scheduler is
    constructed, and the forward a chained step runs is the one ``engine.put``
    runs, under its cache key: a second pass over warmed buckets compiles
    nothing, pipelined or not — behind a closed step or, at its commit time,
    behind an open one."""
    cfg, _, _ = llama_setup
    engine = make_engine(**mgr)
    first = ServingScheduler(engine, ServingConfig(), start=False)
    reqs = _submit_all(first, cfg, 0.8)
    _run_until(first, lambda: all(r.finished for r in reqs))
    first.stop(drain=False)
    keys = set(engine.model._programs)
    assert keys and all(kind == "forward" and len(k) == 3 and all(isinstance(n, int) for n in k)
                        for kind, k in keys)

    sched = ServingScheduler(engine, ServingConfig(), start=False)
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiled.append(name) if "backend_compile" in name else None)
    again = _submit_all(sched, cfg, 0.8)
    _run_until(sched, lambda: all(r.finished for r in again))
    counters = sched.stats()["counters"]
    sched.stop(drain=False)
    assert counters["pipelined_steps"] >= 5
    if mgr is OPEN:
        assert counters["open_behind_steps"] >= 5
    assert [list(r.tokens) for r in again] == [list(r.tokens) for r in reqs]
    assert set(engine.model._programs) == keys
    assert compiled == []
    if mgr is CLOSED:
        # engine.put lands in the same programs: a full 16-token chunk, one sequence
        engine.put([10_001], [np.zeros(16, np.int32)])
        engine.flush(10_001)
        assert set(engine.model._programs) == keys


# ------------------------------------------------------------------- tracing --
def test_tick_spans_say_whether_their_step_went_behind_the_last_and_why_not(
        make_engine, llama_setup):
    cfg, _, _ = llama_setup
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    sched = ServingScheduler(make_engine(**CLOSED), ServingConfig(), start=False)
    reqs = _submit_all(sched, cfg)
    _run_until(sched, lambda: all(r.finished for r in reqs))
    counters = sched.stats()["counters"]
    sched.stop(drain=False)
    spans = telemetry.get_span_recorder().export_since(0)["spans"]
    ticks = [s for s in spans if s["cat"] == "sched" and s["name"] == "tick"
             and s["args"]["kind"] == "put"]
    assert len(ticks) == counters["put_steps"]
    assert sum(t["args"]["pipelined"] for t in ticks) == counters["pipelined_steps"] >= 5
    for t in ticks:
        assert ("drain" in t["args"]) == (t["args"]["pipelined"] == 0)
        inside = sorted((s for s in spans if s is not t and s["cat"] in ("sched", "inference")
                         and t["ts_us"] <= s["ts_us"] < t["ts_us"] + t["dur_us"]),
                        key=lambda s: s["ts_us"])
        names = [s["name"] for s in inside]
        if t["args"]["pipelined"]:
            # admit, build_batch, prepare + put (step i+1), fetch, emit (step i)
            assert names[:6] == ["admit", "build_batch", "prepare", "put", "fetch", "emit"]
            put = inside[3]
            assert put["args"]["chained"] >= 0
    assert {t["args"]["drain"] for t in ticks if not t["args"]["pipelined"]} <= {"open"}
    # a step's phase spans do not overlap the step's before it: a pipelined
    # step's start where the step before it was fetched
    steps = sorted({(s["ts_us"], s["ts_us"] + s["dur_us"]) for s in spans
                    if s["cat"] == "serving" and s["name"] in ("prefill", "decode")})
    assert len(steps) == counters["put_steps"]
    assert all(b[0] >= a[1] for a, b in zip(steps, steps[1:]))
    assert sum(b[0] == a[1] for a, b in zip(steps, steps[1:])) == counters["pipelined_steps"]


def test_chunk_ticks_say_the_same_and_their_spans_keep_what_readers_index(
        make_engine, llama_setup):
    cfg, _, _ = llama_setup
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    sched = ServingScheduler(make_engine(**TWO_SEQS), CHUNKED, start=False)
    reqs = _submit_all(sched, cfg, work=CHUNK_WORK)
    _run_until(sched, lambda: all(r.finished for r in reqs))
    counters = sched.stats()["counters"]
    sched.stop(drain=False)
    spans = telemetry.get_span_recorder().export_since(0)["spans"]
    ticks = [s for s in spans if s["cat"] == "sched" and s["name"] == "tick"
             and s["args"]["kind"] in ("put", "decode_loop")]
    chunk_ticks = [t for t in ticks if t["args"]["kind"] == "decode_loop"]
    assert len(ticks) == counters["batches"]
    assert sum(t["args"]["pipelined"] for t in ticks) == counters["pipelined_steps"]
    assert sum(t["args"]["pipelined"] for t in chunk_ticks) == counters["pipelined_chunks"] >= 3
    for t in chunk_ticks:
        assert ("drain" in t["args"]) == (t["args"]["pipelined"] == 0)
        inside = sorted((s for s in spans if s is not t and s["cat"] in ("sched", "inference")
                         and t["ts_us"] <= s["ts_us"] < t["ts_us"] + t["dur_us"]),
                        key=lambda s: s["ts_us"])
        if t["args"]["pipelined"]:
            # step i+1's launch, then step i's fetch and emit
            assert [s["name"] for s in inside][:6] == \
                ["admit", "build_batch", "prepare", "decode_loop", "fetch", "emit"]
    loops = [s for s in spans if s["cat"] == "inference" and s["name"] == "decode_loop"]
    assert len(loops) == len(chunk_ticks)
    for loop in loops:
        # launch and fetch apart: the fetch wrote its time when it happened
        assert loop["args"]["steps"] == 4 and loop["args"]["launch_us"] <= loop["dur_us"] + 1
        assert loop["args"]["fetch_us"] > 0 and "chained" in loop["args"]
    # a step's member spans: one start a step, none overlapping, K tokens a
    # chunk's member unless its request's cap cut the row
    steps = sorted({(s["ts_us"], s["ts_us"] + s["dur_us"]) for s in spans
                    if s["cat"] == "serving" and s["name"] in ("prefill", "decode")})
    assert len(steps) == counters["batches"]
    assert all(b[0] >= a[1] for a, b in zip(steps, steps[1:]))
    kept = sum(s["args"]["tokens"] for s in spans
               if s["cat"] == "serving" and s["name"] == "decode")
    assert kept == sum(len(r.tokens) for r in reqs) - len(reqs)   # all but the first tokens


# ------------------------------------------------------------- the merge --
@pytest.mark.parametrize("placed", ["one_device", "replicated_on_a_mesh", "split_over_a_mesh"])
def test_chain_feeds_the_named_slots_from_the_ids_wherever_they_lie(placed):
    """``sampling.chain``: slot t takes ``ids[src[t]]`` where ``src[t] >= 0``
    and keeps the host's id elsewhere; rows 1..3 of the batch are untouched.
    Ids replicated over a mesh are read from the default device's replica, ids
    split over it from the host: the same batch either way."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from deepspeed_tpu.inference.v2 import sampling

    tok_meta = np.arange(64, dtype=np.int32).reshape(4, 16)
    ids = np.arange(100, 108, dtype=np.int32)
    src = np.full(16, -1, np.int32)
    src[[0, 3, 15]] = [7, 0, 2]
    if placed == "one_device":
        on_device = jax.device_put(ids, jax.devices()[0])
    else:
        mesh = Mesh(np.array(jax.devices()[:2]), ("x", ))
        spec = PartitionSpec() if placed == "replicated_on_a_mesh" else PartitionSpec("x")
        on_device = jax.device_put(ids, NamedSharding(mesh, spec))
    out = np.asarray(sampling.chain(tok_meta, on_device, src))
    want = tok_meta.copy()
    want[0, [0, 3, 15]] = [107, 100, 102]
    assert out.tolist() == want.tolist()


@pytest.mark.parametrize("placed", ["one_device", "replicated_on_a_mesh", "split_over_a_mesh"])
def test_last_row_takes_a_chunks_last_ids_wherever_they_lie(placed):
    """``sampling.last_row``: row ``steps - 1`` of a chunk's ``[steps, rows]``
    tokens. On one device, and replicated over a mesh (read from the default
    device's replica), by the program built ahead of the first step: nothing
    compiles at the first chunk that has a successor behind it."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from deepspeed_tpu.inference.v2 import sampling

    tokens = np.arange(32, dtype=np.int32).reshape(4, 8)
    if placed == "one_device":
        on_device = jax.device_put(tokens, jax.devices()[0])
    else:
        mesh = Mesh(np.array(jax.devices()[:2]), ("x", ))
        spec = PartitionSpec() if placed == "replicated_on_a_mesh" else PartitionSpec(None, "x")
        on_device = jax.device_put(tokens, NamedSharding(mesh, spec))
    sampling.compiled_last_row(4, 8)
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiled.append(name) if "backend_compile" in name else None)
    out = sampling.last_row(on_device)
    if placed != "split_over_a_mesh":
        assert compiled == [] and out.sharding.device_set == {jax.devices()[0]}
    assert np.asarray(out).tolist() == tokens[-1].tolist()
