"""The device draw (inference/v2/sampling.py) and the shape of the scheduler's
``put`` path around it: the distribution and the greedy rule, a row's
independence of its batch, its bucket and the execute path, a handoff that
carries no sampler state, and the evidence that only ids cross to the host.
"""

import json
import struct

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2 import sampling
from deepspeed_tpu.inference.v2.ragged import handoff
from deepspeed_tpu.inference.v2.spec import TokenTree
from deepspeed_tpu.serving import RequestState, ServingConfig, ServingScheduler
from deepspeed_tpu.serving.config import PrefixCacheConfig, SpeculativeConfig
from deepspeed_tpu.serving.request import Request

MAX_STEPS = 400


def _run_until(sched, pred, max_steps=MAX_STEPS):
    for _ in range(max_steps):
        if pred():
            return
        sched.step()
    raise AssertionError(f"predicate not reached in {max_steps} steps")


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n).tolist()


# ------------------------------------------------------------ the function --
@pytest.mark.parametrize("vary", ["draw_index", "seed"])
def test_draws_follow_softmax_of_logits_over_temperature(vary):
    """24,576 draws of one 16-token distribution, over the positions of one
    seed or over seeds at one position: chi-square against
    ``softmax(logits / T)`` (15 degrees of freedom; 44.3 is p = 1e-4)."""
    rows, calls, T = 2048, 12, 0.7
    logits = np.random.default_rng(0).normal(0.0, 0.8, 16).astype(np.float32)
    batch = jnp.asarray(np.tile(logits, (rows, 1)))
    counts = np.zeros(16)
    for c in range(calls):
        n = np.arange(c * rows, (c + 1) * rows)
        fixed = np.full(rows, 5)
        seed, index = (fixed, n) if vary == "draw_index" else (n, fixed)
        ids = np.asarray(sampling.draw(batch, np.full(rows, T), seed, index))
        counts += np.bincount(ids, minlength=16)
    z = logits.astype(np.float64) / T
    expected = np.exp(z - z.max())
    expected *= counts.sum() / expected.sum()
    assert expected.min() > 20
    assert ((counts - expected)**2 / expected).sum() < 44.3


def test_temperature_zero_is_numpy_argmax_first_index_on_a_tie():
    logits = np.random.default_rng(1).normal(size=(8, 64)).astype(np.float32)
    logits[2, [7, 40]] = logits[2].max() + 1.0   # a tie: the first index wins
    logits[5, :] = 0.25                          # every entry tied
    ids = np.asarray(sampling.draw(jnp.asarray(logits), np.zeros(8), np.arange(8),
                                   np.arange(8)))
    assert ids.tolist() == np.argmax(logits, axis=-1).tolist()
    assert ids[2] == 7 and ids[5] == 0
    # greedy and sampled rows share the program: a sampled neighbour changes nothing
    mixed = np.asarray(sampling.draw(jnp.asarray(logits), np.array([0, 1.0] * 4),
                                     np.arange(8), np.arange(8)))
    assert mixed[0::2].tolist() == ids[0::2].tolist()


def test_a_rows_token_is_its_own_whatever_the_batch_bucket_or_path(make_engine, llama_setup):
    """One row — logits, temperature, seed, draw index — alone, among seven
    batch-mates in the 8-row bucket, in the 16-row bucket, as rows a verify
    step holds on the host, and through ``_spec_accept``: one token."""
    cfg, _, _ = llama_setup
    rng = np.random.default_rng(2)
    row = rng.normal(0.0, 2.0, cfg.vocab_size).astype(np.float32)
    T, seed, index = 0.9, 4242, 5

    def among(bucket, at):
        logits = rng.normal(0.0, 2.0, (bucket, cfg.vocab_size)).astype(np.float32)
        logits[at] = row
        temp = rng.uniform(0.0, 1.5, bucket).astype(np.float32)
        seeds, idx = rng.integers(0, 2**32, bucket), rng.integers(0, 500, bucket)
        temp[at], seeds[at], idx[at] = T, seed, index
        return int(np.asarray(sampling.draw(jnp.asarray(logits), temp, seeds, idx))[at])

    alone = np.zeros((8, cfg.vocab_size), np.float32)
    alone[0] = row
    token = int(np.asarray(sampling.draw(jnp.asarray(alone), [T], [seed], [index]))[0])
    assert among(8, 3) == token and among(8, 7) == token
    assert among(16, 0) == token and among(16, 11) == token
    assert int(sampling.draw_host_rows(row[None], [T], [seed], [index])[0]) == token

    # the verify path: a request that has emitted 5 tokens draws row 0 of its
    # feed at draw index 5
    sched = ServingScheduler(make_engine(), ServingConfig(), start=False)
    req = Request([1, 2, 3], max_new_tokens=32, temperature=T, seed=seed)
    req.tokens = [9] * index
    rows = np.stack([row, rng.normal(size=cfg.vocab_size).astype(np.float32)])
    feed = TokenTree.chain([9, (token + 1) % cfg.vocab_size])
    emitted, path, _ = sched._spec_accept_tree(req, feed, rows, None)
    assert emitted == [token] and path == []  # row 0 drawn, the draft after it rejected
    # a donor's tokens count: 2 generated before a handoff + 3 here
    req.tokens, req._draw_base = [9] * 3, 2
    assert sched._spec_accept_tree(req, TokenTree.chain([9]), rows[:1], None)[0] == [token]
    assert sched.stats()["counters"]["host_draws"] == 2
    sched.stop(drain=False)


@pytest.mark.parametrize("mates,bucket", [(0, 8), (7, 8), (11, 16)])
def test_a_sampled_request_reads_the_same_in_either_sequence_bucket(make_engine, llama_setup,
                                                                    mates, bucket):
    """Through the scheduler: alone, with 7 batch-mates (8 sequences a step)
    and with 11 (the 16-sequence bucket), greedy and sampled mates mixed."""
    cfg, _, _ = llama_setup
    prompt = _prompt(cfg, 9, seed=11)
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))

    def serve(n_mates):
        sched = ServingScheduler(make_engine(), ServingConfig(), start=False)
        req = sched.submit(prompt, max_new_tokens=6, temperature=1.0, seed=42)
        for i in range(n_mates):
            sched.submit(_prompt(cfg, 5 + i, seed=100 + i), max_new_tokens=8,
                         temperature=0.0 if i % 2 else 0.7, seed=i)
        _run_until(sched, lambda: req.finished)
        sched.stop(drain=False)
        return req.result()

    alone = serve(0)
    assert serve(mates) == alone
    fetched = {s["args"]["bytes"] for s in telemetry.get_span_recorder().export_since(0)["spans"]
               if s["cat"] == "sched" and s["name"] == "fetch"}
    assert 4 * bucket in fetched and max(fetched) <= 4 * bucket


# ------------------------------------------------------------ the handoff --
@pytest.mark.parametrize("legacy_rng_state", [False, True])
def test_resume_continues_a_sampled_stream_from_the_generated_count(make_engine, llama_setup,
                                                                    legacy_rng_state):
    """A handoff payload carries no sampler state: the peer continues at
    temperature 0.8 from the payload's ``generated`` count, token-identically
    — over two hops, and with an older payload's ``rng_state`` ignored."""
    cfg, _, _ = llama_setup
    prompt, n, kw = _prompt(cfg, 13, seed=5), 9, dict(temperature=0.8, seed=1234)
    peer = ServingScheduler(make_engine(), ServingConfig(), start=False)
    truth_req = peer.submit(prompt, max_new_tokens=n, **kw)
    _run_until(peer, lambda: truth_req.finished)
    truth = truth_req.result(timeout=1)

    def hop(sched, req, at_least):
        _run_until(sched, lambda: req.state is RequestState.DECODE
                   and len(req.tokens) >= at_least)
        out = sched.request_steal(req.handle)
        assert out["status"] == "exported"
        return out["payload"], list(req.tokens)

    first = ServingScheduler(make_engine(), ServingConfig(), start=False)
    payload, got = hop(first, first.submit(prompt, max_new_tokens=n, **kw), 3)
    header, _ = handoff.unpack(payload)
    assert "rng_state" not in header["extra"] and header["extra"]["generated"] == len(got)
    if legacy_rng_state:
        (hdr_len, ) = struct.unpack_from("<I", payload, len(handoff.MAGIC))
        raw = payload[len(handoff.MAGIC) + 4 + hdr_len:]
        header["extra"]["rng_state"] = {"bit_generator": "PCG64", "state": {"state": 1, "inc": 3},
                                        "has_uint32": 0, "uinteger": 0}
        payload = handoff._frame(json.loads(json.dumps(header)), raw)

    second = ServingScheduler(make_engine(), ServingConfig(), start=False)
    payload, more = hop(second, second.submit_resume(payload, max_new_tokens=n - len(got), **kw), 2)
    got += more
    header, _ = handoff.unpack(payload)
    assert header["extra"]["generated"] == len(got)  # the whole life, not this leg

    last = peer.submit_resume(payload, max_new_tokens=n - len(got), **kw)
    _run_until(peer, lambda: last.finished)
    assert got + last.result(timeout=1) == truth
    for s in (peer, first, second):
        s.stop(drain=False)


# ----------------------------------------------- the shape of the mechanism --
def test_put_path_fetches_ids_builds_nothing_and_counts_its_draws(make_engine, llama_setup,
                                                                  monkeypatch):
    """After mixed sampled and greedy requests (decode_chunk 4, so greedy
    decode-only ticks take ``decode_loop``): every ``put`` tick fetched at
    most 4 bytes a row of the sequence bucket; the engine holds no forward
    program that ``engine.put`` alone would not have made; the draw's
    programs were all built inside ``ServingScheduler.__init__`` (a process
    that has none yet), and nothing at all compiled between the end of
    ``__init__`` and the last token once the engine's forward programs
    existed; and the counters agree with what was streamed."""
    cfg, _, _ = llama_setup
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    monkeypatch.setattr(sampling, "_EXECUTABLES", {})
    prompts = [_prompt(cfg, 6 + 3 * i, seed=20 + i) for i in range(5)]
    temps = [0.0, 0.7, 1.0, 0.0, 0.9]

    def misses():
        snap = telemetry.get_registry().snapshot()
        return sum(v for _, v in snap.get("compile_cache_misses_total", []))

    def serve(engine):
        sched = ServingScheduler(engine, ServingConfig(decode_chunk=4), start=False)
        built = misses()
        draws_built = len(sampling._EXECUTABLES)
        assert draws_built > 0
        # the greedy requests outlive the sampled ones: their last ticks are
        # decode-only and take the loop
        reqs = [sched.submit(p, max_new_tokens=7 if t else 15, temperature=t, seed=i)
                for i, (p, t) in enumerate(zip(prompts, temps))]
        _run_until(sched, lambda: all(r.finished for r in reqs))
        built = misses() - built
        assert len(sampling._EXECUTABLES) == draws_built  # none after __init__
        counters = sched.stats()["counters"]
        sched.stop(drain=False)
        return reqs, counters, built

    engine = make_engine()
    first, _, _ = serve(engine)           # builds the forward programs
    mark = telemetry.now_us()
    again, counters, built = serve(engine)
    assert [r.result() for r in again] == [r.result() for r in first]
    assert built == 0

    spans = telemetry.get_span_recorder().export_since(mark)["spans"]
    ticks = [s for s in spans if s["cat"] == "sched" and s["name"] == "tick"]
    fetches = [s for s in spans if s["cat"] == "sched" and s["name"] == "fetch"]
    emits = [s for s in spans if s["cat"] == "sched" and s["name"] == "emit"]
    put_ticks = [t for t in ticks if t["args"]["kind"] == "put"]
    assert put_ticks and any(t["args"]["kind"] == "decode_loop" for t in ticks)
    # a step is fetched in the tick after the one that dispatched it, one at a
    # time and in order: the i-th fetch is the i-th step's
    steps = [t["args"]["kind"] for t in ticks if t["args"]["kind"] != "none"]
    fetches.sort(key=lambda f: f["ts_us"])
    assert len(fetches) == len(steps)
    for kind, fetch in zip(steps, fetches):
        if kind == "put":
            assert fetch["args"]["bytes"] == 4 * 8  # at most five sequences: the bucket of 8

    # the engine's forward programs are engine.put's: (T, S, MB) buckets only
    programs = engine.lowerable_callables()
    assert set(programs) == {"forward", "decode_loop", "verify", "compact", "block_forward",
                             "block_loop"}  # the last two: a block-diffusion model's, empty here
    assert programs["forward"] and all(
        isinstance(k, tuple) and len(k) == 3 and all(isinstance(d, int) for d in k)
        for k in programs["forward"])
    twin = make_engine()
    for key in sorted(programs["forward"]):
        twin._model._program("forward", key)
    assert sorted(twin.lowerable_callables()["forward"]) == sorted(programs["forward"])

    streamed = sum(len(r.tokens) for r in again)
    in_loops = sum(e["args"]["pushed"] for e in emits if e["args"]["device_draws"] == 0)
    assert counters["host_draws"] == 0
    assert counters["device_draws"] == streamed - in_loops
    assert sum(e["args"]["device_draws"] for e in emits) == counters["device_draws"]
    assert all(e["args"]["sample_us"] == 0 for e in emits)


# ---------------------------------------------- chunks fed from device ids --
def test_a_chunk_fed_through_prev_returns_the_tokens_of_one_fed_from_the_host(make_engine,
                                                                              llama_setup):
    """``put_draw`` -> chunk -> chunk -> ``put_draw`` with nothing fetched
    between (each fed from the ids, or the last row, of the one before, and
    one sequence of the second chunk from the host's token), against the same
    four calls each fetched before the next is made."""
    cfg, _, _ = llama_setup
    prompts = [np.asarray(_prompt(cfg, n, seed=40 + n), np.int32) for n in (9, 13)]
    uids, greedy = [0, 1], (np.zeros(2, np.float32), np.zeros(2, np.uint32),
                            np.zeros(2, np.int32))
    placeholder = [np.zeros(1, np.int32)] * 2

    host = make_engine()
    first = np.asarray(host.put_draw(uids, prompts, *greedy))[:2]
    one = host.decode_loop(uids, list(first), 4)
    two = host.decode_loop(uids, list(one[:, -1]), 4)
    last = np.asarray(host.put_draw(uids, list(two[:, -1]), *greedy))[:2]

    engine = make_engine()
    ids = engine.put_draw(uids, prompts, *greedy)
    chunk_one = engine.dispatch_decode_loop(uids, placeholder, 4, prev=(ids, [0, 1]))
    assert chunk_one.ids.shape == (8, ) and chunk_one.tokens.shape == (4, 8)
    # sequence 1 from the host's token: what a request new to the batch is fed
    chunk_two = engine.dispatch_decode_loop(
        uids, [np.zeros(1, np.int32), one[1, -1:]], 4, prev=(chunk_one.ids, [0, -1]))
    after = engine.put_draw(uids, placeholder, *greedy, prev=(chunk_two.ids, [0, 1]))
    # the bookkeeping was all done at dispatch: nothing is fetched yet
    assert engine._state_manager.get_sequence(0).seen_tokens == 9 + 4 + 4 + 1
    assert np.asarray(ids)[:2].tolist() == first.tolist()
    assert chunk_one.fetch().tolist() == one.tolist()
    assert np.asarray(chunk_two).tolist() == two.tolist()   # np.asarray of a chunk fetches it
    assert np.asarray(after)[:2].tolist() == last.tolist()
    assert np.asarray(chunk_two.ids)[:2].tolist() == two[:, -1].tolist()


def test_warm_draw_builds_the_last_row_program_and_chunks_in_flight_compile_nothing(
        make_engine, llama_setup, monkeypatch):
    """A scheduler that runs chunks builds, in ``__init__``, the program that
    takes a chunk's last row, a sequence bucket each (one that does not,
    none); then two requests under a cap of two sequences, chunk behind chunk:
    a second pass over the warmed buckets compiles nothing, the chunk's program
    is the host-fed one under its key, and the streams repeat."""
    import jax.monitoring
    cfg, _, _ = llama_setup
    monkeypatch.setattr(sampling, "_EXECUTABLES", {})
    mgr = dict(max_ragged_batch_size=64, max_ragged_sequence_count=2)
    plain = ServingScheduler(make_engine(**mgr), ServingConfig(), start=False)
    plain.stop(drain=False)
    assert not [k for k in sampling._EXECUTABLES if k[0] == "last_row"]
    engine = make_engine(**mgr)

    def serve():
        sched = ServingScheduler(engine, ServingConfig(decode_chunk=4), start=False)
        built = dict(sampling._EXECUTABLES)
        reqs = [sched.submit(_prompt(cfg, n, seed=n), max_new_tokens=14) for n in (9, 11)]
        _run_until(sched, lambda: all(r.finished for r in reqs))
        counters = sched.stats()["counters"]
        sched.stop(drain=False)
        assert dict(sampling._EXECUTABLES) == built   # none after __init__
        return [r.result() for r in reqs], counters

    first, _ = serve()
    assert [k for k in sampling._EXECUTABLES if k[0] == "last_row"] == [("last_row", 4, 8)]
    keys = set(engine.model._programs)
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiled.append(name) if "backend_compile" in name else None)
    again, counters = serve()
    assert again == first and counters["pipelined_chunks"] >= 3
    assert compiled == [] and set(engine.model._programs) == keys
    # engine.decode_loop lands in the program the chunks in flight ran
    engine.put([10_001], [np.zeros(20, np.int32)])
    engine.decode_loop([10_001], [np.zeros(1, np.int32)], 4)
    engine.flush(10_001)
    assert set(engine.model._programs) == keys


def test_a_greedy_chunk_puts_nothing_on_the_device_ahead_of_its_program(make_engine, llama_setup,
                                                                       monkeypatch):
    """A chunk's program takes the params, the cache and the batch: no key
    and no temperature are made for it, on the host or the device."""
    import jax
    cfg, _, _ = llama_setup
    made = []
    key = jax.random.PRNGKey
    monkeypatch.setattr(jax.random, "PRNGKey", lambda seed: made.append(seed) or key(seed))
    engine = make_engine()
    prompt = np.asarray(_prompt(cfg, 9, seed=5), np.int32)
    nxt = np.asarray(engine.put_draw([0], [prompt], [0.0], [0], [0]))[:1]
    out = []
    for _ in range(3):
        out.append(engine.decode_loop([0], [nxt], 4))
        nxt = out[-1][0, -1:]
    assert made == []
    loop, = engine.lowerable_callables()["decode_loop"].values()
    assert loop._cache_size() == 1  # the three chunks ran one program
    # the tokens are a fresh engine's, one chunk of twelve
    twin = make_engine()
    first = np.asarray(twin.put_draw([0], [prompt], [0.0], [0], [0]))[:1]
    assert np.concatenate(out, axis=1).tolist() == twin.decode_loop([0], [first], 12).tolist()


def test_speculation_draws_from_host_rows_and_says_so(make_engine, llama_setup):
    cfg, _, _ = llama_setup
    spec = ServingConfig(speculative=SpeculativeConfig(enabled=True, max_draft_tokens=3),
                         prefix_cache=PrefixCacheConfig(enabled=True))
    sched = ServingScheduler(make_engine(block_size=4), spec, start=False)
    prompt = _prompt(cfg, 16, seed=3)
    for _ in range(2):  # the repeat drafts from the first's history
        req = sched.submit(prompt, max_new_tokens=10, temperature=0.8, seed=77)
        _run_until(sched, lambda: req.finished)
    counters = sched.stats()["counters"]
    assert counters["host_draws"] > 0 and counters["spec_steps"] > 0
    assert counters["host_draws"] + counters["device_draws"] == 20
    sched.stop(drain=False)
