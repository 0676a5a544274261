"""End-to-end request tracing through the HTTP server (ISSUE acceptance):
every span of a served request shares one trace id, parents correctly under
the root, and the trace id matches the response header — plus the flight
recorder capturing live scheduler state mid-workload."""

import json
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.serving import (RequestState, ServingConfig, ServingScheduler,
                                   ServingServer)
from deepspeed_tpu.serving.server import TRACE_HEADER


def _post(url, doc, timeout=120):
    req = urllib.request.Request(url + "/v1/generate", data=json.dumps(doc).encode(),
                                 headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def _trace_events(trace_id):
    evs = telemetry.state.spans.chrome_trace()["traceEvents"]
    return [e for e in evs if e.get("ph") == "X"
            and e.get("args", {}).get("trace_id") == trace_id]


@pytest.fixture
def traced_server(make_engine, llama_setup):
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    engine = make_engine()
    srv = ServingServer(ServingScheduler(engine, ServingConfig())).start()
    yield srv, llama_setup[0]
    srv.stop(drain=False)


def test_served_request_exports_one_parented_trace(traced_server):
    srv, cfg = traced_server
    prompt = (np.arange(9) % cfg.vocab_size).tolist()
    with _post(srv.url, {"prompt": prompt, "max_new_tokens": 4}) as resp:
        doc = json.loads(resp.read())
        header_trace = resp.headers[TRACE_HEADER]

    # the header names the trace; the body repeats it with the uid
    assert header_trace and doc["trace_id"] == header_trace
    assert doc["uid"] is not None and doc["state"] == "DONE"

    evs = _trace_events(header_trace)
    names = [e["name"] for e in evs]
    # full lifecycle: QUEUED -> PREFILL -> DECODE iterations -> root closes
    assert names.count("request") == 1
    assert names.count("queued") == 1
    assert names.count("prefill") >= 1
    # the first token falls out of the final prefill chunk's logits, so
    # decode iterations account for the remaining n_tokens - 1
    assert names.count("decode") == doc["n_tokens"] - 1

    root = next(e for e in evs if e["name"] == "request")
    assert root["args"]["parent_id"] is None
    assert root["args"]["uid"] == doc["uid"]
    assert root["args"]["state"] == "DONE"
    assert root["args"]["generated"] == doc["n_tokens"]
    # ISSUE acceptance: the parent chain — every non-root span is a direct
    # child of the root, and they all share the header's trace id
    for e in evs:
        if e["name"] != "request":
            assert e["args"]["parent_id"] == root["args"]["span_id"]
            assert e["args"]["uid"] == doc["uid"]
    # one Perfetto track per request: same tid everywhere, with a name
    assert len({e["tid"] for e in evs}) == 1
    meta = [m for m in telemetry.state.spans.chrome_trace()["traceEvents"]
            if m.get("ph") == "M" and m["args"]["name"] == f"request {header_trace}"]
    assert len(meta) == 1
    # spans nest inside the root's interval
    t0, t1 = root["ts"], root["ts"] + root["dur"]
    assert all(t0 <= e["ts"] and e["ts"] + e["dur"] <= t1 for e in evs)


def test_two_requests_get_distinct_traces_and_engine_spans_link_uids(traced_server):
    srv, cfg = traced_server
    prompt = (np.arange(5) % cfg.vocab_size).tolist()
    traces, uids = [], []
    for _ in range(2):
        with _post(srv.url, {"prompt": prompt, "max_new_tokens": 2}) as resp:
            doc = json.loads(resp.read())
            traces.append(resp.headers[TRACE_HEADER])
            uids.append(doc["uid"])
    assert len(set(traces)) == 2 and len(set(uids)) == 2
    # the engine's batch spans carry the uids that compose each ragged batch
    put_spans = [s for s in telemetry.state.spans.tail(10000) if s["name"] == "put"]
    linked = {u for s in put_spans for u in s["args"].get("uids", [])}
    assert set(uids) <= linked


def test_sse_stream_carries_trace_header_and_done_ids(traced_server):
    srv, cfg = traced_server
    prompt = (np.arange(6) % cfg.vocab_size).tolist()
    with _post(srv.url, {"prompt": prompt, "max_new_tokens": 3, "stream": True}) as resp:
        header_trace = resp.headers[TRACE_HEADER]
        events = [json.loads(line.decode().strip()[len("data: "):])
                  for line in resp if line.decode().strip().startswith("data: ")]
    *tokens, final = events
    assert header_trace
    assert final["done"] is True
    assert final["trace_id"] == header_trace   # SSE metadata joins the trace
    assert final["uid"] is not None            # ...and the engine uid


def test_trace_export_endpoint_drains_the_ring(traced_server):
    """``GET /trace/export?since_us=`` (ISSUE tentpole): the fleet trace
    collector's wire surface — the raw span ring as JSON, stamped with the
    process pid, the remote clock, and the drop count."""
    import os
    srv, cfg = traced_server
    prompt = (np.arange(5) % cfg.vocab_size).tolist()
    with _post(srv.url, {"prompt": prompt, "max_new_tokens": 2}) as resp:
        done = json.loads(resp.read())
    doc = json.loads(urllib.request.urlopen(srv.url + "/trace/export",
                                            timeout=10).read())
    assert doc["pid"] == os.getpid()  # in-process server: our pid
    assert doc["now_us"] > 0 and doc["dropped"] == 0
    names = {s["name"] for s in doc["spans"]}
    assert {"request", "queued", "prefill"} <= names
    root = next(s for s in doc["spans"] if s["name"] == "request")
    assert root["trace_id"] == done["trace_id"]
    # incremental pull: a since_us past the high-water mark drains nothing
    later = json.loads(urllib.request.urlopen(
        srv.url + f"/trace/export?since_us={doc['now_us'] + 1_000_000}",
        timeout=10).read())
    assert later["spans"] == []
    # a garbage since_us is ignored, not a 500
    ok = json.loads(urllib.request.urlopen(
        srv.url + "/trace/export?since_us=banana", timeout=10).read())
    assert ok["spans"]


def test_stats_rows_carry_uid_trace_and_percentiles(traced_server):
    srv, cfg = traced_server
    prompt = (np.arange(4) % cfg.vocab_size).tolist()
    with _post(srv.url, {"prompt": prompt, "max_new_tokens": 2}) as resp:
        done = json.loads(resp.read())
    with _post(srv.url, {"prompt": prompt, "max_new_tokens": 256, "stream": True},
               timeout=120) as resp:
        resp.readline()  # first token: the request is live in DECODE/PREFILL
        stats = json.loads(urllib.request.urlopen(srv.url + "/v1/stats",
                                                  timeout=10).read())
        rows = stats["requests"]
        assert rows and all("uid" in r and "trace_id" in r and "state" in r
                            for r in rows)
        assert done["uid"] not in [r["uid"] for r in rows]  # finished left
        lat = stats["latency"]
        for family in ("ttft_s", "itl_s", "e2e_s"):
            assert set(lat[family]) == {"p50", "p95", "p99"}
        assert lat["ttft_s"]["p50"] is not None  # one request completed
        assert (lat["ttft_s"]["p50"] <= lat["ttft_s"]["p95"]
                <= lat["ttft_s"]["p99"])


def test_scheduler_follows_telemetry_reconfigure(make_engine, llama_setup, tmp_path):
    """A telemetry reconfigure mid-serve installs a new span recorder and
    flight recorder: the live scheduler re-attaches so traces, dumps and
    stall detection follow the new session instead of the displaced one."""
    telemetry.configure(telemetry.TelemetryConfig(
        enabled=True,
        flight_recorder={"enabled": True, "dir": str(tmp_path / "f1"),
                         "watchdog_enabled": False, "signal_enabled": False}))
    cfg = llama_setup[0]
    engine = make_engine()
    scheduler = ServingScheduler(engine, ServingConfig())
    try:
        old_flight = telemetry.get_flight_recorder()
        telemetry.configure(telemetry.TelemetryConfig(
            enabled=True,
            flight_recorder={"enabled": True, "dir": str(tmp_path / "f2"),
                             "watchdog_enabled": False, "signal_enabled": False}))
        new_flight = telemetry.get_flight_recorder()
        assert new_flight is not old_flight
        req = scheduler.submit((np.arange(6) % cfg.vocab_size).tolist(),
                               max_new_tokens=4)
        req.result(timeout=120)
        # the loop re-attached: the NEW recorder dumps this scheduler's state
        path = new_flight.dump("api")
        with open(path) as f:
            doc = json.load(f)
        assert scheduler._flight_channel in doc["state"]
        # ...and the request's spans landed in the NEW session's recorder
        assert any(s.get("trace_id") == req.trace_id
                   for s in telemetry.state.spans.tail(10000))
    finally:
        scheduler.stop(drain=False)


def test_flight_dump_during_active_workload(make_engine, llama_setup, tmp_path):
    """ISSUE acceptance: triggering the recorder during an active serving
    workload captures spans, the registry snapshot and per-request scheduler
    state."""
    telemetry.configure(telemetry.TelemetryConfig(
        enabled=True,
        flight_recorder={"enabled": True, "dir": str(tmp_path / "flight"),
                         "watchdog_enabled": False, "signal_enabled": False}))
    cfg = llama_setup[0]
    engine = make_engine()
    scheduler = ServingScheduler(engine, ServingConfig())
    try:
        req = scheduler.submit((np.arange(6) % cfg.vocab_size).tolist(),
                               max_new_tokens=256)
        next(iter(req.stream))  # decoding is underway
        path = telemetry.get_flight_recorder().dump("api")
        with open(path) as f:
            doc = json.load(f)
        state = doc["state"][scheduler._flight_channel]
        assert scheduler._flight_channel.startswith("serving_scheduler:")
        row = next(r for r in state["requests"] if r["uid"] == req.uid)
        assert row["state"] in ("PREFILL", "DECODE")
        assert row["trace_id"] == req.trace_id
        assert row["kv_blocks"] > 0 and row["offloaded"] is False
        assert state["engine"]["capacity_blocks"] > 0
        assert doc["metrics"]["serving_admissions_total"][0][1] == 1
        assert any(s["name"] in ("prefill", "decode") for s in doc["spans"])
        req.cancel()
    finally:
        scheduler.stop(drain=False)
    # after stop() the provider detaches: later dumps see no scheduler state
    path = telemetry.get_flight_recorder().dump("api")
    with open(path) as f:
        assert not any(k.startswith("serving_scheduler")
                       for k in json.load(f)["state"])


# ------------------------------------------------- scheduler-thread tick phases --
def _sched_spans():
    return [s for s in telemetry.get_span_recorder().export_since(0)["spans"]]


def _simulated_clock(sched, step_s=0.004):
    """The scheduler's clock moves only while it waits, and a program whose
    step has been observed once is said to take ``step_s``: every tick behind
    such a step waits, in whole slices, whatever the machine's speed."""
    t = [0.0]
    sched._now = lambda: t[0]
    sched._pause = lambda seconds: t.__setitem__(0, t[0] + seconds)
    observed = sched._predicted_s
    sched._predicted_s = lambda key: None if observed(key) is None else step_s


def _serve_inline(make_engine, submit, prepare=None, **serving):
    """Serve through a manually stepped scheduler with telemetry on; returns
    the recorded spans."""
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    sched = ServingScheduler(make_engine(), ServingConfig(**serving), start=False)
    if prepare is not None:
        prepare(sched)
    reqs = submit(sched)
    for _ in range(200):
        sched.step()
        if all(r.finished for r in reqs):
            break
    assert all(r.state is RequestState.DONE for r in reqs)
    spans = _sched_spans()
    sched.stop(drain=False)
    return spans


def _inside(child, parent):
    return (parent["ts_us"] <= child["ts_us"]
            and child["ts_us"] + child["dur_us"] <= parent["ts_us"] + parent["dur_us"])


def test_tick_spans_hold_their_phases_in_order(make_engine):
    spans = _serve_inline(
        make_engine,
        lambda s: [s.submit([1, 2, 3, 4, 5], max_new_tokens=5, temperature=0.7, seed=1)],
        prepare=_simulated_clock)
    ticks = [s for s in spans if s["cat"] == "sched" and s["name"] == "tick"]
    assert [t["args"]["tick"] for t in ticks] == sorted(t["args"]["tick"] for t in ticks)
    # the prefill (first token) + four decode steps, and the tick that fetched the last
    assert [t["args"]["kind"] for t in ticks] == ["put"] * 5 + ["none"]
    dispatch = [("sched", "admit"), ("sched", "build_batch"), ("inference", "prepare"),
                ("inference", "put")]
    complete = [("sched", "fetch"), ("sched", "emit")]
    # an open plan (one sequence, a few tokens) stays in flight. The first
    # step of a program — the prompt's five tokens and a decode row share the
    # 8-token bucket — is fetched before anything else in the tick after it
    # (nothing is known of its duration: `open`); every later one has its
    # successor dispatched behind it at its commit time, after a wait, and is
    # fetched under that successor; the last leaves nothing to plan
    waited = [("sched", "commit_wait")] + dispatch + complete
    wanted = [dispatch, complete + dispatch, waited, waited, waited,
              [("sched", "commit_wait")] + dispatch[:2] + complete + dispatch[:2]]
    for i, (tick, want) in enumerate(zip(ticks, wanted)):
        args = tick["args"]
        children = sorted((s for s in spans if s is not tick and _inside(s, tick)
                           and s["cat"] in ("sched", "inference")), key=lambda s: s["ts_us"])
        assert [(c["cat"], c["name"]) for c in children] == want
        for a, b in zip(children, children[1:]):
            assert a["ts_us"] + a["dur_us"] <= b["ts_us"], "phases do not overlap"
        by_name = {c["name"]: c for c in children}
        if args["kind"] == "put":
            behind = i >= 2
            assert args["seqs"] == 1 and args["open"] == 1
            assert args["pipelined"] == args["open_behind"] == int(behind)
            assert ("drain" in args) == (not behind) and args.get("drain", "open") == "open"
            assert args["predicted_us"] == (4000 if behind else 0) and args["lead_us"] >= 0
            assert by_name["prepare"]["args"]["sequences"] == 1
        if "emit" in by_name:
            # the token was drawn on the device: no host-side draw, and the fetch
            # brought the bucket's 8 int32 ids, not a row of logits
            emit = by_name["emit"]
            assert emit["args"]["sample_us"] == 0 and emit["args"]["device_draws"] == 1
            assert emit["args"]["pushed"] == 1
            assert by_name["fetch"]["args"]["bytes"] == 4 * 8
    assert ticks[0]["args"]["tokens"] == 5 and ticks[1]["args"]["tokens"] == 1
    assert sum(s["args"]["finished"] for s in spans if s["name"] == "emit") == 1
    first_admit = min((s for s in spans if s["name"] == "admit"), key=lambda s: s["ts_us"])
    assert first_admit["args"]["admitted"] == 1


@pytest.mark.parametrize("use_paged_kernel,prompt_tokens,arm", [
    (None, 5, "xla_gather"), (True, 5, "paged_token"), (True, 40, "paged_tiled")])
def test_put_span_names_the_attention_arm_its_bucket_took(llama_setup, use_paged_kernel,
                                                          prompt_tokens, arm):
    """``inference.put``'s ``attention`` arg: what modules/heuristics.py chose
    for the bucket the batch was padded to (40 tokens are a bucket of 64: over
    the per-token grid's 32)."""
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                                   DSStateManagerConfig,
                                                                   MemoryConfig)
    cfg, _, params = llama_setup
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    mgr = DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=16),
                               max_context=128)
    engine = build_engine(params, cfg, RaggedInferenceEngineConfig(
        state_manager=mgr, kv_block_size=16, use_paged_kernel=use_paged_kernel))
    try:
        engine.put([0], [np.arange(prompt_tokens) % cfg.vocab_size])
        put = [s for s in _sched_spans() if s["cat"] == "inference" and s["name"] == "put"]
        assert [s["args"]["attention"] for s in put] == [arm]
    finally:
        engine.close()


def test_request_phase_spans_carry_the_tick_that_ran_them(make_engine):
    spans = _serve_inline(
        make_engine, lambda s: [s.submit([1, 2, 3], max_new_tokens=3),
                                s.submit([4, 5, 6, 7], max_new_tokens=2)])
    ticks = {s["args"]["tick"]: s for s in spans if s["cat"] == "sched" and s["name"] == "tick"}
    phases = [s for s in spans if s["cat"] == "serving" and s["name"] in ("prefill", "decode")]
    assert phases
    for s in phases:
        tick = ticks[s["args"]["tick"]]
        assert tick["ts_us"] <= s["ts_us"] < tick["ts_us"] + tick["dur_us"]
        assert {"uid", "tokens", "tick"} <= set(s["args"])  # what benchmark/spans.py reads


def test_decode_loop_tick_is_named_for_its_dispatch(make_engine):
    spans = _serve_inline(make_engine,
                          lambda s: [s.submit([1, 2, 3], max_new_tokens=13)], decode_chunk=4,
                          prepare=_simulated_clock)
    kinds = [s["args"]["kind"] for s in spans if s["cat"] == "sched" and s["name"] == "tick"]
    assert kinds == ["put", "decode_loop", "decode_loop", "decode_loop", "none"]
    # one sequence under a cap of many: every chunk's plan is open. The first
    # chunk follows the prompt's step, the first of ITS program, fetched first;
    # the second follows the first chunk, the first of the chunks' program,
    # fetched first too; the third goes behind the second at its commit time
    chunk_ticks = [s["args"] for s in spans
                   if s["name"] == "tick" and s["args"]["kind"] == "decode_loop"]
    assert [(t["open"], t["pipelined"], t["open_behind"], t.get("drain"), t["predicted_us"])
            for t in chunk_ticks] == [(1, 0, 0, "open", 0), (1, 0, 0, "open", 0),
                                      (1, 1, 1, None, 4000)]
    assert len([s for s in spans if s["name"] == "commit_wait"]) == 2
    loop = next(s for s in spans if s["cat"] == "inference" and s["name"] == "decode_loop")
    assert loop["args"]["steps"] == 4
    prepare = max((s for s in spans if s["name"] == "prepare" and s["ts_us"] <= loop["ts_us"]),
                  key=lambda s: s["ts_us"])
    assert prepare["ts_us"] + prepare["dur_us"] <= loop["ts_us"]
    assert prepare["args"]["tokens"] == 4 and "allocated_blocks" in prepare["args"]


def test_idle_polls_record_nothing_but_no_work(make_engine):
    """A drained, running scheduler: no ``tick`` / ``admit`` / ``build_batch``
    per idle poll, and one ``no_work`` span for many polls."""
    import time

    from deepspeed_tpu.serving.scheduler import _NO_WORK_SPAN_POLLS
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    sched = ServingScheduler(make_engine(), ServingConfig(scheduler_tick_s=0.001))
    time.sleep(0.15)
    req = sched.submit([1, 2, 3], max_new_tokens=2)
    while req.stream.get(timeout=60) is not None:
        pass
    sched.stop()
    spans = _sched_spans()
    idle = [s for s in spans if s["cat"] == "sched" and s["name"] == "no_work"]
    ticks = [s for s in spans if s["cat"] == "sched" and s["name"] == "tick"]
    # the prompt's step, the decode step, and the tick that fetched the second
    # token (an open step stays in flight when its tick ends)
    assert idle and len(ticks) == 3
    before = [s for s in idle if s["ts_us"] + s["dur_us"] <= ticks[0]["ts_us"]]
    # ~150 polls of 1 ms: a handful of spans, not one a poll
    assert 1 <= len(before) <= 150 // _NO_WORK_SPAN_POLLS + 2
    assert max(s["dur_us"] for s in before) >= 5_000
    for s in idle:  # never over a tick
        assert all(s["ts_us"] + s["dur_us"] <= t["ts_us"] or t["ts_us"] + t["dur_us"] <= s["ts_us"]
                   for t in ticks)
    # a phase is a tick's: none a poll (the last tick looks twice: before and
    # after it fetched the step in flight)
    assert len([s for s in spans if s["name"] == "admit"]) == len(ticks) + 1


# PERF.md section 3's audit (PR 52): what each span of the serving path writes, on a
# dense model. Every key has a reader: a benchmark metric, a README table, a finding
# or a test of its own; a key that is not here was read by nothing and went
# (``masked_rows`` / ``rows`` of ``block_loop``, ``host_draws`` of ``emit``,
# ``have_blocks`` of ``peer_prefix_fetch``). A model with experts, a state or an
# index adds its ``moe_*`` / ``ssm_*`` / ``index_*`` / ``tiled_*`` counts to the dispatch.
AUDITED_ARGS = {
    ("sched", "tick"): {"tick", "seqs", "tokens", "kind", "pipelined", "drain", "open",
                        "open_behind", "lead_us", "predicted_us"},
    ("sched", "commit_wait"): set(),
    ("sched", "admit"): {"admitted"},
    ("sched", "build_batch"): {"evicted"},
    ("sched", "fetch"): {"bytes"},
    ("sched", "emit"): {"sample_us", "device_draws", "pushed", "finished"},
    ("serving", "queued"): {"uid"},
    ("serving", "prefill"): {"uid", "tick", "tokens"},
    ("serving", "decode"): {"uid", "tick", "tokens"},
    ("serving", "request"): {"uid", "state", "finish_reason", "prompt_tokens", "cached_tokens",
                             "generated", "resumed"},
    ("inference", "prepare"): {"sequences", "tokens", "released_blocks", "live_blocks_full",
                               "live_blocks_window", "allocated_blocks"},
    ("inference", "put"): {"sequences", "uids", "seqs_live", "seq_bucket", "tokens", "attention",
                           "chained"},
    ("inference", "decode_loop"): {"sequences", "uids", "seqs_live", "seq_bucket", "steps",
                                   "launch_us", "fetch_us", "chained"},
}


def test_every_span_of_the_serving_path_writes_the_audited_args_and_no_other(make_engine):
    spans = _serve_inline(make_engine,
                          lambda s: [s.submit([1, 2, 3], max_new_tokens=13),
                                     s.submit([4, 5, 6, 7], max_new_tokens=2)], decode_chunk=4,
                          prepare=_simulated_clock)
    written = {}
    for s in spans:
        if s["cat"] in ("sched", "serving", "inference"):
            written.setdefault((s["cat"], s["name"]), set()).update(s.get("args") or {})
    assert set(written) == set(AUDITED_ARGS)
    for span, keys in written.items():
        assert keys <= AUDITED_ARGS[span], (span, keys - AUDITED_ARGS[span])
    # what a step of every kind always says
    for span in (("sched", "tick"), ("sched", "emit"), ("inference", "prepare"),
                 ("serving", "request")):
        assert written[span] == AUDITED_ARGS[span], span


def test_active_requests_and_an_exhausted_pool_wait_under_starved_not_no_work(make_engine,
                                                                              monkeypatch):
    """The loop's waiting span says what is waited for: with a request active
    and no KV block free for its next token the pauses are ``starved`` (with
    the counts), and ``no_work`` only while nothing is queued or active."""
    import time

    from deepspeed_tpu.serving import scheduler as scheduler_module
    monkeypatch.setattr(scheduler_module, "_STARVATION_FAIL_TICKS", 40)
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    sched = ServingScheduler(make_engine(num_blocks=3), ServingConfig(scheduler_tick_s=0.001))
    time.sleep(0.03)
    # 40 tokens take the pool's three blocks of 16; the 49th has nowhere to go
    req = sched.submit(list(range(1, 41)), max_new_tokens=20)
    while req.stream.get(timeout=60) is not None:
        pass
    assert req.state is RequestState.FAILED and "starved" in req.error
    time.sleep(0.03)
    sched.stop()
    spans = [s for s in _sched_spans() if s["cat"] == "sched"]
    starved = [s for s in spans if s["name"] == "starved"]
    idle = [s for s in spans if s["name"] == "no_work"]
    ticks = [s for s in spans if s["name"] == "tick"]
    assert 30 <= len(starved) <= 40 and idle
    assert all(s["args"] == {"active": 1, "queued": 0, "free_blocks": 0} for s in starved)
    first, last = starved[0]["ts_us"], starved[-1]["ts_us"] + starved[-1]["dur_us"]
    # no ``no_work`` while the request was starved, and no waiting span over a tick
    assert all(s["ts_us"] + s["dur_us"] <= first or s["ts_us"] >= last for s in idle)
    for s in starved + idle:
        assert all(s["ts_us"] + s["dur_us"] <= t["ts_us"] or t["ts_us"] + t["dur_us"] <= s["ts_us"]
                   for t in ticks)
    # the tick between two starved pauses had work and ran no batch
    assert [t["args"]["kind"] for t in ticks if first < t["ts_us"] < last] == \
        ["none"] * (len(starved) - 1)


def test_profiler_trace_shows_the_scheduler_threads_phases(make_engine, tmp_path):
    """ISSUE acceptance: a ``jax.profiler`` trace of a serving run with
    telemetry on carries ``dstpu.sched.*`` and ``dstpu.inference.*`` events on
    the scheduler thread's line, nested, on the trace's own clock."""
    import glob

    import jax
    from jax.profiler import ProfileData
    telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    sched = ServingScheduler(make_engine(), ServingConfig())
    warm = sched.submit([1, 2, 3], max_new_tokens=2)
    while not warm.finished:
        assert warm.stream.get(timeout=60) is not None or True
    # the tick that fetched a request's last token looks once more for work
    # before it ends: let it end inside the trace (an annotation is written
    # when it closes), so that no phase is traced without its tick
    import time
    time.sleep(0.2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        req = sched.submit([1, 2, 3, 4], max_new_tokens=3)
        while req.stream.get(timeout=60) is not None:
            pass
        time.sleep(0.2)
    finally:
        jax.profiler.stop_trace()
        sched.stop()
    (path, ) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    by_line = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dstpu."):
                    by_line.setdefault(line.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats)))
    (events, ) = [v for v in by_line.values() if any(n == "dstpu.sched.tick" for _, _, n, _ in v)]
    names = {n for _, _, n, _ in events}
    assert {"dstpu.sched.tick", "dstpu.sched.admit", "dstpu.sched.build_batch",
            "dstpu.sched.fetch", "dstpu.sched.emit", "dstpu.inference.prepare",
            "dstpu.inference.put"} <= names
    ticks = [e for e in events if e[2] == "dstpu.sched.tick"]
    assert all("tick" in stats for _, _, _, stats in ticks)
    for s, e, n, _ in events:
        if n not in ("dstpu.sched.tick", "dstpu.sched.no_work"):
            assert any(ts <= s and e <= te for ts, te, _, _ in ticks), n
