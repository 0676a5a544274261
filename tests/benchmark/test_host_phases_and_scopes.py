"""The scheduler thread's phases and the device operations' named scopes, from
the trace alone: ``benchmark/host_phases.py`` and the five readers on it, on
hand-made intervals and on a slice of a chip trace of ``mixtral-chat-steady``
(``fixtures/chip_slice_phases.json``, cut with ``tools/trace_cut_phases.py``)."""

import importlib
import json
import os

import pytest

from benchmark import host_phases as hp
from benchmark import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "chip_slice_phases.json")
MS = 1_000_000  # ns


def _ev(start_ms, end_ms, phase, **stats):
    return hp.HostEvent(int(start_ms * MS), int(end_ms * MS), phase, stats)


def _sorted(events):
    return sorted(events, key=lambda e: (e.start, -e.end))


# one tick of a put (0..20 ms), 2 ms of nothing, a no_work (22..30), a tick of a
# decode_loop chunk of 4 steps (30..70)
EVENTS = _sorted([
    _ev(0, 20, "sched.tick", tick=1),
    _ev(0.5, 1.5, "sched.admit"), _ev(1.5, 3, "sched.build_batch"),
    _ev(3.5, 4.5, "inference.prepare", sequences=2, tokens=9),
    _ev(4.5, 5.5, "inference.put", sequences=2, tokens=9),
    _ev(5.5, 14, "sched.fetch"), _ev(14, 19.5, "sched.emit"),
    _ev(22, 30, "sched.no_work"),
    _ev(30, 70, "sched.tick", tick=2),
    _ev(30.5, 31, "sched.admit"), _ev(31, 32, "sched.build_batch"),
    _ev(32, 33, "inference.prepare", sequences=2, tokens=8),
    _ev(33, 64, "inference.decode_loop", sequences=2, steps=4),
    _ev(64, 64, "sched.fetch"), _ev(64, 69, "sched.emit"),
])
# the device: busy 5..14 ms (the put) and 34..64 ms (the chunk), inside one
# container event that holds the chunk's operations
OPS = [(5 * MS, 9 * MS, "%fusion.1 = bf16[8] fusion()"),
       (9 * MS, 14 * MS, "%copy.2 = bf16[8] copy()"),
       (34 * MS, 64 * MS, "%while.3 = (s32[]) while()"),
       (34 * MS, 54 * MS, "%fusion.4 = bf16[8] fusion()"),
       (54 * MS, 64 * MS, "%custom-call.5 = bf16[8] custom-call()")]
SCOPES = {OPS[0][2]: "jit(_forward_impl)/moe/experts/ecm,emf->ecf/dot_general:",
          OPS[1][2]: "cache:",
          OPS[2][2]: "jit(<unknown>)/while:",
          OPS[3][2]: "jit(<unknown>)/while/body/closed_call/moe/route/top_k:",
          OPS[4][2]: "jit(<unknown>)/while/body/closed_call/attn/paged_kernel/pallas_call:"}
TRACE = tr.Trace({0: OPS}, [])


def _env(events=EVENTS, scopes=SCOPES, trace=TRACE):
    log = []
    return {"trace": trace, "host_phases": (events, scopes), "log": log.append, "logged": log}


def _read(reader, params=None, run=None, env=None):
    module = importlib.import_module(f"benchmark.readers.{reader}")
    return module.read(run or {}, params or {}, _env() if env is None else env)


# ------------------------------------------------------------ host side ------
def test_innermost_resolves_nesting_to_the_deepest_phase():
    segments = hp.innermost(EVENTS)
    assert [(s / MS, e / MS, p) for s, e, p in segments][:9] == [
        (0, 0.5, "sched.tick"), (0.5, 1.5, "sched.admit"), (1.5, 3, "sched.build_batch"),
        (3, 3.5, "sched.tick"), (3.5, 4.5, "inference.prepare"), (4.5, 5.5, "inference.put"),
        (5.5, 14, "sched.fetch"), (14, 19.5, "sched.emit"), (19.5, 20, "sched.tick")]
    assert all(a[1] <= b[0] for a, b in zip(segments, segments[1:])), "disjoint, in time order"
    assert sum(e - s for s, e, _ in segments) == (20 + 8 + 40) * MS  # nothing counted twice
    assert (22 * MS, 30 * MS, "sched.no_work") in segments


def test_innermost_clips_a_child_that_outlives_its_parent_and_takes_three_levels():
    events = _sorted([_ev(0, 10, "a"), _ev(2, 8, "b"), _ev(3, 4, "c"), _ev(9, 12, "late")])
    assert [(s / MS, e / MS, p) for s, e, p in hp.innermost(events)] == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 8, "b"), (8, 9, "a"), (9, 10, "late")]
    assert hp.innermost([]) == []


def test_ticks_find_the_dispatch_the_fetch_and_the_loops_steps():
    put, loop = hp.ticks(EVENTS)
    assert (put["tick"], put["kind"], put["loop_steps"]) == (1, "put", 1)
    assert (put["dispatch_start"], put["fetch_end"]) == (int(4.5 * MS), 14 * MS)
    assert (loop["tick"], loop["kind"], loop["loop_steps"]) == (2, "decode_loop", 4)
    assert (loop["dispatch_start"], loop["fetch_end"]) == (33 * MS, 64 * MS)
    # a tick that dispatched nothing (starved) is no step
    assert hp.ticks([_ev(0, 1, "sched.tick", tick=9), _ev(0, 0.5, "sched.admit")]) == []


def test_scheduler_thread_is_the_one_with_ticks():
    other = [_ev(0, 1, "inference.put")] * 40
    assert hp.scheduler_thread({"python3": other, "dstpu-serving-s/7": EVENTS}) is EVENTS
    assert hp.scheduler_thread({"python3": other}) is other
    assert hp.scheduler_thread({}) == []


def test_idle_shares_by_phase_add_up_to_the_idle_share():
    lo, hi = TRACE.window()  # 5 .. 64 ms
    by_phase = hp.idle_by_phase(hp.innermost(EVENTS), OPS, lo, hi)
    idle_ns = tr.total(tr.gaps(tr.busy(OPS), lo, hi))
    assert idle_ns == 20 * MS and sum(by_phase.values()) == idle_ns
    assert {k: v / MS for k, v in by_phase.items()} == pytest.approx({
        "sched.emit": 5.5, "sched.tick": 1.0, "sched.no_work": 8.0,
        "sched.admit": 0.5, "sched.build_batch": 1.0, "inference.prepare": 1.0,
        "inference.decode_loop": 1.0, "unattributed": 2.0})


def test_idle_in_phase_reader_sums_phases_and_logs_the_whole_table():
    env = _env()
    window_ms = 59.0
    assert _read("trace_idle_in_phase", {"phases": ["sched.emit"]}, env=env) == \
        pytest.approx(100 * 5.5 / window_ms)
    engine = ["inference.prepare", "inference.put", "inference.decode_loop", "inference.verify",
              "inference.verify_tree", "sched.fetch"]
    assert _read("trace_idle_in_phase", {"phases": engine}, env=env) == \
        pytest.approx(100 * 2.0 / window_ms)
    assert _read("trace_idle_in_phase", {"phases": ["sched.no_work"]}, env=env) == \
        pytest.approx(100 * 8.0 / window_ms)
    assert len(env["logged"]) == 2 and "moved by +0.000 ms" in env["logged"][0]
    assert "unattributed 3.39" in env["logged"][1]
    # every share, with the unattributed rest, is the device's idle share
    summary = tr.summarize(TRACE)
    assert sum(env["idle_by_phase"].values()) == pytest.approx(summary["idle_pct_by_chip"][0])


def test_step_device_time_is_busy_time_between_dispatch_and_fetch_per_step():
    env = _env()
    # the put: 9 ms busy; the chunk: 30 ms busy over 4 steps -> readings 9, 7.5 x 4
    assert _read("trace_step_device_time", env=env) == pytest.approx(7.5)
    assert "5 steps" in env["logged"][0]


def test_device_events_are_moved_onto_the_hosts_timeline_by_the_fetch_ends():
    """The profiler's device line 2.8 ms early (as a chip trace had it): the
    offset comes back from the trace itself, and the readings with it."""
    early = [(s - int(2.8 * MS), e - int(2.8 * MS), n) for s, e, n in OPS]
    assert hp.device_offset_ns(hp.ticks(EVENTS), early) == int(2.8 * MS)
    assert hp.device_offset_ns(hp.ticks(EVENTS), OPS) == 0
    assert hp.device_offset_ns([], OPS) == 0  # no ticks (training): nothing to align to
    ops, lo, hi = hp.aligned_chip(tr.Trace({0: early}, []), EVENTS)
    assert (ops, lo, hi) == (OPS, 5 * MS, 64 * MS)
    env = _env(trace=tr.Trace({0: early}, []))
    assert _read("trace_step_device_time", env=env) == pytest.approx(7.5)
    assert _read("trace_idle_in_phase", {"phases": ["sched.emit"]}, env=env) == \
        pytest.approx(100 * 5.5 / 59.0)


# ---------------------------------------------------------- device side ------
@pytest.mark.parametrize("scope,parts", [
    ("jit(_forward_impl)/moe/experts/ecm,emf->ecf/dot_general:", ["moe", "experts", "ecm,emf->ecf"]),
    ("jit(<unknown>)/while/body/closed_call/attn/paged_kernel/pallas_call:",
     ["attn", "paged_kernel"]),
    ("jit(fn)/jit(main)/optimizer/mul:", ["optimizer"]),
    ("jit(fn)/while/body/transpose(jvp(LlamaForCausalLM))/model/layers_0/mlp/dot_general:",
     ["model", "layers_0", "mlp"]),
    ("cache:", []), ("", []),
])
def test_scope_parts_drop_jaxs_wrappers_and_the_operation(scope, parts):
    assert hp.scope_parts(scope) == parts


def test_scoped_seconds_leave_containers_out():
    assert hp.scoped_seconds(OPS, SCOPES, depth=1) == pytest.approx(
        {"moe": 0.024, "(none)": 0.005, "attn": 0.010})
    assert hp.scoped_seconds(OPS, SCOPES, depth=2) == pytest.approx(
        {"moe/experts": 0.004, "moe/route": 0.020, "(none)": 0.005, "attn/paged_kernel": 0.010})


@pytest.mark.parametrize("params,expected", [
    ({"pattern": r"(^|/)moe/"}, 100 * 24 / 39),
    ({"pattern": r"(^|/)moe/(route|dispatch|combine)/"}, 100 * 20 / 39),
    ({"pattern": r"(^|/)attn/"}, 100 * 10 / 39),
    ({"pattern": r"(^|/)(attn|moe|mlp|embed|unembed)/", "invert": True}, 100 * 5 / 39),
    ({"pattern": r"(^|/)optimizer/"}, None),  # the program wrote no such scope
    ({"pattern": r"(^|/)optimizer/", "invert": True}, None),
])
def test_scope_busy_reader(params, expected):
    got = _read("trace_scope_busy", params)
    assert got is None if expected is None else got == pytest.approx(expected)


def test_scope_busy_shares_add_up_to_the_busy_time():
    shares = [_read("trace_scope_busy", {"pattern": p, "invert": inv}) for p, inv in
              ((r"(^|/)moe/", False), (r"(^|/)attn/", False),
               (r"(^|/)(attn|moe|mlp|embed|unembed)/", True))]
    assert sum(shares) == pytest.approx(100.0)


def _message(*fields):
    """A protobuf message from ``(number, value)``: int -> varint, bytes/str ->
    length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_scopes_are_read_from_the_event_metadata_of_an_xplane_file(tmp_path):
    """``tf_op`` as a string and as a reference to a stat's name; a host plane
    and an event without the stat are passed over."""
    stat_meta = [(5, _message((1, 1), (2, _message((1, 1), (2, "tf_op"))))),
                 (5, _message((1, 2), (2, _message((1, 2), (2, "flops"))))),
                 (5, _message((1, 300), (2, _message((1, 300), (2, "jit(f)/attn/gather/exp:")))))]

    def event_meta(key, name, *stats):
        return (4, _message((1, key), (2, _message((1, key), (2, name),
                                                   *[(5, _message(*s)) for s in stats]))))
    device = _message(
        (1, 7), (2, "/device:TPU:0"),
        (3, _message((2, "XLA Ops"), (4, _message((1, 1), (2, 5), (3, 9))))),  # a line: skipped
        event_meta(1, "%fusion.1 = f32[8]", ((1, 2), (3, 99)), ((1, 1), (5, "jit(f)/moe/route/top_k:"))),
        event_meta(2, "%fusion.2 = f32[8]", ((1, 1), (7, 300))),
        event_meta(3, "%copy.3 = f32[8]", ((1, 2), (3, 0))),
        *stat_meta)
    host = _message((2, "/host:CPU"), event_meta(1, "dstpu.sched.tick", ((1, 1), (5, "not a device"))),
                    *stat_meta)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_message((1, device), (1, host), (4, "hostname")))
    assert hp.scopes_by_name(str(path)) == {"%fusion.1 = f32[8]": "jit(f)/moe/route/top_k:",
                                            "%fusion.2 = f32[8]": "jit(f)/attn/gather/exp:"}


# ------------------------------------------------------- program spans -------
def _span(name, ts_ms, dur_ms, cat="sched", **args):
    return {"name": name, "cat": cat, "ts_us": int(ts_ms * 1000), "dur_us": int(dur_ms * 1000),
            "args": args}


SPANS = [
    _span("decode", 1000, 14, "serving", uid=0, tokens=1), _span("emit", 1013, 4, sample_us=2500),
    _span("decode", 1020, 14, "serving", uid=0, tokens=1), _span("emit", 1033, 5, sample_us=3500),
    _span("no_work", 1040, 50),
    _span("decode", 1100, 14, "serving", uid=1, tokens=1), _span("emit", 1113, 3, sample_us=900),
    _span("decode", 1122, 14, "serving", uid=1, tokens=1),
    _span("prepare", 1100.2, 0.4, "inference", sequences=1, tokens=1, allocated_blocks=0),
    _span("emit", 2600, 50, sample_us=40000),  # outside the window
]
RUN = {"spans": SPANS, "t0": 1.0, "seconds": 1.0}


def test_step_gap_skips_pairs_with_no_work_between():
    # 1014 -> 1020 is 6 ms, 1114 -> 1122 is 8 ms; 1034 -> 1100 waited for a request
    assert _read("span_step_gap", run=RUN) == pytest.approx(7.0)
    assert _read("span_step_gap", run=dict(RUN, spans=SPANS[:1])) is None


def test_span_phase_reads_durations_and_numeric_args():
    emit = {"name": "emit", "cat": "sched", "percentile": 50}
    assert _read("span_phase", emit, run=RUN) == pytest.approx(4.0)
    assert _read("span_phase", dict(emit, arg="sample_us", scale=0.001), run=RUN) == \
        pytest.approx(2.5)
    assert _read("span_phase", {"name": "prepare", "cat": "inference", "percentile": 50},
                 run=RUN) == pytest.approx(0.4)
    assert _read("span_phase", dict(emit, name="fetch"), run=RUN) is None


@pytest.mark.parametrize("reader,params", [
    ("span_step_gap", {}), ("span_phase", {"name": "emit", "cat": "sched", "percentile": 50}),
    ("trace_idle_in_phase", {"phases": ["sched.emit"]}), ("trace_step_device_time", {}),
    ("trace_scope_busy", {"pattern": "moe"}),
])
def test_nothing_to_read_off_the_chip_or_from_a_program_without_the_spans(reader, params):
    """A run with no device events (the CPU rehearsal) and a program that wrote
    no ``dstpu.*`` annotation and no scope (the parent commit): None, no raise."""
    log = []
    for trace in (None, tr.Trace({}, [])):
        assert _read(reader, params, run=dict(RUN, trace_path=None),
                     env={"trace": trace, "log": log.append}) is None
    if reader.startswith("trace_"):
        assert _read(reader, params, run=RUN, env=_env(events=[], scopes={})) is None
    assert log == []


PR23_METRICS = ("sched_gap_p50_ms", "sched_emit_p50_ms", "sched_sample_p50_ms",
                "sched_build_batch_p50_ms", "engine_prepare_p50_ms", "idle_in_emit_pct",
                "idle_in_build_batch_pct", "idle_in_admit_pct", "idle_in_engine_pct",
                "idle_no_work_pct", "step_device_any_p50_ms", "moe_busy_pct", "moe_route_busy_pct",
                "attn_busy_pct", "unscoped_busy_pct", "train_optimizer_busy_pct",
                "train_bwd_busy_pct")


def test_every_new_metric_names_a_reader_and_the_cells_of_its_kind():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    new = {name: entries[name] for name in PR23_METRICS}  # by name, wherever they stand
    assert len(new) == 17
    for name, m in new.items():
        with open(os.path.join(root, "benchmark", "metrics", f"{name}.json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(root, "benchmark", "readers", f"{spec['reader']}.py"))
        train = name.startswith("train_")
        assert all(("zero3" in w) == train for w in m["workloads"])
        assert m["moves"] == ("train_tokens_per_s" if train else
                              "ttft_p50_ms" if name == "idle_no_work_pct" else "tpot_p50_ms")


# ------------------------------------------- a recorded slice of a chip trace --
@pytest.fixture(scope="module")
def chip():
    trace, events, scopes = hp.load_json(FIXTURE)
    return trace, events, scopes


def test_chip_slice_has_the_scheduler_ticks_and_scoped_operations(chip):
    trace, events, scopes = chip
    rows = hp.ticks(events)
    assert len(rows) >= 3 and all(t["kind"] == "put" and t["loop_steps"] == 1 for t in rows)
    assert [t["tick"] for t in rows] == sorted(t["tick"] for t in rows)
    phases = {e.phase for e in events}
    assert {"sched.tick", "sched.admit", "sched.build_batch", "inference.prepare", "inference.put",
            "sched.fetch", "sched.emit"} <= phases
    by_scope = hp.scoped_seconds(trace.devices[0], scopes, depth=2)
    assert {"moe/experts", "moe/route", "attn/paged_kernel", "unembed", "embed"} <= set(by_scope)
    assert max(by_scope, key=by_scope.get) == "moe/experts"  # chat decodes: expert banks streamed


def test_chip_slice_device_line_is_off_the_host_line_by_milliseconds(chip):
    """What the alignment is for: as recorded, programs run outside the calls
    that dispatched and fetched them; moved by the offset, inside."""
    trace, events, _ = chip
    rows = hp.ticks(events)[1:-1]  # whole ticks only
    offset = hp.device_offset_ns(hp.ticks(events), trace.devices[0])
    assert 100_000 < abs(offset) < hp.OFFSET_SEARCH_NS

    def outside(ops):
        busy = tr.busy(ops)
        windows = tr.merge((t["dispatch_start"], t["fetch_end"]) for t in rows)
        lo, hi = rows[0]["start"], rows[-1]["end"]
        return tr.total(tr.subtract(tr.clip(busy, lo, hi), windows))

    aligned, _, _ = hp.aligned_chip(trace, events)
    assert outside(aligned) < 0.02 * outside(trace.devices[0]) + 50_000


def test_chip_slice_readers_add_up(chip):
    trace, events, scopes = chip
    env = _env(events=events, scopes=scopes, trace=trace)
    idle_phases = [["sched.emit"], ["sched.build_batch"], ["sched.admit"], ["sched.no_work"],
                   ["inference.prepare", "inference.put", "inference.decode_loop",
                    "inference.verify", "inference.verify_tree", "sched.fetch"]]
    shares = [_read("trace_idle_in_phase", {"phases": p}, env=env) for p in idle_phases]
    idle = tr.summarize(trace)["idle_pct_by_chip"][0]
    rest = sum(env["idle_by_phase"].get(p, 0.0) for p in ("unattributed", "sched.tick"))
    assert sum(shares) + rest == pytest.approx(idle, abs=1e-6)
    assert shares[0] > 3 and shares[4] > 3 and rest < 2  # sampling, and launching, not slack
    busy = [_read("trace_scope_busy", {"pattern": p, "invert": inv}, env=env) for p, inv in
            ((r"(^|/)moe/", False), (r"(^|/)attn/", False), (r"(^|/)(embed|unembed)/", False),
             (r"(^|/)(attn|moe|mlp|embed|unembed)/", True))]
    assert sum(busy) == pytest.approx(100.0, abs=0.5) and busy[0] > 60
    step = _read("trace_step_device_time", env=env)
    assert 5.0 < step < 20.0
