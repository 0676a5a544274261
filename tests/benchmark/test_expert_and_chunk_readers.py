"""The expert GEMMs' share of their roofline and the split of a ``decode_loop``
chunk's idle time (PR 37): ``readers/trace_expert_roofline.py`` and
``readers/trace_chunk_idle.py`` on hand-made spans and intervals, and on a
slice of a chip trace of ``mixtral-longgen-closed``
(``fixtures/chip_slice_chunks.json``, cut with ``tools/trace_cut_phases.py``)."""

import json
import os
import types

import pytest

from benchmark import host_phases as hp, opcount, trace_reduce as tr
from benchmark.readers import trace_chunk_idle as chunk_idle
from benchmark.readers import trace_expert_roofline as roofline
from benchmark.readers import trace_idle_in_phase
from tests.benchmark import tiny

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "chip_slice_chunks.json")
MS = 1_000_000  # ns
PEAKS = opcount.PEAKS["TPU v5 lite"]
NEW_METRICS = {  # the cells PR 37 listed; later cells join the lists
    "moe_grouped_roofline": ["mellum2-repoctx-closed", "trinity-mini-reason-closed"],
    "moe_capacity_roofline": ["mixtral-longgen-closed", "mixtral-rag-closed"],
    "moe_banks_per_assignment": ["trinity-mini-reason-closed"],
    "chunk_launch_p50_ms": ["mixtral-longgen-closed", "mistral-longdoc-closed",
                            "trinity-mini-reason-closed"],
    "chunk_round_trip_p50_ms": ["mixtral-longgen-closed", "mistral-longdoc-closed",
                                "trinity-mini-reason-closed"],
    "idle_in_chunk_run_pct": ["mixtral-longgen-closed", "mistral-longdoc-closed",
                              "trinity-mini-reason-closed"],
}


def _config(name):
    with open(os.path.join(tiny.REPO, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


TRINITY = _config("trinity-mini-serve-1chip")
MIXTRAL = _config("mixtral-8x7b-serve-1chip")
MELLUM = _config("mellum2-12b-a2.5b-serve-1chip")


# ------------------------------------------------------------ the entries ---
@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_each_new_metric_names_its_reader_its_cells_and_a_layer_perf_md_lists(name):
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert set(NEW_METRICS[name]) <= set(entry["workloads"]) and entry["moves"] == "tpot_p50_ms"
    with open(os.path.join(tiny.REPO, "benchmark", "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "readers", f"{spec['reader']}.py"))
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        perf = f.read()
    assert f"`{name}`" in perf and entry["layer"] in perf
    if name.endswith("_roofline"):
        assert entry["unit"] == "%" and entry["better"] == "higher"
        assert entry["source"] == "device_trace" and spec["params"]["moe_path"] in name


# --------------------------------------------------------- the arithmetic ---
def test_expert_ffn_at_trinitys_published_widths():
    """A decode step's 8 rows x top-8 over ~52 touched banks of 3 x 2048 x 1024 bf16."""
    assert roofline.widths(TRINITY) == (128, 2048, 1024, 4, 2)
    flops, nbytes = roofline.expert_ffn(64, 52, 2048, 1024)
    assert flops == 2 * 64 * 2048 * 1024 * 3 == 805_306_368
    assert nbytes == 52 * 12_582_912 + 2 * 64 * 2048 * 2 == 654_835_712
    seconds, bound = opcount.roofline_seconds(flops, nbytes, PEAKS)
    assert bound == "memory" and 4 * seconds * 1e3 == pytest.approx(3.198, abs=1e-3)
    # all 128 banks (what the capacity path streamed until PR 35): 2.46 x the bytes
    assert roofline.expert_ffn(64, 128, 2048, 1024)[1] / nbytes == pytest.approx(2.46, abs=0.01)


def test_expert_ffn_at_mixtrals_and_mellums_published_widths():
    assert roofline.widths(MIXTRAL) == (8, 4096, 14336, 3, 2)
    flops, nbytes = roofline.expert_ffn(32, 8, 4096, 14336)  # longgen: 16 rows x top-2
    assert nbytes == 8 * 352_321_536 + 2 * 32 * 4096 * 2 == 2_819_096_576
    assert flops == 2 * 32 * 4096 * 14336 * 3
    assert 3 * opcount.roofline_seconds(flops, nbytes, PEAKS)[0] * 1e3 == \
        pytest.approx(10.33, abs=0.01)
    # rag: 512 assignments over the same 8 banks: the rows are 0.3 % of the bytes
    assert roofline.expert_ffn(512, 8, 4096, 14336)[1] / nbytes == pytest.approx(1.003, abs=1e-3)
    assert roofline.widths(MELLUM) == (64, 2304, 896, 4, 2)
    flops, nbytes = roofline.expert_ffn(2048, 64, 2304, 896)  # a full chunk: every bank
    assert 4 * opcount.roofline_seconds(flops, nbytes, PEAKS)[0] * 1e3 == \
        pytest.approx(3.97, abs=0.01)
    # an ungated expert has two matrices
    assert roofline.expert_ffn(64, 52, 2048, 1024, gated=False)[0] * 3 == 805_306_368 * 2


# ------------------------------------------- spans -> the least it could take ---
def _span(name, cat, ts_ms, dur_ms, **args):
    return {"name": name, "cat": cat, "ts_us": ts_ms * 1e3, "dur_us": dur_ms * 1e3, "args": args}


# a grouped chunk of 8 steps x 4 layers touching 52 banks a layer-step; a grouped
# put step whose count came out at its fetch; a capacity put; and spans that
# carry no count: the grouped put's dispatch, a fetch of ids alone, the parent's chunk
SPANS = [
    _span("decode_loop", "inference", 10, 44, steps=8, moe_path="grouped", moe_rows=4096,
          moe_assignments=8 * 8 * 4 * 8, moe_banks=52 * 4 * 8, launch_us=900, fetch_us=43000),
    _span("put", "inference", 60, 1, tokens=256, moe_path="grouped", moe_rows=8192,
          moe_assignments=256 * 8 * 4),
    _span("fetch", "sched", 62, 6, bytes=32, moe_path="grouped", moe_assignments=256 * 8 * 4,
          moe_banks=128 * 4),
    _span("put", "inference", 70, 1, tokens=40, moe_path="capacity", moe_rows=32768,
          moe_assignments=40 * 8 * 4, moe_banks=128 * 4),
    _span("fetch", "sched", 72, 6, bytes=32),
    _span("decode_loop", "inference", 80, 44, steps=8, moe_path="grouped", moe_rows=4096,
          moe_assignments=2048),
]


def test_a_step_is_counted_once_by_the_span_that_carries_its_banks():
    grouped = roofline.carriers(SPANS, "grouped")
    assert [(k, banks, a) for _, _, k, banks, a in grouped] == [(8, 1664, 2048), (1, 512, 8192)]
    assert grouped[1][:2] == (62e3, 68e3)  # the fetch, not the dispatch
    assert [(k, banks) for _, _, k, banks, _ in roofline.carriers(SPANS, "capacity")] == \
        [(1, 512)]


def test_the_least_time_is_layer_steps_at_the_spans_mean_banks():
    (chunk, step) = roofline.carriers(SPANS, "grouped")
    least, steps, banks, layer_steps, nbytes = roofline.least_seconds(
        [chunk], 0, 1e6, TRINITY, PEAKS)
    assert (steps, layer_steps) == (8, 32) and banks / layer_steps == 52
    assert least * 1e3 / 8 == pytest.approx(3.198, abs=1e-3)  # the decode step of the issue
    assert nbytes == 32 * 654_835_712
    both = roofline.least_seconds([chunk, step], 0, 1e6, TRINITY, PEAKS)
    assert both[1] == 9 and both[0] > least


def test_banks_are_clamped_to_the_experts_and_to_the_assignments_a_layer_step():
    honest = roofline.least_seconds([(0, 1, 8, 52 * 32, 2048)], 0, 1, TRINITY, PEAKS)
    # a program that says it touched 1000 banks a layer-step of 64 assignments
    loud = roofline.least_seconds([(0, 1, 8, 1000 * 32, 2048)], 0, 1, TRINITY, PEAKS)
    assert loud[2] / loud[3] == 64 and loud[0] / honest[0] == pytest.approx(64 / 52, rel=0.01)
    # ... or of 2048 assignments: never more than the experts there are
    full = roofline.least_seconds([(0, 1, 1, 1000 * 4, 8192)], 0, 1, TRINITY, PEAKS)
    assert full[2] / full[3] == 128


def test_a_span_over_the_slices_edge_counts_by_the_part_inside():
    chunk = (10.0, 54.0, 8, 52 * 32, 2048)
    whole = roofline.least_seconds([chunk], 0, 100, TRINITY, PEAKS)
    half = roofline.least_seconds([chunk], 32, 100, TRINITY, PEAKS)
    none = roofline.least_seconds([chunk], 54, 100, TRINITY, PEAKS)
    assert half[0] == pytest.approx(whole[0] / 2) and half[1] == 4 and none[0] == 0
    # an instantaneous span is in or out
    assert roofline.least_seconds([(5, 5, 1, 512, 8192)], 0, 10, TRINITY, PEAKS)[1] == 1
    assert roofline.least_seconds([(15, 15, 1, 512, 8192)], 0, 10, TRINITY, PEAKS)[1] == 0


# --------------------------------------------------------- the whole reader ---
def _traced_run(device_ms_a_step, sync_at_ms=5.0):
    """The chunk of ``SPANS`` (10..54 ms on the span clock) as 8 steps x 4 layers
    of two kernels on the device, ``device_ms_a_step`` together; the trace's
    clock runs 1000 ms ahead of the span clock."""
    ahead = 1000.0
    # the slice is from the first to the last device event: 5..70 ms on the span clock
    ops = [(int((ahead + at) * MS), int((ahead + at) * MS) + 1000, "%copy.1 = bf16[8] copy()")
           for at in (5.0, 70.0)]
    for i in range(32):
        start = (ahead + 11.5 + i * 1.3) * MS
        wi = int(device_ms_a_step / 4 * 2 / 3 * MS)
        wo = int(device_ms_a_step / 4 / 3 * MS)
        ops.append((int(start), int(start) + wi,
                    f"%grouped_matmul.{i} = bf16[128,2048]{{1,0}} custom-call(bf16[128,2048] %x)"))
        ops.append((int(start) + wi, int(start) + wi + wo,
                    f"%grouped_matmul.{64 + i} = f32[128,2048]{{1,0}} custom-call(bf16[128,1024] %h)"))
        # a fusion that READS a kernel's result is not the kernel
        ops.append((int(start) + wi + wo, int(start) + wi + wo + 1000,
                    f"%fusion.{i} = f32[8,2048]{{1,0}} fusion(f32[128,2048] %grouped_matmul.{64 + i})"))
    host = [(int((ahead + sync_at_ms) * MS), int((ahead + sync_at_ms + 1) * MS),
             "bench.clock_sync", "bench-trace")]
    log = []
    env = {"trace": tr.Trace({0: sorted(ops)}, host), "peaks": PEAKS, "config": TRINITY,
           "log": log.append, "logged": log}
    run = {"spans": SPANS[:1], "trace_slice": types.SimpleNamespace(
        began=0.004, ended=0.1, sync_clock=sync_at_ms / 1e3)}
    return run, env


GROUPED = {"pattern": "^%?grouped_matmul", "moe_path": "grouped"}


def test_the_reader_divides_the_least_time_by_the_kernels_time_and_logs_its_table():
    run, env = _traced_run(device_ms_a_step=3.57)
    value = roofline.read(run, GROUPED, env)
    assert value == pytest.approx(100 * 3.198 / 3.57, abs=0.1)
    (line, ) = env["logged"]
    assert "8.0 steps" in line and "52.00 banks a layer-step" in line and "GB/s reached" in line
    assert float(line.split(":")[-1].split(" GB/s")[0]) == pytest.approx(819 * value / 100, rel=0.01)
    # kernels that ran at the roofline read 100, not more
    run, env = _traced_run(device_ms_a_step=3.198)
    assert roofline.read(run, GROUPED, env) == pytest.approx(100.0, abs=0.1)


def test_nothing_to_read_from_the_parents_spans_off_the_chip_or_without_the_kernel():
    run, env = _traced_run(3.57)
    parent = dict(run, spans=SPANS[5:])  # a chunk span without moe_banks
    assert roofline.read(parent, GROUPED, env) is None
    assert roofline.read(run, dict(GROUPED, moe_path="capacity"), env) is None
    assert roofline.read(run, dict(GROUPED, pattern="no_such_kernel"), env) is None
    assert roofline.read(run, GROUPED, dict(env, trace=tr.Trace({0: []}, []))) is None
    assert roofline.read(run, GROUPED, dict(env, trace=None)) is None
    assert roofline.read(run, GROUPED, dict(env, peaks=None)) is None
    for value in ("round_trip_p50_ms", "idle_in_run_pct"):
        assert chunk_idle.read(run, {"value": value}, dict(env, trace=None)) is None
        # on the chip, a slice without a chunk
        assert chunk_idle.read(run, {"value": value}, dict(env, host_phases=([], {}))) is None


def test_the_capacity_pattern_finds_both_fusions_by_the_type_they_produce():
    import re
    with open(os.path.join(tiny.REPO, "benchmark", "metrics", "moe_capacity_roofline.json")) as f:
        rx = re.compile(json.load(f)["params"]["pattern"])
    wi = "%fusion.248 = bf16[8,16,28672]{2,1,0:T(8,128)(2,1)} fusion(bf16[8,16,4096] %a, %b)"
    wo = "%fusion.249 = bf16[8,256,4096]{2,1,0:T(8,128)(2,1)} fusion(bf16[8,256,14336] %c)"
    assert rx.search(wi) and rx.search(wo)
    for other in ("%fusion.7 = bf16[256,4096]{1,0} fusion(bf16[8,256,4096] %fusion.249)",
                  "%convolution_convert_fusion.1 = f32[8,32000]{1,0} fusion(%x)",
                  "%copy-done.3 = bf16[4096,4096]{1,0} copy-done(%y)",
                  "%paged_attention_update.5 = bf16[16,32,128]{2,1,0} custom-call(%q)"):
        assert not rx.search(other)


# -------------------------------------------------------- the chunk's idle ---
def _ev(start_ms, end_ms, phase, **stats):
    return hp.HostEvent(int(start_ms * MS), int(end_ms * MS), phase, stats)


def test_a_chunks_idle_is_its_round_trip_and_the_gaps_inside_its_run():
    # a call of 40 ms whose run is 2..39 ms with two gaps of 0.25 ms; another
    # that reaches 6 ms past the slice's end
    calls = [(10 * MS, 50 * MS), (60 * MS, 106 * MS)]
    busy = [(12 * MS, 20 * MS), (int(20.25 * MS), 30 * MS), (int(30.25 * MS), 49 * MS),
            (62 * MS, 80 * MS), (int(80.25 * MS), 104 * MS)]
    out = chunk_idle.split(calls, busy, 0, 100 * MS)
    assert out["round_trips"] == [3 * MS]  # 40 - 37; the clipped chunk is left out
    assert out["gaps_ns"] == MS // 2 + MS // 4 and out["outside_ns"] == 0
    assert out["round_trip_ns"] == 3 * MS + 2 * MS  # the clipped one: 62 - 60, up to the edge
    # a call with nothing under it is all round trip
    assert chunk_idle.split([(0, 5 * MS)], busy, 0, 100 * MS)["round_trip_ns"] == 5 * MS


def test_a_helper_program_ahead_of_the_chunks_own_is_not_its_run():
    """A greedy chunk puts ``PRNGKey(0)``'s few microseconds on the device ~2 ms
    before its own program: the wait between the two is the launch's."""
    calls = [(10 * MS, 120 * MS)]
    helper, run = (int(11.5 * MS), int(11.5 * MS) + 300), (int(13.6 * MS), int(118.9 * MS))
    out = chunk_idle.split(calls, [helper, run], 0, 200 * MS)
    assert out["round_trips"] == [110 * MS - (run[1] - run[0])]
    assert out["gaps_ns"] == 0 and out["outside_ns"] == 300
    # operations closer than host_phases.RUN_GAP_NS are one run
    near = (run[0] - hp.RUN_GAP_NS + 1000, run[0] - hp.RUN_GAP_NS + 1300)
    out = chunk_idle.split(calls, [near, run], 0, 200 * MS)
    assert out["gaps_ns"] == hp.RUN_GAP_NS - 1300 and out["outside_ns"] == 0


def test_round_trips_and_gaps_are_the_decode_loop_row_of_the_idle_table():
    events = sorted([
        _ev(0, 100, "sched.tick", tick=1), _ev(1, 3, "inference.prepare"),
        _ev(3, 47, "inference.decode_loop", steps=8), _ev(47, 50, "sched.emit"),
        _ev(100, 200, "sched.tick", tick=2), _ev(101, 103, "inference.prepare"),
        _ev(103, 147, "inference.decode_loop", steps=8), _ev(147, 150, "sched.emit"),
    ], key=lambda e: (e.start, -e.end))
    ops = [(int(s * MS), int(e * MS), f"%fusion.{i} = bf16[8] fusion()") for i, (s, e) in enumerate(
        [(0.5, 1), (4.5, 20), (20.25, 46), (105, 120), (120.4, 146)])]
    log = []
    env = {"trace": tr.Trace({0: ops}, []), "host_phases": (events, {}), "log": log.append}
    run = {"spans": [], "t0": 0.0, "seconds": 1.0}
    trips = chunk_idle.read(run, {"value": "round_trip_p50_ms"}, env)
    gaps = chunk_idle.read(run, {"value": "idle_in_run_pct"}, env)
    lo, hi = env["trace"].window()
    assert trips == pytest.approx(2.75)  # 44 - 41.5 and 44 - 41: the median
    assert gaps == pytest.approx(100 * (0.25 + 0.4) * MS / (hi - lo))
    row = trace_idle_in_phase.table(run, env)["inference.decode_loop"]
    out = env["chunk_idle"]
    assert out["outside_ns"] == 0
    assert 100.0 * (out["round_trip_ns"] + out["gaps_ns"]) / (hi - lo) == pytest.approx(row)
    (line, ) = [entry for entry in log if entry.startswith("decode_loop chunks")]
    assert "2 (2 whole)" in line and f"idle share {row:.2f} %" in line


# ------------------------------------------- a slice of a chip trace (longgen) ---
@pytest.fixture(scope="module")
def chip():
    """155 ms of ``mixtral-longgen-closed`` on the chip (PR 37; cut from a traced
    run of the same programs made for PR 36, which was refused): the tail of one
    ``decode_loop`` chunk, a 121-token ``put`` step, a whole chunk of 8 steps x 16
    sequences, the head of the next; both paths of every program are ``capacity``,
    so the dispatch events carry ``moe_banks`` (every bank) among their stats."""
    trace, _, _ = hp.load_json(FIXTURE)
    # the cut clips a host event at the slice's edges; the program's spans are
    # whole, so the four clipped ones (two chunks and their ticks) get the times
    # the trace had (``unclipped``: index, start, end)
    with open(FIXTURE) as f:
        doc = json.load(f)
    rows = [hp.HostEvent(*row) for row in doc["host"]]
    for index, start, end in doc["unclipped"]:
        rows[index] = rows[index]._replace(start=start, end=end)
    events = sorted(rows, key=lambda e: (e.start, -e.end))
    return tr.Trace(trace.devices, [(0, 1000, "bench.clock_sync", "bench-trace")]), events


def _chip_env(chip, devices=None):
    trace, events = chip
    if devices is not None:
        trace = tr.Trace(devices, trace.host)
    log = []
    return {"trace": trace, "host_phases": (events, {}), "log": log.append, "logged": log,
            "peaks": PEAKS, "config": MIXTRAL}


def _chip_run(chip):
    """The program's spans as the ring would hold them, rebuilt from the trace's
    annotations (a capacity step's ``moe_banks`` is known at entry, so it is
    among the event's stats), on a span clock whose 0 is the trace's."""
    rows = [{"name": e.phase.split(".")[1], "cat": e.phase.split(".")[0],
             "ts_us": e.start / 1e3, "dur_us": (e.end - e.start) / 1e3,
             "args": dict(e.stats)} for e in chip[1]]
    return {"spans": rows, "t0": 0.0, "seconds": 1.0,
            "trace_slice": types.SimpleNamespace(began=0.0, ended=0.155, sync_clock=0.0)}


def _capacity_params():
    with open(os.path.join(tiny.REPO, "benchmark", "metrics", "moe_capacity_roofline.json")) as f:
        return json.load(f)["params"]


def test_chip_slice_the_capacity_gemms_read_near_their_roofline_and_under_it(chip):
    env = _chip_env(chip)
    value = roofline.read(_chip_run(chip), _capacity_params(), env)
    assert 88.0 < value < 97.0
    (line, ) = env["logged"]
    assert "8.00 banks a layer-step" in line and "capacity path" in line
    # the whole chunk alone: 8 steps x 3 layers x the two fusions
    whole = [e for e in chip[1] if e.phase == "inference.decode_loop" and e.stats.get("sequences") == 16
             and e.end - e.start > 90 * MS]
    assert len(whole) == 1 and whole[0].stats["moe_banks"] == 8 * 3 * 8


@pytest.mark.parametrize("cut_ms", [40.0, 60.0, 85.0, 110.0, 128.0])
def test_chip_slice_an_edge_inside_a_chunk_moves_the_least_time_by_a_launch_at_most(chip, cut_ms):
    """The slice ends (or begins) ``cut_ms`` in, in the middle of the whole chunk:
    the span counts by the part of it inside the slice, as the events do. What
    the edge leaves is the part of a call that is not the run (the launch ahead
    of it, the result's way back: 2-4 ms here), under 3 ms of roofline time
    either way whatever the cut: 0.1 % of a 4 s slice's reading, where counting
    the chunk whole or not at all would be a chunk's 83 ms = 2 %."""
    import re
    trace, _ = chip
    params, run = _capacity_params(), _chip_run(chip)
    whole = roofline.read(run, params, _chip_env(chip))
    cut = int(cut_ms * MS)
    for keep in (lambda s, e: e <= cut, lambda s, e: s >= cut):
        devices = {c: [op for op in ops if keep(op[0], op[1])] for c, ops in trace.devices.items()}
        env = _chip_env(chip, devices)
        value = roofline.read(run, params, env)
        took = float(re.search(r"([\d.]+) s in", env["logged"][0]).group(1))
        assert abs(value - whole) / 100 * took < 3e-3


def test_chip_slice_a_chunks_idle_is_all_round_trip_and_adds_up_to_the_tables_row(chip):
    env = _chip_env(chip)
    run = _chip_run(chip)
    trip = chunk_idle.read(run, {"value": "round_trip_p50_ms"}, env)
    gaps = chunk_idle.read(run, {"value": "idle_in_run_pct"}, env)
    out = env["chunk_idle"]
    assert len(out["round_trips"]) == 1 and 3.0 < trip < 6.0  # one whole chunk in the slice
    # the device goes from operation to operation inside a chunk's program ...
    assert gaps < 0.01
    # ... and PRNGKey(0)'s program sits ~2 ms ahead of it, under the same call
    assert 0 < out["outside_ns"] < 5_000
    row = trace_idle_in_phase.table(run, env)["inference.decode_loop"]
    together = out["round_trip_ns"] + out["gaps_ns"] - out["outside_ns"]
    assert 100.0 * together / out["slice_ns"] == pytest.approx(row, rel=1e-6)
    assert any(f"idle share {row:.2f} %" in entry for entry in env["logged"])
