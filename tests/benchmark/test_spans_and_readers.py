"""From the program's spans to engine steps, and the readers that build on
them, on hand-made spans."""

import importlib
import types

import pytest

from benchmark import opcount, spans
from benchmark import trace_reduce as tr
from benchmark.runners import serve


def _span(name, ts, dur, cat="serving", **args):
    return {"name": name, "cat": cat, "ts_us": ts, "dur_us": dur, "args": args}


# window: t0 = 1 s, 1 s long -> [1e6, 2e6) us
ROWS = [
    _span("queued", 1_000_000, 3_000, uid=1),
    # step 1: a prefill chunk of 20 tokens for uid 1 beside a decode of uid 0
    _span("prefill", 1_010_000, 30_000, uid=1, tokens=20),
    _span("decode", 1_010_000, 30_000, uid=0, tokens=1),
    _span("put", 1_010_500, 100, cat="inference", sequences=2, tokens=21, uids=[1, 0]),
    # step 2: decode only, one put
    _span("decode", 1_050_000, 10_000, uid=0, tokens=1),
    _span("decode", 1_050_000, 10_000, uid=1, tokens=1),
    # step 3: a decode_loop chunk of 4 steps (uid 1 kept only 3 of its tokens)
    _span("decode", 1_070_000, 40_000, uid=0, tokens=4),
    _span("decode", 1_070_000, 40_000, uid=1, tokens=3),
    _span("decode_loop", 1_070_200, 50, cat="inference", sequences=2, steps=4, uids=[0, 1]),
    # outside the window
    _span("decode", 2_500_000, 99_000, uid=0, tokens=1),
]
RUN = {"spans": ROWS, "t0": 1.0, "seconds": 1.0, "mode": "serve"}


def _read(reader, params=None, run=RUN, env=None):
    return importlib.import_module(f"benchmark.readers.{reader}").read(run, params or {}, env or {})


def test_steps_group_members_and_find_decode_loop_chunks():
    steps = spans.steps(ROWS)
    assert [(s["ts_us"], s["dur_us"], s["loop_steps"], len(s["members"])) for s in steps] == [
        (1_010_000, 30_000, 1, 2), (1_050_000, 10_000, 1, 2), (1_070_000, 40_000, 4, 2),
        (2_500_000, 99_000, 1, 1)]
    assert len(spans.in_window(steps, RUN)) == 3


def test_span_readers():
    # decode steps: one put of 10 ms and a chunk of 4 x 10 ms -> five readings of 10 ms
    assert _read("span_step_time", {"phase": "decode"}) == pytest.approx(10.0)
    assert _read("span_step_time", {"phase": "prefill"}) == pytest.approx(30.0)
    assert _read("span_seqs_per_step") == pytest.approx(2.0)
    assert _read("span_duration", {"name": "queued", "cat": "serving", "percentile": 50}) == 3.0
    assert _read("span_step_time", {"phase": "decode"}, run=dict(RUN, spans=[])) is None


def test_paged_roofline_rebuilds_contexts_from_the_spans():
    peaks = opcount.peaks_for("TPU v5 lite")
    model = {"n_heads": 4, "n_kv_heads": 2, "head_dim": 8, "n_layers": 3, "block_size": 16}
    slice_ = types.SimpleNamespace(began=1.04, ended=1.2)  # steps 2 and 3, not step 1
    trace = tr.Trace({0: [(0, 2_000, "%paged_attention_update.3 = (bf16[8,4,8]) custom-call()"),
                          (3_000, 9_000, "fusion.1")]}, [])
    run = dict(RUN, model=model, trace_slice=slice_)
    got = _read("trace_paged_roofline", {"pattern": "paged_attention_update",
                                         "kernel_max_tokens": 32},
                run=run, env={"trace": trace, "peaks": peaks})
    # by step 2 uid 0 holds 1 token and uid 1 its 20 prompt tokens; each query
    # attends to what is cached and itself
    calls = [[[2], [21]]] + [[[3 + j], [22 + j]] for j in range(4)]
    least = sum(3 * opcount.roofline_seconds(*opcount.paged_attention(q, 4, 2, 8, 16), peaks)[0]
                for q in calls)
    assert got == pytest.approx(100.0 * least / 2e-6)
    assert _read("trace_paged_roofline", {"pattern": "nothing_by_this_name",
                                          "kernel_max_tokens": 32},
                 run=run, env={"trace": trace, "peaks": peaks}) is None


def test_host_intervals_put_step_spans_on_the_traces_clock():
    slice_ = types.SimpleNamespace(sync_clock=1.0, began=1.0, ended=2.0)
    trace = tr.Trace({}, [(5_000_000, 5_002_000, spans.SYNC_EVENT, "t")])  # clock 1.0 s = 5 ms
    labelled = spans.host_intervals(dict(RUN, trace_slice=slice_), trace)
    offset = 5_000_000 - 1.0e9
    assert labelled[0] == (1_010_000e3 + offset, 1_040_000e3 + offset,
                           "scheduler: inside engine step (put with prefill)")
    assert labelled[1][2].startswith("scheduler: between steps")
    assert [lab for _, _, lab in labelled if "decode_loop chunk" in lab]
    assert any("no step for 20 ms" in lab for _, _, lab in labelled)  # before the late span
    # without the program's spans (a training run) the harness's own marks label the gaps
    trace = tr.Trace({}, [(0, 10, "bench.data_wait", "t"), (20, 30, spans.SYNC_EVENT, "t")])
    assert spans.host_intervals({"spans": []}, trace) == [(0, 10, "bench.data_wait")]


def test_reachable_programs_follow_from_the_cells_parameters():
    engine = {"kv_block_size": 64,
              "state_manager": {"max_ragged_batch_size": 256, "max_ragged_sequence_count": 16,
                                "max_context": 1024}}
    traffic = {"prompt": {"max": 768}, "output": {"max": 256}, "temperature": 0.7}
    forward, loops = serve.reachable_programs(engine, {"decode_chunk": 8}, traffic)
    assert loops == []  # every request is sampled: decode_loop cannot run
    assert len(forward) == 33 and (8, 16, 4) not in forward and (256, 16, 16) in forward
    assert {t for t, _, _ in forward} == {8, 16, 32, 64, 128, 256}
    forward, loops = serve.reachable_programs(engine, {"decode_chunk": 8},
                                              dict(traffic, temperature=0.0))
    assert sorted(loops) == sorted(((s, s, mb), 8, False) for s in (8, 16) for mb in (4, 8, 16))
    # the padding rule restated here is the program's own
    from deepspeed_tpu.inference.v2.ragged import ragged_wrapper as rw
    for n in range(1, 600):
        assert serve._pad_tokens(n) == rw.to_padded(n)
    for n in range(1, 70):
        assert serve._pow2(n) == rw._pow2_pad(n, 4)
