"""The reduction from a trace to numbers: interval arithmetic on hand-made
cases, and the whole reduction on a slice recorded on the chip."""

import os

import pytest

from benchmark import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


@pytest.mark.parametrize("intervals,merged,length", [
    ([], [], 0),
    ([(0, 10)], [(0, 10)], 10),
    ([(5, 7), (0, 10)], [(0, 10)], 10),                  # contained
    ([(0, 5), (5, 8)], [(0, 8)], 8),                     # touching
    ([(0, 5), (3, 8), (10, 12)], [(0, 8), (10, 12)], 10),
    ([(4, 4), (9, 3)], [], 0),                           # empty and reversed are dropped
])
def test_merge_and_total(intervals, merged, length):
    assert tr.merge(intervals) == merged
    assert tr.total(tr.merge(intervals)) == length


@pytest.mark.parametrize("a,b,left", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 10)], [(2, 4), (6, 7)], [(0, 2), (4, 6), (7, 10)]),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(5, 6)], [(0, 10)], []),
    ([(0, 2), (4, 6), (8, 10)], [(1, 9)], [(0, 1), (9, 10)]),
])
def test_subtract(a, b, left):
    assert tr.subtract(a, b) == left


def test_gaps_busy_and_names():
    ops = [(0, 10, "fusion.1"), (5, 20, "fusion.2"), (30, 40, "paged_attention_update.3"),
           (60, 70, "all-gather-start.4")]
    busy = tr.busy(ops)
    assert busy == [(0, 20), (30, 40), (60, 70)] and tr.total(busy) == 40
    assert tr.gaps(busy, 0, 100) == [(20, 30), (40, 60), (70, 100)]
    assert tr.gaps(busy, 10, 65) == [(20, 30), (40, 60)]
    assert tr.seconds_by_name(ops)["fusion.2"] == pytest.approx(15e-9)
    assert [n for _, _, n in tr.matching(ops, "paged_attention")] == ["paged_attention_update.3"]
    assert tr.generic_name("fusion.123") == "fusion" and tr.generic_name("copy") == "copy"


@pytest.mark.parametrize("name,short", [
    ("%fusion.248 = bf16[8,16,28672]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,16,4096]{2,1,0} "
     "%fusion.96, bf16[8,4096,28672]{2,1,0:T(8,128)(2,1)} "
     "%params__layers_0____block_sparse_moe____ExpertFFN_0____wi__.1), kind=kOutput",
     "fusion bf16[8,16,28672] <- layers_N.block_sparse_moe.ExpertFFN_0.wi"),
    ("%copy.34 = bf16[3,2,2048,8,64,128]{5,3,4,2,1,0:T(8,128)(2,1)} copy(bf16[3,2,2048,8,64,128]"
     "{5,4,3,2,1,0} %cache.1), sharding={replicated}", "copy bf16[3,2,2048,8,64,128]"),
    ("%all-gather-start.7 = (f32[1024]{0}, f32[4096]{0}) all-gather-start(f32[1024]{0} %p)",
     "all-gather-start (f32[1024]"),
    ("fusion.12", "fusion"), ("bench.data_wait", "bench.data_wait"),
])
def test_short_name_folds_instances_and_layers(name, short):
    assert tr.short_name(name) == short


def test_exposed_collective_time_is_the_part_no_compute_covers():
    ops = [(0, 100, "all-gather.1"),       # 100 of collective ...
           (20, 50, "fusion.2"),           # ... 30 of it hidden under compute
           (200, 230, "reduce-scatter.3"),  # fully exposed
           (300, 400, "fusion.4"), (320, 330, "all-reduce.5")]  # fully hidden
    assert tr.exposed_collective_ns(ops) == (100 - 30) + 30


def test_attribute_takes_the_label_with_most_overlap():
    labelled = [(0, 10, "a"), (8, 30, "b")]
    assert tr.attribute((5, 12), labelled) == "a"
    assert tr.attribute((9, 20), labelled) == "b"
    assert tr.attribute((40, 50), labelled) == "unattributed"


def test_summarize_two_chips():
    trace = tr.Trace({0: [(0, 50, "fusion.1"), (60, 100, "fusion.2")],
                      1: [(0, 20, "fusion.1"), (80, 100, "copy.9")]},
                     [(45, 65, "bench.data_wait", "t")])
    s = tr.summarize(trace)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx((90 + 40) / 2 * 1e-9)
    assert s["idle_pct_by_chip"] == {0: pytest.approx(10.0), 1: pytest.approx(60.0)}
    ops = dict(map(tuple, s["breakdown"]["device_ops"]))
    assert ops["fusion"] == pytest.approx(110e-9) and ops["copy"] == pytest.approx(20e-9)
    assert [n for n, _ in s["breakdown"]["device_ops"]] == ["fusion", "copy"]
    idle = dict(map(tuple, s["breakdown"]["idle_gaps"]))
    # a gap goes whole to the label that covers most of it: chip 0's (50, 60)
    # and chip 1's (20, 80) both meet the one host interval; averaged over chips
    assert idle["bench.data_wait"] == pytest.approx((10 + 60) / 2 * 1e-9)
    assert len(s["breakdown"]["device_ops"]) <= 10


def test_container_operations_count_as_busy_but_not_as_rows():
    trace = tr.Trace({0: [(0, 100, "%while.3 = (s32[]{:T(128)}, bf16[8]) while(...)"),
                          (10, 40, "fusion.1"), (50, 90, "fusion.2")]}, [])
    s = tr.summarize(trace)
    assert s["busy_s"] == pytest.approx(100e-9)
    assert s["breakdown"]["device_ops"] == [["fusion", pytest.approx(70e-9)]]


def test_recorded_slice_from_the_chip():
    """A slice cut from this PR's first traced run of a cell on a TPU v5e
    (``tools/trace_cut.py``): the reduction finds the chip's operations, a busy
    share strictly between 0 and 1, and the Pallas kernel by its name."""
    path = os.path.join(FIXTURES, "chip_slice.json")
    trace = tr.load_json(path)
    assert trace.devices and all(ops for ops in trace.devices.values())
    s = tr.summarize(trace, labelled=[])
    assert 0 < s["busy_s"] < s["window_s"]
    for share in s["idle_pct_by_chip"].values():
        assert 0 < share < 100
    ops = trace.devices[min(trace.devices)]
    assert tr.matching(ops, "paged_attention_update")
    assert tr.total(tr.busy(ops)) <= sum(e - s_ for s_, e, _ in ops)
    assert s["breakdown"]["device_ops"][0][1] >= s["breakdown"]["device_ops"][-1][1]
