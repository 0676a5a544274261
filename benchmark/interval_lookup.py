"""``trace_reduce.attribute`` in O(log n) a gap, for cells with many short steps.

``trace_reduce.summarize`` labels every idle gap of the traced slice with
``attribute(gap, labelled)``, which walks ALL of ``labelled`` for each gap.
``labelled`` has two intervals per engine step of the whole run
(``spans.host_intervals``) and the slice has one gap per device operation, so
the work grows with the square of the step rate: ~1.5e8 overlaps for a Mixtral
cell's 17 ms steps, ~1e9 (over 300 s; my chip runs, PR 26) for a window cell's
5-8 ms steps, and the run is killed at its limit before it prints a line.

Where ``labelled`` is sorted and disjoint, as the step intervals are, the
intervals that can overlap a gap are found by bisection and walked in the same
order: the same label comes out (the first interval with the largest overlap;
``tests/benchmark/test_window_cell.py`` holds the two against each other). Any
other ``labelled`` goes to the function that was there.

A file the benchmark already has may not be edited by the PR that needs this
(PR 26), so a cell's family module installs it for that cell's process
(``install``); PERF.md section 7 asks a ``benchmark`` PR to move the bisection
into ``trace_reduce.attribute`` and delete this file."""

import bisect

from benchmark import trace_reduce

_plain = trace_reduce.attribute
_last = (None, None, None)  # (labelled, starts, ends) of the list asked about last


def _indexed(labelled):
    """``(starts, ends)`` of a sorted, disjoint ``labelled``, else ``(None, None)``;
    kept for the next gap (``summarize`` passes the same list for every gap)."""
    global _last
    if _last[0] is not labelled:
        ordered = all(labelled[i][1] <= labelled[i + 1][0] and labelled[i][0] <= labelled[i][1]
                      for i in range(len(labelled) - 1))
        _last = (labelled, [s for s, _, _ in labelled], [e for _, e, _ in labelled]) if ordered \
            else (labelled, None, None)
    return _last[1], _last[2]


def attribute(gap, labelled):
    """What ``trace_reduce.attribute`` returns, found by bisection where
    ``labelled`` is a list of sorted, disjoint ``(start, end, label)``."""
    if not isinstance(labelled, list) or len(labelled) < 64:
        return _plain(gap, labelled)
    starts, ends = _indexed(labelled)
    if starts is None:
        return _plain(gap, labelled)
    best, best_ns = "unattributed", 0
    i = bisect.bisect_right(ends, gap[0])  # the first interval that ends after the gap starts
    while i < len(starts) and starts[i] < gap[1]:
        ns = min(ends[i], gap[1]) - max(starts[i], gap[0])
        if ns > best_ns:
            best, best_ns = labelled[i][2], ns
        i += 1
    return best


def install():
    trace_reduce.attribute = attribute
