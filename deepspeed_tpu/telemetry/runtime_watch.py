"""Runtime watch: what stalls the PROCESS, as spans on the clock of every other
span.

A garbage collection or a frozen interpreter shows in the serving spans only as
one long ``sched.emit`` or ``inference.put``, and a descheduled process as
nothing at all. Two sources give such a stall a name:

- *Garbage collections.* One function on ``gc.callbacks``. A collection of
  generation 2, or any pause of at least ``GC_SPAN_US``, becomes the ring span
  ``runtime.gc`` (args ``generation``, ``collected``, ``uncollectable``) and an
  observation of ``runtime_gc_pause_seconds``; a shorter young-generation pass
  costs two clock reads. The callback takes NO lock: a collection starts
  between any two bytecodes of its thread, also inside ``SpanRecorder.tail`` or
  a registry call that holds the very lock ``record`` / ``observe`` would wait
  for. It leaves the pause in a deque and the watch thread writes it, at most
  one period later, under the pause's own timestamps.
- *Host stalls.* One daemon thread, ``dstpu-runtime-watch``, sleeps ``PERIOD_S``
  and measures how late it woke. Later than ``STALL_US`` is the ring span
  ``runtime.stall``, from the instant it should have woken to the instant it
  did (arg ``in_gc``: 1 where a ``runtime.gc`` span overlaps it), and an
  observation of ``runtime_host_late_seconds``. The interpreter lock held
  elsewhere, a collection and the whole process descheduled all make this
  thread late; ``in_gc`` and the other threads' spans say which. Once a second
  it records ``runtime.alive`` (arg ``max_late_us`` of that second): by it a
  reader tells "the watch ran and saw nothing" from "this program has no
  watch", and reads the lateness below the threshold.

Both are ring spans on ``spans.now_us()`` like every other span, and write no
annotation of their own: a reader moves them onto a profiler trace's clock by
the spans that are in both (the scheduler's ``sched.tick``).

Installed by ``TelemetrySession`` and removed by its ``close()``. With no
session there is no callback, no thread, and no check at any call site.
"""

import gc
import threading
import time
from collections import deque

from deepspeed_tpu.telemetry.spans import now_us

THREAD_NAME = "dstpu-runtime-watch"
# a stall is seen to within one period of its start; at 10 ms the thread's own
# CPU time is half that of 5 ms. What its presence takes from a thread that
# traces and lowers does NOT fall with the period (PERF.md section 6, PR 52)
PERIOD_S = 0.010
# two interpreter switch intervals; a quiet chat run on the chip wakes at most
# ~2 ms late (PERF.md section 6, PR 52)
STALL_US = 10_000
GC_SPAN_US = 1_000
ALIVE_EVERY_US = 1_000_000
CAT = "runtime"

_PERIOD_US = int(PERIOD_S * 1e6)
_WATCH = None  # the installed watch: one callback and one thread a process


class RuntimeWatch:
    """The callback and the thread over one registry + span recorder pair.
    ``clock_us`` and ``wait`` (``wait(seconds) -> stop?``) are the test's."""

    def __init__(self, registry, spans, clock_us=now_us, wait=None):
        self._spans = spans
        self._clock = clock_us
        self._stopped = False
        self._wait = wait or self._sleep
        self._thread = None
        self._gc_hist = {g: registry.histogram(
            "runtime_gc_pause_seconds", "Garbage-collection pauses of generation 2 or >= 1 ms",
            labels={"generation": str(g)}) for g in range(3)}
        self._late_hist = registry.histogram(
            "runtime_host_late_seconds",
            "How late the runtime watch's thread woke, where later than the stall threshold")
        # written by the callback alone (collections do not nest), read by the thread
        self._gc_t0 = None
        self._gc_done = deque()  # (t0_us, dur_us, info) of the pauses worth a span

    # ------------------------------------------------------------ collections --
    def on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = self._clock()
            return
        t0 = self._gc_t0
        if t0 is None:  # installed in the middle of a collection
            return
        dur = self._clock() - t0
        if info["generation"] == 2 or dur >= GC_SPAN_US:
            self._gc_done.append((t0, dur, info))
        # last: the watch's thread, late for the length of this collection, takes
        # the interpreter at this function's first bytecode and must still see it
        self._gc_t0 = None

    def flush(self):
        """Write the pauses the callback left; returns their ``(start, end)``."""
        written = []
        while self._gc_done:
            try:
                t0, dur, info = self._gc_done.popleft()
            except IndexError:  # another caller took it
                break
            self._gc_hist[info["generation"]].observe(dur / 1e6)
            self._spans.record("gc", CAT, ts_us=t0, dur_us=dur, args={
                "generation": info["generation"], "collected": info["collected"],
                "uncollectable": info["uncollectable"]})
            written.append((t0, t0 + dur))
        return written

    # ----------------------------------------------------------------- stalls --
    def _sleep(self, seconds):
        # not ``Event.wait``: that builds a lock a call, ten times the cost of the period
        time.sleep(seconds)
        return self._stopped

    def run(self):
        clock = self._clock
        second_began = clock()
        max_late = 0
        while True:
            due = clock() + _PERIOD_US
            stopped = self._wait(PERIOD_S)
            woke = clock()
            collections = self.flush()
            if stopped:
                return
            late = woke - due
            max_late = max(max_late, late)
            if late > STALL_US:
                began = self._gc_t0  # a collection whose ``stop`` has not got to its end
                in_gc = int(any(s < woke and e > due for s, e in collections)
                            or (began is not None and woke - began >= GC_SPAN_US))
                self._spans.record("stall", CAT, ts_us=due, dur_us=late, args={"in_gc": in_gc})
                self._late_hist.observe(late / 1e6)
            if woke - second_began >= ALIVE_EVERY_US:
                self._spans.record("alive", CAT, ts_us=second_began, dur_us=woke - second_began,
                                   args={"max_late_us": max_late})
                second_began, max_late = woke, 0

    def start(self):
        gc.callbacks.append(self.on_gc)
        self._thread = threading.Thread(target=self.run, name=THREAD_NAME, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        if self.on_gc in gc.callbacks:
            gc.callbacks.remove(self.on_gc)
        self._stopped = True
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self.flush()


def install(registry, spans):
    """Start the watch (TelemetrySession does, when telemetry turns on);
    replaces any previous one."""
    global _WATCH
    uninstall()
    _WATCH = RuntimeWatch(registry, spans).start()
    return _WATCH


def uninstall(watch=None):
    """Stop ``watch`` (the installed one by default): its callback leaves
    ``gc.callbacks`` and its thread has ended when this returns."""
    global _WATCH
    watch = watch or _WATCH
    if watch is None:
        return
    watch.stop()
    if _WATCH is watch:
        _WATCH = None
