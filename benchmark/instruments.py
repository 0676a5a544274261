"""What the benchmark takes itself: compile counts, device memory, the
profiler's trace of a slice of the window, and host annotations in that trace."""

import contextlib
import glob
import os
import threading
import time


class CompileMeter:
    """Counts what JAX reports about compilation while the process runs: seconds
    in the backend compiler, programs compiled, persistent-cache hits and
    misses. (Copied from chip_smoke.CompileMeter: a ``jax.monitoring``
    listener, so it sees the scheduler thread's compiles too and needs no
    telemetry session.)"""

    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == self._BACKEND_COMPILE:
            with self._lock:
                self.seconds += seconds
                self.programs += 1

    def _on_event(self, event, **_):
        if event in (self._HIT, self._MISS):
            with self._lock:
                if event == self._HIT:
                    self.hits += 1
                else:
                    self.misses += 1

    def snapshot(self):
        with self._lock:
            return {"seconds": self.seconds, "programs": self.programs, "hits": self.hits,
                    "misses": self.misses}

    def compiled_since(self, before):
        """Programs that went through the backend compiler and were NOT served
        from the persistent cache since ``before`` (a snapshot): JAX reports a
        backend-compile duration for a cache hit too (the time to load it)."""
        now = self.snapshot()
        return (now["programs"] - before["programs"]) - (now["hits"] - before["hits"])


def device_summary(devices):
    """The ``device`` object of the result line, as JAX reports the devices."""
    peaks = [d.memory_stats() or {} for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max((p.get("peak_bytes_in_use", 0) for p in peaks), default=0)}


def annotate(name):
    """A host span in the profiler's own trace (costs nothing when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class TraceSlice:
    """Traces ``[start_s, start_s + length_s)`` of the window from a thread of
    its own, so that starting and stopping the profiler never stalls the load
    generator. ``arm(t0)`` is called with the clock's reading at window time 0."""

    SYNC = "bench.clock_sync"

    def __init__(self, log_dir, start_s, length_s, clock=time.perf_counter):
        self.log_dir, self.start_s, self.length_s, self.clock = log_dir, start_s, length_s, clock
        self.began = self.ended = self.sync_clock = None  # clock readings
        self._thread = None

    def arm(self, t0):
        self._thread = threading.Thread(target=self._run, args=(t0, ), name="bench-trace",
                                        daemon=True)
        self._thread.start()

    def _run(self, t0):
        import jax
        time.sleep(max(0.0, t0 + self.start_s - self.clock()))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the interpreter's own frames: large, and not read
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.began = self.clock()
        # one host event whose start is known on the host clock too: it ties the
        # program's span clock to the trace's
        with jax.profiler.TraceAnnotation(self.SYNC):
            self.sync_clock = self.clock()
            time.sleep(0.001)
        time.sleep(max(0.0, self.began + self.length_s - self.clock()))
        self.ended = self.clock()
        jax.profiler.stop_trace()

    def finish(self, timeout=300):
        """Wait for the trace to be written; returns the ``.xplane.pb`` path."""
        if self._thread is None:
            return None
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop")
        found = sorted(glob.glob(os.path.join(self.log_dir, "plugins", "profile", "*",
                                              "*.xplane.pb")), key=os.path.getmtime)
        return found[-1] if found else None


@contextlib.contextmanager
def telemetry_spans(enabled):
    """The program's telemetry session, spans only, for the traced run: the
    scheduler records no span without one. The untraced run leaves it off."""
    if not enabled:
        yield None
        return
    from deepspeed_tpu import telemetry
    session = telemetry.configure({"enabled": True, "compile_watch": False, "max_spans": 1 << 20})
    try:
        yield session.spans
    finally:
        session.close()
