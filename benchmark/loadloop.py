"""The load generator's one loop, and the arithmetic from its timestamps to the
serving metrics.

One thread sends what is due and consumes what has arrived, on one clock. Time 0
is the start of the measured window; a lead-in before it brings the system to
its steady state, and nothing sent in the lead-in is measured except by the
tokens it completes inside the window. Latency is counted from when a request
was DUE, not from when it was sent: a stalled generator delays later requests,
and that delay is the server's as far as a user can tell.

The loop knows nothing of JAX or of the program: it drives a ``system`` with
``submit(request) -> handle``, ``poll(handle) -> (new_tokens, finished)`` and
``outcome(handle) -> (ok, detail)``; ``sample()`` may return a dict of gauges
taken every ``sample_every_s``.
"""

import contextlib
import heapq
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np


@dataclass
class Request:
    """One request as the traffic generator made it, and what happened to it.
    Times are seconds from the start of the window."""
    index: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    seed: int = 0
    client: Optional[int] = None
    sent_s: Optional[float] = None
    submit_ms: Optional[float] = None   # time inside system.submit
    first_s: Optional[float] = None
    last_s: Optional[float] = None
    done_s: Optional[float] = None
    n_tokens: int = 0
    window_tokens: int = 0   # of its prompt and generated tokens, those served inside the window
    ok: Optional[bool] = None
    detail: str = ""
    handle: object = field(default=None, repr=False)


def run(system, traffic, *, seconds, lead_in_s, drain_s, tick_s=0.001, sample_every_s=0.1,
        clock=time.perf_counter, sleep=time.sleep, annotate=None):
    """Drive ``traffic`` through ``system``. Returns ``(requests, samples, t0)``:
    every request sent, the gauge samples ``[(t, dict)]`` taken inside the
    window, and the clock's reading at time 0."""
    annotate = annotate or (lambda name: contextlib.nullcontext())
    pending = [(r.due_s, r.index, r) for r in traffic.initial()]
    heapq.heapify(pending)
    t0 = clock() + lead_in_s
    sent, active, samples = [], [], []
    next_sample = 0.0
    while True:
        now = clock() - t0
        if now < seconds:
            with annotate("bench.submit"):
                while pending and pending[0][0] <= now:
                    _, _, req = heapq.heappop(pending)
                    req.sent_s = clock() - t0
                    try:
                        req.handle = system.submit(req)
                        active.append(req)
                    except Exception as e:  # a refusal is a failed request, not a crash
                        req.ok, req.detail, req.done_s = False, f"submit: {e!r}", req.sent_s
                    req.submit_ms = (clock() - t0 - req.sent_s) * 1e3
                    sent.append(req)
        with annotate("bench.consume"):
            still = []
            for req in active:
                n_new, finished = system.poll(req.handle)
                if n_new:
                    t = clock() - t0
                    if 0.0 <= t < seconds:
                        # a prompt is served when its first token arrives
                        req.window_tokens += n_new + (req.prompt.size if req.first_s is None
                                                      else 0)
                    if req.first_s is None:
                        req.first_s = t
                    req.last_s = t
                    req.n_tokens += n_new
                if finished:
                    req.done_s = clock() - t0
                    req.ok, req.detail = system.outcome(req.handle)
                    if req.ok and req.n_tokens != req.max_new_tokens:
                        req.ok = False
                        req.detail = f"{req.n_tokens} tokens of {req.max_new_tokens} asked"
                    nxt = traffic.on_finish(req, req.done_s)
                    if nxt is not None:
                        heapq.heappush(pending, (nxt.due_s, nxt.index, nxt))
                else:
                    still.append(req)
            active = still
        now = clock() - t0
        if 0.0 <= now < seconds and now >= next_sample:
            gauges = system.sample()
            if gauges:
                samples.append((now, gauges))
            next_sample = now + sample_every_s
        if now >= seconds:
            # drain: the requests due inside the window still owe a first token
            # and a second (for the gap); nothing else is waited for
            owed = [r for r in active if 0.0 <= r.due_s < seconds and r.n_tokens < 2]
            if not owed or now >= seconds + drain_s:
                break
        wait = tick_s
        if pending and now < seconds:
            wait = min(wait, max(0.0, pending[0][0] - now))
        sleep(wait)
    return sent, samples, t0


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def measured(requests, seconds):
    """The requests the window judges: those due inside it."""
    return [r for r in requests if 0.0 <= r.due_s < seconds]


def failed(r):
    return r.ok is False or r.first_s is None


def ttft_values_ms(reqs):
    """Due-to-first-token per request; one that failed, was refused or got no
    first token before the drain ended counts as the largest value seen."""
    good = [(r.first_s - r.due_s) * 1e3 for r in reqs if not failed(r)]
    if not good:
        return []  # nothing was served: there is no latency to report
    return good + [max(good)] * (len(reqs) - len(good))


def tpot_values_ms(reqs):
    """Per request, the mean gap between its tokens as the consumer saw them."""
    return [(r.last_s - r.first_s) / (r.n_tokens - 1) * 1e3
            for r in reqs if not failed(r) and r.n_tokens >= 2]


def served_tokens_per_s(requests, seconds):
    """Tokens served inside the window, over the window: each generated token
    when it reaches the consumer, each prompt when its first token does,
    whenever the request was sent and whether or not it ends inside the window
    (counting whole requests at their end makes the rate swing with which long
    request happens to straddle the window's edge). A failed request serves
    nothing."""
    return sum(r.window_tokens for r in requests if r.ok is not False) / seconds


def late_values_ms(requests):
    return [(r.sent_s - r.due_s) * 1e3 for r in requests if r.sent_s is not None]


def slo_met_pct(reqs, ttft_limit_ms, tpot_limit_ms):
    """Share of the measured requests that met both limits; a failed one misses."""
    if not reqs:
        return None
    met = 0
    for r in reqs:
        if failed(r):
            continue
        ttft = (r.first_s - r.due_s) * 1e3
        tpot = ((r.last_s - r.first_s) / (r.n_tokens - 1) * 1e3) if r.n_tokens >= 2 else 0.0
        met += ttft <= ttft_limit_ms and tpot <= tpot_limit_ms
    return 100.0 * met / len(reqs)
