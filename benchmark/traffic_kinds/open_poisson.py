"""Open loop: independent users. Requests are due on a schedule fixed before
the run, whether or not earlier ones have finished."""

import numpy as np

from benchmark.loadloop import Request
from benchmark.traffic_kinds import _draw


class Traffic:
    """Parameters (the traffic file's ``params``): ``rate_per_s``, ``prompt`` and
    ``output`` length specs, ``temperature``. Arrivals run from the start of the
    lead-in to the end of the window."""

    def __init__(self, params, seed, seconds, lead_in_s, vocab_size):
        rng = np.random.default_rng([seed, 0x0be7])
        span = seconds + lead_in_s
        n = max(1, int(round(params["rate_per_s"] * span)))
        due = np.cumsum(_draw.exponential_gaps(params["rate_per_s"], n, rng)) - lead_in_s
        prompts = _draw.lengths(params["prompt"], n, rng)
        outputs = _draw.lengths(params["output"], n, rng)
        self.requests = [
            Request(index=i, due_s=float(due[i]), prompt=_draw.tokens(rng, vocab_size, prompts[i]),
                    max_new_tokens=int(outputs[i]), temperature=float(params.get("temperature", 0.0)),
                    seed=int(rng.integers(0, 2**31 - 1)))
            for i in range(n) if due[i] < seconds]

    def initial(self):
        return list(self.requests)

    def on_finish(self, request, now_s):
        return None
