"""Closed loop: callers that each wait for a reply. A client's next request is
due the moment its last reply ended."""

import numpy as np

from benchmark.loadloop import Request
from benchmark.traffic_kinds import _draw


class Traffic:
    """Parameters: ``clients``, ``prompt`` and ``output`` length specs,
    ``temperature``, ``requests_per_client`` (an upper bound on what one client
    can send in a run; the lengths are drawn for that many). The clients start
    spread evenly over the first half of the lead-in, so that they do not move
    in step."""

    def __init__(self, params, seed, seconds, lead_in_s, vocab_size):
        rng = np.random.default_rng([seed, 0xc105ed])
        self.clients = int(params["clients"])
        per = int(params["requests_per_client"])
        n = self.clients * per
        prompts = _draw.lengths(params["prompt"], n, rng).reshape(self.clients, per)
        outputs = _draw.lengths(params["output"], n, rng).reshape(self.clients, per)
        self._temperature = float(params.get("temperature", 0.0))
        # every prompt is made now: what a client sends must not depend on the
        # order in which replies happen to end
        self._prompts = [[_draw.tokens(rng, vocab_size, prompts[c, k]) for k in range(per)]
                         for c in range(self.clients)]
        self._seeds = rng.integers(0, 2**31 - 1, size=(self.clients, per))
        self._outputs = outputs
        self._sent = [0] * self.clients
        self._index = 0
        self._first_due = [-lead_in_s + 0.5 * lead_in_s * c / self.clients
                           for c in range(self.clients)]

    def _next(self, client, due_s):
        k = self._sent[client]
        if k >= self._outputs.shape[1]:
            return None  # the bound was too low for this run; the client stops
        self._sent[client] += 1
        self._index += 1
        return Request(index=self._index, due_s=due_s, prompt=self._prompts[client][k],
                       max_new_tokens=int(self._outputs[client, k]),
                       temperature=self._temperature, seed=int(self._seeds[client, k]),
                       client=client)

    def initial(self):
        return [self._next(c, self._first_due[c]) for c in range(self.clients)]

    def on_finish(self, request, now_s):
        return self._next(request.client, now_s)
