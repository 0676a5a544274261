"""Generated tokens that reached the consumer inside the window, over the
window: ``serve_throughput``'s count without the prompts.

``loadloop.run`` adds a request's whole prompt to its ``window_tokens`` at the
moment its first token arrives, so in a closed loop whose prompts outweigh
their answers the total is a count of the few dozen requests whose first token
fell inside the window (PERF.md section 6, PR 34: 37-40 lumps of 1.5-2.6 k
tokens, 5 % apart between seeds on the same code). The generated tokens alone
are what the decode steps delivered: live sequences over the step time, whatever
lengths a seed serves. A failed request serves nothing, as there.

Read only beside the chip's trace (``host_phases.on_chip``), as the span
readers are and for their reason: the CPU rehearsal's printed metrics are
listed exactly (``tests/benchmark/test_harness_rehearsal.py``)."""

from benchmark import host_phases


def read(run, params, env):
    if run["mode"] != "serve" or not host_phases.on_chip(env):
        return None
    seconds = run["seconds"]
    total = 0
    for r in run["requests"]:
        if r.ok is False:
            continue
        # the prompt was counted with the first token, if that came inside the window
        counted = r.first_s is not None and 0.0 <= r.first_s < seconds
        total += r.window_tokens - (int(r.prompt.size) if counted else 0)
    return total / seconds
