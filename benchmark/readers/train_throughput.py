"""Tokens of the optimizer steps dispatched inside the window (all chips
together), over the time until the last of them had completed: the window
starts with an empty dispatch queue and ends blocked on the last loss."""


def read(run, params, env):
    if run["mode"] != "train":
        return None
    return run["steps"] * run["tokens_per_step"] / run["elapsed_s"]
