"""Prompt and generated tokens served inside the window, over the window
(``loadloop.served_tokens_per_s``)."""

from benchmark import loadloop


def read(run, params, env):
    if run["mode"] != "serve":
        return None
    return loadloop.served_tokens_per_s(run["requests"], run["seconds"])
