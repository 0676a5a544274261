"""Everything before the window: imports, weights, engine or job set-up, the
correctness check, warm-up (compilation included) and the lead-in."""


def read(run, params, env):
    return run["setup_s"]
