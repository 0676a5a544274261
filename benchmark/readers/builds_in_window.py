"""Executables JAX built between the start of the lead-in and the end of the
window (``jax.monitoring`` backend-compile events: a load from the persistent
cache counts too, since it also stalls the step that met the new shape). Must
read 0: every shape belongs to warm-up."""


def read(run, params, env):
    return run["builds_in_window"]
