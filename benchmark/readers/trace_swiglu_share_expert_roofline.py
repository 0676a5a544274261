"""``trace_share_expert_roofline`` for a layer of gated (SwiGLU) experts that
holds a SHARE of those it routes over, under a name a later cell can list: that
reader's count, carriers, clocks and log line, handed only a configuration it
can read — one that states ``first_k_dense_replace`` (the expert layers are the
depth less the leading dense ones) and no ``hybrid_override_pattern`` (a model
of one mixer a block counts its expert blocks by the pattern:
``trace_hybrid_expert_roofline``). Any other configuration gives nothing to
read, where that reader raises: the rehearsals open every metric to every
cell (``tests/benchmark/tiny.py``)."""

from benchmark.readers import trace_share_expert_roofline


def read(run, params, env):
    config = env["config"]
    if "first_k_dense_replace" not in config or "hybrid_override_pattern" in config:
        return None
    return trace_share_expert_roofline.read(run, params, env)
