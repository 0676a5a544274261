"""``trace_paged_roofline`` with the heads' width from the CONFIGURATION: the
runner hands the accepted reader ``hidden_size / heads`` (256 for Falcon-H1,
whose ``head_dim`` is 128 at 20 heads over a 5120-wide stream), so a family
whose heads do not span the stream reads its paged kernel here: the accepted
reader, its time and its count (``opcount.paged_attention``) unchanged, on a
run whose ``model`` carries the configuration's ``head_dim``.

A configuration without a ``head_dim`` of its own gives nothing to read (the
accepted reader is right there)."""

from benchmark.readers import trace_paged_roofline


def read(run, params, env):
    head_dim = env["config"].get("head_dim")
    if not head_dim or "model" not in run or env.get("trace") is None:
        return None
    return trace_paged_roofline.read(dict(run, model=dict(run["model"], head_dim=head_dim)),
                                     params, env)
