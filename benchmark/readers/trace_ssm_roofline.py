"""A state-space scope's share of its roofline, over the traced slice: the two
forms of the Mamba-2 scan (``deepspeed_tpu/inference/v2/modules/ssm.py``),
under the scopes ``ssm/scan`` (the chunked form, a ``put`` step) and
``ssm/step`` (the recurrence, a ``decode_loop`` step). The count is kept here,
and is of the WORK, whatever implements the scope.

The time it took: the summed durations of the device operations whose scope
path matches ``params.pattern`` (``trace_scope_busy``'s reading of the trace),
under :func:`attributed`'s paths: the compiler's own operations on the blocks'
arrays carry no scope and are given one by their result type, so that the
reading and the writing back of a state, which the least below prices, are in
the time whatever carries them (XLA's gather, select and scatter today, a
fused kernel's own scope tomorrow: time cannot leave the metric by moving
between the two).
The least it could take: for every dispatch span (``inference.put`` for
``params.kind`` ``scan``, ``inference.decode_loop`` for ``step``) that starts
inside the slice, from the span's ``ssm_tokens`` (rows through a Mamba-2 block,
over the span's steps and blocks) and ``ssm_segments`` (sequence segments, so),

- bytes: a segment's float32 state ``[heads, head_dim, state]`` read once and
  written once (the state is a SEQUENCE's: a chunk of rows reads and writes it
  once, a decode row once a step), and a row's x, B, C in the stored type, its
  step and its float32 output;
- flop: the recurrence's own, 5 a state element a row (decay, the outer
  product's two, the add, and 2 for the reading with C less the one counted):
  what the chunked form spends more, it spends by choice.

A configuration without ``ssm_state_size``, spans without ``ssm_tokens`` (a
program that has none) or a trace without the scope give nothing to read."""

import bisect
import re

from benchmark import host_phases, opcount, trace_reduce

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
SPAN = {"scan": "put", "step": "decode_loop"}


def ssm_work(rows, segments, heads, head_dim, groups, state, dtype_bytes=2):
    """``(flop, bytes)`` of ``rows`` rows in ``segments`` sequence segments
    through ONE Mamba-2 block's scan."""
    elements = heads * head_dim * state
    flops = 5 * elements * rows
    nbytes = 2 * 4 * elements * segments
    nbytes += rows * ((heads * head_dim + 2 * groups * state) * dtype_bytes + 4 * heads
                      + 4 * heads * head_dim)
    return flops, nbytes


_RESULT = re.compile(r"= \(?([a-z]+\d*)\[([\d,]*)\]")
_FORM = re.compile(r"(^|/)ssm/(scan|step)(/|$)")


def own_arrays(config):
    """``[(scope, dtype or None, trailing dims)]``: the arrays that are a
    Mamba-2 block's and nobody else's, from the configuration's widths — the
    float32 state ``[.., heads, head_dim, state]`` (the pool, the rows gathered
    from it, the rows written back; its scope is the form's, ``ssm/scan`` or
    ``ssm/step``, so ``""`` here), the convolution's kept rows ``[..,
    conv_kernel - 1, conv_dim]`` and the input projection's kernel ``[hidden,
    d_inner + conv_dim + heads]``."""
    heads, state = config["mamba_num_heads"], config["ssm_state_size"]
    d_inner = heads * config["mamba_head_dim"]
    conv_dim = d_inner + 2 * config["n_groups"] * state
    return [("", "f32", (heads, config["mamba_head_dim"], state)),
            ("ssm/conv", None, (config["conv_kernel"] - 1, conv_dim)),
            ("ssm/in_proj", None, (config["hidden_size"], d_inner + conv_dim + heads))]


def _own_scope(name, own):
    """The scope of the blocks' own array that the operation ``name`` (its HLO
    text) makes, None where it makes none of them."""
    m = _RESULT.search(name)
    if m:
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        for scope, dtype, tail in own:
            if dims[-len(tail):] == tail and dtype in (None, m.group(1)):
                return scope
    return None


def attributed(ops, scopes, config, seconds=None):
    """``[(start, end, scope path, name)]`` of one chip's operations, containers
    left out. The path is the program's own (``host_phases.scopes_by_name``) where
    the operation has a scope of the program's. What the COMPILER adds around
    the blocks' arrays carries no ``op_name`` (on the chip: a pool re-laid
    around its update — ``remat_compressed`` / ``slice-done`` / ``copy`` —, a
    kernel's ``copy-done`` between memories, the zeros a gathered state is
    selected against; a loop of slices where the trace does not hand the body
    the loop's scope): such
    an operation whose RESULT is one of :func:`own_arrays` is given that
    array's scope + ``/unscoped``, a state's the form (``ssm/scan`` or
    ``ssm/step``) of the scoped operation of either form nearest to it in time
    — the blocks of one program are all of one form, and a block's gather, scan
    and scatter run side by side. ``seconds``, a dict, is added the time so
    attributed, by scope."""
    own = own_arrays(config)
    forms = sorted((s, m.group(2)) for s, _, name in ops
                   for m in [_FORM.search(scopes.get(name, ""))] if m)
    starts = [s for s, _ in forms]
    by_name, out = {}, []  # a slice has ~1e5 events of ~1e3 names
    for s, e, name in ops:
        if name not in by_name:
            scope = scopes.get(name, "")
            if trace_reduce.CONTAINERS.match(name):
                by_name[name] = None
            elif host_phases.scope_parts(scope):
                by_name[name] = (scope, False)
            else:
                mine = _own_scope(name, own)
                by_name[name] = (scope, False) if mine is None else (mine, True)
        if by_name[name] is None:
            continue
        scope, unscoped = by_name[name]
        if unscoped:
            if not scope and forms:  # a state: the form of the nearest scoped operation
                at = bisect.bisect_left(starts, s)
                scope = "ssm/" + min(forms[max(at - 1, 0):at + 1], key=lambda f: abs(f[0] - s))[1]
            scope = f"{scope or 'ssm'}/unscoped"
            if seconds is not None:
                seconds[scope] = seconds.get(scope, 0.0) + (e - s) / 1e9
        out.append((s, e, scope, name))
    return out


def read(run, params, env):
    trace, peaks, slice_ = env.get("trace"), env.get("peaks"), run.get("trace_slice")
    config = env["config"]
    if peaks is None or slice_ is None or slice_.began is None or "ssm_state_size" not in config \
            or not host_phases.on_chip(env):
        return None
    _, scopes = host_phases.of(run, env)
    rx = re.compile(params["pattern"])
    took, unscoped = 0, {}
    for ops in trace.devices.values():
        took += sum(e - s for s, e, scope, _ in attributed(ops, scopes, config, unscoped)
                    if rx.search(scope))
    took /= 1e9
    if not took:
        return None
    lo, hi = slice_.began * 1e6, slice_.ended * 1e6
    rows = [s["args"] for s in run.get("spans") or []
            if s["name"] == SPAN[params["kind"]] and s.get("cat") == "inference"
            and "ssm_tokens" in (s.get("args") or {}) and lo <= s["ts_us"] < hi]
    if not rows:
        return None
    pattern = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    widths = (config["mamba_num_heads"], config["mamba_head_dim"], config["n_groups"],
              config["ssm_state_size"], DTYPE_BYTES[config.get("torch_dtype", "bfloat16")])
    least = 0.0
    for args in rows:  # a span's counts are over its steps and the model's Mamba-2 blocks
        steps = int(args.get("steps", 1)) * pattern.count("M")
        work = ssm_work(args["ssm_tokens"] / steps, args["ssm_segments"] / steps, *widths)
        least += steps * opcount.roofline_seconds(*work, peaks)[0]
    mine = sum(v for k, v in unscoped.items() if rx.search(k))
    env["log"](f"ssm/{params['kind']}: {len(rows)} spans of the slice, {took:.3f} s in the scope "
               f"({mine:.3f} s of it the compiler's own operations on a state, attributed by "
               f"their result type) against {least:.3f} s at the roofline")
    return 100.0 * least / took
