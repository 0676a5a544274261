"""A delta-rule scope's share of its roofline, over the traced slice: the two
forms of the gated delta rule (``deepspeed_tpu/inference/v2/modules/kda.py``),
under the scopes ``kda/scan`` (a ``put`` step: the chunked form's visits, and
the recurrence's kernel for the segments of one row) and ``kda/step`` (the
recurrence, a ``decode_loop`` step). The count is kept here, and is of the
WORK, whatever implements the scope.

The time it took: the summed durations of the device operations whose scope
path (``host_phases.scopes_by_name``) matches ``params.pattern``, and of the
operations under NO scope of the program's whose result is shaped like a
state (``f32[.., heads, d_k, d_v]``: what a compiler adds around a state, a
copy or a slice of the pool, carries no scope), each given the form of the
scoped delta-rule operation nearest to it in time: time cannot leave the
metric by losing its scope.

The least it could take: for every dispatch span (``inference.put`` for
``params.kind`` ``chunk``, ``inference.decode_loop`` for ``step``) that starts
inside the slice, :func:`kda_work` of the span's ``kda_rows`` (rows through a
delta-rule layer, over the span's steps and layers) and of the states it had
to visit: a row's own on a ``decode_loop`` step (``kda_rows``); on a ``put``
step one a visit of the chunked form (``kda_chunk_visits``) and one a segment
of one row (``kda_rows_in_place``).

A configuration without ``linear_attn_config``, spans without ``kda_rows`` (a
program that has none) or a trace without the scope give nothing to read."""

import bisect
import re

from benchmark import host_phases, opcount, trace_reduce

SPAN = {"chunk": "put", "step": "decode_loop"}
FORM = {"chunk": "scan", "step": "step"}
_RESULT = re.compile(r"= \(?f32\[([\d,]*)\]")
_FORMS = re.compile(r"(^|/)kda/(scan|step)(/|$)")


def kda_work(rows, states, heads, dk, dv):
    """``(flop, bytes)`` of ``rows`` rows through ONE delta-rule layer that
    read and write ``states`` states between them.

    - bytes: a state ``[heads, d_k, d_v]`` float32 read once and written once a
      visit (4 MiB each way at 64 x 128 x 128: a decode row's own, a chunk's
      once for all its rows); a row's q, k and log-decay (d_k a head) and v (d_v
      a head) in float32 as the mixer hands them to the rule, its beta, and its
      float32 output;
    - flop: the recurrence's own, 7 a state element a row (the decay, the
      reading with k and the correction two each, the reading with q two): what
      the chunked form spends more (the triangular system, the pairwise
      decays), it spends by choice."""
    elements = heads * dk * dv
    flops = 7 * elements * rows
    nbytes = 2 * 4 * elements * states
    nbytes += rows * 4 * (heads * (3 * dk + dv) + heads + heads * dv)
    return flops, nbytes


def _seconds(ops, scopes, state, form):
    """Seconds of one chip's operations under ``kda/<form>``, the unscoped
    operations that make a state among them (the module's second paragraph)."""
    forms = sorted((s, m.group(2)) for s, _, name in ops
                   for m in [_FORMS.search(scopes.get(name, ""))] if m)
    starts = [s for s, _ in forms]
    kind, took, added = {}, 0, 0  # a slice has ~1e5 events of ~1e3 names
    for s, e, name in ops:
        if name not in kind:
            scope = scopes.get(name, "")
            m = _FORMS.search(scope)
            made = _RESULT.search(name)
            if trace_reduce.CONTAINERS.match(name):
                kind[name] = None
            elif m:
                kind[name] = m.group(2)
            elif not host_phases.scope_parts(scope) and made and tuple(
                    int(d) for d in made.group(1).split(",") if d)[-3:] == state:
                kind[name] = "nearest"
            else:
                kind[name] = None
        mine = kind[name]
        if mine == "nearest" and forms:
            at = bisect.bisect_left(starts, s)
            mine = min(forms[max(at - 1, 0):at + 1], key=lambda f: abs(f[0] - s))[1]
            added += (e - s) if mine == form else 0
        if mine == form:
            took += e - s
    return took / 1e9, added / 1e9


def read(run, params, env):
    trace, peaks, slice_ = env.get("trace"), env.get("peaks"), run.get("trace_slice")
    linear = env["config"].get("linear_attn_config")
    if peaks is None or slice_ is None or slice_.began is None or not linear \
            or not host_phases.on_chip(env):
        return None
    heads, dk = linear["num_heads"], linear["head_dim"]
    _, scopes = host_phases.of(run, env)
    took = added = 0.0
    for ops in trace.devices.values():
        t, a = _seconds(ops, scopes, (heads, dk, dk), FORM[params["kind"]])
        took, added = took + t, added + a
    if not took:
        return None
    lo, hi = slice_.began * 1e6, slice_.ended * 1e6
    rows = [s["args"] for s in run.get("spans") or []
            if s["name"] == SPAN[params["kind"]] and s.get("cat") == "inference"
            and "kda_rows" in (s.get("args") or {}) and lo <= s["ts_us"] < hi]
    if not rows:
        return None
    n_rows = sum(a["kda_rows"] for a in rows)
    if params["kind"] == "step":
        states = n_rows
    else:
        states = sum(a["kda_chunk_visits"] + a["kda_rows_in_place"] for a in rows)
    least = opcount.roofline_seconds(*kda_work(n_rows, states, heads, dk, dk), peaks)[0]
    env["log"](f"kda/{FORM[params['kind']]}: {len(rows)} spans of the slice, {n_rows} rows a "
               f"layer-step over {states} states, {took:.3f} s in the scope ({added:.3f} s of it "
               f"unscoped operations on a state) against {least:.3f} s at the roofline")
    return 100.0 * least / took
