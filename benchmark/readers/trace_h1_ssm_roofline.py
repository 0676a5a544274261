"""``trace_ssm_roofline`` for the Falcon-H1 family, whose configuration names
its Mamba-2 widths otherwise (``mamba_n_heads``, ``mamba_d_head``,
``mamba_n_groups``, ``mamba_d_state``, ``mamba_d_conv``) and has a Mamba-2
mixer in EVERY layer: the same time (the operations under ``ssm/scan`` or
``ssm/step``, the compiler's own operations on a state among them), the same
least (``trace_ssm_roofline.ssm_work`` of the dispatch spans' ``ssm_tokens``
and ``ssm_segments``, unchanged), at this family's widths.

:func:`hybrid_keys` is the mapping; :func:`attributed` is
``trace_ssm_roofline.attributed`` on the mapped configuration plus one more
kind of the compiler's own array: a PIECE of a state, ``f32[.., heads,
head_dim, k]`` with ``k`` a divisor of the state's width (at 256 columns the
compiler re-lays the pool in two halves of 128 around a ``put`` program's
update), given the form of the scoped operation nearest in time as a whole
state is.

A configuration without ``mamba_d_state``, spans without ``ssm_tokens`` or a
trace without the scope give nothing to read."""

import bisect
import re

from benchmark import host_phases, opcount
from benchmark.readers import trace_ssm_roofline as accepted


def hybrid_keys(config):
    """``config`` with the names ``trace_ssm_roofline`` reads beside its own;
    None for a configuration that is not this family's."""
    if "mamba_d_state" not in config:
        return None
    return dict(config, mamba_num_heads=config["mamba_n_heads"],
                mamba_head_dim=config["mamba_d_head"], n_groups=config["mamba_n_groups"],
                ssm_state_size=config["mamba_d_state"], conv_kernel=config["mamba_d_conv"],
                hybrid_override_pattern="M" * config["num_hidden_layers"])


def attributed(ops, scopes, config, seconds=None):
    """``trace_ssm_roofline.attributed`` (``config``: :func:`hybrid_keys`'s),
    and a piece of a state given a scope as a whole one is."""
    heads, head_dim, state = (config["mamba_num_heads"], config["mamba_head_dim"],
                              config["ssm_state_size"])
    rows = accepted.attributed(ops, scopes, config, seconds)
    forms = sorted((s, m.group(2)) for s, _, scope, _ in rows
                   for m in [accepted._FORM.search(scope)] if m and "unscoped" not in scope)
    starts = [s for s, _ in forms]
    piece = {}  # by name
    out = []
    for s, e, scope, name in rows:
        if not host_phases.scope_parts(scope):
            if name not in piece:
                m = accepted._RESULT.search(name)
                dims = tuple(int(d) for d in m.group(2).split(",") if d) if m else ()
                piece[name] = bool(m) and m.group(1) == "f32" and len(dims) >= 3 \
                    and dims[-3:-1] == (heads, head_dim) and 0 < dims[-1] < state \
                    and state % dims[-1] == 0
            if piece[name] and forms:
                at = bisect.bisect_left(starts, s)
                form = min(forms[max(at - 1, 0):at + 1], key=lambda f: abs(f[0] - s))[1]
                scope = f"ssm/{form}/unscoped"
                if seconds is not None:
                    seconds[scope] = seconds.get(scope, 0.0) + (e - s) / 1e9
        out.append((s, e, scope, name))
    return out


def read(run, params, env):
    trace, peaks, slice_ = env.get("trace"), env.get("peaks"), run.get("trace_slice")
    config = hybrid_keys(env["config"])
    if peaks is None or slice_ is None or slice_.began is None or config is None \
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
            if s["name"] == accepted.SPAN[params["kind"]] and s.get("cat") == "inference"
            and "ssm_tokens" in (s.get("args") or {}) and lo <= s["ts_us"] < hi]
    if not rows:
        return None
    widths = (config["mamba_num_heads"], config["mamba_head_dim"], config["n_groups"],
              config["ssm_state_size"],
              accepted.DTYPE_BYTES[config.get("torch_dtype", "bfloat16")])
    least = 0.0
    nbytes = 0
    for args in rows:  # a span's counts are over its steps and the model's layers
        steps = int(args.get("steps", 1)) * config["num_hidden_layers"]
        work = accepted.ssm_work(args["ssm_tokens"] / steps, args["ssm_segments"] / steps,
                                 *widths)
        least += steps * opcount.roofline_seconds(*work, peaks)[0]
        nbytes += steps * work[1]
    mine = sum(v for k, v in unscoped.items() if rx.search(k))
    env["log"](f"ssm/{params['kind']} (falcon_h1): {len(rows)} spans of the slice, {took:.3f} s "
               f"in the scope ({mine:.3f} s of it the compiler's own operations on a state) "
               f"against {least:.3f} s at the roofline: {nbytes / took / 1e9:.0f} GB/s of the "
               f"work's own bytes")
    return 100.0 * least / took
