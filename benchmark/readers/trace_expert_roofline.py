"""The routed experts' GEMMs as a share of their roofline, over the traced
slice, on the path ``params.moe_path`` names (``grouped`` / ``capacity``).

The time they took: the summed durations of the trace events whose name matches
``params.pattern`` (the grouped kernel by its name; the capacity path's two
fusions by the type they produce, ``[experts, slots, 2 x intermediate | hidden]``:
inside a ``decode_loop``'s scan an operation's text names no parameter, and the
``wo`` fusion carries the scope of its root, ``moe/combine``, so neither the
parameter nor the scope finds both. The ``[experts, slots, hidden]`` dispatch
einsum produces the ``wo`` fusion's type and is counted with it: under 1 % of
the time, and it lowers the reading).

The least they could take: ``expert_ffn`` of what the program's spans say was
routed. A span that carries ``moe_banks`` carries ``moe_assignments`` and
``moe_path`` of the same step(s), and a step is in one such span: the
``sched.fetch`` that fetched a grouped ``put`` step (its count of banks touched
is the device's to say and comes out with the ids), the dispatch span
(``inference.put`` / ``inference.decode_loop``) otherwise — a grouped chunk's
``moe_banks`` is what its steps' routing touched, summed; on the capacity path
it is every bank, every layer, every step, which is what that path streams. (In
the two Mixtral cells that list ``moe_capacity_roofline`` the static count is
also the true one: 32 assignments a layer in longgen and 512 in rag, over 8
experts, leave no bank without a row. A cell of ~5 sequences a step would be
overpriced by it.) A span is ``steps`` x expert layers layer-steps, each priced at
the span's mean banks and assignments a layer-step; banks are clamped to
min(experts, assignments) a layer-step, so a program cannot report itself past
the roofline. Widths and the stored type are the configuration file's, never the
program's. A span that reaches over the slice's edge counts by the part of it
inside: spans are moved onto the trace's clock through ``bench.clock_sync``, the
slice's window onto the host's timeline as ``host_phases.aligned_chip`` moves
the device's events. What is left at an edge is the call's launch, a few
milliseconds of a 4 s slice."""

import re

from benchmark import host_phases, opcount, spans, trace_reduce
from benchmark.readers import trace_chunk_idle

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
CARRIERS = {("fetch", "sched"), ("put", "inference"), ("decode_loop", "inference")}


def expert_ffn(assignments, banks, hidden, intermediate, gated=True, dtype_bytes=2):
    """One expert layer's routed feed-forward for one step: ``assignments``
    rows (token x chosen expert), each through its expert's two matrices, three
    if ``gated`` (gate and up, then down); ``banks`` experts' matrices read
    once, the rows read and written once."""
    matrices = 3 if gated else 2
    flops = 2 * assignments * hidden * intermediate * matrices
    nbytes = banks * hidden * intermediate * matrices * dtype_bytes
    nbytes += 2 * assignments * hidden * dtype_bytes
    return flops, nbytes


def widths(config):
    """``(experts, hidden, intermediate, expert layers, dtype bytes)`` of a
    configuration file."""
    experts = config.get("num_experts") or config["num_local_experts"]
    intermediate = config.get("moe_intermediate_size") or config["intermediate_size"]
    layers = config["num_hidden_layers"] - int(config.get("num_dense_layers") or 0)
    return (experts, config["hidden_size"], intermediate, layers,
            DTYPE_BYTES[config.get("torch_dtype", "bfloat16")])


def carriers(span_rows, moe_path):
    """``[(start_us, end_us, layer-steps / expert layer, banks, assignments)]``
    of the spans that carry ``moe_banks`` on ``moe_path``."""
    out = []
    for s in span_rows:
        args = s.get("args") or {}
        if ((s["name"], s.get("cat")) in CARRIERS and args.get("moe_path") == moe_path
                and "moe_banks" in args and args.get("moe_assignments")):
            out.append((s["ts_us"], s["ts_us"] + s["dur_us"], int(args.get("steps", 1)),
                        args["moe_banks"], args["moe_assignments"]))
    return out


def least_seconds(rows, lo, hi, config, peaks, gated=True):
    """Over ``rows`` (``carriers``' form, times in any one unit) and the
    window ``[lo, hi]``: ``(least seconds, weighted steps, banks, layer-steps,
    bytes)``, each span counted by the share of it inside the window."""
    experts, hidden, intermediate, layers, dtype_bytes = widths(config)
    least = steps = banks_sum = layer_steps = nbytes_sum = 0.0
    for start, end, k, banks, assignments in rows:
        inside = trace_reduce.overlap(start, end, lo, hi)
        share = inside / (end - start) if end > start else float(lo <= start < hi)
        if not share:
            continue
        a = assignments / (k * layers)
        b = min(banks / (k * layers), experts, a)
        flops, nbytes = expert_ffn(a, b, hidden, intermediate, gated, dtype_bytes)
        n = share * k * layers
        least += n * opcount.roofline_seconds(flops, nbytes, peaks)[0]
        steps += share * k
        banks_sum += n * b
        layer_steps += n
        nbytes_sum += n * nbytes
    return least, steps, banks_sum, layer_steps, nbytes_sum


def read(run, params, env):
    trace, peaks, slice_ = env.get("trace"), env.get("peaks"), run.get("trace_slice")
    if peaks is None or slice_ is None or not host_phases.on_chip(env):
        return None
    sync = [s for s, _, name, _ in trace.host if name == spans.SYNC_EVENT]
    rows = carriers(run.get("spans") or [], params["moe_path"])
    if not rows or not sync or slice_.sync_clock is None:
        return None
    rx = re.compile(params["pattern"])
    took = sum(e - s for ops in trace.devices.values() for s, e, n in ops if rx.search(n)) / 1e9
    if not took:
        return None
    # span clock (microseconds) -> trace clock (nanoseconds)
    offset_ns = sync[0] - slice_.sync_clock * 1e9
    _, lo, hi = trace_chunk_idle.aligned_chip(run, env)
    rows = [(start * 1e3 + offset_ns, end * 1e3 + offset_ns, k, banks, assignments)
            for start, end, k, banks, assignments in rows]
    least, steps, banks, layer_steps, nbytes = least_seconds(
        rows, lo, hi, env["config"], peaks, params.get("gated", True))
    if not layer_steps:
        return None
    env["log"](f"expert GEMMs on the {params['moe_path']} path: {steps:.1f} steps of the slice, "
               f"{banks / layer_steps:.2f} banks a layer-step, {took:.3f} s in "
               f"/{params['pattern']}/ events against {least:.3f} s at the roofline: "
               f"{nbytes / took / 1e9:.1f} GB/s reached")
    return 100.0 * least / took
