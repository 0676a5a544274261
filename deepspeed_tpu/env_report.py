"""``ds_report`` analog: environment / compatibility report.

Reference: ``deepspeed/env_report.py:182`` — prints the op-compat matrix,
torch/cuda versions and install paths. The TPU report covers what matters
here: JAX backend + devices, default mesh axes, library versions, and which
native/pallas subsystems are usable on this backend.
"""

import importlib
import sys


def _version(mod):
    try:
        return importlib.import_module(mod).__version__
    except Exception:
        return "not installed"


GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[93m[NO]\033[0m"


def metrics_report(url):
    """``dstpu_report --metrics-url <url>``: scrape a running engine's
    telemetry endpoint and pretty-print it (plus the /healthz verdict)."""
    import json
    import urllib.request

    from deepspeed_tpu.telemetry import scrape_metrics

    base = url if url.startswith(("http://", "https://")) else "http://" + url
    base = base.rstrip("/")
    for suffix in ("/metrics", "/healthz", "/trace"):
        if base.endswith(suffix):
            base = base[:-len(suffix)]
            break
    try:
        with urllib.request.urlopen(base + "/healthz", timeout=5) as resp:
            health = json.loads(resp.read().decode()).get("status", "?")
            health_line = f"{GREEN_OK} ({health}, HTTP {resp.status})"
    except Exception as e:
        health_line = f"{RED_NO} ({e})"
    print("-" * 60)
    print(f"telemetry endpoint ..... {base}")
    print(f"healthz ................ {health_line}")
    print("-" * 60)
    try:
        families = scrape_metrics(base)
    except Exception as e:
        print(f"scrape failed: {e}")
        return 1
    for name in sorted(families):
        fam = families[name]
        header = f"{name} [{fam['type']}]"
        if fam["help"]:
            header += f" — {fam['help']}"
        print(header)
        for sample_name, labels, value in fam["samples"]:
            if sample_name.endswith("_bucket"):
                continue  # count/sum summarize; buckets are for the scraper
            print(f"  {sample_name + _fmt_labels(labels):<44} {value:g}")
        print()
    return 0


def _fmt_labels(labels):
    return "{" + ",".join(f"{k}={v}" for k, v in sorted(labels.items())) + "}" if labels else ""


def _load_trace_events(path):
    """Normalize either export format into one event-dict list: a Chrome trace
    (``traceEvents`` with ts/dur us) or a flight-recorder dump (``spans`` with
    ts_us/dur_us)."""
    import json

    with open(path) as f:
        doc = json.load(f)
    if "traceEvents" in doc:
        return [{"name": e["name"], "cat": e.get("cat", ""), "ts": e["ts"],
                 "dur": e.get("dur", 0), "args": e.get("args", {})}
                for e in doc["traceEvents"] if e.get("ph") == "X"]
    if "spans" in doc:  # flight-recorder dump
        return [{"name": s["name"], "cat": s.get("cat", ""), "ts": s["ts_us"],
                 "dur": s.get("dur_us", 0),
                 "args": {**s.get("args", {}),
                          **({"trace_id": s["trace_id"], "span_id": s.get("span_id"),
                              "parent_id": s.get("parent_id")}
                             if s.get("trace_id") is not None else {})}}
                for s in doc["spans"]]
    raise ValueError(f"{path}: neither a Chrome trace (traceEvents) nor a "
                     f"flight-recorder dump (spans)")


def trace_report(path):
    """``dstpu_report --trace <file>``: per-request timelines (queued/prefill/
    decode durations, recompiles encountered) from an exported Chrome trace or
    a flight-recorder dump."""
    try:
        events = _load_trace_events(path)
    except (OSError, ValueError, KeyError) as e:
        print(f"trace report failed: {e}")
        return 1

    ms = 1e-3  # event times are microseconds
    compiles = [e for e in events if e["name"] == "xla_compile"]
    by_trace = {}
    for e in events:
        trace_id = e["args"].get("trace_id")
        if trace_id is not None:
            by_trace.setdefault(trace_id, []).append(e)

    print("-" * 78)
    print(f"trace ................... {path}")
    print(f"events .................. {len(events)} "
          f"({len(by_trace)} request traces, {len(compiles)} XLA compiles)")
    print("-" * 78)
    if not by_trace:
        print("no request traces found (serve with telemetry enabled; the "
              "X-DSTPU-Trace-Id response header names each request's trace)")
        return 0

    def total(evs, name):
        return sum(e["dur"] for e in evs if e["name"] == name)

    # roots sorted by arrival so the report reads as an admission log
    roots = sorted((evs for evs in by_trace.values()),
                   key=lambda evs: min(e["ts"] for e in evs))
    for evs in roots:
        root = next((e for e in evs if e["name"] == "request"), None)
        head = root or min(evs, key=lambda e: e["ts"])
        args = head["args"]
        t0, t1 = head["ts"], head["ts"] + head["dur"]
        n_recompiles = sum(1 for c in compiles if t0 <= c["ts"] + c["dur"] and c["ts"] <= t1)
        decode_evs = [e for e in evs if e["name"] in ("decode", "decode_loop")]
        decode_toks = sum(e["args"].get("tokens", 0) for e in decode_evs)
        print(f"request uid={args.get('uid')} trace={args.get('trace_id')} "
              f"[{args.get('state', '?')}"
              f"{', ' + str(args.get('finish_reason')) if args.get('finish_reason') else ''}]")
        print(f"  prompt/generated ..... {args.get('prompt_tokens', '?')}t / "
              f"{args.get('generated', '?')}t")
        print(f"  total ................ {head['dur'] * ms:10.3f} ms")
        print(f"  queued ............... {total(evs, 'queued') * ms:10.3f} ms")
        n_prefill = sum(1 for e in evs if e["name"] == "prefill")
        print(f"  prefill .............. {total(evs, 'prefill') * ms:10.3f} ms "
              f"({n_prefill} chunks)")
        decode_total = total(evs, "decode") + total(evs, "decode_loop")
        print(f"  decode ............... {decode_total * ms:10.3f} ms "
              f"({len(decode_evs)} iterations, {decode_toks} tokens)")
        print(f"  recompiles overlapped  {n_recompiles}")
        print()
    return 0


def checkpoint_report(save_dir, keep_last_k=None):
    """``dstpu_report --checkpoint <dir>``: verify every tag's manifest CRCs
    and list good/torn/corrupt/reference status, plus which tags keep-last-K
    retention would keep (K from ``--keep-last-k``, else the newest manifest's
    recorded ``keep_last_k``). Returns 0 when every tag is good."""
    import os

    from deepspeed_tpu.runtime.checkpoint_engine.engine import (
        LATEST_FILE, PREEMPT_MARKER, list_tags, retention_plan,
        verify_checkpoint)

    save_dir = os.path.abspath(save_dir)
    tags = list_tags(save_dir)
    pointed = None
    latest_file = os.path.join(save_dir, LATEST_FILE)
    if os.path.isfile(latest_file):
        with open(latest_file) as f:
            pointed = f.read().strip()

    if keep_last_k is None:
        for entry in tags:  # newest first; the freshest save's config wins
            if entry["manifest"] is not None:
                keep_last_k = entry["manifest"].get("keep_last_k", 0)
                break
    keep, drop = retention_plan(save_dir, keep_last_k or 0)
    survivors = {e["tag"] for e in keep}

    print("-" * 78)
    print(f"checkpoint dir ......... {save_dir}")
    print(f"tags ................... {len(tags)} "
          f"(latest → {pointed or 'none'}, keep_last_k={keep_last_k or 0})")
    if os.path.isfile(os.path.join(save_dir, PREEMPT_MARKER)):
        import json
        with open(os.path.join(save_dir, PREEMPT_MARKER)) as f:
            marker = json.load(f)
        print(f"preemption marker ...... tag {marker.get('tag')} at step "
              f"{marker.get('global_steps')} "
              f"({marker.get('used_s')}s of {marker.get('grace_s')}s grace)")
    print("-" * 78)
    if not tags:
        print("no checkpoint tags found")
        return 1
    all_good = True
    for entry in tags:
        status, detail = verify_checkpoint(entry["path"])
        all_good &= status == "good"
        manifest = entry["manifest"] or {}
        step = manifest.get("global_steps", "?")
        n_files = len(manifest.get("files", {}))
        n_arrays = len(manifest.get("arrays") or {})
        flags = []
        if entry["tag"] == pointed:
            flags.append("latest")
        flags.append("kept" if entry["tag"] in survivors else "prunable")
        verdict = {"good": GREEN_OK, }.get(status, RED_NO)
        print(f"{entry['tag']:<28} {verdict} {status:<9} step={step:<8} "
              f"files={n_files:<4} arrays={n_arrays:<4} [{', '.join(flags)}]")
        if status != "good":
            print(f"{'':<28}   ↳ {detail}")
    print("-" * 78)
    print(f"verdict ................ "
          f"{GREEN_OK + ' all tags verified' if all_good else RED_NO + ' bad tags present (load falls back to the newest good one)'}")
    return 0 if all_good else 1


def gang_report(gang_dir):
    """``dstpu_report --gang <dir>``: render the elastic agent's gang state —
    per-rank liveness (heartbeat age/step/phase, pid, exit code), crash/hang
    history, current vs valid world sizes and the last shrink event. Returns
    0 when the gang is running/done with no recorded failures, 1 otherwise."""
    import os
    import time

    from deepspeed_tpu.elasticity.gang import read_gang_state, read_heartbeats

    gang_dir = os.path.abspath(gang_dir)
    state = read_gang_state(gang_dir)
    beats = read_heartbeats(gang_dir)
    print("-" * 78)
    print(f"gang dir ............... {gang_dir}")
    if state is None and not beats:
        print("no gang state or heartbeats found (is this a DSTPU_GANG_DIR?)")
        return 2
    state = state or {}
    age = time.time() - state["updated_unix"] if "updated_unix" in state else None
    print(f"phase .................. {state.get('phase', '?')}"
          f"{f'  (state written {age:.1f}s ago)' if age is not None else ''}")
    print(f"world .................. {state.get('world', '?')} of initial "
          f"{state.get('initial_world', '?')} "
          f"(valid: {state.get('valid_worlds', '?')})")
    print(f"restarts ............... {state.get('restart_count', '?')}"
          f"/{state.get('max_restarts', '?')}  crashes in window: "
          f"{state.get('crashes_in_window', '?')}/{state.get('max_crashes', '?')} "
          f"(window {state.get('crash_window_s', '?')}s)")
    hang = state.get("hang_timeout_s")
    print(f"hang watchdog .......... "
          f"{f'{hang}s heartbeat staleness' if hang else 'off'}")
    shrink = state.get("last_shrink")
    if shrink:
        print(f"last shrink ............ world {shrink.get('from')} → "
              f"{shrink.get('to')} after {shrink.get('crashes')} crash(es) "
              f"(life {shrink.get('life')})")
    print("-" * 78)
    ranks = state.get("ranks") or {str(r): {"heartbeat": hb}
                                   for r, hb in beats.items()}
    failures = 0
    for rank in sorted(ranks, key=int):
        doc = ranks[rank] or {}
        hb = beats.get(int(rank)) or doc.get("heartbeat")
        alive = doc.get("alive")
        rc = doc.get("exit_code")
        if alive:
            live = GREEN_OK + " alive"
        elif alive is None:
            live = "?  unknown"
        elif rc == 143:
            # the agent's preemption contract: 143 = TrainingPreempted with
            # the final checkpoint committed — a clean drain, not a failure
            live = GREEN_OK + " exit=143 (preempted)"
        else:
            live = (GREEN_OK if rc == 0 else RED_NO) + f" exit={rc}"
        if rc not in (None, 0, 143):
            failures += 1
        if hb:
            beat = (f"beat {hb.get('age_s', 0):.1f}s ago  "
                    f"step={hb.get('step')}  phase={hb.get('phase')}")
        else:
            beat = "no heartbeat this life"
        print(f"rank {rank:<4} {live:<18} {beat}")
    events = state.get("events") or []
    if events:
        print("-" * 78)
        for ev in events[-10:]:
            print(f"life {ev.get('life'):<3} world={ev.get('world'):<3} "
                  f"{ev.get('kind'):<8} {ev.get('detail') or ''}")
    print("-" * 78)
    bad = failures or any(ev.get("kind") in ("crash", "hang") for ev in events) \
        or state.get("phase") == "failed"
    print(f"verdict ................ "
          f"{RED_NO + ' failures recorded' if bad else GREEN_OK + ' gang healthy'}")
    return 1 if bad else 0


_SPARK_CHARS = "▁▂▃▄▅▆▇█"


def _sparkline(values, width=40):
    """Render a value list as a fixed-height unicode sparkline (newest-last,
    truncated to ``width`` points, scaled to the visible min..max)."""
    vals = [v for v in values if v is not None]
    if not vals:
        return ""
    if len(vals) > width:
        vals = vals[-width:]
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK_CHARS[0] * len(vals)
    top = len(_SPARK_CHARS) - 1
    return "".join(_SPARK_CHARS[min(top, int((v - lo) / (hi - lo) * len(_SPARK_CHARS)))]
                   for v in vals)


def _load_timeseries_doc(src):
    """A ``--timeseries`` operand is either a saved JSON file or a live
    router/engine address (``/v1/fleet/timeseries`` is fetched)."""
    import json
    import os
    import urllib.request

    if os.path.isfile(src):
        with open(src) as f:
            return json.load(f)
    base = src if src.startswith(("http://", "https://")) else "http://" + src
    base = base.rstrip("/")
    if not base.endswith("/v1/fleet/timeseries"):
        base += "/v1/fleet/timeseries"
    with urllib.request.urlopen(base, timeout=5) as resp:
        return json.loads(resp.read().decode())


def _render_timeseries_snapshot(label, snap):
    series = (snap or {}).get("series") or {}
    interval = snap.get("interval_s", 0) or 0
    retention = snap.get("retention_points", 0) or 0
    print(f"{label}  interval={interval:g}s  retention={retention} pts "
          f"(~{interval * retention:g}s)  window={snap.get('window_s', '?')}s  "
          f"ticks={snap.get('ticks', '?')}")
    if not series:
        print("  (no series sampled yet)")
        return

    def fmt_ms(v):
        return f"{v * 1e3:.1f}ms" if v is not None else "—"

    def fmt_rate(v):
        return f"{v:.2f}/s" if v is not None else "—"

    for name in sorted(series):
        fam = series[name]
        pts = fam.get("points") or []
        if fam.get("kind") == "histogram":
            # cumulative counts -> per-interval deltas for the sparkline
            counts = [p[1] for p in pts]
            deltas = [max(0, b - a) for a, b in zip(counts, counts[1:])]
            spark = _sparkline(deltas or counts)
            tail = (f"p50={fmt_ms(fam.get('p50'))} p95={fmt_ms(fam.get('p95'))} "
                    f"p99={fmt_ms(fam.get('p99'))} rate={fmt_rate(fam.get('rate'))}")
        elif fam.get("kind") == "counter":
            values = [p[1] for p in pts]
            deltas = [max(0.0, b - a) for a, b in zip(values, values[1:])]
            spark = _sparkline(deltas or values)
            last = values[-1] if values else None
            tail = (f"total={last:g} " if last is not None else "") \
                + f"rate={fmt_rate(fam.get('rate'))}"
        else:  # gauge
            values = [p[1] for p in pts]
            spark = _sparkline(values)
            tail = f"last={values[-1]:g}" if values else ""
        print(f"  {name:<34} {spark:<40} {tail}")


def timeseries_report(src):
    """``dstpu_report --timeseries <file | host:port>``: sparkline tables from
    a ``/v1/fleet/timeseries`` export (router + per-replica sections), a bare
    store snapshot, or a ``/v1/stats`` doc carrying a ``timeseries`` block."""
    try:
        doc = _load_timeseries_doc(src)
    except Exception as e:
        print(f"cannot load time series from {src}: {e}")
        return 2
    if isinstance(doc, dict) and "series" in doc:
        sections = [("snapshot", doc)]
    elif isinstance(doc, dict) and ("router" in doc or "replicas" in doc):
        sections = []
        if doc.get("router"):
            sections.append(("router", doc["router"]))
        for rid, snap in sorted((doc.get("replicas") or {}).items()):
            if snap:
                sections.append((f"replica {rid}", snap))
    elif isinstance(doc, dict) and isinstance(doc.get("timeseries"), dict):
        sections = [("engine", doc["timeseries"])]
    else:
        print(f"{src}: not a time-series doc (expected 'series', "
              f"'router'/'replicas', or a stats doc with 'timeseries')")
        return 2
    print("-" * 78)
    print(f"time series ............ {src}")
    print("-" * 78)
    if not sections:
        print("no time-series data (enable telemetry.timeseries on the "
              "replicas and the router)")
        return 0
    for label, snap in sections:
        _render_timeseries_snapshot(label, snap)
        print()
    return 0


def _load_kv_doc(src):
    """A ``--kv`` operand is either a saved JSON stats doc or a live
    address: ``/v1/fleet/stats`` is tried first (router form), then
    ``/v1/stats`` (single-replica form)."""
    import json
    import os
    import urllib.request

    if os.path.isfile(src):
        with open(src) as f:
            return json.load(f)
    base = src if src.startswith(("http://", "https://")) else "http://" + src
    base = base.rstrip("/")
    if base.endswith("/v1/fleet/stats") or base.endswith("/v1/stats"):
        urls = [base]
    else:
        urls = [base + "/v1/fleet/stats", base + "/v1/stats"]
    last = None
    for url in urls:
        try:
            with urllib.request.urlopen(url, timeout=5) as resp:
                return json.loads(resp.read().decode())
        except Exception as e:  # try the next form; re-raise the last
            last = e
    raise last


def _render_kv_tiers(tiers):
    dev_used = tiers.get("device_blocks_used", 0)
    dev_total = tiers.get("device_blocks_total", 0)
    budget = tiers.get("host_bytes_budget")
    print("tier occupancy:")
    print(f"  device ............... {dev_used}/{dev_total} blocks")
    print(f"  host ................. {tiers.get('host_entries', 0)} entries, "
          f"{tiers.get('host_blocks', 0)} blocks, "
          f"{tiers.get('host_bytes', 0)} bytes"
          + (f" (budget {budget})" if budget else " (budget unbounded)"))
    print(f"  disk ................. {tiers.get('disk_entries', 0)} entries, "
          f"{tiers.get('disk_blocks', 0)} blocks, "
          f"{tiers.get('disk_bytes', 0)} bytes")
    print("ladder counters:")
    print(f"  pressure demotions ... {tiers.get('pressure_demotions', 0)} "
          f"(demote-before-shed passes, device blocks)")
    print(f"  host->disk commits ... {tiers.get('demotions', 0)}")
    print(f"  demote races ......... {tiers.get('demote_races', 0)} "
          f"(reader won mid-spill; reclaimed to host)")
    print(f"  writeback ............ {tiers.get('writeback_pending', 0)} "
          f"pending, {tiers.get('writeback_joins', 0)} joined reads")
    print(f"  reads ................ host {tiers.get('reads_host', 0)} / "
          f"disk {tiers.get('reads_disk', 0)}")
    if "trie_demotions" in tiers:
        print(f"  prefix trie .......... {tiers.get('trie_offloaded_nodes', 0)} "
              f"offloaded nodes, {tiers.get('trie_demotions', 0)} demotions, "
              f"{tiers.get('trie_promotions', 0)} promotions")


def _render_park(park):
    print(f"park store ............. {park.get('sessions', 0)} sessions, "
          f"{park.get('bytes', 0)} bytes (caps: "
          f"{park.get('max_sessions', '?')} sessions / "
          f"{park.get('max_bytes', '?')} bytes, ttl {park.get('ttl_s', '?')}s)")
    print(f"  parks ................ {park.get('parks', 0)}")
    print(f"  rehydrate hits ....... {park.get('rehydrate_hits', 0)}")
    print(f"  rehydrate misses ..... {park.get('rehydrate_misses', 0)} "
          f"(expired or diverged)")
    print(f"  corrupt rejects ...... {park.get('corrupt_rejects', 0)}")
    print(f"  evictions ............ {park.get('evictions', 0)}")
    inventory = park.get("inventory") or []
    if inventory:
        print("parked sessions:")
        print(f"  {'session':<24} {'tokens':>7} {'bytes':>10} "
              f"{'tier':<7} {'parked_by':<12} {'age_s':>8}")
        for row in inventory:
            print(f"  {str(row.get('session', '?')):<24} "
                  f"{row.get('tokens', 0):>7} {row.get('bytes', 0):>10} "
                  f"{str(row.get('tier_source') or '-'):<7} "
                  f"{str(row.get('parked_by') or '-'):<12} "
                  f"{row.get('age_s', 0):>8}")


def kv_report(src):
    """``dstpu_report --kv <stats.json | host:port>``: render the tiered KV
    memory surface — per-tier occupancy and the demotion/promotion counters
    from a serving ``/v1/stats`` doc (its ``kv_tiers`` block), and the
    router's parked-session inventory from a ``/v1/fleet/stats`` doc."""
    try:
        doc = _load_kv_doc(src)
    except Exception as e:
        print(f"cannot load KV stats from {src}: {e}")
        return 2
    if not isinstance(doc, dict):
        print(f"{src}: not a stats doc")
        return 2
    print("-" * 78)
    print(f"tiered KV memory ....... {src}")
    print("-" * 78)
    rendered = False
    if "kv_tiers" in doc:
        rendered = True
        tiers = doc.get("kv_tiers")
        if isinstance(tiers, dict):
            _render_kv_tiers(tiers)
        else:
            print("kv tiers ............... disabled "
                  "(KVTierConfig.enabled=false)")
    router = doc.get("router")
    if isinstance(router, dict):
        rendered = True
        park = router.get("park")
        if isinstance(park, dict):
            _render_park(park)
        else:
            print("park store ............. disabled "
                  "(ParkConfig.enabled=false)")
    if not rendered:
        print(f"{src}: no kv_tiers or router.park block (is this a /v1/stats "
              f"or /v1/fleet/stats doc?)")
        return 2
    return 0


def overload_report(path):
    """``dstpu_report --overload <loadgen-json>``: render the goodput-vs-
    offered-load table from ``bin/dstpu_loadgen --overload --json`` and flag
    the knee point — the first ramp step whose goodput drops below 90% of
    the measured single-replica capacity. Returns 0 when the doc parses and
    has at least one step (a knee is expected on a real overload ramp, not a
    failure)."""
    import json
    import os

    path = os.path.abspath(path)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"cannot read overload report {path}: {e}")
        return 2
    steps = doc.get("steps") or []
    capacity = doc.get("capacity_req_s")
    if not steps or not capacity:
        print(f"{path} has no ramp steps / capacity "
              f"(is this a loadgen --overload --json file?)")
        return 2
    knee_floor = 0.9 * capacity
    # only saturated steps can knee: below capacity, goodput is bounded by
    # the OFFERED rate, not by overload collapse — a 0.5x step can never
    # reach 90% of capacity and must not be flagged
    knee = next((s for s in steps
                 if s.get("offered_req_s", 0.0) >= knee_floor
                 and s.get("goodput_req_s", 0.0) < knee_floor), None)
    print("-" * 78)
    print(f"overload ramp .......... {path}")
    print(f"capacity ............... {capacity:.2f} req/s "
          f"(deadline {doc.get('deadline_s', 0):.2f}s, interactive_frac "
          f"{doc.get('interactive_frac', '?')}, "
          f"{doc.get('requests_per_step', '?')} requests/step)")
    print(f"knee floor ............. {knee_floor:.2f} req/s (90% of capacity)")
    has_slo = any(isinstance(s.get("slo"), dict) for s in steps)
    if has_slo:
        spec = doc.get("slo_spec") or {}
        print(f"slo .................... {spec.get('metric', 'ttft')} <= "
              f"{spec.get('target_s', '?')}s for {spec.get('target_ratio', '?')} "
              f"of requests (burn alert at {spec.get('burn_threshold', '?')}x)")
    print("-" * 78)
    print(f"{'offered':>8} {'req/s':>8} {'goodput':>8} {'ok':>5} "
          f"{'on-ddl':>6} {'shed':>5} {'degr':>5} {'hedged':>6} "
          f"{'ttft_i_p99':>11} {'ttft_b_p99':>11}"
          + (f" {'burn':>7}" if has_slo else ""))

    def _p99_ms(step, cls):
        p99 = ((step.get("ttft") or {}).get(cls) or {}).get("p99_s")
        return f"{p99 * 1e3:>9.1f}ms" if p99 is not None else f"{'—':>11}"

    def _burn(step):
        slo = step.get("slo") or {}
        burn = slo.get("burn_rate")
        if burn is None:
            return f" {'—':>7}"
        return f" {burn:>6.2f}{'!' if slo.get('breached') else ' '}"

    for step in steps:
        marker = "  <- knee" if step is knee else ""
        print(f"{step.get('offered_x', 0):>7.1f}x "
              f"{step.get('offered_req_s', 0):>8.2f} "
              f"{step.get('goodput_req_s', 0):>8.2f} {step.get('ok', 0):>5} "
              f"{step.get('on_deadline', 0):>6} {step.get('shed', 0):>5} "
              f"{step.get('degraded', 0):>5} {step.get('hedged', 0):>6} "
              f"{_p99_ms(step, 'interactive')} {_p99_ms(step, 'batch')}"
              + (_burn(step) if has_slo else "")
              + marker)
    print("-" * 78)
    if has_slo:
        first = doc.get("slo_first_breach_step")
        if first is None:
            print(f"slo verdict ............ {GREEN_OK} no step breached the "
                  f"SLO burn threshold")
        else:
            breach = steps[first] if 0 <= first < len(steps) else {}
            print(f"slo verdict ............ first breach at step {first} "
                  f"({breach.get('offered_x', '?')}x offered, burn "
                  f"{(breach.get('slo') or {}).get('burn_rate', float('nan')):.2f})")
    if knee is None:
        print(f"verdict ................ {GREEN_OK} goodput held >= 90% of "
              f"capacity through {steps[-1].get('offered_x', 0):.1f}x offered "
              f"load (no knee)")
    else:
        print(f"verdict ................ knee at "
              f"{knee.get('offered_x', 0):.1f}x offered load: goodput "
              f"{knee.get('goodput_req_s', 0):.2f} req/s < "
              f"{knee_floor:.2f} req/s floor")
    return 0


def spec_report(path):
    """``dstpu_report --spec <loadgen-json>``: render the per-drafter
    speculative-decoding comparison table from ``bin/dstpu_loadgen
    --spec-demo --json`` — acceptance rate, tokens per decode dispatch, and
    ITL percentiles for each drafter family the run observed (prompt_lookup
    vs learned, or both under auto arbitration / --drafter pins). Returns 0
    when the doc parses and carries at least one drafter row."""
    import json
    import os

    path = os.path.abspath(path)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"cannot read speculative report {path}: {e}")
        return 2
    drafters = doc.get("drafters") or {}
    overall = doc.get("overall") or {}
    if not drafters:
        print(f"{path} has no per-drafter rows "
              f"(is this a loadgen --spec-demo --json file against a "
              f"speculation-enabled server?)")
        return 2
    wl = doc.get("workload") or {}
    print("-" * 78)
    print(f"speculative decoding ... {path}")
    demo = wl.get("spec_demo")
    if demo:
        print(f"workload ............... --spec-demo "
              f"{demo[0]}:{demo[1] if len(demo) > 1 else 1} "
              f"({wl.get('ok', '?')}/{wl.get('requests', '?')} ok"
              + (f", pinned --drafter {wl['drafter_pin']}"
                 if wl.get("drafter_pin") else "")
              + ")")
    drafted = overall.get("drafted", 0)
    print(f"overall ................ accept_rate="
          f"{overall.get('accepted', 0) / max(1, drafted):.2f} "
          f"({overall.get('accepted', 0)}/{drafted} drafts) "
          f"tokens_per_step={overall.get('tokens_per_step', 0):.2f}")
    print("-" * 78)
    print(f"{'drafter':<14} {'reqs':>5} {'accepted':>9} {'drafted':>8} "
          f"{'accept':>7} {'tok/step':>9} {'itl_p50':>9} {'itl_p99':>9}")

    def _ms(agg, pct):
        v = (agg.get("itl") or {}).get(pct, (agg.get("itl") or {}).get(str(pct)))
        return f"{v * 1e3:>7.1f}ms" if isinstance(v, (int, float)) \
            and v == v else f"{'—':>9}"

    best = max(drafters, key=lambda n: drafters[n].get("tokens_per_step", 0))
    for name in sorted(drafters):
        agg = drafters[name]
        marker = "  <- best" if name == best and len(drafters) > 1 else ""
        print(f"{name:<14} {agg.get('requests', 0):>5} "
              f"{agg.get('accepted', 0):>9} {agg.get('drafted', 0):>8} "
              f"{agg.get('accept_rate', 0):>7.2f} "
              f"{agg.get('tokens_per_step', 0):>9.2f} "
              f"{_ms(agg, 50)} {_ms(agg, 99)}" + marker)
    print("-" * 78)
    print(f"verdict ................ {GREEN_OK} best tokens/step: {best} "
          f"({drafters[best].get('tokens_per_step', 0):.2f})")
    return 0


def _load_usage_doc(src):
    """A ``--usage`` operand is either a saved JSON file (a ``/v1/usage`` /
    ``/v1/fleet/usage`` / ``/v1/stats`` doc, or a ``bin/dstpu_loadgen
    --tenants --json`` file) or a live address: ``/v1/usage`` is tried first
    (single replica; the ``perf`` join rides along from ``/v1/stats``), then
    the router's ``/v1/fleet/usage``."""
    import json
    import os
    import urllib.request

    if os.path.isfile(src):
        with open(src) as f:
            return json.load(f)
    base = src if src.startswith(("http://", "https://")) else "http://" + src
    base = base.rstrip("/")
    if base.endswith(("/v1/usage", "/v1/fleet/usage", "/v1/stats")):
        urls = [base]
    else:
        urls = [base + "/v1/usage", base + "/v1/fleet/usage"]
    last = None
    for url in urls:
        try:
            with urllib.request.urlopen(url, timeout=5) as resp:
                doc = json.loads(resp.read().decode())
        except Exception as e:
            last = e
            continue
        if url.endswith("/v1/usage") and "perf" not in doc:
            stats_url = url[: -len("/v1/usage")] + "/v1/stats"
            try:
                with urllib.request.urlopen(stats_url, timeout=5) as resp:
                    doc["perf"] = json.loads(resp.read().decode()).get("perf")
            except Exception:
                pass
        return doc
    raise last if last is not None else RuntimeError("no usage doc")


def _render_ledger_tenants(tenants):
    """The cost-ledger tenant table (``/v1/usage`` / ``/v1/fleet/usage``
    shape: nested token/kv/wire accumulators per tenant)."""
    print(f"{'tenant':<14} {'reqs':>5} {'billed_tok':>10} {'device_s':>9} "
          f"{'kv_blk_s':>9} {'wire_B':>10} {'saved_tok':>9}")
    for name in sorted(tenants, key=lambda n: -(tenants[n].get("tokens") or
                                                {}).get("billed", 0)):
        row = tenants[name]
        tokens = row.get("tokens") or {}
        saved = row.get("saved_tokens") or {}
        print(f"{name:<14} {row.get('requests', 0):>5} "
              f"{tokens.get('billed', 0):>10} "
              f"{row.get('device_seconds', 0.0):>9.3f} "
              f"{sum((row.get('kv_block_seconds') or {}).values()):>9.2f} "
              f"{sum((row.get('wire_bytes') or {}).values()):>10} "
              f"{sum(saved.values()):>9}")


def _render_loadgen_tenants(tenants):
    """The client-side tenant table (``bin/dstpu_loadgen --tenants --json``
    shape: offered/ok/shed counts, goodput, TTFT percentiles)."""
    print(f"{'tenant':<14} {'reqs':>5} {'ok':>5} {'shed':>5} "
          f"{'goodput':>9} {'ttft_p50':>10} {'ttft_p99':>10}")

    def _ms(row, pct):
        v = (row.get("ttft_ms") or {}).get(pct)
        return f"{v:>8.1f}ms" if isinstance(v, (int, float)) else f"{'—':>10}"

    for name in sorted(tenants, key=lambda n: -tenants[n].get("requests", 0)):
        row = tenants[name]
        print(f"{name:<14} {row.get('requests', 0):>5} {row.get('ok', 0):>5} "
              f"{row.get('shed', 0):>5} "
              f"{row.get('goodput_req_s', 0.0):>7.2f}/s "
              f"{_ms(row, 'p50')} {_ms(row, 'p99')}")


def _render_perf_join(perf):
    """The predicted-vs-observed table: one row per (program, bucket) the
    engine dispatched, joined live against the roofline prediction. A ratio
    near 1 means the analytic model holds; sustained drift raised the
    ``perf_drift_events_total`` rows shown in the last column."""
    rows = (perf or {}).get("programs") or []
    if not rows:
        print("predicted-vs-observed .. no dispatches observed yet")
        return
    print(f"predicted-vs-observed .. chip={perf.get('chip', '?')} "
          f"drift_factor={perf.get('drift_factor', '?')}")
    print(f"{'program':<24} {'bucket':>8} {'disp':>6} {'pred':>10} "
          f"{'obs_p50':>10} {'ratio':>7} {'drift':>6}")
    def _ms(v):
        return (f"{v * 1e3:>8.2f}ms" if isinstance(v, (int, float)) and v == v
                else f"{'—':>10}")

    for row in sorted(rows, key=lambda r: (r.get("program", ""),
                                           r.get("bucket", 0))):
        ratio = row.get("ratio")
        print(f"{row.get('program', '?'):<24} {row.get('bucket', 0):>8} "
              f"{row.get('dispatches', 0):>6} "
              f"{_ms(row.get('predicted_s'))} {_ms(row.get('observed_p50_s'))} "
              + (f"{ratio:>7.2f}" if isinstance(ratio, (int, float))
                 else f"{'—':>7}")
              + f" {row.get('drift_events', 0):>6}")


def usage_report(src):
    """``dstpu_report --usage <file | host:port>``: tenant cost-attribution
    tables plus the predicted-vs-observed perf join. The operand is a live
    replica/router address, a saved ``/v1/usage`` / ``/v1/fleet/usage`` /
    ``/v1/stats`` doc, or a ``bin/dstpu_loadgen --tenants --json`` file."""
    try:
        doc = _load_usage_doc(src)
    except Exception as e:
        print(f"cannot load usage doc from {src}: {e}")
        return 2
    if not isinstance(doc, dict):
        print(f"{src}: not a usage doc")
        return 2
    perf = doc.get("perf")
    if isinstance(doc.get("usage"), dict):  # a /v1/stats doc
        doc = doc["usage"]
    print("-" * 78)
    print(f"cost attribution ....... {src}")
    print("-" * 78)
    if doc.get("enabled") is False:
        print("cost ledger disabled (run the server with telemetry active "
              "and ServingConfig.cost.enabled)")
        return 0
    totals = doc.get("totals")
    if isinstance(totals, dict):
        tokens = totals.get("tokens") or {}
        print(f"totals ................. requests={totals.get('requests', 0)} "
              f"billed_tokens={tokens.get('billed', 0)} "
              f"device_s={totals.get('device_seconds', 0.0):.3f} "
              f"dispatches={totals.get('dispatches', 0)}")
    tenants = doc.get("tenants") or {}
    if not tenants:
        print("no tenant rows yet")
    elif any("goodput_req_s" in row for row in tenants.values()):
        _render_loadgen_tenants(tenants)
    else:
        _render_ledger_tenants(tenants)
    if isinstance(doc.get("fair_share"), dict):
        fs = doc["fair_share"]
        print(f"fair share ............. sheds={fs.get('sheds', 0)} "
              f"tenants={len(fs.get('tenants') or ())}")
    if perf is not None:
        print("-" * 78)
        _render_perf_join(perf)
    print("-" * 78)
    return 0


def main(argv=None):
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if "--spec" in argv:
        idx = argv.index("--spec")
        if idx + 1 >= len(argv):
            print("usage: dstpu_report --spec <loadgen-spec-demo.json>")
            return 2
        return spec_report(argv[idx + 1])
    if "--overload" in argv:
        idx = argv.index("--overload")
        if idx + 1 >= len(argv):
            print("usage: dstpu_report --overload <loadgen-overload.json>")
            return 2
        return overload_report(argv[idx + 1])
    if "--gang" in argv:
        idx = argv.index("--gang")
        if idx + 1 >= len(argv):
            print("usage: dstpu_report --gang <dir>")
            return 2
        return gang_report(argv[idx + 1])
    if "--checkpoint" in argv:
        idx = argv.index("--checkpoint")
        if idx + 1 >= len(argv):
            print("usage: dstpu_report --checkpoint <dir> [--keep-last-k K]")
            return 2
        keep = None
        if "--keep-last-k" in argv:
            kidx = argv.index("--keep-last-k")
            if kidx + 1 >= len(argv):
                print("usage: dstpu_report --checkpoint <dir> [--keep-last-k K]")
                return 2
            keep = int(argv[kidx + 1])
        return checkpoint_report(argv[idx + 1], keep_last_k=keep)
    if "--perf" in argv:
        idx = argv.index("--perf")
        if idx + 1 >= len(argv):
            print("usage: dstpu_report --perf <budgets-dir | gate-report.json>")
            return 2
        from deepspeed_tpu.perf.reporting import perf_report
        return perf_report(argv[idx + 1])
    if "--metrics-url" in argv:
        idx = argv.index("--metrics-url")
        if idx + 1 >= len(argv):
            print("usage: dstpu_report --metrics-url <host:port | http://...>")
            return 2
        return metrics_report(argv[idx + 1])
    if "--trace" in argv:
        idx = argv.index("--trace")
        if idx + 1 >= len(argv):
            print("usage: dstpu_report --trace <chrome-trace.json | flight-dump.json>")
            return 2
        return trace_report(argv[idx + 1])
    if "--timeseries" in argv:
        idx = argv.index("--timeseries")
        if idx + 1 >= len(argv):
            print("usage: dstpu_report --timeseries <timeseries.json | host:port>")
            return 2
        return timeseries_report(argv[idx + 1])
    if "--usage" in argv:
        idx = argv.index("--usage")
        if idx + 1 >= len(argv):
            print("usage: dstpu_report --usage <usage.json | host:port>")
            return 2
        return usage_report(argv[idx + 1])
    if "--kv" in argv:
        idx = argv.index("--kv")
        if idx + 1 >= len(argv):
            print("usage: dstpu_report --kv <stats.json | host:port>")
            return 2
        return kv_report(argv[idx + 1])
    import deepspeed_tpu
    print("-" * 60)
    print("DeepSpeed-TPU C++/JAX environment report")
    print("-" * 60)
    print(f"deepspeed_tpu version ... {deepspeed_tpu.__version__}")
    print(f"python ................. {sys.version.split()[0]}")
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy"):
        print(f"{mod:<22} ... {_version(mod)}")
    print("-" * 60)
    # this process asks the backend itself (and so holds the chip while it
    # runs); a backend that cannot be reached raises
    import jax
    devices = jax.devices()
    mems = [m.kind for m in devices[0].addressable_memories()]
    print(f"backend ................ {jax.default_backend()}")
    print(f"devices ................ {len(devices)}: {devices[0].device_kind}")
    print(f"process count .......... {jax.process_count()}")
    print(f"memory kinds ........... {mems}")
    print(f"host offload ........... "
          f"{GREEN_OK if 'pinned_host' in mems else RED_NO}")
    print("-" * 60)
    # native-op compat matrix (reference env_report.py op_report / ds_report)
    from deepspeed_tpu.ops.op_builder import ALL_OPS
    for name, cls in ALL_OPS.items():
        b = cls()
        ok = b.is_compatible()
        print(f"native op {name:<12} ... {GREEN_OK if ok else RED_NO}"
              f"{'' if ok else '  (' + str(b.error_log) + ')'}")
    print("-" * 60)
    from deepspeed_tpu.utils import groups
    print(f"mesh axes .............. {groups.MESH_AXES}")
    if groups.mesh_is_initialized():
        print(f"mesh ................... {dict(groups.get_mesh().shape)}")
    else:
        print("mesh ................... not initialized (created at engine init)")
    print("-" * 60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
