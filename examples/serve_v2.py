"""Quickstart: FastGen-style ragged serving with the v2 engine.

Prefill + on-device decode_loop + continuous-batching generate + the
inference-checkpoint round-trip, on a tiny random llama.

Run (virtual 8-device CPU mesh; on a TPU host drop both variables):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/serve_v2.py

Server mode (``DSTPU_SERVE_MODE=server``): start the persistent serving layer
— ServingScheduler + ServingServer on an ephemeral port — submit two
overlapping SSE streaming requests over HTTP, and print tokens as they
arrive; then drain gracefully.

Fleet mode (``DSTPU_SERVE_MODE=fleet``): a disaggregated 4-replica fleet — two
prefill-role and two decode-role in-process replicas behind the FleetRouter.
Each request prefills (plus first token) on a prefill replica, hands its KV
off as a portable payload, and finishes decoding on a decode replica; the
final SSE event shows both legs. Then a fleet-wide graceful drain.

Supervised mode (``DSTPU_SERVE_MODE=supervised``): the fault-tolerance loop —
a ReplicaSupervisor owns two replica slots (readiness-gated registration),
one replica is killed mid-fleet, the supervisor detects the death and
restarts it automatically (visible as ``fleet_restarts_total`` and in the
``/v1/fleet/stats`` supervisor table), and requests keep flowing throughout
because the router's failover + circuit breaker route around the hole.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.realpath(__file__))))
import tempfile

import numpy as np

from deepspeed_tpu.models.llama import LlamaConfig, init_params
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import (build_engine, build_hf_engine,
                                                       generate)
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig,
                                                               MemoryConfig)


def serve_main():
    """Persistent-server demo: overlapping streaming requests over HTTP, each
    traced end-to-end (X-DSTPU-Trace-Id), plus a flight-recorder dump and a
    per-request timeline report from the exported Chrome trace."""
    import json
    import threading
    import urllib.request

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler, ServingServer

    trace_dir = tempfile.mkdtemp()
    telemetry.configure(telemetry.TelemetryConfig(
        enabled=True,
        trace_path=os.path.join(trace_dir, "serve.trace.json"),
        flight_recorder={"enabled": True, "dir": os.path.join(trace_dir, "flight"),
                         "watchdog_enabled": False}))

    cfg = LlamaConfig.tiny(vocab_size=512, max_position_embeddings=128)
    _, params = init_params(cfg, seq_len=16)
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(
            memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=128),
            max_context=128, max_ragged_batch_size=256, max_ragged_sequence_count=8),
        kv_block_size=16)
    engine = build_engine(params, cfg, engine_config)
    scheduler = ServingScheduler(engine, ServingConfig(decode_chunk=4))
    server = ServingServer(scheduler).start()
    print(f"serving on {server.url}")

    def stream_one(name, prompt, n):
        body = json.dumps({"prompt": prompt, "max_new_tokens": n,
                           "stream": True}).encode()
        req = urllib.request.Request(server.url + "/v1/generate", data=body,
                                     headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            trace_id = resp.headers["X-DSTPU-Trace-Id"]
            for line in resp:
                line = line.decode().strip()
                if not line.startswith("data: "):
                    continue
                event = json.loads(line[len("data: "):])
                if event.get("done"):
                    assert event["trace_id"] == trace_id
                    print(f"[{name}] done: state={event['state']} "
                          f"trace={trace_id} tokens={event['tokens']}")
                else:
                    print(f"[{name}] token {event['index']}: {event['token']}")

    rng = np.random.default_rng(0)
    threads = [threading.Thread(target=stream_one,
                                args=(name, rng.integers(0, cfg.vocab_size, n).tolist(), 8))
               for name, n in (("A", 24), ("B", 9))]
    for t in threads:
        t.start()  # both requests are in flight concurrently
    for t in threads:
        t.join()

    stats = json.loads(urllib.request.urlopen(server.url + "/v1/stats",
                                              timeout=10).read())
    assert stats["counters"]["completed"] == 2, stats
    assert stats["latency"]["ttft_s"]["p50"] is not None, stats

    # black-box dump on demand (same payload a SIGUSR1 would produce)
    dump_path = telemetry.get_flight_recorder().dump("demo")
    with open(dump_path) as f:
        dump = json.load(f)
    assert dump["metrics"]["serving_completions_total"][0][1] == 2
    print(f"flight dump: {dump_path}")

    server.stop()  # graceful drain
    assert engine.free_blocks == 128, "KV blocks must all return to the pool"
    engine.close()

    telemetry.shutdown()  # writes trace_path
    from deepspeed_tpu.env_report import trace_report
    assert trace_report(os.path.join(trace_dir, "serve.trace.json")) == 0
    print("OK")


def fleet_main():
    """Disaggregated-fleet demo: 2 prefill + 2 decode in-process replicas
    behind the router; each request's KV hands off between pools mid-request
    and the final event carries the per-leg replica attribution."""
    import json
    import threading
    import urllib.request

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.fleet import FleetRouter, ReplicaManager
    from deepspeed_tpu.serving import ServingConfig

    telemetry.configure(telemetry.TelemetryConfig(enabled=True))

    cfg = LlamaConfig.tiny(vocab_size=512, max_position_embeddings=128)
    _, params = init_params(cfg, seq_len=16)
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(
            memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=128),
            max_context=128, max_ragged_batch_size=256, max_ragged_sequence_count=8),
        kv_block_size=16)

    manager = ReplicaManager(engine_factory=lambda: build_engine(params, cfg, engine_config),
                             serving_config=ServingConfig(decode_chunk=4))
    for _ in range(2):
        manager.add_local(role="prefill")
        manager.add_local(role="decode")
    router = FleetRouter(manager).start()
    print(f"fleet router on {router.url} (pools: "
          f"{manager.pool_size('prefill')} prefill, {manager.pool_size('decode')} decode)")

    def stream_one(name, prompt, n):
        body = json.dumps({"prompt": prompt, "max_new_tokens": n,
                           "stream": True, "session": name}).encode()
        req = urllib.request.Request(router.url + "/v1/generate", data=body,
                                     headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            trace_id = resp.headers["X-DSTPU-Trace-Id"]
            for line in resp:
                line = line.decode().strip()
                if not line.startswith("data: "):
                    continue
                event = json.loads(line[len("data: "):])
                if event.get("done"):
                    legs = [(leg["kind"], leg["replica"]) for leg in event["legs"]]
                    assert [k for k, _ in legs] == ["prefill", "decode"], legs
                    assert event["trace_id"] == trace_id
                    print(f"[{name}] done: state={event['state']} legs={legs} "
                          f"tokens={event['tokens']}")
                else:
                    print(f"[{name}] token {event['index']}: {event['token']}")

    rng = np.random.default_rng(0)
    threads = [threading.Thread(target=stream_one,
                                args=(name, rng.integers(0, cfg.vocab_size, n).tolist(), 8))
               for name, n in (("A", 24), ("B", 9))]
    for t in threads:
        t.start()  # both requests cross the prefill->decode boundary concurrently
    for t in threads:
        t.join()

    stats = json.loads(urllib.request.urlopen(router.url + "/v1/fleet/stats",
                                              timeout=10).read())
    assert stats["roles"] == {"prefill": 2, "decode": 2}, stats
    dispatches = {row["id"]: row["dispatches"] for row in stats["replicas"]}
    assert sum(dispatches.values()) >= 4, dispatches  # 2 requests x 2 legs
    print(f"per-replica dispatches: {dispatches}")

    router.stop()  # fleet-wide graceful drain (schedulers stopped, engines closed)
    telemetry.shutdown()
    print("OK")


def supervised_main():
    """Fault-tolerance demo: a supervised 2-replica fleet survives a replica
    kill — the supervisor readiness-gates registration, detects the death,
    restarts the replica with backoff, and the router serves through it all
    (failover during the outage, full capacity after the restart)."""
    import json
    import time
    import urllib.request

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.fleet import (FleetConfig, FleetRouter, ReplicaManager,
                                     SlotState, SupervisorConfig)
    from deepspeed_tpu.fleet.supervisor import ReplicaSupervisor
    from deepspeed_tpu.serving import ServingConfig

    telemetry.configure(telemetry.TelemetryConfig(enabled=True))

    cfg = LlamaConfig.tiny(vocab_size=512, max_position_embeddings=128)
    _, params = init_params(cfg, seq_len=16)
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(
            memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=128),
            max_context=128, max_ragged_batch_size=256, max_ragged_sequence_count=8),
        kv_block_size=16)

    manager = ReplicaManager(
        engine_factory=lambda: build_engine(params, cfg, engine_config),
        config=FleetConfig(probe_ttl_s=0.0),
        serving_config=ServingConfig(decode_chunk=4))
    supervisor = ReplicaSupervisor(manager, SupervisorConfig(
        poll_interval_s=0.05, restart_backoff_base_s=0.1,
        restart_backoff_cap_s=0.5, max_crashes=5, crash_window_s=120.0))
    slot_a = supervisor.add_local(role="mixed")
    supervisor.add_local(role="mixed")
    supervisor.start()
    assert supervisor.wait_ready(timeout=300), "replicas never became ready"
    router = FleetRouter(manager).start()
    print(f"supervised fleet on {router.url}: "
          f"{manager.pool_size('mixed')} replicas "
          f"(registration was gated on /healthz readiness)")

    def generate(name):
        body = json.dumps({"prompt": rng.integers(0, cfg.vocab_size, 12).tolist(),
                           "max_new_tokens": 6}).encode()
        req = urllib.request.Request(router.url + "/v1/generate", data=body,
                                     headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            doc = json.loads(resp.read())
        assert doc["state"] == "DONE", doc
        print(f"[{name}] done: state={doc['state']} "
              f"replica={doc['legs'][0]['replica']} tokens={doc['tokens']}")

    rng = np.random.default_rng(0)
    generate("before-kill")

    # a replica dies abruptly (what a SIGKILL'd process looks like in-process)
    slot_a.replica.kill("demo crash")
    print(f"killed replica {slot_a.id}; serving continues on the survivor...")
    generate("during-outage")  # failover + breaker route around the hole

    deadline = time.monotonic() + 300
    while not (slot_a.state is SlotState.READY and slot_a.restarts >= 1):
        assert time.monotonic() < deadline, "supervisor never restarted the replica"
        time.sleep(0.05)
    print(f"supervisor restarted {slot_a.id} automatically "
          f"(restarts={slot_a.restarts})")
    generate("after-restart")

    stats = json.loads(urllib.request.urlopen(
        router.url + "/v1/fleet/stats", timeout=10).read())
    sup = stats["supervisor"]
    assert sup["restarts"] >= 1, sup
    assert all(s["state"] == "READY" for s in sup["slots"]), sup
    assert manager.pool_size("mixed") == 2
    restarts_metric = telemetry.get_registry().snapshot()["fleet_restarts_total"]
    assert restarts_metric[0][1] >= 1
    print(f"supervisor table: restarts={sup['restarts']} "
          f"slots={[(s['id'], s['state']) for s in sup['slots']]}")

    supervisor.stop()
    router.stop()  # graceful fleet-wide drain
    telemetry.shutdown()
    print("OK")


def main():
    cfg = LlamaConfig.tiny(vocab_size=512, max_position_embeddings=128)
    _, params = init_params(cfg, seq_len=16)
    engine_config = RaggedInferenceEngineConfig(
        state_manager=DSStateManagerConfig(
            memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=128),
            max_context=128, max_ragged_batch_size=256, max_ragged_sequence_count=8),
        kv_block_size=16,
        # int4 at-rest weights (ZeRO-Inference): halve again with bits=4
        weight_quantization={"enabled": True, "bits": 8},
        # serving telemetry: batch/token/KV gauges on a scrapeable endpoint
        # (ephemeral port; curl <metrics_url> or bin/dstpu_report --metrics-url)
        telemetry={"enabled": True, "http": {"enabled": True, "port": 0}})
    engine = build_engine(params, cfg, engine_config)
    print(f"metrics endpoint: {engine.metrics_url}")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (24, 9, 40)]

    # continuous batching, chunks of 4 decode steps per device dispatch
    outs = generate(engine, prompts, max_new_tokens=12, decode_chunk=4)
    for i, out in enumerate(outs):
        print(f"seq {i}: {len(prompts[i])} prompt tokens -> {out}")

    # KV offload: evict a cold sequence; it restores transparently on touch
    pre = engine.put([7], [prompts[0]])
    engine.offload_sequence(7)
    first = np.asarray([int(np.argmax(np.asarray(pre)[0]))], np.int32)
    toks = engine.decode_loop([7], [first], 4)   # restore + 4 steps, one program
    print("offload/restore decode:", np.asarray(toks)[0].tolist())

    # inference-checkpoint round-trip
    d = tempfile.mkdtemp()
    engine.serialize(d)
    from deepspeed_tpu.telemetry import TelemetryConfig
    rebuilt = build_hf_engine(  # auto-detects the DS checkpoint; keep the one
        d, engine_config.model_copy(update={"telemetry": TelemetryConfig()}))
    np.testing.assert_allclose(np.asarray(rebuilt.put([0], [prompts[1]])),
                               np.asarray(engine.put([9], [prompts[1]])),
                               rtol=1e-4, atol=1e-4)
    print("serialize round-trip OK")
    import urllib.request
    with urllib.request.urlopen(engine.metrics_url, timeout=5) as resp:
        body = resp.read().decode()
    assert "inference_batches_total" in body and "inference_tokens_total" in body
    print("metrics scrape OK")
    engine.close()
    print("OK")


if __name__ == "__main__":
    from deepspeed_tpu.utils.jax_platform import enable_compile_cache
    enable_compile_cache()
    mode = os.environ.get("DSTPU_SERVE_MODE")
    if mode == "server":
        serve_main()
    elif mode == "fleet":
        fleet_main()
    elif mode == "supervised":
        supervised_main()
    else:
        main()
