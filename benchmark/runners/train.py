"""Runner for ``"mode": "train"`` configurations: ``deepspeed_tpu.initialize``
and the fused ``train_batch``, fed by a traffic kind of the batch family
(``batch(i)``) from a prefetch thread."""

import contextlib
import queue
import threading
import time

import numpy as np

from benchmark import check, instruments

CHECK_STEPS = 4          # on one repeated batch, the first of them against the reference
BLOCK_EVERY = 10         # steps between waits on a loss, to bound the dispatch queue
PREFETCH_DEPTH = 2


class Prefetcher:
    """Builds batches ahead of the training loop on one thread."""

    def __init__(self, traffic, first):
        self._traffic, self._next = traffic, first
        self._queue = queue.Queue(maxsize=PREFETCH_DEPTH)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-prefetch", daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            batch = self._traffic.batch(self._next)
            self._next += 1
            while not self._stop.is_set():
                try:
                    self._queue.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    pass

    def get(self):
        return self._queue.get(timeout=120)

    def close(self):
        self._stop.set()
        self._thread.join(10)


def run(ctx):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.utils import groups

    config, traffic_doc, log, family = ctx["config"], ctx["traffic"], ctx["log"], ctx["family"]
    seed, seconds, chips = ctx["seed"], ctx["seconds"], ctx["chips"]
    train = config["train"]
    cfg = family.program_config(config, **config.get("program", {}))
    seq_len, micro = train["seq_len"], train["micro_batch_per_chip"]
    gas = train["gradient_accumulation_steps"]
    sequences = micro * chips * gas
    tokens_per_step = sequences * seq_len
    traffic = ctx["traffic_kind"].Traffic(traffic_doc["params"], seed, sequences, seq_len,
                                          cfg.vocab_size)
    annotate = instruments.annotate if ctx["trace"] else (lambda name: contextlib.nullcontext())

    devices = jax.devices()[:chips]
    groups.initialize_mesh(devices=devices, force=True)
    ds_config = dict(train["deepspeed"], train_micro_batch_size_per_gpu=micro,
                     gradient_accumulation_steps=gas)
    # the check batch: ONE packed sequence, repeated, so that the engine's mean
    # loss is that sequence's loss and the reference needs one forward
    ids, labels = traffic.batch(0)
    check_batch = (np.repeat(ids[:1], sequences, axis=0), np.repeat(labels[:1], sequences, axis=0))
    t = time.perf_counter()
    engine, _, _, _ = deepspeed_tpu.initialize(model=family.training_module(cfg), config=ds_config,
                                               example_batch=check_batch, rng_seed=seed)
    jax.block_until_ready((engine.params, engine.opt_state))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(engine.params))
    log(f"initialize: {n_params} parameters over data={chips} in {time.perf_counter() - t:.1f}s")

    t = time.perf_counter()
    ref_loss = family.reference.next_token_loss(engine.params, config, ids[0], labels[0])
    log(f"reference loss {ref_loss:.6f} on one sequence of {seq_len} tokens in "
        f"{time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    losses = [float(engine.train_batch(batch=check_batch)) for _ in range(CHECK_STEPS)]
    same, detail = check.loss_close(ref_loss, losses[0])
    fell = bool(np.isfinite(losses).all() and losses[-1] < check.LOSS_MUST_FALL_TO * losses[0])
    log(f"correct: first step {detail} -> {'ok' if same else 'WRONG'}; losses on the repeated "
        f"batch {[round(x, 4) for x in losses]} -> {'ok' if fell else 'DID NOT FALL BY A TENTH'} "
        f"({time.perf_counter() - t:.1f}s)")

    prefetch = Prefetcher(traffic, first=1)
    slice_ = None
    if ctx["trace"]:
        slice_ = instruments.TraceSlice(ctx["trace_dir"], traffic_doc["trace_start_s"],
                                        traffic_doc["trace_length_s"])
    try:
        # one step on fresh data, blocked: the window starts with an empty queue
        float(engine.train_batch(batch=prefetch.get()))
        t0 = time.perf_counter()
        if slice_ is not None:
            slice_.arm(t0)
        before = ctx["meter"].snapshot()
        pending, done_losses, steps = [], [], 0
        while time.perf_counter() - t0 < seconds:
            with annotate("bench.data_wait"):
                batch = prefetch.get()
            with annotate("bench.train_batch_dispatch"):
                pending.append(engine.train_batch(batch=batch))
            steps += 1
            if steps % BLOCK_EVERY == 0:
                with annotate("bench.block_on_loss"):
                    done_losses += [float(x) for x in pending]
                pending = []
        with annotate("bench.block_on_loss"):
            done_losses += [float(x) for x in pending]
        elapsed = time.perf_counter() - t0
        after = ctx["meter"].snapshot()
    finally:
        prefetch.close()
    trace_path = slice_.finish() if slice_ is not None else None
    bad = int((~np.isfinite(done_losses)).sum())
    log(f"window: {steps} steps of {tokens_per_step} tokens in {elapsed:.3f}s; loss "
        f"{done_losses[0]:.4f} -> {done_losses[-1]:.4f}; {bad} not finite")
    engine.destroy()
    return {
        "mode": "train", "correct": bool(same and fell), "attempted": steps, "failed": bad,
        "seconds": seconds, "t0": t0, "steps": steps, "elapsed_s": elapsed,
        "tokens_per_step": tokens_per_step, "n_params": n_params, "chips": chips,
        "builds_in_window": after["programs"] - before["programs"], "spans": [],
        "trace_path": trace_path, "trace_slice": slice_,
        "model": {"n_heads": cfg.num_attention_heads, "n_kv_heads": cfg.num_key_value_heads,
                  "head_dim": cfg.hidden_size // cfg.num_attention_heads,
                  "n_layers": cfg.num_hidden_layers, "hidden_size": cfg.hidden_size,
                  "vocab_size": cfg.vocab_size, "seq_len": seq_len,
                  "sequences_per_chip": micro * gas},
    }
