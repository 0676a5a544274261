"""Base ragged transformer model implementation.

Reference: ``deepspeed/inference/v2/model_implementations/inference_transformer_base.py``
(DSTransformerModelBase:49 — attn/mlp/moe module composition, KV cache config and
sizing, ``get_kv_requirements``/``maybe_allocate_kv``/``kv_cache_config``) and
``inference_policy_base.py:104``.

TPU execution model: ``forward(ragged_batch)`` runs ONE jitted program per batch
*bucket* (padded token/sequence/block counts — see ragged_wrapper.py). The program
consumes the paged KV cache array functionally (donated in, returned out) and the
padded metadata arrays; scatter updates into the cache use XLA drop-mode so padding
never corrupts live blocks. Per-layer compute is supplied by subclasses via
``layer_forward``; embed/unembed live here, as does the logits gather (only each
sequence's final token is unembedded — reference ``logits_gather.cu`` semantics).
"""

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2 import sampling
from deepspeed_tpu.inference.v2.ragged.manager_configs import KVCacheConfig
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (_pow2_pad, padded_sequences,
                                                              padded_tokens, sequence_buckets,
                                                              token_buckets)
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import DSSequenceDescriptor
from deepspeed_tpu.telemetry import compile_watch


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * w).astype(x.dtype)


def scaled_dot(x, kernel, scale):
    """``(x kernel) scale`` with the scale on the float32 product: a
    multiplier that is no power of two would otherwise be rounded to the
    activations' type once itself and round the product once more."""
    return (jnp.dot(x, kernel.astype(x.dtype), preferred_element_type=jnp.float32)
            * scale).astype(x.dtype)


def _root(params):
    """Normalize the two training-tree layouts: LlamaForCausalLM nests everything
    under "model"; MixtralForCausalLM's tree is flat."""
    return params["model"] if "model" in params else params


class DSTransformerModelBase:
    """Subclasses define ``layer_forward(params, li, x, attn_fn, batch)``, and
    state where they differ from the defaults here: the shape properties (the
    config's fields of the HF names), ``embed`` / ``unembed`` (the
    ``embed_tokens`` / final norm / ``lm_head`` tree; :attr:`final_norm` names
    the norm), the smallest buckets (:attr:`one_table_bucket`,
    :attr:`one_sequence_bucket`)."""

    # the final norm's name in the tree and its epsilon's in the config
    final_norm: Tuple[str, str] = ("norm", "rms_norm_eps")
    # one program for every block table / every count of sequences: the whole table
    # (``max_context``) / the whole ``max_ragged_sequence_count`` is the one bucket
    one_table_bucket: bool = False
    one_sequence_bucket: bool = False

    def __init__(self, params, config, engine_config, state_manager=None):
        wq = getattr(engine_config, "quantization", None)
        if wq is not None and wq.enabled:
            # ZeRO-Inference weight quantization: int8 at rest, dequantized
            # inside the jitted forward (inference/v2/quantization.py)
            if engine_config.tensor_parallel.tp_size > 1:
                raise NotImplementedError(
                    "weight_quantization with TP>1: AutoTP classifies by leaf "
                    "paths, which quantized subtrees change — quantize per-shard "
                    "after placement instead (not yet wired)")
            from deepspeed_tpu.inference.v2.quantization import quantize_tree
            params = quantize_tree(params, min_size=wq.min_size, bits=wq.bits)
        self._params = params
        self._config = config
        self._engine_config = engine_config
        self._state_manager = None
        # (kind, key) -> [the jit, what a step calls: the jit or its
        # compile-watch wrapper; None until the program runs] (``_program``)
        self._programs = {}
        # the newest forward program's counts of routed work (``moe_count_names``),
        # int32 [expert layers, counts] ON THE DEVICE (a bucket on the grouped
        # path), else None
        self.last_moe_banks = None
        self._group_windows = None
        if state_manager is not None:
            self.set_state_manager(state_manager)

    # ------------------------------------------------------------ properties --
    @property
    def config(self):
        return self._config

    @property
    def num_layers(self) -> int:
        return self._config.num_hidden_layers

    @property
    def num_kv_layers(self) -> int:
        """Layers that keep K/V (a row a token): the depth of the K/V array and
        what a layer's cache index counts. Every layer, unless the model says
        otherwise (one whose blocks are not all attention)."""
        return self.num_layers

    @property
    def num_kv_heads(self) -> int:
        return self._config.num_key_value_heads

    @property
    def num_heads(self) -> int:
        return self._config.num_attention_heads

    @property
    def head_dim(self) -> int:
        return self._config.head_dim

    @property
    def vocab_size(self) -> int:
        return self._config.vocab_size

    @property
    def max_context(self) -> int:
        return self._engine_config.state_manager.max_context

    # ------------------------------------------------------------- kv sizing --
    def kv_cache_config(self) -> KVCacheConfig:
        sm = self._engine_config.state_manager
        model_dtype = getattr(self._config, "dtype", jnp.bfloat16)
        # normalize through np.dtype: keying on the jnp scalar OBJECTS would
        # silently default an equivalent representation (np.float32,
        # np.dtype('float32')) to a bf16 cache under an fp32 model
        cache_dtype = np.dtype(model_dtype).name
        if cache_dtype not in ("bfloat16", "float16", "float32"):
            cache_dtype = "bfloat16"
        return KVCacheConfig(block_size=self._engine_config.kv_block_size,
                             num_allocation_groups=self.kv_groups,
                             group_windows=self.group_windows,
                             cache_shape=(self.num_kv_layers, self.num_kv_heads, self.head_dim),
                             state_widths=self.kv_state_widths,
                             min_table_bucket=self.min_table_bucket,
                             min_sequence_bucket=self.min_sequence_bucket,
                             min_token_bucket=self.min_token_bucket,
                             attention_block=self.attention_block,
                             cache_dtype=cache_dtype,
                             sequence_state=self.sequence_state,
                             sequence_slots=sm.max_tracked_sequences if self.sequence_state else 0)

    @property
    def kv_state_widths(self) -> Tuple[int, ...]:
        """The widths of the rows a token keeps a layer where its cached state
        is not a K/V pair of heads (``KVCacheConfig.state_widths``); empty for
        K and V."""
        return ()

    @property
    def sequence_state(self) -> tuple:
        """The pools of a per-SEQUENCE state group (``SequenceStateSpec``s,
        ``KVCacheConfig.sequence_state``): state a layer keeps a sequence
        whatever its length, a slot a tracked sequence
        (``max_tracked_sequences`` of them: the limit on tracked sequences,
        which ``can_schedule``, ``query`` and ``dispatch_decode_loop`` count,
        is the count of free slots, so admission needs no second counter).
        Empty for a model whose every layer keeps a row a token."""
        return ()

    @property
    def min_table_bucket(self) -> int:
        """The smallest block-table bucket (``KVCacheConfig.min_table_bucket``);
        a model with one program for every table up to some length says so:
        :attr:`one_table_bucket`, the whole table, a power of two of blocks."""
        if not self.one_table_bucket:
            return 4
        sm = self._engine_config.state_manager
        return _pow2_pad(-(-sm.max_context // self._engine_config.kv_block_size))

    @property
    def min_sequence_bucket(self) -> int:
        """The smallest sequence bucket (``KVCacheConfig.min_sequence_bucket``),
        which the token bucket starts at too; a model with one program for
        every batch up to some count of sequences says so
        (:attr:`one_sequence_bucket`)."""
        if not self.one_sequence_bucket:
            return 8
        return padded_sequences(self._engine_config.state_manager.max_ragged_sequence_count)

    @property
    def attention_block(self) -> int:
        """The length B of the blocks a model that generates by diffusion over
        blocks attends by (``KVCacheConfig.attention_block``): a query sees
        every key up to its block's end, blocks counted from position 0. 0: a
        causal model, every other."""
        return 0

    @property
    def min_token_bucket(self) -> int:
        """The smallest token bucket (``KVCacheConfig.min_token_bucket``): a
        row a sequence of the smallest sequence bucket, unless the model says
        otherwise (one whose step feeds a block a sequence)."""
        return self.min_sequence_bucket

    # what a forward program's count of routed work holds: the last axis of the
    # device array it returns beside its result (``RaggedMoE``'s ``banks_out``)
    moe_count_names: Tuple[str, ...] = ("moe_banks", "moe_visits")

    def batch_counts(self, ragged_batch, steps: int = 1) -> dict:
        """Work counters of a step that depend on the batch's positions (the
        host's copy of them), for the dispatch's span; ``steps`` > 1: over a
        ``decode_loop`` chunk. A bucket on the query-tiled grid: the passes its
        kernels make over the layers, those of them that own one token, and
        those that take the kernel's few-row arm (no more rows than one block of
        ``attention_block``; the one-token ones where there is no block mask)
        (``ops/pallas/paged_attention.py:tiled_passes``)."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        bucket_tokens = batch["tok_meta"].shape[1]
        if self.attention_arm(bucket_tokens) != "paged_tiled":
            return {}
        from deepspeed_tpu.ops.pallas.paged_attention import tiled_passes
        seq = np.asarray(batch["seq_meta"])
        counts = tiled_passes(seq[:, 1], seq[:, 2], bucket_tokens, self.attention_block)
        # ``steps`` > 1 on this grid: the forwards of a block loop, all alike
        names = ("tiled_passes", "tiled_one_token_passes", "tiled_few_row_passes")
        return {name: n * self.num_kv_layers * steps for name, n in zip(names, counts)}

    def set_state_manager(self, state_manager):
        self._state_manager = state_manager

    @property
    def state_manager(self):
        return self._state_manager

    def get_kv_requirements(self, seq_desc: DSSequenceDescriptor, max_new_tokens: int,
                            max_new_blocks: int) -> Tuple[int, int]:
        """How many of ``max_new_tokens`` can run given ``max_new_blocks`` free
        blocks, and how many blocks that takes (reference
        inference_transformer_base.py get_kv_requirements). The blocks are the
        NEW ones past the table's end: what a sliding-window sequence released
        behind it (:meth:`maybe_free_kv`) is already back in the pool the
        caller counts, so a long context is admitted by what it holds, not by
        its length; ``max_context`` still bounds its positions."""
        bs = self._state_manager.kv_block_size
        # a block of positions takes one block id in every layer group
        groups = self.kv_groups
        # the per-sequence table cap (max_context) bounds schedulable tokens
        # too: admission must reject here, not crash in extend_kv_cache after
        # blocks were already pulled from the pool
        seq_cap = seq_desc.max_blocks - seq_desc.cur_allocated_blocks
        max_new_entries = min(max_new_blocks // groups, seq_cap)
        total = seq_desc.seen_tokens + max_new_tokens
        entries_needed = (total + bs - 1) // bs - seq_desc.cur_allocated_blocks
        if entries_needed <= max_new_entries:
            return max_new_tokens, max(0, entries_needed) * groups
        # clip tokens to what the block budget allows
        capacity = (seq_desc.cur_allocated_blocks + max_new_entries) * bs - seq_desc.seen_tokens
        return max(0, capacity), max_new_entries * groups

    def maybe_allocate_kv(self, seq_desc: DSSequenceDescriptor, n_new_tokens: int) -> None:
        sched, n_blocks = self.get_kv_requirements(seq_desc, n_new_tokens,
                                                   self._state_manager.free_blocks)
        if sched < n_new_tokens:
            # the do_checks=True path rejects this earlier with a
            # SchedulingError; an unchecked put must fail LOUDLY — silently
            # under-allocating would scatter KV through out-of-range block-
            # table entries and corrupt other sequences
            raise ValueError(
                f"sequence {seq_desc.tracking_id}: {n_new_tokens} new tokens need more "
                f"KV blocks than the free pool / per-sequence max_context allows "
                f"(schedulable: {sched})")
        if n_blocks > 0:
            seq_desc.extend_kv_cache(self._state_manager.allocate_blocks(n_blocks))

    def maybe_free_kv(self, seq_desc: DSSequenceDescriptor) -> int:
        """After a step: each layer group with a sliding window gives back
        every block ALL of whose positions are more than that window behind
        the sequence's next query (rolling release); returns how many. A
        full-causal group keeps its blocks until flush. The step just
        dispatched may still be reading them: whoever is handed them next
        writes in a LATER program, and the pool is threaded through the
        programs in order."""
        return sum(self._state_manager.release_passed_blocks(seq_desc, window, group)
                   for group, window in enumerate(self.group_windows) if window > 0)

    def max_live_blocks(self, n_tokens: int) -> int:
        """The most KV blocks a sequence of ``n_tokens`` holds at once, over
        its layer groups: in a full-causal group all of them, in a group under
        a sliding window the window's, one step's feed and the two blocks the
        window's ends straddle."""
        return sum(self.max_live_blocks_in(group, n_tokens)
                   for group in range(self.kv_groups))

    def max_live_blocks_in(self, group: int, n_tokens: int) -> int:
        bs = self._engine_config.kv_block_size
        whole = -(-int(n_tokens) // bs)
        window = self.group_windows[group]
        if window <= 0:
            return whole
        feed = self._engine_config.state_manager.max_ragged_batch_size
        return min(whole, (window + feed - 1) // bs + 2)

    # ---------------------------------------------------------------- forward --
    def forward(self, ragged_batch):
        """Run the ragged forward; returns logits [n_seqs, vocab] (one row per
        sequence — its final token), and updates the paged KV cache in place."""
        logits, n = self._forward_padded(ragged_batch)
        return logits[:n] if n else logits[:0]

    def forward_draw(self, ragged_batch, temperature, seed, draw_index, prev=None):
        """:meth:`forward`, then one token drawn per sequence ON THE DEVICE
        (:mod:`~deepspeed_tpu.inference.v2.sampling`): the bucket's forward
        program — the one :meth:`forward` runs — and, dispatched right behind
        it, the draw over its padded logits. Returns the device int32
        ``[S_bucket]`` ids; rows past the live sequences are padding. The
        per-sequence ``temperature`` / ``seed`` / ``draw_index`` are host
        vectors, one entry a live sequence. ``prev`` = ``(ids, src)`` feeds
        token slot t from ``ids[src[t]]`` — the ids an earlier call returned,
        fetched or not — wherever ``src[t] >= 0`` (``sampling.chain``, one
        tiny program in front of the same forward)."""
        logits, _ = self._forward_padded(ragged_batch, prev)
        return sampling.draw(logits, temperature, seed, draw_index)

    def _forward_padded(self, ragged_batch, prev=None):
        """The bucket's program over ``ragged_batch``: its padded
        ``[S_bucket, vocab]`` logits and the live sequence count."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        bucket = self._bucket_of(batch)
        fn = self._program("forward", bucket)
        cache = self._state_manager.kv_cache.cache
        tok_meta = batch["tok_meta"] if prev is None else sampling.chain(batch["tok_meta"], *prev)
        dev = {"tok_meta": tok_meta, "seq_meta": batch["seq_meta"]}
        logits, new_cache, *banks = fn(self._params, cache, dev)
        self._state_manager.kv_cache.set_cache(new_cache)
        self.last_moe_banks = banks[0] if banks else None  # left on the device
        return logits, int(batch["n_seqs"])

    def warm_draw(self, chunk_steps: int = 0) -> None:
        """Compile the draw for every sequence bucket this engine's
        configuration can produce, placed as the forward's logits are: on the
        KV pool's mesh, replicated, or on the default device of a mesh-less
        engine. (Under tensor parallelism the compiler may leave the logits
        split over the vocabulary; that draw is then built at its first
        step.) And the program that feeds a step from the ids of the one
        before (``sampling.chain``), for every pair of a token bucket and a
        sequence bucket: a few operations each. With ``chunk_steps`` > 1,
        also the program that takes the last row of a ``decode_loop`` chunk of
        that many steps (``sampling.last_row``), a sequence bucket each."""
        from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding
        pool = self._state_manager.kv_cache.sharding
        placed = (NamedSharding(pool.mesh, PartitionSpec()) if pool is not None
                  else SingleDeviceSharding(jax.devices()[0]))
        sm = self._engine_config.state_manager
        # a batch holds a token and a KV block for each of its sequences
        most = min(sm.max_ragged_sequence_count, sm.max_ragged_batch_size,
                   self._state_manager.kv_cache.num_blocks)
        least = self.min_sequence_bucket
        for rows in sequence_buckets(most, least):
            sampling.compiled(rows, self.vocab_size, placed)
            for tokens in token_buckets(sm.max_ragged_batch_size, least):
                sampling.compiled_chain(tokens, rows)
            if chunk_steps > 1:
                sampling.compiled_last_row(chunk_steps, rows)

    # ---------------------------------------------------------- the programs --
    # kind -> (compile-watch site, the donated argument: the cache, the method
    # traced, the entries of the key that are static arguments of it)
    _PROGRAM_KINDS = {
        "forward": ("inference_forward", 1, "_forward_impl", {}),
        "decode_loop": ("inference_decode_loop", 1, "_decode_loop_impl", {"n_steps": 1}),
        "verify": ("inference_verify", 1, "_verify_impl", {"greedy": 3}),
        "compact": ("inference_kv_compact", 0, "_compact_impl", {}),
        "block_forward": ("inference_block_forward", 1, "_block_forward_impl", {}),
        "block_loop": ("inference_block_loop", 1, "_block_loop_impl", {"n_blocks": 1})}

    def _program(self, kind, key, run=True):
        """The jitted program of ``kind`` at ``key``: ``forward`` at a ``(T, S,
        MB)`` bucket, ``decode_loop`` at :meth:`_loop_key`, ``verify`` at
        :meth:`_verify_key`, ``compact`` at ``("compact", n_pairs)``. The one
        place that makes a ``jax.jit`` and keeps it. A step (``run``) is
        handed what it calls: at its first, the jit is wrapped for the compile
        watch, which attributes the key's XLA compile (and any later internal
        recompile) to the kind's site in the compile_* metrics and the trace.
        ``run=False`` hands out the jit itself, which can ``.lower()`` as the
        wrapper cannot, and leaves the watch alone: lowering a program that
        never ran is analysis, not a cache entry."""
        entry = self._programs.get((kind, key))
        if entry is None:
            _, donated, impl, static = self._PROGRAM_KINDS[kind]
            impl = getattr(self, impl)
            if static:
                impl = partial(impl, **{name: key[i] for name, i in static.items()})
            entry = self._programs[kind, key] = [jax.jit(impl, donate_argnums=(donated, )), None]
        if not run:
            return entry[0]
        if entry[1] is None:
            cw = compile_watch.get()
            entry[1] = entry[0] if cw is None else cw.wrap(self._PROGRAM_KINDS[kind][0], key,
                                                           entry[0])
        return entry[1]

    def lowerable_callables(self):
        """The programs that have run, as raw ``jax.jit`` callables (they
        support ``.lower()``), by kind and under their cache keys: ``forward``
        by ``(T, S, MB)`` bucket, ``decode_loop`` by ``(bucket, n_steps,
        False)``, ``verify`` (the speculative verify step) by ``("verify",
        bucket, tree, greedy)`` and ``compact`` (the accepted-path KV re-pack)
        by ``("compact", n_pairs)``. The official hook for HLO-level analysis
        (deepspeed_tpu/perf/): what a step calls may be a compile-watch
        wrapper, which cannot lower."""
        out = {kind: {} for kind in self._PROGRAM_KINDS}
        for (kind, key), (fn, called) in self._programs.items():
            if called is not None:
                out[kind][key] = fn
        return out

    def _synthetic_batch(self, bucket=None):
        """Shape/dtype-faithful device-batch arrays for ``bucket`` (default:
        the smallest bucket the ragged wrapper produces) — lowering needs
        avals, not live data. Built directly (the wrapper's own pad helpers
        give the bucket shape): ``RaggedBatchWrapper.finalize`` would report
        the bucket to the compile watch, and an analysis-only lowering must
        not pollute the bucket-churn recompile telemetry."""
        if bucket is None:
            least = self.min_sequence_bucket
            bucket = (padded_tokens(1, least), padded_sequences(1, least),
                      _pow2_pad(1, self.min_table_bucket))
        T, S, MB = bucket
        return {"tok_meta": np.zeros((4, T), np.int32),
                "seq_meta": np.full((S, 4 + self.kv_groups * MB + self._slot_columns), -1,
                                    np.int32)}

    def lower_forward(self, bucket=None):
        """Lower the ragged forward at ``bucket`` (``(T, S, MB)``; default
        smallest) against the live params + paged KV cache and return the
        ``jax.stages.Lowered``. Never executes; the program is the
        ``_forward_impl`` jit :meth:`forward` runs for that bucket."""
        dev = self._synthetic_batch(bucket)
        return self._program("forward", self._bucket_of(dev), run=False).lower(
            self._params, self._state_manager.kv_cache.cache, dev)

    def lower_decode_loop(self, n_steps: int, bucket=None):
        """Lower the ``n_steps`` on-device decode program (the
        ``_decode_loop_impl`` jit :meth:`decode_loop` runs)."""
        dev = self._synthetic_batch(bucket)
        return self._program("decode_loop", self._loop_key(dev, n_steps), run=False).lower(
            self._params, self._state_manager.kv_cache.cache, dev)

    def lower_verify(self, bucket=None, tree: bool = False, greedy: bool = False):
        """Lower the speculative verify step at ``bucket`` (default smallest)
        — the ``_verify_impl`` jit :meth:`forward_verify` runs: the causal
        program, or with ``tree`` the ancestor-mask one (its synthetic
        ``tree_meta`` is a chain; lowering consumes avals only, and the mask
        program is the same for every tree shape at a bucket). Never
        executes."""
        dev = self._synthetic_batch(bucket)
        if tree:
            T = dev["tok_meta"].shape[1]
            dev["tree_meta"] = np.stack([np.arange(-1, T - 1, dtype=np.int32),
                                         np.arange(T, dtype=np.int32)])
        return self._program("verify", self._verify_key(dev, greedy), run=False).lower(
            self._params, self._state_manager.kv_cache.cache, dev)

    # ------------------------------------------------------------ decode loop --
    def decode_loop(self, ragged_batch, n_steps: int, prev=None):
        """Decode ``n_steps`` tokens per sequence in ONE device program, each
        the argmax of its logits (a sampled request is drawn at its own
        ``(seed, draw_index)`` by :meth:`forward_draw`, a step at a time).

        The host-loop decode (one ``put`` per generated token) pays a full
        host→device dispatch round-trip and a logits transfer per token. This
        runs the whole generation as a ``lax.scan``: per step, one ragged
        forward (same program as :meth:`forward`, either attention path),
        argmax next token, advance the on-device metadata. KV blocks for all
        ``n_steps`` tokens must be pre-allocated (engine_v2.decode_loop does
        this).

        Returns ``(tokens, banks)`` as the program left them ON THE DEVICE,
        still being computed (the caller's fetch is the wait): generated tokens
        ``[n_steps, S_bucket]``, column i sequence-slot i, rows steps; and the
        expert banks each step touched in each expert layer beside the
        program's other counts (``moe_count_names``), int32 ``[n_steps, expert
        layers, counts]``, where the bucket routes by sorting, else None. The
        cache is updated in place with the n_steps inserted tokens (the last
        generated token is not yet inserted, matching the host-loop semantics).

        ``prev`` = ``(ids, src)`` as for :meth:`forward_draw`: token slot t's
        input id is ``ids[src[t]]``, still on the device, wherever
        ``src[t] >= 0`` (``sampling.chain`` in front of the same program).
        """
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        fn = self._program("decode_loop", self._loop_key(batch, n_steps))
        tok_meta = batch["tok_meta"] if prev is None else sampling.chain(batch["tok_meta"], *prev)
        tokens, new_cache, *banks = fn(self._params, self._state_manager.kv_cache.cache,
                                       {"tok_meta": tok_meta, "seq_meta": batch["seq_meta"]})
        self._state_manager.kv_cache.set_cache(new_cache)
        return tokens, (banks[0] if banks else None)

    def _loop_key(self, batch, n_steps):
        """``(bucket, n_steps, False)``. The constant is the benchmark's: its
        warm-up unpacks three entries and compares keys it guesses with these
        (``benchmark/runners/serve.py``; it said ``sampled`` while the loop
        could draw)."""
        return (self._bucket_of(batch), int(n_steps), False)

    def _decode_loop_impl(self, params, cache, batch, *, n_steps):
        tok_meta = jnp.asarray(batch["tok_meta"])
        seq_meta = jnp.asarray(batch["seq_meta"])

        def step(carry, _):
            cache, tok_meta, seq_meta = carry
            # banks: the forward's count of expert banks touched, where it has one
            logits, cache, *banks = self._forward_impl(
                params, cache, {"tok_meta": tok_meta, "seq_meta": seq_meta,
                                "one_token_rows": True})
            next_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S]
            tv = tok_meta[3] > 0
            # decode batches carry one token per sequence: slot i ↔ sequence i
            new_ids = jnp.where(tv, next_ids[tok_meta[1]], tok_meta[0])
            tok_meta = tok_meta.at[0].set(new_ids).at[2].add(tv.astype(tok_meta.dtype))
            sv = (seq_meta[:, 3] > 0).astype(seq_meta.dtype)
            seq_meta = seq_meta.at[:, 0].add(sv)
            return (cache, tok_meta, seq_meta), (next_ids, *banks)

        # the scan stacks the steps' tokens and, beside them, their bank counts
        (cache, _, _), (tokens, *banks) = jax.lax.scan(
            step, (cache, tok_meta, seq_meta), None, length=n_steps)
        return (tokens, cache, *banks)

    # ------------------------------------------------------------ block steps --
    # A model that generates by diffusion over blocks (``attention_block`` = B >
    # 0; its config states ``denoising_steps`` and ``mask_token_id``): a decode
    # step of a sequence is a BLOCK of B rows at positions seen .. seen + B - 1,
    # rewritten under the block mask until every row has its token, and then
    # committed once. Every forward of a block writes the block's K/V into its
    # slots of the pool (the paged kernel inserts before it attends); what
    # makes it count is ``seen_tokens``, which only the commit moves
    # (write-then-truncate, as ``rollback``).
    def block_forward(self, ragged_batch):
        """One DENOISE forward: the bucket's program over a batch that feeds one
        block a sequence, with EVERY row unembedded. Returns float32 logits
        ``[T_bucket, vocab]`` on the device, row ``i * B + j`` sequence i's
        row j (row j scores the token AT position j: no shift)."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        fn = self._program("block_forward", self._bucket_of(batch))
        logits, new_cache, *_ = fn(self._params, self._state_manager.kv_cache.cache,
                                   {"tok_meta": batch["tok_meta"], "seq_meta": batch["seq_meta"]})
        self._state_manager.kv_cache.set_cache(new_cache)
        return logits

    def _block_forward_impl(self, params, cache, batch):
        return self._forward_impl(params, cache, batch, rows="all")

    def block_loop(self, ragged_batch, masked, n_blocks: int):
        """``n_blocks`` blocks a sequence in ONE device program
        (:meth:`_block_loop_impl`). The batch feeds each sequence's FIRST
        block: its known ids, and ``masked`` (``[T_bucket]``, 1 = the row has
        no token yet; every later block starts all masked). KV blocks for all
        ``n_blocks * B`` positions must be allocated. Returns, on the device
        and still being computed, ``(ids, steps, confidences, banks)``: int32
        and int8 ``[T_bucket / B, n_blocks * B]``, row i sequence-slot i, the
        token at each position and the denoise step at which it took it (-1:
        it was given); float32 ``[T_bucket / B, n_blocks, denoising_steps,
        B]``, what the choice of rows was made on — each still-masked row's
        confidence behind each denoise forward, -1 for a row that had its
        token; and where the bucket routes by sorting the expert banks each
        block's forwards touched, int32 ``[n_blocks, expert layers]``, else
        None."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        fn = self._program("block_loop", (self._bucket_of(batch), int(n_blocks)))
        ids, steps, conf, new_cache, *banks = fn(
            self._params, self._state_manager.kv_cache.cache,
            {"tok_meta": batch["tok_meta"], "seq_meta": batch["seq_meta"], "masked": masked})
        self._state_manager.kv_cache.set_cache(new_cache)
        return ids, steps, conf, (banks[0] if banks else None)

    def _block_loop_impl(self, params, cache, batch, *, n_blocks):
        """``n_blocks * denoising_steps + 1`` forwards. A block's
        ``denoising_steps`` denoise forwards (scope ``diffusion/denoise``), after
        each of which the ``B / denoising_steps`` masked rows of a sequence
        whose greedy token is most confident take it (``diffusion/unmask``:
        argmax, its float32 softmax probability, the best of the masked rows,
        ties to the earlier row — ``low_confidence_static``). A block's COMMIT —
        its finished rows through every layer once more, so that the pool holds
        their final K/V — rides the next block's first denoise forward
        (:meth:`_two_blocks`: one forward of 2B rows a sequence, the expert
        banks read once for both; only the next block's rows are unembedded),
        behind which the metadata moves on by B. The chunk's last block has no
        successor in the program and keeps a commit forward of its own
        (``diffusion/commit``, no row unembedded: the published algorithm reads
        no logits of it). A sequence whose first block came part given has
        nothing left to take in its last denoise forwards: the program's shape
        is the batch's, not a sequence's."""

        cfg = self._config
        B, n_denoise, mask_id = self.attention_block, cfg.denoising_steps, cfg.mask_token_id
        tok_meta = jnp.asarray(batch["tok_meta"])
        seq_meta = jnp.asarray(batch["seq_meta"])
        T = tok_meta.shape[1]
        valid = tok_meta[3] > 0

        def forward(params, cache, ids, tok_meta, seq_meta, rows):
            return self._forward_impl(params, cache, {"tok_meta": tok_meta.at[0].set(ids),
                                                      "seq_meta": seq_meta}, rows=rows)

        def unmask(logits, ids, masked, taken, step):
            with jax.named_scope("diffusion/unmask"):
                x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                conf = jnp.max(jax.nn.softmax(logits, axis=-1), axis=-1)
                conf = jnp.where(masked, conf, -1.0).reshape(T // B, B)
                _, best = jax.lax.top_k(conf, B // n_denoise)  # equal: the earlier row
                chosen = jnp.any(best[:, :, None] == jnp.arange(B)[None, None, :], axis=1)
                chosen = chosen.reshape(T) & masked
                ids = jnp.where(chosen, x0, ids)
                taken = jnp.where(chosen, step.astype(jnp.int8), taken)
            return ids, masked & ~chosen, taken, conf.reshape(T)

        # the B-row denoise forward has two uses (the first block's steps, a
        # later block's steps behind its fused one): a jit of its own, so that
        # the second is the first's trace
        @jax.jit
        def denoise(params, tok_meta, seq_meta, carry, step):
            cache, ids, masked, taken = carry
            with jax.named_scope("diffusion/denoise"):
                logits, cache, *banks = forward(params, cache, jnp.where(masked, mask_id, ids),
                                                tok_meta, seq_meta, "all")
            ids, masked, taken, conf = unmask(logits, ids, masked, taken, step)
            return (cache, ids, masked, taken), (conf, *banks)

        def steps(cache, tok_meta, seq_meta, ids, masked, taken, first):
            """Denoise forwards ``first .. denoising_steps - 1`` of a block."""
            (cache, ids, _, taken), (conf, *banks) = jax.lax.scan(
                partial(denoise, params, tok_meta, seq_meta), (cache, ids, masked, taken),
                jnp.arange(first, n_denoise))
            return cache, ids, taken, conf, tuple(b.sum(axis=0) for b in banks)

        def next_block(carry, _):
            cache, tok_meta, seq_meta, ids = carry
            with jax.named_scope("diffusion/denoise"):
                logits, cache, *fused = forward(
                    params, cache, jnp.concatenate([ids, jnp.full((T, ), mask_id, ids.dtype)]),
                    *self._two_blocks(tok_meta, seq_meta), slice(T, 2 * T))
            tok_meta, seq_meta = self._next_block(tok_meta, seq_meta)
            ids, masked, taken, conf0 = unmask(logits, ids, valid, jnp.full((T, ), -1, jnp.int8),
                                               jnp.int8(0))
            cache, ids, taken, conf, banks = steps(cache, tok_meta, seq_meta, ids, masked, taken, 1)
            if banks and not fused:
                raise ValueError(
                    f"a block loop over {T} rows routes by sorting and counts the banks it "
                    f"reads; its fused forward of {2 * T} rows takes the capacity path "
                    f"(modules/heuristics.py:moe_implementation) and counts none")
            # the banks are counted where the bucket's own forwards count them
            banks = tuple(b + f for b, f in zip(banks, fused))
            return (cache, tok_meta, seq_meta, ids), \
                (ids, taken, jnp.concatenate([conf0[None], conf]), *banks)

        masked = (jnp.asarray(batch["masked"]) > 0) & valid
        cache, ids, taken, conf, banks = steps(cache, tok_meta, seq_meta, tok_meta[0], masked,
                                               jnp.full((T, ), -1, jnp.int8), 0)
        blocks = [a[None] for a in (ids, taken, conf, *banks)]  # the first block's; then the rest
        if n_blocks > 1:
            (cache, tok_meta, seq_meta, ids), rest = jax.lax.scan(
                next_block, (cache, tok_meta, seq_meta, ids), None, length=n_blocks - 1)
            blocks = [jnp.concatenate(pair) for pair in zip(blocks, rest)]
        with jax.named_scope("diffusion/commit"):
            _, cache, *committed = forward(params, cache, ids, tok_meta, seq_meta, "none")
        ids, taken, conf, *banks = blocks
        banks = tuple(b.at[-1].add(c) for b, c in zip(banks, committed))

        def by_sequence(a):  # [n_blocks, T] -> [T / B, n_blocks * B]
            return a.reshape(n_blocks, T // B, B).transpose(1, 0, 2).reshape(T // B, n_blocks * B)

        # [n_blocks, n_denoise, T] -> [T / B, n_blocks, n_denoise, B]
        conf = conf.reshape(n_blocks, n_denoise, T // B, B).transpose(2, 0, 1, 3)
        return (by_sequence(ids), by_sequence(taken), conf, cache, *banks)

    def _next_block(self, tok_meta, seq_meta):
        """The metadata of the block step one block on: every live row's and
        sequence's position moved by B. numpy in, numpy out (the host's count
        of what the program runs: :meth:`block_loop_counts`); traced arrays in
        the program."""
        B = self.attention_block
        row, column = np.arange(4)[:, None] == 2, np.arange(seq_meta.shape[1]) == 0
        return (tok_meta + B * (row & (tok_meta[3] > 0)).astype(np.int32),
                seq_meta + B * (column & (seq_meta[:, 3:4] > 0)).astype(np.int32))

    def _two_blocks(self, tok_meta, seq_meta):
        """The metadata of ONE forward over a block step's rows and, behind
        them, the rows of the block after (``[4, 2T]``, ``[2S, ...]``): the
        second block as a batch of its own — its sequences are entries ``S ..
        2S - 1``, each with the block table of its first-block entry and
        ``seq_seen`` B further — so that the paged kernel's tile grid takes each
        block as the few-row pass it always was. A tile's passes run in the
        order of the entries and a pass's insert has landed before the next
        pass walks (``ops/pallas/paged_attention.py:_tiled_kernel``, ``landed``),
        the grid's tiles run in order, and the XLA arm scatters every row before
        it gathers: the second block's queries see the first block's K/V of
        this same forward."""
        xp = np if isinstance(tok_meta, np.ndarray) else jnp
        T, S = tok_meta.shape[1], seq_meta.shape[0]
        tok_next, seq_next = self._next_block(tok_meta, seq_meta)
        tok_next = tok_next + S * (np.arange(4)[:, None] == 1).astype(np.int32)  # its own entry
        seq_next = seq_next + T * (np.arange(seq_meta.shape[1]) == 2).astype(np.int32)  # last_tok
        return (xp.concatenate([tok_meta, tok_next], axis=1),
                xp.concatenate([seq_meta, seq_next], axis=0))

    def block_loop_counts(self, ragged_batch, n_blocks: int) -> dict:
        """What a block loop of ``n_blocks`` blocks says on its span of the
        forwards its program runs (:meth:`_block_loop_impl`): ``steps``, the
        forwards of the whole batch, ``n_blocks * denoising_steps + 1``, of
        which ``fused_commits`` (``n_blocks - 1``) carry two blocks a
        sequence; and :meth:`dispatch_counts` / :meth:`batch_counts` summed
        over both kinds — the fused forward's ``2T`` rows route twice the
        assignments, and its passes on the tile grid are those of the metadata
        it is fed (:meth:`_two_blocks`). ``moe_path`` is the bucket's own."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        tok, seq = np.asarray(batch["tok_meta"]), np.asarray(batch["seq_meta"])
        live, fused = int((tok[3] > 0).sum()), n_blocks - 1
        alone = n_blocks * self._config.denoising_steps + 1 - fused
        counts = {"steps": alone + fused, "fused_commits": fused}
        two = dict(zip(("tok_meta", "seq_meta"), self._two_blocks(tok, seq)))
        for k, rows, fed in ((alone, live, batch), (fused, 2 * live, two)):
            if not k:
                continue
            n_padded = fed["tok_meta"].shape[1]
            for name, n in {**self.dispatch_counts(n_padded, rows, k),
                            **self.batch_counts(fed, k)}.items():
                counts[name] = counts.get(name, n) if isinstance(n, str) else counts.get(name, 0) + n
        return counts

    @property
    def _slot_columns(self) -> int:
        """Columns of ``seq_meta`` behind the block tables: a sequence's slot
        in the per-sequence state group, where the model has one."""
        return 1 if self.sequence_state else 0

    def _bucket_of(self, batch):
        """``(T, S, MB)`` of a packed batch: the jit cache key."""
        seq_meta = batch["seq_meta"]
        return (batch["tok_meta"].shape[1], seq_meta.shape[0],
                (seq_meta.shape[1] - 4 - self._slot_columns) // self.kv_groups)

    def _unpack_batch(self, batch):
        """Packed [4,T]/[S,4+G*MB] metadata → the named per-field views (built
        inside jit: free slices, no extra transfers). ``block_table`` is
        ``[S, MB]``, or ``[S, G, MB]`` for a model with G > 1 KV layer groups
        (:meth:`_kv_view` hands a layer its own). A model with a per-sequence
        state group reads ``state_slot`` [S] from the column behind the
        tables, and ``one_token_rows``: the step is ``decode_loop``'s, every
        sequence feeds one token and token i is sequence i's."""
        tok, seq = batch["tok_meta"], batch["seq_meta"]
        out = dict(input_ids=tok[0], token_seq=tok[1], token_pos=tok[2],
                   token_valid=tok[3].astype(bool), seq_seen=seq[:, 0],
                   seq_ntok=seq[:, 1], last_tok=seq[:, 2],
                   seq_valid=seq[:, 3].astype(bool), block_table=seq[:, 4:])
        if self._slot_columns:
            out.update(block_table=seq[:, 4:-1], state_slot=seq[:, -1],
                       one_token_rows=bool(batch.get("one_token_rows", False)))
        if self.kv_groups > 1:
            out["block_table"] = out["block_table"].reshape(seq.shape[0], self.kv_groups, -1)
        return out

    def _forward_impl(self, params, cache, batch, rows="last"):
        """One ragged forward. ``rows``: which rows are unembedded — each
        sequence's ``last`` token (a step that yields one token a sequence),
        ``all`` of the batch's (a denoise forward of a block step: ``[T,
        vocab]``), ``none`` (a block's commit: the K/V is all it is for, and
        the logits are None), or a ``slice`` of the batch's rows (a block
        loop's fused forward: the rows that still want a token, behind the
        rows that are only committed)."""
        from deepspeed_tpu.inference.v2.quantization import dequantize_tree

        params = dequantize_tree(params)  # no-op without quantized leaves
        batch = self._unpack_batch(batch)
        # an expert layer that routes by sorting appends the banks it touched
        # (``RaggedMoE``'s ``banks_out``); where any did, the program returns
        # them with the kernel's visits (``moe_count_names``), int32 [expert
        # layers, counts], as one more output
        banks = batch["moe_banks"] = []
        x = self.embed(params, batch["input_ids"])
        attn = partial(self._paged_attention, batch=batch)
        for li in range(self.num_layers):
            x, cache = self.layer_forward(params, li, x, cache, attn, batch)
        if isinstance(rows, slice):
            x = x[rows]
        if rows == "none":
            logits = None
        else:
            # unembed ONLY each sequence's last token (reference logits_gather)
            x_last = x[batch["last_tok"]] if rows == "last" else x
            logits = self.unembed(params, x_last).astype(jnp.float32)
        return (logits, cache, jnp.stack(banks)) if banks else (logits, cache)

    # ----------------------------------------------------- speculative verify --
    def _verify_key(self, dev, greedy):
        """``("verify", bucket, tree, greedy)``: the two facts that pick the
        verify program at a bucket are named in its key."""
        return ("verify", self._bucket_of(dev), "tree_meta" in dev, bool(greedy))

    def forward_verify(self, ragged_batch, greedy: bool = False):
        """The speculative verify step: the layer compute of :meth:`forward`
        with EVERY fed position unembedded, so one ragged pass prices each
        sequence's next-input token plus its drafts. Which program runs is read
        off the batch: one that carries ``tree_meta`` (the ragged wrapper packs
        it when a sequence was inserted with ``tree=``) takes the ancestor
        mask — a node attends to the committed prefix plus its own root path,
        so sibling branches sharing the batch cannot see each other — and one
        without it is a plain causal feed through :meth:`_paged_attention`,
        the arm :meth:`forward` would take at that bucket.

        Returns device arrays ``(rows_or_ids, hidden)``: float32 logits ``[T,
        vocab]`` (row t scores the token AFTER batch position t's path) or,
        with ``greedy``, their int32 argmax ``[T]`` (``T * 4`` bytes to fetch
        instead of ``T * vocab * 4``), and the final residual ``[T, hidden]``
        float32 a learned draft head reads. KV is written for every fed
        position, wrong drafts included (a tree node at slot ``seen +
        node_index``); the caller keeps the accepted path and truncates the
        rest (``engine_v2.compact_accepted``)."""
        batch = ragged_batch.device_batch if hasattr(ragged_batch, "device_batch") else ragged_batch
        dev = {k: batch[k] for k in ("tok_meta", "seq_meta", "tree_meta") if k in batch}
        fn = self._program("verify", self._verify_key(dev, greedy))
        out, hidden, new_cache = fn(self._params, self._state_manager.kv_cache.cache, dev)
        self._state_manager.kv_cache.set_cache(new_cache)
        return out, hidden

    def _verify_impl(self, params, cache, batch, *, greedy):
        """:meth:`_forward_impl` minus the last-token gather. With
        ``tree_meta`` the packed ``token_pos`` is the KV SLOT (``seen +
        node_index``) and the model sees the LOGICAL position ``seen + depth``
        (rotary embeddings encode tree depth, not slot); the attention closure
        keeps the slots for the cache scatter."""
        from deepspeed_tpu.inference.v2.quantization import dequantize_tree

        params = dequantize_tree(params)
        tree_meta = batch.get("tree_meta")
        batch = self._unpack_batch(batch)
        if tree_meta is None:
            attn = partial(self._paged_attention, batch=batch)
        else:
            tree_meta = jnp.asarray(tree_meta)
            parents, depths = tree_meta[0], tree_meta[1]
            slot_pos = batch["token_pos"]
            batch = dict(batch,
                         token_pos=batch["seq_seen"][batch["token_seq"]] + depths)
            attn = partial(self._tree_paged_attention, batch=batch,
                           slot_pos=slot_pos, parents=parents, depths=depths)
        x = self.embed(params, batch["input_ids"])
        for li in range(self.num_layers):
            x, cache = self.layer_forward(params, li, x, cache, attn, batch)
        hidden = x.astype(jnp.float32)  # pre-final-norm residual, token-major
        logits = self.unembed(params, x).astype(jnp.float32)
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), hidden, cache
        return logits, hidden, cache

    # -------------------------------------------------------- paged attention --
    @property
    def attention_window(self) -> int:
        """The one sliding attention window of a model whose layers all see
        alike, in tokens; 0 = full causal (mistral sets it via its model
        config). What a LAYER sees is :meth:`attention_window_of`."""
        return 0

    def attention_window_of(self, li: int) -> int:
        """Layer ``li``'s sliding window in tokens; 0 = every earlier key."""
        return self.attention_window

    @property
    def group_windows(self) -> Tuple[int, ...]:
        """The window of each KV layer group. Layers are grouped by position
        in the shortest period of the per-layer windows: layer ``li`` is in
        group ``li % len(group_windows)`` (``ragged/kv_cache.py``). One window
        for every layer — none included — is one group."""
        if self._group_windows is None:
            windows = [int(self.attention_window_of(li)) for li in range(self.num_kv_layers)]
            period = next(p for p in range(1, len(windows) + 1)
                          if len(windows) % p == 0
                          and all(w == windows[i % p] for i, w in enumerate(windows)))
            self._group_windows = tuple(windows[:period])
        return self._group_windows

    @property
    def kv_groups(self) -> int:
        return len(self.group_windows)

    def _kv_view(self, batch, li):
        """Layer ``li``'s block table ``[S, MB]`` and its layer index in the
        cache array."""
        groups = self.kv_groups
        if groups == 1:
            return batch["block_table"], li
        return batch["block_table"][:, li % groups], li // groups

    def dispatch_counts(self, n_padded: int, n_tokens: int, steps: int = 1) -> dict:
        """Work counters of one ``put`` step, or of the ``steps`` of one
        ``decode_loop`` chunk, over an ``n_padded``-token bucket holding
        ``n_tokens`` live tokens, for the dispatch's span (sparse models: the
        path to the experts, expert rows computed and assignments routed)."""
        return {}

    def moe_path(self, n_padded: int):
        """How an ``n_padded``-token bucket's program reaches its experts
        (``grouped`` / ``capacity``); None for a model without experts."""
        return None

    def attention_arm(self, T: int) -> str:
        """The attention arm a ``T``-token bucket takes (``paged_token`` /
        ``paged_tiled`` / ``xla_gather``); delegates to the heuristics layer
        (reference modules/heuristics.py:36-165)."""
        from deepspeed_tpu.inference.v2.modules.heuristics import attention_implementation
        return attention_implementation(self, self._engine_config, T)

    def _paged_attention(self, q, k_new, v_new, cache, li, *, batch):
        """Insert the new K/V into the paged cache and attend each query token
        to its sequence's history (the last ``attention_window`` keys of it for
        a sliding-window model): the Pallas kernel walking the block table
        (grid over tokens for a decode bucket, over query tiles for a larger
        one), or the XLA arm (scatter, then gather per-sequence K/V). Table
        entries of blocks the window has passed may be released (-1): the
        kernel never walks them, the XLA arm masks what it gathers for them.

        q: [T, H, D]; k_new/v_new: [T, KVH, D];
        cache: [L / groups, 2, num_blocks, KVH, bs, D]. Window, block table
        and cache layer are layer ``li``'s own (:meth:`_kv_view`)."""

        token_pos = batch["token_pos"]
        window = self.attention_window_of(li)
        table, li = self._kv_view(batch, li)
        # a block mask is one more static argument of the kernels, named only
        # where the model has one
        masked = {"block": self.attention_block} if self.attention_block else {}

        # scopes (under the caller's ``attn``): ``paged_kernel`` / ``kv_write``
        # + ``gather`` name the arm a device operation belongs to in the trace
        arm = self.attention_arm(q.shape[0])
        if arm != "xla_gather":
            # fused KV-insert + blocked attention; the cache is aliased through
            # the kernel (an XLA-side scatter would copy it at the boundary)
            from jax.sharding import PartitionSpec as P

            from deepspeed_tpu.ops.pallas import paged_attention

            if arm == "paged_tiled":
                update = paged_attention.paged_attention_prefill
                meta = (table, batch["seq_seen"], batch["seq_ntok"], batch["last_tok"])
            else:
                update = paged_attention.paged_attention_update
                meta = (table, batch["token_seq"], token_pos, batch["token_valid"])

            def kernel(q, k_new, v_new, cache, *meta):
                return update(q, k_new, v_new, cache, li, *meta, window=window, **masked)

            args = (q, k_new, v_new, cache) + meta
            placed = None if self._state_manager is None else self._state_manager.kv_cache.sharding
            with jax.named_scope("paged_kernel"):
                if placed is None or placed.mesh.size == 1:
                    return kernel(*args)
                # the SPMD partitioner cannot split a Mosaic kernel: on a mesh
                # each device runs it over what the cache's placement gives it —
                # its KV heads under tensor parallelism, everything under expert
                # parallelism
                heads = P(None, placed.spec[3], None)
                return jax.shard_map(kernel, mesh=placed.mesh,
                                     in_specs=(heads, heads, heads, placed.spec,
                                               P(), P(), P(), P()),
                                     out_specs=(heads, placed.spec), check_vma=False)(*args)

        cache = self._kv_write(cache, li, k_new, v_new, token_pos, batch, table)
        with jax.named_scope("gather"):
            return self._gather_attention(q, cache, li, batch, table, window, **masked), cache

    @staticmethod
    def _kv_write(cache, li, k_new, v_new, slot_pos, batch, table):
        """Scatter the new K/V into cache layer ``li``'s blocks at ``slot_pos``
        (``table``: that layer's block table)."""

        with jax.named_scope("kv_write"):
            MB = table.shape[1]
            NB, bs = cache.shape[2], cache.shape[4]
            blk_idx = slot_pos // bs
            blk_ids = table[batch["token_seq"], jnp.minimum(blk_idx, MB - 1)]
            # padding tokens and unallocated (-1) table slots route to NB — a
            # POSITIVE out-of-bounds index: scatter mode="drop" discards those
            # writes, whereas -1 would WRAP to block NB-1 and corrupt it
            blk_ids = jnp.where(batch["token_valid"] & (blk_ids >= 0), blk_ids, NB)
            offs = slot_pos % bs
            cache = cache.at[li, 0, blk_ids, :, offs].set(k_new.astype(cache.dtype), mode="drop")
            return cache.at[li, 1, blk_ids, :, offs].set(v_new.astype(cache.dtype), mode="drop")

    def _gather_history(self, cache, li, table, dtype):
        """Each sequence's keys and values of cache layer ``li``, gathered
        through its block ``table`` (released entries read block 0: the
        caller masks them): two ``[S, MB * bs, H, D]`` arrays of ``dtype``,
        the KV heads repeated to the query heads'."""

        S, MB = table.shape
        KVH, D = self.num_kv_heads, self.head_dim
        table = jnp.maximum(table, 0)  # [S, MB]
        k_hist = cache[li, 0][table]  # [S, MB, KVH, bs, D]
        v_hist = cache[li, 1][table]
        KV = MB * cache.shape[4]
        k_hist = k_hist.transpose(0, 2, 1, 3, 4).reshape(S, KVH, KV, D) \
            .transpose(0, 2, 1, 3).astype(dtype)
        v_hist = v_hist.transpose(0, 2, 1, 3, 4).reshape(S, KVH, KV, D) \
            .transpose(0, 2, 1, 3).astype(dtype)
        if KVH != self.num_heads:  # GQA
            rep = self.num_heads // KVH
            k_hist = jnp.repeat(k_hist, rep, axis=2)
            v_hist = jnp.repeat(v_hist, rep, axis=2)
        return k_hist, v_hist

    def _gather_attention(self, q, cache, li, batch, table, window, block=0):
        """The XLA arm: gather each sequence's history from the layer's block
        ``table`` and attend densely under its ``window``, or with ``block`` >
        0 up to the end of each query's block. q: [T, H, D]; returns
        [T, H, D]."""

        S = table.shape[0]
        H, D = self.num_heads, self.head_dim
        token_seq = batch["token_seq"]
        token_pos = batch["token_pos"]
        token_valid = batch["token_valid"]
        k_hist, v_hist = self._gather_history(cache, li, table, q.dtype)
        KV = k_hist.shape[1]

        # --- densify queries per sequence ------------------------------------
        local_q = token_pos - batch["seq_seen"][token_seq]
        Qm = int(np.max([1, q.shape[0]]))  # dense q rows per seq, bounded by T
        q_dense = jnp.zeros((S, Qm, H, D), q.dtype)
        seq_ids = jnp.where(token_valid, token_seq, S)  # OOB drop for padding
        q_dense = q_dense.at[seq_ids, jnp.minimum(local_q, Qm - 1)].set(q, mode="drop")

        scale = 1.0 / (D**0.5)
        logits = jnp.einsum("sqhd,skhd->shqk", q_dense, k_hist).astype(jnp.float32) * scale
        kv_pos = jnp.arange(KV)[None, None, None, :]              # [1,1,1,KV]
        q_pos = (batch["seq_seen"][:, None] + jnp.arange(Qm)[None, :])[:, None, :, None]
        if block:
            q_pos = q_pos | (block - 1)
        valid_kv = kv_pos <= q_pos                                # causal incl. self
        seq_len = (batch["seq_seen"] + batch["seq_ntok"])[:, None, None, None]
        valid_kv &= kv_pos < seq_len
        if window > 0:  # the layer's sliding window
            valid_kv &= kv_pos > q_pos - window
        logits = jnp.where(valid_kv, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out_dense = jnp.einsum("shqk,skhd->sqhd", probs, v_hist)

        # --- back to token-major ---------------------------------------------
        out = out_dense[token_seq, jnp.minimum(local_q, Qm - 1)]  # [T, H, D]
        return jnp.where(token_valid[:, None, None], out, 0.0)

    def _tree_paged_attention(self, q, k_new, v_new, cache, li, *, batch,
                              slot_pos, parents, depths):
        """Tree-attention over the paged cache: each query node sees the
        committed prefix plus its ANCESTOR-OR-SELF nodes only — sibling draft
        branches sharing the feed are mutually invisible. New K/V scatter at
        SLOT positions (``seen + node_index``, distinct per node) while
        ``batch["token_pos"]`` already carries the LOGICAL (depth-based)
        positions the rotary embedding consumed.

        Every query node attends a PER-QUERY virtual KV view in which its
        depth-d ancestor occupies kv index ``seen + d`` — the slot a causal
        feed of that root path would write — so the masked logits, softmax
        and value contraction see the operands the causal program sees for
        that path, at the same indices (the two programs agree to float32
        rounding, not bitwise: XLA orders each program's sums itself). The
        view is a gather of the shared history — ``Qm`` is a handful of
        draft nodes, so the duplication is bounded by the tree budget.

        Always the XLA fallback path: the Pallas paged kernel assumes a
        contiguous causal feed and cannot express the ancestor view."""

        T = q.shape[0]
        window = self.attention_window_of(li)
        table, li = self._kv_view(batch, li)
        S = table.shape[0]
        H, D = self.num_heads, self.head_dim

        token_seq = batch["token_seq"]
        token_valid = batch["token_valid"]

        # --- scatter new kv at slot positions --------------------------------
        cache = self._kv_write(cache, li, k_new, v_new, slot_pos, batch, table)
        k_hist, v_hist = self._gather_history(cache, li, table, q.dtype)
        KV = k_hist.shape[1]

        # --- densify queries + tree metadata per sequence --------------------
        local_q = slot_pos - batch["seq_seen"][token_seq]  # node index in feed
        Qm = int(np.max([1, T]))
        seq_ids = jnp.where(token_valid, token_seq, S)  # OOB drop for padding
        row = jnp.minimum(local_q, Qm - 1)
        q_dense = jnp.zeros((S, Qm, H, D), q.dtype).at[seq_ids, row].set(q, mode="drop")
        parent_dense = jnp.full((S, Qm), -1, jnp.int32) \
            .at[seq_ids, row].set(parents.astype(jnp.int32), mode="drop")
        depth_dense = jnp.zeros((S, Qm), jnp.int32) \
            .at[seq_ids, row].set(depths.astype(jnp.int32), mode="drop")

        # --- ancestors by depth: abd[s, i, d] = node on i's root path at
        # depth d, or -1. Parent pointers are topological (parent < child), so
        # Qm hops of pointer-chasing reach every ancestor.
        s_ix = jnp.arange(S)[:, None]
        i_ix = jnp.arange(Qm)[None, :]

        def _hop(_, carry):
            abd, cur = carry
            d = jnp.take_along_axis(depth_dense, jnp.clip(cur, 0, Qm - 1), axis=1)
            abd = abd.at[s_ix, i_ix, jnp.where(cur >= 0, d, Qm)].set(
                jnp.maximum(cur, -1), mode="drop")
            nxt = jnp.take_along_axis(parent_dense, jnp.clip(cur, 0, Qm - 1), axis=1)
            return abd, jnp.where(cur >= 0, nxt, -1)

        abd, _ = jax.lax.fori_loop(
            0, Qm, _hop,
            (jnp.full((S, Qm, Qm), -1, jnp.int32),
             jnp.tile(jnp.arange(Qm, dtype=jnp.int32)[None, :], (S, 1))))

        # --- per-query virtual KV: committed slots pass through; feed slot
        # seen+d resolves to the query's depth-d ancestor's slot -------------
        kvr = jnp.arange(KV)
        seen_v = batch["seq_seen"]
        d_of_kv = kvr[None, :] - seen_v[:, None]                     # [S, KV]
        in_feed = (d_of_kv >= 0) & (d_of_kv < Qm)
        node = abd[jnp.arange(S)[:, None, None],
                   jnp.arange(Qm)[None, :, None],
                   jnp.clip(d_of_kv, 0, Qm - 1)[:, None, :]]         # [S, Qm, KV]
        src = jnp.where(in_feed[:, None, :],
                        jnp.where(node >= 0, seen_v[:, None, None] + node, KV),
                        kvr[None, None, :])                          # [S, Qm, KV]
        src_c = jnp.clip(src, 0, KV - 1)
        k_q = k_hist[jnp.arange(S)[:, None, None], src_c]            # [S, Qm, KV, H, D]
        v_q = v_hist[jnp.arange(S)[:, None, None], src_c]

        scale = 1.0 / (D**0.5)
        logits = jnp.einsum("sihd,sikhd->shik", q_dense, k_q).astype(jnp.float32) * scale
        # visibility: committed prefix, or an existing ancestor-or-self at the
        # depth slot; the logical kv position of feed slot seen+d IS seen+d,
        # so the sliding window applies to the raw kv index either way
        valid_kv = (kvr[None, None, :] < seen_v[:, None, None]) | \
            (in_feed[:, None, :] & (node >= 0))                      # [S, Qm, KV]
        if window > 0:
            q_log = seen_v[:, None] + depth_dense                    # [S, Qm]
            valid_kv &= kvr[None, None, :] > q_log[:, :, None] - window
        logits = jnp.where(valid_kv[:, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out_dense = jnp.einsum("shik,sikhd->sihd", probs, v_q)

        # --- back to token-major ---------------------------------------------
        out = out_dense[token_seq, jnp.minimum(local_q, Qm - 1)]  # [T, H, D]
        out = jnp.where(token_valid[:, None, None], out, 0.0)
        return out, cache

    # ---------------------------------------------------------- kv compaction --
    def compact_kv(self, seq_desc: DSSequenceDescriptor, src_slots, dst_slots) -> None:
        """Copy KV at ``src_slots`` to ``dst_slots`` (absolute token slots of
        ``seq_desc``) across every layer and both K/V in ONE jitted
        gather-then-scatter — the tree-verify accepted-path re-pack: accepted
        nodes live at scattered slots ``seen0 + node_index`` and must land at
        contiguous ``seen0 + 1..m`` before the rejected tail is truncated.
        The gather reads the pre-copy cache, so overlapping src/dst pairs are
        safe. Jitted per pow2-padded copy count; padded pairs scatter to an
        out-of-range block and drop."""

        self._state_manager.kv_cache.refuse("compact_kv")
        src = np.asarray(src_slots, np.int64).reshape(-1)
        dst = np.asarray(dst_slots, np.int64).reshape(-1)
        if src.size != dst.size:
            raise ValueError("compact_kv needs matching src/dst slot lists")
        if src.size == 0:
            return
        bs = self._state_manager.kv_block_size
        # the slots are the feed's own: never released. A block id holds one
        # layer group's layers, so each pair is copied once a group, through
        # that group's table
        tables = seq_desc.block_tables
        n = src.size * tables.shape[0]
        NB = self._state_manager.kv_cache.cache.shape[2]
        P = _pow2_pad(n, 2)
        src_blk = np.zeros(P, np.int32)
        src_off = np.zeros(P, np.int32)
        dst_blk = np.full(P, NB, np.int32)  # pad -> positive OOB -> drop
        dst_off = np.zeros(P, np.int32)
        src_blk[:n] = tables[:, src // bs].reshape(-1)
        src_off[:n] = np.tile(src % bs, tables.shape[0])
        dst_blk[:n] = tables[:, dst // bs].reshape(-1)
        dst_off[:n] = np.tile(dst % bs, tables.shape[0])

        new_cache = self._program("compact", ("compact", P))(
            self._state_manager.kv_cache.cache, src_blk, src_off, dst_blk, dst_off)
        self._state_manager.kv_cache.set_cache(new_cache)

    @staticmethod
    def _compact_impl(cache, src_blk, src_off, dst_blk, dst_off):
        # advanced indexing at axes 2 (block) and 4 (offset) puts the pair
        # axis first: vals[p, l, kv, h, d]
        vals = cache[:, :, src_blk, :, src_off]
        return cache.at[:, :, dst_blk, :, dst_off].set(vals, mode="drop")

    # ------------------------------------------------------------- serialize --
    def flattened_params(self):
        return jax.tree.leaves(self._params)

    # Subclass hooks -----------------------------------------------------------
    @jax.named_scope("embed")
    def embed(self, params, ids):
        """The tokens' rows of the embedding, times the config's
        ``embedding_multiplier`` where it has one (on the float32 product)."""
        cfg = self._config
        rows = _root(params)["embed_tokens"]["embedding"][ids]
        multiplier = getattr(cfg, "embedding_multiplier", None)
        if multiplier is None:
            return rows.astype(cfg.dtype)
        return (rows.astype(jnp.float32) * multiplier).astype(cfg.dtype)

    def layer_forward(self, params, li, x, cache, attn_fn, batch):
        raise NotImplementedError

    @jax.named_scope("unembed")
    def unembed(self, params, x):
        r = _root(params)
        norm, eps = self.final_norm
        x = _rms(x, r[norm]["weight"], getattr(self._config, eps))
        return x @ r["lm_head"]["kernel"].astype(x.dtype)
