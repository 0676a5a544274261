"""FastGen ragged inference engine.

Reference: ``deepspeed/inference/v2/engine_v2.py`` (InferenceEngineV2:32 —
``put()``:135 inserts ragged sequences and runs one forward; ``query``/
``can_schedule`` token/KV-block occupancy logic; ``flush``; ``serialize``; the
fork's ``empty_run``:308 participating in EP collectives with zero tokens).

TPU execution model: the engine composes a :class:`RaggedBatchWrapper` on the
host, the model runs ONE jitted program per padded batch *bucket* (static
shapes), and the paged KV cache flows through the program functionally
(donated). TP/EP sharding is carried by the global device mesh
(``deepspeed_tpu.utils.groups``) — param/activation sharding constraints inside
the model program replace the reference's explicit process-group collectives.
"""

import json
import os
from typing import Iterable, List, Optional, Tuple

import numpy as np

from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.ragged.ragged_manager import DSStateManager
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import RaggedBatchWrapper
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import PlaceholderSequenceDescriptor
from deepspeed_tpu.inference.v2.scheduling_utils import SchedulingError, SchedulingResult
from deepspeed_tpu.telemetry import get_span_recorder as _tel_get_spans
from deepspeed_tpu.telemetry import is_active as _tel_is_active
from deepspeed_tpu.telemetry import live_span as _tel_live_span
from deepspeed_tpu.telemetry import now_us as _tel_now_us
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.logging import logger


class DecodeChunk:
    """A ``decode_loop`` chunk on the device, not fetched
    (:meth:`InferenceEngineV2.dispatch_decode_loop`). ``tokens`` is the
    program's device ``[n_steps, S_bucket]`` result, column i sequence i of
    the batch; ``ids`` its last row, taken on the device: what a later
    ``put_draw`` or chunk takes as ``prev``'s ids, so the step after a chunk
    can be dispatched while the chunk runs. :meth:`fetch` (``np.asarray`` of
    the chunk is the same) is the wait for the device."""

    __slots__ = ("tokens", "n_seqs", "_ids", "_banks", "_args", "_count")

    def __init__(self, tokens, n_seqs, banks=None, args=None, count=None):
        self.tokens, self.n_seqs = tokens, n_seqs
        # how the banks array reads as span args (``InferenceEngineV2.moe_counts``)
        self._count = count
        self._ids = None
        # on the grouped path the banks its routing touched, int32 [n_steps,
        # expert layers], and under a telemetry session the chunk's
        # ``decode_loop`` span args (only then are the banks ever read: their
        # copy to the host is under way)
        self._banks, self._args = banks, args

    @property
    def ids(self):
        if self._ids is None:
            from deepspeed_tpu.inference.v2 import sampling
            self._ids = sampling.last_row(self.tokens)
        return self._ids

    def fetch(self) -> np.ndarray:
        """The generated tokens ``[n_seqs, n_steps]`` on the host. The chunk's
        span learns here what only the fetch can say (the ring keeps a span's
        args dict, so what is written now is read with the span):
        ``fetch_us``, the blocking transfer, and on the grouped path
        ``moe_banks``, the banks touched over the chunk's steps and expert
        layers — 4 bytes a layer-step that came out behind the tokens."""
        return self._to_host(self.tokens)[:, :self.n_seqs].T

    def _to_host(self, result) -> np.ndarray:
        args, self._args = self._args, None
        t0 = _tel_now_us()
        out = np.asarray(result)
        if args is not None:
            args["fetch_us"] = _tel_now_us() - t0
            if self._banks is not None:
                args.update(self._count(self._banks))
        return out

    def __array__(self, dtype=None, copy=None):
        return self.fetch()


class BlockChunk(DecodeChunk):
    """A block loop's chunk on the device, not fetched
    (:meth:`InferenceEngineV2.dispatch_block_loop`): ``tokens`` is the
    program's ``[rows, n_blocks * B]`` ids, row i sequence i of the batch, and
    beside them the int8 denoise step at which each position took its token
    (-1: it was given). :meth:`fetch` returns the ids ``[n_seqs, n_blocks *
    B]`` and leaves the steps in :attr:`steps`; :attr:`confidences` fetches,
    for whoever asks (a check; no serving path does), what the rows were chosen
    on: float32 ``[n_seqs, n_blocks, denoising_steps, B]``, each still-masked
    row's confidence behind each denoise forward, -1 where the row had its
    token. The chunk after it needs none
    of its tokens (a block starts all masked): there is no ``ids`` to hand on.
    :meth:`note` adds to the chunk's ``block_loop`` span what its caller
    learns later (the tokens it kept, the tick that dispatched it)."""

    __slots__ = ("steps", "_taken", "_conf", "_span")

    def __init__(self, tokens, taken, conf, n_seqs, banks=None, args=None, count=None):
        super().__init__(tokens, n_seqs, banks, args, count)
        self._taken, self._conf, self._span, self.steps = taken, conf, args, None

    @property
    def confidences(self) -> np.ndarray:
        return np.asarray(self._conf)[:self.n_seqs]

    @property
    def ids(self):
        raise ValueError("a block loop's chunk hands no ids on: the next block starts masked")

    def fetch(self) -> np.ndarray:
        ids = self._to_host(self.tokens)[:self.n_seqs]
        self.steps = np.asarray(self._taken)[:self.n_seqs]
        return ids

    def note(self, **args) -> None:
        if self._span is not None:
            self._span.update(args)


class InferenceEngineV2:

    def __init__(self, model, engine_config: RaggedInferenceEngineConfig) -> None:
        """``model`` is a built :class:`DSTransformerModelBase` subclass (the
        engine_factory constructs it from a policy; the reference builds it from
        ``policy.build_model`` — here the model consumes training pytrees
        directly so no container-mapping step exists)."""
        self._config = engine_config

        if engine_config.simulated_gating:
            from deepspeed_tpu.inference.v2.modules.moe import enable_simulated_gating
            enable_simulated_gating(engine_config.simulated_gating_temperature)

        self._model = model
        self._initialize_comm_groups()
        self._place_params()

        kv_config = model.kv_cache_config()
        self._batch = RaggedBatchWrapper(engine_config.state_manager,
                                         block_size=engine_config.kv_block_size,
                                         num_groups=kv_config.num_allocation_groups,
                                         min_table_bucket=kv_config.min_table_bucket,
                                         min_sequence_bucket=kv_config.min_sequence_bucket,
                                         min_token_bucket=kv_config.min_token_bucket,
                                         attention_block=kv_config.attention_block,
                                         state_slots=kv_config.sequence_slots)
        self._state_manager = DSStateManager(engine_config.state_manager, kv_config)
        self._model.set_state_manager(self._state_manager)

        # unified telemetry (telemetry/): batch/token/KV gauges + spans +
        # optional /metrics //healthz endpoint, startable purely from config
        self._telemetry = None
        self._tel_metrics = None
        if engine_config.telemetry.enabled:
            from deepspeed_tpu import telemetry
            self._telemetry = telemetry.configure(engine_config.telemetry)
            self._tel_metrics = self._build_tel_metrics(self._telemetry.registry)

        # a ServingScheduler attaches here (serving/scheduler.py); close()
        # stops it so the engine can always be torn down safely
        self._serving_scheduler = None

        # cost-attribution hook (telemetry/ledger.py + perf/observed.py): a
        # scheduler with an active telemetry session installs a callable
        # ``(kind, n_seqs, n_tokens, wall_seconds)`` invoked around every
        # jitted dispatch (put / decode_loop / verify_tree). None —
        # the default, and always the case with telemetry off — costs one
        # attribute load per dispatch.
        self.dispatch_observer = None

        # rolling release (sliding-window models): blocks given back to the
        # pool as the window passed them, ever and as of the last ``prepare``
        # span (which reports the difference as ``released_blocks``)
        self._released_blocks = 0
        self._released_at_prepare = 0
        # how the newest ``put`` step's or ``decode_loop`` chunk's program
        # routed its tokens to experts (``grouped`` / ``capacity``; None for a
        # dense model, or before any)
        self.last_moe_path = None
        # the program the newest ``put`` step or ``decode_loop`` chunk was
        # compiled for: the chunk's steps (0: a ``put`` step) and the padded
        # shapes of the batch it took, ``(steps, T, S, seq_meta columns)`` —
        # what a caller that times steps keys them by (None before any)
        self.last_step_key = None
        # under a telemetry session, what the fetch of the newest ``put`` step
        # reports of its experts where its bucket routes by sorting:
        # ``moe_path``, ``moe_rows``, ``moe_assignments`` and ``moe_banks``,
        # the last the step's own counts, int32 [expert layers, counts], a
        # device array whose copy to the host is under way; else None, and
        # nothing more than the step's result is ever fetched
        self.last_moe_fetch = None

    # ------------------------------------------------------------------ groups --
    def _initialize_comm_groups(self) -> None:
        """Reference engine_v2.py:108 creates TP (and fork: EP-replica) process
        groups; here both are axes of the one global mesh."""
        tp = self._config.tensor_parallel.tp_size
        ep = self._config.expert_parallel.replica_num if self._config.expert_parallel.enabled else 1
        if groups.mesh_is_initialized():
            mesh = groups.get_mesh()
            if tp > 1:
                assert mesh.shape[groups.MODEL_AXIS] == tp, \
                    f"mesh model axis {mesh.shape[groups.MODEL_AXIS]} != tp_size {tp}"
            if ep > 1:
                assert mesh.shape[groups.EXPERT_AXIS] == ep, \
                    f"mesh expert axis {mesh.shape[groups.EXPERT_AXIS]} != replica_num {ep}"
        elif tp > 1 or ep > 1:
            groups.initialize_mesh(model_parallel_size=tp, expert_parallel_size=ep)

    def _place_params(self) -> None:
        """TP>1 and/or EP>1 (incl. TP+EP, which the reference rejects at
        engine_v2.py:85): place the param tree with AutoTP-derived shardings.
        Over ``model`` the SPMD partitioner inserts the per-layer all-reduce the
        reference's ``LinearAllreduce`` modules perform (module_inject/
        layers.py:16); over ``expert`` each chip holds only its own experts'
        banks, which is the layout the EP shard_map consumes — left where the
        caller made them, the banks would sit whole on one device and be
        resharded inside every step. A tree that already has these shardings
        (``init_params(..., mesh=...)``) is not moved."""
        tp = self._config.tensor_parallel.tp_size
        ep = self._config.expert_parallel.replica_num if self._config.expert_parallel.enabled else 1
        if tp <= 1 and ep <= 1:
            return
        import jax
        from jax.sharding import NamedSharding
        from deepspeed_tpu.module_inject.auto_tp import auto_tp_specs

        mesh = groups.get_mesh()
        specs = auto_tp_specs(self._model._params)
        self._model._params = jax.device_put(
            self._model._params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs))
        logger.info(f"inference-v2: AutoTP placed params (tp={tp} over model, "
                    f"ep={ep} over expert)")

    # ------------------------------------------------------------ properties --
    @property
    def free_blocks(self) -> int:
        return self._state_manager.free_blocks

    @property
    def released_blocks(self) -> int:
        """KV blocks given back to the pool by the rolling release of a
        sliding-window model since the engine was built."""
        return self._released_blocks

    @property
    def n_kv_cache_groups(self) -> int:
        """KV layer groups: block tables a sequence keeps (window and full
        layers side by side take one each; ``ragged/kv_cache.py``)."""
        return self._state_manager.num_groups

    @property
    def model(self):
        return self._model

    @property
    def telemetry_session(self):
        return self._telemetry

    @property
    def serving_scheduler(self):
        """The attached :class:`ServingScheduler` (None when not serving)."""
        return self._serving_scheduler

    @property
    def metrics_url(self) -> Optional[str]:
        """The served ``/metrics`` URL (None unless ``telemetry.http.enabled``)."""
        return self._telemetry.metrics_url if self._telemetry is not None else None

    def close(self) -> None:
        """Tear the engine down (idempotent): stop an attached serving
        scheduler and stop the telemetry endpoint / flush sinks."""
        if self._serving_scheduler is not None:
            self._serving_scheduler.stop(drain=False)
            self._serving_scheduler = None
        if self._telemetry is not None:
            self._telemetry.close()
            self._telemetry = None

    # ----------------------------------------------------------------- put() --
    # Spans (cat ``inference``; live only under a telemetry session, and then
    # also ``dstpu.inference.*`` annotations in a jax.profiler trace):
    # ``prepare`` is the host work from entry to just before the jitted call;
    # ``put`` / ``decode_loop`` / ``verify_tree`` time the DISPATCH
    # of that call (plus, where the method itself fetches, the fetch) — not the
    # device: JAX returns before the device finishes, and the caller's
    # ``np.asarray`` is where the wait shows. A ``decode_loop`` span is the
    # launch alone, ``launch_us`` (entry until the jitted call has returned);
    # ``fetch_us`` (0 until then) is written when the chunk is fetched, under
    # whatever span the fetcher is in, and is the blocking transfer's time.
    def _prepare(self, spans, batch_uids, feeds, do_checks, n_tokens, steps=0, trees=None):
        """The host side of one step, under the ``prepare`` span: admission
        check, restore of offloaded sequences, KV allocation and the ragged
        batch. ``feeds``: each sequence's token array. ``steps``: the step is a
        ``decode_loop`` chunk of that many steps (0: one ragged forward)."""
        args = None
        if spans is not None:
            free_before = self._state_manager.free_blocks
            args = self._prepare_args(len(batch_uids), n_tokens)
        with _tel_live_span(spans, "prepare", "inference", args):
            if do_checks:
                # BEFORE restoring: can_schedule counts offloaded sequences'
                # restore cost, so admission failure is a SchedulingError here,
                # never a raw allocator error mid-restore
                schedule_check = self.can_schedule(batch_uids, [t.size for t in feeds], steps)
                if schedule_check != SchedulingResult.Success:
                    raise SchedulingError(schedule_check)
            # touching an offloaded sequence restores it first (ZeRO-Inference
            # KV-offload choreography; see ragged_manager.offload_sequence)
            for uid in batch_uids:
                self._state_manager.restore_sequence(uid)

            self._batch.clear()
            for i, (uid, tokens) in enumerate(zip(batch_uids, feeds)):
                seq_desc = self._state_manager.get_or_create_sequence(uid)
                # a chunk's KV blocks are allocated for the WHOLE generation:
                # the device loop cannot allocate mid-scan, and the block
                # table is static inside it
                self._model.maybe_allocate_kv(seq_desc, steps or tokens.size)
                seq_desc.pre_forward(tokens.size)
                self._batch.insert_sequence(
                    seq_desc, tokens, do_checks=do_checks,
                    tree=None if trees is None else (trees[i].parents, trees[i].depths))

            self._batch.finalize()
            if args is not None:
                args["allocated_blocks"] = free_before - self._state_manager.free_blocks

    def _prepare_args(self, n_sequences: int, n_tokens: int) -> dict:
        """A ``prepare`` span's args: the batch, what the rolling release gave
        back since the last one, and the blocks every tracked sequence holds
        as this step begins, in full-causal and in sliding-window layer
        groups."""
        held = self._live_blocks_by_kind()
        released = self._released_blocks - self._released_at_prepare
        self._released_at_prepare = self._released_blocks
        return {"sequences": n_sequences, "tokens": n_tokens, "released_blocks": released,
                "live_blocks_full": held["full"], "live_blocks_window": held["window"]}

    def _live_blocks_by_kind(self) -> dict:
        """Pool blocks held by tracked, resident sequences: ``full`` in layer
        groups that keep every key, ``window`` in groups under a sliding
        window."""
        held = {"full": 0, "window": 0}
        windows = self._model.group_windows
        for uid, seq in self._state_manager.tracked_sequences.items():
            if self._state_manager.is_offloaded(uid):
                continue
            for group, window in enumerate(windows):
                held["window" if window > 0 else "full"] += seq.live_blocks_in(group)
        return held

    def _telemetry_sinks(self):
        """``(spans, observer, metrics)``: all None with telemetry off and no
        scheduler cost plane attached."""
        # the engine session's recorder, or a globally-configured session's
        # (same fallback policy as :meth:`_resolve_tel_metrics`)
        spans = self._telemetry.spans if self._telemetry is not None else _tel_get_spans()
        return spans, self.dispatch_observer, self._resolve_tel_metrics()

    @staticmethod
    def _dispatch_args(spans, batch_uids, **counts):
        """A dispatch span's ``args`` (None while telemetry is off). The uids
        link the batch span to the per-request serving traces: each uid's
        request track carries the same uid in its args."""
        if spans is None:
            return None
        return dict(counts, sequences=len(batch_uids), uids=[int(u) for u in batch_uids])

    def _dispatch(self, spans, batch_uids, batch_tokens, prev, steps=0):
        """What a ``put`` step or a ``decode_loop`` chunk (``steps`` of it)
        says of the batch just prepared as it is dispatched:
        :attr:`last_moe_path` and :attr:`last_step_key`, the dispatch span's
        args, and ``prev`` by token slot."""
        n_padded = self._batch.device_batch["tok_meta"].shape[1]
        # how the bucket's program routes its tokens to experts (grouped /
        # capacity; None for a dense model): the scheduler counts steps by it
        self.last_moe_path = self._model.moe_path(n_padded)
        self.last_step_key = (steps, n_padded, *self._batch.device_batch["seq_meta"].shape)
        args = None
        if spans is not None:
            n_tokens = int(sum(t.size for t in batch_tokens))
            # the step's live sequences and the sequence count it was padded to
            args = self._dispatch_args(spans, batch_uids, seqs_live=len(batch_uids),
                                       seq_bucket=self._batch.device_batch["seq_meta"].shape[0],
                                       **({"steps": steps} if steps else {"tokens": n_tokens}))
            if not steps:
                # the arm the bucket's program takes (modules/heuristics.py):
                # paged_tiled / paged_token / xla_gather
                args["attention"] = self._model.attention_arm(n_padded)
            # a sparse model's moe_path, moe_rows and moe_assignments (and, on
            # the capacity path, moe_banks: every bank); every step of a chunk
            # routes this bucket
            args.update(self._model.dispatch_counts(n_padded, n_tokens, steps or 1))
            args.update(self._model.batch_counts(self._batch, steps or 1))
        return args, self._prev_by_slot(prev, batch_tokens, n_padded, args)

    def _post_forward(self, batch_uids, steps: int = 1, release: bool = True,
                      unit: int = 1) -> None:
        """Commit the fed tokens (and the ``steps - 1`` a chunk's loop
        inserted behind them, each of ``unit`` positions: a block loop's are
        blocks) and, unless the caller may still roll some back (the verify
        steps: a released block cannot come back), let the model release what
        the window has passed."""
        for uid in batch_uids:
            seq_desc = self._state_manager.get_sequence(uid)
            seq_desc.post_forward()
            if steps > 1:
                seq_desc.pre_forward((steps - 1) * unit)
                seq_desc.post_forward()
            if release:
                self._released_blocks += self._model.maybe_free_kv(seq_desc)

    def put(self, batch_uids: Iterable[int], batch_tokens: Iterable, do_checks: bool = True):
        """Run one ragged forward over ``batch_uids``/``batch_tokens``; returns
        logits ``[len(batch_uids), vocab]`` — each sequence's final token only.
        The logits are a device array still being computed: the ``put`` span
        is the dispatch, the caller's fetch is the wait."""
        return self._put(batch_uids, batch_tokens, do_checks, None)

    def put_draw(self, batch_uids: Iterable[int], batch_tokens: Iterable,
                 temperature, seed, draw_index, do_checks: bool = True, prev=None):
        """:meth:`put`, with each sequence's next token drawn on the device
        (:mod:`~deepspeed_tpu.inference.v2.sampling`): the same forward
        program, the draw dispatched behind it, and device int32 ids
        ``[S_bucket]`` back — entry i is ``batch_uids[i]``'s, the rest is
        padding — in place of ``[n, vocab]`` float32 logits. ``temperature``
        (0 = greedy), ``seed`` and ``draw_index`` (tokens the request has
        emitted over its whole life) hold one entry a sequence; a sequence's
        token depends on its own three and its logits, never on the batch.

        The ids are returned before anything is fetched, and a later call can
        take them as they are: ``prev`` = ``(ids, index)``, the ids an
        earlier ``put_draw`` returned and, for each sequence of THIS batch,
        the entry of them that is its first input token (-1: the token in
        ``batch_tokens`` stands). The merge is one tiny device program in
        front of the bucket's forward program, which is the one
        :meth:`put` runs, under the same cache key; so a step whose decode
        rows continue the step before can be dispatched while that one still
        runs, and the device goes from one into the other. All host
        bookkeeping (KV allocation, ``seen_tokens``, the rolling release) is
        done when the call returns, as for any step.
        :meth:`warm_draw` builds the draw's and the merge's programs ahead of
        the first call."""
        return self._put(batch_uids, batch_tokens, do_checks,
                         (temperature, seed, draw_index), prev)

    def warm_draw(self, chunk_steps: int = 0) -> None:
        """Compile :meth:`put_draw`'s draw for every sequence bucket this
        engine can produce, and its ``prev`` merge for every token bucket
        beside (a ``ServingScheduler`` calls it when it is constructed:
        set-up, never a first request's stall). ``chunk_steps``: the steps of
        the ``decode_loop`` chunks whose last row will be handed on as
        ``prev`` (:attr:`DecodeChunk.ids`): that program too, a bucket each."""
        self._model.warm_draw(chunk_steps)

    def moe_counts(self, banks) -> dict:
        """A grouped program's count of routed work, fetched, as span args:
        ``moe_banks`` and ``moe_visits`` (the grouped kernel's (expert, row
        tile) visits: ``(moe_visits - moe_banks) / moe_visits`` of them are an
        expert's further row tile) summed over the expert layers (and a
        chunk's steps); a model whose layers hold a share of their experts
        counts what landed here too, and the sorted rows it walked
        (``moe_assignments_local``, ``moe_rows_walked``: ``model.moe_count_names``,
        the array's last axis)."""
        counts = np.asarray(banks)
        return {name: int(counts[..., i].sum())
                for i, name in enumerate(self._model.moe_count_names)}

    def _put(self, batch_uids, batch_tokens, do_checks, draw, prev=None):
        batch_uids = list(batch_uids)
        batch_tokens = [np.atleast_1d(np.asarray(t)) for t in batch_tokens]
        spans, observer, metrics = self._telemetry_sinks()
        live = spans is not None or observer is not None or metrics is not None
        n_tokens = int(sum(t.size for t in batch_tokens)) if live else 0

        self._prepare(spans, batch_uids, batch_tokens, do_checks, n_tokens)
        args, prev = self._dispatch(spans, batch_uids, batch_tokens, prev)
        self.last_moe_fetch = None
        with _tel_live_span(spans, "put", "inference", args):
            if observer is not None:
                _t0 = _tel_now_us()
            if draw is None:
                out = self._model.forward(self._batch)
                assert out.shape[0] == self._batch.current_sequences
            else:
                out = self._model.forward_draw(self._batch, *draw, prev=prev)
            if observer is not None:
                observer("put", len(batch_uids), n_tokens, (_tel_now_us() - _t0) / 1e6)
            if args is not None and self._model.last_moe_banks is not None:
                # a grouped step's count of banks touched is the device's to
                # say: its transfer started now, behind the step, and handed
                # over unread for the span of the step's fetch
                banks = self._model.last_moe_banks
                banks.copy_to_host_async()
                self.last_moe_fetch = {"moe_path": args["moe_path"],
                                       "moe_rows": args["moe_rows"],
                                       "moe_assignments": args["moe_assignments"],
                                       "moe_banks": banks}
            self._post_forward(batch_uids)
        if metrics is not None:
            self._write_telemetry(metrics, batch_tokens=n_tokens)
        return out

    @staticmethod
    def _prev_by_slot(prev, batch_tokens, n_padded, args):
        """``prev`` = ``(ids, index)`` a sequence → ``(ids, src)`` a token
        slot: a sequence's FIRST token is the one fed from the device. The
        dispatch span's ``chained`` counts the slots fed so."""
        if prev is None:
            return None
        ids, index = prev
        src = np.full(n_padded, -1, np.int32)
        src[np.cumsum([0] + [t.size for t in batch_tokens[:-1]])] = index
        if args is not None:
            args["chained"] = int((src >= 0).sum())
        return ids, src

    @staticmethod
    def _build_tel_metrics(reg) -> dict:
        return {
            "batches": reg.counter("inference_batches_total", "Ragged batches executed"),
            "tokens": reg.counter("inference_tokens_total", "Tokens scheduled into batches"),
            "in_flight": reg.gauge("inference_in_flight_tokens",
                                   "Tokens in the last ragged batch"),
            "free_blocks": reg.gauge("inference_kv_free_blocks", "Free KV-cache blocks"),
            "tracked": reg.gauge("inference_tracked_sequences", "Sequences tracked"),
            "empty_runs": reg.counter("inference_empty_runs_total",
                                      "EP lock-step forwards with zero tokens"),
            "released": reg.gauge("inference_kv_released_blocks",
                                  "KV blocks a sliding window's rolling release has "
                                  "given back to the pool"),
            **{f"live_{kind}": reg.gauge("inference_kv_group_live_blocks",
                                         "KV blocks tracked sequences hold, by the kind of "
                                         "layer group holding them", labels={"kind": kind})
               for kind in ("full", "window")},
        }

    def _resolve_tel_metrics(self) -> Optional[dict]:
        """The inference_* families — always on the process-wide registry
        (an engine session's registry IS ``telemetry.get_registry()``, the
        singleton). With an engine-owned session the dict is built at init
        and lives until ``close()``; otherwise it is built lazily and
        returned only while a globally-configured session is active (the
        serving quickstart configures telemetry process-wide, not per
        engine), so a ``telemetry.shutdown()`` mid-process stops metric
        writes along with spans. Disabled telemetry costs one boolean check
        here."""
        if self._telemetry is not None:
            return self._tel_metrics
        if not _tel_is_active():
            return None
        if self._tel_metrics is None:
            from deepspeed_tpu import telemetry
            self._tel_metrics = self._build_tel_metrics(telemetry.get_registry())
        return self._tel_metrics

    def _write_telemetry(self, metrics: dict, batch_tokens: int) -> None:
        metrics["batches"].inc()
        metrics["tokens"].inc(batch_tokens)
        metrics["in_flight"].set(batch_tokens)
        metrics["free_blocks"].set(self._state_manager.free_blocks)
        metrics["released"].set(self._released_blocks)
        for kind, n in self._live_blocks_by_kind().items():
            metrics[f"live_{kind}"].set(n)
        metrics["tracked"].set(self._state_manager.n_tracked_sequences)

    # ------------------------------------------------------------ decode_loop --
    def decode_loop(self, batch_uids: Iterable[int], batch_tokens: Iterable,
                    n_steps: int, do_checks: bool = True) -> np.ndarray:
        """Generate ``n_steps`` tokens per sequence in ONE device program (no
        host round-trip per token — see DSTransformerModelBase.decode_loop),
        each the argmax of its logits. ``batch_tokens`` holds each sequence's
        next-input token; returns generated tokens ``[n_seqs, n_steps]``.

        Each entry is ONE token: a feed of several (a next-input token plus
        drafts) is a speculative verify step, :meth:`verify_tree`'s. A
        sampled request is drawn at its own ``(seed, draw_index)`` by
        :meth:`put_draw`, a step at a time.

        EOS is not monitored on device: the loop always runs ``n_steps``; the
        caller trims at the first EOS (the fixed-shape scan is what makes the
        loop a single compiled program).

        This is :meth:`dispatch_decode_loop` fetched at once; a caller that has
        other work for the host while the chunk runs takes the two apart.
        """
        return self.dispatch_decode_loop(batch_uids, batch_tokens, n_steps, do_checks).fetch()

    def dispatch_decode_loop(self, batch_uids: Iterable[int], batch_tokens: Iterable,
                             n_steps: int, do_checks: bool = True, prev=None) -> DecodeChunk:
        """:meth:`decode_loop`, launched and NOT fetched: everything that needs
        only counts is done when the call returns — the checks, the KV blocks
        of all ``n_steps`` tokens, ``seen_tokens``, the rolling release — and
        the :class:`DecodeChunk` holds the program's tokens on the device.
        ``prev`` = ``(ids, index)`` with :meth:`put_draw`'s meaning: sequence
        i's input token is entry ``index[i]`` of ``ids`` — a ``put_draw``'s
        ids or an earlier chunk's :attr:`DecodeChunk.ids`, fetched or not —
        unless ``index[i]`` is -1 (the token in ``batch_tokens`` stands). The
        merge is ``put_draw``'s (one tiny program in front of the chunk's,
        which is the one a host-fed chunk runs, under the same cache key), so
        a chunk that continues the step before is dispatched while that one
        still runs. :meth:`warm_draw` builds the merge, and the program that
        takes a chunk's last row."""
        batch_uids = list(batch_uids)
        batch_tokens = [np.atleast_1d(np.asarray(t)) for t in batch_tokens]
        if any(t.size != 1 for t in batch_tokens):
            raise ValueError("decode_loop takes exactly one next-input token per "
                             "sequence (a multi-token feed is verify_tree's)")
        if n_steps < 1:
            raise ValueError("n_steps must be >= 1")
        spans, observer, metrics = self._telemetry_sinks()
        n_tokens = len(batch_uids) * n_steps
        self._prepare(spans, batch_uids, batch_tokens, do_checks, n_tokens, steps=n_steps)
        args, prev = self._dispatch(spans, batch_uids, batch_tokens, prev, steps=n_steps)
        with _tel_live_span(spans, "decode_loop", "inference", args):
            if observer is not None or spans is not None:
                _t0 = _tel_now_us()
            tokens, banks = self._model.decode_loop(self._batch, n_steps, prev=prev)
            if spans is not None:
                # the call's two parts: the second is the fetcher's to write
                args.update(launch_us=_tel_now_us() - _t0, fetch_us=0)
                if banks is not None:
                    banks.copy_to_host_async()  # rides behind the tokens, not after them
            if observer is not None:
                observer("decode_loop", len(batch_uids), n_tokens, (_tel_now_us() - _t0) / 1e6)
        if metrics is not None:
            self._write_telemetry(metrics, batch_tokens=n_tokens)
        self._post_forward(batch_uids, steps=n_steps)
        return DecodeChunk(tokens, len(batch_uids), banks, args, self.moe_counts)

    # ------------------------------------------------------------ block steps --
    # A model that generates by diffusion over blocks (``model.attention_block``
    # = B): its prompts go through :meth:`put` in whole blocks (prefill, and a
    # block's commit: the same program), its decode steps through the two
    # below, on the same ``_prepare`` / ``_dispatch`` / ``_post_forward``.
    def _block_feeds(self, batch_uids, blocks, flags):
        """Each sequence's block as the program is fed it: the mask token where
        its flag says the row has no token yet; and the flags by token slot."""
        B = self._model.attention_block
        if not B:
            raise ValueError(f"a {type(self._model).__name__} does not generate by blocks "
                             f"(attention_block is 0): block_forward and the block loop are a "
                             f"block-diffusion model's")
        mask_id = self._model.config.mask_token_id
        blocks = [np.asarray(b, np.int32).reshape(-1) for b in blocks]
        flags = [np.asarray(f).astype(bool).reshape(-1) for f in flags]
        if any(b.size != B or f.size != B for b, f in zip(blocks, flags)) or \
                not len(batch_uids) == len(blocks) == len(flags):
            raise ValueError(f"a block step takes one block of {B} ids and {B} flags a sequence")
        return ([np.where(f, mask_id, b).astype(np.int32) for b, f in zip(blocks, flags)],
                np.concatenate(flags) if flags else np.zeros(0, bool))

    def block_forward(self, batch_uids: Iterable[int], blocks: Iterable, flags: Iterable,
                      do_checks: bool = True):
        """ONE denoise forward of each sequence's next block: ``blocks[i]`` the
        B ids of ``batch_uids[i]``'s rows at positions ``seen .. seen + B - 1``,
        ``flags[i]`` which of them have no token yet (they are fed the mask
        token, whatever their id). Returns float32 logits ``[n, B, vocab]`` on
        the device, row j scoring the token AT position j. Nothing it writes
        counts: the block's K/V lands in slots past ``seen_tokens``, which does
        not move (the KV blocks those slots are in stay allocated; the block's
        commit — a :meth:`put` of the finished block — overwrites them)."""
        batch_uids = list(batch_uids)
        feeds, _ = self._block_feeds(batch_uids, blocks, flags)
        spans, observer, metrics = self._telemetry_sinks()
        n_tokens = int(sum(t.size for t in feeds))
        self._prepare(spans, batch_uids, feeds, do_checks, n_tokens)
        args, _ = self._dispatch(spans, batch_uids, feeds, None)
        with _tel_live_span(spans, "block_forward", "inference", args):
            if observer is not None:
                _t0 = _tel_now_us()
            logits = self._model.block_forward(self._batch)
            if observer is not None:
                observer("block_forward", len(batch_uids), n_tokens, (_tel_now_us() - _t0) / 1e6)
            for uid in batch_uids:  # the rows were in flight and are dropped, not committed
                self._state_manager.get_sequence(uid).pre_forward(0)
        if metrics is not None:
            self._write_telemetry(metrics, batch_tokens=n_tokens)
        B = self._model.attention_block
        return logits[:n_tokens].reshape(len(batch_uids), B, -1)

    def block_loop(self, batch_uids, blocks, flags, n_blocks: int, do_checks: bool = True):
        """:meth:`dispatch_block_loop` fetched at once: ``(ids, steps)``."""
        chunk = self.dispatch_block_loop(batch_uids, blocks, flags, n_blocks, do_checks)
        return chunk.fetch(), chunk.steps

    def dispatch_block_loop(self, batch_uids: Iterable[int], blocks: Iterable, flags: Iterable,
                            n_blocks: int, do_checks: bool = True) -> BlockChunk:
        """``n_blocks`` blocks a sequence in ONE device program, launched and
        NOT fetched: per block ``denoising_steps`` denoise forwards and the
        choice of rows on the device; a block's commit rides the next block's
        first denoise forward, the chunk's last block's is a forward of its
        own: ``n_blocks * denoising_steps + 1`` forwards of the batch
        (``DSTransformerModelBase._block_loop_impl``). ``blocks`` / ``flags``
        are each sequence's FIRST block as for :meth:`block_forward` (the
        prompt's rows past its last whole block, the rest masked); every later
        block starts all masked, so a chunk needs nothing of the chunk before
        it but program order. Everything that needs only counts is done when
        the call returns: the KV blocks of all ``n_blocks * B`` positions,
        ``seen_tokens`` (+ ``n_blocks * B``). The ``block_loop`` span is the
        launch: ``seqs``, ``blocks`` and ``forwards`` (both a sequence;
        ``forwards`` counts forwards of B ROWS a sequence, ``denoising_steps +
        1`` a block: what the attention kernel and the experts are handed,
        however the program packs them — a fused forward is two), ``tokens``
        (the positions that take a token in the chunk; :meth:`BlockChunk.note`
        corrects it to what the caller kept), ``steps`` (the program's
        forwards of the whole batch), ``fused_commits`` (those of them that
        carry two blocks a sequence: ``n_blocks - 1``), the work counts over
        both kinds (``model.block_loop_counts``), ``launch_us``; ``fetch_us``
        and a grouped bucket's ``moe_banks`` are the fetch's to write."""
        batch_uids = list(batch_uids)
        feeds, masked = self._block_feeds(batch_uids, blocks, flags)
        if n_blocks < 1:
            raise ValueError("n_blocks must be >= 1")
        B, n = self._model.attention_block, len(batch_uids)
        n_denoise = self._model.config.denoising_steps
        spans, observer, metrics = self._telemetry_sinks()
        n_tokens = n * n_blocks * B
        self._prepare(spans, batch_uids, feeds, do_checks, n_tokens, steps=n_blocks * B)
        args, _ = self._dispatch(spans, batch_uids, feeds, None, steps=n_blocks * n_denoise + 1)
        if args is not None:
            taking = int(masked.sum()) + n * (n_blocks - 1) * B
            args.update(self._model.block_loop_counts(self._batch, n_blocks))
            args.update(seqs=n, blocks=n * n_blocks, forwards=n * n_blocks * (n_denoise + 1),
                        tokens=taking)
        slots = np.zeros(self._batch.device_batch["tok_meta"].shape[1], np.int32)
        slots[:masked.size] = masked
        with _tel_live_span(spans, "block_loop", "inference", args):
            if observer is not None or spans is not None:
                _t0 = _tel_now_us()
            ids, taken, conf, banks = self._model.block_loop(self._batch, slots, n_blocks)
            if spans is not None:
                args.update(launch_us=_tel_now_us() - _t0, fetch_us=0)
                if banks is not None:
                    banks.copy_to_host_async()
            if observer is not None:
                observer("block_loop", n, n_tokens, (_tel_now_us() - _t0) / 1e6)
        if metrics is not None:
            self._write_telemetry(metrics, batch_tokens=n_tokens)
        self._post_forward(batch_uids, steps=n_blocks, unit=B)
        return BlockChunk(ids, taken, conf, n, banks, args, self.moe_counts)

    # ------------------------------------------------------ speculative verify --
    def verify_tree(self, batch_uids: Iterable[int], trees: Iterable,
                    greedy: bool = False, do_checks: bool = True,
                    hidden: bool = True) -> List[dict]:
        """The speculative verify step: feed each sequence a draft TREE
        (:class:`~deepspeed_tpu.inference.v2.spec.tree.TokenTree`, root =
        next-input token; a linear ``1+k`` feed is ``TokenTree.chain``)
        through ONE ragged forward that scores every node. The batch's shape
        picks the program: when every tree ``is_chain`` the feed is a plain
        causal one (the attention arm ``put`` takes at that bucket, the Pallas
        kernel on a TPU); one branching tree puts the whole batch under the
        ancestor mask, several candidate branches priced by one dispatch.
        Returns one dict per sequence:

        - ``rows``:   float32 ``[n_nodes, vocab]`` logits (None when greedy) —
          row j scores the token AFTER node j's root path;
        - ``ids``:    int32 ``[n_nodes]`` device-argmax ids (greedy only);
        - ``hidden``: float32 ``[n_nodes, hidden]`` final residual states —
          the learned draft head's input for the next draft step (None, and
          never fetched, with ``hidden=False``).

        Every node's KV is written at slot ``seen + node_index`` and committed
        (``seen_tokens`` advances by ``n_nodes``); the caller walks the tree
        with the spec-off sampling rule and re-packs/truncates via
        :meth:`compact_accepted`."""
        self._state_manager.kv_cache.refuse("verify_tree")
        batch_uids = list(batch_uids)
        trees = list(trees)
        spans, observer, metrics = self._telemetry_sinks()
        n_tokens = int(sum(t.size for t in trees))
        self._prepare(spans, batch_uids, [t.tokens for t in trees], do_checks, n_tokens,
                      trees=None if all(t.is_chain for t in trees) else trees)
        args = self._dispatch_args(spans, batch_uids, tokens=n_tokens)
        with _tel_live_span(spans, "verify_tree", "inference", args):
            if observer is not None:
                _t0 = _tel_now_us()
            rows, states = self._model.forward_verify(self._batch, greedy=greedy)
            rows = np.asarray(rows)
            states = np.asarray(states) if hidden else None
            if observer is not None:
                observer("verify_tree", len(batch_uids), n_tokens,
                         (_tel_now_us() - _t0) / 1e6)
            self._post_forward(batch_uids, release=False)
        if metrics is not None:
            self._write_telemetry(metrics, batch_tokens=n_tokens)
        # insertion order is batch order: each sequence's nodes are one
        # contiguous token-major run
        out, offset = [], 0
        for tree in trees:
            n = tree.size
            out.append({"rows": None if greedy else rows[offset:offset + n],
                        "ids": rows[offset:offset + n] if greedy else None,
                        "hidden": states[offset:offset + n] if hidden else None})
            offset += n
        return out

    def compact_accepted(self, uid: int, n_fed: int, path_indices) -> int:
        """Tree-aware KV compaction after a :meth:`verify_tree` step over an
        ``n_fed``-node tree: keep the root plus the accepted path
        ``path_indices`` (ascending LOCAL node indices, root excluded),
        re-pack their KV to contiguous slots ``seen0 + 1..m`` in one jitted
        gather-then-scatter, and truncate the rejected remainder with the
        write-then-truncate rollback. Chain-shaped acceptances (``path[j] ==
        j+1``, the prompt-lookup case) skip the device copy entirely. Returns
        the number of rejected positions truncated."""
        seq_desc = self._state_manager.get_sequence(uid)
        if seq_desc is None:
            raise ValueError(f"compact_accepted: unknown uid {uid}")
        path = [int(i) for i in path_indices]
        if any(not (0 < i < n_fed) for i in path) or \
                any(b <= a for a, b in zip(path, path[1:])):
            raise ValueError(f"accepted path must be ascending non-root node "
                             f"indices inside the {n_fed}-node tree: {path}")
        copies = [(i, j + 1) for j, i in enumerate(path) if i != j + 1]
        if copies:
            seen0 = seq_desc.seen_tokens - n_fed  # committed count pre-feed
            self._model.compact_kv(seq_desc,
                                   [seen0 + s for s, _ in copies],
                                   [seen0 + d for _, d in copies])
        rejected = n_fed - 1 - len(path)
        self.rollback(uid, rejected)  # with its sliding-window check
        return rejected

    def rollback(self, uid: int, n_tokens: int) -> None:
        """Truncate ``uid``'s last ``n_tokens`` committed tokens after a
        verify step rejected them: the stale KV stays in its blocks and is
        overwritten when the correct tokens are fed at those positions
        (write-then-truncate — the mechanism chunk-decode over-run already
        relies on). The blocks stay allocated for the sequence."""
        if n_tokens <= 0:
            return
        self._state_manager.kv_cache.refuse("rollback")
        seq_desc = self._state_manager.get_sequence(uid)
        if seq_desc is None:
            raise ValueError(f"rollback: unknown uid {uid}")
        for group, window in enumerate(self._model.group_windows):
            released = seq_desc.released_in(group)
            if window <= 0 or not released:
                continue
            first_seen = max(seq_desc.seen_tokens - n_tokens - window + 1, 0)
            if first_seen // self._state_manager.kv_block_size < released:
                raise ValueError(
                    f"rollback({n_tokens}): uid {uid} would need keys from position "
                    f"{first_seen}, in a block its attention window already released")
        seq_desc.rollback(n_tokens)

    # ------------------------------------------------------------- scheduling --
    def query(self, uid: int, max_request_tokens: int, max_request_blocks: int) -> Tuple[int, int]:
        """(tokens schedulable, blocks required) for a hypothetical request."""
        seq_desc = self._state_manager.get_sequence(uid)
        if seq_desc is None:
            if self._state_manager.n_tracked_sequences >= self._config.state_manager.max_tracked_sequences:
                return (0, 0)
            seq_desc = PlaceholderSequenceDescriptor()
        restore = self._restore_cost(uid, seq_desc)
        toks, blocks = self._model.get_kv_requirements(
            seq_desc, max_request_tokens, max_request_blocks - restore)
        return toks, blocks + restore

    def _restore_cost(self, uid, seq_desc) -> int:
        """Device blocks a touch of ``uid`` must re-allocate first: an
        offloaded sequence's stale descriptor still reports its (freed)
        blocks as resident."""
        return seq_desc.live_blocks if self._state_manager.is_offloaded(uid) else 0

    def can_schedule(self, uids: Iterable[int], lengths: Iterable[int],
                     steps: int = 0) -> SchedulingResult:
        """Whether one step can take ``lengths[i]`` tokens of sequence
        ``uids[i]``. ``steps``: the step is a ``decode_loop`` chunk of that
        many steps, a token a sequence. Each SCAN STEP's ragged batch then
        holds one token per sequence, so the token budget is the sequence
        count's (and is looked at before any sequence), while the KV-block
        budget must cover all ``steps`` appended tokens per sequence."""
        uids, lengths = list(uids), list(lengths)
        limits = self._config.state_manager
        cur_seqs = self._state_manager.n_tracked_sequences
        free_blocks = self._state_manager.free_blocks
        batch_len = sum(lengths)

        if len(uids) > limits.max_ragged_sequence_count:
            return SchedulingResult.BatchSequenceLimitExceeded
        if steps and batch_len > limits.max_ragged_batch_size:
            return SchedulingResult.BatchTokenLimitExceeded

        for uid, length in zip(uids, lengths):
            seq_desc = self._state_manager.get_sequence(uid)
            if seq_desc is None:
                cur_seqs += 1
                seq_desc = PlaceholderSequenceDescriptor()
            restore = self._restore_cost(uid, seq_desc)
            sched_len, sched_blocks = self._model.get_kv_requirements(
                seq_desc, steps or length, free_blocks - restore)
            if sched_len != (steps or length):
                return SchedulingResult.KVCacheLimitExceeded
            free_blocks -= sched_blocks + restore

        if cur_seqs > limits.max_tracked_sequences:
            return SchedulingResult.EngineSequenceLimitExceeded
        if batch_len > limits.max_ragged_batch_size:
            return SchedulingResult.BatchTokenLimitExceeded
        return SchedulingResult.Success

    def flush(self, uid: int) -> None:
        self._state_manager.flush_sequence(uid)

    def cache_refusal(self, operation: str) -> Optional[Exception]:
        """Why this engine's cache cannot share, move or roll back what
        ``operation`` does (``ragged/kv_cache.py``: ``CACHE_OPERATIONS``), as
        the error to raise; None where it can."""
        return self._state_manager.kv_cache.refusal(operation)

    # ------------------------------------------------------------- kv offload --
    def offload_sequence(self, uid: int) -> None:
        """Evict a cold sequence's KV blocks to the host (or NVMe, when
        ``state_manager.offload_path`` is set), freeing device blocks for
        other sequences. The next put/decode_loop touching ``uid`` restores
        it transparently. Reference role: ``kv_cache.py:166`` offload +
        the ZeRO-Inference KV-offload leg (BASELINE.md)."""
        self._state_manager.offload_sequence(uid)

    def is_offloaded(self, uid: int) -> bool:
        return self._state_manager.is_offloaded(uid)

    # ------------------------------------------------------------- kv handoff --
    def export_sequence(self, uid: int, tokens=(), extra: Optional[dict] = None,
                        seen_tokens: Optional[int] = None,
                        version: Optional[int] = None) -> bytes:
        """Snapshot ``uid`` as a portable bytes payload — token history, KV-block
        contents and caller ``extra`` state — for :meth:`import_sequence` on
        ANOTHER engine: the fleet prefill→decode KV-block handoff transport,
        built on the same gather/scatter machinery as
        :meth:`offload_sequence`/``restore_sequence`` but serializable across a
        process or network boundary. ``seen_tokens`` caps the committed count
        the recipient adopts (chunked decode feeds ahead of the kept history;
        the recipient deterministically recomputes the trimmed tail). The
        sequence stays tracked here; ``flush(uid)`` once the recipient has
        taken over. ``version`` selects the frame version (None = the live
        handoff default; ``handoff.PARK_VERSION`` for parked-session frames,
        which carry a versioned tier record)."""
        from deepspeed_tpu.inference.v2.ragged.handoff import VERSION, pack_sequence
        return pack_sequence(self._state_manager, uid, tokens, extra=extra,
                             seen_tokens=seen_tokens,
                             version=VERSION if version is None else version)

    def import_sequence(self, payload: bytes, uid: Optional[int] = None) -> Tuple[int, dict]:
        """Recreate an exported sequence from a :meth:`export_sequence` payload
        under ``uid`` (default: the donor's uid); the next put/decode_loop
        continues it exactly where the donor stopped. Returns ``(uid, header)``
        — the header carries the token history and the exporter's ``extra``
        generation state."""
        from deepspeed_tpu.inference.v2.ragged.handoff import import_payload
        return import_payload(self._state_manager, payload, uid=uid)

    def flush_all(self) -> None:
        """Recycle every tracked sequence's KV blocks (hybrid-engine post-
        generation cleanup; reference release_inference_cache role)."""
        for uid in list(self._state_manager.tracked_sequences):
            self._state_manager.flush_sequence(uid)

    # ---------------------------------------------------------- lowering hooks --
    def lowerable_callables(self) -> dict:
        """The engine's jitted device programs as raw ``jax.jit`` callables
        (``.lower()``-able), those that have run: ``forward`` keyed by ``(T, S,
        MB)`` pad bucket, ``decode_loop`` by ``(bucket, n_steps, False)``,
        ``verify`` by ``("verify", bucket, tree, greedy)`` and ``compact`` by
        ``("compact", n_pairs)``. This is the official hook for HLO-level
        analysis (the deepspeed_tpu/perf/ gates); what a step calls may be a
        compile-watch wrapper shared with telemetry and cannot lower."""
        return self._model.lowerable_callables()

    def lower_forward(self, bucket=None):
        """``jax.stages.Lowered`` of the ragged forward at ``bucket``
        (default: the smallest bucket). Never executes."""
        return self._model.lower_forward(bucket)

    def lower_decode_loop(self, n_steps: int, bucket=None):
        """``jax.stages.Lowered`` of the on-device ``n_steps`` decode scan."""
        return self._model.lower_decode_loop(n_steps, bucket=bucket)

    def lower_verify(self, bucket=None, tree: bool = False, greedy: bool = False):
        """``jax.stages.Lowered`` of the speculative verify step (one ragged
        forward unembedding every fed position and returning the draft head's
        hidden states): the causal program, or with ``tree`` the one under
        the ancestor mask. Never executes."""
        return self._model.lower_verify(bucket, tree=tree, greedy=greedy)

    # -------------------------------------------------------------- empty_run --
    def empty_run(self) -> None:
        """Participate in EP collectives with zero live tokens (fork
        engine_v2.py:308) — keeps idle replicas in lock-step with busy ones:
        the smallest bucket's forward with every validity mask false."""
        metrics = self._resolve_tel_metrics()
        if metrics is not None:
            metrics["empty_runs"].inc()
        self._batch.clear()
        self._batch.finalize()
        self._model.forward(self._batch)

    # -------------------------------------------------------------- serialize --
    def serialize(self, save_path: str) -> None:
        """Write flattened params + metadata (reference engine_v2.py:289).
        ``engine_factory.build_engine_from_ds_checkpoint`` is the loader.

        Format notes: sub-byte/non-native dtypes (bf16) are stored as
        same-width uint views with the logical dtype in the metadata (npz
        can't carry ml_dtypes); trees must be pure string-keyed dicts with
        '/'-free keys (anything else cannot round-trip through the path
        encoding and is REJECTED here, not corrupted on load); the model
        config is JSON (no pickle — a checkpoint directory must never be an
        arbitrary-code-execution vector)."""
        import dataclasses

        import jax

        os.makedirs(save_path, exist_ok=True)
        leaves_with_paths = jax.tree_util.tree_flatten_with_path(self._model._params)[0]
        arrays, meta = {}, []
        for i, (path, leaf) in enumerate(leaves_with_paths):
            if not path:
                raise ValueError(
                    "serialize needs a dict param tree (a bare-leaf root has "
                    "no key path to encode and would not round-trip)")
            keys = []
            for k in path:
                key = getattr(k, "key", None)
                if not isinstance(key, str) or "/" in key:
                    raise ValueError(
                        f"serialize supports string-keyed dict trees with "
                        f"'/'-free keys only; cannot round-trip node {k!r} "
                        f"in path {jax.tree_util.keystr(path)}")
                keys.append(key)
            arr = np.asarray(jax.device_get(leaf))
            logical = str(arr.dtype)
            if arr.dtype.kind not in "fiub" or logical == "bfloat16":
                arr = arr.view(np.dtype(f"u{arr.dtype.itemsize}"))
            arrays[f"p{i}"] = arr
            meta.append({"path": "/".join(keys), "shape": list(leaf.shape),
                         "dtype": logical})
        np.savez(os.path.join(save_path, "params_rank0.npz"), **arrays)
        with open(os.path.join(save_path, "metadata_rank0.json"), "w") as f:
            json.dump(meta, f)

        cfg = self._model.config
        fields = {}
        for f_ in dataclasses.fields(cfg):
            v = getattr(cfg, f_.name)
            try:
                json.dumps(v)
            except TypeError:
                v = {"__dtype__": np.dtype(v).name}
            fields[f_.name] = v
        with open(os.path.join(save_path, "ds_model_config.json"), "w") as f:
            json.dump({"config_class": f"{type(cfg).__module__}.{type(cfg).__qualname__}",
                       "fields": fields}, f, indent=2)
        logger.info(f"serialized {len(arrays)} param tensors to {save_path}")
