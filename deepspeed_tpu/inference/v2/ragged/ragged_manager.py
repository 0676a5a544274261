"""Sequence + KV-cache state manager.

Reference: ``deepspeed/inference/v2/ragged/ragged_manager.py`` (DSStateManager:19 —
uid → DSSequenceDescriptor tracking over a BlockedKVCache).
"""

from typing import Dict, Optional

import numpy as np

from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig, KVCacheConfig
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import DSSequenceDescriptor
from deepspeed_tpu.utils.logging import logger


class DSStateManager:

    def __init__(self, config: DSStateManagerConfig, kv_config: KVCacheConfig):
        self._config = config
        self._kv_config = kv_config
        self._seqs: Dict[int, DSSequenceDescriptor] = {}
        self._offloaded: Dict[int, int] = {}  # uid -> host-pool handle
        self._kv_cache = BlockedKVCache(kv_config, config.memory_config,
                                        offload_path=config.offload_path)

    # ------------------------------------------------------------- sequences --
    def get_sequence(self, uid: int) -> Optional[DSSequenceDescriptor]:
        return self._seqs.get(uid)

    def get_or_create_sequence(self, uid: int) -> DSSequenceDescriptor:
        seq = self._seqs.get(uid)
        if seq is not None:
            return seq
        return self._create_sequence(uid)

    def _create_sequence(self, uid: int) -> DSSequenceDescriptor:
        if uid in self._seqs:
            raise ValueError(f"sequence {uid} already tracked")
        if self.n_tracked_sequences >= self._config.max_tracked_sequences:
            raise RuntimeError(f"max_tracked_sequences={self._config.max_tracked_sequences} reached")
        max_blocks = (self._config.max_context + self._kv_config.block_size - 1) // self._kv_config.block_size
        seq = DSSequenceDescriptor(uid, max_blocks_per_seq=max_blocks,
                                   num_groups=self._kv_config.num_allocation_groups)
        if self._kv_cache.num_slots:
            if not self._kv_cache.free_slots:
                raise RuntimeError(f"sequence {uid}: no free slot in the per-sequence state "
                                   f"group ({self._kv_cache.num_slots} slots, all held)")
            seq.state_slot = self._kv_cache.reserve_slot()
        self._seqs[uid] = seq
        return seq

    def create_cached_sequence(self, uid: int, blocks, seen_tokens: int) -> DSSequenceDescriptor:
        """Create a sequence whose block table arrives **pre-populated** — the
        prefix-cache hit path: ``blocks`` already hold the KV for the first
        ``seen_tokens`` committed tokens (shared, read-only; the caller holds
        one reference per block on this sequence's behalf, which
        ``flush_sequence`` returns). The next forward continues at position
        ``seen_tokens`` exactly like a restored or imported sequence."""
        self._kv_cache.refuse("create_cached_sequence")
        blocks = np.atleast_1d(np.asarray(blocks)).astype(np.int64)
        seen_tokens = int(seen_tokens)
        if seen_tokens < 0 or seen_tokens > blocks.size * self._kv_config.block_size:
            raise ValueError(
                f"create_cached_sequence: seen_tokens={seen_tokens} does not fit "
                f"{blocks.size} blocks of {self._kv_config.block_size} tokens")
        seq = self._create_sequence(uid)
        try:
            if blocks.size:
                seq.extend_kv_cache(blocks)
            seq.pre_forward(seen_tokens)
            seq.post_forward()
        except Exception:
            del self._seqs[uid]  # the caller still owns the block references
            raise
        return seq

    def flush_sequence(self, uid: int) -> None:
        """Release all state for a sequence (reference ragged_manager.py:110)."""
        seq = self._seqs.pop(uid, None)
        if seq is None:
            logger.warning(f"flush_sequence: unknown uid {uid}")
            return
        handle = self._offloaded.pop(uid, None)
        if handle is not None:
            self._kv_cache.drop_offloaded(handle)
        elif seq.live_blocks > 0:
            self._kv_cache.free(seq.live_kv_blocks)
        if seq.state_slot is not None:
            # back with the allocator as it is: whoever takes it next starts
            # from zero by its own count of tokens seen, not by a wipe
            self._kv_cache.free_slot(seq.state_slot)
            seq.state_slot = None

    def release_passed_blocks(self, seq: DSSequenceDescriptor, window: int,
                              group: int = 0) -> int:
        """Rolling release under a sliding ``window``, in the table of layer
        ``group`` (whose layers have that window): give back to the allocator
        every block ALL of whose positions are more than ``window`` behind the
        sequence's next query (position ``seen_tokens``, which sees keys
        ``seen_tokens - window + 1 ..``). Returns the number released.
        An offloaded sequence is left alone: its table is not live."""
        if window <= 0 or seq.tracking_id in self._offloaded:
            return 0
        passed = max(seq.seen_tokens - window + 1, 0) // self._kv_config.block_size
        freed = seq.release_leading(passed, group)
        if freed:
            self._kv_cache.free(freed)
        return len(freed)

    # ----------------------------------------------------------- kv offload --
    def is_offloaded(self, uid: int) -> bool:
        return uid in self._offloaded

    def sequence_tier(self, uid: int) -> str:
        """Which tier of the KV ladder currently holds ``uid``'s cache:
        ``device`` for a resident block table, else the tiered store's answer
        (``host`` | ``disk``) for the offloaded payload."""
        handle = self._offloaded.get(uid)
        if handle is None:
            return "device"
        return self._kv_cache.offload_tier(handle)

    def offload_sequence(self, uid: int) -> None:
        """Evict a (cold) sequence's KV blocks to the host tier, freeing its
        device blocks for other sequences. The sequence stays tracked; the
        next forward that touches it restores it (engine put/decode_loop)."""
        self._kv_cache.refuse("offload_sequence")
        seq = self._seqs.get(uid)
        if seq is None:
            raise ValueError(f"offload_sequence: unknown uid {uid}")
        if uid in self._offloaded:
            return
        if seq.in_flight_tokens:
            raise RuntimeError(f"offload_sequence: uid {uid} has in-flight tokens")
        if seq.live_blocks == 0:
            return
        self._offloaded[uid] = self._kv_cache.offload(seq.live_kv_blocks)
        seq.kv_tier = self.sequence_tier(uid)

    def demote_sequence(self, uid: int, wait: bool = False) -> bool:
        """Push an already-offloaded sequence one tier colder (host→disk);
        returns whether a demotion was scheduled. The brownout controller's
        demote-before-shed stage calls this for the coldest offloaded
        sessions before any queued work is shed."""
        handle = self._offloaded.get(uid)
        if handle is None:
            return False
        demoted = self._kv_cache.demote_offloaded(handle, wait=wait)
        if demoted:
            seq = self._seqs.get(uid)
            if seq is not None:
                seq.kv_tier = "disk" if wait else self.sequence_tier(uid)
        return demoted

    def restore_sequence(self, uid: int) -> None:
        """Bring an offloaded sequence's KV back into fresh device blocks and
        rewrite its block table. Raises if the device pool cannot hold it
        (offload other sequences first)."""
        handle = self._offloaded.pop(uid, None)
        if handle is None:
            return
        try:
            new_blocks = self._kv_cache.restore(handle)
        except Exception:
            self._offloaded[uid] = handle  # payload intact; caller may evict + retry
            raise
        seq = self._seqs[uid]
        seq.replace_kv_blocks(new_blocks)
        seq.kv_tier = "device"

    # ------------------------------------------------------------ kv handoff --
    def export_sequence(self, uid: int) -> dict:
        """Portable snapshot of a tracked sequence — committed-token count plus
        KV-block contents — for :meth:`import_sequence` on another manager (the
        fleet prefill→decode handoff; bytes framing lives in
        ``ragged/handoff.py``). An offloaded sequence is restored first (its
        payload is already host-side, but export must observe one canonical
        path). The sequence stays tracked and resident here; the caller
        flushes once the recipient has taken over."""
        self._kv_cache.refuse("export_sequence")
        seq = self._seqs.get(uid)
        if seq is None:
            raise ValueError(f"export_sequence: unknown uid {uid}")
        if seq.in_flight_tokens:
            raise RuntimeError(f"export_sequence: uid {uid} has in-flight tokens")
        if seq.released_blocks:
            raise ValueError(
                f"export_sequence: uid {uid} has passed its attention window and "
                f"released {seq.released_blocks} KV blocks; a handoff or park frame "
                f"carries a whole block table and cannot hold it — recompute the "
                f"sequence on the recipient instead")
        if uid in self._offloaded:
            self.restore_sequence(uid)
        kv = (self._kv_cache.gather_blocks(seq.kv_blocks)
              if seq.cur_allocated_blocks > 0 else None)
        return {"uid": uid, "seen_tokens": seq.seen_tokens, "kv": kv}

    def import_sequence(self, snapshot: dict, uid: Optional[int] = None) -> int:
        """Recreate an exported sequence under ``uid`` (default: the donor's
        uid): fresh device blocks, contents written back, committed-token
        count restored. Raises without consuming anything when the uid is
        already tracked, the payload's geometry doesn't fit this cache, or
        the device pool can't hold it (evict and retry)."""
        self._kv_cache.refuse("import_sequence")
        uid = int(snapshot["uid"] if uid is None else uid)
        if uid in self._seqs:
            raise ValueError(f"import_sequence: uid {uid} already tracked")
        kv = snapshot["kv"]
        seq = self._create_sequence(uid)
        try:
            if kv is not None:
                if kv.shape[2] > seq.max_blocks:
                    raise ValueError(
                        f"import_sequence: payload holds {kv.shape[2]} blocks; "
                        f"this manager caps sequences at {seq.max_blocks} "
                        f"(max_context={self._config.max_context})")
                seq.extend_kv_cache(self._kv_cache.scatter_blocks(kv))
            seq.pre_forward(int(snapshot["seen_tokens"]))
            seq.post_forward()
        except Exception:
            del self._seqs[uid]  # scatter freed its blocks on failure
            raise
        return uid

    @property
    def tracked_sequences(self) -> Dict[int, DSSequenceDescriptor]:
        return self._seqs

    @property
    def n_tracked_sequences(self) -> int:
        return len(self._seqs)

    # --------------------------------------------------------------- kv cache --
    @property
    def kv_cache(self) -> BlockedKVCache:
        return self._kv_cache

    @property
    def kv_block_size(self) -> int:
        return self._kv_config.block_size

    @property
    def free_blocks(self) -> int:
        return self._kv_cache.free_blocks

    @property
    def free_slots(self):
        """Free slots of the per-sequence state group; None for a model without one."""
        return self._kv_cache.free_slots

    @property
    def num_slots(self) -> int:
        return self._kv_cache.num_slots

    @property
    def num_groups(self) -> int:
        """KV layer groups: block tables a sequence, block ids a block of
        positions (kv_cache.py)."""
        return self._kv_config.num_allocation_groups

    def allocate_blocks(self, n_blocks: int):
        return self._kv_cache.reserve(n_blocks)
