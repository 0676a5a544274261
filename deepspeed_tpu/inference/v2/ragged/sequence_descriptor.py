"""Sequence tracking.

Reference: ``deepspeed/inference/v2/ragged/sequence_descriptor.py``
(DSSequenceDescriptor — per-sequence KV block table, seen/in-flight token counts).

The block table is indexed by position: entry ``i`` holds the block of tokens
``i * block_size ..``. Under a sliding attention window the leading entries are
RELEASED once every position in them is out of every later query's sight
(``release_leading``): such an entry is a hole (``RELEASED`` = -1), its block is
back with the allocator, and nothing dereferences it again — the attention
kernel starts its walk at the window, the XLA arm masks what it gathers.
"""

from typing import List, Optional

import numpy as np

RELEASED = -1  # a block-table entry whose block the window has passed


class DSSequenceDescriptor:

    def __init__(self, tracking_id: int, max_blocks_per_seq: int = 256):
        self.tracking_id = tracking_id
        self._seen_tokens = 0
        self._in_flight_tokens = 0
        self._max_blocks = max_blocks_per_seq
        self._kv_blocks: List[int] = []
        self._released = 0  # leading table entries that are holes
        # which tier of the KV ladder holds this sequence's cache — one of
        # ragged.tiering.TIERS. "device" while the block table is live; the
        # state manager flips it to the store-reported tier across an
        # offload (ragged_manager.offload_sequence / restore_sequence)
        self.kv_tier: str = "device"

    @property
    def seen_tokens(self) -> int:
        return self._seen_tokens

    @property
    def in_flight_tokens(self) -> int:
        return self._in_flight_tokens

    @property
    def cur_allocated_blocks(self) -> int:
        """Entries of the block table, released ones included: the positions
        the table covers, in blocks. What the sequence HOLDS is
        :attr:`live_blocks`."""
        return len(self._kv_blocks)

    @property
    def released_blocks(self) -> int:
        return self._released

    @property
    def live_blocks(self) -> int:
        return len(self._kv_blocks) - self._released

    @property
    def max_blocks(self) -> int:
        return self._max_blocks

    @property
    def kv_blocks(self) -> np.ndarray:
        """The table by position; a released entry reads ``RELEASED``."""
        return np.asarray(self._kv_blocks, dtype=np.int64)

    @property
    def live_kv_blocks(self) -> np.ndarray:
        """The blocks the sequence holds, oldest first."""
        return np.asarray(self._kv_blocks[self._released:], dtype=np.int64)

    def kv_cache_ids(self, on_device: bool = False) -> np.ndarray:
        return self.kv_blocks

    def extend_kv_cache(self, new_blocks) -> None:
        new_blocks = np.atleast_1d(np.asarray(new_blocks)).tolist()
        if len(self._kv_blocks) + len(new_blocks) > self._max_blocks:
            raise ValueError(f"Sequence {self.tracking_id} exceeds max blocks {self._max_blocks}")
        self._kv_blocks.extend(int(b) for b in new_blocks)

    def replace_kv_blocks(self, new_blocks) -> None:
        """Swap the held blocks for fresh ids (KV offload→restore hands back
        different device blocks; token order is preserved, holes stay)."""
        new_blocks = np.atleast_1d(np.asarray(new_blocks)).tolist()
        if len(new_blocks) != self.live_blocks:
            raise ValueError(f"restore returned {len(new_blocks)} blocks for a "
                             f"sequence that holds {self.live_blocks}")
        self._kv_blocks[self._released:] = [int(b) for b in new_blocks]

    def release_leading(self, n_entries: int) -> List[int]:
        """Turn table entries ``[released_blocks, n_entries)`` into holes and
        return the block ids they held, for the caller to give back to the
        allocator. Entries already released stay so."""
        n_entries = min(int(n_entries), len(self._kv_blocks))
        freed = self._kv_blocks[self._released:n_entries]
        if freed:
            self._kv_blocks[self._released:n_entries] = [RELEASED] * len(freed)
            self._released = n_entries
        return freed

    def pre_forward(self, num_tokens: int) -> None:
        """Reference: mark tokens as in-flight before the forward."""
        self._in_flight_tokens = num_tokens

    def post_forward(self) -> None:
        """Reference: commit in-flight tokens to seen after the forward."""
        self._seen_tokens += self._in_flight_tokens
        self._in_flight_tokens = 0

    def rollback(self, n_tokens: int) -> None:
        """Forget the last ``n_tokens`` committed tokens (write-then-truncate):
        their KV stays in place and is overwritten when the correct tokens are
        fed at those positions — the speculative-verify rejection path. The
        blocks stay allocated; only the committed count moves."""
        n_tokens = int(n_tokens)
        if self._in_flight_tokens:
            raise RuntimeError(f"sequence {self.tracking_id}: rollback with "
                               f"{self._in_flight_tokens} in-flight tokens")
        if n_tokens < 0 or n_tokens > self._seen_tokens:
            raise ValueError(f"rollback({n_tokens}) with {self._seen_tokens} "
                             f"committed tokens")
        self._seen_tokens -= n_tokens


class PlaceholderSequenceDescriptor(DSSequenceDescriptor):
    """Ephemeral stand-in used by ``engine.query``/``can_schedule`` for uids the
    engine does not know yet (reference sequence_descriptor.py Placeholder...)."""

    def __init__(self, tracking_id: int = -1, max_blocks_per_seq: int = 2**30):
        super().__init__(tracking_id, max_blocks_per_seq=max_blocks_per_seq)
