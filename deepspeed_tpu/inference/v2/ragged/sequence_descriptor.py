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
    """``num_groups`` block tables, one a KV layer group (layers that share a
    table: ``ragged/kv_cache.py``). Every table covers the same positions;
    they differ in what has been released. A model whose layers all see alike
    has one group, and the single-table accessors (``kv_blocks``,
    ``live_kv_blocks``, ``released_blocks``, ``release_leading``) are that
    group's."""

    def __init__(self, tracking_id: int, max_blocks_per_seq: int = 256, num_groups: int = 1):
        self.tracking_id = tracking_id
        self._seen_tokens = 0
        self._in_flight_tokens = 0
        self._max_blocks = max_blocks_per_seq
        self._kv_blocks: List[List[int]] = [[] for _ in range(num_groups)]
        self._released = [0] * num_groups  # leading table entries that are holes, by group
        # which tier of the KV ladder holds this sequence's cache — one of
        # ragged.tiering.TIERS. "device" while the block table is live; the
        # state manager flips it to the store-reported tier across an
        # offload (ragged_manager.offload_sequence / restore_sequence)
        self.kv_tier: str = "device"
        # the sequence's slot in a per-sequence state group (``kv_cache.py``):
        # the state manager's, from the sequence's creation to its flush; None
        # for a model whose every layer keeps a row a token
        self.state_slot: Optional[int] = None

    @property
    def seen_tokens(self) -> int:
        return self._seen_tokens

    @property
    def in_flight_tokens(self) -> int:
        return self._in_flight_tokens

    @property
    def num_groups(self) -> int:
        return len(self._kv_blocks)

    @property
    def cur_allocated_blocks(self) -> int:
        """Entries of each block table, released ones included: the positions
        the tables cover, in blocks. What the sequence HOLDS is
        :attr:`live_blocks`."""
        return len(self._kv_blocks[0])

    @property
    def released_blocks(self) -> int:
        """Leading entries released in EVERY table: positions no layer can
        read again."""
        return min(self._released)

    def released_in(self, group: int) -> int:
        return self._released[group]

    @property
    def live_blocks(self) -> int:
        """Blocks of the pool the sequence holds, over all groups."""
        return sum(len(t) - r for t, r in zip(self._kv_blocks, self._released))

    def live_blocks_in(self, group: int) -> int:
        return len(self._kv_blocks[group]) - self._released[group]

    @property
    def max_blocks(self) -> int:
        return self._max_blocks

    @property
    def block_tables(self) -> np.ndarray:
        """``[num_groups, entries]`` by position; a released entry reads
        ``RELEASED``."""
        return np.asarray(self._kv_blocks, dtype=np.int64).reshape(len(self._kv_blocks), -1)

    @property
    def kv_blocks(self) -> np.ndarray:
        """The one table of a one-group sequence, by position."""
        if len(self._kv_blocks) != 1:
            raise ValueError(f"sequence {self.tracking_id} has {len(self._kv_blocks)} block "
                             f"tables (KV layer groups); kv_blocks reads one — use block_tables")
        return np.asarray(self._kv_blocks[0], dtype=np.int64)

    @property
    def live_kv_blocks(self) -> np.ndarray:
        """The blocks the sequence holds: oldest first, group after group."""
        return np.asarray([b for t, r in zip(self._kv_blocks, self._released) for b in t[r:]],
                          dtype=np.int64)

    def extend_kv_cache(self, new_blocks) -> None:
        """Append ``new_blocks`` — ``num_groups`` x n ids, group-major (a flat
        list of n for a one-group sequence): n more entries in every table."""
        groups = len(self._kv_blocks)
        new_blocks = np.asarray(new_blocks, dtype=np.int64).reshape(groups, -1)
        if len(self._kv_blocks[0]) + new_blocks.shape[1] > self._max_blocks:
            raise ValueError(f"Sequence {self.tracking_id} exceeds max blocks {self._max_blocks}")
        for table, ids in zip(self._kv_blocks, new_blocks.tolist()):
            table.extend(ids)

    def replace_kv_blocks(self, new_blocks) -> None:
        """Swap the held blocks for fresh ids (KV offload→restore hands back
        different device blocks; token order is preserved, holes stay). In
        :attr:`live_kv_blocks` order."""
        new_blocks = np.atleast_1d(np.asarray(new_blocks)).tolist()
        if len(new_blocks) != self.live_blocks:
            raise ValueError(f"restore returned {len(new_blocks)} blocks for a "
                             f"sequence that holds {self.live_blocks}")
        at = 0
        for table, released in zip(self._kv_blocks, self._released):
            n = len(table) - released
            table[released:] = [int(b) for b in new_blocks[at:at + n]]
            at += n

    def release_leading(self, n_entries: int, group: int = 0) -> List[int]:
        """Turn entries ``[released, n_entries)`` of ``group``'s table into
        holes and return the block ids they held, for the caller to give back
        to the allocator. Entries already released stay so."""
        table = self._kv_blocks[group]
        released = self._released[group]
        n_entries = min(int(n_entries), len(table))
        freed = table[released:n_entries]
        if freed:
            table[released:n_entries] = [RELEASED] * len(freed)
            self._released[group] = n_entries
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
