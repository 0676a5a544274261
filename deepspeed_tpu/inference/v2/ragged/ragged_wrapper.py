"""Ragged batch container.

Reference: ``deepspeed/inference/v2/ragged/ragged_wrapper.py`` (RaggedBatchWrapper:31
— host shadow buffers for input ids / token→sequence map / per-sequence descriptors /
KV block lists, finalized into device tensors once per forward).

TPU design: XLA needs static shapes, so ``finalize()`` pads every buffer to a
*bucket*: token count rounded up with :func:`to_padded`, sequence count to a
multiple of 8 (at least the model's ``min_sequence_bucket``, which the token
count then starts at too), per-sequence block count to a power of two. Each distinct bucket
shape compiles once; steady-state decode reuses one bucket. Padded token slots
carry an out-of-range KV block id so cache scatters drop them (XLA scatter
``mode=drop`` — no masking pass needed).
"""

from typing import List

import numpy as np

from deepspeed_tpu.inference.v2.ragged.manager_configs import DSStateManagerConfig
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import DSSequenceDescriptor
from deepspeed_tpu.telemetry import compile_watch


def to_padded(original_size: int) -> int:
    """Pad a token count to a compile-friendly bucket: powers of two up to 64
    (8 minimum — decode batches stay small and must not burn a 64-token MLP),
    then 128-granularity for prefill chunks."""
    if original_size <= 64:
        n = 8
        while n < original_size:
            n *= 2
        return n
    return (original_size + 127) // 128 * 128


def _pad_to(n: int, mult: int) -> int:
    return max(mult, (n + mult - 1) // mult * mult)


def padded_sequences(n: int, least: int = 8) -> int:
    """A batch of ``n`` sequences' bucket S: a multiple of 8, at least the
    model's smallest (``KVCacheConfig.min_sequence_bucket``)."""
    return max(_pad_to(n, 8), least)


def padded_tokens(n: int, least: int = 8) -> int:
    """A batch of ``n`` tokens' bucket T under a smallest sequence bucket of
    ``least``: a batch is padded to at least a token a sequence row."""
    return to_padded(max(n, least))


def sequence_buckets(most: int, least: int = 8):
    """The padded sequence counts (a bucket's S) that batches of 1 to
    ``most`` sequences land in."""
    return sorted({padded_sequences(n, least) for n in range(1, most + 1)})


def token_buckets(most: int, least: int = 8):
    """The padded token counts (a bucket's T) that batches of 1 to ``most``
    tokens land in."""
    return sorted({padded_tokens(n, least) for n in range(1, most + 1)})


def _pow2_pad(n: int, minimum: int = 4) -> int:
    """Power-of-two bucket: the block-table width grows every block with plain
    granularity padding, which would recompile the decode program every few
    generated tokens; pow2 bucketing bounds recompiles to log2(max_blocks)."""
    p = minimum
    while p < n:
        p *= 2
    return p


class RaggedBatchWrapper:
    """Host-side composition of one ragged forward batch."""

    def __init__(self, config: DSStateManagerConfig, block_size: int = 128,
                 num_groups: int = 1, min_table_bucket: int = 4, state_slots: int = 0,
                 min_sequence_bucket: int = 8, min_token_bucket: int = 0,
                 attention_block: int = 0) -> None:
        """``num_groups``: block tables a sequence (KV layer groups,
        ``ragged/kv_cache.py``); the batch carries them side by side.
        ``min_table_bucket``: the smallest block-table bucket
        (``KVCacheConfig.min_table_bucket``), ``min_sequence_bucket`` the
        smallest sequence bucket (``KVCacheConfig.min_sequence_bucket``).
        ``state_slots``: the slots of a
        per-sequence state group (``KVCacheConfig.sequence_slots``); over 0,
        ``seq_meta`` carries each sequence's slot as one more column behind
        its block tables. ``min_token_bucket``: the smallest token bucket
        where that is more than a row a sequence of the smallest sequence
        bucket. ``attention_block`` (``KVCacheConfig.attention_block``) = B >
        0: the model attends under a block mask, which is right only where
        every sequence's rows are whole blocks; :meth:`insert_sequence` holds
        every feed to that."""
        self._config = config
        self._block_size = block_size
        self._num_groups = num_groups
        self._min_table_bucket = min_table_bucket
        self._min_sequence_bucket = min_sequence_bucket
        self._min_token_bucket = max(min_token_bucket, min_sequence_bucket)
        self._attention_block = attention_block
        self._state_slots = state_slots
        self.clear()

    def clear(self) -> None:
        self._token_ids: List[int] = []
        self._token_seq: List[int] = []      # token -> index of its sequence in this batch
        self._token_pos: List[int] = []      # absolute position within the sequence
        # tree-verify metadata (inference/v2/spec/tree.py): per token, the
        # parent's LOCAL feed index within its sequence (-1 = root) and the
        # root distance. Linear feeds default to the chain (parent = i-1,
        # depth = i), so mixed chain/tree batches pack uniformly.
        self._token_parent: List[int] = []
        self._token_depth: List[int] = []
        self._has_tree = False
        self._seq_descs: List[DSSequenceDescriptor] = []
        self._seq_seen: List[int] = []
        self._seq_ntok: List[int] = []
        self._seq_blocks: List[np.ndarray] = []
        self._seq_slots: List[int] = []
        self._device_batch = None

    @property
    def current_sequences(self) -> int:
        return len(self._seq_descs)

    @property
    def current_tokens(self) -> int:
        return len(self._token_ids)

    def insert_sequence(self, seq_desc: DSSequenceDescriptor, tokens, do_checks: bool = True,
                        tree=None) -> None:
        """``tree`` (optional) is a ``(parents, depths)`` pair of local-index
        arrays aligned with ``tokens`` — a speculative token tree (see
        spec/tree.py). Token i then occupies KV SLOT ``seen + i`` (sibling
        branches get distinct cache slots) while its ``token_pos`` stays the
        slot position; the verify program derives the LOGICAL (RoPE)
        position ``seen + depths[i]`` from the packed tree metadata."""
        tokens = np.atleast_1d(np.asarray(tokens)).astype(np.int32)
        if do_checks:
            if self.current_tokens + tokens.size > self._config.max_ragged_batch_size:
                raise ValueError("ragged batch token budget exceeded")
            if self.current_sequences + 1 > self._config.max_ragged_sequence_count:
                raise ValueError("ragged batch sequence budget exceeded")
        if tree is not None:
            # validate BEFORE mutating: a rejected insert must leave the
            # wrapper consistent so the caller can retry with a clean feed
            parents = np.asarray(tree[0], np.int32).reshape(-1)
            depths = np.asarray(tree[1], np.int32).reshape(-1)
            if do_checks:
                if parents.size != tokens.size or depths.size != tokens.size:
                    raise ValueError("tree metadata must align with the token feed")
                if tokens.size and (parents[0] != -1 or depths[0] != 0):
                    raise ValueError("tree node 0 must be the root (parent -1, depth 0)")
                if any(not (-1 <= int(parents[i]) < i) for i in range(tokens.size)):
                    raise ValueError("tree parents must be topological local indices")
        seq_idx = len(self._seq_descs)
        seen = seq_desc.seen_tokens
        B = self._attention_block
        if B and (seen % B or tokens.size % B or tree is not None):
            # checked or not: under the block mask a row sees its block's end,
            # which is in the pool only if the block came whole, and the
            # kernel's passes are whole blocks only if every feed before it
            # in the batch was (earlier inserts passed here: rows start at a
            # multiple of B)
            raise ValueError(
                f"sequence {seq_desc.tracking_id}: a feed of {tokens.size} tokens at position "
                f"{seen} under a block mask of {B}: a sequence's rows in a step start at a "
                f"multiple of the block and number a multiple of it"
                + ("; a draft tree has no block" if tree is not None else ""))
        self._seq_descs.append(seq_desc)
        self._seq_seen.append(seen)
        self._seq_ntok.append(int(tokens.size))
        self._seq_blocks.append(seq_desc.block_tables)
        if self._state_slots:
            self._seq_slots.append(seq_desc.state_slot)
        self._token_ids.extend(int(t) for t in tokens)
        self._token_seq.extend([seq_idx] * tokens.size)
        self._token_pos.extend(range(seen, seen + tokens.size))
        if tree is None:
            self._token_parent.extend(range(-1, tokens.size - 1))
            self._token_depth.extend(range(tokens.size))
        else:
            self._token_parent.extend(int(p) for p in parents)
            self._token_depth.extend(int(d) for d in depths)
            self._has_tree = True

    def finalize(self):
        """Pad to the bucket and build the device-ready numpy struct."""
        T = padded_tokens(self.current_tokens, self._min_token_bucket)
        S = padded_sequences(self.current_sequences, self._min_sequence_bucket)
        mb = max((b.shape[1] for b in self._seq_blocks), default=1)
        MB = _pow2_pad(mb, self._min_table_bucket)
        G = self._num_groups
        cw = compile_watch.get()
        if cw is not None:
            # (T, S, MB) IS the jit cache key downstream — the watch counts
            # batch-to-batch bucket churn, the leading recompile indicator
            cw.note_bucket((T, S, MB))
        n_tok = self.current_tokens
        n_seq = self.current_sequences

        input_ids = np.zeros(T, np.int32)
        token_seq = np.full(T, S - 1, np.int32)
        token_pos = np.zeros(T, np.int32)
        token_valid = np.zeros(T, bool)
        input_ids[:n_tok] = self._token_ids
        token_seq[:n_tok] = self._token_seq
        token_pos[:n_tok] = self._token_pos
        token_valid[:n_tok] = True

        seq_seen = np.zeros(S, np.int32)
        seq_ntok = np.zeros(S, np.int32)
        last_tok = np.zeros(S, np.int32)
        seq_valid = np.zeros(S, bool)
        # padded/invalid slots point one past the last block -> scatters drop;
        # group g's table is columns [g * MB, (g + 1) * MB)
        block_table = np.full((S, G, MB), -1, np.int32)
        cursor = 0
        for i in range(n_seq):
            seq_seen[i] = self._seq_seen[i]
            seq_ntok[i] = self._seq_ntok[i]
            cursor += self._seq_ntok[i]
            last_tok[i] = cursor - 1
            seq_valid[i] = True
            blocks = self._seq_blocks[i]
            block_table[i, :, :blocks.shape[1]] = blocks

        # Pack into TWO device arrays (plus host-only counts): every h2d
        # transfer pays dispatch latency, and decode issues one batch per
        # generated token — 2 transfers/step, not 10.
        # transformer_base._unpack_batch restores the named views inside jit.
        tok_meta = np.stack([input_ids, token_seq, token_pos,
                             token_valid.astype(np.int32)])  # [4, T]
        seq_meta = np.concatenate([
            np.stack([seq_seen, seq_ntok, last_tok, seq_valid.astype(np.int32)], axis=1),
            block_table.reshape(S, G * MB)
        ], axis=1)  # [S, 4 + G * MB]
        if self._state_slots:
            # a padding row points one past the last slot, so that its scatter
            # drops: the block table's convention
            slots = np.full((S, 1), self._state_slots, np.int32)
            slots[:n_seq, 0] = self._seq_slots
            seq_meta = np.concatenate([seq_meta, slots], axis=1)  # [S, 4 + G * MB + 1]
        self._device_batch = dict(
            tok_meta=tok_meta,
            seq_meta=seq_meta,
            n_tokens=n_tok,
            n_seqs=n_seq,
        )
        if self._has_tree:
            # packed only when a tree was inserted: the plain decode/prefill
            # hot path builds exactly the two arrays it always did
            parent = np.full(T, -1, np.int32)
            depth = np.zeros(T, np.int32)
            parent[:n_tok] = self._token_parent
            depth[:n_tok] = self._token_depth
            self._device_batch["tree_meta"] = np.stack([parent, depth])  # [2, T]
        return self._device_batch

    @property
    def device_batch(self):
        assert self._device_batch is not None, "finalize() the batch first"
        return self._device_batch

