"""What a model's per-sequence cache may share, move or roll back: the one
table (``ragged/kv_cache.py``: ``CACHE_OPERATIONS``, ``BlockedKVCache.refusal``)
against the refusals the six modules that used to know it raised one by one
(PR 46: the table below was read off the parent's code, cell for cell)."""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.ragged.kv_cache import CACHE_OPERATIONS, BlockedKVCache
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig,
                                                               KVCacheConfig, MemoryConfig,
                                                               SequenceStateSpec)
from deepspeed_tpu.inference.v2.ragged.ragged_manager import DSStateManager

# the kinds of cache the families build: K/V under one table (Llama, Mixtral);
# one table under a sliding window (Mistral); window and full layers side by
# side, a table a group (Mellum, Trinity); latent rows (DeepSeek-V3.2); a
# per-sequence state group beside the K/V array (Nemotron-H)
KINDS = {
    "kv": dict(),
    "window": dict(group_windows=(24, )),
    "groups": dict(num_allocation_groups=4, group_windows=(24, 24, 24, 0)),
    "latent": dict(state_widths=(128, 16)),
    "slots": dict(sequence_slots=3, sequence_state=(
        SequenceStateSpec(name="ssm", layers=2, shape=(4, 8)), )),
}
# V / N: refused with a ValueError / a NotImplementedError, for the kind after
# the colon; -: served (or left to the operation under it, or to the sequence)
TABLE = """
prefix_cache            share     -   V:win   V:win   V:lat   V:slot
kv_tiers                move      -   V:win   V:win   V:lat   V:slot
speculative             rollback  -   -       -       -       V:slot
frames                  move      -   V:win   V:win   -       V:slot
create_cached_sequence  share     -   -       V:tab   -       N:slot
fork_blocks             share     -   -       -       N:lat   N:slot
offload_sequence        move      -   -       -       -       N:slot
export_sequence         move      -   -       -       -       N:slot
import_sequence         move      -   -       V:tab   -       N:slot
gather_blocks           move      -   -       -       N:lat   N:slot
scatter_blocks          move      -   -       -       N:lat   N:slot
verify_tree             rollback  -   -       -       -       N:slot
compact_kv              rollback  -   -       -       N:lat   N:slot
rollback                rollback  -   -       -       -       N:slot
"""
WORDS = {"win": ["sliding-window model", "attention window is 24"],
         "tab": ["4 block tables"],
         "lat": ["latent KV group", "latent group", r"\(128, 16\)"],
         "slot": ["per-sequence state group", "ssm"]}
ROWS = [line.split() for line in TABLE.strip().splitlines()]
CELLS = [(row[0], kind, cell) for row in ROWS for kind, cell in zip(KINDS, row[2:])]
# where an operation is a method of the pool or of the state manager, the cell
# is also asked of the method itself
CALLS = {
    "fork_blocks": lambda m: m.kv_cache.fork_blocks([0]),
    "gather_blocks": lambda m: m.kv_cache.gather_blocks([0]),
    "scatter_blocks": lambda m: m.kv_cache.scatter_blocks(np.zeros((4, 2, 1, 1, 8, 8))),
    "create_cached_sequence": lambda m: m.create_cached_sequence(5, [], 0),
    "offload_sequence": lambda m: m.offload_sequence(0),
    "export_sequence": lambda m: m.export_sequence(0),
    "import_sequence": lambda m: m.import_sequence({"uid": 5, "seen_tokens": 0, "kv": None}),
}


def _manager(kind):
    config = KVCacheConfig(block_size=8, cache_shape=(4, 1, 8), cache_dtype="float32",
                           **KINDS[kind])
    manager = DSStateManager(
        DSStateManagerConfig(memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=8),
                             max_context=64, max_tracked_sequences=3), config)
    manager.get_or_create_sequence(0)
    return manager


def test_the_table_names_every_operation_and_its_class():
    assert [(row[0], row[1]) for row in ROWS] == \
        [(op, entry[0]) for op, entry in CACHE_OPERATIONS.items()]


@pytest.mark.parametrize("operation, kind, cell", CELLS,
                         ids=[f"{op}-{kind}" for op, kind, _ in CELLS])
def test_a_cache_refuses_or_serves_as_the_table_says(operation, kind, cell):
    manager = _manager(kind)
    refusal = manager.kv_cache.refusal(operation)
    if cell == "-":
        assert refusal is None
        return
    error, why = cell.split(":")
    assert type(refusal) is {"V": ValueError, "N": NotImplementedError}[error]
    said = CACHE_OPERATIONS[operation][1] or operation
    assert str(refusal).startswith(said + " cannot serve ")
    for words in WORDS[why]:
        with pytest.raises(type(refusal), match=words):
            raise refusal
    if operation in CALLS:
        with pytest.raises(type(refusal), match=WORDS[why][0]):
            CALLS[operation](manager)
        assert manager.n_tracked_sequences == 1 and manager.free_blocks == 8  # nothing changed
