"""Ragged engine configs.

Reference: ``deepspeed/inference/v2/ragged/manager_configs.py`` (KVCacheConfig,
DSStateManagerConfig, AllocationMode).
"""

from enum import Enum
from typing import Optional, Tuple

from pydantic import Field

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel


class AllocationMode(Enum):
    RESERVE = "reserve"
    ALLOCATE = "allocate"


class SequenceStateSpec(DeepSpeedConfigModel):
    """One pool of a per-SEQUENCE state group: ``[layers, slots, *shape]`` of
    ``dtype``, a slot a live sequence whatever its length."""
    name: str
    layers: int = Field(1, gt=0)
    shape: Tuple[int, ...]
    dtype: str = "float32"


class KVCacheConfig(DeepSpeedConfigModel):
    block_size: int = 128
    # KV layer groups: layer li reads block table li % groups at cache layer
    # li // groups, so a block id holds num_layers / groups layers of ONE group
    # (kv_cache.py). One group is one table for every layer.
    num_allocation_groups: int = Field(1, gt=0)
    # each group's sliding attention window in tokens (0: it keeps every key).
    # The model's to say, beside its groups: under a window a sequence gives
    # blocks back as it grows, and its table has holes (kv_cache.py, ``refusal``)
    group_windows: Tuple[int, ...] = (0, )
    # (layers that keep K/V, num_heads, head_size): the first is the count of
    # layers with a row a token, not of the model's blocks
    cache_shape: Tuple[int, int, int] = (0, 0, 0)
    # A token's state a layer where it is NOT a K/V pair of heads: one row of
    # each of these widths (a latent-attention model: its latent row and its
    # index key), one pool ``[layers, blocks, block_size, width]`` a width, all
    # addressed by the one block table. Empty = the K/V pair ``cache_shape``
    # says; with it ``cache_shape``'s heads and head_size are not read.
    state_widths: Tuple[int, ...] = ()
    # the smallest block-table bucket a batch is padded to (a power of two):
    # programs differ by bucket, and a model that has one program for every
    # table up to some length says so here
    min_table_bucket: int = Field(4, gt=0)
    # the smallest sequence bucket a batch is padded to (a multiple of 8; the
    # token bucket starts at it): a model with one program for every batch up
    # to some count of sequences says so here
    min_sequence_bucket: int = Field(8, gt=0)
    # the smallest token bucket; 0 = a row a sequence of the smallest sequence
    # bucket. A model whose step feeds a block a sequence says more
    min_token_bucket: int = Field(0, ge=0)
    # B > 0: the model generates by diffusion over blocks of B positions. Its
    # attention sees up to the end of a query's block, so every feed is whole
    # blocks (the batch checks it), and a block's K/V is rewritten until its
    # commit: what shares, moves or rolls back a sequence's cache mid-block is
    # refused (kv_cache.py, ``refusal``)
    attention_block: int = Field(0, ge=0)
    cache_dtype: str = "bfloat16"
    # A per-SEQUENCE state group (a state-space layer's recurrent state, its
    # convolution's tail): one pool a spec, ``sequence_slots`` slots each, a
    # slot a tracked sequence from its first token to its flush, whatever its
    # length; beside the K/V array in the ONE cache pytree the programs take.
    # Empty = every layer's state is a row a token.
    sequence_state: Tuple[SequenceStateSpec, ...] = ()
    sequence_slots: int = Field(0, ge=0)


class MemoryConfig(DeepSpeedConfigModel):
    mode: AllocationMode = AllocationMode.RESERVE
    size: int = Field(int(1e9), gt=0)  # bytes reserved / blocks allocated


class DSStateManagerConfig(DeepSpeedConfigModel):
    max_tracked_sequences: int = Field(2048, gt=0)
    max_ragged_batch_size: int = Field(768, gt=0)
    max_ragged_sequence_count: int = Field(512, gt=0)
    max_context: int = Field(8192, gt=0)
    memory_config: MemoryConfig = MemoryConfig()
    # spill offloaded KV blocks to files under this dir (NVMe tier, via the
    # native AIO engine) instead of holding them in host memory
    offload_path: Optional[str] = None
