"""Blocked (paged) KV cache.

Reference: ``deepspeed/inference/v2/ragged/kv_cache.py`` (BlockedKVCache:40 —
reserve/free block ids, device cache tensors, offload/restore hooks).

TPU layout: ONE cache array of shape
``[layers_per_group, 2, num_blocks, kv_heads, block_size, head_dim]`` — a (layer,
k|v, block) triple is one contiguous ``[kv_heads, block_size, head_dim]`` tile,
which is exactly one DMA for the Pallas paged-attention kernel
(``ops/pallas/paged_attention.py``) and a clean dynamic-slice for the XLA gather
fallback. The trailing ``[block_size, head_dim]`` = (16, 128) matches the TPU tile
so per-block copies are layout-native.

Layer groups (``KVCacheConfig.num_allocation_groups`` = G): layers that keep
different spans of a sequence (a window layer its last ``window`` tokens, a full
layer all of them) cannot share a block table. Layer ``li`` belongs to group
``li % G`` and lives at cache layer ``li // G``, so ``layers_per_group =
num_layers / G`` and a block id holds that many layers of ONE group: a sequence
takes a block id per group for each block of positions, and gives a window
group's back alone. One pool, one allocator, one ``free_blocks``. A model whose
layers all see alike is one group of ``num_layers`` layers: the array it always
had.

A LATENT group (``KVCacheConfig.state_widths``, a latent-attention model): a
token's state a layer is a row of each stated width, not K and V of heads. The
cache is then a TUPLE of pools ``[layers, num_blocks, block_size, width]``, one
a width, that the one allocator's block ids address alike; a block id's bytes
are ``block_size x layers x sum(widths)`` values. What moves block CONTENTS
(fork, offload / restore, handoff frames) is written for the K/V array
(``CACHE_OPERATIONS``).

A per-SEQUENCE state group (``KVCacheConfig.sequence_state``, a model with
state-space layers): layers whose state is one array a live sequence whatever
its length. One pool a spec, ``[layers, slots, ...]``, zero-initialised, beside
what the blocks hold in the cache pytree: ``cache`` is then ``(K/V array, pool,
...)``, or ``((latent pool, ...), pool, ...)`` for a model whose other layers
keep latent rows (both groups in one cache: the tuple of latent pools stands
where the K/V array stood). A SLOT is what a block is to the K/V array: handed
out by an allocator of the same kind whose unit is a sequence (``reserve_slot``
/ ``free_slot``), at the sequence's first token and until its flush. The blocks
hold the layers that keep a row a token (``cache_shape[0]``), which such a
model counts apart from its slots' layers. A slot is in no block table
(``CACHE_OPERATIONS``: what such a cache refuses; one that is of several kinds
refuses by the name of each).
"""

import os
from typing import Optional, Tuple

import numpy as np

from deepspeed_tpu.inference.v2.ragged.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.v2.ragged.manager_configs import AllocationMode, KVCacheConfig, MemoryConfig
from deepspeed_tpu.inference.v2.ragged.tiering import TieredKVStore
from deepspeed_tpu.utils.logging import logger


def _dtype_size(name):
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[name]


def _cache_sharding(kv_heads: int):
    """Where the cache array lives: on the engine's mesh, KV heads split over
    the ``model`` axis (tensor parallelism shards attention by head) and
    replicated over every other axis — under expert parallelism each chip
    attends over all tokens and only the MoE exchanges them. None (the default
    device) when no mesh exists, i.e. a single-device engine."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.utils import groups
    if not groups.mesh_is_initialized():
        return None
    mesh = groups.get_mesh()
    tp = mesh.shape[groups.MODEL_AXIS]
    heads_axis = groups.MODEL_AXIS if tp > 1 and kv_heads % tp == 0 else None
    return NamedSharding(mesh, P(None, None, None, heads_axis, None, None))


# What SHARES a sequence's blocks (a prefix hit, its copy-on-write), MOVES them
# (offload / restore and the tier ladder, handoff / park / resume frames, export
# / import) or ROLLS tokens BACK (a speculative verify step, its re-pack, a
# rollback), and the kinds of cache that refuse each: operation -> (class, its
# name in a refusal where that says more than the key, kinds). A kind an
# operation does not name it serves, or leaves to the operation under it
# (``offload_sequence`` a latent group to ``gather_blocks``) or to its own look
# at the one sequence (``export_sequence`` and ``rollback`` under a window: the
# sequence may not have released anything yet; ``export_sequence`` of several
# tables: ``DSSequenceDescriptor.kv_blocks`` reads one, where there are blocks).
CACHE_OPERATIONS = {
    # what a deployment's configuration or a request asks for: theirs to fix
    "prefix_cache": ("share", "", "window latent slots blocks"),
    "kv_tiers": ("move", "", "window latent slots blocks"),
    "speculative": ("rollback", "", "slots blocks"),
    "frames": ("move", "handoff, park and resume frames", "window slots blocks"),
    # what a caller of the state manager, the pool, the engine or the model does
    "create_cached_sequence": ("share", "create_cached_sequence (a prefix-cache hit)",
                               "tables slots"),
    "fork_blocks": ("share", "fork_blocks (a prefix-cache copy-on-write)", "latent slots"),
    "offload_sequence": ("move", "", "slots"),
    "export_sequence": ("move", "export_sequence (a handoff or park frame)", "slots"),
    "import_sequence": ("move", "", "tables slots"),
    "gather_blocks": ("move", "gather_blocks (offload, a handoff or park frame)",
                      "latent slots"),
    "scatter_blocks": ("move", "scatter_blocks (restore, an imported frame)", "latent slots"),
    "verify_tree": ("rollback", "", "slots blocks"),
    "compact_kv": ("rollback", "compact_kv (a tree-verify re-pack)", "latent slots blocks"),
    "rollback": ("rollback", "", "slots"),
}
_ASKED = ("prefix_cache", "kv_tiers", "speculative", "frames")
# kind -> (what the cache is and why it refuses, the error of a call that runs into it)
_CACHE_KINDS = {
    "window": ("a sliding-window model (a layer's attention window is {}): its sequences release "
               "the blocks their window has passed, and what shares or moves a sequence carries "
               "its whole block table", ValueError),
    "tables": ("a model that keeps {} block tables a sequence (KV layer groups, some with a "
               "sliding window): a shared or exported table cannot stand for them", ValueError),
    "latent": ("a latent KV group (rows of widths {} a token a layer, not K and V of heads): what "
               "shares, moves or re-packs block contents is written for the K/V array, and a "
               "latent group has no speculative verify", NotImplementedError),
    "slots": ("a per-sequence state group ({}: a recurrent state a sequence, in a slot and in no "
              "block table): shared blocks, a moved table or a rolled-back draft would leave the "
              "slot's state behind, and it cannot be wound back without a snapshot a draft",
              NotImplementedError),
    "blocks": ("a model that generates by diffusion over blocks of {} positions: a step hands "
               "over a block of tokens, not one, a draft has no causal step to be verified by, "
               "and a request between two blocks holds rows that are not K/V yet, which a "
               "shared prefix, a tier or a frame would have to carry", NotImplementedError),
}


class _LazyAIO:
    """Spill-file I/O for the tiered store that defers to the cache's AIO
    engine — built lazily so a cache that never spills never imports
    ``ops.aio`` or touches the spill directory."""

    def __init__(self, cache: "BlockedKVCache"):
        self._cache = cache

    def sync_pwrite(self, buf, path):
        self._cache._aio_handle().sync_pwrite(buf, path)

    def sync_pread(self, buf, path):
        self._cache._aio_handle().sync_pread(buf, path)


class BlockedKVCache:

    def __init__(self, config: KVCacheConfig, memory_config: MemoryConfig,
                 offload_path: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        self._config = config
        num_layers, kv_heads, head_dim = config.cache_shape
        if num_layers % config.num_allocation_groups:
            raise ValueError(f"{num_layers} layers do not split into "
                             f"{config.num_allocation_groups} KV layer groups of equal depth")
        num_layers //= config.num_allocation_groups  # the layers one block id holds
        self._layers_per_group = num_layers
        widths = tuple(config.state_widths)
        # values a token keeps a layer: its state rows, or K and V of every head
        token_values = sum(widths) if widths else 2 * kv_heads * head_dim
        block_bytes = (config.block_size * num_layers * token_values *
                       _dtype_size(config.cache_dtype))
        if memory_config.mode == AllocationMode.RESERVE:
            num_blocks = max(1, int(memory_config.size // block_bytes))
        else:
            num_blocks = int(memory_config.size)
        self._num_blocks = num_blocks
        self._allocator = BlockedAllocator(num_blocks)

        dtype = {"bfloat16": jnp.bfloat16, "float16": jnp.float16, "float32": jnp.float32}[config.cache_dtype]
        if widths:
            # every head reads the one row: nothing to split, replicated over a mesh
            self._sharding = _cache_sharding(0)
            if self._sharding is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                self._sharding = NamedSharding(self._sharding.mesh, PartitionSpec())
            self._cache = tuple(jnp.zeros((num_layers, num_blocks, config.block_size, w), dtype,
                                          device=self._sharding) for w in widths)
        else:
            self._sharding = _cache_sharding(kv_heads)
            self._cache = jnp.zeros((num_layers, 2, num_blocks, kv_heads, config.block_size,
                                     head_dim), dtype, device=self._sharding)
        self._block_bytes = block_bytes
        logger.info(f"BlockedKVCache: {num_blocks} blocks x {config.block_size} tokens "
                    f"({num_blocks * block_bytes / 1e9:.2f} GB)")
        # a per-sequence state group: its pools ride in the cache pytree behind
        # the K/V array or the tuple of latent pools, its slots come from an
        # allocator of the blocks' kind
        self._slots = None
        if config.sequence_state:
            if config.sequence_slots < 1:
                raise ValueError("a per-sequence state group needs sequence_slots >= 1")
            whole = self._pool_sharding(config)
            self._slots = BlockedAllocator(config.sequence_slots)
            pools = tuple(jnp.zeros((spec.layers, config.sequence_slots) + tuple(spec.shape),
                                    jnp.dtype(spec.dtype), device=whole)
                          for spec in config.sequence_state)
            self._cache = (self._cache, ) + pools
            logger.info(f"BlockedKVCache: {config.sequence_slots} sequence slots "
                        f"({sum(p.nbytes for p in pools) / 1e9:.2f} GB in "
                        f"{[spec.name for spec in config.sequence_state]})")

        # off-device tiers (reference BlockedKVCache:40 declares
        # offload/restore and raises NotImplementedError — implemented here
        # as the host→disk ladder in ragged/tiering.py): offloaded payloads
        # land in host memory and demote to spill files under offload_path
        # when the host tier runs past its budget
        self._offload_path = offload_path
        self._aio = None
        self._tiers = TieredKVStore(spill_dir=offload_path, io=_LazyAIO(self))
        # pre-tiering NVMe semantics: offload_path with no host budget means
        # every offload spills to disk, synchronously (configure_tiering
        # replaces this with the budgeted async ladder)
        self._sync_spill = offload_path is not None
        self._restore_fn = None
        self._fork_fn = None

    @property
    def free_blocks(self) -> int:
        return self._allocator.free_blocks

    @property
    def num_blocks(self) -> int:
        return self._num_blocks

    @property
    def block_size(self) -> int:
        return self._config.block_size

    @property
    def cache(self):
        return self._cache

    @property
    def block_bytes(self) -> int:
        """Bytes one block id holds: ``block_size`` tokens of every layer of a group."""
        return self._block_bytes

    def _pool_sharding(self, config: KVCacheConfig):
        """Where a per-sequence state group's pools live: whole on every chip
        of the engine's mesh, as a latent group's pools are, beside them or
        beside the K/V array (every chip scans
        every sequence; under expert parallelism only the MoE exchanges
        tokens). Refused by name: a ``model`` axis, which splits attention by
        head and would have to split the pools by theirs; and more bytes than
        the device has free (``sequence_slots`` is the engine's
        ``max_tracked_sequences``, 2048 unless the deployment says)."""
        from jax.sharding import NamedSharding, PartitionSpec

        from deepspeed_tpu.accelerator import get_accelerator
        from deepspeed_tpu.utils import groups
        names = [spec.name for spec in config.sequence_state]
        mesh = self._sharding.mesh if self._sharding is not None else None
        if mesh is not None and mesh.shape[groups.MODEL_AXIS] > 1:
            raise NotImplementedError(
                f"a per-sequence state group ({names}) on a mesh with model="
                f"{mesh.shape[groups.MODEL_AXIS]}: tensor parallelism splits the K/V array by "
                f"head, and these pools (a state a sequence a layer) are not split by theirs")
        need = sum(spec.layers * config.sequence_slots * int(np.prod(spec.shape))
                   * _dtype_size(spec.dtype) for spec in config.sequence_state)
        free = get_accelerator().available_memory()
        if get_accelerator().total_memory() and need > free:
            raise ValueError(
                f"a per-sequence state group ({names}) of {config.sequence_slots} slots "
                f"(state_manager.max_tracked_sequences: a slot a tracked sequence) needs "
                f"{need / 1e9:.2f} GB, {need / config.sequence_slots / 1e6:.2f} MB a slot, and "
                f"the device has {free / 1e9:.2f} GB free beside the weights and the K/V "
                f"blocks: lower max_tracked_sequences to what the deployment serves at once")
        return None if mesh is None else NamedSharding(mesh, PartitionSpec())

    @property
    def num_slots(self) -> int:
        """Slots of the per-sequence state group; 0 for a cache without one."""
        return self._config.sequence_slots if self._slots is not None else 0

    @property
    def free_slots(self):
        """Free slots of the per-sequence state group; None without one."""
        return None if self._slots is None else self._slots.free_blocks

    def reserve_slot(self) -> int:
        return int(self._slots.allocate(1)[0])

    def free_slot(self, slot: int) -> None:
        self._slots.free([slot])

    def refusal(self, operation: str) -> Optional[Exception]:
        """Why this cache cannot serve ``operation`` (a name of
        ``CACHE_OPERATIONS``), as the error to raise, or None where it can.
        Read off what the cache holds: a window over some group (holes in a
        table), more tables than one a sequence, latent rows, slots; a cache of
        several of the operation's kinds (latent rows AND slots) is refused by
        the name of each. A configuration's or a request's refusal is a
        ``ValueError``; a call's is its first kind's."""
        config = self._config
        _, said, kinds = CACHE_OPERATIONS[operation]
        mine = {"window": max(config.group_windows),
                "tables": config.num_allocation_groups > 1 and config.num_allocation_groups,
                "latent": tuple(config.state_widths),
                "slots": [spec.name for spec in config.sequence_state],
                "blocks": config.attention_block}
        held = [kind for kind in kinds.split() if mine[kind]]
        if not held:
            return None
        is_a = " and ".join(_CACHE_KINDS[kind][0].format(mine[kind]) for kind in held)
        return (ValueError if operation in _ASKED else _CACHE_KINDS[held[0]][1])(
            f"{said or operation} cannot serve {is_a} — recompute the sequence instead")

    def refuse(self, operation: str) -> None:
        """Raise :meth:`refusal`'s answer, where it has one."""
        error = self.refusal(operation)
        if error is not None:
            raise error

    @property
    def sharding(self):
        """The cache's ``NamedSharding`` on the engine's mesh; None for a cache
        on the default device of a mesh-less engine."""
        return self._sharding

    def set_cache(self, cache):
        self._cache = cache

    def reserve(self, num_blocks: int):
        return self._allocator.allocate(num_blocks)

    def free(self, blocks):
        self._allocator.free(blocks)

    def incref(self, blocks) -> None:
        """Add one reference per block (prefix-cache sharing; see
        ``BlockedAllocator.incref``). ``free`` is the matching decref."""
        self._allocator.incref(blocks)

    def ref_count(self, block: int) -> int:
        return self._allocator.ref_count(block)

    def fork_blocks(self, src_blocks) -> np.ndarray:
        """Copy-on-write fork: allocate fresh blocks and device-copy
        ``src_blocks``' contents (every layer, K and V) into them, returning
        the new ids. The sources are untouched — the caller maps the copies
        into a sequence that is about to *write* where the sources are shared
        read-only (the prefix cache's first-divergent-block fork). A failed
        allocation consumes nothing."""
        import jax
        import jax.numpy as jnp

        self.refuse("fork_blocks")
        src_blocks = np.atleast_1d(np.asarray(src_blocks)).astype(np.int64)
        new_blocks = self._allocator.allocate(src_blocks.size)
        if self._fork_fn is None:
            self._fork_fn = jax.jit(
                lambda cache, src, dst: cache.at[:, :, dst].set(cache[:, :, src]),
                donate_argnums=(0, ))
        try:
            new_cache = self._fork_fn(self._cache, jnp.asarray(src_blocks),
                                      jnp.asarray(new_blocks))
            jax.block_until_ready(new_cache)
        except Exception:
            self._allocator.free(new_blocks)
            raise
        self._cache = new_cache
        return new_blocks

    def gather_blocks(self, blocks) -> np.ndarray:
        """Device→host copy of ``blocks``' contents (every layer, K and V)
        WITHOUT freeing them — the read half of :meth:`offload`, reused by the
        fleet KV-handoff exporter (``ragged/handoff.py``), where the donor
        keeps its blocks until the recipient has taken over."""
        import jax
        import jax.numpy as jnp

        self.refuse("gather_blocks")
        blocks = np.atleast_1d(np.asarray(blocks)).astype(np.int64)
        return np.asarray(jax.device_get(self._cache[:, :, jnp.asarray(blocks)]))

    def scatter_blocks(self, data) -> np.ndarray:
        """Allocate fresh device blocks and write ``data`` (a
        :meth:`gather_blocks`/offload-shaped payload
        ``[layers, 2, n, kv_heads, block_size, head_dim]``) into them; returns
        the new block ids — the write half of :meth:`restore`, reused by the
        fleet KV-handoff importer. A failed allocation or write consumes
        nothing."""
        self.refuse("scatter_blocks")
        data = np.asarray(data)
        _, kv_heads, head_dim = self._config.cache_shape
        num_layers = self._layers_per_group
        expect = (num_layers, 2, kv_heads, self._config.block_size, head_dim)
        got = data.shape[:2] + data.shape[3:] if data.ndim == 6 else None
        if got != expect:
            raise ValueError(
                f"scatter_blocks: payload shape {data.shape} does not fit this "
                f"cache's geometry [layers=2x{num_layers}, n, kv_heads={kv_heads}, "
                f"block_size={self._config.block_size}, head_dim={head_dim}]")
        new_blocks = self._allocator.allocate(data.shape[2])
        try:
            self._write_blocks(data, new_blocks)
        except Exception:
            self._allocator.free(new_blocks)
            raise
        return new_blocks

    def _write_blocks(self, data, block_ids) -> None:
        import jax
        import jax.numpy as jnp

        if self._restore_fn is None:
            self._restore_fn = jax.jit(
                lambda cache, payload, ids: cache.at[:, :, ids].set(payload.astype(cache.dtype)),
                donate_argnums=(0, ))
        new_cache = self._restore_fn(self._cache, jnp.asarray(data),
                                     jnp.asarray(block_ids))
        jax.block_until_ready(new_cache)
        self._cache = new_cache

    def offload(self, blocks) -> int:
        """Move ``blocks``' contents (every layer, K and V) to the host tier
        and free the device blocks for reuse. Returns a handle for
        :meth:`restore`.

        Role parity: reference ``kv_cache.py`` ``offload`` (declared :166,
        unimplemented there). Divergence: device block ids are NOT stable
        across an offload — freeing returns them to the allocator, and restore
        hands back fresh ids (the caller rewrites its block table; the
        state manager's ``offload_sequence`` does exactly that). This is the
        functional-array formulation: the cache is an immutable jax array, so
        "parking" data in place has no meaning.
        """
        blocks = np.atleast_1d(np.asarray(blocks)).astype(np.int64)
        data = self.gather_blocks(blocks)
        handle = self._tiers.put(data)
        self._allocator.free(blocks)
        if self._sync_spill:
            self._tiers.demote(handle, wait=True)
        return handle

    def restore(self, handle: int) -> np.ndarray:
        """Allocate fresh device blocks, write the offloaded contents back,
        and return the new block ids (see :meth:`offload` on id stability)."""
        needed = self._tiers.n_blocks(handle)
        if needed > self._allocator.free_blocks:
            # fail before touching disk: the caller's evict-and-retry loop
            # must not pay a full payload read per failed attempt
            raise ValueError(
                f"Allocator has {self._allocator.free_blocks} free blocks, "
                f"but {needed} were requested")
        data, _tier = self._tiers.read(handle)
        # on failure the payload stays in the store (and on disk): the
        # caller's evict-and-retry contract depends on it surviving a failed
        # restore
        new_blocks = self.scatter_blocks(data)
        self._tiers.drop(handle)
        return new_blocks

    def drop_offloaded(self, handle: int) -> None:
        """Discard an offloaded payload without restoring (sequence flushed)."""
        self._tiers.drop(handle)

    def configure_tiering(self, spill_dir: Optional[str] = None,
                          host_bytes: Optional[int] = None) -> None:
        """Enable the budgeted host→disk ladder (serving ``kv_tiers`` config
        arrives after the engine — and this cache — are built). Replaces the
        legacy spill-everything-synchronously NVMe mode: offloads land in host
        memory and demote asynchronously when over ``host_bytes``."""
        if spill_dir is not None:
            self._offload_path = spill_dir
        self._sync_spill = False
        self._tiers.configure(spill_dir=spill_dir, host_bytes=host_bytes)

    def offload_tier(self, handle: int) -> str:
        """Which tier currently holds an offloaded payload (host | disk)."""
        return self._tiers.tier_of(handle)

    def demote_offloaded(self, handle: int, wait: bool = False) -> bool:
        """Push one offloaded payload host→disk (brownout's demote stage)."""
        return self._tiers.demote(handle, wait=wait)

    def tier_stats(self) -> dict:
        return self._tiers.stats()

    @property
    def tiered_store(self) -> TieredKVStore:
        return self._tiers

    def _aio_handle(self):
        if self._aio is None:
            from deepspeed_tpu.ops.aio import AsyncIOHandle
            os.makedirs(self._offload_path, exist_ok=True)
            self._aio = AsyncIOHandle(thread_count=2)
        return self._aio
