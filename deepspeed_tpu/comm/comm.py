"""Public collectives API over XLA.

TPU-native analog of ``deepspeed/comm/comm.py`` (the torch.distributed-compatible
surface: all_reduce / all_gather_into_tensor / reduce_scatter_tensor /
all_to_all_single / broadcast / barrier, plus ``init_distributed`` with env
discovery and the ``@timed_op`` comms-profiling wrapper, comm.py:101-771).

SPMD semantics
--------------
The reference's collectives act on *per-rank local tensors*. Under single-controller
SPMD the equivalent is a jax.Array sharded over the group's mesh axes along its
leading dimension — shard i plays the role of rank i's local tensor:

  - ``all_reduce(x, group)``:    x:[G, ...] sharded on dim0 → each shard replaced by
                                 the elementwise reduction over shards (shape kept).
  - ``all_gather_into_tensor``:  x:[G, s, ...] sharded on dim0 → [G*s, ...] fully
                                 replicated (torch-style concat along dim0).
  - ``reduce_scatter_tensor``:   x:[G, G*s, ...] sharded dim0 → [G, s, ...] sharded
                                 dim0; shard i = sum over ranks of slice i.
  - ``all_to_all_single``:       x:[G, G, ...] sharded dim0 → transpose of rank/chunk.
  - ``broadcast(x, src)``:       every shard replaced by shard ``src``.

``group`` is a mesh-axis name or tuple of names (see utils/groups.py); None means
the dense data-parallel group. These eager wrappers are for host-driven code and
tests; inside a jitted train step use ``jax.lax`` collectives directly — the engine
does — so XLA can fuse and overlap them.
"""

import functools
import os
import time

import numpy as np

from deepspeed_tpu.comm.backend import Backend
from deepspeed_tpu.comm.reduce_op import ReduceOp
from deepspeed_tpu.utils import groups as groups_mod
from deepspeed_tpu.utils.comms_logging import CommsLogger
from deepspeed_tpu.utils.logging import logger

cdb = None  # current distributed backend (reference: comm.py:41)
comms_logger = CommsLogger()
timers = {}


class XLABackend(Backend):
    """The one backend: XLA collectives over the global mesh (ICI/DCN)."""

    def __init__(self):
        import jax
        super().__init__(name="xla", rank=jax.process_index(), size=jax.process_count())
        self.init_process_group()


def is_initialized():
    return cdb is not None


def init_distributed(dist_backend="xla",
                     auto_mpi_discovery=True,
                     distributed_port=29500,
                     verbose=True,
                     timeout=None,
                     init_method=None,
                     dist_init_required=None,
                     config=None,
                     rank=-1,
                     world_size=-1):
    """Bootstrap multi-host JAX + build the global mesh.

    Reference: comm.py:604-771 (init_distributed with MPI/AML/SageMaker discovery
    feeding torch.distributed rendezvous). Here the rendezvous is JAX's coordination
    service: on multi-host launches we call ``jax.distributed.initialize`` with
    coordinator discovery from env (DSTPU_COORDINATOR / MASTER_ADDR, or OpenMPI vars
    as in the reference's ``mpi_discovery``).
    """
    global cdb
    if cdb is not None:
        return cdb
    import jax

    coord = os.environ.get("DSTPU_COORDINATOR") or os.environ.get("COORDINATOR_ADDRESS")
    nproc = int(os.environ.get("DSTPU_NUM_PROCESSES", os.environ.get("WORLD_SIZE", "0")) or 0)
    proc_id = os.environ.get("DSTPU_PROCESS_ID", os.environ.get("RANK"))
    if coord is None and auto_mpi_discovery and "OMPI_COMM_WORLD_SIZE" in os.environ:
        # OpenMPI discovery, reference comm.py mpi_discovery()
        nproc = int(os.environ["OMPI_COMM_WORLD_SIZE"])
        proc_id = os.environ["OMPI_COMM_WORLD_RANK"]
        coord = f"{os.environ.get('MASTER_ADDR', 'localhost')}:{distributed_port}"
    if coord is not None and nproc > 1:
        _enable_cpu_cross_process_collectives()
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc,
                                   process_id=int(proc_id or 0))
        if verbose:
            logger.info(f"jax.distributed initialized: process {jax.process_index()}/{jax.process_count()}")
    # initialize() blocks until every process joined the (freshly bound)
    # coordinator, so this instant is gang-synchronized to within the release
    # skew — monitored_barrier uses it to reject a PREVIOUS job's leftover
    # rendezvous files when no DSTPU_JOB_ID scopes the rendezvous dir
    global _init_done_unix
    _init_done_unix = time.time()
    cdb = XLABackend()
    return cdb


_init_done_unix = None  # set by init_distributed (gang-synchronized instant)


def _enable_cpu_cross_process_collectives():
    """CPU gangs need an explicit cross-process collectives backend: the
    default CPU client refuses multi-process computations outright
    ("Multiprocess computations aren't implemented on the CPU backend"), which
    is what broke ``test_local_two_process_training`` from seed. jaxlib ships
    gloo; selecting it *before* ``jax.distributed.initialize`` makes a
    multi-process CPU mesh a real gang — the tier-1 formulation every gang
    fault-tolerance gate trains on. TPU/GPU platforms are untouched (their
    collectives ride ICI/DCN/NCCL natively)."""
    import jax
    platforms = (getattr(jax.config, "jax_platforms", None)
                 or os.environ.get("JAX_PLATFORMS") or "")
    if not platforms:
        # unset = jax autodetects; guessing CPU here would break TPU/GPU
        # hosts, but a CPU-only host WILL hit "Multiprocess computations
        # aren't implemented on the CPU backend" — say so up front
        logger.warning("multi-process init with JAX_PLATFORMS unset: if this "
                       "host resolves to the CPU backend, set "
                       "JAX_PLATFORMS=cpu so the gloo cross-process "
                       "collectives backend is selected")
        return
    if platforms.split(",")[0].strip().lower() != "cpu":
        return
    if jax.config.jax_cpu_collectives_implementation != "gloo":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        logger.info("CPU gang: cross-process collectives backend = gloo")


def destroy_process_group(group=None):
    global cdb
    cdb = None


def get_rank(group=None):
    """Host process rank (reference rank == device rank; under SPMD one process
    drives many devices, so this is the process index)."""
    import jax
    return jax.process_index()


def get_world_size(group=None):
    """Number of devices in ``group`` (mesh axes), or all devices if None."""
    import jax
    if group is None:
        return len(jax.devices())
    return groups_mod._axis_size(group)


def get_local_rank():
    return int(os.environ.get("LOCAL_RANK", 0))


# ---- eager collective implementations --------------------------------------------


def _resolve_group(group):
    if group is None:
        group = groups_mod.get_data_parallel_axes()
    if isinstance(group, str):
        group = (group, )
    return tuple(group)


def _group_spec(axes):
    from jax.sharding import PartitionSpec as P
    return P(axes)


_REDUCE_FNS = None


def _reduce_fn(op):
    import jax
    import jax.numpy as jnp
    global _REDUCE_FNS
    if _REDUCE_FNS is None:
        _REDUCE_FNS = {
            ReduceOp.SUM: jax.lax.psum,
            ReduceOp.AVG: lambda x, ax: jax.lax.pmean(x, ax),
            ReduceOp.MAX: jax.lax.pmax,
            ReduceOp.MIN: jax.lax.pmin,
            ReduceOp.PRODUCT: lambda x, ax: jnp.exp(jax.lax.psum(jnp.log(x), ax)),
        }
    if op not in _REDUCE_FNS:
        raise NotImplementedError(f"ReduceOp {op} not supported")
    return _REDUCE_FNS[op]


def timed_op(func):
    """Profile collectives through the comms logger and/or the unified
    telemetry layer (reference: comm.py:101-134 @timed_op). Disabled (the
    default) the wrapper costs two boolean checks and nothing else — the
    telemetry registry/span sinks are only touched when ``telemetry.state
    .active``."""
    from deepspeed_tpu import telemetry

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        if not (comms_logger.enabled or telemetry.state.active):
            return func(*args, **kwargs)
        import jax
        name = func.__name__
        t0 = time.time()
        result = func(*args, **kwargs)
        jax.block_until_ready(result)
        elapsed = time.time() - t0
        tensor = args[0] if args else kwargs.get("tensor")
        size = int(np.prod(tensor.shape)) * tensor.dtype.itemsize if tensor is not None else 0
        if comms_logger.enabled:
            comms_logger.append(name, kwargs.get("log_name", name), elapsed, size)
        if telemetry.state.active:
            telemetry.record_comm_op(name, elapsed, size)
        return result

    return wrapper


def _shard_map(fn, in_specs, out_specs):
    import jax
    mesh = groups_mod.get_mesh()
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)


def _device_put_grouped(tensor, axes):
    """Lay ``tensor`` out with dim0 sharded over the group axes."""
    import jax
    from jax.sharding import NamedSharding
    mesh = groups_mod.get_mesh()
    sharding = NamedSharding(mesh, _group_spec(axes))
    return jax.device_put(tensor, sharding)


@timed_op
def all_reduce(tensor, op=ReduceOp.SUM, group=None, async_op=False, log_name=None):
    axes = _resolve_group(group)
    red = _reduce_fn(op)
    spec = _group_spec(axes)
    tensor = _device_put_grouped(tensor, axes)
    return _shard_map(lambda x: red(x, axes), spec, spec)(tensor)


@timed_op
def inference_all_reduce(tensor, op=ReduceOp.SUM, group=None, async_op=False, log_name=None):
    return all_reduce(tensor, op=op, group=group)


@timed_op
def all_gather_into_tensor(tensor, group=None, async_op=False, log_name=None):
    import jax
    axes = _resolve_group(group)
    spec = _group_spec(axes)
    tensor = _device_put_grouped(tensor, axes)
    from jax.sharding import PartitionSpec as P

    def f(x):
        # x: [G_local=1, s, ...] → concat over group → [G*s, ...]
        g = jax.lax.all_gather(x, axes, axis=0, tiled=True)
        return g.reshape((-1, ) + g.shape[2:])

    return _shard_map(f, spec, P())(tensor)


# legacy name used across the reference
allgather_fn = all_gather_into_tensor


@timed_op
def reduce_scatter_tensor(tensor, op=ReduceOp.SUM, group=None, async_op=False, log_name=None):
    import jax
    axes = _resolve_group(group)
    spec = _group_spec(axes)
    tensor = _device_put_grouped(tensor, axes)
    red = "sum" if op in (ReduceOp.SUM, ReduceOp.AVG) else None
    if red is None:
        raise NotImplementedError("reduce_scatter supports SUM/AVG")
    G = groups_mod._axis_size(axes)

    def f(x):
        # x: [1, G*s, ...] per rank → scatter dim1 into G chunks, sum over ranks
        chunks = x.reshape((G, -1) + x.shape[2:])  # [G, s, ...]
        out = jax.lax.psum_scatter(chunks, axes, scatter_dimension=0, tiled=False)
        if op == ReduceOp.AVG:
            out = out / G
        return out[None]  # [1, s, ...]

    return _shard_map(f, spec, spec)(tensor)


reduce_scatter_fn = reduce_scatter_tensor


@timed_op
def all_to_all_single(tensor, group=None, async_op=False, log_name=None):
    import jax
    axes = _resolve_group(group)
    spec = _group_spec(axes)
    tensor = _device_put_grouped(tensor, axes)

    def f(x):
        # x: [1, G, ...] per rank; exchange chunk j with rank j.
        return jax.lax.all_to_all(x, axes, split_axis=1, concat_axis=0, tiled=False).reshape(x.shape)

    return _shard_map(f, spec, spec)(tensor)


@timed_op
def broadcast(tensor, src=0, group=None, async_op=False, log_name=None):
    import jax
    import jax.numpy as jnp
    axes = _resolve_group(group)
    spec = _group_spec(axes)
    tensor = _device_put_grouped(tensor, axes)

    def f(x):
        idx = jax.lax.axis_index(axes)
        masked = jnp.where(idx == src, x, jnp.zeros_like(x))
        return jax.lax.psum(masked, axes)

    return _shard_map(f, spec, spec)(tensor)


@timed_op
def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, async_op=False, log_name=None):
    # On an SPMD mesh a rooted reduce has no cost advantage over all_reduce.
    return all_reduce(tensor, op=op, group=group)


def all_gather(tensor, group=None, async_op=False, log_name=None):
    """Reference list-based all_gather; the SPMD form returns the stacked
    [G, ...] tensor (what the reference writes into its tensor_list)."""
    return all_gather_into_tensor(tensor, group=group)


def all_gather_coalesced(tensors, group=None, async_op=False):
    return [all_gather_into_tensor(t, group=group) for t in tensors]


def all_reduce_coalesced(tensors, op=ReduceOp.SUM, group=None, async_op=False):
    return [all_reduce(t, op=op, group=group) for t in tensors]


def all_to_all(tensor, group=None, async_op=False, log_name=None):
    return all_to_all_single(tensor, group=group)


def reduce_scatter(tensor, op=ReduceOp.SUM, group=None, async_op=False, log_name=None):
    return reduce_scatter_tensor(tensor, op=op, group=group)


def gather(tensor, dst=0, group=None, async_op=False, log_name=None):
    """Rooted gather: under SPMD the gathered result exists on every rank (a
    rooted variant has no cost advantage on a mesh) — reference semantics are
    a superset."""
    return all_gather_into_tensor(tensor, group=group)


def scatter(tensor, src=0, group=None, async_op=False, log_name=None):
    """Rank r receives chunk r of the SOURCE rank's row (stacked layout:
    dim0 = ranks, each row = the flattened scatter list) — the inverse of
    :func:`all_gather`."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.utils import groups as _g

    axes = _resolve_group(group)
    spec = _group_spec(axes)
    tensor = _device_put_grouped(tensor, axes)
    mesh = _g.get_mesh()
    G = 1
    for ax in (axes if isinstance(axes, (tuple, list)) else (axes, )):
        G *= mesh.shape.get(ax, 1)

    if tensor.ndim < 2:
        raise ValueError("scatter expects the stacked [ranks, chunks...] layout "
                         "(dim0 = ranks, dim1 = the flattened scatter list)")
    if tensor.shape[1] % G != 0:
        raise ValueError(f"scatter: dim-1 size {tensor.shape[1]} must divide evenly "
                         f"into {G} chunks (the reference rejects unequal chunks too)")

    def f(x):
        idx = jax.lax.axis_index(axes)
        masked = jnp.where(idx == src, x, jnp.zeros_like(x))
        full = jax.lax.psum(masked, axes)  # the source row, on every rank
        chunk = full.shape[1] // G
        return jax.lax.dynamic_slice_in_dim(full, idx * chunk, chunk, axis=1)

    return _shard_map(f, spec, spec)(tensor)


# -- point-to-point: no user-level p2p under single-program SPMD ---------------
def send(tensor, dst, group=None, tag=0):
    raise NotImplementedError("point-to-point send/recv does not exist under "
                              "single-program SPMD; express neighbor exchange with "
                              "jax.lax.ppermute inside shard_map (see runtime/pipe)")


def recv(tensor, src, group=None, tag=0):
    raise NotImplementedError("see send(): use jax.lax.ppermute inside shard_map")


def isend(tensor, dst, group=None, tag=0):
    return send(tensor, dst, group, tag)


def irecv(tensor, src, group=None, tag=0):
    return recv(tensor, src, group, tag)


# -- groups / ranks -------------------------------------------------------------
def get_world_group():
    """The whole-mesh group (None = all axes in this API)."""
    return None


def new_group(ranks=None):
    """Mesh axes ARE the process groups here; arbitrary rank sets cannot be
    carved out of an SPMD mesh. The world group (all ranks, device-count
    convention like get_world_size) is allowed for compatibility."""
    if ranks is None or sorted(ranks) == list(range(get_world_size())):
        return None
    raise NotImplementedError("arbitrary-rank groups: use mesh axis names "
                              "(groups.initialize_mesh) as the group structure")


def get_global_rank(group=None, group_rank=0):
    if group is None:
        return int(group_rank)
    raise NotImplementedError(
        "an axis-name group has one replica per remaining-mesh coordinate, so "
        "group_rank alone does not determine a global rank; compute positions "
        "with jax.lax.axis_index inside shard_map instead")


def get_all_ranks_from_group(group=None):
    from deepspeed_tpu.utils import groups as _g
    axes = _resolve_group(group)
    size = 1
    mesh = _g.get_mesh()
    for ax in (axes if isinstance(axes, (tuple, list)) else (axes, )):
        size *= mesh.shape.get(ax, 1)
    return list(range(size))


# -- capability probes (reference has_* feature detection) ----------------------
def is_available() -> bool:
    return True


def has_all_gather_into_tensor() -> bool:
    return True


def has_reduce_scatter_tensor() -> bool:
    return True


def has_all_reduce_coalesced() -> bool:
    return True


def has_coalescing_manager() -> bool:
    return False  # XLA fuses collectives; there is no manual manager


def set_backend(backend_name=None):
    ...  # the XLA backend is the only one; kept for API parity


def init_deepspeed_backend(ds_backend=None, timeout=None, init_method=None):
    ...  # init_distributed covers this


def mpi_discovery(distributed_port=29500, verbose=True):
    """Populate the full DSTPU_* rendezvous contract from OpenMPI env
    (reference comm.py mpi_discovery: rank/size from env, the coordinator
    address broadcast from rank 0 via mpi4py — MASTER_ADDR/PORT there)."""
    import os
    import socket
    env = os.environ
    if "OMPI_COMM_WORLD_RANK" not in env:
        return
    env.setdefault("DSTPU_PROCESS_ID", env["OMPI_COMM_WORLD_RANK"])
    env.setdefault("DSTPU_NUM_PROCESSES", env["OMPI_COMM_WORLD_SIZE"])
    if "DSTPU_COORDINATOR" not in env:
        try:
            from mpi4py import MPI
            comm = MPI.COMM_WORLD
            host = comm.bcast(socket.gethostbyname(socket.gethostname()), root=0)
            env["DSTPU_COORDINATOR"] = f"{host}:{distributed_port}"
        except ImportError:
            logger.warning("mpi_discovery: mpi4py unavailable — set DSTPU_COORDINATOR "
                           "to rank-0's host:port yourself or use the dstpu launcher "
                           "(it exports the full contract)")
    if verbose:
        logger.info(f"mpi_discovery: rank={env['DSTPU_PROCESS_ID']} "
                    f"world={env['DSTPU_NUM_PROCESSES']} "
                    f"coordinator={env.get('DSTPU_COORDINATOR', 'UNSET')}")


# -- cloud-environment detectors (reference comm.py:586-676) --------------------
def in_aml() -> bool:
    import os
    return "AZUREML_EXPERIMENT_ID" in os.environ


def in_aws_sm() -> bool:
    import os
    return "SM_TRAINING_ENV" in os.environ


def in_dlts() -> bool:
    import os
    return "DLTS_JOB_ID" in os.environ


def patch_aml_env_for_torch_nccl_backend(*a, **k):
    ...  # NCCL env shims do not apply to the XLA backend


def patch_aws_sm_env_for_torch_nccl_backend(*a, **k):
    ...


def barrier(group=None):
    import jax
    jax.effects_barrier()


class BarrierTimeoutError(RuntimeError):
    """``monitored_barrier`` expired its deadline; the message names the
    absent ranks (the reference raises the first absent rank unless
    ``wait_all_ranks`` — here the full set is always collected, it costs
    nothing with a file rendezvous)."""


DEFAULT_BARRIER_TIMEOUT_S = 300.0

# per-(name) generation counters: barrier semantics require every rank to
# reach every barrier, so per-process counters agree across the gang
_barrier_generations = {}


def _barrier_timeouts_metric():
    from deepspeed_tpu import telemetry
    if not telemetry.is_active():
        return None
    return telemetry.get_registry().counter(
        "barrier_timeouts_total",
        "monitored_barrier deadline expiries (absent ranks named in the error)")


def _barrier_rendezvous_dir():
    """Where ranks rendezvous: the gang dir when the elastic agent armed one
    (shared-fs multi-host gangs set it explicitly), else a coordinator-keyed
    tempdir — same-host CPU gangs (the tier-1 formulation) share /tmp."""
    from deepspeed_tpu.elasticity.gang import GANG_DIR_ENV
    gang_dir = os.environ.get(GANG_DIR_ENV)
    if gang_dir:
        return os.path.join(gang_dir, "barriers")
    coord = os.environ.get("DSTPU_COORDINATOR") or os.environ.get("COORDINATOR_ADDRESS")
    if not coord:
        return None
    import hashlib
    import tempfile
    # key by coordinator AND the per-launch job nonce (launcher/launch.py,
    # DSElasticAgent both export one): a later job reusing the same
    # coordinator address must never rendezvous against this job's leftovers
    job = os.environ.get("DSTPU_JOB_ID", "")
    key = hashlib.sha1(f"{coord}|{job}".encode()).hexdigest()[:12]
    return os.path.join(tempfile.gettempdir(), f"dstpu_barrier_{key}")


def _file_barrier(bdir, name, generation, rank, world, timeout_s, poll_s=0.02,
                  min_unix=None, on_wait=None):
    """Rendezvous: every rank drops ``<name>.g<gen>.rank<k>`` and polls until
    all ``world`` files of this generation exist. Deadline expiry raises
    :class:`BarrierTimeoutError` naming the absent ranks. Files persist one
    generation (a rank may observe completion and race ahead before a slow
    peer has read the files), then each rank reaps its own older ones.

    ``min_unix``: only accept peer files stamped at or after it — the guard
    against a PREVIOUS job's leftovers in a shared rendezvous dir (a stale
    file predates the current job's coordinator bind, so any stamp from this
    gang's init epoch onward is fresh; only meaningful when all ranks share
    one clock). None = accept any file. ``on_wait`` is called once per poll
    iteration while waiting (liveness reporting)."""
    import time as _time
    os.makedirs(bdir, exist_ok=True)

    def fname(g, r):
        return os.path.join(bdir, f"{name}.g{g}.rank{r}")

    accepted = set()  # a once-fresh file can only be replaced by a fresher one

    def present(g, r):
        if r in accepted:
            return True
        fp = fname(g, r)
        if not os.path.exists(fp):
            return False
        if min_unix is not None:
            try:
                with open(fp) as f:
                    import json as _json
                    if _json.load(f).get("unix", 0) < min_unix:
                        return False
            except (OSError, ValueError):
                return False  # torn/stale: the owner rewrites it atomically
        accepted.add(r)
        return True

    from deepspeed_tpu.elasticity.gang import atomic_write_json
    atomic_write_json(fname(generation, rank), {"rank": rank, "unix": _time.time()})
    deadline = _time.monotonic() + timeout_s
    while True:
        absent = [r for r in range(world) if not present(generation, r)]
        if not absent:
            break
        if _time.monotonic() > deadline:
            m = _barrier_timeouts_metric()
            if m is not None:
                m.inc()
            raise BarrierTimeoutError(
                f"monitored_barrier {name!r} (generation {generation}) timed "
                f"out after {timeout_s:.1f}s: rank {rank} waited on absent "
                f"ranks {absent} of world {world}")
        if on_wait is not None:
            on_wait()
        _time.sleep(poll_s)
    # reap this rank's file from two generations back — old enough that every
    # peer has necessarily left that barrier (they are at generation-1+)
    if generation >= 2:
        try:
            os.unlink(fname(generation - 2, rank))
        except OSError:
            pass


def monitored_barrier(group=None, timeout=None, wait_all_ranks=False, name="monitored"):
    """A barrier that actually enforces its ``timeout`` (the reference's
    torch.distributed ``monitored_barrier``; the seed version silently
    dropped it — a dead rank wedged its peers forever). ``timeout`` is
    seconds or a ``datetime.timedelta``; expiry raises
    :class:`BarrierTimeoutError` naming the absent ranks and counts
    ``barrier_timeouts_total``.

    Multi-process gangs rendezvous through files (the gang dir when the
    elastic agent armed one, else a coordinator-keyed tempdir — CPU gangs
    share a host). Single-process worlds reduce to an effects barrier. When
    no rendezvous dir is derivable (no gang dir, no coordinator), the
    deadline is unenforceable; that is logged loudly and the call falls
    back to the plain barrier."""
    import datetime
    import jax
    world = jax.process_count()
    if world <= 1:
        barrier(group)
        return
    if isinstance(timeout, datetime.timedelta):
        timeout_s = timeout.total_seconds()
    else:
        timeout_s = DEFAULT_BARRIER_TIMEOUT_S if timeout is None else float(timeout)
    bdir = _barrier_rendezvous_dir()
    if bdir is None:
        logger.warning("monitored_barrier: no rendezvous dir (set "
                       "DSTPU_GANG_DIR or DSTPU_COORDINATOR); the timeout "
                       "cannot be enforced — falling back to a plain barrier")
        barrier(group)
        return
    rank = jax.process_index()
    # scope by supervision life: a relaunched gang starts at generation 0
    # again, and the previous life's rendezvous files must not satisfy it
    name = f"{name}.l{os.environ.get('DSTPU_RESTART_COUNT', '0') or '0'}"
    generation = _barrier_generations.get(name, 0)
    _barrier_generations[name] = generation + 1
    # collective entry is a liveness event: a rank blocked here past the
    # deadline raises; a rank that never *arrives* shows a stale heartbeat.
    # While WAITING, keep beating (throttled): a rank legitimately parked at
    # a barrier behind a slow peer is making supervised progress — the hang
    # watchdog must not tear down a healthy gang for it
    from deepspeed_tpu.elasticity.gang import GANG_DIR_ENV, GangHeartbeat
    hb = GangHeartbeat.from_env(rank=rank)
    on_wait = None
    if hb is not None:
        hb.beat(phase=f"barrier:{name}")
        last_beat = [time.monotonic()]

        def on_wait():
            now = time.monotonic()
            if now - last_beat[0] >= 1.0:
                last_beat[0] = now
                hb.beat(phase=f"barrier:{name}")
    # without a job-scoped dir (manual launches: no DSTPU_JOB_ID) a previous
    # job on the same coordinator left files here; anything stamped before
    # this gang's init epoch (minus clock slack) is stale — a dead rank must
    # time the barrier out, not be impersonated by a leftover. Only armed on
    # the host-local tempdir path: a shared-fs gang dir spans hosts whose
    # wall clocks must not be compared
    min_unix = None
    if not os.environ.get("DSTPU_JOB_ID") and not os.environ.get(GANG_DIR_ENV) \
            and _init_done_unix is not None:
        min_unix = _init_done_unix - 5.0
    _file_barrier(bdir, name, generation, rank, world, timeout_s,
                  min_unix=min_unix, on_wait=on_wait)
    barrier(group)


def log_summary(show_straggler=False):
    """Print per-op communication statistics (reference: comm.py:422).

    With ``show_straggler=True`` on a multi-process job this is a COLLECTIVE
    (cross-rank latency allgather, as in the reference): call it on every
    process, not under an ``if rank == 0`` guard."""
    comms_logger.log_all(print_log=True, show_straggler=show_straggler)


def configure(deepspeed_config=None, enabled=None, prof_all=None, prof_ops=None, verbose=None, debug=None):
    comms_logger.configure(deepspeed_config=deepspeed_config,
                           enabled=enabled,
                           prof_all=prof_all,
                           prof_ops=prof_ops,
                           verbose=verbose,
                           debug=debug)
