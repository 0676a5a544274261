"""The token draw, on the device.

One function serves every path that turns logits into a token: the ragged
forward's padded ``[S_bucket, vocab]`` logits (``engine.put_draw``, what the
serving scheduler's ``put`` path calls) and the rows a speculative verify
step holds. Only int32 ids cross to the host.

Each row carries its own ``temperature`` (0 = greedy), ``seed`` and
``draw_index`` (how many tokens its request has emitted over its whole life),
and its key is ``fold_in(key(seed), draw_index)``: a request's stream is a
function of ``(seed, position)`` alone, so a row's token does not depend on
its batch-mates, on the sequence bucket, on which execute path a tick took or
on which replica holds the request. Temperature is data, not a compile-time
flag: greedy and sampled rows share one program.

A step's ids can feed the next step without leaving the device
(``chain``): the serving scheduler dispatches a step behind the one in flight
when no arrival could have joined it, and its decode rows take their input
ids from the ids being drawn. A ``decode_loop`` chunk hands on the last row
of its ``[steps, rows]`` tokens the same way (``last_row``).

The program is compiled ahead of time (``compiled``) — per row count, vocab
and the logits' sharding — and kept for the process: a ``jax.jit`` cache
would key on whether its argument is committed, and a draw that compiles on
the first request is a stall inside somebody's time to first token.
"""

import threading

import numpy as np

from deepspeed_tpu.telemetry import compile_watch

_EXECUTABLES = {}
_LOCK = threading.Lock()


def _executable(key, site, watched, build):
    """``build()``'s executable under ``key``: built on the first call (its
    compile reported to the compile watch as ``site`` / ``watched``), then
    shared by every engine of the process."""
    exe = _EXECUTABLES.get(key)
    if exe is None:
        with _LOCK:
            exe = _EXECUTABLES.get(key)
            if exe is None:
                cw = compile_watch.get()
                exe = (build if cw is None else cw.wrap(site, watched, build))()
                _EXECUTABLES[key] = exe
    return exe


def draw_tokens(logits, temperature, seed, draw_index):
    """``[R, vocab]`` float32 logits and per-row ``temperature`` (float32),
    ``seed`` (uint32), ``draw_index`` (int32) → ``[R]`` int32 ids. A row with
    ``temperature > 0`` draws from ``softmax(logits / temperature)``
    (Gumbel-max, in the logits' float32); any other row is ``argmax(logits)``,
    first index on a tie."""
    import jax
    import jax.numpy as jnp

    def row(l, t, s, i):
        # threefry named: a seeded stream must not change with the process's
        # default PRNG implementation
        key = jax.random.fold_in(jax.random.key(s, impl="threefry2x32"), i)
        noisy = l / t + jax.random.gumbel(key, l.shape, l.dtype)
        return jnp.argmax(jnp.where(t > 0, noisy, l)).astype(jnp.int32)

    with jax.named_scope("draw"):  # its device time, by name, in a trace
        return jax.vmap(row)(logits, temperature, seed, draw_index)


def compiled(rows: int, vocab: int, sharding):
    """The draw's executable for ``[rows, vocab]`` logits placed as
    ``sharding``; built on the first call, then shared by every engine of the
    process."""

    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        # the per-row vectors arrive as host arrays: replicated wherever the
        # logits live
        vec = (NamedSharding(sharding.mesh, PartitionSpec())
               if isinstance(sharding, NamedSharding) else sharding)
        return jax.jit(draw_tokens).lower(
            jax.ShapeDtypeStruct((rows, vocab), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((rows, ), jnp.float32, sharding=vec),
            jax.ShapeDtypeStruct((rows, ), jnp.uint32, sharding=vec),
            jax.ShapeDtypeStruct((rows, ), jnp.int32, sharding=vec)).compile()

    return _executable((rows, vocab, sharding), "inference_draw", (rows, vocab), build)


def chain_ids(tok_meta, ids, src):
    """A ragged batch's packed ``[4, T]`` token metadata with the input ids of
    its row 0 taken, where ``src[t] >= 0``, from ``ids[src[t]]`` — the
    ``[R]`` ids the step before drew, still on the device; a slot with
    ``src[t] < 0`` keeps the id the host wrote."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("chain"):
        row = jnp.where(src >= 0, ids[jnp.maximum(src, 0)], tok_meta[0])
        return tok_meta.at[0].set(row)


def compiled_chain(tokens: int, rows: int):
    """:func:`chain_ids`' executable for a ``[4, tokens]`` batch fed from
    ``[rows]`` ids. Lowered with no sharding named, so its result is as free
    to place as the host array it stands in for: the forward program that
    takes it next sees the argument it was compiled for and is not built a
    second time."""

    def build():
        import jax
        import jax.numpy as jnp
        return jax.jit(chain_ids).lower(
            jax.ShapeDtypeStruct((4, tokens), jnp.int32),
            jax.ShapeDtypeStruct((rows, ), jnp.int32),
            jax.ShapeDtypeStruct((tokens, ), jnp.int32)).compile()

    return _executable(("chain", tokens, rows), "inference_chain", (tokens, rows), build)


def _on_default_device(array):
    """``array`` as the default device holds it whole — itself, or that
    device's replica of an array replicated over a mesh — else None (split
    over a mesh, or on another chip)."""
    import jax
    default = jax.local_devices()[0]
    if array.sharding.device_set == {default}:
        return array
    if array.is_fully_replicated:
        for shard in array.addressable_shards:
            if shard.device == default:
                return shard.data
    return None


def chain(tok_meta: np.ndarray, ids, src: np.ndarray):
    """``tok_meta`` (host) with the slots ``src`` names fed from the device
    ids ``ids`` of the step before (:func:`chain_ids`), as a device array:
    nothing is fetched, so the step that takes it can be dispatched while the
    one that draws ``ids`` still runs. Ids that do not lie whole on the
    default device (split over a mesh, or on another chip) are fetched and
    merged from the host instead: the same batch, one step later."""
    local = _on_default_device(ids)
    ids = np.asarray(ids) if local is None else local
    return compiled_chain(tok_meta.shape[1], ids.shape[0])(tok_meta, ids, src)


def compiled_last_row(steps: int, rows: int):
    """The executable that takes row ``steps - 1`` of a ``decode_loop``
    chunk's int32 ``[steps, rows]`` tokens: the ids the chunk's successor is
    fed from (``chain``'s ``ids``)."""

    def build():
        import jax
        import jax.numpy as jnp
        return jax.jit(lambda tokens: tokens[-1]).lower(
            jax.ShapeDtypeStruct((steps, rows), jnp.int32)).compile()

    return _executable(("last_row", steps, rows), "inference_last_row", (steps, rows), build)


def last_row(tokens):
    """``tokens[-1]`` of a chunk's device ``[steps, rows]`` tokens, as a device
    array: nothing is fetched. Tokens replicated over a mesh are read from
    the default device's replica, as ``chain`` reads a draw's ids (the
    compiled program, built ahead of the first step); tokens split over it are
    indexed where they lie (``chain`` then fetches them)."""
    local = _on_default_device(tokens)
    return tokens[-1] if local is None else compiled_last_row(*local.shape)(local)


def _padded(values, rows: int, dtype) -> np.ndarray:
    out = np.zeros(rows, dtype)
    out[:len(values)] = values
    return out


def draw(logits, temperature, seed, draw_index):
    """Draw one token per row of the device array ``logits`` ``[R, vocab]``;
    the per-row vectors may be shorter than R (the live rows: the rest is
    padding, drawn greedily and ignored). Returns the device ``[R]`` int32
    ids, still being computed."""
    rows, vocab = logits.shape
    return compiled(rows, vocab, logits.sharding)(
        logits, _padded(temperature, rows, np.float32), _padded(seed, rows, np.uint32),
        _padded(draw_index, rows, np.int32))


def draw_host_rows(rows: np.ndarray, temperature, seed, draw_index) -> np.ndarray:
    """:func:`draw` for logits rows held on the HOST (a speculative verify
    step's): the same program, fed from the host, fetched at once. Rows are
    padded to a multiple of 8 so a verify feed's width is not a program."""
    import jax.numpy as jnp
    n = rows.shape[0]
    padded = np.zeros((-(-n // 8) * 8, rows.shape[1]), np.float32)
    padded[:n] = rows
    return np.asarray(draw(jnp.asarray(padded), temperature, seed, draw_index))[:n]
