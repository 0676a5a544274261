"""What every served family's tiny engine traces and counts, as data: the
``put`` and chunk programs' hashes (``program_hashes._stable`` of the jaxpr),
the scope paths in their lowered text (a jaxpr's text carries no
``jax.named_scope``; ``benchmark/readers/`` match on these paths), and the
span args the metrics read (``dispatch_counts`` / ``moe_path`` /
``batch_counts``) of one fixed ``put`` of mixed lengths and one chunk of 8
positions. ``test_family_pins.py`` holds each family to ``family_pins.json``,
which PR 59 recorded on its PARENT tree before it moved any code (PR 60
re-recorded it: ``moe_count_names`` gained ``moe_visits`` in every family, and the
``put`` / ``chunk`` hashes of the four families that hold a share of their experts
moved with the one more count their programs return; every other hash held; PR 62
ADDED ``longcat_flash``'s entry and left every other as it was; PR 64 re-recorded it:
the five families that hold a share of their experts count ``moe_rows_walked`` and their
``put`` / ``chunk`` hashes moved with it, the six that hold every expert held theirs; PR 65
ADDED ``granitemoehybrid``'s entry and left every other as it was: the query scale and the
tied head touched no shared program)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m tests.unit.inference.v2.family_pins --record

One engine a family, built by the family's own test file (``engine_of`` /
``_engine`` / ``program_hashes._engine``) from that file's tiny model. Nothing
here runs a program: the batches are prepared on the host (``_prepare`` /
``_post_forward``) and the programs traced and lowered, never compiled."""

import functools
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

from tests.unit.inference.v2.program_hashes import _stable

TABLE = os.path.join(os.path.dirname(__file__), "family_pins.json")
FAMILIES = ("mixtral", "mistral", "mellum", "afmoe", "sdar_moe", "deepseek_v32", "nemotron_h",
            "falcon_h1", "solar_open2", "kimi_linear", "longcat_flash", "granitemoehybrid")
# the put's feeds (mixed lengths; whole blocks of 4 for the block-diffusion family) and the
# chunk's positions a sequence
FEEDS, CHUNK = (16, 4, 8), 8


def _engine(family):
    """The family's tiny engine, as its own test file builds it."""
    key = jax.random.PRNGKey(3)
    if family in ("mixtral", "mistral"):
        from tests.unit.inference.v2 import program_hashes
        return program_hashes._engine(family, False)
    if family == "mellum":
        from deepspeed_tpu.models import mellum
        from tests.unit.inference.v2 import test_mellum as t
        cfg = mellum.MellumConfig(dtype=jnp.float32, **t.SIZES)
        return t._engine((cfg, mellum.init_params(cfg, key)[1]))
    if family == "afmoe":
        from tests.unit.inference.v2 import test_afmoe as t
        return t._engine(t._model())
    if family == "sdar_moe":
        from deepspeed_tpu.models import sdar_moe as m
        from tests.unit.inference.v2 import test_sdar_moe as t
        cfg = m.SdarMoeConfig.tiny(dtype=jnp.float32)
    elif family == "deepseek_v32":
        from deepspeed_tpu.models import deepseek_v32 as m
        from tests.unit.inference.v2 import test_deepseek_v32 as t
        cfg = m.DeepseekV32Config.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1)
    elif family == "nemotron_h":  # a state of one lane tile: the pool is on the kernels' rule
        from deepspeed_tpu.models import nemotron_h as m
        from tests.unit.inference.v2 import test_nemotron_h as t
        cfg = m.NemotronHConfig.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1,
                                     ssm_state_size=128)
    elif family == "falcon_h1":
        from deepspeed_tpu.models import falcon_h1 as m
        from tests.unit.inference.v2 import test_falcon_h1 as t
        cfg = m.FalconH1Config.tiny(dtype=jnp.float32, mamba_d_state=128)
    elif family == "solar_open2":
        from deepspeed_tpu.models import solar_open2 as m
        from tests.unit.inference.v2 import test_solar_open2 as t
        cfg = m.SolarOpen2Config.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1)
    elif family == "kimi_linear":
        from deepspeed_tpu.models import kimi_linear as m
        from tests.unit.inference.v2 import test_kimi_linear as t
        cfg = m.KimiLinearConfig.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1,
                                      **t.LAYERS)
    elif family == "longcat_flash":
        from deepspeed_tpu.models import longcat_flash as m
        from tests.unit.inference.v2 import test_longcat_flash as t
        cfg = m.LongcatFlashConfig.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1)
    elif family == "granitemoehybrid":  # a state of one lane tile: the pool is on the kernels' rule
        from deepspeed_tpu.models import granitemoehybrid as m
        from tests.unit.inference.v2 import test_granitemoehybrid as t
        cfg = m.GraniteMoeHybridConfig.tiny(dtype=jnp.float32, experts_held=4, expert_rank=1,
                                            mamba_d_state=128)
    else:
        raise ValueError(family)
    return t.engine_of(cfg, m.init_params(cfg, rng=key)[1])


def _sha(jaxpr):
    return hashlib.sha256(_stable(jaxpr).encode()).hexdigest()


_OP_PATH = re.compile(r'loc\("([^"]*)"')
# a path's parts that are a transformation's (``jit(_forward_impl)``), not a named scope's
_NOT_A_SCOPE = re.compile(r"^\w+\(")


def _scopes(lowered):
    """The sorted set of ``jax.named_scope`` paths the lowered program's
    operations sit under: each operation's ``loc`` name without its
    transformations' parts and without its last part (the primitive). A loop's
    ``while`` / ``body`` / ``cond`` and a kernel's own name are parts like any."""
    paths = set()
    for path in _OP_PATH.findall(lowered.as_text(debug_info=True)):
        if path.startswith("/") or ".py" in path:  # a source file's location, not an operation's
            continue
        parts = [p for p in path.split("/")[:-1] if not _NOT_A_SCOPE.match(p)]
        if parts:
            paths.add("/".join(parts))
    return sorted(paths)


def _plain(counts):
    return {k: (v if isinstance(v, str) or v is None else int(v)) for k, v in counts.items()}


def _span_counts(engine, steps, n_tokens):
    """What ``_dispatch`` puts on the step's span of the batch just prepared."""
    model = engine.model
    n_padded = engine._batch.device_batch["tok_meta"].shape[1]
    return _plain({"moe_path": model.moe_path(n_padded),
                   **model.dispatch_counts(n_padded, n_tokens, steps),
                   **model.batch_counts(engine._batch, steps)})


@functools.lru_cache(maxsize=None)
def observed(family):
    """``{what: value}`` of ``family``'s tiny engine on this tree."""
    engine = _engine(family)
    model = engine.model
    cache = model.state_manager.kv_cache.cache
    out = {}
    # ------------------------------------------------------------ programs --
    bucket = model._bucket_of(model._synthetic_batch(None))
    dev = model._synthetic_batch(bucket)
    out["put"] = _sha(jax.make_jaxpr(model._forward_impl)(model._params, cache, dev))
    out["put_scopes"] = _scopes(model.lower_forward(bucket))
    B = model.attention_block
    if B:  # generation by blocks: the block loop is its chunk
        n_blocks = CHUNK // B
        dev = dict(dev, masked=np.zeros(dev["tok_meta"].shape[1], np.int32))
        loop = functools.partial(model._block_loop_impl, n_blocks=n_blocks)
        out["chunk"] = _sha(jax.make_jaxpr(loop)(model._params, cache, dev))
        out["chunk_scopes"] = _scopes(
            model._program("block_loop", (bucket, n_blocks), run=False).lower(
                model._params, cache, dev))
    else:
        loop = functools.partial(model._decode_loop_impl, n_steps=CHUNK)
        out["chunk"] = _sha(jax.make_jaxpr(loop)(model._params, cache, dev))
        out["chunk_scopes"] = _scopes(model.lower_decode_loop(CHUNK))
    # -------------------------------------------------------------- counts --
    uids = list(range(len(FEEDS)))
    rng = np.random.default_rng(59)
    feeds = [rng.integers(0, 256, n).astype(np.int32) for n in FEEDS]
    engine._prepare(None, uids, feeds, True, sum(FEEDS))
    out["put_counts"] = _span_counts(engine, 1, sum(FEEDS))
    engine._post_forward(uids)
    if B:
        feeds = [f[:B] for f in feeds]
        engine._prepare(None, uids, feeds, True, len(uids) * CHUNK, steps=CHUNK)
        steps = n_blocks * model.config.denoising_steps + 1
        out["chunk_counts"] = _span_counts(engine, steps, len(uids) * B)
        out["block_loop_counts"] = _plain(model.block_loop_counts(engine._batch, n_blocks))
    else:
        feeds = [f[:1] for f in feeds]
        engine._prepare(None, uids, feeds, True, len(uids) * CHUNK, steps=CHUNK)
        out["chunk_counts"] = _span_counts(engine, CHUNK, len(uids))
    out["moe_count_names"] = list(model.moe_count_names)
    engine.close()
    return out


def recorded():
    with open(TABLE) as f:
        return json.load(f)


if __name__ == "__main__":
    if sys.argv[1:] != ["--record"]:
        sys.exit(__doc__)
    table = {"jax": jax.__version__, "families": {f: observed(f) for f in FAMILIES}}
    with open(TABLE, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"recorded {len(FAMILIES)} families in {TABLE}")
