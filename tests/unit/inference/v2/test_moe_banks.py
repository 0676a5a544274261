"""The expert banks a step touched come out of the device with its result
(PR 37): a bucket that routes by sorting returns, beside its logits or tokens,
``(group_sizes > 0).sum()`` of every expert layer; the engine reads it only
under a telemetry session, where the step's result is already on the host, and
the spans that carry ``moe_banks`` carry ``moe_assignments`` and ``moe_path``
of the same step(s). A ``decode_loop`` span is the chunk's launch; what only
the fetch can say (``fetch_us``, a grouped chunk's ``moe_banks``) is written
into its args when the chunk is fetched, in the call or ticks later (PR 38). Tiny afmoe (top-4 of 64: the 8-token bucket and the
``decode_loop``s are grouped) and tiny Mellum (top-8 of 64: the 128-token
bucket is) engines on the CPU."""

import jax
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE
from tests.unit.inference.v2 import test_afmoe, test_mellum

afmoe_model = test_afmoe._model
_ids = test_afmoe._ids


@pytest.fixture(scope="module")
def wide_model():  # top-4 of 64 over four expert layers behind a dense one
    return afmoe_model(num_experts=64)


@pytest.fixture(scope="module")
def narrow_model():  # top-4 of 16: every bucket keeps the masks
    return afmoe_model()


@pytest.fixture(scope="module")
def mellum_model():  # top-8 of 64, four expert layers, experts 16 wide
    from deepspeed_tpu.models import mellum
    cfg = mellum.MellumConfig(dtype=jax.numpy.float32,
                              **dict(test_mellum.SIZES, **test_mellum.MANY_EXPERTS))
    return cfg, mellum.init_params(cfg, jax.random.PRNGKey(5))[1]


@pytest.fixture
def session():
    session = telemetry.configure({"enabled": True, "compile_watch": False})
    yield session
    telemetry.shutdown()


def _spans(session, name, cat):
    return [s for s in session.spans.export_since(0)["spans"]
            if s["name"] == name and s["cat"] == cat]


@pytest.fixture
def chosen(monkeypatch):
    """``{layer: [the [T, k] experts a call of that layer chose]}``, handed out
    of whatever program the layer is traced into."""
    seen = {}
    choose = RaggedMoE._choose

    def recording(self, probs, select_bias=None):
        topk_p, topk_e = choose(self, probs, select_bias)
        jax.debug.callback(
            lambda e, layer=self.layer_id: seen.setdefault(layer, []).append(np.asarray(e)),
            topk_e)
        return topk_p, topk_e

    monkeypatch.setattr(RaggedMoE, "_choose", recording)
    return seen


# ------------------------------------------------------- the count itself ---
@pytest.mark.parametrize("family, live, bucket", [("afmoe", 5, 8), ("mellum", 100, 128)])
def test_the_devices_count_is_numpys_count_of_distinct_chosen_experts(
        request, chosen, family, live, bucket):
    """``live`` tokens in a ``bucket``-token program: the bucket's other tokens
    are invalid and the sorted buffer is padded to whole 128-row tiles; neither
    counts for a bank."""
    if family == "afmoe":
        engine = test_afmoe._engine(request.getfixturevalue("wide_model"))
    else:
        engine = test_mellum._engine(request.getfixturevalue("mellum_model"), budget=128,
                                     capacity_factor=8.0, max_context=256)
    assert engine.model.moe_path(bucket) == "grouped"
    engine.put([0], [_ids(7, live)])
    counts = np.asarray(engine.model.last_moe_banks)
    jax.effects_barrier()
    layers = len(engine.model._moes)
    assert engine.model.moe_count_names == ("moe_banks", "moe_visits")
    assert counts.shape == (layers, 2) and counts.dtype == np.int32
    banks, visits = counts[:, 0], counts[:, 1]
    # the kernel's (expert, row tile) visits (PR 60): a visit an expert that has
    # rows and one more a row tile it runs on into; ``live * top_k`` rows lie
    # in so many row tiles, and each tile boundary adds at most one visit
    tiles = -(-live * engine.model._moes[0].top_k // 128)
    assert (visits >= banks).all() and (visits <= banks + tiles - 1).all()
    assert engine.moe_counts(counts) == {"moe_banks": int(banks.sum()),
                                         "moe_visits": int(visits.sum())}
    for layer in range(layers):
        (topk_e, ) = chosen[layer]
        assert topk_e.shape[0] == bucket
        assert banks[layer] == np.unique(topk_e[:live]).size
        # the invalid tokens chose experts too: they are not counted
        assert banks[layer] <= np.unique(topk_e).size
    assert (banks >= engine.model._moes[0].top_k).all()


def test_a_capacity_bucket_hands_out_no_count_and_says_every_bank(narrow_model, wide_model):
    engine = test_afmoe._engine(narrow_model)
    engine.put([0], [_ids(8, 20)])
    assert engine.model.last_moe_banks is None
    tokens, banks = engine.model.decode_loop(_loop_batch(engine, 1), 4)
    assert banks is None and np.asarray(tokens).shape == (4, 8)
    counts = engine.model.dispatch_counts
    # experts x expert layers x steps: what the capacity path's GEMMs stream
    assert counts(32, 20)["moe_banks"] == 16 * 4
    assert counts(8, 1, steps=4)["moe_banks"] == 16 * 4 * 4
    # the grouped path's count is the routing's: not known at the dispatch
    grouped = test_afmoe._engine(wide_model).model.dispatch_counts
    assert "moe_banks" not in grouped(8, 1, steps=4) and grouped(32, 20)["moe_banks"] == 64 * 4


def _loop_batch(engine, n_seqs):
    """A ragged batch of ``n_seqs`` one-token sequences with room for a chunk."""
    engine._batch.clear()
    for uid in range(100, 100 + n_seqs):
        seq = engine._state_manager.get_or_create_sequence(uid)
        engine.model.maybe_allocate_kv(seq, 8)
        seq.pre_forward(1)
        engine._batch.insert_sequence(seq, _ids(uid, 1))
    engine._batch.finalize()
    return engine._batch


# ------------------------------------------------------------- the chunk ---
@pytest.mark.parametrize("n_seqs", [1, 3])
def test_a_grouped_chunk_returns_its_banks_a_step_a_layer_and_the_span_sums_them(
        wide_model, session, monkeypatch, n_seqs):
    engine = test_afmoe._engine(wide_model)
    handed = []
    loop = engine.model.decode_loop

    def watching(*args, **kwargs):
        out = loop(*args, **kwargs)
        handed.append(out)
        return out

    monkeypatch.setattr(engine.model, "decode_loop", watching)
    uids = list(range(n_seqs))
    engine.put(uids, [_ids(10 + u, 6) for u in uids])
    tokens = engine.decode_loop(uids, [_ids(20 + u, 1) for u in uids], 4)
    assert tokens.shape == (n_seqs, 4)
    (dev_tokens, dev_banks), = handed
    assert isinstance(dev_tokens, jax.Array) and isinstance(dev_banks, jax.Array)
    counts = np.asarray(dev_banks)
    # [n_steps, expert layers, (banks, visits)]; one row tile: a visit a bank
    assert counts.shape == (4, 4, 2) and counts.dtype == np.int32
    banks = counts[..., 0]
    np.testing.assert_array_equal(counts[..., 1], banks)
    # a token picks an expert at most once: one live row touches exactly top-k
    # banks a layer-step, and the bucket's padding rows none
    assert (banks >= 4).all() and (banks <= 4 * n_seqs).all()
    (span, ) = _spans(session, "decode_loop", "inference")
    args = span["args"]
    assert args["moe_banks"] == args["moe_visits"] == banks.sum()
    assert isinstance(args["moe_banks"], int)
    assert args["moe_path"] == "grouped" and args["moe_assignments"] == n_seqs * 4 * 4 * 4
    # the span is the launch; the fetch wrote its own part when it happened
    assert 0 <= args["launch_us"] <= span["dur_us"] + 1 and args["fetch_us"] > 0


def test_a_grouped_chunk_left_in_flight_says_its_banks_when_it_is_fetched(wide_model, session):
    engine = test_afmoe._engine(wide_model)
    engine.put([0], [_ids(10, 6)])
    chunk = engine.dispatch_decode_loop([0], [_ids(20, 1)], 4)
    (span, ) = _spans(session, "decode_loop", "inference")
    assert span["args"]["fetch_us"] == 0 and "moe_banks" not in span["args"]
    assert span["args"]["steps"] == 4 and span["args"]["moe_path"] == "grouped"
    assert chunk.fetch().shape == (1, 4)
    (span, ) = _spans(session, "decode_loop", "inference")
    # one live row touches exactly top-k banks a layer-step
    assert span["args"]["fetch_us"] > 0 and span["args"]["moe_banks"] == 4 * 4 * 4


def test_a_capacity_chunks_span_says_every_bank_at_entry(narrow_model, session):
    engine = test_afmoe._engine(narrow_model)
    engine.put([0], [_ids(8, 20)])
    engine.decode_loop([0], [_ids(9, 1)], 4)
    (loop, ) = _spans(session, "decode_loop", "inference")
    (put, ) = _spans(session, "put", "inference")
    assert loop["args"]["moe_path"] == put["args"]["moe_path"] == "capacity"
    assert loop["args"]["moe_banks"] == 16 * 4 * 4 and put["args"]["moe_banks"] == 16 * 4
    assert {"launch_us", "fetch_us"} <= set(loop["args"])


# ----------------------------------------------- the scheduler's fetch span ---
def _serve(engine, decode_chunk, lengths=(9, 5), new_tokens=10):
    from deepspeed_tpu.serving import ServingConfig, ServingScheduler
    scheduler = ServingScheduler(engine, ServingConfig(decode_chunk=decode_chunk))
    try:
        handles = [scheduler.submit(_ids(40 + i, n), max_new_tokens=new_tokens, temperature=0.0)
                   for i, n in enumerate(lengths)]
        for handle in handles:
            while handle.stream.get(timeout=120) is not None:
                pass
            assert handle.state.name == "DONE", handle.error
        return scheduler.stats()["counters"]
    finally:
        scheduler.stop(drain=False)


def test_the_fetch_of_a_grouped_put_step_carries_its_banks_with_its_assignments(wide_model,
                                                                               session):
    """decode_chunk 1: every decode step is an 8-token ``put`` on the grouped
    path, a prompt's chunks are 32- and 16-token ones on the capacity path."""
    counters = _serve(test_afmoe._engine(wide_model), decode_chunk=1)
    rows = session.spans.export_since(0)["spans"]
    carrying = [s for s in rows if "moe_banks" in (s.get("args") or {})]
    # the one rule a reader needs
    assert carrying and all({"moe_assignments", "moe_path"} <= set(s["args"]) for s in carrying)
    fetches = [s for s in carrying if (s["name"], s["cat"]) == ("fetch", "sched")]
    puts = [s for s in rows if (s["name"], s["cat"]) == ("put", "inference")]
    grouped_puts = [s for s in puts if s["args"]["moe_path"] == "grouped"]
    assert len(fetches) == len(grouped_puts) == counters["moe_grouped_steps"] > 0
    for fetch in fetches:
        args = fetch["args"]
        assert args["moe_path"] == "grouped" and isinstance(args["moe_banks"], int)
        rows_live = args["moe_assignments"] // (4 * 4)  # top-k x expert layers
        assert 4 * 4 <= args["moe_banks"] <= args["moe_assignments"] and 1 <= rows_live <= 2
        # the grouped kernel's visits ride beside the banks (PR 60); 8 rows are ONE
        # row tile, so a visit a bank: none is an expert's further row tile
        assert args["moe_visits"] == args["moe_banks"]
    # the dispatch span of a grouped step keeps what it had; a capacity step's says every bank
    assert all("moe_banks" not in s["args"] for s in grouped_puts)
    assert all(s["args"]["moe_banks"] == 64 * 4 for s in puts if s["args"]["moe_path"] == "capacity")
    # each step's assignments are counted once among the spans that carry banks
    assert sorted(s["args"]["moe_assignments"] for s in fetches) == \
        sorted(s["args"]["moe_assignments"] for s in grouped_puts)


def test_chunks_in_flight_are_each_in_one_carrier_with_their_steps(wide_model, session):
    """Eight greedy requests fill the sequence cap: their chunks of 4 go behind
    one another, each fetched under its successor by the scheduler's ``fetch``
    span — which carries no banks for a chunk: the chunk's own ``decode_loop``
    span does, with the ``steps`` a reader prices them over, so a step is in
    exactly one span that carries ``moe_banks``."""
    counters = _serve(test_afmoe._engine(wide_model), decode_chunk=4, lengths=(5, ) * 8,
                      new_tokens=14)
    assert counters["pipelined_chunks"] >= 2 and counters["moe_grouped_chunks"] >= 3
    rows = session.spans.export_since(0)["spans"]
    loops = [s for s in rows if (s["name"], s["cat"]) == ("decode_loop", "inference")]
    assert len(loops) == counters["moe_grouped_chunks"]
    for loop in loops:
        args = loop["args"]
        live = len(args["uids"])
        assert args["steps"] == 4 and args["moe_assignments"] == live * 4 * 4 * 4
        assert 4 * 4 * 4 <= args["moe_banks"] <= args["moe_assignments"] and args["fetch_us"] > 0
    carrying = [s for s in rows if "moe_banks" in (s.get("args") or {})
                and s["args"].get("moe_path") == "grouped"]
    fetches = [s for s in carrying if (s["name"], s["cat"]) == ("fetch", "sched")]
    assert len(carrying) == len(loops) + len(fetches)
    assert len(fetches) == counters["moe_grouped_steps"]   # the grouped put steps, no chunk
    assert all("steps" not in s["args"] for s in fetches)
    assert any(s["args"].get("chained") for s in loops)   # fed from the step before, on the device


# -------------------------------------------- nothing more is fetched when off ---
@pytest.fixture
def device_gets(monkeypatch):
    """Counts every conversion of a device array to numpy, whoever asks."""
    from jax._src.array import ArrayImpl
    calls = []
    to_numpy = ArrayImpl.__array__

    def counting(self, *args, **kwargs):
        calls.append(self.shape)
        return to_numpy(self, *args, **kwargs)

    monkeypatch.setattr(ArrayImpl, "__array__", counting)
    return calls


@pytest.mark.parametrize("traced", [False, True], ids=["telemetry off", "telemetry on"])
def test_with_no_telemetry_session_a_step_is_fetched_once_and_nothing_else(
        request, wide_model, device_gets, traced):
    """Chunks of 4 on the grouped path and 8-token ``put`` steps on it too.
    Off: one device-to-host transfer a step, the ids or the chunk's tokens, as
    before the count existed. On: one more, 16 bytes, for each grouped step."""
    if traced:
        request.getfixturevalue("session")
    engine = test_afmoe._engine(wide_model)
    del device_gets[:]
    # a 5-token prompt alone is an 8-token bucket; two prompts together a 16-token one
    counters = _serve(engine, decode_chunk=4, lengths=(5, ), new_tokens=7)
    for name, n in _serve(engine, decode_chunk=4, lengths=(9, 5), new_tokens=7).items():
        counters[name] += n
    steps = counters["put_steps"] + counters["moe_grouped_chunks"]
    grouped = counters["moe_grouped_steps"] + counters["moe_grouped_chunks"]
    assert counters["moe_grouped_chunks"] > 0 and counters["moe_grouped_steps"] > 0
    assert counters["moe_capacity_chunks"] == 0 and counters["moe_capacity_steps"] > 0
    assert len(device_gets) == steps + (grouped if traced else 0)
    assert engine.last_moe_fetch is None or traced


@pytest.mark.parametrize("traced", [False, True], ids=["telemetry off", "telemetry on"])
def test_the_counts_way_to_the_host_starts_at_the_launch_and_only_under_a_session(
        request, wide_model, monkeypatch, traced):
    """A grouped ``put`` step's count and a grouped chunk's are sent on their way
    right behind the launch (``copy_to_host_async``), so reading them after the
    result waits for nothing; with no session nobody asks for them at all."""
    from jax._src.array import ArrayImpl
    started = []
    start = ArrayImpl.copy_to_host_async

    def recording(self):
        started.append(self.shape)
        return start(self)

    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", recording)
    if traced:
        request.getfixturevalue("session")
    engine = test_afmoe._engine(wide_model)
    engine.put([0], [_ids(7, 5)])  # an 8-token bucket: grouped
    handed = engine.last_moe_fetch
    engine.decode_loop([0], [_ids(9, 1)], 4)
    if not traced:
        assert started == [] and handed is None
        return
    # [expert layers, (banks, visits)], then [n_steps, expert layers, (banks, visits)]
    assert started == [(4, 2), (4, 4, 2)]
    assert handed["moe_banks"] is engine.model.last_moe_banks and handed["moe_path"] == "grouped"
    assert handed["moe_assignments"] == 5 * 4 * 4  # live tokens x top-k x expert layers


# ------------------------------------- a share's put steps: the rows it walked ---
def test_a_shares_put_steps_count_the_rows_they_walked_of_the_rows_they_had(session):
    """One of sixteen experts held at top-4: a 64-token bucket's 256 sorted
    rows have a window of 128 (``heuristics.moe_row_window``), its 8-, 16- and
    32-token buckets are one row tile and have none. The ``fetch`` span of
    every ``put`` step carries the bucket's rows beside the rows walked
    (PR 64)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import deepseek_v32 as ds
    from tests.unit.inference.v2.test_deepseek_v32 import engine_of
    cfg = ds.DeepseekV32Config.tiny(dtype=jnp.float32, experts_held=1, expert_rank=3)
    engine = engine_of(cfg, ds.init_params(cfg, rng=jax.random.PRNGKey(3))[1])
    layers = len(engine.model._moes)
    assert [engine.model._moes[0].row_window(t) for t in (8, 32, 64)] == [None, None, 128]
    counters = _serve(engine, decode_chunk=1, lengths=(60, 50), new_tokens=4)
    rows = session.spans.export_since(0)["spans"]
    fetches = [s["args"] for s in rows if (s["name"], s["cat"]) == ("fetch", "sched")
               and "moe_rows_walked" in (s.get("args") or {})]
    assert len(fetches) == counters["moe_grouped_steps"] == counters["put_steps"] > 0
    windowed = [a for a in fetches if a["moe_rows"] == 256 * layers]
    assert windowed and all(a["moe_rows_walked"] % 128 == 0 and
                            a["moe_rows_walked"] <= a["moe_rows"] for a in fetches)
    # nothing like half of a 64-token bucket's choices lands on one expert of sixteen
    assert all(a["moe_rows_walked"] <= 128 * layers for a in windowed)
    assert all(a["moe_rows_walked"] == a["moe_rows"] for a in fetches if a not in windowed)
