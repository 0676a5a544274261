"""Learned-drafter speculative decoding through the serving scheduler, on a
fixture model where prompt-lookup is structurally blind.

The fixture: a tiny llama whose attention/MLP outputs are zeroed (o_proj and
down_proj kernels = 0) so the residual stream at every position is exactly
``embed(token)`` — a pure function of the current token — and whose lm_head
is rewritten so the greedy next token is ``perm[current]`` for a single
256-cycle permutation ``perm``. Greedy generation therefore walks the cycle:
every emitted token is DISTINCT, so n-gram prompt-lookup never fires (its
acceptance is provably zero on this text), while the Medusa heads can learn
``perm^(2+h)`` from self-distilled data and draft perfectly.

This is the PR-19 acceptance-rate floor gate: on non-templated text the
learned drafter's acceptance strictly beats prompt-lookup's at the same k,
and the same N emitted tokens cost strictly fewer engine batches — plus the
bitwise-identity, auto-arbitration, handoff, and brownout contracts for the
tree-verify path. Mechanism units (head math, tree packing, engine
verify_tree) live in tests/unit/inference/v2/test_spec.py.
"""

import copy

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.ragged.manager_configs import (AllocationMode,
                                                               DSStateManagerConfig,
                                                               MemoryConfig)
from deepspeed_tpu.inference.v2.spec.distill import self_distill
from deepspeed_tpu.inference.v2.spec.learned import MedusaDraftHead
from deepspeed_tpu.serving import ServingConfig, ServingScheduler, SpeculativeConfig

from .test_speculative import _run_until


@pytest.fixture(scope="module")
def perm_setup(llama_setup):
    """(cfg, params, order, perm): the permutation-Markov fixture model.

    With attention and MLP outputs zeroed, position t's pre-unembed residual
    is embed(tok_t) (RoPE only lives inside the zeroed attention path), and
    the permuted lm_head — column perm[v] holds the normalized embedding of
    v, scaled — makes perm[current] the greedy argmax by a wide margin."""
    cfg, _, params = llama_setup
    m = copy.deepcopy(jax.tree.map(np.asarray, params)["model"])
    for name, layer in m.items():
        if name.startswith("layers_"):
            layer["self_attn"]["o_proj"]["kernel"] = np.zeros_like(
                layer["self_attn"]["o_proj"]["kernel"])
            layer["mlp"]["down_proj"]["kernel"] = np.zeros_like(
                layer["mlp"]["down_proj"]["kernel"])
    rng = np.random.default_rng(5)
    V, H = cfg.vocab_size, cfg.hidden_size
    order = rng.permutation(V)  # one V-cycle => all walked tokens distinct
    perm = np.empty(V, np.int64)
    perm[order] = np.roll(order, -1)
    emb = m["embed_tokens"]["embedding"]
    hn = emb / np.linalg.norm(emb, axis=1, keepdims=True)
    W = np.zeros((H, V), np.float32)
    W[:, perm] = hn.T * 8.0
    m["lm_head"]["kernel"] = W
    return cfg, {"model": m}, order, perm


@pytest.fixture
def make_perm_engine(perm_setup):
    """Engine factory over the permutation params (conftest's make_engine is
    bound to the unmodified llama weights); closes every build at teardown."""
    cfg, params, _, _ = perm_setup
    engines = []

    def _make(num_blocks=64, block_size=16, max_context=512):
        mgr = DSStateManagerConfig(
            memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE,
                                       size=num_blocks),
            max_context=max_context)
        engine = build_engine(params, cfg,
                              RaggedInferenceEngineConfig(state_manager=mgr,
                                                          kv_block_size=block_size))
        engines.append(engine)
        return engine

    yield _make
    for engine in engines:
        engine.close()


@pytest.fixture(scope="module")
def distilled(perm_setup, tmp_path_factory):
    """Self-distilled draft heads for the fixture model, trained ONCE for the
    module entirely from the model's own greedy generations (satellite
    contract: no external data). Returns (head_path, loss_trace)."""
    cfg, params, order, _ = perm_setup
    mgr = DSStateManagerConfig(
        memory_config=MemoryConfig(mode=AllocationMode.ALLOCATE, size=64),
        max_context=512)
    engine = build_engine(params, cfg,
                          RaggedInferenceEngineConfig(state_manager=mgr,
                                                      kv_block_size=16))
    try:
        prompts = [[int(t) for t in order[i * 32:i * 32 + 8]] for i in range(6)]
        head, losses = self_distill(engine, prompts=prompts, num_heads=3,
                                    max_new_tokens=40, steps=400, lr=5e-3,
                                    seed=0)
    finally:
        engine.close()
    path = tmp_path_factory.mktemp("spec_heads") / "perm_heads.npz"
    head.save(str(path))
    return str(path), losses


def _learned_config(head_path, k=3, drafter="learned", **spec_kw):
    spec = SpeculativeConfig(enabled=True, drafter=drafter, max_draft_tokens=k,
                             draft_head_path=head_path, **spec_kw)
    return ServingConfig(speculative=spec)


def _cycle_prompt(order, start=100, n=8):
    return [int(t) for t in order[start:start + n]]


# ------------------------------------------------------------ distillation --
def test_self_distill_learns_the_permutation(perm_setup, distilled):
    """Distill smoke: the loss trace collapses, and the saved heads reload to
    predict perm^(2+h) — i.e. the heads really learned the target's dynamics
    from the target's own generations, not from any external corpus."""
    cfg, params, _, perm = perm_setup
    path, losses = distilled
    assert losses[-1] < losses[0]
    assert losses[-1] < 0.1  # prototype converges to ~1e-3
    head = MedusaDraftHead.load(path)
    emb = params["model"]["embed_tokens"]["embedding"].astype(np.float32)
    lp = head.head_log_probs(emb)  # hidden state for token v IS embed(v)
    for h in range(head.num_heads):
        targ = np.arange(cfg.vocab_size)
        for _ in range(2 + h):
            targ = perm[targ]
        acc = (np.argmax(lp[h], axis=-1) == targ).mean()
        assert acc > 0.5, f"head {h} accuracy {acc:.2f}"


# ---------------------------------------------------------- token identity --
def test_learned_drafter_token_identical_greedy(make_perm_engine, perm_setup,
                                                distilled):
    """Cold (no hidden state yet: root-only bootstrap tree) AND warm learned
    runs emit exactly the spec-off token sequence — and the warm half really
    speculated through the tree path."""
    _, _, order, _ = perm_setup
    path, _ = distilled
    prompt = _cycle_prompt(order)
    N = 16

    off = ServingScheduler(make_perm_engine(), ServingConfig(), start=False)
    on_engine = make_perm_engine()
    on = ServingScheduler(on_engine, _learned_config(path), start=False)
    try:
        ref = off.submit(prompt, max_new_tokens=N)
        _run_until(off, lambda: ref.finished)

        cold = on.submit(prompt, max_new_tokens=N)
        _run_until(on, lambda: cold.finished)
        assert cold.result() == ref.result()
        assert cold.spec_accepted > 0
        assert cold.decode_steps < N - 1

        warm = on.submit(prompt, max_new_tokens=N)
        _run_until(on, lambda: warm.finished)
        assert warm.result() == ref.result()
        assert warm.spec_accepted > 0
    finally:
        off.stop(drain=False)
        on.stop(drain=False)
    # tree rollback + compaction leave the KV pool balance exact
    assert on_engine.free_blocks == on_engine._state_manager.kv_cache.num_blocks


def test_learned_drafter_token_identical_sampled(make_perm_engine, perm_setup,
                                                 distilled):
    """Seeded sampling through the tree path: each emitted token is drawn
    with the request's own stream in spec-off draw order, so the learned
    drafter is bitwise identical at the same seed even off-greedy."""
    _, _, order, _ = perm_setup
    path, _ = distilled
    prompt = _cycle_prompt(order)
    kw = dict(max_new_tokens=12, temperature=0.8, seed=77)

    off = ServingScheduler(make_perm_engine(), ServingConfig(), start=False)
    on = ServingScheduler(make_perm_engine(), _learned_config(path), start=False)
    try:
        ref = off.submit(prompt, **kw)
        _run_until(off, lambda: ref.finished)
        got = on.submit(prompt, **kw)
        _run_until(on, lambda: got.finished)
        assert got.result() == ref.result()
        # the verifier ran rows (not device argmax) yet stayed identical
        assert got.decode_steps > 0
    finally:
        off.stop(drain=False)
        on.stop(drain=False)


# ------------------------------------------------- one verify step, by shape --
def test_the_verify_program_follows_the_trees_shape(make_perm_engine, perm_setup,
                                                    distilled):
    """Who drafts does not pick the program; the batch's shape does. With a
    learned head, the bootstrap tick's root-only tree is a chain and takes the
    causal program (``put``'s attention arm); only a branching draft reaches
    the ancestor-mask one. With ``prompt_lookup``, a tick without a draft is a
    plain ``put`` (no verify program exists), a tick with one the causal
    program again."""
    _, _, order, _ = perm_setup
    path, _ = distilled
    walk = _cycle_prompt(order, n=12)

    def verify_programs(engine):
        return {(tree, greedy)
                for _, _, tree, greedy in engine.lowerable_callables()["verify"]}

    def serve(config, prompt, at_first_decode=None):
        engine = make_perm_engine()
        sched = ServingScheduler(engine, config, start=False)
        try:
            req = sched.submit(prompt, max_new_tokens=10)
            if at_first_decode is not None:
                _run_until(sched, lambda: req.decode_steps >= 1)
                assert verify_programs(engine) == at_first_decode
            _run_until(sched, lambda: req.finished)
        finally:
            sched.stop(drain=False)
        return req, verify_programs(engine), sched.stats()["counters"]

    off, none, _ = serve(ServingConfig(), walk[:8])
    assert none == set()

    learned, programs, counters = serve(_learned_config(path), walk[:8],
                                        at_first_decode={(False, True)})
    assert programs == {(False, True), (True, True)}
    assert learned.result() == off.result() and counters["spec_drafted_learned"] > 0

    lookup_cfg = ServingConfig(speculative=SpeculativeConfig(
        enabled=True, drafter="prompt_lookup", max_draft_tokens=3))
    # every token of the walk is new: nothing to look up, nothing but put
    blind, programs, counters = serve(lookup_cfg, walk[:8])
    assert programs == set() and counters["spec_tree_nodes"] == 0
    assert blind.result() == off.result()
    # the walk, then its start again: the history holds what comes next
    again, programs, counters = serve(lookup_cfg, walk + walk[:4])
    assert programs == {(False, True)}
    assert again.result() == [int(t) for t in order[104:114]]
    assert again.spec_accepted > 0 and again.decode_steps < 9
    assert counters["spec_tree_nodes"] > counters["spec_drafted_lookup"] > 0
    assert counters["spec_tree_compactions"] == 0  # a chain's path needs no copy


# --------------------------------------------------- acceptance-floor gate --
def test_learned_acceptance_strictly_beats_prompt_lookup(make_perm_engine,
                                                         perm_setup, distilled):
    """THE satellite gate: on the cycle walk every token is new, so
    prompt-lookup accepts NOTHING (n-grams never repeat) and pays one engine
    batch per token, while the learned head drafts the walk and lands the
    same N tokens in strictly fewer batches at >1 tokens/step — all three
    runs token-identical."""
    _, _, order, _ = perm_setup
    path, _ = distilled
    prompt = _cycle_prompt(order)
    N = 20

    def run(cfg):
        sched = ServingScheduler(make_perm_engine(), cfg, start=False)
        try:
            req = sched.submit(prompt, max_new_tokens=N)
            _run_until(sched, lambda: req.finished)
        finally:
            sched.stop(drain=False)
        return req

    off = run(ServingConfig())
    lookup = run(ServingConfig(speculative=SpeculativeConfig(
        enabled=True, drafter="prompt_lookup", max_draft_tokens=3)))
    learned = run(_learned_config(path))

    assert off.result() == lookup.result() == learned.result()
    assert lookup.spec_accepted == 0          # structurally blind here
    assert learned.spec_accepted > 0
    assert learned.spec_accepted > lookup.spec_accepted  # the strict floor
    # same emitted tokens, strictly fewer engine batches
    assert learned.decode_steps < lookup.decode_steps
    assert len(learned.tokens) / learned.decode_steps > 1.0


# --------------------------------------------------------- auto arbitration --
def test_auto_arbitration_converges_to_learned(make_perm_engine, perm_setup,
                                               distilled):
    """drafter=auto cold-explores both drafters, scores them on acceptance
    EWMA, and settles on the learned head (lookup scores 0 on the cycle walk)
    — without perturbing the emitted tokens."""
    _, _, order, _ = perm_setup
    path, _ = distilled
    prompt = _cycle_prompt(order)
    N = 20

    off = ServingScheduler(make_perm_engine(), ServingConfig(), start=False)
    auto = ServingScheduler(make_perm_engine(),
                            _learned_config(path, drafter="auto"), start=False)
    try:
        ref = off.submit(prompt, max_new_tokens=N)
        _run_until(off, lambda: ref.finished)
        req = auto.submit(prompt, max_new_tokens=N)
        _run_until(auto, lambda: req.finished)

        assert req.result() == ref.result()
        # both drafters were raced and scored; learned won
        assert req._spec_ewmas.get("learned") is not None
        assert req._spec_ewmas.get("prompt_lookup") is not None
        assert req._spec_ewmas["learned"] > req._spec_ewmas["prompt_lookup"]
        assert req.spec_accepted > 0
        assert auto._counters["spec_drafter_switches"] >= 1

        doc = auto.stats()["speculative"]
        assert doc["drafter"] == "auto"
        assert doc["drafters"]["learned"]["accepted"] > 0
        assert doc["drafters"]["learned"]["ewma"] > \
            (doc["drafters"]["prompt_lookup"]["ewma"] or 0.0)
        assert doc["tree"]["nodes"] > 0
    finally:
        off.stop(drain=False)
        auto.stop(drain=False)


def test_drafter_pin_overrides_auto_arbitration(make_perm_engine, perm_setup,
                                                distilled):
    """submit(drafter=...) pins the request's drafter family: a learned pin
    on an auto scheduler never explores prompt-lookup, an unknown pin is a
    submission-time ValueError, and output stays identical either way."""
    _, _, order, _ = perm_setup
    path, _ = distilled
    prompt = _cycle_prompt(order)

    sched = ServingScheduler(make_perm_engine(),
                             _learned_config(path, drafter="auto"), start=False)
    try:
        with pytest.raises(ValueError):
            sched.submit(prompt, max_new_tokens=4, drafter="medusa")

        pinned = sched.submit(prompt, max_new_tokens=16, drafter="learned")
        _run_until(sched, lambda: pinned.finished)
        assert pinned.spec_accepted > 0
        assert pinned._spec_last_drafter == "learned"
        assert "prompt_lookup" not in pinned._spec_ewmas  # never explored

        free = sched.submit(prompt, max_new_tokens=16)
        _run_until(sched, lambda: free.finished)
        assert free.result() == pinned.result()  # pin never changes tokens
        assert "prompt_lookup" in free._spec_ewmas  # auto raced both
    finally:
        sched.stop(drain=False)


# ------------------------------------------------------------------ handoff --
def test_handoff_preserves_learned_drafter_state(make_perm_engine, perm_setup,
                                                 distilled):
    """Mid-stream handoff between two schedulers serving the SAME draft head:
    the per-drafter EWMAs and head id ride the payload, the recipient adopts
    them at admission, and the continuation is token-identical."""
    _, _, order, _ = perm_setup
    path, _ = distilled
    prompt = _cycle_prompt(order)

    whole_s = ServingScheduler(make_perm_engine(), ServingConfig(), start=False)
    donor = ServingScheduler(make_perm_engine(),
                             _learned_config(path, drafter="auto"), start=False)
    recipient = ServingScheduler(make_perm_engine(),
                                 _learned_config(path, drafter="auto"),
                                 start=False)
    try:
        whole = whole_s.submit(prompt, max_new_tokens=16)
        _run_until(whole_s, lambda: whole.finished)

        head = donor.submit(prompt, max_new_tokens=8, handoff=True)
        _run_until(donor, lambda: head.finished)
        assert head.spec_accepted > 0  # the donor really speculated
        assert head.handoff_payload is not None

        tail = recipient.submit_resume(head.handoff_payload, max_new_tokens=8)
        # same head id on both sides: the learned EWMA survives the hop
        assert tail._spec_ewmas == {k: v for k, v in head._spec_ewmas.items()
                                    if v is not None}
        assert tail.spec_accepted == head.spec_accepted
        _run_until(recipient, lambda: tail.finished)
        assert head.result() + tail.result() == whole.result()
    finally:
        whole_s.stop(drain=False)
        donor.stop(drain=False)
        recipient.stop(drain=False)


def test_handoff_across_different_heads_drops_only_learned_ewma(
        make_perm_engine, perm_setup, distilled, tmp_path):
    """A recipient serving a DIFFERENT draft head must not trust the donor's
    learned-acceptance evidence (it describes another head) — it drops only
    the learned EWMA and re-explores, keeping the lookup EWMA and the
    token-identity contract."""
    cfg, _, order, _ = perm_setup
    path, _ = distilled
    fresh = MedusaDraftHead.fresh(cfg.hidden_size, cfg.vocab_size, num_heads=3,
                                  seed=9)
    other = tmp_path / "other_heads.npz"
    fresh.save(str(other))
    prompt = _cycle_prompt(order)

    whole_s = ServingScheduler(make_perm_engine(), ServingConfig(), start=False)
    donor = ServingScheduler(make_perm_engine(),
                             _learned_config(path, drafter="auto"), start=False)
    recipient = ServingScheduler(make_perm_engine(),
                                 _learned_config(str(other), drafter="auto"),
                                 start=False)
    try:
        whole = whole_s.submit(prompt, max_new_tokens=16)
        _run_until(whole_s, lambda: whole.finished)

        head = donor.submit(prompt, max_new_tokens=8, handoff=True)
        _run_until(donor, lambda: head.finished)
        assert head._spec_ewmas.get("learned") is not None

        tail = recipient.submit_resume(head.handoff_payload, max_new_tokens=8)
        assert "learned" not in tail._spec_ewmas  # foreign head: re-explore
        if head._spec_ewmas.get("prompt_lookup") is not None:
            assert tail._spec_ewmas["prompt_lookup"] == \
                head._spec_ewmas["prompt_lookup"]
        _run_until(recipient, lambda: tail.finished)
        assert head.result() + tail.result() == whole.result()
    finally:
        whole_s.stop(drain=False)
        donor.stop(drain=False)
        recipient.stop(drain=False)


# ----------------------------------------------------------------- brownout --
def test_brownout_stage2_disables_tree_drafting(make_perm_engine, perm_setup,
                                                distilled):
    """Brownout stage ≥2 zeroes the draft budget in tree mode too: no trees
    are built (the tick rides the plain put path, one token per dispatch),
    the tree-node counter freezes, and output is degraded-not-different."""
    from tests.unit.serving.test_overload import _force_stage
    _, _, order, _ = perm_setup
    path, _ = distilled
    prompt = _cycle_prompt(order)

    sched = ServingScheduler(make_perm_engine(), _learned_config(path),
                             start=False)
    try:
        base = sched.submit(prompt, max_new_tokens=8)
        _run_until(sched, lambda: base.finished)
        assert base.spec_accepted > 0  # stage 0: tree speculation on
        nodes_before = sched._counters["spec_tree_nodes"]

        _force_stage(sched, 2, pin=True)
        req = sched.submit(prompt, max_new_tokens=8)
        assert "speculative_disabled" in req.degraded_mode
        _run_until(sched, lambda: req.finished)
        assert req.spec_drafted == 0
        assert req.decode_steps == 7  # one token per dispatch again
        assert req.tokens == base.tokens  # degraded, not different
        assert sched._counters["spec_tree_nodes"] == nodes_before
    finally:
        sched.stop(drain=False)
