"""Ragged MoE for inference, with disaggregated expert parallelism (the fork's
core feature).

Reference: ``deepspeed/inference/v2/modules/implementations/moe/cutlass_multi_gemm.py``
(DSMultiGemmMoE:28) and the fork's ``cutlass_multi_gemm_ep.py`` (DSMultiGemmMoEEp:32)
— top-k gating → moe_scatter → EP all_to_all dispatch → grouped GEMM → all_to_all
return → moe_gather, with ``empty_run`` participation.

TPU formulation of the fork's architecture: each EP replica *owns its own slice of
the flat token dim* (the reference's per-rank ragged batches). Under ``shard_map``
over the ``expert`` mesh axis, every replica routes its local tokens, packs them
into fixed-capacity per-destination-rank buffers (XLA collectives are shape-static,
so the fork's variable-size ``all_to_all_single`` of counts+tokens
(cutlass_multi_gemm_ep.py:311,340) becomes one capacity-padded ``lax.all_to_all``),
runs its local experts' grouped GEMM over tokens received from *all* replicas, and
a second ``lax.all_to_all`` (cutlass_multi_gemm_ep.py:389) returns results to the
token owners, where the top-k combine weights are applied. ``empty_run`` is a
forward with zero live tokens: every replica still enters both collectives —
exactly the deadlock-avoidance contract of the fork (engine_v2.py:308).

Simulated gating (fork ``top_k_gating/expert_probs.py``): when enabled, router
logits are replaced by a per-layer synthetic distribution with a temperature knob,
decoupling load-balance experiments from real router weights. The reference ships
measured Mixtral expert-count tables; we synthesize a skewed per-layer
distribution from a seeded Dirichlet instead (same knob semantics, no dataset
dependency), sharpened/flattened by ``softmax(log(p)/temperature)``. The draw is
seeded per (layer, batch, replica): callers thread a data-dependent ``gate_seed``
(the model passes the sum of live token positions, so successive decode steps
route differently) and the EP body folds in the replica index.

On ONE replica a bucket reaches its experts by one of two paths, chosen from
static shapes by ``heuristics.moe_implementation``: the capacity path
(``_dense_forward``: the same ``[tokens, experts, capacity]`` one-hot masks as
under EP, into static per-expert buffers, every expert's bank multiplied) or
the grouped path (``_grouped_forward``: the assignments sorted by expert, one
gather of their rows, one grouped matmul a projection —
``ops/pallas/grouped_matmul.py`` — and the rows summed back to their tokens;
dropless whatever the skew, no capacity, and no bank read that has no row; how
many banks had a row is data on the device, and a caller that passes
``banks_out`` is handed the count, beside the kernel's count of visits).
A layer that holds a SHARE of its experts has most of its assignments land on
another chip: the sort places its own first, and where the share is small
enough (``heuristics.moe_row_window``) dispatch, experts and combine walk the
sorted rows a static window at a time as far as what landed reaches, and the
counts say how many rows were walked (``_grouped_forward``).
The grouped path is taken where the masks would cost a real share of the
experts (many narrow experts, a full chunk of tokens), and where the bucket's
assignments cannot touch more than half of the banks (a decode step's 8 rows
at top-8 of 128, also inside ``decode_loop``'s scan: the step then streams the
~52 banks it routed to and not all 128).

What the router does is the model's to say and one step for every path
(``_router_probs``, ``_choose``): scores by softmax over the experts or by a
sigmoid of each (``score_func``), the ``top_k`` largest picked, of the scores or
of score + a per-expert ``select_bias`` that picks and does not weigh, the
chosen scores renormalised (``norm_topk_prob``) and scaled (``route_scale``).
A model may route over experts that have NO bank (``zero_experts``: the
router's last outputs, each of which returns its input): they are chosen and
weighed with the others, reach no sort, no buffer and no bank, and the token's
input times the sum of their weights is added where the token lives, under the
scope ``zero``. The grouped path computes them; such a layer routes by sorting
whatever the bucket, and is refused on an expert-parallel mesh.

Named scopes (``jax.named_scope``, metadata only), the same four on both paths:
``route`` (router probabilities + capacity packing, or the sort),
``dispatch`` (tokens into the expert-major buffer), ``experts`` (the grouped
GEMMs), ``combine`` (back to token-major with the routing weights) and, under
expert parallelism, ``a2a`` (the two all-to-alls). They are relative: the
caller's ``moe`` scope (mixtral_v2's FFN phase) makes them ``moe/route`` ... in
the device trace. The one place where that does not hold is the body of the
loop over row windows: JAX traces a ``while``'s body under an empty name stack
and lowers it under ``while/body``, so a scope inside it reads
``moe/while/body/dispatch`` on the device and a reader that looks for
``moe/dispatch/`` (``moe_route_busy_pct``) passes it by. The body's scopes
therefore restate the caller's (``LOOP_SCOPE``): ``moe/while/body/moe/dispatch``.
"""

from typing import Optional

import numpy as np

from deepspeed_tpu.utils import groups

_SIMULATED_GATING = {"enabled": False, "temperature": 1.0}

# what the scopes inside the loop over row windows start with: the scope every
# model program wraps this module in (the module docstring's last paragraph)
LOOP_SCOPE = "moe/"


def enable_simulated_gating(temperature: float = 1.0) -> None:
    _SIMULATED_GATING["enabled"] = True
    _SIMULATED_GATING["temperature"] = float(temperature)


def disable_simulated_gating() -> None:
    _SIMULATED_GATING["enabled"] = False


def simulated_gating_enabled() -> bool:
    return _SIMULATED_GATING["enabled"]


def simulated_expert_probs(layer_id: int, num_experts: int, temperature: Optional[float] = None):
    """Per-layer synthetic expert distribution (seeded, deterministic)."""
    import jax.numpy as jnp
    if temperature is None:
        temperature = _SIMULATED_GATING["temperature"]
    rng = np.random.default_rng(1000 + layer_id)
    p = rng.dirichlet(np.full(num_experts, 2.0))
    logp = np.log(np.maximum(p, 1e-9)) / max(temperature, 1e-6)
    e = np.exp(logp - logp.max())
    return jnp.asarray(e / e.sum(), jnp.float32)


class RaggedMoE:
    """Functional top-k MoE over flat tokens [T, M] with disaggregated EP."""

    def __init__(self, num_experts: int, top_k: int = 2, capacity_factor: float = 2.0,
                 expert_axis: str = groups.EXPERT_AXIS, layer_id: int = 0,
                 norm_topk_prob: bool = True, score_func: str = "softmax",
                 route_scale: float = 1.0, n_group: int = 1, topk_group: int = 1,
                 held: Optional[int] = None, first_held: int = 0, zero_experts: int = 0):
        """``norm_topk_prob``: renormalise the ``top_k`` chosen probabilities to
        sum to 1, as the model states it (Mixtral does; a top-1 router that
        weights by the raw probability passes False). ``score_func``: how a
        router logit becomes an expert's score, ``softmax`` over the experts or
        ``sigmoid`` of each alone, in float32 either way. ``route_scale``
        multiplies the routing weights after the renormalisation. ``n_group`` /
        ``topk_group``: the group limit (:meth:`_choose`); 1 is none.
        ``held`` / ``first_held``: this layer holds experts ``first_held ..
        first_held + held`` of the ``num_experts`` it routes over (one chip's
        share of a layer that several chips share): its banks are ``[held,
        ...]``, it computes the assignments that land on them and nothing for
        the rest: no exchange, no stand-in for the absent chips. The weights
        are renormalised over all the chosen, held here or not.
        ``zero_experts``: the router has that many outputs MORE, behind the
        ``num_experts`` that have banks: experts that return their input
        (:meth:`_zero_term`). They are no chip's to hold: a share computes
        them whole. Such a layer takes the grouped path whatever the bucket
        (:meth:`path`) and is not built for an expert-parallel mesh."""
        if score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"ragged MoE scores by softmax or sigmoid, not {score_func!r}")
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"ragged MoE needs 1 <= top_k <= num_experts, got top_k={top_k} "
                             f"of {num_experts}")
        if zero_experts < 0 or (zero_experts and n_group > 1):
            raise ValueError(f"{zero_experts} experts without a bank under {n_group} routing "
                             f"groups: they belong to no group")
        self.num_experts = num_experts
        self.zero_experts = int(zero_experts)
        self.top_k = top_k
        self.norm_topk_prob = bool(norm_topk_prob)
        self.score_func = score_func
        self.route_scale = float(route_scale)
        self.capacity_factor = capacity_factor
        self.expert_axis = expert_axis
        self.layer_id = layer_id
        if num_experts % n_group or not 1 <= topk_group <= n_group \
                or top_k > topk_group * (num_experts // n_group):
            raise ValueError(f"{num_experts} experts in {n_group} groups, {topk_group} kept, "
                             f"top_k={top_k}")
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        if held is not None and not 0 <= first_held <= num_experts - held:
            raise ValueError(f"experts {first_held}..{first_held + held} of {num_experts}")
        self.held, self.first_held = held, int(first_held)

    @property
    def experts_here(self) -> int:
        """Experts whose banks this layer holds: all it routes over, or its share."""
        return self.num_experts if self.held is None else self.held

    def _here(self, topk_e):
        """The chosen experts as indices into THIS layer's banks: themselves,
        or for a share the local index, ``experts_here`` (no bank: every arm
        drops it) for an expert another chip holds and for one that has no
        bank anywhere (``zero_experts``)."""
        import jax.numpy as jnp
        if self.held is None:
            if not self.zero_experts:
                return topk_e
            return jnp.minimum(topk_e, self.num_experts)  # an expert without a bank
        local = topk_e - self.first_held
        return jnp.where((local >= 0) & (local < self.held), local, self.held)

    def capacity(self, tokens: int) -> int:
        """Slots an expert has for a batch of ``tokens``. With ``capacity_factor
        = num_experts / top_k`` it is ``tokens``: no assignment can be dropped
        (a token picks an expert at most once)."""
        return max(4, int(np.ceil(tokens * self.top_k / self.num_experts * self.capacity_factor)))

    def path(self, tokens: int, intermediate: int, ep: int = 1) -> str:
        """``grouped`` or ``capacity``: the path a ``tokens``-token bucket takes
        through experts ``intermediate`` wide (``modules/heuristics.py``)."""
        from deepspeed_tpu.inference.v2.modules.heuristics import moe_implementation
        if self.zero_experts:
            return "grouped"  # the path that computes an expert without a bank
        return moe_implementation(tokens, self.num_experts, self.top_k, self.capacity(tokens),
                                  intermediate, ep, held=self.held)

    def row_window(self, tokens: int):
        """Rows of the sorted buffer a ``tokens``-token bucket's grouped
        program walks at a time, or None: no window, every row at once
        (``heuristics.moe_row_window``)."""
        from deepspeed_tpu.inference.v2.modules.heuristics import moe_row_window
        return moe_row_window(tokens, self.top_k, self.num_experts + self.zero_experts, self.held)

    def expert_rows(self, tokens: int, ep: int = 1, path: str = "capacity") -> int:
        """Rows the expert GEMMs compute for a ``tokens``-token bucket, live or
        padding: on the capacity path every slot of every expert (over all
        ``ep`` replicas), whatever was routed; on the grouped path a row an
        assignment of the padded bucket."""
        if path == "grouped":
            from deepspeed_tpu.ops.pallas.grouped_matmul import padded_rows
            return padded_rows(tokens * self.top_k)
        return self.num_experts * ep * self.capacity(-(-tokens // ep))

    # ------------------------------------------------------------------ gating --
    def _router_probs(self, h, gate_w, gate_seed=None, replica=None):
        import jax
        import jax.numpy as jnp
        if simulated_gating_enabled():
            # Load-testing mode: every token draws from the synthetic per-layer
            # distribution; the batch seed + replica index diversify the draw.
            outputs = self.num_experts + self.zero_experts
            probs = simulated_expert_probs(self.layer_id, outputs)
            T = h.shape[0]
            key = jax.random.PRNGKey(1000 + self.layer_id)
            if gate_seed is not None:
                key = jax.random.fold_in(key, gate_seed)
            if replica is not None:
                key = jax.random.fold_in(key, replica)
            u = jax.random.uniform(key, (T, outputs))
            # Gumbel trick over the fixed distribution
            logits = jnp.log(probs)[None, :] - jnp.log(-jnp.log(jnp.maximum(u, 1e-9)))
            return jax.nn.softmax(logits, axis=-1)
        logits = h.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        if self.score_func == "sigmoid":
            return jax.nn.sigmoid(logits)
        return jax.nn.softmax(logits, axis=-1)

    def _choose(self, probs, select_bias=None):
        """The ``top_k`` experts of each token and their routing weights
        ``[T, k]``: what all three arms share. With a ``select_bias`` [E] the
        experts are the largest of score + bias and the weights their SCORES
        (the bias picks, it does not weigh); then the renormalisation over the
        chosen and the route scale, as the model states them. Under a group
        limit (``n_group`` > 1) the experts are first cut to the ``topk_group``
        groups of largest score, a group's score the sum of its two largest
        score + bias; the pick is then among the kept groups' experts."""
        import jax
        import jax.numpy as jnp
        if self.n_group > 1:
            T, E = probs.shape
            biased = probs if select_bias is None else probs + select_bias.astype(probs.dtype)
            per_group = biased.reshape(T, self.n_group, E // self.n_group)
            group_score = jax.lax.top_k(per_group, 2)[0].sum(-1)  # [T, groups]
            floor = jax.lax.top_k(group_score, self.topk_group)[0][:, -1:]
            kept = jnp.repeat(group_score >= floor, E // self.n_group, axis=1)
            _, topk_e = jax.lax.top_k(jnp.where(kept, biased, -jnp.inf), self.top_k)
            topk_p = jnp.take_along_axis(probs, topk_e, axis=-1)
        elif select_bias is None:
            topk_p, topk_e = jax.lax.top_k(probs, self.top_k)  # [T, k]
        else:
            _, topk_e = jax.lax.top_k(probs + select_bias.astype(probs.dtype), self.top_k)
            topk_p = jnp.take_along_axis(probs, topk_e, axis=-1)
        if self.norm_topk_prob:
            denom = jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)
            topk_p = topk_p / denom  # renormalized over the chosen k (Mixtral's 2)
        if self.route_scale != 1.0:
            topk_p = topk_p * self.route_scale
        return topk_p, topk_e

    def _zero_chosen(self, topk_e, token_valid=None):
        """bool ``[T, k]``: the live tokens' choices that are experts WITHOUT a
        bank (``zero_experts``: the router's outputs behind ``num_experts``)."""
        zero = topk_e >= self.num_experts
        return zero if token_valid is None else zero & token_valid[:, None]

    def _zero_term(self, h, topk_p, topk_e, token_valid=None):
        """``h`` times the summed weights of each token's chosen experts
        without a bank, float32 ``[T, M]``: an identity expert's output is its
        input, so they cost one multiply where the token lives, whichever
        chip holds the others."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope("zero"):
            weight = jnp.where(self._zero_chosen(topk_e, token_valid),
                               topk_p.astype(jnp.float32), 0.0).sum(-1, keepdims=True)
            return h.astype(jnp.float32) * weight

    # ------------------------------------------------------- capacity packing --
    def _pack(self, probs, token_valid, C, dtype, select_bias=None):
        """Top-k assignment with capacity packing (reference moe_scatter).

        Returns combine [T, E, C] (f32 routing weights) and dispatch [T, E, C]
        (0/1 in ``dtype``). Slot counters are SHARED across the k choices
        (reference top2gating: locations2 += sum(mask1)) — otherwise a
        first-choice and a second-choice token land in the same capacity slot
        and their hidden states sum in the expert buffer."""
        import jax
        import jax.numpy as jnp

        T, E = probs.shape[0], self.experts_here
        combine = jnp.zeros((T, E, C), jnp.float32)
        dispatch = jnp.zeros((T, E, C), dtype)
        topk_p, topk_e = self._choose(probs, select_bias)
        topk_e = self._here(topk_e)
        fill = self._fill_level_by_level if self.top_k <= 2 else self._fill_in_one_pass
        return fill(topk_p, topk_e, token_valid, C, combine, dispatch)

    def _fill_level_by_level(self, topk_p, topk_e, token_valid, C, combine, dispatch):
        """One pass and one scatter a choice level: the top-1 / top-2 program
        (what the Mixtral cells trace)."""
        import jax
        import jax.numpy as jnp

        T, E = topk_e.shape[0], self.experts_here
        base = jnp.zeros((E, ), jnp.int32)
        for j in range(topk_e.shape[1]):
            e_j = topk_e[:, j]  # [T]
            if token_valid is not None:
                # invalid tokens must not consume capacity slots: route them OOB
                e_j = jnp.where(token_valid, e_j, E)
            onehot = jax.nn.one_hot(e_j, E, dtype=jnp.int32)  # [T, E]; OOB -> all-zero
            slot = jnp.cumsum(onehot, axis=0) * onehot - 1  # position within expert
            slot_t = slot.max(axis=1) + (onehot @ base)  # [T]; -1 for OOB tokens
            ok = (slot_t < C) & (slot_t >= 0)
            t_idx = jnp.arange(T)
            slot_c = jnp.where(ok, slot_t, C)  # OOB slot -> dropped by scatter
            combine = combine.at[t_idx, e_j, slot_c].add(
                jnp.where(ok, topk_p[:, j], 0.0), mode="drop")
            dispatch = dispatch.at[t_idx, e_j, slot_c].add(
                jnp.where(ok, 1.0, 0.0).astype(dispatch.dtype), mode="drop")
            base = base + onehot.sum(axis=0)
        return combine, dispatch

    def _fill_in_one_pass(self, topk_p, topk_e, token_valid, C, combine, dispatch):
        """The same slots for every choice level in ONE pass: the T x k
        assignments laid out level-major (all first choices in token order,
        then all second choices, ...) are in the order the level-by-level loop
        fills slots, so one cumulative count over that list gives each
        assignment the slot the loop gives it (tier-1 holds the two against
        each other). One scatter of T x k updates in place of k scatters of T:
        a top-8 layer's program is about half the unrolled loop's to compile
        (2.4 s against 4.4 s a 256-token bucket of 8 layers, compiled for a
        v5e here: PERF.md section 6, PR 30), which a cell with 49 programs to
        warm inside its run limit needs."""
        import jax
        import jax.numpy as jnp

        T, k = topk_e.shape
        E = self.experts_here
        e_flat = topk_e.T.reshape(k * T)
        p_flat = topk_p.T.reshape(k * T)
        t_flat = jnp.tile(jnp.arange(T), k)
        if token_valid is not None:
            # invalid tokens must not consume capacity slots: route them OOB
            e_flat = jnp.where(jnp.tile(token_valid, k), e_flat, E)
        onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)  # [k T, E]; OOB -> all-zero
        slot = (jnp.cumsum(onehot, axis=0) * onehot).max(axis=1) - 1  # -1 for OOB tokens
        ok = (slot < C) & (slot >= 0)
        slot_c = jnp.where(ok, slot, C)  # OOB slot -> dropped by scatter
        combine = combine.at[t_flat, e_flat, slot_c].add(jnp.where(ok, p_flat, 0.0), mode="drop")
        dispatch = dispatch.at[t_flat, e_flat, slot_c].add(
            jnp.where(ok, 1.0, 0.0).astype(dispatch.dtype), mode="drop")
        return combine, dispatch

    @staticmethod
    def banks_in_lane_tiles(wi, wo):
        """An UNGATED bank pair ``wi [E, M, F]`` / ``wo [E, F, M]`` with the
        intermediate width F padded with zeros to whole lane tiles
        (``grouped_matmul.lane_padded``), as given where it is whole already:
        the grouped kernel takes whole lane tiles and falls to ``ragged_dot``
        on anything else. Exact for an activation with ``act(0) = 0``: a zero
        column of ``wi`` meets a zero row of ``wo``. Once, where the weights
        are loaded — never inside a step."""
        import jax.numpy as jnp

        from deepspeed_tpu.ops.pallas.grouped_matmul import lane_padded
        F = wo.shape[-2]
        if wi.shape[-1] != F:
            raise NotImplementedError(f"banks_in_lane_tiles: a gated bank (wi {wi.shape[-1]} "
                                      f"wide over wo's {F}) interleaves two projections")
        pad = lane_padded(F) - F
        if not pad:
            return wi, wo
        return (jnp.pad(wi, ((0, 0), (0, 0), (0, pad))), jnp.pad(wo, ((0, 0), (0, pad), (0, 0))))

    def _expert_ffn(self, buf, wi, wo, activation):
        """Grouped expert GEMM over an expert-major buffer [E?, C?, M] (the
        reference's CUTLASS multi-GEMM, moe_gemm.cu:175 role)."""
        import jax.numpy as jnp
        hpre = jnp.einsum("ecm,emf->ecf", buf, wi.astype(buf.dtype))
        if wi.shape[-1] == 2 * wo.shape[-2]:  # fused (gate|up) SwiGLU bank
            from deepspeed_tpu.moe.layer import gated_expert_act
            hmid = gated_expert_act(hpre, activation)
        else:
            hmid = activation(hpre)
        return jnp.einsum("ecf,efm->ecm", hmid, wo.astype(buf.dtype))

    # ----------------------------------------------------------------- forward --
    def __call__(self, h, gate_w, wi, wo, token_valid=None, activation=None, mesh=None,
                 gate_seed=None, select_bias=None, banks_out=None):
        """h: [T, M]; gate_w: [M, E]; wi: [E, M, F]; wo: [E, F, M] (the training
        ExpertFFN bank layout — EP-shards on the leading dim); ``select_bias``:
        float32 [E] added to the scores to PICK the experts (:meth:`_choose`),
        or None. Dispatches to the disaggregated shard_map path when the mesh
        has an expert axis > 1. ``banks_out``: a list; a bucket on the grouped
        path appends the int32 scalar of expert banks this call's routing
        touched (the banks its GEMMs read), the other paths append nothing
        (they multiply every bank, whatever was routed)."""
        import jax

        if activation is None:
            activation = jax.nn.silu
        if mesh is None:
            try:
                mesh = groups.get_mesh()
            except Exception:
                mesh = None
        ep = int(mesh.shape.get(self.expert_axis, 1)) if mesh is not None else 1
        if ep > 1 and self.held is not None:
            raise NotImplementedError(
                "a layer that holds a share of its experts runs on one replica: the exchange "
                "of an expert-parallel mesh would need the other chips' shares")
        if ep > 1 and self.zero_experts:
            raise NotImplementedError(
                "experts without a bank (zero_experts) are computed on the grouped path of one "
                "replica: the capacity masks of an expert-parallel mesh have no term for them")
        if ep > 1 and self.num_experts % ep == 0:
            return self._ep_forward(h, gate_w, wi, wo, token_valid, activation, mesh,
                                    ep, gate_seed, select_bias)
        if ep > 1:
            from deepspeed_tpu.utils.logging import logger
            logger.warning(f"RaggedMoE: {self.num_experts} experts not divisible by EP "
                           f"degree {ep}; falling back to GSPMD expert-sharded compute "
                           f"(no token disaggregation)")
        if self.path(h.shape[0], wo.shape[-2], ep) == "grouped":
            return self._grouped_forward(h, gate_w, wi, wo, token_valid, activation, gate_seed,
                                         select_bias, banks_out)
        return self._dense_forward(h, gate_w, wi, wo, token_valid, activation, gate_seed,
                                   mesh if ep > 1 else None, select_bias)

    def _grouped_forward(self, h, gate_w, wi, wo, token_valid, activation, gate_seed,
                         select_bias=None, banks_out=None):
        """Single-replica path that routes by SORTING: the T x k assignments
        ordered by expert, one gather of their rows, one grouped matmul a
        projection (each expert's rows against its own bank), the routing
        weights applied in float32 and the rows summed back to their tokens.
        Every assignment has a row whatever the skew: dropless by construction,
        ``capacity_factor`` has no part in it. An invalid token's assignments
        sort behind every expert's and belong to no group. ``banks_out``, a
        list, is appended int32 ``[banks, visits]``: the experts that have a
        row, whose banks the two GEMMs read (an invalid token's and the
        padding's rows count for none), and the (expert, row tile) visits the
        kernel's schedule makes of them (``grouped_matmul.visit_count``: what
        passes the banks is the visits that are an expert's further row tile).
        A layer that holds a SHARE of its experts (``held``) sorts the
        assignments of the others' experts behind its own, as an invalid
        token's, and appends ``[banks, assignments, visits, rows walked]``
        that landed here.

        Such a layer WALKS THE ROWS THAT LANDED ON IT (PR 64). What landed is
        the first ``n_local`` sorted rows and a few per cent of the bucket's
        (LongCat: ~64 of a 256-token step's 3,072), and the gather of the rows,
        the mask, the gather back, the weights and the sum over all of them
        were 8.6 % of that cell's busy time for rows the kernel never stored
        (PERF.md section 6, PR 64, step 0: combine 406 us, dispatch 114 us a
        layer-step on a TPU v5e). Where ``heuristics.moe_row_window`` gives
        the bucket a window of ``W`` rows, everything from dispatch to combine
        runs on ``W`` sorted rows at a time, from the first, until past
        ``n_local`` (a ``while`` on the device's own count: one window when
        what landed fits it, none when nothing landed, every one under a router
        that lands everything here): nothing is dropped whatever the skew. A
        window's groups are each group's rows inside it (a group that straddles
        two windows has its bank read in both; the row tiles and so the visits
        are the un-windowed schedule's), and its rows reach their tokens by
        one float32 matmul with the ``[T, W]`` matrix of which row is whose
        (exact products at HIGHEST precision; a token's at most ``k`` rows
        are summed in another order than the gather's), so there is no inverse
        permutation. A layer without a window (one that holds every expert,
        a share of a half, a bucket of one row tile) runs the program it ran
        before."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.grouped_matmul import padded_rows, visit_count

        T, M = h.shape
        E, k = self.experts_here, self.top_k
        rows = padded_rows(T * k)
        window = self.row_window(T)
        with jax.named_scope("route"):
            probs = self._router_probs(h, gate_w, gate_seed=gate_seed)  # [T, E] float32
            topk_p, topk_e = self._choose(probs, select_bias)  # [T, k]
            e_flat = self._here(topk_e).reshape(T * k)  # token-major: assignment a is token a // k
            if token_valid is not None:
                e_flat = jnp.where(jnp.repeat(token_valid, k), e_flat, E)
            e_flat = jnp.pad(e_flat, (0, rows - T * k), constant_values=E)
            slots = jnp.arange(rows, dtype=jnp.int32)
            # stable: an expert's rows stay in token order
            e_sorted, order = jax.lax.sort((e_flat, slots), num_keys=1, is_stable=True)
            group_sizes = (e_flat[:, None] == jnp.arange(E)[None, :]).sum(0, dtype=jnp.int32)
            n_local = None if self.held is None else group_sizes.sum(dtype=jnp.int32)
            if banks_out is not None:
                here, walked = [], []
                if self.held is not None:
                    here = [n_local]
                    # (the last window of a bucket that is no whole number of
                    # them is padding past ``rows``: not rows of the bucket)
                    walked = [jnp.int32(rows) if window is None
                              else jnp.minimum(-(-n_local // window) * window, rows)]
                zero = [self._zero_chosen(topk_e, token_valid).sum(dtype=jnp.int32)] \
                    if self.zero_experts else []
                banks_out.append(jnp.stack([(group_sizes > 0).sum(dtype=jnp.int32), *here,
                                            visit_count(group_sizes), *walked, *zero]))
            if window is None:
                # where each assignment's row went: the inverse permutation
                _, back = jax.lax.sort((order, slots), num_keys=1)

        def experts_over(slots_of, experts_of, sizes, under=""):
            """Sorted rows (the assignment slot and the expert of each, in
            groups of ``sizes``) through dispatch and the experts: float32
            ``[len(slots_of), M]``, the rows of no expert zero. ``under``: what
            the scopes' names start with (the loop over windows, below)."""
            with jax.named_scope(under + "dispatch"):
                buf = h[jnp.minimum(slots_of // k, T - 1)]
            with jax.named_scope(under + "experts"):
                out = self._grouped_ffn(buf, wi, wo, sizes, activation)  # float32
            with jax.named_scope(under + "combine"):
                # rows behind the last group (an invalid token's, the padding,
                # another chip's) are no expert's: whatever the kernel left there
                return jnp.where((experts_of < E)[:, None], out, 0.0)

        if window is None:
            out = experts_over(order, e_sorted, group_sizes)
            with jax.named_scope("combine"):
                out = (out[back[:T * k]].reshape(T, k, M) * topk_p[:, :, None]).sum(axis=1)
        else:
            # whole windows of the sorted rows, so that the last one is whole too
            spare = -rows % window
            order = jnp.pad(order, (0, spare), constant_values=rows)
            e_sorted = jnp.pad(e_sorted, (0, spare), constant_values=E)
            ends = jnp.cumsum(group_sizes)
            weights = topk_p.reshape(T * k)

            def one_window(carry):
                """Rows ``start .. start + window`` of the sorted order: each
                group's rows among them against its bank, summed onto their
                tokens."""
                start, out = carry
                with jax.named_scope(LOOP_SCOPE + "route"):
                    mine = jax.lax.dynamic_slice(order, (start, ), (window, ))
                    experts_of = jax.lax.dynamic_slice(e_sorted, (start, ), (window, ))
                    inside = jnp.clip(ends, start, start + window) \
                        - jnp.clip(ends - group_sizes, start, start + window)
                rows_out = experts_over(mine, experts_of, inside, under=LOOP_SCOPE)
                with jax.named_scope(LOOP_SCOPE + "combine"):
                    # a row's weight is its assignment's; which row is whose as a
                    # matrix: exact products, a token's rows summed in float32
                    rows_out = rows_out * weights[jnp.minimum(mine, T * k - 1)][:, None]
                    to_token = (jnp.arange(T)[:, None] == (mine // k)[None, :])
                    out = out + jnp.matmul(to_token.astype(jnp.float32), rows_out,
                                           precision=jax.lax.Precision.HIGHEST)
                return start + window, out

            _, out = jax.lax.while_loop(lambda carry: carry[0] < n_local, one_window,
                                        (jnp.int32(0), jnp.zeros((T, M), jnp.float32)))
        if not self.zero_experts:
            with jax.named_scope("combine"):
                return out.astype(h.dtype)
        return (out + self._zero_term(h, topk_p, topk_e, token_valid)).astype(h.dtype)

    def _grouped_ffn(self, buf, wi, wo, group_sizes, activation):
        """The experts over expert-sorted rows [rows, M]: operands in the rows'
        dtype, float32 accumulation, a float32 result for the combine."""
        import jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.grouped_matmul import group_visits, grouped_matmul
        visits = group_visits(group_sizes, buf.shape[0])  # one schedule, both projections
        hpre = grouped_matmul(buf, wi.astype(buf.dtype), group_sizes, buf.dtype, visits=visits)
        if wi.shape[-1] == 2 * wo.shape[-2]:  # fused (gate|up) SwiGLU bank
            from deepspeed_tpu.moe.layer import gated_expert_act
            hmid = gated_expert_act(hpre, activation)
        else:
            hmid = activation(hpre)
        return grouped_matmul(hmid, wo.astype(buf.dtype), group_sizes, jnp.float32,
                              visits=visits)

    def _dense_forward(self, h, gate_w, wi, wo, token_valid, activation, gate_seed,
                       mesh=None, select_bias=None):
        """Single-replica path: all tokens local, no explicit collectives. When a
        degenerate EP mesh is passed (experts not divisible), the expert buffers
        are still constraint-sharded so GSPMD partitions the grouped GEMM."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.sequence.layer import _constrain

        T, M = h.shape
        E = self.num_experts
        C = self.capacity(T)
        with jax.named_scope("route"):
            probs = self._router_probs(h, gate_w, gate_seed=gate_seed)  # [T, E]
            if token_valid is not None:
                probs = probs * token_valid[:, None]
            combine, dispatch = self._pack(probs, token_valid, C, h.dtype, select_bias)
        with jax.named_scope("dispatch"):
            buf = jnp.einsum("tec,tm->ecm", dispatch, h)  # [E, C, M]
            if mesh is not None:
                buf = _constrain(buf, (self.expert_axis, None, None), mesh)
        with jax.named_scope("experts"):
            out = self._expert_ffn(buf, wi, wo, activation)
            if mesh is not None:
                out = _constrain(out, (self.expert_axis, None, None), mesh)
        with jax.named_scope("combine"):
            return jnp.einsum("tec,ecm->tm", combine.astype(h.dtype), out)

    def _ep_forward(self, h, gate_w, wi, wo, token_valid, activation, mesh, ep, gate_seed,
                    select_bias=None):
        """Disaggregated EP: each replica owns T/ep tokens and its E/ep experts.

        The fork's data flow (cutlass_multi_gemm_ep.py):
          1. local top-k routing + capacity packing of OWN tokens
          2. all_to_all #1: per-destination-replica expert buffers out, every
             replica's tokens for MY experts in   (ref :311,:340 — counts are
             subsumed by the static capacity padding)
          3. local grouped GEMM over [E_local, ep*C] received tokens
          4. all_to_all #2: results back to token owners (ref :389)
          5. local combine with the saved top-k weights (moe_gather)
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        ax = self.expert_axis
        T, M = h.shape
        E = self.num_experts
        El = E // ep
        Tp = -(-T // ep) * ep  # pad so every replica owns the same token count
        if token_valid is None:
            token_valid = jnp.ones((T, ), bool)
        if Tp != T:
            h = jnp.pad(h, ((0, Tp - T), (0, 0)))
            token_valid = jnp.pad(token_valid, (0, Tp - T))
        Tl = Tp // ep
        C = self.capacity(Tl)
        seed = jnp.asarray(0 if gate_seed is None else gate_seed, jnp.int32)

        def body(h_l, gate_w, wi_l, wo_l, tv_l, seed_l, *bias):
            with jax.named_scope("route"):
                replica = jax.lax.axis_index(ax)
                probs = self._router_probs(h_l, gate_w, gate_seed=seed_l, replica=replica)
                probs = probs * tv_l[:, None]
                combine, dispatch = self._pack(probs, tv_l, C, h_l.dtype, *bias)
            with jax.named_scope("dispatch"):
                buf = jnp.einsum("tec,tm->ecm", dispatch, h_l)       # [E, C, M]
                buf = buf.reshape(ep, El, C, M)                      # dest-replica major
            with jax.named_scope("a2a"):
                buf = jax.lax.all_to_all(buf, ax, 0, 0, tiled=True)  # a2a #1: dispatch
            with jax.named_scope("experts"):
                merged = buf.transpose(1, 0, 2, 3).reshape(El, ep * C, M)
                out = self._expert_ffn(merged, wi_l, wo_l, activation)
                out = out.reshape(El, ep, C, M).transpose(1, 0, 2, 3)
            with jax.named_scope("a2a"):
                ret = jax.lax.all_to_all(out, ax, 0, 0, tiled=True)  # a2a #2: return
            with jax.named_scope("combine"):
                ret = ret.reshape(E, C, M)                           # global-expert major
                return jnp.einsum("tec,ecm->tm", combine.astype(h_l.dtype), ret)

        bias = () if select_bias is None else (select_bias, )  # replicated, as the gate
        shmap = jax.shard_map(body, mesh=mesh,
                              in_specs=(P(ax), P(), P(ax), P(ax), P(ax), P()) + (P(), ) * len(bias),
                              out_specs=P(ax), check_vma=False)
        out = shmap(h, gate_w, wi, wo, token_valid, seed, *bias)
        return out[:T]
