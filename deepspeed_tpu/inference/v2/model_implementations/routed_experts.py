"""A model some of whose layers route over experts, whole or one chip's share
of them: what every such family shares. A family inherits :class:`RoutedExperts`
beside its base, calls :meth:`_build_moes` where it is built, and runs a layer's
experts by :meth:`_routed_beside_shared` (or, where its layers are a mixer and
then this feed-forward over the ``mlp`` subtree, by :meth:`_ffn_phase`).

Reads the engine config's ``expert_parallel`` and the model's ``_config.rms_norm_eps``;
everything else is what the family states to :meth:`_build_moes`.

Scopes in the device trace: ``mlp`` (a dense layer), ``moe`` with ``moe/shared``
(the always-on expert) beside ``RaggedMoE``'s own (``moe/zero`` among them where
the router has experts without a bank).
"""

import jax

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import _rms, _swiglu
from deepspeed_tpu.inference.v2.modules.moe import RaggedMoE


class RoutedExperts:

    def _build_moes(self, layer_ids, num_experts, top_k, width, *, dense_layers=0, held=None,
                    first_held=0, **router):
        """``_moes``: one ``RaggedMoE`` an expert layer (``layer_ids``: what each
        is told it is), ``width`` wide, behind ``dense_layers`` leading layers
        whose feed-forward is dense; the capacity factor is the engine's
        ``expert_parallel`` one, ``router`` what the model says of its routing
        beyond the default (softmax, renormalised over the chosen, unscaled).
        ``held`` of the ``num_experts`` from ``first_held`` on where the model
        is one chip's share of its layers: the device then counts what landed
        here (``moe_assignments_local``) beside the banks, and the sorted rows
        its dispatch, experts and combine walked (``moe_rows_walked``: whole
        row windows as far as what landed reaches, one unless the router is
        skewed; every row of the bucket where there is no window,
        ``heuristics.moe_row_window``; over the host's ``moe_rows`` it is the
        share of the bucket walked);
        where the router also has outputs that are experts without a bank
        (``zero_experts`` among ``router``), the choices that fell on those
        (``moe_assignments_zero``)."""
        ep_cfg = getattr(self._engine_config, "expert_parallel", None)
        share = held is not None and held < num_experts
        self._moes = [
            RaggedMoE(num_experts=num_experts, top_k=top_k,
                      capacity_factor=(ep_cfg.capacity_factor if ep_cfg is not None else 2.0),
                      layer_id=li, held=held if share else None, first_held=first_held, **router)
            for li in layer_ids]
        self._expert_width, self._dense_layers = width, dense_layers
        if share:
            self.moe_count_names = ("moe_banks", "moe_assignments_local", "moe_visits",
                                    "moe_rows_walked")
        if router.get("zero_experts"):
            self.moe_count_names += ("moe_assignments_zero", )

    def _expert_parallel(self):
        """The devices a layer's experts are spread over: 1, unless the model
        serves on a mesh with an expert axis and says so."""
        return 1

    def moe_path(self, n_padded):
        """``grouped`` / ``capacity``: how an ``n_padded``-token bucket's
        program routes (``modules/heuristics.py``; one answer for every layer,
        the layers being alike); None without an expert layer."""
        if not self._moes:
            return None
        return self._moes[0].path(n_padded, self._expert_width, self._expert_parallel())

    def dispatch_counts(self, n_padded, n_tokens, steps=1):
        """``moe_rows``: rows the expert GEMMs compute this step on the path
        the bucket takes (``moe_path``), summed over the layers: every
        expert's every slot on the capacity path, a row a padded assignment on
        the grouped one; ``moe_assignments``: live tokens x top-k x layers,
        what had to be — every assignment the router made, held here or not
        (what landed here is the device's to say: ``moe_assignments_local``).
        Both over the ``steps`` of a ``decode_loop`` chunk. On the capacity
        path also ``moe_banks``, the expert banks the GEMMs read: every expert
        held here of every expert layer, every step. On the grouped path that
        count is the routing's, out of the device with the step's result
        (``RaggedMoE.__call__``'s ``banks_out``), and whoever fetches the
        result adds it."""
        if not self._moes:
            return {}
        ep, path = self._expert_parallel(), self.moe_path(n_padded)
        counts = {"moe_path": path,
                  "moe_rows": steps * sum(m.expert_rows(n_padded, ep, path) for m in self._moes),
                  "moe_assignments": steps * n_tokens * sum(m.top_k for m in self._moes)}
        if path == "capacity":
            counts["moe_banks"] = steps * sum(m.experts_here for m in self._moes)
        return counts

    @staticmethod
    def _gating_inputs(batch):
        """``token_valid`` and ``banks_out`` of a step's batch, as ``RaggedMoE``
        takes them: the program's list of the banks each grouped expert layer
        touched (``_forward_impl`` returns it stacked; a verify step keeps none)."""
        return {"token_valid": batch["token_valid"], "banks_out": batch.get("moe_banks")}

    def _routed_beside_shared(self, ei, h, gate, banks, bias, shared, batch, *,
                              activation=jax.nn.silu, dense=_swiglu):
        """Expert layer ``ei`` over the normed rows ``h``: the routed sum over
        ``banks`` (``{wi, wo}``; ``bias`` the selection's, or None) plus, where
        the tree has one (``shared`` not None), the always-on shared expert
        ``dense(h, shared)``: every token, once."""
        out = self._moes[ei](h, gate, banks["wi"], banks["wo"], activation=activation,
                             select_bias=bias, **self._gating_inputs(batch)).astype(h.dtype)
        if shared is not None:
            with jax.named_scope("shared"):
                out = out + dense(h, shared)
        return out

    def _ffn_phase(self, lp, li, x, batch):
        """Layer ``li``'s feed-forward under ``post_attention_layernorm`` and
        its residual, over the subtree ``lp["mlp"]``: the dense SwiGLU in the
        leading ``dense_layers``, the routed experts beside the shared one
        after them."""
        dense = li < self._dense_layers
        with jax.named_scope("mlp" if dense else "moe"):
            h = _rms(x, lp["post_attention_layernorm"]["weight"], self._config.rms_norm_eps)
            mp = lp["mlp"]
            if dense:
                return x + _swiglu(h, mp)
            return x + self._routed_beside_shared(
                li - self._dense_layers, h, mp["gate"], mp["experts"],
                mp["e_score_correction_bias"], mp.get("shared_experts"), batch)
