"""Mellum-2 ragged inference model (``model_type="mellum"``).

Llama's attention phase and Mixtral's ``moe`` phase over the parameter tree of
:mod:`deepspeed_tpu.models.mellum`, with what the architecture adds read from
its config layer by layer: ``layer_types`` gives each layer its attention
window (``sliding_window`` or none; the KV pool groups the layers by it,
``ragged/kv_cache.py``) and its rotary embedding (``rope_parameters``:
``default`` or ``yarn``, with the attention factor folded in);
routing is top-``num_experts_per_tok`` of ``num_experts``, renormalised as
``norm_topk_prob`` says.
"""

from deepspeed_tpu.inference.v2.model_implementations.llama_v2 import LlamaV2Model, _rotate_half
from deepspeed_tpu.inference.v2.model_implementations.mixtral_v2 import MixtralV2Model
from deepspeed_tpu.models.mellum import MellumConfig, rotary_cos_sin


class LayerTypedMoEModel(MixtralV2Model):
    """A sparse model whose config says, layer by layer (``layer_types``), how
    far a layer's attention sees (``window_of``) and how it rotates q and k
    (``rope_of``; None = not at all): the glue between such a config and
    Llama's attention phase, Mixtral's routed experts and the KV layer groups.
    ``sparse_layers`` of the stack carry experts; ``router`` is what the model
    says of its routing beyond ``RaggedMoE``'s default."""

    def __init__(self, params, config, engine_config, state_manager=None, *, sparse_layers,
                 **router):
        # LlamaV2Model's own constructor: MixtralV2Model's converts a MixtralConfig
        LlamaV2Model.__init__(self, params, config, engine_config, state_manager)
        self._moe_config = config
        self._build_moes(range(sparse_layers), config.num_experts, config.num_experts_per_tok,
                         config.moe_intermediate_size, **router)

    def _build_rope(self, max_context):
        """No table: a layer type's rotary parameters. The angles are computed
        in the program from the step's positions (:meth:`_rotate`): two tables
        built to ``max_context`` 16384 are 16 MiB of constants in EVERY
        bucket's program, which a persistent compile cache has to hold 49
        times and the compiler to read as often."""
        return {kind: self._config.rope_of(kind) for kind in set(self._config.layer_types)}

    def _rotate(self, li, x, pos):
        rope = self._rope[self._config.layer_types[li]]
        if rope is None:  # a layer without position encoding: q and k as they are
            return x
        cos, sin = rotary_cos_sin(rope, pos, self._config.head_dim)
        return _rotate_half(x, cos[:, None, :], sin[:, None, :])

    def attention_window_of(self, li):
        return self._config.window_of(li)

    @property
    def attention_window(self):
        # not AttributeError: getattr(model, "attention_window", 0) must not read 0
        raise ValueError(f"a {self._config.model_type} model has no one attention window: ask "
                         f"attention_window_of(layer) or group_windows")


class MellumV2Model(LayerTypedMoEModel):

    def __init__(self, params, config: MellumConfig, engine_config, state_manager=None):
        super().__init__(params, config, engine_config, state_manager,
                         sparse_layers=config.num_hidden_layers,
                         norm_topk_prob=config.norm_topk_prob)
