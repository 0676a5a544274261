"""Mellum family (``model_type="mellum"``): window and full attention layers
side by side, top-8-of-64 sparse experts. From a configuration file to the
program's own objects.

The program's ``MellumConfig`` is imported before anything else: a program
without it (no ``deepspeed_tpu.models.mellum``: no layer groups in its KV pool,
no top-k above 2 in its router) cannot serve this family, and a run of its cell
exits here, in seconds, before any weight is made.

Like ``mistral_windowed`` (PERF.md section 6, PR 25 and PR 26: a whole run has
to end inside the driver's limit from an empty compile cache) the weights are
made layer by layer (the program's initialiser compiles one layer once), the
reference runs on token ids padded to ONE length (each of its jitted parts is
compiled once a layer type, not once a prompt length; the model is causal, so
the rows asked for do not see the padding), and the traced
slice's idle gaps are labelled by bisection: this family's steps take 6-19 ms
(``benchmark/interval_lookup.py``).
"""

from types import SimpleNamespace

try:
    from deepspeed_tpu.models.mellum import MellumConfig
except ImportError as e:
    raise SystemExit(
        f"benchmark: this program has no deepspeed_tpu.models.mellum ({e}): it cannot serve a "
        f"model whose layers keep different spans of a sequence (window and full attention "
        f"side by side need a block table a layer group in the KV pool) nor route top-8 of 64 "
        f"experts. Nothing was measured.")

from benchmark import interval_lookup
from benchmark.references import mellum as plain_reference

MODEL_KEYS = ("vocab_size", "hidden_size", "head_dim", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "num_experts", "num_experts_per_tok",
              "moe_intermediate_size", "norm_topk_prob", "sliding_window", "rope_parameters",
              "max_position_embeddings", "rms_norm_eps", "tie_word_embeddings", "attention_bias")

interval_lookup.install()


def program_config(sizes):
    """The first ``num_hidden_layers`` entries of the published ``layer_types``
    and ``mlp_layer_types``: the configuration keeps both lists whole."""
    import jax.numpy as jnp
    n = sizes["num_hidden_layers"]
    return MellumConfig(dtype=getattr(jnp, sizes.get("torch_dtype", "bfloat16")),
                        layer_types=tuple(sizes["layer_types"][:n]),
                        mlp_layer_types=tuple(sizes["mlp_layer_types"][:n]),
                        **{k: sizes[k] for k in MODEL_KEYS})


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed, layer by layer."""
    import jax
    from deepspeed_tpu.models import mellum
    return mellum.init_params(cfg, rng=jax.random.PRNGKey(seed), param_dtype=cfg.dtype)[1]


def _forward_logits_padded(params, sizes, ids, rows=None, routing_gaps=None):
    """``references/mellum.py:forward_logits`` of ``ids`` padded with token 0 to
    half the configuration's ``max_context``, or to all of it where they are
    longer: the same rows, and one compilation for the four prompts of a check
    (stratified draws: the longest is the distribution's 7/8 quantile, 5767
    tokens under ``repoctx-closed``)."""
    import numpy as np
    ids = np.asarray(ids)
    longest = sizes["engine"]["state_manager"]["max_context"]
    padded = np.zeros(max(ids.size, longest if ids.size > longest // 2 else longest // 2),
                      ids.dtype)
    padded[:ids.size] = ids
    return plain_reference.forward_logits(params, sizes, padded,
                                          rows=np.arange(ids.size) if rows is None else rows,
                                          routing_gaps=routing_gaps)


reference = SimpleNamespace(forward_logits=_forward_logits_padded)  # named for the harness
