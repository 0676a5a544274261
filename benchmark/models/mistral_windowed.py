"""Mistral family served PAST its sliding window (contexts longer than
``sliding_window``): the ``mistral`` family's objects and plain reference, behind
one question to the program.

A window model is measured only where the program serves it with the paged
Pallas kernel. A program whose own rule
(``inference/v2/modules/heuristics.py:attention_implementation``) sends a window
model's 256-token bucket to the XLA-gather arm on a TPU took 343 ms for a
512-token step of this model and 522 s of cold set-up (PERF.md section 7,
PR 22): a run of such a cell does not end inside the driver's limit, so this
family says so and exits before any weight is made. The question is put to the
rule itself, not to a version or a commit; off the TPU the gather arm is what
every model runs, and nothing is refused.

A whole run of this family's cell has to end inside the driver's limit from an
empty compile cache (PERF.md section 6, PR 25 and PR 26), and seconds not spent
before the warm-up are depth the configuration can keep. So the weights and the
reference are the ``mistral`` family's, made with fewer compilations: the
weights layer by layer by the program's own one-layer initializer (compiled
once), and the reference on token ids padded to ONE length (its jitted layer is
compiled once, not once a prompt length; the model is causal, so the rows asked
for do not see the padding).
"""

from types import SimpleNamespace

from benchmark import interval_lookup
from benchmark.models import mistral
from benchmark.models.mistral import training_module  # noqa: F401  (the family's)
from benchmark.references import mistral as plain_reference

PROBE_BUCKET_TOKENS = 256

# this family's cells run 5-8 ms steps: labelling the traced slice's idle gaps
# step by step would outlast the run's limit (benchmark/interval_lookup.py)
interval_lookup.install()


def arm_of_a_window_bucket(sizes, rule=None):
    """What the program's rule answers for this configuration's model and engine
    and a 256-token bucket: ``paged_tiled`` / ``paged_token`` / ``xla_gather``."""
    if rule is None:
        from deepspeed_tpu.inference.v2.modules.heuristics import attention_implementation as rule
    heads = sizes["num_attention_heads"]
    model = SimpleNamespace(attention_window=int(sizes.get("sliding_window") or 0),
                            num_heads=heads, num_kv_heads=sizes["num_key_value_heads"],
                            head_dim=sizes.get("head_dim") or sizes["hidden_size"] // heads)
    engine = sizes.get("engine", {})
    engine_config = SimpleNamespace(kv_block_size=engine.get("kv_block_size", 64),
                                    use_paged_kernel=engine.get("use_paged_kernel"))
    return rule(model, engine_config, PROBE_BUCKET_TOKENS)


def refusal(sizes, backend, rule=None):
    """The message this family exits with, or None where the cell can run."""
    if backend != "tpu" or not sizes.get("sliding_window"):
        return None
    arm = arm_of_a_window_bucket(sizes, rule)
    if arm != "xla_gather":
        return None
    return (f"benchmark: this program serves a sliding-window model on the XLA-gather attention "
            f"arm on a TPU (attention_implementation answers {arm!r} for attention_window="
            f"{sizes['sliding_window']} and a {PROBE_BUCKET_TOKENS}-token bucket). On that arm a "
            f"512-token step of Mistral-7B took 343 ms and cold set-up 522 s (PERF.md section 7, "
            f"PR 22): a run of this cell would not end inside its time limit. Nothing was "
            f"measured; the cell needs the window in the paged Pallas kernel.")


def serving_params(cfg, seed):
    """bf16 weights made on the device from the seed: ``mistral.serving_params``
    of a ONE-layer model, once per layer with the seed folded with the layer's
    index; embedding, final norm and head are the first call's."""
    import dataclasses

    import jax
    from deepspeed_tpu.models import llama
    one_layer = dataclasses.replace(cfg, num_hidden_layers=1)
    key = jax.random.PRNGKey(seed)
    params = None
    for i in range(cfg.num_hidden_layers):
        made = llama.init_params(one_layer, rng=jax.random.fold_in(key, i),
                                 param_dtype=cfg.dtype)[1]
        if params is None:
            params = made
        else:
            params["model"][f"layers_{i}"] = made["model"]["layers_0"]
    return params


def _forward_logits_padded(params, sizes, ids, rows=None, routing_gaps=None):
    """``references/mistral.py:forward_logits`` of ``ids`` padded with token 0 to
    the configuration's ``max_context``: the same rows, one compilation."""
    import numpy as np
    ids = np.asarray(ids)
    padded = np.zeros(max(ids.size, sizes["engine"]["state_manager"]["max_context"]), ids.dtype)
    padded[:ids.size] = ids
    return plain_reference.forward_logits(params, sizes, padded,
                                          rows=np.arange(ids.size) if rows is None else rows,
                                          routing_gaps=routing_gaps)


reference = SimpleNamespace(forward_logits=_forward_logits_padded)  # named for the harness


def program_config(sizes, **overrides):
    import jax
    message = refusal(sizes, jax.default_backend())
    if message:
        raise SystemExit(message)
    return mistral.program_config(sizes, **overrides)
