"""Model FLOP/s utilisation: trained tokens per second x model FLOPs per token
(opcount.train_flops_per_token; recomputation not counted) over chips x peak."""

from benchmark import opcount


def read(run, params, env):
    if run["mode"] != "train" or env["peaks"] is None:
        return None
    m = run["model"]
    per_token = opcount.train_flops_per_token(run["n_params"], m["vocab_size"], m["hidden_size"],
                                              m["n_layers"], m["seq_len"])
    rate = run["steps"] * run["tokens_per_step"] / run["elapsed_s"]
    return 100.0 * rate * per_token / (run["chips"] * env["peaks"]["bf16_flops_per_s"])
