"""A throw-away benchmark root for the CPU rehearsals: a copy of ``benchmark/``
with tiny configurations, traffic and a ``BENCHMARK.json`` of its own. Nothing
in the repository's own benchmark is touched."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_MIXTRAL = {
    "family": "mixtral", "mode": "serve", "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_local_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "sliding_window": None, "vocab_size": 256,
    "engine": {"kv_block_size": 16,
               "state_manager": {"memory_config": {"mode": "allocate", "size": 64},
                                 "max_context": 64, "max_ragged_batch_size": 32,
                                 "max_ragged_sequence_count": 8},
               "expert_parallel": {"capacity_factor": 2.0}},
    "serving": {"decode_chunk": 4, "queue_capacity": 1024},
}
TINY_MISTRAL_SERVE = {
    "family": "mistral", "mode": "serve", "torch_dtype": "float32", "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "rms_norm_eps": 1e-5, "rope_theta": 1e4, "sliding_window": 64,
    "tie_word_embeddings": False, "vocab_size": 256,
    "engine": {"kv_block_size": 16,
               "state_manager": {"memory_config": {"mode": "allocate", "size": 64},
                                 "max_context": 64, "max_ragged_batch_size": 32,
                                 "max_ragged_sequence_count": 8}},
    "serving": {"decode_chunk": 4, "queue_capacity": 1024},
}
TINY_MISTRAL_TRAIN = {
    "family": "mistral", "mode": "train", "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "rms_norm_eps": 1e-5, "rope_theta": 1e4, "sliding_window": 64,
    "tie_word_embeddings": False, "vocab_size": 256,
    "program": {"use_flash_attention": False, "remat": False, "sliding_window": 0},
    "train": {"seq_len": 64, "micro_batch_per_chip": 2, "gradient_accumulation_steps": 1,
              "deepspeed": {"optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                            "zero_optimization": {"stage": 3,
                                                  "stage3_param_persistence_threshold": 0},
                            "bf16": {"enabled": True}}},
}
_LEN = {"prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5, "min": 4, "max": 40},
        "output": {"dist": "uniform", "min": 4, "max": 12}}
_TIMES = {"lead_in_s": 0.5, "drain_s": 5.0, "trace_start_s": 0.2, "trace_length_s": 0.5}
TRAFFIC = {
    "tiny-open": dict(_TIMES, kind="open_poisson", slo={"ttft_ms": 5000, "tpot_ms": 1000},
                      params=dict(_LEN, rate_per_s=15.0, temperature=0.7)),
    "tiny-closed": dict(_TIMES, kind="closed_clients",
                        params=dict(_LEN, clients=4, requests_per_client=50, temperature=0.0)),
    "tiny-packed": {"kind": "packed_documents", "trace_start_s": 0.2, "trace_length_s": 0.5,
                    "params": {"document": {"dist": "lognormal", "median": 20, "sigma": 1.0,
                                            "min": 2, "max": 200}, "eos_token_id": 2}},
}
CELLS = [("tiny-mixtral-open", "tiny-mixtral", "tiny-open", 1),
         ("tiny-mixtral-closed", "tiny-mixtral", "tiny-closed", 1),
         ("tiny-mistral-open", "tiny-mistral-serve", "tiny-open", 1),
         ("tiny-mistral-train", "tiny-mistral-train", "tiny-packed", 4)]
CONFIGS = {"tiny-mixtral": TINY_MIXTRAL, "tiny-mistral-serve": TINY_MISTRAL_SERVE,
           "tiny-mistral-train": TINY_MISTRAL_TRAIN}


def write_json(path, doc):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)


def make_root(tmp):
    """Copy ``benchmark/`` to ``tmp`` and give it the tiny cells. The metrics
    are the repository's own, each open to every cell whose mode has it."""
    root = str(tmp)
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    own = {w["name"]: w for w in bench["workloads"]}
    serve = {n for n, w in own.items() if "serve" in w["config"]}
    open_loop = {n for n in serve if "steady" in n}
    tiny_serve = [c[0] for c in CELLS if CONFIGS[c[1]]["mode"] == "serve"]
    tiny_open = [c[0] for c in CELLS if c[2] == "tiny-open"]
    tiny_train = [c[0] for c in CELLS if CONFIGS[c[1]]["mode"] == "train"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" not in m:
            continue
        names = set(m["workloads"])
        m["workloads"] = (tiny_open if names <= open_loop else
                          tiny_serve if names <= serve else tiny_train)
    bench["configs"] = [{"name": n, "source": "none: a test preset", "reduced": [],
                         "file": f"benchmark/configs/{n}.json", "why": "CPU rehearsal"}
                        for n in CONFIGS]
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": k, "why": "CPU rehearsal"}
                          for n, c, t, k in CELLS]
    write_json(os.path.join(root, "BENCHMARK.json"), bench)
    for name, doc in CONFIGS.items():
        write_json(os.path.join(root, "benchmark", "configs", f"{name}.json"), doc)
    for name, doc in TRAFFIC.items():
        write_json(os.path.join(root, "benchmark", "traffic", f"{name}.json"), doc)
    return root


def last_line(text):
    return json.loads(text.strip().splitlines()[-1])
