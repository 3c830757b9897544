"""Granite-4.0-H's pattern at a toy width, for the CPU tests: one period of
ten layers with the softmax layer at index 5, 8 state-space heads of 32
values with 16 state values each (two rows of four heads in the state's
layout), 4 softmax heads of 16 whose scale is NOT 16 ** -0.5, the lineage's
multipliers as published, a tied head; and its cell in the miniature checkout
of ``tiny_root``."""

from __future__ import annotations

import json
import os

import tiny_root

CONFIG = {
    "name": "tiny-granite", "family": "granitemoehybrid", "model_type": "granitemoehybrid",
    "attention_bias": False, "attention_multiplier": 0.125, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 32, "mamba_d_state": 16, "mamba_expand": 4, "mamba_n_groups": 1,
    "mamba_n_heads": 8, "mamba_proj_bias": False, "max_position_embeddings": 512,
    "normalization_function": "rmsnorm", "num_attention_heads": 4, "num_experts_per_tok": 0,
    "num_hidden_layers": 10, "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 128,
    "tie_word_embeddings": True, "vocab_size": 16384, "reduced": [], "tokenizer_vocab": 16384,
    "check": {"why": "a toy width: 4 probes of 40 + 12 tokens cross a prefill chunk of 32, so state "
                     "and tail are handed from piece to piece before the answer is decoded",
              "probes": 4, "probe_prompt": 40, "probe_tokens": 12},
}
# the pattern at a width where the real configuration's check block is tried against lower
# precisions and planted faults: heads of the published size (64 values, 128 state values)
MID = {**CONFIG, "name": "mid-granite", "hidden_size": 256, "intermediate_size": 512,
       "shared_intermediate_size": 512, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
       "mamba_n_heads": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
       "attention_multiplier": 0.015625}
CELL = "tiny-granite.closed"
FLAGS = tiny_root.FLAGS + ["--prefill-chunk", "32"]


def lay(root: str) -> None:
    """The toy configuration and its cell into the miniature checkout
    ``root`` (``tiny_root.build``), reporting what the other one-chip closed
    loop reports and, beside that, the real cell's own per-layer entries."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-granite.json"), "w") as f:
        json.dump(CONFIG, f)
    entry = {"name": CELL, "config": "tiny-granite", "traffic": "closed", "chips": 1, "why": "rehearsal"}
    with open(os.path.join(bench, "workloads", f"{CELL}.json"), "w") as f:
        json.dump({**entry, "flags": FLAGS}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append(entry)
    manifest["configs"].append({"name": "tiny-granite", "file": "benchmark/configs/tiny-granite.json",
                                "source": "none", "reduced": [], "why": "rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "tiny-moe.closed" in m.get("workloads", []):
                m["workloads"].append(CELL)
    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    held = {m["name"] for m in manifest["per_layer"]}
    manifest["per_layer"] += [{**m, "workloads": [CELL]} for m in real["per_layer"]
                              if m.get("workloads") == ["granite-4.0-h-micro.batch_prompted"]
                              and m["name"] not in held]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
