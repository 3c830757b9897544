"""Granite-4.0-H-Small's pattern at a toy width WITH THE PUBLISHED HEAD SIZES,
for the CPU tests: one period of ten layers with the softmax layer at index 5,
8 state-space heads of 64 values with 128 state values each (the state's
layout as served, ``[4, 128, 128]`` a row), 2 softmax heads of 128 (each its own
key and value head, so that ``--tp 2`` reaches its refusal) whose scale
is 1/128 (no whole millionth: the header states it in billionths), in every
layer a router over 16 experts of which the file holds 4, 2 chosen a token (an
eighth, where the published 10 of 72 is a seventh: the bucket rule caps both at
half a step), each 32 wide, beside a shared expert of 64; the lineage's
multipliers as published, a tied head; and its cell in the miniature checkout
of ``tiny_root``."""

from __future__ import annotations

import json
import os

import tiny_root

REAL_CELL = "granite-4.0-h-small.batch_prompted"
CONFIG = {
    "name": "tiny-granite-moe", "family": "granitemoehybrid_moe", "model_type": "granitemoehybrid",
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 256, "intermediate_size": 32,
    "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 8, "mamba_proj_bias": False, "max_position_embeddings": 512,
    "normalization_function": "rmsnorm", "num_attention_heads": 2, "num_experts_per_tok": 2,
    "num_hidden_layers": 10, "num_key_value_heads": 2, "num_local_experts": 4,
    "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 64,
    "tie_word_embeddings": True, "vocab_size": 16384, "reduced": ["num_local_experts"],
    "reduced_from": {"num_local_experts": 16}, "first_routed_expert": 4, "tokenizer_vocab": 16384,
    # a top 2 of 16 at width 256 is decided by less than the Q80 rounding moves it at some
    # positions: such a position is left out, and so is the verdict's floor of positions
    "check": {"why": "a toy width: 4 probes of 40 + 12 tokens cross a prefill chunk of 32, so state "
                     "and tail are handed from piece to piece before the answer is decoded; near-ties "
                     "of a top 2 of 16 are left out by the reference's routing gap",
              "probes": 4, "probe_prompt": 40, "probe_tokens": 12, "min_compared_share": 0.1},
}
# the pattern at a width where a top 10 of 72 (18 held) is decided as published: the size at which
# the REAL cell's check block is tried against lower precisions
MID = {**{k: v for k, v in CONFIG.items() if k != "check"}, "name": "mid-granite-moe", "hidden_size": 512,
       "mamba_n_heads": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "intermediate_size": 96,
       "shared_intermediate_size": 192, "num_local_experts": 18, "num_experts_per_tok": 10,
       "reduced_from": {"num_local_experts": 72}, "first_routed_expert": 0}
CELL = "tiny-granite-moe.closed"
FLAGS = tiny_root.FLAGS + ["--prefill-chunk", "32"]


def lay(root: str) -> None:
    """The toy configuration and its cell into the miniature checkout
    ``root`` (``tiny_root.build``), reporting what the real cell reports:
    every per-layer entry of this repository's ``BENCHMARK.json`` that lists
    the real cell lists the toy one."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", f"{CONFIG['name']}.json"), "w") as f:
        json.dump(CONFIG, f)
    entry = {"name": CELL, "config": CONFIG["name"], "traffic": "closed", "chips": 1, "why": "rehearsal"}
    with open(os.path.join(bench, "workloads", f"{CELL}.json"), "w") as f:
        json.dump({**entry, "flags": FLAGS}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    manifest["workloads"].append(entry)
    manifest["configs"].append({"name": CONFIG["name"], "file": f"benchmark/configs/{CONFIG['name']}.json",
                                "source": "none", "reduced": [], "why": "rehearsal"})
    held = {m["name"]: m for m in manifest["per_layer"]}
    for m in real["per_layer"]:
        if REAL_CELL in m.get("workloads", []):
            if m["name"] in held:
                held[m["name"]]["workloads"].append(CELL)
            else:
                manifest["per_layer"].append({**m, "workloads": [CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
