"""K-EXAONE's pattern at a toy width, for the CPU tests: two periods (8
layers, every fourth full, the others a window of 16 positions), 4 heads of 16
over 2 K/V heads with q/k norm, a leading dense layer, then 16 routed experts
of which the file holds 4 (8..11) beside a shared one, factor 2.5; and its
cell in the miniature checkout of ``tiny_root``."""

from __future__ import annotations

import json
import os

import tiny_root

_KINDS = ["full_attention" if l % 4 == 3 else "sliding_attention" for l in range(8)]
CONFIG = {
    "name": "tiny-exaone", "family": "exaone_moe", "model_type": "exaone_moe",
    "first_k_dense_replace": 1, "head_dim": 16, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "layer_types": _KINDS, "max_position_embeddings": 512,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7, "moe_intermediate_size": 32,
    "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0], "n_group": 1,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 8, "num_key_value_heads": 2, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "sliding_window": 16,
    "sliding_window_pattern": "LLLG",
    "sliding_windows": [0 if k == "full_attention" else 16 for k in _KINDS],
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 16384,
    "reduced": ["num_experts"], "reduced_from": {"num_experts": 16}, "first_routed_expert": 8,
    "tokenizer_vocab": 16384,
    # a top 2 of 16 at width 64 is decided by less than the Q80 rounding moves it at a tenth of
    # the positions: such a position is left out, and so is the verdict's floor of positions
    "check": {"why": "a toy width: 4 probes of 40 + 12 tokens cross a prefill chunk of 32 and two "
                     "windows of 16; near-ties of a top 2 of 16 are left out by the reference's "
                     "routing gap",
              "probes": 4, "probe_prompt": 40, "probe_tokens": 12, "router_tie": 0.01,
              "min_compared_share": 0.1},
}
# the pattern at a width where a top 4 of 64 (8 held) is decided as the published top 8 of 128 is:
# the size at which the REAL cell's check block is tried against lower precisions
MID = {**CONFIG, "name": "mid-exaone", "hidden_size": 256, "head_dim": 64, "intermediate_size": 512,
       "moe_intermediate_size": 128, "num_experts": 8, "reduced_from": {"num_experts": 64},
       "first_routed_expert": 24, "num_experts_per_tok": 4}
CELL = "tiny-exaone.docs"
FLAGS = tiny_root.FLAGS + ["--prefill-chunk", "32"]
REAL_CELL = "k-exaone.doc_sessions"


def lay(root: str) -> None:
    """The toy configuration and its cell (the miniature's document sessions)
    into the miniature checkout ``root`` (``tiny_root.build``), reporting what
    the real cell reports: every per-layer entry of this repository's
    ``BENCHMARK.json`` that lists the real cell lists the toy one."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-exaone.json"), "w") as f:
        json.dump(CONFIG, f)
    entry = {"name": CELL, "config": "tiny-exaone", "traffic": "docs", "chips": 1, "why": "rehearsal"}
    with open(os.path.join(bench, "workloads", f"{CELL}.json"), "w") as f:
        json.dump({**entry, "flags": FLAGS}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    manifest["workloads"].append(entry)
    manifest["configs"].append({"name": "tiny-exaone", "file": "benchmark/configs/tiny-exaone.json",
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
