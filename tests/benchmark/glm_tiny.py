"""GLM-4.7-Flash's pattern at a toy width, for the CPU tests: 4 layers of
latent attention (4 heads; a query latent of 32 and a key/value latent of 32;
a head 24 unrotated + 8 rotated values, values of 16: a cache row of 40), a
leading dense layer, then 8 routed experts top 2, ALL held, beside a shared
one, factor 1.8; and its cell in the miniature checkout of ``tiny_root``."""

from __future__ import annotations

import json
import os

import tiny_root

CONFIG = {
    "name": "tiny-glm", "family": "glm4_moe_lite", "model_type": "glm4_moe_lite",
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "max_position_embeddings": 512, "moe_intermediate_size": 32,
    "n_group": 1, "n_routed_experts": 8, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 2, "num_hidden_layers": 4,
    "num_key_value_heads": 4, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 1.8, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "vocab_size": 16384, "reduced": [], "tokenizer_vocab": 16384,
    # a top 2 of 8 at width 64 is decided by less than the Q80 rounding moves it at some
    # positions: such a position is left out, and so is the verdict's floor of positions
    "check": {"why": "a toy width: 4 probes of 40 + 12 tokens cross a prefill chunk of 32; "
                     "near-ties of a top 2 of 8 are left out by the reference's routing gap",
              "probes": 4, "probe_prompt": 40, "probe_tokens": 12, "router_tie": 0.01,
              "min_compared_share": 0.1},
}
# the pattern at a width where a top 4 of 64 is decided as the published one is: the size at
# which the REAL cell's check block is tried against lower precisions
MID = {**CONFIG, "name": "mid-glm", "hidden_size": 256, "intermediate_size": 512,
       "moe_intermediate_size": 128, "n_routed_experts": 64, "num_experts_per_tok": 4,
       "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 96, "kv_lora_rank": 64,
       "qk_nope_head_dim": 48, "qk_rope_head_dim": 16, "v_head_dim": 64}
CELL = "tiny-glm.docs"
# the cell's own block, as the real cell's: more than one long probe (they are sent first, scored
# by one pass of the reference, and judged with the short ones)
CELL_CHECK = {"why": "a rehearsal of a cell whose context is long: two of its four probes cross six "
                     "prefill chunks of 32 and three pages (the short ones 64 tokens, the least the "
                     "generator's template makes exactly)",
              "long_probes": 2, "long_probe_prompt": 200, "probe_prompt": 64}
FLAGS = tiny_root.FLAGS + ["--prefill-chunk", "32"]
REAL_CELL = "glm-4.7-flash.doc_sessions"


def lay(root: str) -> None:
    """The toy configuration and its cell (the miniature's document sessions)
    into the miniature checkout ``root`` (``tiny_root.build``), reporting what
    the real cell reports: every per-layer entry of this repository's
    ``BENCHMARK.json`` that lists the real cell lists the toy one."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-glm.json"), "w") as f:
        json.dump(CONFIG, f)
    entry = {"name": CELL, "config": "tiny-glm", "traffic": "docs", "chips": 1, "why": "rehearsal"}
    with open(os.path.join(bench, "workloads", f"{CELL}.json"), "w") as f:
        json.dump({**entry, "flags": FLAGS, "check": CELL_CHECK}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    manifest["workloads"].append(entry)
    manifest["configs"].append({"name": "tiny-glm", "file": "benchmark/configs/tiny-glm.json",
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
