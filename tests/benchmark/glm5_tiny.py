"""GLM-5's pattern at a toy width, for the CPU tests: ``glm_tiny``'s latent
layers (4 heads; latents of 32; a head 24 unrotated + 8 rotated values, values
of 16: a cache row of 40) with an indexer in every layer (4 heads of 16, the 48
best positions a query: FEWER than the tests' prompts, so the selection cuts),
a leading dense layer, then 16 routed experts top 2 of which the file holds 4
(4..7) beside a shared one, factor 2.5; and its cell in the miniature checkout
of ``tiny_root``."""

from __future__ import annotations

import json
import os

import tiny_root

CONFIG = {
    "name": "tiny-glm5", "family": "glm_moe_dsa", "model_type": "glm_moe_dsa",
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu",
    "head_dim": 8, "hidden_size": 64, "index_head_dim": 16, "index_n_heads": 4, "index_topk": 48,
    "indexer_rope_interleave": True, "intermediate_size": 128, "kv_lora_rank": 32,
    "max_position_embeddings": 512, "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 2, "num_hidden_layers": 4,
    "num_key_value_heads": 4, "num_nextn_predict_layers": 1, "q_lora_rank": 32,
    "qk_head_dim": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-05,
    "rope_interleave": True, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 16, "vocab_size": 16384,
    "reduced": ["n_routed_experts"], "reduced_from": {"n_routed_experts": 16},
    "first_routed_expert": 4, "tokenizer_vocab": 16384,
    # a top 2 of 16 at width 64 is decided by less than the Q80 rounding moves it at some
    # positions: such a position is left out, and so is the verdict's floor of positions
    "check": {"why": "a toy width: 4 probes of 40 + 12 tokens cross a prefill chunk of 32; "
                     "near-ties of a top 2 of 16 are left out by the reference's routing gap",
              "probes": 4, "probe_prompt": 40, "probe_tokens": 12, "router_tie": 0.01,
              "min_compared_share": 0.1},
}
# the pattern at a width where a top 4 of 64 (8 held) is decided as the published top 8 of 256 is,
# with the PUBLISHED index_topk, which a long probe of 4128 tokens crosses: the size at which the
# REAL cell's check block is tried against lower precisions
MID = {**CONFIG, "name": "mid-glm5", "hidden_size": 256, "intermediate_size": 512,
       "moe_intermediate_size": 128, "n_routed_experts": 8, "reduced_from": {"n_routed_experts": 64},
       "first_routed_expert": 24, "num_experts_per_tok": 4, "q_lora_rank": 96, "kv_lora_rank": 64,
       "qk_nope_head_dim": 48, "qk_rope_head_dim": 16, "head_dim": 16, "qk_head_dim": 64, "v_head_dim": 64,
       "index_n_heads": 8, "index_head_dim": 32, "index_topk": 2048}
CELL = "tiny-glm5.docs"
# the cell's own block, as the real cell's: a long probe whose positions lie past index_topk
CELL_CHECK = {"why": "a rehearsal of a cell whose context is long: one of its four probes crosses six "
                     "prefill chunks of 32 and three pages and is answered past index_topk 48, where "
                     "the selection cuts (the short ones 64 tokens, the least the generator's template "
                     "makes exactly)",
              "long_probes": 1, "long_probe_prompt": 200, "probe_prompt": 64}
FLAGS = tiny_root.FLAGS + ["--prefill-chunk", "32"]
REAL_CELL = "glm-5.doc_sessions"


def lay(root: str) -> None:
    """The toy configuration and its cell (the miniature's document sessions)
    into the miniature checkout ``root`` (``tiny_root.build``), reporting what
    the real cell reports: every per-layer entry of this repository's
    ``BENCHMARK.json`` that lists the real cell lists the toy one."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-glm5.json"), "w") as f:
        json.dump(CONFIG, f)
    entry = {"name": CELL, "config": "tiny-glm5", "traffic": "docs", "chips": 1, "why": "rehearsal"}
    with open(os.path.join(bench, "workloads", f"{CELL}.json"), "w") as f:
        json.dump({**entry, "flags": FLAGS, "check": CELL_CHECK}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    manifest["workloads"].append(entry)
    manifest["configs"].append({"name": "tiny-glm5", "file": "benchmark/configs/tiny-glm5.json",
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
