"""Solar-Open2's pattern at a toy width, for the CPU tests: two periods (8
layers, every fourth softmax), 4 heads of 16 in both kinds of layer, 16
routed experts of which the file holds 4 (8..11) and one shared expert; and
its cell in the miniature checkout of ``tiny_root``."""

from __future__ import annotations

import json
import os

import tiny_root

CONFIG = {
    "name": "tiny-solar", "family": "solar_open2", "model_type": "solar_open2",
    "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
                           "num_kv_heads": None},
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4, "head_dim": 16,
    "num_key_value_heads": 2, "vocab_size": 16384, "intermediate_size": 128,
    "moe_intermediate_size": 32, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 512, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": [0, 4], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 2, "reduced": ["n_routed_experts"],
    "reduced_from": {"n_routed_experts": 16}, "first_routed_expert": 8, "kda_low_rank_dim": 32,
    "tokenizer_vocab": 16384,
    # a top 2 of 16 at width 64 is decided by less than the Q80 rounding moves it at a tenth of
    # the positions: such a position is left out, and so is the verdict's floor of positions
    "check": {"why": "a toy width: 4 probes of 40 + 12 tokens cross a prefill chunk of 32; near-ties "
                     "of a top 2 of 16 are left out by the reference's routing gap",
              "probes": 4, "probe_prompt": 40, "probe_tokens": 12, "router_tie": 0.01,
              "min_compared_share": 0.1},
}
# the pattern at a width where a top 4 of 64 (8 held) is decided as the published top 8 of 320 is:
# the size at which the REAL configuration's check block is tried against lower precisions
MID = {**CONFIG, "name": "mid-solar", "hidden_size": 256, "num_attention_heads": 4, "head_dim": 64,
       "num_key_value_heads": 2, "moe_intermediate_size": 128, "intermediate_size": 256,
       "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 64, "num_heads": 4,
                              "num_kv_heads": None},
       "n_routed_experts": 8, "reduced_from": {"n_routed_experts": 64}, "first_routed_expert": 24,
       "num_experts_per_tok": 4, "kda_low_rank_dim": 64}
CELL = "tiny-solar.closed"
FLAGS = tiny_root.FLAGS + ["--prefill-chunk", "32"]


def lay(root: str, real: dict | None = None) -> None:
    """The toy configuration and its cell into the miniature checkout
    ``root`` (``tiny_root.build``), reporting what the other one-chip closed
    loop reports and what the real Solar cell reports beside that (``real``:
    the manifest that says so, this repository's ``BENCHMARK.json``)."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-solar.json"), "w") as f:
        json.dump(CONFIG, f)
    entry = {"name": CELL, "config": "tiny-solar", "traffic": "closed", "chips": 1, "why": "rehearsal"}
    with open(os.path.join(bench, "workloads", f"{CELL}.json"), "w") as f:
        json.dump({**entry, "flags": FLAGS}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append(entry)
    manifest["configs"].append({"name": "tiny-solar", "file": "benchmark/configs/tiny-solar.json",
                                "source": "none", "reduced": [], "why": "rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "tiny-moe.closed" in m.get("workloads", []):
                m["workloads"].append(CELL)
    if real is None:
        with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
            real = json.load(f)
    # the per-layer entries the real cell reports and the miniature does not hold yet (those whose
    # list names cells without a stand-in here): on the toy cell, whoever else joins their lists
    held = {m["name"] for m in manifest["per_layer"]}
    manifest["per_layer"] += [{**m, "workloads": [CELL]} for m in real["per_layer"]
                              if "solar-open2.batch_prompted" in m.get("workloads", [])
                              and m["name"] not in held]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
