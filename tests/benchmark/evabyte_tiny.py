"""EvaByte's pattern at a toy width, for the CPU tests: 2 layers of EVA
attention (a window of 64 positions, a summary for every 8), 4 heads of 16
with a key head each, 8 prediction heads on the output matrix, the byte
vocabulary of 320; and its cell in the miniature checkout of ``tiny_root``."""

from __future__ import annotations

import json
import os

import tiny_root

CONFIG = {
    "name": "tiny-evabyte", "family": "evabyte", "model_type": "evabyte",
    "attention_bias": False, "attention_class": "eva", "chunk_size": 8, "fp32_ln": False,
    "fp32_logits": True, "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 64,
    "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 128,
    "lazy_init": True, "max_position_embeddings": 512, "max_seq_length": 512, "mixedp_attn": True,
    "norm_add_unit_offset": True, "num_attention_heads": 4, "num_chunks": None,
    "num_hidden_layers": 2, "num_key_value_heads": 4, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 64, "reduced": [], "reduced_from": {}, "tokenizer_vocab": 320,
}
# wider, for the precision control: the size at which the REAL cell's check block is tried
MID = {**CONFIG, "name": "mid-evabyte", "hidden_size": 256, "intermediate_size": 512,
       "num_attention_heads": 8, "num_key_value_heads": 8, "num_hidden_layers": 4}
CELL = "tiny-evabyte.docs"
FLAGS = tiny_root.FLAGS + ["--prefill-chunk", "32", "--kv-page-size", "16"]
REAL_CELL = "evabyte.doc_sessions"


def lay(root: str) -> None:
    """The toy configuration and its cell (the miniature's document sessions)
    into the miniature checkout ``root`` (``tiny_root.build``), reporting what
    the real cell reports: every per-layer entry of ``BENCHMARK.json`` that
    lists the real cell lists the toy one."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-evabyte.json"), "w") as f:
        json.dump(CONFIG, f)
    entry = {"name": CELL, "config": "tiny-evabyte", "traffic": "docs", "chips": 1, "why": "rehearsal"}
    with open(os.path.join(bench, "workloads", f"{CELL}.json"), "w") as f:
        json.dump({**entry, "flags": FLAGS, "check": {**tiny_root.DOCS_CHECK}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    manifest["workloads"].append(entry)
    manifest["configs"].append({"name": "tiny-evabyte", "file": "benchmark/configs/tiny-evabyte.json",
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
