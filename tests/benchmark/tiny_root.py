"""A miniature checkout for rehearsing ``run_cell`` on the CPU: the real
harness, reference, families, check defaults and layer-metric readers
(symlinked), with tiny configurations, short traffic and a ``BENCHMARK.json``
of their own."""

from __future__ import annotations

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = {"hidden_act": "silu", "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
        "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 32, "vocab_size": 16384,
        "max_position_embeddings": 512, "rms_norm_eps": 1e-05, "rope_theta": 1000000.0,
        "tokenizer_vocab": 16384}
CONFIGS = {
    "tiny-dense": {**TINY, "name": "tiny-dense", "family": "llama", "arch": "llama"},
    "tiny-moe": {**TINY, "name": "tiny-moe", "family": "llama", "arch": "mixtral",
                 "num_local_experts": 8, "num_experts_per_tok": 2},
}
TRAFFIC = {
    "open": {"loop": "open", "rate_rps": 3.0, "lead_in_s": 1, "template_seed": 1,
             "sessions": {"turns_mean": 2, "turns_max": 3, "backfill_s": 4,
                          "think_s": {"dist": "exponential", "mean": 1.0, "min": 0.5, "max": 2}},
             "system_prompts": {"pool": 2, "zipf_s": 1.1, "tokens": 96},
             "user_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
             "output_tokens": {"dist": "uniform", "min": 3, "max": 6},
             "context_cap": 400, "prompt_cap": 256, "drain_limit_s": 60, "warm_pool_overflow": True},
    "closed": {"loop": "closed", "callers": 2, "lead_in_s": 1, "block": 8,
               "user_tokens": {"dist": "uniform", "min": 4, "max": 16},
               "output_tokens": {"dist": "constant", "value": 4},
               "context_cap": 400, "drain_limit_s": 60},
    # document sessions (mistral7b.long_doc_qa in small): a caller asks each fresh document twice
    "docs": {"loop": "closed", "callers": 2, "lead_in_s": 1, "block": 8,
             "documents": {"tokens": {"dist": "uniform", "min": 96, "max": 160}, "asks": 2},
             "user_tokens": {"dist": "uniform", "min": 4, "max": 16},
             "output_tokens": {"dist": "constant", "value": 4},
             "context_cap": 400, "prompt_cap": 256, "drain_limit_s": 60, "warm_pool_overflow": True,
             "trace_lead_in_s": 1.5},
}
# the check block of the document cell's own file: one of its 8 probes has a prompt of 200 tokens,
# seven prefill chunks of 32 and three pages at this cell's flags
DOCS_CHECK = {"why": "a rehearsal of a cell whose context is long: the last probe crosses prefill chunks "
                     "and pages, and its positions are compared like the rest",
              "long_probes": 1, "long_probe_prompt": 200}
FLAGS = ["--dtype", "q40", "--parallel", "2", "--max-seq-len", "512", "--kv-pages", "24",
         "--telemetry", "--decode-chunk", "4"]


def build(root: str, device_kind: str = "cpu") -> str:
    """Lay the miniature checkout out under ``root``; return ``root``."""
    bench = os.path.join(root, "benchmark")
    os.makedirs(bench)
    os.symlink(os.path.join(REPO, "distributed_llama_tpu"), os.path.join(root, "distributed_llama_tpu"))
    os.symlink(os.path.join(REPO, "native"), os.path.join(root, "native"))
    for name in ("harness", "reference", "layer_metrics", "check.json", "__init__.py"):
        os.symlink(os.path.join(REPO, "benchmark", name), os.path.join(bench, name))
    # a directory of its own, so that a test can lay a further family beside the real ones
    os.makedirs(os.path.join(bench, "families"))
    for name in os.listdir(os.path.join(REPO, "benchmark", "families")):
        if name != "__pycache__":
            os.symlink(os.path.join(REPO, "benchmark", "families", name),
                       os.path.join(bench, "families", name))
    with open(os.path.join(bench, "peaks.json"), "w") as f:
        json.dump({"source": "none: a CPU rehearsal", device_kind: {"hbm_bytes_per_s": 1e11}}, f)
    cells = {
        "tiny.open": ("tiny-dense", "open", 1, FLAGS),
        "tiny-moe.closed": ("tiny-moe", "closed", 1, FLAGS),
        "tiny-tp4.closed": ("tiny-dense", "closed", 4, FLAGS + ["--tp", "4"]),
        "tiny-docs.closed": ("tiny-dense", "docs", 1, FLAGS + ["--prefill-chunk", "32"]),
    }
    checks = {"tiny-docs.closed": DOCS_CHECK}
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(bench, sub))
    for name, cfg in CONFIGS.items():
        with open(os.path.join(bench, "configs", f"{name}.json"), "w") as f:
            json.dump(cfg, f)
    for name, mix in TRAFFIC.items():
        with open(os.path.join(bench, "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
    workloads = []
    for name, (config, mix, chips, flags) in cells.items():
        entry = {"name": name, "config": config, "traffic": mix, "chips": chips, "why": "rehearsal"}
        workloads.append(entry)
        with open(os.path.join(bench, "workloads", f"{name}.json"), "w") as f:
            json.dump({**entry, "flags": flags, **({"check": checks[name]} if name in checks else {})}, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    stands_for = {"mistral7b.chat_shared": "tiny.open", "mixtral8x7b.batch_decode": "tiny-moe.closed",
                  "mistral7b.single_stream": "tiny-tp4.closed", "mistral7b.long_doc_qa": "tiny-docs.closed"}
    # the real metrics, on the miniature's cells: a cell with no stand-in here is left out of a
    # metric's list, and a metric of such cells alone is left out whole (a test that wants such
    # a cell in the miniature lays it in itself: test_bench_family.lay_toy_family, solar_tiny.lay)
    for group in ("end_to_end", "per_layer"):
        for m in real[group]:
            if "workloads" in m:
                m["workloads"] = [stands_for[w] for w in m["workloads"] if w in stands_for]
        real[group] = [m for m in real[group] if m.get("workloads", True)]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump({**real, "paths": ["benchmark"], "workloads": workloads,
                   "configs": [{"name": n, "file": f"benchmark/configs/{n}.json", "source": "none",
                                "reduced": [], "why": "rehearsal"} for n in CONFIGS]}, f)
    return root
