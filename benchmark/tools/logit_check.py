#!/usr/bin/env python3
"""A one-off for the chip, recorded in PERF.md: each configuration at full
width and TWO layers through ``InferenceEngine.prefill`` (Q40 weights, the
kernels the server runs) against the benchmark's plain float32 reference on
the same file. Logits, not tokens: max |engine - reference| over max|logit|.

    python3 benchmark/tools/logit_check.py [config ...]
"""

from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np

    from benchmark import families
    from benchmark.harness import modelfile, traffic
    from benchmark.harness.cell import load_check
    from benchmark.reference.qfile import QFile
    from distributed_llama_tpu.engine import InferenceEngine

    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}))
    if dev.platform != "tpu":
        return 3
    names = argv or sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmark", "configs")))
    directory = os.path.join(ROOT, "benchmark", ".cache", "logit_check")
    worst_of_all = 0.0
    for name in names:
        with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
            config = dict(json.load(f), num_hidden_layers=2, name=f"{name}.2l")
        model, _ = modelfile.write_artifacts(config, 7, directory, config["max_position_embeddings"])
        reference, check = families.load(config, "reference"), load_check(config=config)
        probes = traffic.probe_requests(7, 2, check["probe_prompt"], 1)
        worst = 0.0
        for p in probes:
            ids = traffic.encode_chat(p.body["messages"])
            engine = InferenceEngine(model, dtype="q40", max_seq_len=2048)
            got = np.asarray(engine.prefill(ids), np.float32)
            del engine
            want = reference.forward(QFile(model, reference), np.asarray([ids], np.int32),
                                     np.asarray([len(ids) - 1]))[0, 0]
            err = float(np.abs(got - want).max() / np.abs(want).max())
            worst = max(worst, err)
            print(f"{name} (2 layers, full width, {len(ids)} tokens): max err {err:.3e} of max|logit|; "
                  f"argmax engine {int(got.argmax())} reference {int(want.argmax())}")
        print(f"{name}: worst {worst:.3e} (tolerance {check['logit_tol']})")
        worst_of_all = max(worst_of_all, worst / check["logit_tol"])
        shutil.rmtree(directory, ignore_errors=True)
    return 0 if worst_of_all <= 1.0 else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
