"""A short process on the host's CPU, beside the server's warm-up: the plain
reference over the probes the server answered, teacher-forced with the
server's own tokens, so every answered position is compared against the
reference's logits for the same context. It leaves two cores to the server
and the load generator.

    JAX_PLATFORMS=cpu python -m benchmark.reference.probe_child <bench_dir> <config.json> \
        <model.m> <probes.json> <out.json>

The forward pass and the file's layout are those of the family that the
configuration's file names, found under ``<bench_dir>/families/``.

``probes.json``: [{"prompt": [ids], "answer": [ids]}]. ``out.json``: the seconds it took, and per probe
and answered position the reference's best token, how far the server's token
is below it, the top-1/top-2 margin, all as shares of max|logit|, and for a
sparse-expert model how close the position's nearest routing choice was.
"""

from __future__ import annotations

import json
import os
import sys
import time


def score(logits, answers: list[list[int]], router_gaps: list | None = None) -> list[list[dict]]:
    """Per probe and answered position: the served token, the reference's
    best, the served token's deficit and the top-1/top-2 margin, both as
    shares of max|logit|. ``logits`` [probes, positions, vocab] are the
    reference's for the contexts the served tokens were chosen in;
    ``router_gaps`` the layers' [probes, positions] routing gaps of a
    sparse-expert model, of which a position gets the smallest."""
    import numpy as np

    tightest = np.min(router_gaps, axis=0) if router_gaps else None
    out = []
    for i, answer in enumerate(answers):
        rows = []
        for j, tok in enumerate(answer):
            row = logits[i, j]
            order = np.argsort(row)[-2:]
            scale = float(np.abs(row).max())
            rows.append({
                "server": int(tok), "reference": int(order[1]),
                "deficit": float(row[order[1]] - row[tok]) / scale,
                "margin": float(row[order[1]] - row[order[0]]) / scale,
                "scale": scale, "top": float(row[order[1]]), "mean": float(row.mean()),
                "std": float(row.std()),
                "router_gap": None if tightest is None else float(tightest[i, j]),
            })
        out.append(rows)
    return out


def main(argv: list[str]) -> int:
    t0 = time.monotonic()
    bench_dir, config_path, model, probes_path, out_path = argv
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) > 4:
        os.sched_setaffinity(0, cores[2:])
    import jax
    import numpy as np

    if jax.devices()[0].platform != "cpu":
        print("reference: run with JAX_PLATFORMS=cpu, the chip is the server's", file=sys.stderr)
        return 3

    from benchmark import families
    from benchmark.reference.qfile import QFile

    with open(config_path) as f:
        family = families.load(json.load(f), "reference", bench_dir)
    with open(probes_path) as f:
        probes = json.load(f)
    qf = QFile(model, family)
    out: list = [None] * len(probes)
    # one pass for the probes of each prompt length (a cell may send long probes among the short)
    for n_prompt in sorted({len(p["prompt"]) for p in probes}):
        group = [i for i, p in enumerate(probes) if len(p["prompt"]) == n_prompt]
        n_ans = max(len(probes[i]["answer"]) for i in group)
        tokens = np.zeros((len(group), n_prompt + n_ans), np.int32)
        for row, i in enumerate(group):
            tokens[row, :n_prompt] = probes[i]["prompt"]
            tokens[row, n_prompt:n_prompt + len(probes[i]["answer"])] = probes[i]["answer"]
        # position n_prompt - 1 + j predicts answer token j
        positions = np.arange(n_prompt - 1, n_prompt - 1 + n_ans)
        gaps: list = []
        logits = family.forward(qf, tokens, positions, gaps)
        for i, rows in zip(group, score(logits, [probes[i]["answer"] for i in group], gaps)):
            out[i] = rows
    with open(out_path, "w") as f:
        json.dump({"seconds": time.monotonic() - t0, "probes": out}, f)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    sys.exit(main(sys.argv[1:]))
