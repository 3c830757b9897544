#!/usr/bin/env python3
"""Rows that SAMPLE through a cell's server on the chip (PR 46).

Every cell of the benchmark sends temperature 0, so no cell shows what a
batch with sampling rows pays: since PR 46 a decode step runs the sampler's
softmax and top-k only where some row of the bucket samples, and a batch in
which one does has to cost what it cost before. This script starts the
cell's server as the harness does (its flags, its configuration, weights
from ``--seed``) and drives it with the repo's own load generator
(``python -m distributed_llama_tpu.loadgen``) at the server's default
sampler settings, ``--callers`` requests in flight at a time (arrivals past
that are counted as dropped and never sent): once unmeasured, to build the
programs, then once between two scrapes of ``/metrics``.

    python3 tools/sampled_load.py [--workload granite-4.0-h-micro.batch_prompted] [--seed N]

from the root of a checkout whose ``BENCHMARK.json`` lists the cell (a copy
of another commit too: the harness and the program are taken from the
working directory). Last line of stdout: one JSON object with the window's
``decode_chunk_device_ms_mean`` (the completion ledger's seconds of
``decode_chunk`` over its launches), the mean joined rows of a chunk and,
where the program counts them, the chunks by the arm their sampler took.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.getcwd()
sys.path.insert(0, ROOT)

from benchmark.harness import cell as cell_mod  # noqa: E402
from benchmark.harness import modelfile, prom, readers  # noqa: E402


def loadgen(server, args, seed: int, requests: int, report: str) -> dict:
    cmd = [
        sys.executable, "-m", "distributed_llama_tpu.loadgen",
        "--url", f"http://127.0.0.1:{server.port}", "--seed", str(seed),
        "--requests", str(requests), "--arrival", "uniform", "--rate", str(args.rate),
        "--max-inflight", str(args.callers), "--timeout-s", "300",
        "--temperature", str(args.temperature), "--topp", str(args.topp),
        "--prefixes", "512", "--prefix-chars", str(args.prompt_chars),
        "--suffixes", "512", "--suffix-chars", "120",
        "--tenants", f"default:share=1,max_tokens={args.max_tokens}", "--out", report,
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)  # the chip is the server's
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True)
    if done.returncode != 0 or not os.path.exists(report):
        raise SystemExit(f"loadgen exited {done.returncode}:\n{done.stderr[-3000:]}")
    with open(report) as f:
        return json.load(f)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="granite-4.0-h-micro.batch_prompted")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--topp", type=float, default=0.9)
    p.add_argument("--callers", type=int, default=32)
    p.add_argument("--rate", type=float, default=20.0, help="arrivals a second, sent or dropped")
    p.add_argument("--seconds", type=float, default=60.0, help="the measured schedule's length")
    p.add_argument("--prompt-chars", type=int, default=600)
    p.add_argument("--max-tokens", type=int, default=256)
    p.add_argument("--platform", default="tpu", help="cpu for a rehearsal at a toy size")
    args = p.parse_args()

    cell = cell_mod.Cell(ROOT, args.workload)
    cache = os.path.join(cell.dir, ".cache")
    model_dir = os.path.join(cache, "model")
    shutil.rmtree(model_dir, ignore_errors=True)
    os.makedirs(cache, exist_ok=True)
    server = None
    try:
        model, tokenizer = modelfile.write_artifacts(
            cell.config, args.seed, model_dir, cell.config["max_position_embeddings"], cell.dir)
        t = time.monotonic()
        server = cell_mod.Server(cell, model, tokenizer, cache, args.platform)
        server.wait_ready(1000.0)
        device = server.control("/device")
        cell_mod.log(f"[setup] server ready in {time.monotonic() - t:.1f} s; device {json.dumps(device)}")
        t = time.monotonic()
        warm = loadgen(server, args, args.seed + 1, int(args.rate * 40), os.path.join(cache, "warm.json"))
        cell_mod.log(f"[setup] warm-up load took {time.monotonic() - t:.1f} s: {json.dumps(warm['aggregate']['counts'])}")
        built = server.control("/compiles")["count"]
        before = server.scrape()
        report = loadgen(server, args, args.seed, int(args.rate * args.seconds), os.path.join(cache, "load.json"))
        after = server.scrape()
        built = server.control("/compiles")["count"] - built

        ctx = readers.Context(before, after, {})
        layer_metrics = os.path.join(cell.dir, "layer_metrics")
        result = {
            "workload": args.workload, "seed": args.seed, "device": device,
            "temperature": args.temperature, "topp": args.topp, "callers": args.callers,
            # the benchmark's own readers, over this load's two scrapes
            **{name: readers.read_metric(layer_metrics, name, ctx)[0]
               for name in ("decode_chunk_device_ms_mean", "decode_active_rows_mean")},
            "decode_chunks": prom.delta(
                before, after, "dllama_device_programs_total", {"program": "decode_chunk"}),
            "sampler_chunks": {
                path: prom.delta(before, after, "dllama_decode_chunk_sampler_total", {"path": path})
                for path in ("greedy", "sampled")
            },
            "expert_rows": {
                f"{rows}.{phase}": prom.delta(before, after, "dllama_moe_expert_rows_total", {"rows": rows, "phase": phase})
                for phase in ("piece", "decode") for rows in ("launched", "computed", "chosen")
            },
            "programs_built_in_window": built,
            "loadgen": {k: report["aggregate"][k] for k in ("counts", "tokens_streamed", "tpot_ms")},
            "loadgen_wall_s": report["wall_s"],
        }
        print(json.dumps(result), flush=True)
        return 0
    finally:
        if server is not None:
            server.stop()
        shutil.rmtree(model_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
