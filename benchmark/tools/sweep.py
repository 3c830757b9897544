#!/usr/bin/env python3
"""The one sweep that finds an open-loop mix's knee, run by hand on the chip
when the cell is defined (never by the driver):

    python3 benchmark/tools/sweep.py <cell> <seed> <step_seconds> <rate> [<rate> ...]

One server process, one load: after the cell's own warm-up it offers the
mix at each rate of the ladder for ``step_seconds`` (after a 5 s lead-in at
that rate), waits for the streams to end, and prints a table. The knee is the
highest rate with no refusal, no backlog growing through the step and at
least 90 % of requests inside the limits; the cell then runs at four fifths
of it, written into the traffic file with this table. The last line,
``KNEE <rate>``, is the highest rate of the ladder that was sustained: no
refusal, no failure, the streams drained within 10 s of the step's end, and
the mean TTFT of the step's second half at most 1.5 times that of its first.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TTFT_LIMIT_MS, TPOT_LIMIT_MS = 2000.0, 50.0


def main(argv: list[str]) -> int:
    t_process = time.monotonic()
    sys.path.insert(0, ROOT)
    from benchmark.harness import client, modelfile, stats, traffic
    from benchmark.harness.cell import Cell, Server, log

    cell = Cell(ROOT, argv[0])
    seed, step = int(argv[1]), float(argv[2])
    rates = [float(r) for r in argv[3:]]
    cache = os.path.join(cell.dir, ".cache")
    model_dir = os.path.join(cache, "model")
    shutil.rmtree(model_dir, ignore_errors=True)
    model, tokenizer = modelfile.write_artifacts(
        cell.config, seed, model_dir, cell.config["max_position_embeddings"], cell.dir)
    server = Server(cell, model, tokenizer, cache, "tpu")
    try:
        server.wait_ready(1000.0)
        log(f"device {json.dumps(server.control('/device'))}")
        rows = cell.flag("--parallel", 2)
        pool = cell.flag("--kv-pages", 0) * cell.flag("--kv-page-size", 64)
        warm = client.waves(server.port, traffic.warmup_waves(cell.mix, seed, rows, pool), 900.0)
        built = server.control("/compiles")
        log(f"warm-up: {len(warm)} requests, {sum(not r.ok for r in warm)} failed; "
            f"set-up {time.monotonic() - t_process:.0f} s; {built['count']} programs built, "
            f"{built['cache_hits']} from the cache, "
            f"{sum(e['seconds'] for e in built['events']):.0f} s in the compiler")
        table = []
        for i, rate in enumerate(rates):
            mix = dict(cell.mix, rate_rps=rate, lead_in_s=5)
            schedule = traffic.open_loop_schedule(mix, seed + i, step)
            built0 = server.control("/compiles")["count"]
            t0 = time.monotonic() + 0.2
            w0, w1 = t0 + 5.0, t0 + 5.0 + step
            records = client.open_loop(server.port, schedule, t0, w1 + 60.0, timeout=step + 120.0)
            win = [r for r in records if w0 <= r.due < w1]
            metrics, details = stats.end_to_end(
                win, w0, step, [t for r in records for t in r.deltas],
                ["ttft_p95_ms", "tpot_p95_ms", "stall_p95_ms", "out_tok_s"])
            done = [r for r in win if r.ok]
            met = [r for r in done if r.ttft * 1e3 <= TTFT_LIMIT_MS
                   and (r.tpot is None or r.tpot * 1e3 <= TPOT_LIMIT_MS)]
            half = w0 + step / 2
            first = [r.ttft * 1e3 for r in done if r.due < half]
            second = [r.ttft * 1e3 for r in done if r.due >= half]
            row = {
                "rate_rps": rate, "sent": len(win), "refused_429": sum(r.status == 429 for r in win),
                "failed": details["failed"], "attained_pct": 100.0 * len(met) / max(1, len(win)),
                "ttft_p50_ms": details["percentiles_ms"].get("ttft", {}).get("p50"), "ttft_p95_ms": metrics.get("ttft_p95_ms"),
                "tpot_p50_ms": details["percentiles_ms"].get("tpot", {}).get("p50"), "tpot_p95_ms": metrics.get("tpot_p95_ms"),
                "stall_p95_ms": metrics.get("stall_p95_ms"), "out_tok_s": metrics["out_tok_s"],
                "ttft_mean_first_half_ms": sum(first) / max(1, len(first)),
                "ttft_mean_second_half_ms": sum(second) / max(1, len(second)),
                "drain_s": max((r.deltas[-1] for r in records if r.deltas), default=w1) - w1,
                "programs_built": server.control("/compiles")["count"] - built0,
            }
            table.append(row)
            log(json.dumps({k: (round(v, 1) if isinstance(v, float) else v) for k, v in row.items()}))
        log("SWEEP " + json.dumps(table))
        sustained = [r["rate_rps"] for r in table
                     if r["refused_429"] == 0 and r["failed"] == 0 and r["drain_s"] <= 10.0
                     and r["ttft_mean_second_half_ms"] <= 1.5 * r["ttft_mean_first_half_ms"]]
        log(f"KNEE {max(sustained) if sustained else 0.0}")
        rc = server.stop()
        log(f"server exit code {rc}")
    finally:
        if server.proc.poll() is None:
            server.proc.kill()
            server.proc.wait()
        shutil.rmtree(model_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
