#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

Runs one cell on the machine it is started on, which must hold the TPU chips
the cell asks for, and prints the result object as the last line of stdout.
Any failure to produce a result exits non-zero and prints no result line.
``--trace 0`` measures; ``--trace 1`` measures with 2 s of profiler in the
middle of its window and prints the per-layer metrics; ``--trace 2`` is a
``--trace 0`` run followed, in the same process, by a short traced phase of
the same traffic, and prints both kinds of metric (harness/cell.py).
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from benchmark.harness.cell import BenchFailure, run_cell

    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds, args.trace,
                          require_platform="tpu", t_process=T_PROCESS)
    except BenchFailure as e:
        print(f"BENCHMARK FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
