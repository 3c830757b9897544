#!/usr/bin/env python3
"""How widely the runs of one cell spread, against the bounds of ``BENCHMARK.json``; run by
hand when a cell's bounds are fitted or checked (never by the driver):

    python3 benchmark/tools/spread.py --workload <cell> [--requests] [--manifest <file>] <run.out> ...

Each file is the standard output of one run of the cell (``run.py``'s log, whose last line is
the result). For every end-to-end metric of the cell it prints the median over the runs, the
spread (the distance between the quartiles, ``statistics.quantiles(values, n=4)``, over the
median), the spread once the run farthest from the median is left out (as the driver reads
tightness), the bound, and whether the spread is at most half the bound: the rule a judged
pairing has to meet in a set of six runs, a seed each. ``setup_s`` is printed and not held to
it (it is judged by its median alone, and a checkout's first run compiles). With
``--requests`` the same is printed for the median AND the mean over each run's completed
requests of ``ttft``, ``tpot`` and ``stall``, recomputed from the log's per-request line, so
that a form can be chosen, or a recorded quantity watched, without another run; such a row
is held to the bound of the cell's end-to-end entry of that form, where it has one.

Exit code 1 when a judged pairing spreads past half its bound, 2 when a file holds no result.
Standard library only."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PER_REQUEST = "[window] per request, ms (ttft, tpot, stall): "
QUANTITIES = ("ttft", "tpot", "stall")


def spread(values: list[float]) -> float:
    """The distance between the quartiles over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def without_farthest(values: list[float]) -> list[float]:
    mid = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - mid))
    return values[:far] + values[far + 1:]


def read_run(path: str) -> tuple[dict, dict | None]:
    """(the result line's metrics by name, the per-request samples by quantity or None)."""
    with open(path) as f:
        lines = f.read().splitlines()
    result = next((json.loads(line) for line in reversed(lines) if line.startswith("{")), None)
    if result is None or "metrics" not in result:
        raise ValueError(f"{path}: no result line")
    samples = None
    for line in lines:
        if line.startswith(PER_REQUEST):
            rows = json.loads(line[len(PER_REQUEST):])
            samples = {q: [r[i] for r in rows if r[i] is not None] for i, q in enumerate(QUANTITIES)}
    return {k: v["value"] for k, v in result["metrics"].items()}, samples


def request_forms(samples: dict) -> dict:
    """Median and mean over one run's completed requests, under the names of their forms."""
    out = {}
    for q, v in samples.items():
        if v:
            out[f"{q}_p50_ms"] = statistics.median(v)
            out[f"{q}_mean_ms"] = statistics.fmean(v)
    return out


def table(entries: dict, runs: list[dict]) -> tuple[list[str], bool]:
    """(a line for every name of ``runs``, whether every judged pairing holds). ``runs`` maps
    names to values, one dict a run; ``entries`` the cell's end-to-end entries by those names."""
    lines, holds = [], True
    for name in sorted({k for r in runs for k in r}):
        values = [r[name] for r in runs if name in r]
        if len(values) < 3:
            lines.append(f"{name:24s} {len(values)} runs: too few for a spread")
            continue
        s, s_less = spread(values), spread(without_farthest(values))
        entry = entries.get(name)
        if entry is None:
            verdict = "no judged entry of this form in the cell"
        elif entry["name"] == "setup_s":
            verdict = f"bound {100 * entry['bound']:.0f} %: judged by its median alone"
        else:
            ok = s <= entry["bound"] / 2
            holds &= ok
            verdict = (f"bound {100 * entry['bound']:.0f} % ({entry['name']}): "
                       f"{'holds' if ok else 'OVER half the bound'}")
        lines.append(f"{name:24s} n {len(values)}  median {statistics.median(values):10.4f}  spread "
                     f"{100 * s:5.2f} %  without the farthest {100 * s_less:5.2f} %  {verdict}")
    return lines, holds


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--requests", action="store_true")
    ap.add_argument("files", nargs="+")
    args = ap.parse_args(argv)
    with open(args.manifest) as f:
        manifest = json.load(f)
    if not any(w["name"] == args.workload for w in manifest["workloads"]):
        print(f"no workload {args.workload!r} in {args.manifest}", file=sys.stderr)
        return 2
    try:
        runs = [read_run(path) for path in args.files]
    except ValueError as e:
        print(e, file=sys.stderr)
        return 2
    judged = [m for m in manifest["end_to_end"] if args.workload in m.get("workloads", [args.workload])]
    by_name = {m["name"]: m for m in judged}
    lines, holds = table(by_name, [{k: v for k, v in metrics.items() if k in by_name} for metrics, _ in runs])
    print(f"{args.workload}: {len(runs)} runs, the result lines")
    print("\n".join(lines))
    if args.requests:
        if any(samples is None for _, samples in runs):
            print("--requests: a log without its per-request line", file=sys.stderr)
            return 2
        # a form's bound is that of the cell's entry of that form, whatever follows its first dot
        more, held = table({m["name"].split(".")[0]: m for m in judged}, [request_forms(s) for _, s in runs])
        print("median and mean over each run's completed requests")
        print("\n".join(more))
        holds &= held
    return 0 if holds else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
