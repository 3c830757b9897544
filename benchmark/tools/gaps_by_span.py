#!/usr/bin/env python3
"""What the host was doing while the device sat idle, from one capture.

A capture taken by the program (``POST /debug/profile``, docs/OBSERVABILITY.md)
holds the device's ``XLA Ops`` and, on the same clock, the program's own spans
as ``dllama/<name>`` annotations on the host's thread lines. This tool lays the
two side by side:

* every device idle gap (the reduction's own: the time inside the traced span
  that no op covers) goes to the innermost ``dllama/*`` annotation open on the
  host at that instant. Several threads hold spans at once (each request's
  consumer loop, the one that pumps the scheduler), so a span that WORKS
  (builds, dispatches, delivers) wins over one that waits on the device
  (``*_fetch``), which wins over a consumer between its pops
  (``decode_stream``), which wins over a consumer parked on the scheduler
  (``sched_wait``); among equals, the one entered last;
* every launch of a small eager program (``PjitFunction(dynamic_slice)`` ... on
  the host: the ``jit_dynamic_slice`` ... modules of the device) goes to the
  innermost annotation open on ITS thread when it was issued.

    JAX_PLATFORMS=cpu python benchmark/tools/gaps_by_span.py <capture_dir>
    python benchmark/tools/gaps_by_span.py --workload <cell> --seed <n> [--seconds 51]

The second form runs the cell with ``--trace 2`` on the machine with the chip
and prints the table of its capture before the harness deletes it (the result
line is printed last, as ``run.py`` prints it). An addition beside the
harness, not a reader: no per-layer metric comes from here.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import trace_reduce  # noqa: E402

PREFIX = "dllama/"
HOST_PLANE = "/host:CPU"
PLANES = re.compile(r"^/device:TPU:\d+$|^/host:CPU$")
# the eager one-element programs the scheduler issues from Python between chunks
EAGER = ("dynamic_slice", "squeeze", "convert_element_type", "_slice_page", "concatenate")
NO_SPAN = "(no span open)"


def _rank(name: str) -> int:
    """0 works, 1 waits on the device, 2 consumes, 3 is parked."""
    if name == "sched_wait":
        return 3
    if name == "decode_stream":
        return 2
    return 1 if name.endswith("_fetch") else 0


def innermost(events: list) -> list[tuple[int, int, str, int]]:
    """One thread's ``[name, start, dur]`` spans -> disjoint ``(a, b, name,
    entered)`` pieces, each the innermost span open over ``[a, b)``."""
    out: list[tuple[int, int, str, int]] = []
    stack: list[tuple[str, int, int]] = []  # (name, start, end)
    t = 0

    def emit(until: int) -> None:
        nonlocal t
        if stack and until > t:
            out.append((t, until, stack[-1][0], stack[-1][1]))
        t = max(t, until)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= start:
            emit(stack[-1][2])
            stack.pop()
        emit(start)
        t = max(t, start)
        stack.append((name, start, start + dur))
    while stack:
        emit(stack[-1][2])
        stack.pop()
    return out


def winner_timeline(threads: dict[str, list]) -> list[tuple[int, int, str]]:
    """All threads' innermost pieces -> one disjoint timeline of the span that
    answers for each instant (module docstring's order)."""
    pieces = [p for evs in threads.values() for p in innermost(evs)]
    cuts = sorted({x for a, b, _, _ in pieces for x in (a, b)})
    open_at: list[list] = [[] for _ in cuts]
    for a, b, name, entered in pieces:
        for i in range(bisect.bisect_left(cuts, a), bisect.bisect_left(cuts, b)):
            open_at[i].append((_rank(name), -entered, name))
    out: list[tuple[int, int, str]] = []
    for i, held in enumerate(open_at[:-1]):
        if held:
            name = min(held)[2]
            if out and out[-1][2] == name and out[-1][1] == cuts[i]:
                out[-1] = (out[-1][0], cuts[i + 1], name)
            else:
                out.append((cuts[i], cuts[i + 1], name))
    return out


def idle_by_span(gaps: list[tuple[int, int]], timeline: list[tuple[int, int, str]]) -> dict:
    """Nanoseconds of ``gaps`` under each span of ``timeline``."""
    starts = [a for a, _, _ in timeline]
    out: dict[str, int] = {}
    for g0, g1 in gaps:
        covered = 0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(timeline) and timeline[i][0] < g1:
            a, b, name = timeline[i]
            part = min(b, g1) - max(a, g0)
            if part > 0:
                out[name] = out.get(name, 0) + part
                covered += part
            i += 1
        if g1 - g0 > covered:
            out[NO_SPAN] = out.get(NO_SPAN, 0) + (g1 - g0 - covered)
    return out


def launches_by_span(host: dict[str, list]) -> dict[str, dict[str, int]]:
    """{eager program: {issuing span: launches}}: a ``PjitFunction(<name>)``
    event belongs to the innermost annotation open on its own thread."""
    out: dict[str, dict[str, int]] = {}
    wanted = {f"PjitFunction({n})": f"jit_{n}" for n in EAGER}
    for events in host.values():
        pieces = innermost([[n[len(PREFIX):], s, d] for n, s, d in events if n.startswith(PREFIX)])
        starts = [a for a, _, _, _ in pieces]
        for name, start, _ in events:
            module = wanted.get(name)
            if module is None:
                continue
            i = bisect.bisect_right(starts, start) - 1
            span = pieces[i][2] if i >= 0 and start < pieces[i][1] else NO_SPAN
            by = out.setdefault(module, {})
            by[span] = by.get(span, 0) + 1
    return out


def analyse(planes: dict) -> dict:
    """``trace_reduce.load``'s planes (device and host) -> the two tables."""
    host = planes.get(HOST_PLANE) or {}
    devices = {n: ls for n, ls in planes.items() if n.startswith("/device:")}
    if not devices:
        raise ValueError("the capture holds no device plane")
    name = max(devices, key=lambda n: sum(len(evs) for evs in devices[n].values()))
    lines = {ln.split("#")[0]: evs for ln, evs in devices[name].items()}
    ops = lines.get(trace_reduce.OPS_LINE) or []
    every = [e for evs in lines.values() for e in evs]
    if not ops:
        raise ValueError("the capture holds no device operation")
    t0, t1 = min(e[1] for e in every), max(e[1] + e[2] for e in every)
    busy = trace_reduce._union([(s, s + d) for _, s, d in ops])
    edges = [(t0, t0)] + busy + [(t1, t1)]
    gaps = [(a_end, b0) for (_, a_end), (b0, _) in zip(edges, edges[1:]) if b0 > a_end]
    spans = {line: [[n[len(PREFIX):], s, d] for n, s, d in evs if n.startswith(PREFIX)]
             for line, evs in host.items()}
    spans = {line: evs for line, evs in spans.items() if evs}
    idle = idle_by_span(gaps, winner_timeline(spans))
    total = sum(b - a for a, b in gaps)
    modules: dict[str, list] = {}
    for n, _, d in lines.get(trace_reduce.MODULES_LINE) or []:
        m = modules.setdefault(trace_reduce._module_name(n), [0, 0])
        m[0] += 1
        m[1] += d
    durations: dict[str, list] = {}
    for evs in spans.values():
        for n, _, d in evs:
            c = durations.setdefault(n, [0, 0])
            c[0] += 1
            c[1] += d
    return {
        "device": name, "window_s": (t1 - t0) / 1e9, "idle_s": total / 1e9,
        "span_times": {k: {"count": c, "seconds": ns / 1e9} for k, (c, ns) in durations.items()},
        "host_spans": sum(len(e) for e in spans.values()), "host_threads": len(spans),
        "idle_by_span": sorted(([k, v / 1e9] for k, v in idle.items()), key=lambda kv: -kv[1]),
        "named_share": 100.0 * (1.0 - idle.get(NO_SPAN, 0) / total) if total else 100.0,
        "eager_launches": launches_by_span(host),
        "modules": {k: {"count": c, "seconds": ns / 1e9} for k, (c, ns) in modules.items()},
    }


def table(a: dict) -> str:
    rows = [f"device {a['device']}: {a['window_s']:.3f} s traced, {a['idle_s'] * 1e3:.1f} ms idle "
            f"({100 * a['idle_s'] / a['window_s']:.1f} %); {a['host_spans']} dllama/* spans on "
            f"{a['host_threads']} host threads; {a['named_share']:.1f} % of the idle time under a span",
            "", "| host span open during the gap | idle ms | share of idle |", "| --- | --- | --- |"]
    for name, s in a["idle_by_span"]:
        rows.append(f"| `{name}` | {s * 1e3:.2f} | {100 * s / a['idle_s']:.1f} % |")
    rows += ["", "| host span | count | total ms | mean ms |", "| --- | --- | --- | --- |"]
    for name, t in sorted(a["span_times"].items(), key=lambda kv: -kv[1]["seconds"]):
        rows.append(f"| `{name}` | {t['count']} | {t['seconds'] * 1e3:.1f} | "
                    f"{t['seconds'] * 1e3 / t['count']:.2f} |")
    rows += ["", "| eager program | on the device | issued from (launches) |", "| --- | --- | --- |"]
    eager = {f"jit_{n}" for n in EAGER}
    for module in sorted(set(a["eager_launches"]) | (set(a["modules"]) & eager)):
        dev = a["modules"].get(module)
        by = a["eager_launches"].get(module, {})
        rows.append(f"| `{module}` | " + (f"{dev['count']} x, {dev['seconds'] * 1e3:.2f} ms" if dev else "-")
                    + " | " + (", ".join(f"`{k}` {v}" for k, v in sorted(by.items(), key=lambda kv: -kv[1]))
                               or "-") + " |")
    return "\n".join(rows)


def load_planes(trace_dir: str) -> dict:
    """{plane: {"<line>#<i>": [[name, start_ns, duration_ns], ...]}} of the
    newest xplane under ``trace_dir``, device and host. Lines are kept one by
    one: Python's threads share one line NAME."""
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes: dict = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if PLANES.match(plane.name):
            planes[plane.name] = {
                f"{line.name}#{i}": [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
                for i, line in enumerate(plane.lines)}
    return planes


def of_capture(trace_dir: str) -> dict:
    return analyse(load_planes(trace_dir))


def ledger(before: list, after: list, decode_chunk: int) -> dict:
    """The scheduler's row-step ledger over the measured window, from the two
    scrapes at its edges: each fate, their sum, and what the sum must equal."""
    from benchmark.harness import prom

    def d(name, **labels):
        return prom.delta(before, after, name, labels or None) or 0.0

    fates = {f: d("dllama_decode_row_steps_total", fate=f)
             for f in ("masked", "orphaned", "quarantined", "unread", "consumed")}
    chunks = d("dllama_decode_chunk_rows_count", kind="bucket")
    bucket_rows = d("dllama_decode_chunk_rows_sum", kind="bucket")
    return {"fates": fates, "fates_sum": sum(fates.values()), "chunks": chunks,
            "bucket_rows_x_steps": bucket_rows * decode_chunk,
            "active_rows": d("dllama_decode_chunk_rows_sum", kind="active"),
            "tokens_generated": d("dllama_tokens_generated_total"),
            "tokens_streamed": d("dllama_tokens_streamed_total")}


def run_cell_and_tabulate(workload: str, seed: int, seconds: float) -> int:
    """``run.py --trace 2`` with one thing more: the capture's tables and the
    window's ledger, printed before the harness deletes the trace."""
    from benchmark.harness import cell as cell_mod

    trace_facts = cell_mod._trace_facts

    def facts_and_tables(cell, cache, trace_dir, device, records, sent_prompt, marks, *a, **kw):
        try:
            found = of_capture(trace_dir)
            found["ledger"] = ledger(marks["before"], marks["after"], int(device["decode_chunk"]))
            found["ledger"]["window_s"] = seconds
            decode = [v for k, v in found["modules"].items() if "decode_chunk" in k]
            found["ledger"]["traced_decode_chunks_per_s"] = (
                sum(m["count"] for m in decode) / found["window_s"])
            print(f"[gaps] {workload} seed {seed}\n{table(found)}\n[gaps] ledger of the measured "
                  f"window: {json.dumps(found['ledger'])}", flush=True)
        except Exception as e:  # the run's own result must not depend on this tool
            print(f"[gaps] failed: {e!r}", file=sys.stderr, flush=True)
        return trace_facts(cell, cache, trace_dir, device, records, sent_prompt, marks, *a, **kw)

    cell_mod._trace_facts = facts_and_tables
    try:
        result = cell_mod.run_cell(ROOT, workload, seed, seconds, 2, require_platform="tpu",
                                   t_process=time.monotonic())
    except cell_mod.BenchFailure as e:
        print(f"BENCHMARK FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("capture", nargs="?", help="a capture's directory")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--json", action="store_true", help="print the analysis as JSON")
    args = ap.parse_args()
    if args.workload:
        return run_cell_and_tabulate(args.workload, args.seed, args.seconds)
    if not args.capture:
        ap.error("give a capture's directory or --workload")
    a = of_capture(args.capture)
    print(json.dumps(a) if args.json else table(a))
    return 0


if __name__ == "__main__":
    sys.exit(main())
