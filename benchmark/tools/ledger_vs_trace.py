#!/usr/bin/env python3
"""The scheduler's completion ledger held against the device, from one capture.

The ledger (``distributed_llama_tpu/telemetry/device_ledger.py``) credits the
device's time to programs from the instants at which they finished: the
earliest instant any thread saw (a chunk's consumer as its fetch returned, the
watcher thread's own look at a prompt piece). Each credited interval is a span
``device_interval`` on the watcher's thread, and in a capture (``POST
/debug/profile``) the span lies on the xplane as ``dllama/device_interval``,
on the clock of the device's own ``XLA Modules`` and ``XLA Ops``. The watcher
wakes a little after the stamp it credits, so the span's copy in the capture's
``host_spans.json`` carries the interval AS CREDITED (``credit_ts``,
``credit_dur``: what ``dllama_device_seconds_total`` moved by), on the
monotonic clock; the pairs of copies of one span tie that clock to the
xplane's (:func:`credited_on_trace_clock`). This tool lays the CREDITED
intervals beside the device over the span both cover (the spans themselves
where a capture's ring carries no credit: a tree before the credit was
recorded):

* per observed program (``decode_chunk``, ``prefill_piece``, ``spec_verify``):
  the credited seconds beside the seconds of the device modules they name, plus
  those of the programs nobody can wait for (a publish, a hit's copy, the
  carry's write ...) that ran since the observed program before: the device
  runs in dispatch order, so they finished inside this program's interval,
  and the ledger cannot tell them from it, and says so; and how much later
  than the credit the watcher's spans closed;
* idle: the time no credited interval covers beside ``1 - union of XLA Ops``;
* the spans' own sums in ``host_spans.json`` (whole spans, on the monotonic
  clock), where the capture wrote one.

    JAX_PLATFORMS=cpu python benchmark/tools/ledger_vs_trace.py <capture_dir>
    python benchmark/tools/ledger_vs_trace.py --workload <cell> --seed <n> [--seconds 51]

The second form runs the cell with ``--trace 2`` on the machine with the chip
and prints the table before the harness deletes the capture, and beside it the
window's own check: the five ``dllama_device_seconds_total`` series' deltas
over the measured window's two scrapes against the window's seconds. The
result line is printed last, as ``run.py`` prints it. An addition beside the
harness, not a reader: no per-layer metric comes from here.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import trace_reduce  # noqa: E402

SPAN = "dllama/device_interval"
HOST_PLANE = "/host:CPU"
# the device modules an observed program's spans name
PROGRAMS = {
    "decode_chunk": re.compile(r"decode_chunk"),
    "spec_verify": re.compile(r"spec_verify"),
    "prefill_piece": re.compile(r"prefill|slab_forward"),
}
IDLE_SERIES = ("no_work", "work_waiting")


def program_of(module: str) -> str | None:
    return next((p for p, rx in PROGRAMS.items() if rx.search(module)), None)


def credited_on_trace_clock(spans: list, ring: list) -> list | None:
    """The credited intervals ``[[program, start_ns, dur_ns]]`` on the xplane's
    clock, or None where the ring's copies carry none. ``spans``: the xplane's
    ``[program, start_ns, dur_ns]``; ``ring``: ``host_spans.json``'s
    ``device_interval`` events (``ts`` in monotonic microseconds). One span is
    in both (the capture's first or last perhaps in one only), so the offset
    between the clocks is the one under which most starts coincide."""
    ring = sorted((e for e in ring if "credit_ts" in e.get("args", {})), key=lambda e: e["ts"])
    starts = sorted(s for _, s, _ in spans)
    if not ring or not starts:
        return None

    def coincide(offset: float) -> int:
        n = 0
        for e in ring:
            at = e["ts"] * 1e3 + offset
            i = bisect.bisect_left(starts, at)
            n += any(abs(starts[j] - at) < 2e5 for j in (i - 1, i) if 0 <= j < len(starts))
        return n

    edge = 4  # a span missing from one copy is at the capture's edge
    offsets = [s - e["ts"] * 1e3 for s in starts[:edge] for e in ring[:edge]]
    offset = max(offsets, key=coincide)
    return [[str(e["args"].get("program")), int(e["args"]["credit_ts"] * 1e3 + offset),
             int(e["args"]["credit_dur"] * 1e3)] for e in ring]


def ring_events(trace_dir: str) -> list | None:
    """The ``device_interval`` events of the capture's ``host_spans.json``, or
    None where the capture wrote no such file."""
    path = os.path.join(trace_dir, "host_spans.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return [e for e in json.load(f)["traceEvents"] if e["name"] == "device_interval"]


def load(trace_dir: str) -> dict:
    """``{"spans": [[program, start_ns, dur_ns]], "credited": the same as credited or None,
    "modules": [[name, start, dur]], "ops": [[name, start, dur]]}`` of the newest xplane
    under ``trace_dir``: the ledger's spans off the host plane, the busiest device plane's
    lines, the credited intervals off ``host_spans.json``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    spans: list = []
    devices: dict = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == SPAN:
                        program = dict(e.stats).get("program")
                        spans.append([str(program), int(e.start_ns), int(e.duration_ns)])
        elif trace_reduce.DEVICE_PLANE.match(plane.name):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
                    lines[line.name] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                        for e in line.events]
    if not devices:
        raise ValueError("the capture holds no device plane")
    busiest = max(devices.values(), key=lambda ls: sum(len(evs) for evs in ls.values()))
    return {"spans": spans, "credited": credited_on_trace_clock(spans, ring_events(trace_dir) or []),
            "modules": busiest.get(trace_reduce.MODULES_LINE) or [],
            "ops": busiest.get(trace_reduce.OPS_LINE) or []}


def _clip(start: int, dur: int, w0: int, w1: int) -> int:
    return max(0, min(start + dur, w1) - max(start, w0))


def analyse(cap: dict) -> dict:
    """``load``'s lists -> the two sides over the span both cover. The ledger's
    side is the credited intervals where the capture has them, else the spans."""
    watched = sorted(cap["spans"], key=lambda s: s[1])
    credited = cap.get("credited")
    spans = sorted(credited, key=lambda s: s[1]) if credited else watched
    modules = [[trace_reduce._module_name(n), s, d] for n, s, d in cap["modules"]]
    ops = cap["ops"]
    if not spans:
        raise ValueError("the capture holds no dllama/device_interval span: a server "
                         "without the completion ledger, or telemetry off")
    if not modules:
        raise ValueError("the capture holds no device module")
    w0 = max(spans[0][1], min(s for _, s, _ in modules))
    w1 = min(max(s + d for _, s, d in spans), max(s + d for _, s, d in modules))
    if w1 <= w0:
        raise ValueError("the spans and the device's modules do not overlap")
    window = w1 - w0
    by: dict = {p: {"spans": 0, "ledger_ns": 0, "watched_ns": 0, "modules": 0, "device_ns": 0,
                    "beside_ns": 0, "beside": 0} for p in PROGRAMS}
    for program, s, d in spans:
        got = _clip(s, d, w0, w1)
        if got and program in by:
            by[program]["spans"] += 1
            by[program]["ledger_ns"] += got
    for program, s, d in watched:
        if program in by:
            by[program]["watched_ns"] += _clip(s, d, w0, w1)
    unobserved: dict = {}
    waiting = [0, 0]  # launches and ns of unobserved programs since the last observed one
    for name, s, d in sorted(modules, key=lambda m: m[1]):
        got = _clip(s, d, w0, w1)
        if not got:
            continue
        program = program_of(name)
        if program is not None:
            # the device runs in dispatch order: what nobody waited for since the last
            # observed program finished before this one did, inside ITS interval
            by[program]["modules"] += 1
            by[program]["device_ns"] += got
            by[program]["beside"] += waiting[0]
            by[program]["beside_ns"] += waiting[1]
            waiting = [0, 0]
            continue
        u = unobserved.setdefault(name, {"count": 0, "ns": 0})
        u["count"] += 1
        u["ns"] += got
        waiting[0] += 1
        waiting[1] += got
    covered = trace_reduce._union([(max(s, w0), min(s + d, w1)) for _, s, d in spans
                                   if _clip(s, d, w0, w1)])
    busy = trace_reduce._union([(max(s, w0), min(s + d, w1)) for _, s, d in (ops or modules)
                                if _clip(s, d, w0, w1)])
    programs = {}
    for p, v in by.items():
        if not (v["spans"] or v["modules"]):
            continue
        device = v["device_ns"] + v["beside_ns"]
        programs[p] = {
            "spans": v["spans"], "ledger_s": v["ledger_ns"] / 1e9,
            "watched_s": v["watched_ns"] / 1e9,
            "modules": v["modules"], "device_s": v["device_ns"] / 1e9,
            "unobserved_beside": v["beside"], "unobserved_beside_s": v["beside_ns"] / 1e9,
            "ledger_over_device_pct": 100.0 * (v["ledger_ns"] / device - 1.0) if device else None,
        }
    return {
        "window_s": window / 1e9,
        "ledger_side": "credited" if credited else "spans",
        "programs": programs,
        "unobserved": {k: {"count": v["count"], "seconds": v["ns"] / 1e9}
                       for k, v in sorted(unobserved.items(), key=lambda kv: -kv[1]["ns"])},
        "ledger_idle_pct": 100.0 * (1.0 - sum(b - a for a, b in covered) / window),
        "device_idle_pct": 100.0 * (1.0 - sum(b - a for a, b in busy) / window),
    }


def ring_sums(trace_dir: str) -> dict | None:
    """Seconds and count of the ``device_interval`` spans in the capture's
    ``host_spans.json`` by program (the ring's copy, whole spans, not
    clipped), or None where the capture wrote no such file."""
    events = ring_events(trace_dir)
    if events is None:
        return None
    out: dict = {}
    for e in events:
        p = out.setdefault(str(e["args"].get("program")), {"count": 0, "seconds": 0.0})
        p["count"] += 1
        p["seconds"] += e["dur"] / 1e6
    return out


def table(a: dict, ring: dict | None = None) -> str:
    side = ("the intervals as credited (host_spans.json, on the xplane's clock)"
            if a.get("ledger_side") == "credited" else
            "the watcher's spans (the capture's ring carries no credited interval)")
    rows = [f"{a['window_s']:.3f} s covered by both the ledger and the device's modules; the ledger's "
            f"side: {side}; idle: ledger {a['ledger_idle_pct']:.2f} % (no interval), device "
            f"{a['device_idle_pct']:.2f} % (1 - union of XLA Ops), "
            f"{a['ledger_idle_pct'] - a['device_idle_pct']:+.2f} points",
            "", "| program | intervals | ledger s | the watcher's spans s | modules | device s "
            "| + unobserved in front (launches, s) | ledger / (device + in front) |",
            "| --- | --- | --- | --- | --- | --- | --- | --- |"]
    for p, v in a["programs"].items():
        over = v["ledger_over_device_pct"]
        rows.append(f"| `{p}` | {v['spans']} | {v['ledger_s']:.4f} | {v['watched_s']:.4f} | {v['modules']} | "
                    f"{v['device_s']:.4f} | {v['unobserved_beside']}, {v['unobserved_beside_s']:.4f} | "
                    + ("-" if over is None else f"{over:+.2f} %") + " |")
    rows += ["", "| unobserved program (counted, never waited for) | launches | seconds | ms a launch |",
             "| --- | --- | --- | --- |"]
    for name, v in a["unobserved"].items():
        rows.append(f"| `{name}` | {v['count']} | {v['seconds']:.4f} | "
                    f"{1e3 * v['seconds'] / v['count']:.3f} |")
    if ring is not None:
        rows += ["", "host_spans.json (whole spans of the capture, not clipped): " + ", ".join(
            f"`{p}` {v['count']} spans {v['seconds']:.4f} s" for p, v in sorted(ring.items()))]
    return "\n".join(rows)


def window_sum(before: list, after: list, seconds: float) -> dict:
    """The five ``dllama_device_seconds_total`` series over the measured
    window's two scrapes, against the window's seconds."""
    from benchmark.harness import prom

    name = "dllama_device_seconds_total"
    series = {p: prom.delta(before, after, name, {"program": p})
              for p in (*PROGRAMS, *IDLE_SERIES)}
    total = prom.delta(before, after, name)
    counted = "dllama_device_programs_total"  # the observed programs and the unobservable ones
    launches = {lab["program"]: prom.delta(before, after, counted, {"program": lab["program"]})
                for n, lab, _ in after if n == counted}
    return {"series_s": series, "sum_s": total, "window_s": seconds,
            "sum_over_window_pct": None if total is None else 100.0 * (total / seconds - 1.0),
            "launches": launches}


def run_cell_and_tabulate(workload: str, seed: int, seconds: float) -> int:
    """``run.py --trace 2`` with one thing more: the capture's table and the
    window's sum, printed before the harness deletes the trace."""
    from benchmark.harness import cell as cell_mod

    trace_facts = cell_mod._trace_facts

    def facts_and_table(cell, cache, trace_dir, device, records, sent_prompt, marks, w0, w1,
                        *a, **kw):
        try:
            print(f"[ledger] {workload} seed {seed}: the measured window: "
                  f"{json.dumps(window_sum(marks['before'], marks['after'], w1 - w0))}", flush=True)
            print(f"[ledger] the capture\n{table(analyse(load(trace_dir)), ring_sums(trace_dir))}",
                  flush=True)
        except Exception as e:  # the run's own result must not depend on this tool
            print(f"[ledger] failed: {e!r}", file=sys.stderr, flush=True)
        return trace_facts(cell, cache, trace_dir, device, records, sent_prompt, marks, w0, w1,
                           *a, **kw)

    cell_mod._trace_facts = facts_and_table
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
    a = analyse(load(args.capture))
    print(json.dumps(a) if args.json else table(a, ring_sums(args.capture)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
