"""From a ``jax.profiler`` trace to device busy/idle time, per-op and
per-module sums, and idle gaps. The arithmetic (``reduce``) works on plain
lists of ``[name, start_ns, duration_ns]`` so that the tests check it on a
trimmed recording; ``load`` is the thin reader of the ``.xplane.pb``.

Run as a program (``python -m benchmark.harness.trace_reduce <trace_dir>``,
with ``JAX_PLATFORMS=cpu``: reading a trace needs JAX's protobuf reader, not
the chip) it prints the reduction as one JSON object.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir: str, plane_regex: re.Pattern = DEVICE_PLANE) -> dict:
    """{plane: {line: [[name, start_ns, duration_ns], ...]}} for the device
    planes of the newest trace under ``trace_dir``, plus an inventory of
    every plane and line (names and event counts) under ``"_inventory"``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes: dict = {}
    inventory: dict = {}
    for plane in data.planes:
        keep = plane_regex.match(plane.name) is not None
        inv = inventory.setdefault(plane.name, {})
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events] if keep else None
            inv[line.name] = len(events) if keep else sum(1 for _ in line.events)
            if keep:
                planes.setdefault(plane.name, {})[line.name] = events
    planes["_inventory"] = inventory
    return planes


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _self_times(events: list) -> dict[str, int]:
    """Per-name self time on one line: an event's duration less the part its
    nested events cover (a ``while`` spans the ops of its body)."""
    out: dict[str, int] = {}
    stack: list[list] = []  # [name, end, self_ns]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0) + done[2]
        if stack:
            stack[-1][2] -= min(dur, max(0, stack[-1][1] - start))
        stack.append([name, end, dur])
    while stack:
        done = stack.pop()
        out[done[0]] = out.get(done[0], 0) + done[2]
    return out


def _op_name(name: str) -> str:
    """``%fusion.12 = (bf16[16,2048]{...}, ...) fusion(...)`` -> ``fusion
    bf16[16,2048]``: the trace prints an op as its whole HLO line; the opcode
    name without its instance number, and the first result shape, say which
    kind of op it is and group the per-layer copies of one op."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"(\.\d+)+$", "", head.lstrip("%"))
    shape = re.search(r"[a-z]+\d*\[[\d,]*\]", rest)
    return f"{base} {shape.group(0)}" if shape else base


def _module_name(name: str) -> str:
    """``jit_decode_chunk(123456)`` -> ``jit_decode_chunk``: the program id
    changes from run to run, the name does not."""
    return re.sub(r"\(\d+\)$", "", name)


def _by_kind(self_times: dict[str, int]) -> dict[str, int]:
    out: dict[str, int] = {}
    for name, ns in self_times.items():
        kind = _op_name(name)
        out[kind] = out.get(kind, 0) + ns
    return out


def reduce_plane(lines: dict) -> dict:
    """One device's lines -> span, busy time, per-op self time, per-module
    count and time, and the idle gaps labelled by the modules around them."""
    ops = lines.get(OPS_LINE) or []
    modules = sorted(lines.get(MODULES_LINE) or [], key=lambda e: e[1])
    every = [e for evs in lines.values() for e in evs]
    if not every:
        return {"window_ns": 0, "busy_ns": 0, "ops": {}, "launches": {}, "modules": {}, "gaps": {}}
    t0 = min(e[1] for e in every)
    t1 = max(e[1] + e[2] for e in every)
    # an op runs on the device; where a trace has no ops line, a module does
    busy = _union([(s, s + d) for _, s, d in (ops or modules)])
    mods: dict[str, dict] = {}
    for name, _, dur in modules:
        m = mods.setdefault(_module_name(name), {"count": 0, "ns": 0})
        m["count"] += 1
        m["ns"] += dur

    def around(t: int) -> str:
        before = after = "edge"
        for name, s, d in modules:
            if s + d <= t:
                before = _module_name(name)
            elif s >= t:
                after = _module_name(name)
                break
            else:
                return f"inside {_module_name(name)}"
        return f"{before} -> {after}"

    gaps: dict[str, int] = {}
    edges = [(t0, t0)] + busy + [(t1, t1)]
    for (_, a_end), (b_start, _) in zip(edges, edges[1:]):
        if b_start > a_end:
            label = around(a_end)
            gaps[label] = gaps.get(label, 0) + (b_start - a_end)
    return {
        "window_ns": t1 - t0,
        "busy_ns": sum(b - a for a, b in busy),
        "ops": _by_kind(_self_times(ops)),
        "launches": dict(collections.Counter(_op_name(e[0]) for e in ops)),
        "modules": mods,
        "gaps": gaps,
    }


def reduce(planes: dict, chips: int) -> dict:
    """All device planes -> the facts the per-layer readers and the result
    line use; busy time is averaged over the ``chips`` used."""
    per = {name: reduce_plane(lines) for name, lines in planes.items()
           if not name.startswith("_")}
    used = sorted(per, key=lambda n: per[n]["busy_ns"], reverse=True)[:chips]
    if not used or not any(per[n]["busy_ns"] for n in used):
        raise ValueError("the trace holds no device operation")
    window = max(per[n]["window_ns"] for n in used)
    busy = sum(per[n]["busy_ns"] for n in used) / len(used)
    first = per[used[0]]
    top_ops = sorted(first["ops"].items(), key=lambda kv: kv[1], reverse=True)[:10]
    top_gaps = sorted(first["gaps"].items(), key=lambda kv: kv[1], reverse=True)[:10]
    return {
        "window_s": window / 1e9,
        "busy_s": busy / 1e9,
        "idle_share": 100.0 * (1.0 - busy / window),
        "modules": {k: {"count": v["count"], "seconds": v["ns"] / 1e9}
                    for k, v in first["modules"].items()},
        "device_ops": [[k, v / 1e9] for k, v in top_ops],
        # every kind of op, for the readers that look for a kernel by name: its events on the
        # line (a kernel's launches) and its self time
        "ops": {k: {"count": first["launches"][k], "seconds": v / 1e9}
                for k, v in first["ops"].items()},
        "idle_gaps": [[k, v / 1e9] for k, v in top_gaps],
        "planes_used": used,
    }


def main(argv: list[str]) -> int:
    trace_dir, chips = argv[0], int(argv[1])
    planes = load(trace_dir)
    out = {"inventory": planes["_inventory"]}
    try:
        out.update(reduce(planes, chips))
    except ValueError as e:
        out["error"] = str(e)
    if len(argv) > 2:  # a trimmed copy of the events, for the tests' fixture
        keep = int(argv[3]) if len(argv) > 3 else 4000
        trimmed = {p: {ln: evs[:keep] for ln, evs in lines.items()}
                   for p, lines in planes.items() if not p.startswith("_")}
        with open(argv[2], "w") as f:
            json.dump(trimmed, f)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
