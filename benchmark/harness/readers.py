"""Per-layer metric readers. A reader is a file
``benchmark/layer_metrics/<reader>.json`` that names one of the kinds below
and its arguments. A per-layer metric of ``BENCHMARK.json`` finds its reader
by its name up to the first dot: ``queue_ms_mean.open`` and
``queue_ms_mean.closed`` are one reader under two entries, because an entry's
``moves`` must be an end-to-end metric of every cell it is reported in. A
reader that finds nothing to read returns None and the metric is left out of
the line."""

from __future__ import annotations

import json
import os
import re

from benchmark.harness import prom


class Context:
    """What a traced run collected: ``before``/``after`` are parsed /metrics
    scrapes at the window's edges, ``facts`` the named values from the
    generator, the control thread, the trace reduction, the table of peaks
    and the counts of the configuration's family."""

    def __init__(self, before: list, after: list, facts: dict):
        self.before, self.after, self.facts = before, after, facts


def _read(spec: dict, ctx: Context) -> float | None:
    kind = spec["kind"]
    if kind == "counter_delta":
        value = prom.delta(ctx.before, ctx.after, spec["metric"], spec.get("labels"))
    elif kind == "histogram_mean":
        s = prom.delta(ctx.before, ctx.after, spec["metric"] + "_sum", spec.get("labels"))
        n = prom.delta(ctx.before, ctx.after, spec["metric"] + "_count", spec.get("labels"))
        value = s / n if s is not None and n else None
    elif kind == "fact":
        value = ctx.facts.get(spec["key"])
    elif kind == "hbm_share":
        # bytes the step must read x steps run / time the programs took / peak
        mods = [v for k, v in (ctx.facts.get("trace.modules") or {}).items()
                if re.search(spec["modules"], k)]
        busy = sum(m["seconds"] for m in mods)
        steps = sum(m["count"] for m in mods) * ctx.facts.get(spec["steps_per_call"], 0.0)
        value = (100.0 * ctx.facts[spec["bytes"]] * steps / busy / ctx.facts["peaks"]["hbm_bytes_per_s"]
                 if busy and steps else None)
    elif kind == "kernel_roofline":
        value = _kernel_roofline(spec, ctx.facts)
    elif kind == "ratio":
        num, den = _read(spec["num"], ctx), _read(spec["den"], ctx)
        value = num / den if num is not None and den else None
    else:
        raise ValueError(f"unknown reader kind {kind!r}")
    return None if value is None else value * spec.get("scale", 1.0)


def _kernel_roofline(spec: dict, facts: dict) -> float | None:
    """The share of their roofline that the device ops named like ``ops`` (a
    regex with a group ``role``) ran at: for each, the least time one launch
    could take (the larger of its bytes over the chip's bandwidth and its
    operations over the peak rate the reader names; both counted by the
    configuration's family from the role and the result shape in the op's
    name) times its launches, over the device seconds the trace gives it."""
    launch, peaks = facts["model.kernel_launch"], facts["peaks"]
    least = seconds = 0.0
    for name, op in (facts.get("trace.ops") or {}).items():
        found = re.search(spec["ops"], name)
        if found is None:
            continue
        shape = [int(n) for n in re.search(r"\[([\d,]*)\]", name).group(1).split(",")]
        nbytes, operations = launch(found.group("role"), shape)
        least += op["count"] * max(nbytes / peaks["hbm_bytes_per_s"], operations / peaks[spec["rate"]])
        seconds += op["seconds"]
    return 100.0 * least / seconds if seconds else None


def read_metric(directory: str, name: str, ctx: Context) -> tuple[float | None, str]:
    """(value or None, unit) of the per-layer metric ``name``."""
    with open(os.path.join(directory, f"{name.split('.')[0]}.json")) as f:
        meta = json.load(f)
    return _read(meta["reader"], ctx), meta["unit"]
