"""Shared summary statistics: percentiles for latency samples.

The ONE implementation behind the load generator's per-tenant
TTFT/TPOT/E2E p50/p90/p99 report (``distributed_llama_tpu/loadgen``), so
"p99" means the same estimator everywhere a number is published. Pure
stdlib, no numpy — loadgen's report path must stay importable in a
client-only process.

Estimator: linear interpolation between closest ranks (the numpy default,
``q/100 * (n-1)`` fractional index).
"""

from __future__ import annotations

import math
from typing import Iterable

# the percentiles every summary() reports — the serving-latency contract
# (docs/SERVING.md): median, common-case tail, SLO tail
SUMMARY_PERCENTILES = (50.0, 90.0, 99.0)


def percentile(values: Iterable[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks. Raises on an empty input — a missing sample set must
    surface as an error at the call site, not as a silent 0 that reads
    like a great latency."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile() of an empty sample set")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    idx = (q / 100.0) * (len(xs) - 1)
    lo = math.floor(idx)
    hi = math.ceil(idx)
    if lo == hi:
        return xs[lo]
    frac = idx - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


def summarize(values: Iterable[float], unit: str = "") -> dict:
    """p50/p90/p99 + count/mean/min/max of a sample set, as the plain dict
    shape the loadgen report embeds (``{"n": ..., "mean": ..., "p50": ...,
    "p90": ..., "p99": ..., "min": ..., "max": ...}``). Empty input returns
    ``{"n": 0}`` — an absent percentile is distinguishable from a zero one."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return {"n": 0}
    out: dict = {
        "n": len(xs),
        "mean": round(sum(xs) / len(xs), 3),
        "min": round(xs[0], 3),
        "max": round(xs[-1], 3),
    }
    for q in SUMMARY_PERCENTILES:
        out[f"p{int(q)}"] = round(percentile(xs, q), 3)
    if unit:
        out["unit"] = unit
    return out
