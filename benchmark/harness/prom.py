"""Prometheus text -> numbers, and window deltas of counters and histograms.
Means come from ``_sum``/``_count`` deltas: exact, no bucket interpolation."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> list[tuple[str, dict, float]]:
    """Every sample line as (name, labels, value)."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line.strip())
        if m is None:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")), value))
    return out


def total(samples: list[tuple[str, dict, float]], name: str, labels: dict | None = None) -> float | None:
    """Sum of every series of ``name`` whose labels include ``labels``; None
    if there is no such series (a reader then has nothing to read)."""
    want = labels or {}
    vals = [v for n, lab, v in samples
            if n == name and all(lab.get(k) == str(x) for k, x in want.items())]
    return sum(vals) if vals else None


def delta(before, after, name: str, labels: dict | None = None) -> float | None:
    b = total(after, name, labels)
    if b is None:
        return None
    return b - (total(before, name, labels) or 0.0)
