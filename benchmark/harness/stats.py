"""Arithmetic from request records to the end-to-end metrics. No clock is read
here: everything is a function of recorded instants, so the tests check it on
hand-made timestamps."""

from __future__ import annotations

import dataclasses
import math
import re


@dataclasses.dataclass
class Record:
    """What the client saw of one request. Instants are ``time.monotonic``
    seconds; ``deltas`` holds the arrival instant of every content delta."""

    index: int
    due: float
    sent: float = math.nan
    status: int | None = None
    deltas: list[float] = dataclasses.field(default_factory=list)
    finish: str | None = None  # finish_reason of the terminal chunk
    done: bool = False  # [DONE] seen
    error: str | None = None
    asked: int = 0
    prompt_tokens: int = 0
    text: str | None = None  # the answer of a request that was not streamed (the probes)

    @property
    def ok(self) -> bool:
        """Completed: 200, a terminal chunk and [DONE], no error event, and
        every asked token as a delta of its own (the benchmark's weights emit
        neither EOS nor a piece the server would drop or hold back, so one
        delta is one token)."""
        return (self.status == 200 and self.done and self.error is None
                and self.finish == "length" and len(self.deltas) == self.asked > 0)

    @property
    def ttft(self) -> float:
        return self.deltas[0] - self.due

    @property
    def tpot(self) -> float | None:
        n = len(self.deltas)
        return (self.deltas[-1] - self.deltas[0]) / (n - 1) if n > 1 else None

    @property
    def stall(self) -> float | None:
        d = self.deltas
        return max(b - a for a, b in zip(d, d[1:])) if len(d) > 1 else None


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of no samples")
    v = sorted(values)
    k = (len(v) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


_TAIL = re.compile(r"^(ttft|tpot|stall)_(?:p(\d{1,2})|(mean))_ms$")


def end_to_end(records: list[Record], t0: float, seconds: float, all_deltas: list[float],
               names: list[str]) -> tuple[dict, dict]:
    """(metrics, details) of one window ``[t0, t0 + seconds)``. ``records``
    are the requests DUE in the window; ``all_deltas`` the arrival instants of
    every delta of every request the run sent, lead-in included, because a
    token received in the window counts wherever its request was due.
    ``names`` are the metrics wanted: ``out_tok_s``, or over the window's
    completed requests a percentile, ``<ttft|tpot|stall>_p<NN>_ms``, or the
    arithmetic mean, ``<ttft|tpot|stall>_mean_ms``; a name this function does
    not know is left to the caller. What follows a name's
    first dot only tells entries of one quantity apart (``tpot_p50_ms.batch``
    is ``tpot_p50_ms`` under the bound of the cells that list it)."""
    done = [r for r in records if r.ok]
    samples = {
        "ttft": [r.ttft * 1e3 for r in done],
        "tpot": [r.tpot * 1e3 for r in done if r.tpot is not None],
        "stall": [r.stall * 1e3 for r in done if r.stall is not None],
    }
    in_window = sum(1 for t in all_deltas if t0 <= t < t0 + seconds)
    metrics = {}
    for name in names:
        quantity = name.split(".")[0]
        m = _TAIL.match(quantity)
        if m and samples[m.group(1)]:
            v = samples[m.group(1)]
            metrics[name] = sum(v) / len(v) if m.group(3) else percentile(v, int(m.group(2)))
        elif quantity == "out_tok_s":
            metrics[name] = in_window / seconds
    lags = [(r.sent - r.due) * 1e3 for r in records if not math.isnan(r.sent)]
    details = {
        "attempted": len(records), "completed": len(done),
        "failed": len(records) - len(done),
        "samples": {k: len(v) for k, v in samples.items()},
        "percentiles_ms": {k: {f"p{q}": round(percentile(v, q), 3) for q in (50, 75, 90, 95)}
                           for k, v in samples.items() if v},
        "send_lag_p50_ms": percentile(lags, 50) if lags else None,
        "send_lag_max_ms": max(lags, default=None),
        "tokens_in_window": in_window,
        "failures": [
            {"index": r.index, "status": r.status, "error": r.error, "finish": r.finish,
             "deltas": len(r.deltas), "asked": r.asked}
            for r in records if not r.ok
        ][:10],
    }
    return metrics, details
