"""The one span path: nested wall-time spans, written to the profiler's
timeline and (while recording) to a ring buffer with Chrome trace-event
JSON export.

A span does two things. It enters a ``jax.profiler.TraceAnnotation`` named
``dllama/<name>``, so during a profiler capture every engine and scheduler
span lies on the xplane's host lines, on the clock of the device planes'
``XLA Ops``; outside a capture the annotation is the profiler's own no-op
(one C++ check, ~0.4 µs) and its arguments are not formatted. And, while
the tracer is RECORDING, it appends one event to a fixed-size ring (old
spans fall off; a long-running process never grows). The ring records by
default — the CLI's ``generate``/``inference --trace-out`` export, the tests
— and the API server switches it off outside a capture
(``telemetry/capture.py``), where nothing could read it.

Events keep ABSOLUTE ``time.monotonic()`` instants, the clock the flight
recorder, the request traces and the benchmark's client use too
(``CLOCK_MONOTONIC``: one clock for every process of a machine). An origin
is subtracted only at export. The disabled path never reaches this module
(the telemetry facade hands out a shared no-op span instead).
"""

from __future__ import annotations

import collections
import json
import threading
import time

ANNOTATION_PREFIX = "dllama/"

_annotation = None  # jax.profiler.TraceAnnotation, imported at the first span


def _annotation_cls():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class SpanEvent:
    """One recorded span; ``ts_us`` is an absolute monotonic instant."""

    __slots__ = ("name", "ts_us", "dur_us", "tid", "depth", "args")

    def __init__(self, name, ts_us, dur_us, tid, depth, args):
        self.name = name
        self.ts_us = ts_us
        self.dur_us = dur_us
        self.tid = tid
        self.depth = depth
        self.args = args


class _Span:
    """Context manager: a profiler annotation, and one complete ("X") ring
    event on exit if the tracer was recording at entry."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_depth", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self):
        # TraceMe formats **args only while a profiler session is active
        self._ann = _annotation_cls()(ANNOTATION_PREFIX + self.name, **self.args)
        self._ann.__enter__()
        if self._tracer.recording:
            local = self._tracer._local
            self._depth = getattr(local, "depth", 0)
            local.depth = self._depth + 1
            self._t0 = time.monotonic()
        else:
            self._t0 = None
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            t1 = time.monotonic()
            self._tracer._local.depth = self._depth
            self._tracer._record(
                SpanEvent(
                    self.name,
                    self._t0 * 1e6,
                    (t1 - self._t0) * 1e6,
                    threading.get_ident(),
                    self._depth,
                    self.args,
                )
            )
        self._ann.__exit__(*exc)
        return False


class _NullSpan:
    """Shared no-op span for disabled telemetry: zero state, zero recording."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class SpanTracer:
    def __init__(self, capacity: int = 65536):
        # export origin only (chrome://tracing wants small numbers); events
        # themselves are absolute
        self._origin_us = time.monotonic() * 1e6
        self._lock = threading.Lock()
        self._local = threading.local()
        self._events: collections.deque[SpanEvent] = collections.deque(maxlen=capacity)
        # False: spans still annotate the profiler's timeline but pay no
        # clock read, no lock and no append (the server outside a capture)
        self.recording = True

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)

    def _record(self, ev: SpanEvent) -> None:
        with self._lock:
            self._events.append(ev)

    def events(self) -> list[SpanEvent]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def chrome_trace(self, since_us: float | None = None, origin_us: float | None = None) -> dict:
        """The buffered spans as a Chrome trace-event JSON object: those
        that started at or after ``since_us`` (absolute monotonic µs; all by
        default), their ``ts`` less ``origin_us`` (the tracer's creation by
        default; 0 keeps them absolute)."""
        origin = self._origin_us if origin_us is None else origin_us
        trace_events = [
            {
                "name": ev.name,
                "ph": "X",
                "ts": ev.ts_us - origin,
                "dur": ev.dur_us,
                "pid": 0,
                "tid": ev.tid,
                "args": {**ev.args, "depth": ev.depth},
            }
            for ev in self.events()
            if since_us is None or ev.ts_us >= since_us
        ]
        return {"traceEvents": trace_events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path
