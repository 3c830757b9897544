"""The capture control: a short profiler trace of the LIVE process, with the
program's own spans on the same timeline.

Only the process that holds the chip can trace it, so the control lives in
the program (``POST /debug/profile`` on the API server, when ``--telemetry``)
and not in a tool beside it. ``start`` begins a JAX profiler session (what
``jax.profiler.start_trace`` begins) and switches the ring tracer's
recording on; ``stop`` — or ``max_seconds``, whichever comes first — ends
both and writes the interval's ring spans as ``host_spans.json`` (Chrome trace events, absolute ``time.monotonic`` µs)
beside the profiler's ``plugins/profile/<time>/*.xplane.pb``. Every
``tel.span`` of the interval is also IN the xplane, as a
``dllama/<name>`` annotation on its thread's host line, on the clock of the
device planes' ``XLA Ops`` (telemetry/tracer.py).

One capture at a time: a second ``start`` raises :class:`CaptureBusy` (409).
The profiler traces TraceMe annotations and device activity, not every
Python call (``python_tracer_level`` 0): a serving process makes thousands
of calls a second per request thread, and collecting 5 s of them has killed
a server at ``stop_trace`` (PERF.md, PR 22) — the spans are the host's
timeline. Hence also the small default ``max_seconds``.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time

DEFAULT_MAX_SECONDS = 2.0
HOST_SPANS_FILE = "host_spans.json"


class CaptureBusy(RuntimeError):
    """A capture is already running (one at a time)."""


class NoCapture(RuntimeError):
    """``stop`` without a running capture."""


def _profiler_session(options):
    """A profiler session of our own, begun at once. ``jax.profiler.start_trace``
    / ``stop_trace`` would do, but ``stop_trace`` also converts the trace to
    ``trace.json.gz``: 30 of the 45 s it took to stop 2 s of a 16-layer
    server's trace on the v5e (PERF.md, PR 23), which nothing here reads. The
    session's ``stop()`` gives the xplane's bytes and no more."""
    from jax._src.lib import _profiler

    return _profiler.ProfilerSession(options)


class Capture:
    """Owns the one profiler session of the process and the ring tracer's
    recording switch while it runs."""

    def __init__(self, tracer):
        self._tracer = tracer
        self._lock = threading.Lock()
        self._running: dict | None = None
        self._timer: threading.Timer | None = None
        self.last: dict | None = None  # what the last capture's stop returned

    def status(self) -> dict:
        with self._lock:
            return {"running": self._running is not None, "last": self.last}

    def start(self, directory: str, max_seconds: float | None = None) -> dict:
        """Begin a capture into ``directory``; it stops itself after
        ``max_seconds`` (default :data:`DEFAULT_MAX_SECONDS`) unless
        :meth:`stop` comes first."""
        import jax

        limit = DEFAULT_MAX_SECONDS if max_seconds is None else float(max_seconds)
        if not limit > 0:
            raise ValueError(f"max_seconds must be positive, got {max_seconds!r}")
        with self._lock:
            if self._running is not None:
                raise CaptureBusy("a capture is already running")
            os.makedirs(directory, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.devices()  # the backend before the session, or the device is not traced
            session = _profiler_session(options)
            # the pair that ties the xplane's wall clock (its "Task
            # Environment" plane gives profile_start_time in time_ns) to
            # the monotonic clock of spans, request traces and flight events
            clock = {"monotonic_ns": time.monotonic_ns(), "time_ns": time.time_ns()}
            self._running = {"dir": directory, "clock": clock, "session": session,
                             "ring_was_recording": self._tracer.recording}
            self._tracer.recording = True
            self._timer = threading.Timer(limit, self._auto_stop, args=(clock,))
            self._timer.daemon = True
            self._timer.start()
            return {"started": True, "dir": directory, "max_seconds": limit, "clock": clock}

    def _auto_stop(self, clock: dict) -> None:
        try:
            self._stop(clock, "max_seconds")
        except NoCapture:
            pass  # stop() came first

    def stop(self) -> dict:
        return self._stop(None, "stop")

    def _stop(self, only_if: dict | None, reason: str) -> dict:
        with self._lock:
            run = self._running
            if run is None or (only_if is not None and run["clock"] is not only_if):
                raise NoCapture("no capture is running")
            self._running = None
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            self._tracer.recording = run["ring_was_recording"]
            t = time.monotonic()
            xspace = run["session"].stop()
            stopped_ns = time.monotonic_ns()
            # where jax.profiler.stop_trace, TensorBoard and the benchmark's
            # reduction look for it
            profile = os.path.join(run["dir"], "plugins", "profile",
                                   time.strftime("%Y_%m_%d_%H_%M_%S"))
            os.makedirs(profile, exist_ok=True)
            with open(os.path.join(profile, socket.gethostname() + ".xplane.pb"), "wb") as f:
                f.write(xspace)
            since_us = run["clock"]["monotonic_ns"] / 1e3
            spans = self._tracer.chrome_trace(since_us=since_us, origin_us=0.0)
            spans["clock"] = run["clock"]
            path = os.path.join(run["dir"], HOST_SPANS_FILE)
            with open(path, "w") as f:
                json.dump(spans, f)
            if not run["ring_was_recording"]:
                self._tracer.clear()  # nothing can read the ring past this file
            self.last = {
                "stopped": True, "reason": reason, "dir": run["dir"],
                "host_spans": path, "spans": len(spans["traceEvents"]),
                "seconds": (stopped_ns - run["clock"]["monotonic_ns"]) / 1e9,
                "stop_trace_seconds": time.monotonic() - t,
                "clock": run["clock"],
            }
            return self.last
