"""The completion ledger: when each program a scheduler dispatched finished,
so that the device's time divides by program over ANY window (ISSUE 41).

A profiler capture is two seconds; the server runs for days and a
benchmark's window for 51 s. The one thing only the scheduler can keep for
all of it is the instant each of its programs finished. The device runs one
scheduler's programs in dispatch order, so from those instants alone:

* at every dispatch the scheduler hands :meth:`DeviceLedger.dispatched` the
  program's name and its smallest output that is NOT donated onward (a
  chunk's token bundle, a prompt piece's held-expert sum or logits), under
  the lock it already holds: one clock read and one queue put;
* ONE watcher thread a ledger takes the entries in order and, with no lock
  held, learns when each finished WITHOUT parking in the runtime
  (:meth:`DeviceLedger._await`): a program whose output a consumer fetches
  anyway (a decode chunk, a verify step) is stamped by that consumer as its
  fetch returns, and the watcher sleeps until then; a prompt piece, which
  nobody fetches, is asked ``is_ready()`` once a millisecond. It reads
  ``time.monotonic()`` (the clock of every span), drops the handle and
  credits ``max(previous completion, dispatch) -> completion`` to the
  program and ``previous completion -> dispatch``, where positive, to idle;
* idle is ``work_waiting`` from the instant the scheduler had work in hand
  (a stream joined whose request needs another chunk, a prompt admitted and
  not yet wholly dispatched: :meth:`work_began` / :meth:`work_ended`, under
  the scheduler's lock) and ``no_work`` before it: a chip that waits for
  the host reads apart from a chip that waits for a request.

``dllama_device_seconds_total{state,program}`` therefore sums, over its five
series, to the wall time since the ledger was bound, within the program in
flight. Programs whose only outputs are donated onward (a publish, a hit's
copy, the carry's write, a snapshot, ``_sample_row``'s token that the
request itself fetches) cannot be waited for without holding what the next
program needs: they are COUNTED at dispatch (:meth:`counted`,
``dllama_device_programs_total``) and their device time falls into the next
observed interval (docs/OBSERVABILITY.md, "The completion ledger").

A thread that returns from a device wait needs the GIL back, and with 16-32
consumers delivering tokens that can take the interpreter's switch interval
(5 ms): so a completion is the EARLIEST instant any thread saw it
(:meth:`Entry.observed`: the fetching consumer's, ``next_token``'s wait on
a prompt piece). What lag is left moves time between neighbouring intervals
and never changes the sum.

Each credited interval is also a span ``device_interval`` on the watcher's
thread, open from the moment the watcher starts to wait for the entry until
its wait returns: during a capture it lies on the xplane beside ``XLA Ops``.
The watcher wakes a little after the stamp it credits, so the span's copy in
the tracer's ring (a capture's ``host_spans.json``) also carries the interval
AS CREDITED (``credit_ts``, ``credit_dur``: monotonic microseconds, what the
counters moved by), and ``benchmark/tools/ledger_vs_trace.py`` holds those
against the device.

With telemetry off :func:`bind` hands out :data:`NULL_LEDGER`: no thread, no
queue, no clock read.
"""

from __future__ import annotations

import queue
import threading
import time

OBSERVED = ("decode_chunk", "prefill_piece", "spec_verify")
IDLE = ("no_work", "work_waiting")
# launched and never waited for: every output is donated to the next program
COUNTED = (
    "publish", "restore", "window_tail", "spill_slice", "spill_reload",
    "carry_put", "sample_row", "snapshot",
)
# the watcher's wake while nothing is dispatched: idle is credited this often,
# so a scrape in a quiet minute still finds the series at the wall's time
IDLE_TICK_S = 0.25
# between two looks at a running program's handle. A prompt piece is seen
# finished at most a millisecond late. A program that a consumer fetches is
# stamped by the consumer, exactly: the watcher looks itself only in case
# nobody does (a chunk the watchdog dropped)
POLL_S = 0.001
FETCHED_POLL_S = 0.05
FETCHED = ("decode_chunk", "spec_verify")


class Entry:
    """One dispatched program on the ledger's queue."""

    __slots__ = ("program", "t_dispatch", "handle", "work_since", "trace", "attrs", "done_at",
                 "seen")

    def __init__(self, program, t_dispatch, handle, work_since, trace, attrs):
        self.program = program
        self.t_dispatch = t_dispatch
        self.handle = handle
        self.work_since = work_since
        self.trace = trace
        self.attrs = attrs
        self.done_at: float | None = None
        self.seen = threading.Event()

    def observed(self, t: float) -> None:
        """Another thread saw this program finished at ``t`` (it read the
        clock as its own device wait returned): the earliest instant wins,
        and the watcher need not look any longer."""
        if self.done_at is None or t < self.done_at:
            self.done_at = t
        self.seen.set()


class _NullEntry:
    __slots__ = ()

    def observed(self, t: float) -> None:
        pass


NULL_ENTRY = _NullEntry()


class _NullLedger:
    """Telemetry off: no thread, no queue, nothing appended."""

    __slots__ = ()
    enabled = False

    def dispatched(self, program, handle, trace=None, **attrs):
        return NULL_ENTRY

    def counted(self, program, n: int = 1) -> None:
        pass

    def piece_rows(self, real: int, pad: int) -> None:
        pass

    def work_began(self) -> None:
        pass

    def work_ended(self) -> None:
        pass

    def close(self) -> None:
        pass


NULL_LEDGER = _NullLedger()


class DeviceLedger:
    enabled = True

    def __init__(self, idle_tick_s: float = IDLE_TICK_S):
        from distributed_llama_tpu import telemetry

        self._tel = telemetry.DeviceLedgerInstruments()
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._idle_tick_s = idle_tick_s
        # the instant up to which every second has been credited to a series
        self._accounted = time.monotonic()
        # since when the scheduler has had work in hand; None: it has none
        self.work_since: float | None = None
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="dllama-device-ledger", daemon=True
        )
        self._thread.start()

    # -- the scheduler's side (its lock held; nothing here waits) --------

    def dispatched(self, program: str, handle, trace=None, **attrs) -> Entry:
        """``program`` was just enqueued on the device; ``handle`` is the
        output to wait for. ``attrs`` go on the interval's span, ``trace``
        (a request's TraceContext) gets a prompt piece's device interval."""
        self._tel.launches[program].inc()
        entry = Entry(program, time.monotonic(), handle, self.work_since, trace, attrs)
        self._queue.put(entry)
        return entry

    def counted(self, program: str, n: int = 1) -> None:
        """``n`` launches of a program that cannot be waited for."""
        self._tel.launches[program].inc(n)

    def piece_rows(self, real: int, pad: int) -> None:
        self._tel.piece_rows_real.inc(real)
        self._tel.piece_rows_pad.inc(pad)

    def work_began(self) -> None:
        if self.work_since is None:
            self.work_since = time.monotonic()

    def work_ended(self) -> None:
        self.work_since = None

    def close(self) -> None:
        self._closed = True
        self._queue.put(None)

    # -- the watcher ------------------------------------------------------

    def _run(self) -> None:
        span = self._tel.span
        while True:
            try:
                entry = self._queue.get(timeout=self._idle_tick_s)
            except queue.Empty:
                self._credit_idle(time.monotonic(), self.work_since)
                continue
            if entry is None:
                return
            with span("device_interval", program=entry.program, **entry.attrs) as sp:
                self._await(entry)
                now = time.monotonic()
                entry.handle = None  # at once: nothing of the program outlives it here
                start, done = self._credit(
                    entry, now if entry.done_at is None else min(now, entry.done_at)
                )
                # the ring's copy of the span says what was credited
                sp.args.update(credit_ts=start * 1e6, credit_dur=(done - start) * 1e6)

    def _await(self, entry: Entry) -> None:
        """Return once ``entry``'s program has finished, without ever parking
        in the runtime. The first form of this thread called
        ``block_until_ready()`` on every handle: on the chip one request in
        some 250 of the single-stream cell then waited 2.5-3.3 s in the fetch
        of its fused first token (and no request of the parent's did); the
        second asked ``is_ready()`` once a millisecond throughout, which cured
        that and cost the single stream 2.5 % of its pace (PERF.md sections 6
        and 7, PR 41). So: a program that a consumer fetches is stamped by the
        consumer (:meth:`Entry.observed` wakes this thread), and only a prompt
        piece is asked. ``is_ready()`` is what the scheduler itself asks of a
        queued prompt piece under its lock."""
        handle = entry.handle
        poll = FETCHED_POLL_S if entry.program in FETCHED else POLL_S
        try:
            while not (entry.seen.wait(poll) or handle.is_ready() or self._closed):
                pass
        except Exception:
            pass  # a failed program fails its own request; its time passed all the same

    def _credit_idle(self, until: float, work_since: float | None) -> None:
        acc = self._accounted
        if until <= acc:
            return
        split = until if work_since is None else min(max(work_since, acc), until)
        self._tel.idle["no_work"].inc(split - acc)
        self._tel.idle["work_waiting"].inc(until - split)
        self._accounted = until

    def _credit(self, entry: Entry, done: float) -> tuple[float, float]:
        """Credit the idle in front of ``entry``'s dispatch and its program's
        interval; returns that interval."""
        self._credit_idle(entry.t_dispatch, entry.work_since)
        start = self._accounted  # max(previous completion, this dispatch)
        done = max(done, start)
        self._tel.busy[entry.program].inc(done - start)
        self._accounted = done
        if entry.trace is not None:
            entry.trace.add_span(
                "prefill_chunk_device", start, done - start, **entry.attrs
            )
        return start, done


def bind(enabled: bool) -> DeviceLedger | _NullLedger:
    """The ledger a scheduler binds at construction."""
    return DeviceLedger() if enabled else NULL_LEDGER
