"""The completion ledger (ISSUE 41, telemetry/device_ledger.py): when each
program a scheduler dispatched finished, credited to
``dllama_device_seconds_total{state,program}`` by a watcher thread off the
lock. The ledger alone is driven with scripted handles (no JAX: a handle is
anything with ``is_ready``); the scheduler's side with the tiny
model on the CPU. Every test has a time limit of its own (``limited``)."""

import functools
import gc
import threading
import time
import weakref

import pytest

from distributed_llama_tpu import telemetry
from distributed_llama_tpu.engine import faults
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.telemetry import device_ledger
from distributed_llama_tpu.telemetry.trace import TraceContext

from tests.test_batch_decode import PROMPTS, build_engine
from tests.test_scheduler_ledger import LENGTHS, decode_all, enabled  # noqa: F401  (the fixture)

SERIES = (("busy", "decode_chunk"), ("busy", "prefill_piece"), ("busy", "spec_verify"),
          ("idle", "no_work"), ("idle", "work_waiting"))


def limited(seconds: float):
    """A test's own time limit: its body runs on a thread that has
    ``seconds`` to end (a hung watcher or wait fails the test, not the run)."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            out: dict = {}

            def body():
                try:
                    fn(*args, **kwargs)
                except BaseException as e:  # re-raised on the test's own thread
                    out["error"] = e

            t = threading.Thread(target=body, daemon=True)
            t.start()
            t.join(seconds)
            assert not t.is_alive(), f"{fn.__name__} did not end within {seconds} s"
            if "error" in out:
                raise out["error"]
        return run
    return wrap


def seconds() -> dict:
    c = telemetry.REGISTRY.get("dllama_device_seconds_total")
    return {program: c.labels(state=state, program=program).value for state, program in SERIES}


def launches() -> dict:
    c = telemetry.REGISTRY.get("dllama_device_programs_total")
    return {p: c.labels(program=p).value
            for p in device_ledger.OBSERVED + device_ledger.COUNTED}


class Handle:
    """A scripted program output: ready when the test says so."""

    def __init__(self, fail: bool = False):
        self._done = threading.Event()
        self.fail = fail

    def finish(self, entry=None):
        """The program ends; ``entry``: and its consumer's fetch returns, as a
        decode chunk's does (nobody fetches a prompt piece: the watcher asks)."""
        self._done.set()
        if entry is not None:
            entry.observed(time.monotonic())

    def is_ready(self):
        if self._done.is_set() and self.fail:
            raise RuntimeError("the program failed on the device")
        return self._done.is_set()


def settled(ledger, n: int = 0) -> None:
    """Wait until the watcher has taken every entry (and credited it)."""
    t = time.monotonic()
    while ledger._queue.qsize() > n and time.monotonic() - t < 10:
        time.sleep(0.005)
    time.sleep(0.03)


@limited(30)
def test_the_five_series_exist_at_zero_and_sum_to_the_wall_time(enabled):
    t0 = time.monotonic()
    ledger = device_ledger.DeviceLedger(idle_tick_s=0.02)
    assert set(seconds()) == {p for _, p in SERIES} and all(v == 0 for v in launches().values())
    longest = 0.15
    for program, length in (("prefill_piece", 0.05), ("decode_chunk", longest), ("decode_chunk", 0.1)):
        h = Handle()
        entry = ledger.dispatched(program, h, bucket=4, rows=2)
        time.sleep(length)
        h.finish(entry if program == "decode_chunk" else None)
        time.sleep(0.04)  # the host between two programs: idle
    settled(ledger)
    time.sleep(0.05)  # a tick: the idle since the last completion is credited too
    got, wall = seconds(), time.monotonic() - t0
    assert abs(sum(got.values()) - wall) < longest
    assert 0.24 <= got["decode_chunk"] < 0.6  # a loaded machine sleeps longer, never shorter
    assert 0.045 <= got["prefill_piece"] < 0.3
    assert got["spec_verify"] == 0
    assert launches()["decode_chunk"] == 2 and launches()["prefill_piece"] == 1
    ledger.close()


@limited(30)
def test_a_piece_behind_a_chunk_is_credited_from_the_chunks_completion(enabled):
    ledger = device_ledger.DeviceLedger(idle_tick_s=5)
    chunk, piece = Handle(), Handle()
    fetched = ledger.dispatched("decode_chunk", chunk)
    ledger.dispatched("prefill_piece", piece)  # queued on the device behind the chunk
    time.sleep(0.2)
    chunk.finish(fetched)
    time.sleep(0.06)
    piece.finish()
    settled(ledger)
    got = seconds()
    assert 0.19 <= got["decode_chunk"] < 0.5
    # from the chunk's completion, not from its own dispatch: the two together are what passed
    assert 0.05 <= got["prefill_piece"] < got["decode_chunk"]
    ledger.close()


@limited(30)
def test_the_earliest_instant_any_thread_saw_is_the_completion(enabled):
    """The watcher may get the GIL back late: a consumer's own stamp counts."""
    ledger = device_ledger.DeviceLedger(idle_tick_s=5)
    h = Handle()
    entry = ledger.dispatched("decode_chunk", h)
    time.sleep(0.05)
    entry.observed(entry.t_dispatch + 0.02)  # the fetching thread saw it at 20 ms
    entry.observed(entry.t_dispatch + 0.04)  # a later stamp does not move it
    h.finish()
    settled(ledger)
    assert seconds()["decode_chunk"] == pytest.approx(0.02, abs=1e-6)
    # the span closed when the watcher woke, later than the stamp; its copy in the ring carries
    # the interval as credited, which is what ledger_vs_trace.py holds against the device
    span = [e for e in telemetry.TRACER.events() if e.name == "device_interval"][-1]
    assert span.dur_us > 4e4
    assert span.args["credit_ts"] == pytest.approx(entry.t_dispatch * 1e6, abs=1)
    assert span.args["credit_dur"] == pytest.approx(2e4, abs=1)
    # the time after it is idle, not lost: the next entry's idle starts at the stamp
    idle = seconds()["no_work"] + seconds()["work_waiting"]
    nxt = ledger.dispatched("prefill_piece", Handle())
    nxt.handle.finish()
    settled(ledger)
    idle = seconds()["no_work"] + seconds()["work_waiting"] - idle
    assert idle == pytest.approx(nxt.t_dispatch - entry.t_dispatch - 0.02, abs=1e-6)
    ledger.close()


@limited(30)
def test_idle_is_no_work_until_the_scheduler_has_work_and_work_waiting_from_then(enabled):
    ledger = device_ledger.DeviceLedger(idle_tick_s=5)
    time.sleep(0.1)  # nothing in hand
    ledger.work_began()
    time.sleep(0.05)  # a request in hand, the host on its way to the dispatch
    h = Handle()
    ledger.dispatched("prefill_piece", h)
    h.finish()
    settled(ledger)
    got = seconds()
    assert 0.09 <= got["no_work"] < 0.4
    assert 0.045 <= got["work_waiting"] < 0.3
    ledger.work_ended()
    before = seconds()
    time.sleep(0.08)
    ledger._credit_idle(time.monotonic(), ledger.work_since)  # a tick
    assert 0.07 <= seconds()["no_work"] - before["no_work"] < 0.5
    assert seconds()["work_waiting"] == before["work_waiting"]
    ledger.close()


@limited(30)
def test_unobservable_programs_are_counted_and_never_waited_for(enabled):
    ledger = device_ledger.DeviceLedger(idle_tick_s=5)
    for program in device_ledger.COUNTED:
        ledger.counted(program)
    ledger.counted("spill_slice", 3)
    assert ledger._queue.qsize() == 0  # nothing for the watcher
    got = launches()
    assert got["spill_slice"] == 4 and all(got[p] == 1 for p in device_ledger.COUNTED if p != "spill_slice")
    assert all(got[p] == 0 for p in device_ledger.OBSERVED)
    ledger.piece_rows(65, 63)
    rows = telemetry.REGISTRY.get("dllama_prefill_piece_rows_total")
    assert (rows.labels(kind="real").value, rows.labels(kind="pad").value) == (65, 63)
    ledger.close()


@limited(30)
def test_the_handle_is_released_at_completion(enabled):
    ledger = device_ledger.DeviceLedger(idle_tick_s=5)
    h = Handle()
    ref = weakref.ref(h)
    entry = ledger.dispatched("decode_chunk", h)
    h.finish(entry)
    del h
    settled(ledger)
    gc.collect()
    assert entry.handle is None and ref() is None and ledger._queue.qsize() == 0
    ledger.close()


@limited(30)
def test_a_failed_program_leaves_the_watcher_running_and_the_sum_intact(enabled):
    t0 = time.monotonic()
    ledger = device_ledger.DeviceLedger(idle_tick_s=0.02)
    bad = Handle(fail=True)
    ledger.dispatched("decode_chunk", bad)  # nobody's fetch returns: the watcher looks itself
    time.sleep(0.05)
    bad.finish()
    good = Handle()
    ledger.dispatched("prefill_piece", good)
    time.sleep(0.05)
    good.finish()
    settled(ledger)
    time.sleep(0.05)
    assert ledger._thread.is_alive()
    got = seconds()
    assert got["decode_chunk"] > 0.03 and got["prefill_piece"] > 0.03
    assert abs(sum(got.values()) - (time.monotonic() - t0)) < 0.25
    ledger.close()
    ledger._thread.join(5)
    assert not ledger._thread.is_alive()


@limited(30)
def test_a_request_trace_gets_each_prompt_pieces_device_interval(enabled):
    ledger = device_ledger.DeviceLedger(idle_tick_s=5)
    ctx = TraceContext("req-1", "default")
    h = Handle()
    ledger.dispatched("prefill_piece", h, trace=ctx, bucket=128, rows=65, row=3, request="req-1")
    time.sleep(0.03)
    h.finish()
    settled(ledger)
    spans = [e for e in ctx.tree()["attempts"][0]["spans"] if e["name"] == "prefill_chunk_device"]
    assert len(spans) == 1 and spans[0]["args"]["bucket"] == 128 and spans[0]["dur_us"] > 2e4
    ring = [e for e in telemetry.TRACER.events() if e.name == "device_interval"]
    assert ring and ring[-1].args["program"] == "prefill_piece" and ring[-1].args["request"] == "req-1"
    ledger.close()


# ----------------------------------------------------------------------
# the scheduler's side, with the tiny model
# ----------------------------------------------------------------------


@limited(240)
def test_a_scheduler_run_sums_to_its_wall_time_and_counts_what_it_cannot_wait_for(tmp_path, enabled):
    engine = build_engine(tmp_path)
    t0 = time.monotonic()  # the ledger is bound as the scheduler's construction begins
    sched = BatchScheduler(engine, n_rows=4, chunk=4, prefix_cache=True, page_size=4)
    streams = [sched.new_stream() for _ in range(4)][:3]
    popped, errs = decode_all(sched, streams, LENGTHS)
    assert errs == [None] * 3 and sched._pending is None
    settled(sched._ledger)
    time.sleep(2 * device_ledger.IDLE_TICK_S)
    got, wall = seconds(), time.monotonic() - t0
    assert abs(sum(got.values()) - wall) < device_ledger.IDLE_TICK_S + 0.5
    assert got["decode_chunk"] > 0 and got["prefill_piece"] > 0 and got["work_waiting"] > 0
    n = launches()
    chunks = telemetry.REGISTRY.get("dllama_decode_chunk_rows").labels(kind="bucket").count
    assert n["decode_chunk"] == chunks and n["prefill_piece"] == 3
    assert n["carry_put"] == 3 and n["sample_row"] == 3 and n["publish"] >= 1
    rows = telemetry.REGISTRY.get("dllama_prefill_piece_rows_total")
    assert rows.labels(kind="real").value == sum(len(PROMPTS[i % len(PROMPTS)]) for i in range(3))
    assert rows.labels(kind="pad").value > 0
    # nothing of a finished program is kept: the queue is empty, the pieces' entries hold no handle
    assert sched._ledger._queue.qsize() == 0 and sched._last_piece_entry.handle is None
    # every row has left: the scheduler has no work, and the idle from here on says so
    assert sched._ledger.work_since is None
    before = seconds()
    time.sleep(3 * device_ledger.IDLE_TICK_S)
    after = seconds()
    assert after["no_work"] - before["no_work"] > device_ledger.IDLE_TICK_S
    assert after["work_waiting"] == before["work_waiting"]
    sched.close()


@limited(240)
def test_idle_with_a_stream_joined_is_work_waiting(tmp_path, enabled):
    engine = build_engine(tmp_path)
    sched = BatchScheduler(engine, n_rows=2, chunk=4)
    s = sched.new_stream()
    prompt = PROMPTS[0]
    first = s.prefill_device(prompt, 0.0, 0.9, 7)
    got, before = [], {}

    def on_token(prev, tok):
        got.append(tok)
        if len(got) == 5:  # a chunk's tokens handed on: the consumer dawdles, the chip waits for it
            settled(sched._ledger)
            before.update(seconds())
            time.sleep(0.3)
        return len(got) < 9

    s.stream_decode(first, on_token, 0.0, 0.9, seed=7, limit=s.pos + 12, first_prev=prompt[-1])
    settled(sched._ledger)  # the next chunk's dispatch closed the idle interval
    assert seconds()["work_waiting"] - before["work_waiting"] >= 0.25
    assert seconds()["no_work"] - before["no_work"] < 0.1
    sched.close()


@limited(240)
def test_the_gap_in_front_of_a_requests_last_chunk_is_work_waiting(tmp_path, enabled):
    """The chunk that carries every joined row to its stop ends the claim to
    work for the idle BEHIND its dispatch; the gap in front of it was waited
    with a row in hand."""
    engine = build_engine(tmp_path)
    sched = BatchScheduler(engine, n_rows=2, chunk=4)
    s = sched.new_stream()
    prompt = PROMPTS[0]
    first = s.prefill_device(prompt, 0.0, 0.9, 7)
    got, before = [], {}

    def on_token(prev, tok):
        got.append(tok)
        if len(got) == 5:  # the first chunk's last token: the second chunk is the request's last
            settled(sched._ledger)
            before.update(seconds())
            time.sleep(0.2)  # shorter than a tick: the whole gap is credited at the dispatch
        return len(got) < 9

    s.stream_decode(first, on_token, 0.0, 0.9, seed=7, limit=s.pos + 8, first_prev=prompt[-1])
    settled(sched._ledger)
    assert launches()["decode_chunk"] == 2 and sched._ledger.work_since is None
    assert seconds()["work_waiting"] - before["work_waiting"] >= 0.19
    assert seconds()["no_work"] - before["no_work"] < 0.05
    sched.close()


@limited(240)
def test_a_quarantined_dispatch_leaves_the_watcher_running(tmp_path, enabled):
    faults.install(faults.parse("batch.dispatch:kind=raise,after=1,count=8"))
    engine = build_engine(tmp_path)
    t0 = time.monotonic()
    sched = BatchScheduler(engine, n_rows=4, chunk=4, retries=1, retry_backoff_s=0.001)
    streams = [sched.new_stream() for _ in range(4)][:3]
    popped, errs = decode_all(sched, streams, LENGTHS)
    assert any(isinstance(e, faults.RowQuarantined) for e in errs)
    faults.clear()
    sched._faults = faults.active_plan()
    for s in streams:
        s.reset()
    popped, errs = decode_all(sched, streams, LENGTHS)  # the scheduler serves on
    assert errs == [None] * 3
    settled(sched._ledger)
    time.sleep(2 * device_ledger.IDLE_TICK_S)
    assert sched._ledger._thread.is_alive() and sched._ledger._queue.qsize() == 0
    assert abs(sum(seconds().values()) - (time.monotonic() - t0)) < device_ledger.IDLE_TICK_S + 0.5
    assert sched._ledger.work_since is None  # the quarantined rows left: no work is claimed for them
    sched.close()


@limited(240)
def test_kv_cache_occupancy_is_the_joined_rows_positions_over_the_slab(tmp_path, enabled):
    engine = build_engine(tmp_path)
    sched = BatchScheduler(engine, n_rows=4, chunk=4)
    streams = [sched.new_stream() for _ in range(4)][:3]
    seen = []
    note = sched._note_dispatched

    def spy(bucket, joined, steps):
        note(bucket, joined, steps)
        seen.append((sum(s.pos for s in joined), telemetry.REGISTRY.get("dllama_kv_cache_occupancy").value))

    sched._note_dispatched = spy
    popped, errs = decode_all(sched, streams, LENGTHS)
    assert errs == [None] * 3 and seen
    for positions, value in seen:
        assert value == pytest.approx(positions / (4 * engine.cfg.seq_len))
    assert max(v for _, v in seen) > max(len(p) for p in PROMPTS) / (4 * engine.cfg.seq_len)
    sched.close()


@limited(240)
def test_a_spec_verify_step_is_its_own_program(tmp_path, enabled):
    engine = build_engine(tmp_path)
    sched = BatchScheduler(engine, n_rows=4, chunk=4, spec_draft=3)
    streams = [sched.new_stream() for _ in range(4)][:3]
    popped, errs = decode_all(sched, streams, LENGTHS, spec_draft=3)
    assert errs == [None] * 3
    settled(sched._ledger)
    assert seconds()["spec_verify"] > 0 and seconds()["decode_chunk"] == 0
    assert launches()["spec_verify"] > 0 and launches()["carry_put"] == 0
    assert sched._ledger.work_since is None
    sched.close()


@limited(240)
def test_in_a_capture_the_credited_intervals_lie_on_the_xplanes_clock(tmp_path, enabled):
    """What ``ledger_vs_trace.py`` reads: the xplane's copy of each
    ``device_interval`` span and the ring's, which carries the credit."""
    import glob

    from jax.profiler import ProfileData

    from benchmark.tools import ledger_vs_trace
    from distributed_llama_tpu.telemetry.capture import Capture

    engine = build_engine(tmp_path)
    sched = BatchScheduler(engine, n_rows=4, chunk=4)
    streams = [sched.new_stream() for _ in range(4)][:3]
    decode_all(sched, streams, LENGTHS)  # every program built
    settled(sched._ledger)
    before = seconds()
    cap = Capture(telemetry.TRACER)
    cap.start(str(tmp_path / "cap"), 60)
    for s in streams:
        s.reset()
    decode_all(sched, streams, LENGTHS)
    settled(sched._ledger)
    cap.stop()
    after = seconds()
    (path,) = glob.glob(str(tmp_path / "cap" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    spans = [[str(dict(e.stats).get("program")), int(e.start_ns), int(e.duration_ns)]
             for plane in ProfileData.from_file(path).planes if plane.name == ledger_vs_trace.HOST_PLANE
             for line in plane.lines for e in line.events if e.name == ledger_vs_trace.SPAN]
    ring = ledger_vs_trace.ring_events(str(tmp_path / "cap"))
    credited = ledger_vs_trace.credited_on_trace_clock(spans, ring)
    assert len(spans) >= 5 and len(credited) == len(ring) >= len(spans) - 1
    # a credited interval ends inside its span (the watcher woke at or after the stamp) and
    # begins at most a moment before it (the previous completion, or this program's dispatch)
    for (program, start, dur), (c_program, c_start, c_dur) in zip(
            sorted(spans, key=lambda s: s[1])[-4:], sorted(credited, key=lambda c: c[1])[-4:]):
        assert program == c_program
        assert start - 50e6 < c_start < start + dur and c_start + c_dur <= start + dur + 2e5
    # and the credits ARE what the counters moved by
    for program in ("decode_chunk", "prefill_piece"):
        credit = sum(e["args"]["credit_dur"] for e in ring if e["args"]["program"] == program) / 1e6
        assert credit == pytest.approx(after[program] - before[program], abs=1e-6)
    sched.close()


NEW_READERS = {
    "window_idle_share": lambda v: 0 < v < 100,
    "window_host_idle_share": lambda v: 0 < v < 100,
    "window_decode_share": lambda v: 0 < v < 100,
    "window_prefill_share": lambda v: 0 < v < 100,
    "decode_chunk_device_ms_mean": lambda v: v > 0,
    "prefill_piece_device_ms_mean": lambda v: v > 0,
    "prefill_pad_row_share": lambda v: 0 < v < 100,
    "chunk_build_ms_mean": lambda v: v > 0,
}


def reader_directory(name: str) -> str:
    """The manifest's readers, or for the four held back from it (ISSUE 41's
    rule; tests/benchmark/test_bench_ledger.py says why) where they wait."""
    import os

    repo = os.path.dirname(os.path.dirname(__file__))
    held_back = os.path.join(repo, "tests", "benchmark", "data", "held_back_readers")
    if os.path.exists(os.path.join(held_back, f"{name}.json")):
        return held_back
    return os.path.join(repo, "benchmark", "layer_metrics")


@pytest.fixture(scope="module")
def scrapes(tmp_path_factory):
    """/metrics text before and after three streams through a scheduler: what
    the benchmark's readers parse."""
    from benchmark.harness import prom

    telemetry.reset()
    telemetry.enable()
    try:
        engine = build_engine(tmp_path_factory.mktemp("scrapes"))
        sched = BatchScheduler(engine, n_rows=4, chunk=4)
        before = prom.parse(telemetry.prometheus_text())
        t0 = time.monotonic()
        decode_all(sched, [sched.new_stream() for _ in range(4)][:3], LENGTHS)
        settled(sched._ledger)
        time.sleep(2 * device_ledger.IDLE_TICK_S)
        yield before, prom.parse(telemetry.prometheus_text()), time.monotonic() - t0
        sched.close()
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.mark.parametrize("reader", sorted(NEW_READERS))
def test_each_new_reader_file_reads_the_programs_own_metrics(scrapes, reader):
    from benchmark.harness import readers

    before, after, _ = scrapes
    value, unit = readers.read_metric(reader_directory(reader), reader, readers.Context(before, after, {}))
    assert value is not None, f"{reader}: the program exposes nothing this reader finds"
    assert NEW_READERS[reader](value), (reader, value, unit)


def test_the_four_window_shares_and_no_work_make_the_whole_window(scrapes):
    from benchmark.harness import prom, readers

    before, after, wall = scrapes
    ctx = readers.Context(before, after, {})
    read = lambda name: readers.read_metric(reader_directory(name), name, ctx)[0]  # noqa: E731
    # idle + decode + prefill = 100 % where no verify step ran
    assert read("window_idle_share") + read("window_decode_share") + read("window_prefill_share") == \
        pytest.approx(100.0)
    assert read("window_host_idle_share") <= read("window_idle_share")
    # all five series are in the first scrape (they exist from the bind): a delta can be read
    assert len([1 for n, _, _ in before if n == "dllama_device_seconds_total"]) == 5
    assert prom.delta(before, after, "dllama_device_seconds_total") == pytest.approx(wall, abs=0.5)


@limited(30)
def test_a_fetched_program_is_stamped_by_its_consumer_and_a_piece_is_asked(enabled):
    """The watcher never parks in the runtime: it sleeps until a chunk's
    consumer stamps it (and looks itself only every 50 ms, in case nobody
    does), and asks a prompt piece, which nobody fetches, once a millisecond."""
    ledger = device_ledger.DeviceLedger(idle_tick_s=5)

    class Counting(Handle):
        looks = 0

        def is_ready(self):
            self.looks += 1
            return super().is_ready()

    chunk = Counting()
    entry = ledger.dispatched("decode_chunk", chunk, bucket=2)
    time.sleep(0.12)
    chunk.finish(entry)
    settled(ledger)
    assert chunk.looks <= 3  # one look every 50 ms at most: the consumer's stamp ended the wait
    piece = Counting()
    ledger.dispatched("prefill_piece", piece, bucket=64)
    time.sleep(0.1)
    t = time.monotonic()
    piece.finish()
    settled(ledger)
    assert 20 <= piece.looks <= 110  # a loaded machine looks less often, never more
    assert ledger._accounted - t < 0.05  # seen within the poll, not at some later tick
    assert 0.1 <= seconds()["prefill_piece"] < 0.3
    ledger.close()
