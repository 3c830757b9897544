"""The batched scheduler's ledger (ISSUE 23): every row-step a decode chunk
computed ends under exactly one fate, the server counts the tokens it hands
to clients, and a chunk that takes seconds leaves a flight event saying on
which side of the fetch the time went. CPU, tiny model."""

import json
import threading
import time

import pytest

from distributed_llama_tpu import telemetry
from distributed_llama_tpu.engine import faults
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.telemetry import flight

from tests.test_batch_decode import PROMPTS, build_engine

FATES = ("masked", "orphaned", "quarantined", "unread", "consumed")
LENGTHS = (3, 9, 14)  # unequal: rows leave at different chunks, so some chunks run ahead


@pytest.fixture
def enabled():
    """Telemetry on before the engine binds its instruments, clean afterwards."""
    telemetry.reset()
    telemetry.enable()
    faults.clear()
    flight.RECORDER.clear()
    yield
    faults.clear()
    flight.RECORDER.clear()
    telemetry.disable()
    telemetry.reset()


def fates() -> dict:
    c = telemetry.REGISTRY.get("dllama_decode_row_steps_total")
    return {f: c.labels(fate=f).value for f in FATES}


def rows(kind: str):
    return telemetry.REGISTRY.get("dllama_decode_chunk_rows").labels(kind=kind)


def decode_all(sched, streams, lengths, spec_draft=0):
    """Every stream requests at once; returns (tokens popped from the
    scheduler per stream, error per stream)."""
    popped = [0] * len(streams)
    errs = [None] * len(streams)
    next_token = sched.next_token

    def counting(stream):
        tok = next_token(stream)
        popped[streams.index(stream)] += 1
        return tok

    sched.next_token = counting

    def one(i):
        s, prompt = streams[i], PROMPTS[i % len(PROMPTS)]
        try:
            first = s.prefill_device(prompt, 0.0, 0.9, 11 + i)
            got = []

            def on_token(prev, tok):
                got.append(tok)
                return len(got) < lengths[i]

            s.stream_decode(first, on_token, 0.0, 0.9, seed=11 + i, limit=s.pos + lengths[i],
                            first_prev=prompt[-1], spec_draft=spec_draft, prompt_tokens=prompt)
        except Exception as e:
            errs[i] = e

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(streams))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not any(t.is_alive() for t in threads)
    return popped, errs


@pytest.mark.parametrize("n_rows", [3, 4])
def test_row_step_fates_sum_to_the_work_the_chunks_computed(tmp_path, enabled, n_rows):
    engine = build_engine(tmp_path)
    sched = BatchScheduler(engine, n_rows=n_rows, chunk=4)
    streams = [sched.new_stream() for _ in range(n_rows)][:3]  # a fourth row stays masked
    popped, errs = decode_all(sched, streams, LENGTHS)
    assert errs == [None] * 3
    f = fates()
    bucket = rows("bucket")
    assert bucket.count > 0 and sched._pending is None  # every stream left, the chunk ahead drained
    # the invariant: bucket rows x steps, chunk by chunk, all accounted for
    assert sum(f.values()) == bucket.sum * sched.chunk
    assert f["masked"] == (bucket.sum - rows("active").sum) * sched.chunk
    assert f["consumed"] == sum(popped) and f["quarantined"] == 0
    # delivered to a queue = consumed + unread: what dllama_tokens_generated_total counts,
    # with each request's first token, which its prefill sampled
    generated = telemetry.REGISTRY.get("dllama_tokens_generated_total").value
    assert f["consumed"] + f["unread"] + len(streams) == generated
    # streams of unequal length: the chunk dispatched ahead of a row's end is not consumed
    assert f["orphaned"] + f["unread"] > 0
    host = telemetry.REGISTRY.get("dllama_chunk_host_seconds")
    wait = telemetry.REGISTRY.get("dllama_chunk_fetch_wait_seconds")
    assert host.count == wait.count == bucket.count and host.sum > 0 and wait.sum >= 0


@pytest.mark.parametrize("spec_draft", [0, 3], ids=["chunk", "verify"])
def test_chunk_build_seconds_is_observed_once_a_dispatched_chunk(tmp_path, enabled, spec_draft):
    """``dllama_chunk_build_seconds``: the time the scheduler's lock was held
    for a dispatch, one observation a chunk (plain or verify), and a part of
    that chunk's host time."""
    engine = build_engine(tmp_path)
    sched = BatchScheduler(engine, n_rows=4, chunk=4, spec_draft=spec_draft)
    streams = [sched.new_stream() for _ in range(4)][:3]
    popped, errs = decode_all(sched, streams, LENGTHS, spec_draft=spec_draft)
    assert errs == [None] * 3 and sched._pending is None
    build = telemetry.REGISTRY.get("dllama_chunk_build_seconds")
    host = telemetry.REGISTRY.get("dllama_chunk_host_seconds")
    assert build.count == rows("bucket").count == host.count > 0
    assert 0 < build.sum <= host.sum
    # the ledger with the carry in place: still bucket rows x steps, all accounted for
    if not spec_draft:
        assert sum(fates().values()) == rows("bucket").sum * sched.chunk


def test_row_step_fates_with_a_quarantined_row(tmp_path, enabled):
    faults.install(faults.parse("batch.row:kind=nan,row=1,after=1,count=1"))
    engine = build_engine(tmp_path)
    sched = BatchScheduler(engine, n_rows=4, chunk=4, retry_backoff_s=0.001)
    streams = [sched.new_stream() for _ in range(4)][:3]
    popped, errs = decode_all(sched, streams, (10, 10, 10))
    assert isinstance(errs[1], faults.RowQuarantined) and errs[0] is None and errs[2] is None
    f = fates()
    assert f["quarantined"] == sched.chunk  # the one chunk whose row came back corrupt
    assert sum(f.values()) == rows("bucket").sum * sched.chunk
    assert f["consumed"] == sum(popped)


def test_row_step_fates_in_spec_verify_steps(tmp_path, enabled):
    """A verify step advances a row by what it emitted: the ledger counts
    that, and 1 for a row that was masked, orphaned without a result or
    quarantined, so each bucket row of each step still has one fate."""
    engine = build_engine(tmp_path)
    sched = BatchScheduler(engine, n_rows=4, chunk=4, spec_draft=3)
    assert sched.spec_draft == 3
    streams = [sched.new_stream() for _ in range(4)][:3]
    popped, errs = decode_all(sched, streams, LENGTHS, spec_draft=3)
    assert errs == [None] * 3
    f = fates()
    bucket, active = rows("bucket"), rows("active")
    assert bucket.count > 0 and sched._pending is None
    assert f["masked"] == bucket.sum - active.sum  # one step per dispatch
    assert f["consumed"] == sum(popped) and f["quarantined"] == 0
    generated = telemetry.REGISTRY.get("dllama_tokens_generated_total").value
    assert f["consumed"] + f["unread"] + len(streams) == generated
    # every joined row of every step is delivered (>= 1 token each) or orphaned (>= 1)
    assert f["consumed"] + f["unread"] + f["orphaned"] >= active.sum


def test_a_slow_chunk_leaves_a_flight_event_naming_the_phase(tmp_path, enabled):
    faults.install(faults.parse("replica.slow:kind=delay,delay_ms=120,row=0,after=1,count=1"))
    engine = build_engine(tmp_path)
    sched = BatchScheduler(engine, n_rows=2, chunk=4)
    sched.slow_chunk_s = 0.1  # 2 s in service; the injected delay sits inside the fetch
    popped, errs = decode_all(sched, [sched.new_stream()], (10,))
    assert errs == [None]
    events = flight.RECORDER.snapshot()["replicas"].get("0", [])
    # the first chunk may be slow too, on the host: its dispatch builds the program
    slow = [e for e in events if e["kind"] == "slow_chunk" and e["phase"] == "fetch"]
    assert len(slow) == 1, events
    assert all(e["where"] == "host" for e in events
               if e["kind"] == "slow_chunk" and e["phase"] != "fetch")
    ev = slow[0]
    assert ev["phase"] == "fetch" and ev["where"] == "device" and ev["mode"] == "chunk"
    assert ev["seconds"] >= 0.12 and ev["fetch_s"] >= 0.12
    assert ev["seconds"] >= ev["dispatch_s"] + ev["queued_s"] + ev["fetch_s"] + ev["deliver_s"] - 1e-3
    assert abs(ev["t_s"] - time.monotonic()) < 120  # absolute monotonic seconds


class TestTokensStreamed:
    """``dllama_tokens_streamed_total`` is what clients were sent."""

    def _state(self, tmp_path, name):
        from tests.test_faults import make_state

        return make_state(tmp_path, name, parallel=2)

    def test_equals_the_deltas_a_streaming_client_received(self, tmp_path, enabled):
        state = self._state(tmp_path, "sse")
        chunks = []
        out = state.complete({"messages": [{"role": "user", "content": "hi there"}],
                              "max_tokens": 11, "stream": True}, chunks.append)
        assert out is None and chunks[-1] == "[DONE]"
        deltas = [c for c in map(json.loads, chunks[:-1])
                  if c["choices"][0]["delta"].get("content")]
        streamed = telemetry.REGISTRY.get("dllama_tokens_streamed_total").value
        # a delta carries one token's text unless a possible stop-string prefix held some
        # back: no fewer tokens than deltas, and no more than the request may generate
        assert deltas and len(deltas) <= streamed <= 11
        generated = telemetry.REGISTRY.get("dllama_tokens_generated_total").value
        assert streamed <= generated + 1  # the first token comes with the prefill, not a chunk
        f = fates()
        assert f["consumed"] <= streamed <= f["consumed"] + 1

    def test_counts_a_non_streamed_answer_once(self, tmp_path, enabled):
        state = self._state(tmp_path, "plain")
        out = state.complete({"messages": [{"role": "user", "content": "hello"}],
                              "max_tokens": 7}, lambda s: None)
        streamed = telemetry.REGISTRY.get("dllama_tokens_streamed_total").value
        assert 0 < streamed <= out["usage"]["completion_tokens"] == 7


NEW_READERS = {  # reader file -> what it must read after the decode below
    "decode_consumed_share": lambda v: 0 < v < 100,
    "decode_orphaned_share": lambda v: 0 <= v < 100,
    "decode_active_rows_mean": lambda v: 1 <= v <= 3,
    "decode_row_fill_share": lambda v: 25 <= v <= 100,
    "prefill_chunks_ahead_mean": lambda v: 0 <= v <= 2,
    "chunk_host_ms_mean": lambda v: v > 0,
    "chunk_fetch_wait_ms_mean": lambda v: v >= 0,
    "program_builds_in_window": lambda v: v >= 0,
    "server_ttft_ms_mean": lambda v: v > 0,
}


@pytest.fixture(scope="module")
def scrapes(tmp_path_factory):
    """/metrics text before and after one batched request through the server
    state and three scheduler streams: what the benchmark's readers parse."""
    from distributed_llama_tpu import platform
    from tests.test_faults import make_state

    from benchmark.harness import prom

    telemetry.reset()
    telemetry.enable()
    try:
        platform._install_compile_listeners()
        tmp = tmp_path_factory.mktemp("scrapes")
        state = make_state(tmp, "readers", parallel=4)
        before = prom.parse(telemetry.prometheus_text())
        chunks = []
        state.complete({"messages": [{"role": "user", "content": "hello"}], "max_tokens": 9,
                        "stream": True}, chunks.append)
        sched = state.batch
        decode_all(sched, [s.stream for s in state.slots][:3], LENGTHS)
        yield before, prom.parse(telemetry.prometheus_text())
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.mark.parametrize("reader", sorted(NEW_READERS))
def test_each_new_reader_file_reads_the_programs_own_metrics(scrapes, reader):
    import os

    from benchmark.harness import readers

    directory = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark", "layer_metrics")
    before, after = scrapes
    value, unit = readers.read_metric(directory, reader, readers.Context(before, after, {}))
    assert value is not None, f"{reader}: the program exposes nothing this reader finds"
    assert NEW_READERS[reader](value), (reader, value, unit)
