"""Request-scoped tracing, flight recorder, and SLO attribution (ISSUE 16).

Four layers, mirroring the subsystem:

* :class:`TraceContext` / :class:`RequestTraceStore` units — span
  recording, attempt siblings, TTFT/TPOT derivation, replay stage
  folding, bounded retention with seeded Bernoulli sampling and the
  slow-TTFT always-keep override.
* :class:`FlightRecorder` units — bounded per-replica rings, auto-dump
  retention + JSON artifacts, and the faults fire-observer wiring (every
  chaos injection lands in the black box with its site name).
* Serving-level trace assembly over real HTTP — ``/debug/trace/<id>``
  returns one tree whose stage attribution sums to the measured E2E
  within 10%, ``/debug/flight`` serves the live rings, and the dump CLI
  fetches both.
* The failover acceptance test — an injected ``replica.crash``
  mid-decode yields ONE tree per victim with both attempts as siblings
  (the replay tagged ``replayed=true``), and the flight recorder's
  death dump names the fault site and the victim trace ids.

Everything runs on tiny seeded synthetic models under JAX_PLATFORMS=cpu
(tier-1 safe); the ``chaos`` marker tags the HTTP chaos classes.
"""

import json
import time

import pytest

from distributed_llama_tpu import telemetry
from distributed_llama_tpu.engine import faults
from distributed_llama_tpu.telemetry import flight
from distributed_llama_tpu.telemetry.trace import (
    MAX_EVENTS,
    NULL_TRACE_SPAN,
    RequestTraceStore,
    TraceContext,
    span,
)


@pytest.fixture(autouse=True)
def _clean_recorder():
    flight.RECORDER.clear()
    yield
    flight.RECORDER.clear()
    flight.RECORDER.dump_dir = None


@pytest.fixture
def enabled():
    """Telemetry ON with a clean registry; restores disabled + clean
    afterwards so test order never leaks global state."""
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.disable()
    telemetry.reset()


# ----------------------------------------------------------------------
# TraceContext units
# ----------------------------------------------------------------------


class TestTraceContext:
    def test_span_helper_records_on_ctx_and_noops_on_none(self):
        ctx = TraceContext("r1", "default")
        with span(ctx, "queue_wait", depth=3):
            pass
        assert span(None, "queue_wait") is NULL_TRACE_SPAN
        (ev,) = list(ctx.events)
        assert ev["name"] == "queue_wait" and ev["args"] == {"depth": 3}
        assert ev["dur_us"] >= 0 and ev["attempt"] == 0

    def test_mark_token_derives_ttft_and_tpot(self):
        ctx = TraceContext("r1", "default")
        assert ctx.ttft_s is None and ctx.tpot_s is None
        ctx.mark_token()
        assert ctx.ttft_s is not None
        assert ctx.tpot_s is None  # one token has no spread
        time.sleep(0.01)
        ctx.mark_token()
        ctx.mark_token()
        assert ctx.emitted == 3
        assert ctx.tpot_s == pytest.approx(
            (ctx.last_token_s - ctx.first_token_s) / 2
        )

    def test_replay_attempt_is_a_sibling_and_folds_stages(self):
        ctx = TraceContext("r1", "default")
        ctx.begin_attempt(replayed=False)
        ctx.set_replica(0)
        ctx.add_stage("queue", 0.1)
        ctx.add_stage("decode", 0.4)
        ctx.add_span("decode_stream", time.perf_counter(), 0.4)
        # the failover replay: a NEW attempt in the SAME context
        ctx.begin_attempt(replayed=True)
        ctx.set_replica(1)
        ctx.add_stage("queue", 0.05)   # folds into "replay"
        ctx.add_stage("decode", 0.6)   # folds into "replay"
        ctx.add_span("decode_stream", time.perf_counter(), 0.6)
        tree = ctx.tree()
        assert [a["replayed"] for a in tree["attempts"]] == [False, True]
        assert [a["replica"] for a in tree["attempts"]] == [0, 1]
        assert [len(a["spans"]) for a in tree["attempts"]] == [1, 1]
        assert tree["stages"]["queue"] == pytest.approx(0.1)
        assert tree["stages"]["decode"] == pytest.approx(0.4)
        assert tree["stages"]["replay"] == pytest.approx(0.65)

    def test_set_replica_backfills_live_attempt(self):
        ctx = TraceContext("r1", "default")
        ctx.begin_attempt()
        assert ctx.attempts[-1]["replica"] is None
        ctx.set_replica(2)
        assert ctx.attempts[-1]["replica"] == 2

    def test_events_are_bounded(self):
        ctx = TraceContext("r1", "default")
        for i in range(MAX_EVENTS + 64):
            ctx.add_span("sse_send", 0.0, 0.0, i=i)
        assert len(ctx.events) == MAX_EVENTS
        # oldest fell off, newest kept
        assert list(ctx.events)[-1]["args"]["i"] == MAX_EVENTS + 63

    def test_chrome_trace_shape(self):
        ctx = TraceContext("r1", "default")
        ctx.begin_attempt()
        with ctx.span("prefill", tokens=4):
            pass
        ctx.begin_attempt(replayed=True)
        with ctx.span("decode_stream"):
            pass
        chrome = ctx.chrome_trace()
        evs = chrome["traceEvents"]
        assert all(e["ph"] == "X" for e in evs)
        names = [e["name"] for e in evs]
        assert "attempt0" in names and "attempt1 (replay)" in names
        assert "prefill" in names and "decode_stream" in names
        # the replay's spans live on its own tid (perfetto row)
        tids = {e["name"]: e["tid"] for e in evs}
        assert tids["prefill"] == 0 and tids["decode_stream"] == 1
        json.dumps(chrome)  # the export is valid JSON end to end


class TestRequestTraceStore:
    def test_sample_rate_zero_drops_fast_requests(self):
        store = RequestTraceStore(sample_rate=0.0, slow_ttft_s=10.0)
        ctx = store.begin("r1", "default")
        assert store.get("r1") is ctx  # inflight is always findable
        assert store.finish(ctx) is False
        assert store.get("r1") is None and ctx.sampled is False
        assert store.stats()["kept_total"] == 0

    def test_slow_ttft_overrides_the_sampler(self):
        store = RequestTraceStore(sample_rate=0.0, slow_ttft_s=0.0001)
        ctx = store.begin("slow", "default")
        time.sleep(0.002)
        ctx.mark_token()
        assert store.finish(ctx) is True
        assert store.get("slow") is ctx and ctx.sampled is True
        s = store.stats()
        assert s["kept_total"] == 1 and s["slow_kept_total"] == 1

    def test_retention_is_bounded(self):
        store = RequestTraceStore(capacity=4, sample_rate=1.0)
        for i in range(10):
            store.finish(store.begin(f"r{i}", "default"))
        s = store.stats()
        assert s["retained"] == 4 and s["kept_total"] == 10
        assert store.get("r0") is None and store.get("r9") is not None

    def test_sampling_is_seeded_and_deterministic(self):
        def kept(n=50):
            store = RequestTraceStore(sample_rate=0.5, slow_ttft_s=0)
            return [
                store.finish(store.begin(f"r{i}", "t")) for i in range(n)
            ]

        a, b = kept(), kept()
        assert a == b  # Random(0): retention never depends on wall entropy
        assert any(a) and not all(a)

    def test_e2e_set_at_finish(self):
        store = RequestTraceStore()
        ctx = store.begin("r1", "default")
        assert ctx.e2e_s is None
        store.finish(ctx)
        assert ctx.e2e_s is not None and ctx.e2e_s >= 0


# ----------------------------------------------------------------------
# FlightRecorder units
# ----------------------------------------------------------------------


class TestFlightRecorder:
    def test_rings_are_per_replica_and_bounded(self):
        rec = flight.FlightRecorder(capacity=8)
        for i in range(20):
            rec.record(0, "state", frm=0, to=1, i=i)
        rec.record(1, "failover", victims=2)
        snap = rec.snapshot()
        assert len(snap["replicas"]["0"]) == 8
        assert snap["replicas"]["0"][-1]["i"] == 19  # oldest fell off
        assert snap["replicas"]["1"][0]["kind"] == "failover"
        assert snap["recorded_total"] == 21
        # seq is a global order across rings
        assert snap["replicas"]["1"][0]["seq"] == 21

    def test_dump_snapshots_ring_and_is_bounded(self, tmp_path):
        rec = flight.FlightRecorder(max_dumps=2, dump_dir=str(tmp_path))
        rec.record(0, "replica_lost", cause="crash", victims=2)
        d = rec.dump(0, "replica_death", victim_trace_ids=["a", "b"])
        assert d["reason"] == "replica_death"
        assert d["victim_trace_ids"] == ["a", "b"]
        assert [e["kind"] for e in d["events"]] == ["replica_lost"]
        for _ in range(3):
            rec.dump(0, "watchdog_stall")
        assert len(rec.dumps()) == 2  # bounded retention
        # the JSON artifact lands on disk (written from a daemon thread)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            files = list(tmp_path.glob("dllama-flight-r0-*.json"))
            if len(files) >= 4:
                break
            time.sleep(0.01)
        art = json.loads(
            sorted(tmp_path.glob("dllama-flight-r0-*.json"))[0].read_text()
        )
        assert art["reason"] == "replica_death"

    def test_fault_observer_records_site(self):
        """Every chaos injection that actually fires lands in the ring
        with its faults.SITES site name — the ROBUSTNESS.md contract that
        a chaos post-mortem starts from the injection."""
        flight.install_fault_observer()
        faults.install(faults.parse("batch.row:kind=raise,row=3,count=1"))
        try:
            plan = faults.active_plan()
            with pytest.raises(faults.InjectedFault):
                plan.fire("batch.row", row=3)
            snap = flight.RECORDER.snapshot()
            fires = [
                e for ring in snap["replicas"].values() for e in ring
                if e["kind"] == "fault_fire"
            ]
            assert len(fires) == 1
            assert fires[0]["site"] == "batch.row"
            assert fires[0]["fault_kind"] == "raise"
            assert fires[0]["replica"] == 3  # the targeted row's ring
        finally:
            faults.clear()

    def test_untargeted_fire_lands_in_unscoped_ring(self):
        flight.install_fault_observer()
        faults.install(faults.parse("engine.forward:kind=raise,count=1"))
        try:
            with pytest.raises(faults.InjectedFault):
                faults.active_plan().fire("engine.forward")
            snap = flight.RECORDER.snapshot()
            assert str(flight.UNSCOPED) in snap["replicas"]
        finally:
            faults.clear()


# ----------------------------------------------------------------------
# Serving-level trace assembly (real HTTP, tiny synthetic model)
# ----------------------------------------------------------------------


def _get_json(url, path):
    from tests.test_faults import get

    status, body = get(url, path)
    return status, json.loads(body)


def _cli_json(capsys):
    """The dump CLI logs its fetch lines before the payload — parse the
    JSON document that follows them."""
    out = capsys.readouterr().out
    return json.loads(out[out.index("{"):])


@pytest.mark.chaos
class TestTraceHTTP:
    def test_trace_endpoint_attribution_and_flight(self, tmp_path, enabled):
        """The tentpole acceptance: a request's /debug/trace/<id> tree
        exists, carries the serving-rhythm spans, and its stage
        attribution sums to the measured E2E within 10%."""
        from tests.test_faults import get, make_state, post_raw, serve_state

        state = make_state(tmp_path, "trace", parallel=2)
        assert state.traces is not None  # telemetry on → store built
        url, server = serve_state(state)
        try:
            t0 = time.perf_counter()
            status, headers, body = post_raw(
                url, {"messages": [{"role": "user", "content": "hello"}],
                      "max_tokens": 24},
            )
            client_e2e = time.perf_counter() - t0
            assert status == 200
            rid = headers["X-Request-Id"]
            assert body["id"] == f"chatcmpl-{rid}"

            status, tree = _get_json(url, f"/debug/trace/{rid}")
            assert status == 200
            assert tree["request_id"] == rid and tree["sampled"] is True
            names = {
                s["name"] for a in tree["attempts"] for s in a["spans"]
            }
            # the serving rhythm: front door → placement → prefill →
            # decode (no sse_send: this was a non-streaming completion)
            assert {"queue_wait", "placement", "prefill",
                    "decode_stream"} <= names
            assert len(tree["attempts"]) == 1
            assert tree["attempts"][0]["replayed"] is False
            assert tree["emitted"] == body["usage"]["completion_tokens"]
            assert tree["ttft_s"] is not None and tree["tpot_s"] is not None

            # the attribution contract: queue+placement+prefill+decode
            # account for the request's measured wall time within 10% —
            # with a small absolute floor: under a warm jit cache (full
            # suite) the whole request is ~10ms and the fixed
            # HTTP-parse/tokenize/respond cost outside the stages would
            # otherwise dominate the ratio
            attributed = sum(tree["stages"].values())
            assert tree["e2e_s"] is not None
            tol = max(0.10 * tree["e2e_s"], 0.025)
            assert abs(attributed - tree["e2e_s"]) <= tol, (
                tree["stages"], tree["e2e_s"])
            tol = max(0.10 * client_e2e, 0.025)
            assert abs(client_e2e - attributed) <= tol, (
                tree["stages"], client_e2e)

            # Chrome export of the same tree
            status, chrome = _get_json(
                url, f"/debug/trace/{rid}?format=chrome"
            )
            assert status == 200
            assert {e["name"] for e in chrome["traceEvents"]} >= {
                "attempt0", "prefill", "decode_stream"}

            # a miss is diagnosable: the 404 body carries the store stats
            status, miss = _get_json(url, "/debug/trace/nope")
            assert status == 404
            assert miss["tracing_enabled"] is True
            assert miss["store"]["kept_total"] >= 1

            # the live flight view always serves (empty rings are fine:
            # nothing died in this test)
            status, snap = _get_json(url, "/debug/flight")
            assert status == 200
            assert "replicas" in snap and "dumps" in snap
        finally:
            server.shutdown()
            if state.pool is not None:
                state.pool.close()

    def test_dump_cli_fetches_trace_and_flight(self, tmp_path, enabled,
                                               capsys):
        from distributed_llama_tpu.telemetry.dump import main as dump_main

        from tests.test_faults import make_state, post_raw, serve_state

        state = make_state(tmp_path, "dumpcli", parallel=2)
        url, server = serve_state(state)
        try:
            status, headers, _ = post_raw(
                url, {"messages": [{"role": "user", "content": "hi"}],
                      "max_tokens": 4},
            )
            assert status == 200
            rid = headers["X-Request-Id"]
            assert dump_main(["--url", url, "--trace", rid]) == 0
            chrome = _cli_json(capsys)
            assert "traceEvents" in chrome  # default export is Chrome
            assert dump_main(
                ["--url", url, "--trace", rid, "--format", "json"]
            ) == 0
            tree = _cli_json(capsys)
            assert tree["request_id"] == rid
            assert dump_main(["--url", url, "--flight"]) == 0
            snap = _cli_json(capsys)
            assert "replicas" in snap
            # an unknown id exits 1 (the 404), not a traceback
            assert dump_main(["--url", url, "--trace", "nope"]) == 1
        finally:
            server.shutdown()
            if state.pool is not None:
                state.pool.close()

    def test_telemetry_off_means_no_store_and_404(self, tmp_path):
        """PR 1 contract: telemetry off → no trace store, every stream's
        trace stays None, and the debug endpoint answers an honest 404."""
        from tests.test_faults import make_state, post_raw, serve_state

        telemetry.disable()
        state = make_state(tmp_path, "off", parallel=2)
        assert state.traces is None
        url, server = serve_state(state)
        try:
            status, headers, _ = post_raw(
                url, {"messages": [{"role": "user", "content": "hi"}],
                      "max_tokens": 4},
            )
            assert status == 200
            status, miss = _get_json(
                url, f"/debug/trace/{headers['X-Request-Id']}"
            )
            assert status == 404 and miss["tracing_enabled"] is False
            if state.batch is not None:
                assert all(
                    s.trace is None for s in state.batch._streams
                )
        finally:
            server.shutdown()
            if state.pool is not None:
                state.pool.close()


# ----------------------------------------------------------------------
# The failover acceptance test: ONE tree, sibling attempts, black box
# ----------------------------------------------------------------------


@pytest.mark.chaos
class TestFailoverTrace:
    def test_crash_yields_one_tree_with_replay_sibling(self, tmp_path,
                                                       enabled):
        """ISSUE 16 acceptance: an injected replica.crash mid-decode
        yields ONE trace tree per victim with both attempts as siblings
        (the replay tagged replayed=true, each attempt stamped with its
        replica), stage attribution still summing to E2E within 10%, and
        the flight recorder's death dump naming the fault site and the
        victim trace ids."""
        from tests.test_fair_sched import SseStream
        from tests.test_faults import serve_state
        from tests.test_replicas import _SLOW, make_replica_state

        faults.clear()
        faults.install(faults.parse(
            f"replica.crash:kind=raise,row=0,after=16,count=1;{_SLOW}"
        ))
        try:
            state = make_replica_state(
                tmp_path, "tchaos", replicas=2, parallel=2
            )
            assert state.traces is not None
            url, server = serve_state(state)
            try:
                body = {"messages": [
                    {"role": "user", "content": "tell me a very long story"}
                ], "max_tokens": 96}
                streams = [SseStream(url, dict(body)) for _ in range(4)]
                rids = [s.resp.getheader("X-Request-Id") for s in streams]
                for s in streams:
                    s.read_first_delta()
                    s.read_rest()
                assert all(s.error_type is None for s in streams)
                assert state.pool.failovers_total == 1
                assert state.pool.last_failover_victims == 2

                trees = {}
                for rid in rids:
                    # the handler finishes a trace AFTER the last SSE byte
                    # is out, so the client can get here first: wait for
                    # the finished tree (e2e_s set), bounded
                    deadline = time.monotonic() + 5.0
                    while True:
                        status, tree = _get_json(url, f"/debug/trace/{rid}")
                        assert status == 200, rid
                        if tree["e2e_s"] is not None or time.monotonic() > deadline:
                            break
                        time.sleep(0.02)
                    trees[rid] = tree
                victims = [
                    t for t in trees.values() if len(t["attempts"]) == 2
                ]
                healthy = [
                    t for t in trees.values() if len(t["attempts"]) == 1
                ]
                assert len(victims) == 2 and len(healthy) == 2
                for t in victims:
                    first, replay = t["attempts"]
                    assert first["replayed"] is False
                    assert replay["replayed"] is True
                    assert first["replica"] == 0  # died there
                    # the replay lands wherever placement routes it — the
                    # survivor, or replica 0 again after its fast restart
                    assert replay["replica"] in (0, 1)
                    assert replay["start_us"] > first["start_us"]
                    # the replay's whole re-run folded into one bucket so
                    # the primary breakdown stays attributable
                    assert t["stages"].get("replay", 0) > 0
                    # attribution still sums: the dead attempt's partial
                    # decode is recorded (the try/finally in _complete_on)
                    attributed = sum(t["stages"].values())
                    tol = max(0.10 * t["e2e_s"], 0.025)
                    assert abs(attributed - t["e2e_s"]) <= tol, (
                        t["stages"], t["e2e_s"])
                for t in healthy:
                    assert t["attempts"][0]["replayed"] is False
                    assert "replay" not in t["stages"]

                # the black box: the injection fired, the failover it
                # caused is recorded with the victims' trace ids, and the
                # death dump was retained
                status, snap = _get_json(url, "/debug/flight")
                assert status == 200
                events = [
                    e for ring in snap["replicas"].values() for e in ring
                ]
                fires = [e for e in events if e["kind"] == "fault_fire"]
                assert any(e["site"] == "replica.crash" for e in fires)
                fos = [e for e in events if e["kind"] == "failover"]
                assert len(fos) == 1
                victim_ids = {t["request_id"] for t in victims}
                assert set(fos[0]["victim_trace_ids"]) == victim_ids
                dumps = [
                    d for d in snap["dumps"]
                    if d["reason"] == "replica_death"
                ]
                assert len(dumps) == 1 and dumps[0]["replica"] == 0
                assert set(dumps[0]["victim_trace_ids"]) == victim_ids
                # the dump's ring shows the injection that caused it
                assert any(
                    e["kind"] == "fault_fire"
                    and e["site"] == "replica.crash"
                    for e in dumps[0]["events"]
                )
            finally:
                server.shutdown()
                state.pool.close()
        finally:
            faults.clear()
