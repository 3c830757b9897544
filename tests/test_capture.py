"""The capture control (ISSUE 23, telemetry/capture.py): a short profiler
trace of the live process with the program's spans on its timeline, behind
``POST /debug/profile``; the span ring records only while one runs; and
``benchmark/tools/gaps_by_span.py`` reads such a capture. CPU, tiny model."""

import glob
import json
import os
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from distributed_llama_tpu import telemetry
from distributed_llama_tpu.telemetry.capture import HOST_SPANS_FILE, Capture, CaptureBusy, NoCapture
from distributed_llama_tpu.telemetry.tracer import ANNOTATION_PREFIX, SpanTracer


@pytest.fixture
def enabled():
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.TRACER.recording = True
    telemetry.TRACER.clear()
    telemetry.disable()
    telemetry.reset()


def xplane_events(directory: str) -> dict:
    """{plane: {line: [(name, start_ns, dur_ns)]}} of the capture's xplane."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
    assert len(paths) == 1, paths
    return {p.name: {ln.name: [(e.name, e.start_ns, e.duration_ns) for e in ln.events]
                     for ln in p.lines}
            for p in ProfileData.from_file(paths[0]).planes}


def post(url: str, body, raw: bytes | None = None):
    req = urllib.request.Request(url + "/debug/profile",
                                 data=raw if raw is not None else json.dumps(body).encode())
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestCapture:
    def test_spans_land_in_the_xplane_and_in_host_spans_on_the_monotonic_clock(self, tmp_path):
        tracer = SpanTracer()
        tracer.recording = False  # as the server leaves it outside a capture
        cap = Capture(tracer)
        with tracer.span("forward", step=0):
            pass
        assert tracer.events() == []  # no clock read, no lock, no append outside a capture
        f = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((64, 64))
        f(x).block_until_ready()

        t_before = time.monotonic()
        started = cap.start(str(tmp_path / "cap"), max_seconds=30)
        assert started["started"] and cap.status()["running"] and tracer.recording
        with tracer.span("sched_build", bucket=4, active=3):
            with tracer.span("batch_decode_chunk", steps=4):
                f(x).block_until_ready()
        with pytest.raises(CaptureBusy):
            cap.start(str(tmp_path / "other"))
        stopped = cap.stop()
        t_after = time.monotonic()

        assert stopped["reason"] == "stop" and stopped["spans"] == 2
        assert not cap.status()["running"] and cap.status()["last"] == stopped
        assert tracer.recording is False and tracer.events() == []  # off again, ring emptied
        with pytest.raises(NoCapture):
            cap.stop()
        spans = json.load(open(tmp_path / "cap" / HOST_SPANS_FILE))
        by_name = {e["name"]: e for e in spans["traceEvents"]}
        assert set(by_name) == {"sched_build", "batch_decode_chunk"}
        for e in by_name.values():  # absolute time.monotonic() microseconds
            assert t_before * 1e6 <= e["ts"] <= e["ts"] + e["dur"] <= t_after * 1e6
        assert by_name["sched_build"]["args"] == {"bucket": 4, "active": 3, "depth": 0}
        assert by_name["batch_decode_chunk"]["args"]["depth"] == 1
        clock = spans["clock"]  # ties the xplane's wall clock to the monotonic one
        assert clock == started["clock"] and t_before * 1e9 <= clock["monotonic_ns"] <= t_after * 1e9
        assert abs(clock["time_ns"] - time.time_ns()) < 120e9

        planes = xplane_events(str(tmp_path / "cap"))
        host = [e for evs in planes["/host:CPU"].values() for e in evs]
        notes = {n: (s, d) for n, s, d in host if n.startswith(ANNOTATION_PREFIX)}
        assert set(notes) == {"dllama/sched_build", "dllama/batch_decode_chunk"}
        (s0, d0), (s1, d1) = notes["dllama/sched_build"], notes["dllama/batch_decode_chunk"]
        assert s0 <= s1 and s1 + d1 <= s0 + d0  # nested on the xplane as in the ring
        # ... and on the clock of what the device ran: the program's execution lies inside
        ran = [(s, d) for n, s, d in host if n.startswith("PjitFunction(")]
        assert ran and all(s1 <= s and s + d <= s1 + d1 for s, d in ran)

    def test_a_capture_stops_itself_after_max_seconds(self, tmp_path):
        tracer = SpanTracer()
        cap = Capture(tracer)
        cap.start(str(tmp_path / "cap"), max_seconds=0.3)
        deadline = time.monotonic() + 30
        while cap.status()["running"] and time.monotonic() < deadline:
            time.sleep(0.05)
        last = cap.status()["last"]
        assert last is not None and last["reason"] == "max_seconds" and last["seconds"] >= 0.3
        assert tracer.recording is True  # a CLI tracer that recorded before keeps recording
        assert os.path.exists(tmp_path / "cap" / HOST_SPANS_FILE)
        with pytest.raises(NoCapture):
            cap.stop()
        with pytest.raises(ValueError):
            cap.start(str(tmp_path / "bad"), max_seconds=0)
        cap.start(str(tmp_path / "again"), max_seconds=30)  # usable again
        assert cap.stop()["reason"] == "stop"


class TestDebugProfileRoute:
    def test_start_stop_conflict_and_the_ring_outside_a_capture(self, tmp_path, enabled):
        from tests.test_faults import make_state, post_raw, serve_state

        state = make_state(tmp_path, "cap", parallel=2)
        assert state.capture is not None
        telemetry.TRACER.recording = False  # what serve() does with --telemetry
        telemetry.TRACER.clear()
        url, server = serve_state(state)
        body = {"messages": [{"role": "user", "content": "hello"}], "max_tokens": 6}
        try:
            assert post_raw(url, dict(body))[0] == 200
            assert telemetry.TRACER.events() == []  # the hot path recorded nothing
            assert post(url, {"action": "stop"})[0] == 409  # none running
            assert post(url, {"action": "begin"})[0] == 400
            assert post(url, {"action": "start"})[0] == 400  # no dir
            assert post(url, None, raw=b"{not json")[0] == 400
            directory = str(tmp_path / "capture")
            status, started = post(url, {"action": "start", "dir": directory, "max_seconds": 60})
            assert status == 200 and started["max_seconds"] == 60
            status, err = post(url, {"action": "start", "dir": directory})
            assert status == 409 and err["error"]["type"] == "capture_conflict"
            assert post_raw(url, dict(body))[0] == 200
            status, stopped = post(url, {"action": "stop"})
            assert status == 200 and stopped["reason"] == "stop" and stopped["spans"] > 0
            assert telemetry.TRACER.recording is False and telemetry.TRACER.events() == []
            names = {e["name"] for e in json.load(open(stopped["host_spans"]))["traceEvents"]}
            assert {"sched_build", "batch_decode_chunk", "sched_post_dispatch",
                    "batch_decode_fetch", "sched_deliver", "prefill_chunk_dispatch",
                    "decode_stream"} <= names
            routes = telemetry.REGISTRY.get("dllama_http_requests_total")
            ok = routes.labels(route="/debug/profile", status="200")
            deadline = time.monotonic() + 5  # a request is counted after its response is sent
            while ok.value < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert ok.value == 2
            assert routes.labels(route="/debug/profile", status="409").value == 2
            # the xplane holds the same spans as annotations: the tool reads them
            from benchmark.tools import gaps_by_span

            planes = gaps_by_span.load_planes(directory)
            host = planes[gaps_by_span.HOST_PLANE]
            seen = {n for evs in host.values() for n, _, _ in evs if n.startswith("dllama/")}
            assert {"dllama/" + n for n in names} == seen
            launches = gaps_by_span.launches_by_span(host)
            # the small one-element programs, by the span that issued them; the
            # spans of a decode dispatch issue none (what a chunk needs per row
            # crosses as whole vectors with the chunk program itself)
            assert sum(launches.get("jit_convert_element_type", {}).values()) > 0
            issued = {s for by in launches.values() for s in by}
            assert issued and not issued & {"sched_post_dispatch", "sched_build"}, launches
        finally:
            server.shutdown()

    def test_the_control_needs_telemetry(self, tmp_path):
        from tests.test_faults import make_state, serve_state

        telemetry.disable()
        state = make_state(tmp_path, "off", parallel=2)
        assert state.capture is None
        url, server = serve_state(state)
        try:
            assert post(url, {"action": "start", "dir": str(tmp_path / "x")})[0] == 404
        finally:
            server.shutdown()


class TestGapsBySpan:
    """The attribution arithmetic on hand-made lines (nanoseconds)."""

    def test_idle_goes_to_the_span_that_answers_for_the_instant(self):
        from benchmark.tools import gaps_by_span as g

        planes = {
            "/device:TPU:0": {
                "XLA Ops": [["op", 0, 100], ["op", 200, 100], ["op", 400, 100], ["op", 900, 100]],
                "XLA Modules": [["jit_decode(1)", 0, 300], ["jit_dynamic_slice(2)", 400, 100],
                                ["jit_dynamic_slice(3)", 900, 100]],
            },
            "/host:CPU": {
                "pump": [["dllama/decode_stream", 0, 1000], ["dllama/sched_build", 90, 60],
                         ["dllama/batch_decode_fetch", 150, 200],
                         ["dllama/sched_post_dispatch", 480, 120],
                         ["PjitFunction(dynamic_slice)", 500, 10], ["PjitFunction(squeeze)", 700, 5]],
                "other": [["dllama/decode_stream", 0, 1000], ["dllama/sched_wait", 100, 300],
                          ["PjitFunction(dynamic_slice)", 820, 10]],
            },
        }
        a = g.analyse(planes)
        idle = dict(a["idle_by_span"])
        # gaps: [100, 200) [300, 400) [500, 900) = 600 ns
        assert a["idle_s"] == pytest.approx(600e-9) and a["named_share"] == 100.0
        # [100,150) a span that works beats the parked and the consuming; [150,200) and
        # [300,350) the fetch beats both; [350,400) consuming beats parked
        assert idle["sched_build"] == pytest.approx(50e-9)
        assert idle["batch_decode_fetch"] == pytest.approx(100e-9)
        assert idle["sched_post_dispatch"] == pytest.approx(100e-9)  # [500, 600)
        assert idle["decode_stream"] == pytest.approx(350e-9)  # [350,400) + [600,900)
        assert "sched_wait" not in idle
        assert a["eager_launches"] == {
            "jit_dynamic_slice": {"sched_post_dispatch": 1, "decode_stream": 1},
            "jit_squeeze": {"decode_stream": 1},
        }
        assert a["modules"] == {"jit_dynamic_slice": {"count": 2, "seconds": 200e-9},
                                "jit_decode": {"count": 1, "seconds": 300e-9}}
        assert a["span_times"]["decode_stream"] == {"count": 2, "seconds": 2000e-9}
        assert a["span_times"]["sched_build"] == {"count": 1, "seconds": 60e-9}
        text = g.table(a)
        assert "`sched_post_dispatch`" in text and "`jit_dynamic_slice` | 2 x" in text

    def test_idle_under_no_span_is_said_so(self):
        from benchmark.tools import gaps_by_span as g

        planes = {"/device:TPU:0": {"XLA Ops": [["op", 0, 100], ["op", 300, 100]]},
                  "/host:CPU": {"t": [["dllama/sched_deliver", 100, 50]]}}
        a = g.analyse(planes)
        assert dict(a["idle_by_span"]) == {"sched_deliver": pytest.approx(50e-9),
                                           g.NO_SPAN: pytest.approx(150e-9)}
        assert a["named_share"] == pytest.approx(25.0)
        with pytest.raises(ValueError, match="no device"):
            g.analyse({"/host:CPU": {}})
