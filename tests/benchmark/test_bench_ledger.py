"""The completion ledger's readers (ISSUE 41) against a recorded pair of
scrapes, against a program that has no such series (the parent commit), and
the arithmetic of ``benchmark/tools/ledger_vs_trace.py`` on hand-made lines.
The recorded pair (``data/ledger_scrapes.json``) is the tiny model's
scheduler on the CPU: a fixture for parsing, never a device number."""

import json
import os

import pytest

from benchmark.harness import prom, readers
from benchmark.tools import ledger_vs_trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
READERS = os.path.join(REPO, "benchmark", "layer_metrics")
# ISSUE 41's rule: an entry whose series missed the check against the device in some cell is
# left out of the manifest. The idle series and the prompt pieces' seconds missed it in one
# capture of mistral7b.long_doc_qa (PERF.md section 7, "Left by PR 41"), so the four readers of
# those series wait here, as they were written, for the `benchmark` PR that admits them
HELD_BACK_READERS = os.path.join(REPO, "tests", "benchmark", "data", "held_back_readers")
HELD_BACK = ("window_idle_share", "window_host_idle_share", "window_prefill_share",
             "prefill_piece_device_ms_mean")
SHARES = ("window_idle_share", "window_host_idle_share", "window_decode_share", "window_prefill_share")
ADMITTED = ("window_decode_share", "decode_chunk_device_ms_mean", "prefill_pad_row_share",
            "chunk_build_ms_mean")
NEW = ADMITTED + HELD_BACK
MS = 1_000_000


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(REPO, "tests", "benchmark", "data", "ledger_scrapes.json")) as f:
        rec = json.load(f)
    return prom.parse(rec["before"]), prom.parse(rec["after"]), rec["seconds_between"]


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


def read(name: str, before, after):
    directory = HELD_BACK_READERS if name in HELD_BACK else READERS
    return readers.read_metric(directory, name, readers.Context(before, after, {}))


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_resolves_on_the_recorded_scrapes(recorded, name):
    before, after, _ = recorded
    value, unit = read(name, before, after)
    assert value is not None and value > 0
    assert unit == ("%" if name.endswith("_share") else "ms")
    if unit == "%":
        assert value < 100


@pytest.mark.parametrize("name", ADMITTED)
def test_a_new_entry_is_reported_by_every_cell_and_moves_out_tok_s(manifest, name):
    entry = manifest[name]
    assert "workloads" not in entry  # every cell reports it, those that later PRs add too
    assert (entry["source"], entry["moves"]) == ("program_counter", "out_tok_s")
    with open(os.path.join(READERS, f"{name}.json")) as f:
        meta = json.load(f)
    assert meta["unit"] == entry["unit"] and meta["reader"]["kind"] in ("ratio", "histogram_mean")


def test_the_five_series_deltas_equal_the_seconds_between_the_scrapes(recorded):
    before, after, between = recorded
    assert prom.delta(before, after, "dllama_device_seconds_total") == pytest.approx(between, abs=0.3)
    by_hand = sum(prom.delta(before, after, "dllama_device_seconds_total", {"program": p})
                  for p in ("decode_chunk", "prefill_piece", "spec_verify", "no_work", "work_waiting"))
    assert by_hand == pytest.approx(prom.delta(before, after, "dllama_device_seconds_total"))
    got = ledger_vs_trace.window_sum(before, after, between)
    assert got["sum_s"] == pytest.approx(by_hand) and abs(got["sum_over_window_pct"]) < 20
    assert got["launches"]["decode_chunk"] > 0 and got["launches"]["carry_put"] > 0


def test_the_shares_are_parts_of_one_whole(recorded):
    before, after, _ = recorded
    idle, host, decode, prefill = (read(n, before, after)[0] for n in SHARES)
    assert idle + decode + prefill == pytest.approx(100.0)  # no verify step in the recording
    assert 0 < host < idle
    ms = read("decode_chunk_device_ms_mean", before, after)[0]
    seconds = prom.delta(before, after, "dllama_device_seconds_total", {"program": "decode_chunk"})
    chunks = prom.delta(before, after, "dllama_device_programs_total", {"program": "decode_chunk"})
    assert ms == pytest.approx(1e3 * seconds / chunks)


@pytest.mark.parametrize("name", [n for n in NEW if n != "chunk_build_ms_mean"])
def test_a_program_without_the_ledger_leaves_the_entry_out(name):
    """The parent commit under this PR's benchmark files: no such series, so
    the reader finds nothing, returns None and raises nothing."""
    parent = prom.parse("dllama_tokens_generated_total 96\ndllama_chunk_build_seconds_sum 1\n"
                        "dllama_chunk_build_seconds_count 4\n")
    assert read(name, parent, parent) == (None, "%" if name.endswith("_share") else "ms")


def capture():
    """A prompt piece with the carry's write behind it, then two chunks with
    an idle gap between them: spans as the watcher would open and close them,
    a little after the device."""
    return {
        "spans": [["prefill_piece", 0, 40 * MS], ["decode_chunk", 40 * MS, 300 * MS],
                  ["decode_chunk", 400 * MS, 290 * MS]],
        "modules": [["jit__slab_prefill_single_paged(11)", 1 * MS, 38 * MS],
                    ["jit__carry_put(12)", 39 * MS + MS // 2, MS // 10],
                    ["jit_decode_chunk_batched_paged(13)", 40 * MS, 299 * MS],
                    ["jit_decode_chunk_batched_paged(13)", 401 * MS, 288 * MS]],
        "ops": [["%fusion.1 = f32[8]{0} fusion(...)", 1 * MS, 38 * MS],
                ["%copy.2 = s32[4]{0} copy(...)", 39 * MS + MS // 2, MS // 10],
                ["%while.3 = (s32[]) while(...)", 40 * MS, 299 * MS],
                ["%while.3 = (s32[]) while(...)", 401 * MS, 288 * MS]],
    }


def test_ledger_vs_trace_lays_spans_beside_modules_over_the_span_both_cover():
    a = ledger_vs_trace.analyse(capture())
    assert a["window_s"] == pytest.approx(0.688)  # first module's start to the last one's end
    chunk, piece = a["programs"]["decode_chunk"], a["programs"]["prefill_piece"]
    assert (chunk["spans"], chunk["modules"]) == (2, 2)
    assert chunk["ledger_s"] == pytest.approx(0.589) and chunk["device_s"] == pytest.approx(0.587)
    assert chunk["ledger_over_device_pct"] == pytest.approx(100 * (0.589 / 0.5871 - 1))
    # the carry's write ran behind the piece and in front of the first chunk: the device runs in
    # dispatch order, so it finished inside the CHUNK's interval and counts beside the chunk
    assert (chunk["unobserved_beside"], chunk["unobserved_beside_s"]) == (1, pytest.approx(1e-4))
    assert (piece["unobserved_beside"], piece["unobserved_beside_s"]) == (0, 0)
    assert piece["ledger_over_device_pct"] == pytest.approx(100 * (0.039 / 0.038 - 1))
    assert a["unobserved"] == {"jit__carry_put": {"count": 1, "seconds": pytest.approx(1e-4)}}
    assert a["ledger_idle_pct"] == pytest.approx(100 * 0.060 / 0.688)
    assert a["device_idle_pct"] == pytest.approx(100 * (1 - (0.038 + 0.0001 + 0.299 + 0.288) / 0.688))
    assert "spec_verify" not in a["programs"]
    text = ledger_vs_trace.table(a, {"decode_chunk": {"count": 2, "seconds": 0.59}})
    assert "`jit__carry_put` | 1 |" in text and "host_spans.json" in text


def test_ledger_vs_trace_holds_the_credited_intervals_and_not_the_watchers_wake():
    """A chunk's consumer stamps it as its fetch returns and the watcher wakes
    later: what the counters moved by is in the ring's copy of the span, and
    that is the ledger's side where a capture has it."""
    cap = capture()
    cap["spans"] = [["prefill_piece", 0, 40 * MS], ["decode_chunk", 40 * MS, 304 * MS],
                    ["decode_chunk", 400 * MS, 294 * MS]]  # closed 4 ms after the stamps
    cap["credited"] = [["prefill_piece", 0, 39 * MS + MS // 2], ["decode_chunk", 39 * MS + MS // 2, 300 * MS],
                       ["decode_chunk", 400 * MS, 290 * MS]]
    a = ledger_vs_trace.analyse(cap)
    chunk = a["programs"]["decode_chunk"]
    assert a["ledger_side"] == "credited" and a["window_s"] == pytest.approx(0.688)
    assert chunk["ledger_s"] == pytest.approx(0.589) and chunk["watched_s"] == pytest.approx(0.593)
    assert a["ledger_idle_pct"] == pytest.approx(100 * 0.0605 / 0.688)
    assert "as credited" in ledger_vs_trace.table(a)
    assert ledger_vs_trace.analyse(capture())["ledger_side"] == "spans"


def test_the_rings_credits_are_laid_on_the_xplanes_clock_by_the_spans_both_hold():
    off = 7_000_000_000_123  # the xplane's clock less the monotonic one, ns
    ring = [{"name": "device_interval", "ts": 1e6 * t, "dur": 1e6 * d,
             "args": {"program": p, "credit_ts": 1e6 * (t - 0.001), "credit_dur": 1e6 * (d - 0.002)}}
            for p, t, d in (("decode_chunk", 99.7, 0.3), ("prefill_piece", 100.0, 0.04),
                            ("decode_chunk", 100.04, 0.3), ("decode_chunk", 100.4, 0.29))]
    # the xplane lost the span that was open when the capture began, and opens each a little early
    spans = [[e["args"]["program"], int(e["ts"] * 1e3) + off - 3000, int(e["dur"] * 1e3) + 5000]
             for e in ring[1:]]
    got = ledger_vs_trace.credited_on_trace_clock(spans, ring)
    assert [g[0] for g in got] == ["decode_chunk", "prefill_piece", "decode_chunk", "decode_chunk"]
    for g, e in zip(got, ring):
        assert g[1] == pytest.approx(e["args"]["credit_ts"] * 1e3 + off - 3000, abs=10)
        assert g[2] == pytest.approx(e["args"]["credit_dur"] * 1e3, abs=2)
    # a ring from before the credit was recorded, or none: nothing to lay
    assert ledger_vs_trace.credited_on_trace_clock(spans, [{**e, "args": {}} for e in ring]) is None
    assert ledger_vs_trace.credited_on_trace_clock(spans, []) is None


def test_ledger_vs_trace_says_what_a_capture_lacks():
    cap = capture()
    with pytest.raises(ValueError, match="no dllama/device_interval span"):
        ledger_vs_trace.analyse({**cap, "spans": []})  # the parent's capture
    with pytest.raises(ValueError, match="no device module"):
        ledger_vs_trace.analyse({**cap, "modules": []})
    assert ledger_vs_trace.program_of("jit_spec_verify_chunk_batched") == "spec_verify"
    assert ledger_vs_trace.program_of("jit__publish_pages") is None


def test_ring_sums_reads_the_captures_host_spans(tmp_path):
    assert ledger_vs_trace.ring_sums(str(tmp_path)) is None
    events = [{"name": "device_interval", "ts": 1.0, "dur": 250_000.0, "args": {"program": "decode_chunk"}},
              {"name": "device_interval", "ts": 2.0, "dur": 50_000.0, "args": {"program": "prefill_piece"}},
              {"name": "sched_build", "ts": 3.0, "dur": 10.0, "args": {}}]
    (tmp_path / "host_spans.json").write_text(json.dumps({"traceEvents": events}))
    assert ledger_vs_trace.ring_sums(str(tmp_path)) == {
        "decode_chunk": {"count": 1, "seconds": 0.25}, "prefill_piece": {"count": 1, "seconds": 0.05}}
