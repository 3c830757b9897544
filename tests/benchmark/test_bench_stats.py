"""Arithmetic from delta timestamps to the end-to-end metrics, on hand-made
records, chunked delivery included."""

import math

import pytest

from benchmark.harness import prom, readers, stats


def rec(due, deltas, finish="length", status=200, done=True, asked=None, error=None, index=0):
    return stats.Record(index=index, due=due, sent=due + 0.001, status=status, deltas=list(deltas),
                        finish=finish, done=done, error=error,
                        asked=len(deltas) if asked is None else asked)


def test_ttft_is_timed_from_the_due_instant_not_the_send():
    r = rec(10.0, [10.5, 10.6])
    r.sent = 10.3  # the generator ran late: the user still waited from 10.0
    assert r.ttft == pytest.approx(0.5)


def test_chunked_delivery_pace_and_freeze():
    # one fused first token, then chunks of 32 tokens 0.4 s apart, each
    # chunk's tokens arriving together
    deltas = [1.0] + [1.4] * 32 + [1.8] * 32 + [2.2] * 32
    r = rec(0.9, deltas)
    assert r.tpot == pytest.approx((2.2 - 1.0) / 96)  # the pace a user reads at
    assert r.stall == pytest.approx(0.4)  # the freeze a user sees: one chunk
    gaps = [b - a for a, b in zip(deltas, deltas[1:])]
    assert stats.percentile(gaps, 95) == 0.0  # why the raw gaps' p95 is not a metric


def test_single_token_requests_have_no_pace():
    r = rec(0.0, [0.2])
    assert r.tpot is None and r.stall is None and r.ok


@pytest.mark.parametrize("values,q,want", [
    ([1.0], 95, 1.0),
    ([1.0, 2.0], 50, 1.5),
    (list(range(1, 101)), 95, 95.05),
    ([5.0, 1.0, 3.0], 0, 1.0),
    ([5.0, 1.0, 3.0], 100, 5.0),
])
def test_percentile_interpolates_between_closest_ranks(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


@pytest.mark.parametrize("kw", [
    dict(status=429, deltas=[]), dict(status=503, deltas=[]), dict(done=False, deltas=[1.0]),
    dict(error="server_error", deltas=[1.0]), dict(finish=None, deltas=[1.0]), dict(deltas=[]),
])
def test_refused_errored_and_unfinished_requests_fail(kw):
    deltas = kw.pop("deltas")
    assert not rec(0.0, deltas, **kw).ok


@pytest.mark.parametrize("case,kw", [
    ("the model ended it: the benchmark's weights have no EOS", dict(finish="stop", asked=64)),
    ("a delta short of the asked tokens", dict(finish="length", asked=3)),
    ("a delta too many", dict(finish="length", asked=1)),
])
def test_a_stream_that_is_not_its_asked_tokens_fails(case, kw):
    assert not rec(0.0, [0.1, 0.2], **kw).ok, case
    assert rec(0.0, [0.1, 0.2], finish="length", asked=2).ok


def test_window_accounting():
    t0, seconds = 100.0, 10.0
    a = rec(101.0, [101.5, 102.0, 102.5], index=0)
    b = rec(109.0, [109.9, 110.5, 111.0], index=1)  # ends after the window: still a sample
    c = rec(105.0, [], status=429, index=2)
    lead_in = rec(99.0, [99.5, 100.5, 101.0], index=3)  # due before: tokens count, latency does not
    everything = [a, b, c, lead_in]
    metrics, details = stats.end_to_end([a, b, c], t0, seconds,
                                        [t for r in everything for t in r.deltas],
                                        ["ttft_p95_ms", "ttft_p50_ms", "out_tok_s", "setup_s"])
    assert set(metrics) == {"ttft_p95_ms", "ttft_p50_ms", "out_tok_s"}  # setup_s is the caller's
    assert metrics["ttft_p50_ms"] == pytest.approx(700.0)
    assert details["samples"] == {"ttft": 2, "tpot": 2, "stall": 2}
    assert details["attempted"] == 3 and details["failed"] == 1 and details["completed"] == 2
    assert metrics["out_tok_s"] == pytest.approx((3 + 1 + 2) / seconds)
    assert metrics["ttft_p95_ms"] == pytest.approx(stats.percentile([500.0, 900.0], 95))
    assert details["failures"][0]["status"] == 429
    assert details["send_lag_max_ms"] == pytest.approx(1.0)


def test_a_suffix_after_the_first_dot_names_the_same_quantity():
    r = rec(1.0, [1.2, 1.5, 2.5])
    metrics, _ = stats.end_to_end([r], 0.0, 5.0, r.deltas,
                                  ["tpot_p50_ms", "tpot_p50_ms.batch", "stall_p50_ms.batch", "out_tok_s.x", "nope.batch"])
    assert set(metrics) == {"tpot_p50_ms", "tpot_p50_ms.batch", "stall_p50_ms.batch", "out_tok_s.x"}
    assert metrics["tpot_p50_ms.batch"] == metrics["tpot_p50_ms"] == pytest.approx(650.0)
    assert metrics["stall_p50_ms.batch"] == pytest.approx(1000.0) and metrics["out_tok_s.x"] == pytest.approx(0.6)


def _three_requests():
    # ttft 200, 400, 900 ms; tpot 150, 300 ms and none (one delta); stall 200, 500 ms and none
    return [rec(1.0, [1.2, 1.3, 1.5], index=0), rec(2.0, [2.4, 2.5, 3.0], index=1),
            rec(3.0, [3.9], index=2)]


@pytest.mark.parametrize("case,records,names,want", [
    ("a mean is the sum over the completed requests' samples over their count", _three_requests(),
     ["ttft_mean_ms", "tpot_mean_ms", "stall_mean_ms"],
     {"ttft_mean_ms": (200 + 400 + 900) / 3, "tpot_mean_ms": (150 + 300) / 2, "stall_mean_ms": (200 + 500) / 2}),
    ("it is not the median, which the same samples still give", _three_requests(),
     ["ttft_mean_ms.batch", "ttft_p50_ms"], {"ttft_mean_ms.batch": 500.0, "ttft_p50_ms": 400.0}),
    ("a failed request gives no sample", _three_requests() + [rec(4.0, [9.0, 9.5], finish="stop", index=3),
                                                              rec(4.0, [], status=429, index=4)],
     ["ttft_mean_ms", "stall_mean_ms"], {"ttft_mean_ms": 500.0, "stall_mean_ms": 350.0}),
    ("a name with a suffix reads the same quantity", _three_requests(),
     ["stall_mean_ms", "stall_mean_ms.batch", "stall_mean_ms.rows16"],
     {"stall_mean_ms": 350.0, "stall_mean_ms.batch": 350.0, "stall_mean_ms.rows16": 350.0}),
    ("an unknown form is left to the caller", _three_requests(),
     ["ttft_mean_ms", "ttft_max_ms", "ttft_mean_s", "queue_mean_ms", "ttft_mean", "ttft_pmean_ms"],
     {"ttft_mean_ms": 500.0}),
    ("no completed request, no mean", [rec(1.0, [], status=503)], ["ttft_mean_ms", "tpot_mean_ms"], {}),
])
def test_the_mean_form(case, records, names, want):
    metrics, _ = stats.end_to_end(records, 0.0, 10.0, [], names)
    assert set(metrics) == set(want), case
    for name, value in want.items():
        assert metrics[name] == pytest.approx(value), f"{case}: {name}"


def test_a_window_with_no_completed_request_reports_no_latency():
    metrics, details = stats.end_to_end([rec(1.0, [], status=503)], 0.0, 5.0, [],
                                        ["ttft_p95_ms", "out_tok_s"])
    assert "ttft_p95_ms" not in metrics and metrics["out_tok_s"] == 0.0 and details["failed"] == 1


TEXT = """# HELP dllama_tokens_generated_total tokens
# TYPE dllama_tokens_generated_total counter
dllama_tokens_generated_total 120
dllama_request_stage_seconds_sum{stage="queue",tenant="default"} 1.5
dllama_request_stage_seconds_count{stage="queue",tenant="default"} 10
dllama_request_stage_seconds_sum{stage="prefill",tenant="default"} 9.0
dllama_request_stage_seconds_count{stage="prefill",tenant="default"} 10
dllama_prefix_cache_matched_tokens_sum 640
"""
LATER = TEXT.replace(" 120", " 300").replace("} 1.5", "} 2.5").replace(
    '"queue",tenant="default"} 10', '"queue",tenant="default"} 20').replace(" 640", " 1280")


def test_prometheus_deltas_and_label_filters():
    before, after = prom.parse(TEXT), prom.parse(LATER)
    assert prom.delta(before, after, "dllama_tokens_generated_total") == 180
    assert prom.delta(before, after, "dllama_request_stage_seconds_sum", {"stage": "queue"}) == pytest.approx(1.0)
    assert prom.delta(before, after, "dllama_request_stage_seconds_sum", {"stage": "prefill"}) == 0
    assert prom.delta(before, after, "no_such_metric") is None
    assert prom.delta([], after, "dllama_tokens_generated_total") == 300  # a series born in the window


@pytest.mark.parametrize("spec,want", [
    ({"kind": "counter_delta", "metric": "dllama_tokens_generated_total"}, 180.0),
    ({"kind": "histogram_mean", "metric": "dllama_request_stage_seconds",
      "labels": {"stage": "queue"}, "scale": 1000}, 100.0),
    ({"kind": "histogram_mean", "metric": "dllama_request_stage_seconds",
      "labels": {"stage": "prefill"}}, None),  # nothing observed in the window
    ({"kind": "counter_delta", "metric": "absent_total"}, None),
    ({"kind": "fact", "key": "control.compiles_in_window"}, 0.0),
    ({"kind": "fact", "key": "absent"}, None),
    ({"kind": "ratio", "scale": 100,
      "num": {"kind": "counter_delta", "metric": "dllama_prefix_cache_matched_tokens_sum"},
      "den": {"kind": "fact", "key": "gen.prompt_tokens_in_window"}}, 50.0),
    ({"kind": "ratio", "num": {"kind": "fact", "key": "absent"},
      "den": {"kind": "fact", "key": "gen.prompt_tokens_in_window"}}, None),
    ({"kind": "hbm_share", "modules": "decode_chunk", "bytes": "model.decode_step_bytes",
      "steps_per_call": "server.decode_chunk"}, 100.0 * 4e9 * (10 * 32) / 4.0 / 819e9),
    ({"kind": "hbm_share", "modules": "no_such_program", "bytes": "model.decode_step_bytes",
      "steps_per_call": "server.decode_chunk"}, None),
])
def test_reader_kinds(spec, want):
    facts = {"control.compiles_in_window": 0.0, "gen.prompt_tokens_in_window": 1280.0,
             "trace.modules": {"jit_decode_chunk_batched_paged": {"count": 10, "seconds": 4.0},
                               "jit__slab_prefill_single_paged": {"count": 3, "seconds": 0.5}},
             "model.decode_step_bytes": 4e9, "server.decode_chunk": 32.0,
             "peaks": {"hbm_bytes_per_s": 819e9}}
    got = readers._read(spec, readers.Context(prom.parse(TEXT), prom.parse(LATER), facts))
    assert got is None if want is None else got == pytest.approx(want)


def test_unknown_reader_kind_is_an_error():
    with pytest.raises(ValueError):
        readers._read({"kind": "guess"}, readers.Context([], [], {}))


def test_nan_sent_instants_do_not_reach_the_lag():
    r = stats.Record(index=0, due=1.0)  # never sent
    assert math.isnan(r.sent)
    _, details = stats.end_to_end([r], 0.0, 5.0, [], [])
    assert details["send_lag_p50_ms"] is None and details["send_lag_max_ms"] is None
