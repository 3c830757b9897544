"""Disabled-telemetry overhead micro-benchmark (ISSUE 1, marked slow).

The telemetry design contract is zero overhead when disabled: the decode
hot path holds pre-bound null instruments whose methods are no-ops, and
the only added work versus the seed's hand-rolled ``perf_counter`` deltas
is those no-op calls (once per DISPATCH, never per token).

This test measures that added work directly and bounds it against the
documented decode budget: docs/PERF.md puts one-chip Q40 decode at
~8.7 ms/token, and the chunked serving path records telemetry once per
32-token dispatch (~278 ms of device work). The per-dispatch overhead must
stay under 1% of the PER-TOKEN budget — orders of magnitude stricter than
the real per-dispatch budget, so a pass here implies <<1% end-to-end.

A real A/B against the seed binary is impossible in-tree (the seed has no
telemetry to disable); bounding the delta-work against the measured token
budget is the honest equivalent.
"""

import time

import pytest

from distributed_llama_tpu import telemetry
from distributed_llama_tpu.telemetry import Stopwatch

# docs/PERF.md: Q40 decode ~8.7-9.1 ms/token on one v5e chip; use the fast
# end so the bound is conservative
DECODE_MS_PER_TOKEN = 8.7
N = 20_000


def _seed_pattern_cost(n: int) -> float:
    """Per-iteration seconds of the seed's hand-rolled timing pattern."""
    acc = 0.0
    t_start = time.perf_counter()
    for _ in range(n):
        start = time.perf_counter()
        acc += (time.perf_counter() - start) * 1000.0
    total = time.perf_counter() - t_start
    assert acc >= 0.0
    return total / n


def _telemetry_pattern_cost(n: int) -> float:
    """Per-iteration seconds of the replacement pattern with telemetry
    DISABLED: Stopwatch + the exact null-instrument calls the engine's
    _note_decode/_note_prefill and span sites make per dispatch."""
    assert not telemetry.is_enabled()

    class Tel:  # mirror of EngineInstruments' disabled binding
        enabled = False
        span = staticmethod(telemetry.span_factory())
        tokens_generated = telemetry.counter("x_total")
        decode_latency = telemetry.histogram("x_seconds")
        kv_occupancy = telemetry.gauge("x_occ")

    tel = Tel()
    acc = 0.0
    t_start = time.perf_counter()
    for _ in range(n):
        sw = Stopwatch()
        with tel.span("decode_chunk_dispatch", pos=0, steps=32):
            pass
        per_token_ms = sw.elapsed_ms() / 32
        if tel.enabled:  # the engine's guard: skipped entirely when disabled
            tel.tokens_generated.inc(32)
            tel.decode_latency.observe(per_token_ms / 1000.0)
            tel.kv_occupancy.set(0.5)
        acc += per_token_ms
    total = time.perf_counter() - t_start
    assert acc >= 0.0
    return total / n


@pytest.mark.slow
def test_disabled_telemetry_decode_overhead_under_1_percent():
    telemetry.reset()
    telemetry.disable()
    # warm both paths (bytecode caches, branch predictors), then measure
    _seed_pattern_cost(1000)
    _telemetry_pattern_cost(1000)
    seed_s = _seed_pattern_cost(N)
    tel_s = _telemetry_pattern_cost(N)

    added_ms_per_dispatch = max(0.0, (tel_s - seed_s)) * 1000.0
    budget_ms = DECODE_MS_PER_TOKEN * 0.01  # 1% of ONE token's budget
    assert added_ms_per_dispatch < budget_ms, (
        f"disabled-telemetry pattern adds {added_ms_per_dispatch * 1000:.2f} µs "
        f"per dispatch; budget is {budget_ms * 1000:.0f} µs (1% of one "
        f"{DECODE_MS_PER_TOKEN} ms token — and telemetry records once per "
        f"32-token dispatch, so the real margin is 32x wider)"
    )
    # and nothing leaked into the registry
    assert telemetry.REGISTRY.names() == []


@pytest.mark.slow
def test_null_instrument_calls_are_submicrosecond():
    """The raw no-op calls themselves: sub-µs each, so even a site that
    fired per token would sit far under 1% of the token budget."""
    telemetry.disable()
    c = telemetry.counter("y_total")
    h = telemetry.histogram("y_seconds")
    g = telemetry.gauge("y_g")
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
        h.observe(0.001)
        g.set(1.0)
    per_call_us = (time.perf_counter() - t0) / (3 * n) * 1e6
    assert per_call_us < 5.0, f"null instrument call costs {per_call_us:.2f} µs"


def test_with_telemetry_off_the_scheduler_starts_no_ledger_thread_and_appends_nothing(tmp_path):
    """The completion ledger (ISSUE 41) is on where ``--telemetry`` is on and
    nowhere else: with it off the scheduler binds the shared null ledger (no
    watcher thread, no queue, no clock read) and a served request leaves the
    registry untouched."""
    import threading

    from distributed_llama_tpu.engine.batch import BatchScheduler
    from distributed_llama_tpu.telemetry import device_ledger

    from tests.test_batch_decode import PROMPTS, batch_stream_tokens, build_engine

    telemetry.reset()
    telemetry.disable()
    before = set(threading.enumerate())  # an earlier test's schedulers may have left theirs
    engine = build_engine(tmp_path)
    sched = BatchScheduler(engine, n_rows=2, chunk=4)
    assert sched._ledger is device_ledger.NULL_LEDGER and not sched._ledger.enabled
    stream = sched.new_stream()
    assert len(batch_stream_tokens(stream, PROMPTS[0], 0.0, 0.9, 3, 6)) == 6
    assert sched._last_piece_entry is device_ledger.NULL_ENTRY
    assert sched._prompts_open == 0  # the work in hand is not tracked either
    assert {t.name for t in set(threading.enumerate()) - before} == set()
    assert telemetry.REGISTRY.names() == []
    sched.close()
