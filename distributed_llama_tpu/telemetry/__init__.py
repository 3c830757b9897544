"""Unified telemetry: metrics registry + span tracer + exposition (ISSUE 1).

One process-global :class:`~distributed_llama_tpu.telemetry.registry.MetricsRegistry`
and one :class:`~distributed_llama_tpu.telemetry.tracer.SpanTracer` back every
instrument in the engine, the parallel backends and the API server.
The reference engine's only observability is ad-hoc stat prints
(reference: src/apps/dllama/dllama.cpp:49-93); this module is the shared sink.

Toggling
--------
Telemetry is OFF by default. Enable with the ``--telemetry`` CLI flag
(dllama-tpu / dllama-tpu-api) or ``DLLAMA_TELEMETRY=1`` in the
environment (read once at import). ``enable()`` / ``disable()`` switch the
process at runtime, but instruments are BOUND at component construction:
code binds once (engine ``__init__``, server startup) via :func:`counter` /
:func:`gauge` / :func:`histogram` / the ``span`` factory, and gets back

* the real registry-registered instrument when telemetry is enabled, or
* a shared null singleton whose methods are no-ops when it is disabled.

That bind-once contract is the zero-overhead-when-disabled design: the hot
decode loop holds direct attribute references, pays one no-op method call
per *dispatch* (never per token), performs no dict lookups, and never
mutates the registry. Components constructed before ``enable()`` keep their
null instruments — construct (or rebind) after enabling.

Metric names are listed in docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import os
import time

from distributed_llama_tpu.telemetry.registry import (  # noqa: F401  (re-export)
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from distributed_llama_tpu.telemetry.tracer import (  # noqa: F401  (re-export)
    NULL_SPAN,
    SpanTracer,
)

REGISTRY = MetricsRegistry()
TRACER = SpanTracer()

_ENV_VAR = "DLLAMA_TELEMETRY"
_enabled = os.environ.get(_ENV_VAR, "").strip().lower() in ("1", "true", "on", "yes")


def is_enabled() -> bool:
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Clear the registry and the span ring buffer (tests)."""
    REGISTRY.reset()
    TRACER.clear()


# ----------------------------------------------------------------------
# Null instruments: the disabled-mode bind targets. One shared stateless
# singleton per kind — no locks, no values, no registry entry. Tradeoff:
# .labels(...) cannot validate label NAMES here (the shared singleton
# knows no declaration, and a per-call check would tax the disabled hot
# path), so a labelnames typo only surfaces when telemetry is enabled —
# every labelled call site must therefore be covered by an enabled-mode
# test (tests/test_telemetry.py does this for all current sites).
# ----------------------------------------------------------------------


class _NullCounter:
    __slots__ = ()
    value = 0.0

    def inc(self, n: float = 1.0) -> None:
        pass

    def labels(self, **kw):
        return self


class _NullGauge:
    __slots__ = ()
    value = 0.0

    def set(self, v: float) -> None:
        pass

    def inc(self, n: float = 1.0) -> None:
        pass

    def dec(self, n: float = 1.0) -> None:
        pass

    def labels(self, **kw):
        return self


class _NullHistogram:
    __slots__ = ()
    count = 0
    sum = 0.0

    def observe(self, v: float) -> None:
        pass

    def labels(self, **kw):
        return self


NULL_COUNTER = _NullCounter()
NULL_GAUGE = _NullGauge()
NULL_HISTOGRAM = _NullHistogram()


def counter(name: str, help: str = "", labelnames=()) -> Counter | _NullCounter:
    if not _enabled:
        return NULL_COUNTER
    return REGISTRY.counter(name, help, labelnames)


def gauge(name: str, help: str = "", labelnames=()) -> Gauge | _NullGauge:
    if not _enabled:
        return NULL_GAUGE
    return REGISTRY.gauge(name, help, labelnames)


def histogram(
    name: str, help: str = "", labelnames=(), buckets=DEFAULT_LATENCY_BUCKETS
) -> Histogram | _NullHistogram:
    if not _enabled:
        return NULL_HISTOGRAM
    return REGISTRY.histogram(name, help, labelnames, buckets=buckets)


def _null_span(name: str, **args):
    return NULL_SPAN


def _real_span(name: str, **args):
    return TRACER.span(name, **args)


def span_factory():
    """The span entry point to BIND at construction time: returns either the
    live tracer's span() or a factory handing out the shared no-op span."""
    return _real_span if _enabled else _null_span


def trace_span(name: str, **args):
    """``with trace_span("decode", step=pos):`` — checks the enable flag per
    call; hot paths should bind :func:`span_factory` once instead."""
    return (_real_span if _enabled else _null_span)(name, **args)


def prometheus_text() -> str:
    return REGISTRY.prometheus_text()


def chrome_trace() -> dict:
    return TRACER.chrome_trace()


def export_chrome_trace(path: str) -> str:
    return TRACER.export_chrome_trace(path)


# ----------------------------------------------------------------------
# Shared wall-clock helper: the ONE copy of the perf-timing pattern that
# engine/engine.py and parallel/tensor_parallel.py used to hand-roll.
# ----------------------------------------------------------------------


class Stopwatch:
    """``sw = Stopwatch(); ...; ms = sw.elapsed_ms()`` — restartable, on
    ``time.monotonic`` (the one clock of spans, request traces, flight
    events and the benchmark's client), so ``_t0`` is an instant a
    request trace's ``add_span`` takes as it is."""

    __slots__ = ("_t0",)

    def __init__(self):
        self._t0 = time.monotonic()

    def restart(self) -> None:
        self._t0 = time.monotonic()

    def elapsed_s(self) -> float:
        return time.monotonic() - self._t0

    def elapsed_ms(self) -> float:
        return (time.monotonic() - self._t0) * 1000.0


# ----------------------------------------------------------------------
# Instrument bundles: each subsystem binds its instruments in one place so
# hot code holds plain attributes (and can skip whole blocks on .enabled).
# ----------------------------------------------------------------------


class EngineInstruments:
    """The engine's metric surface (bound once per InferenceEngine)."""

    def __init__(self):
        self.enabled = _enabled
        self.span = span_factory()
        self.tokens_generated = counter(
            "dllama_tokens_generated_total",
            "Decoded tokens delivered to engine streams: under the batched "
            "scheduler WHOLE chunks per row, the chunk dispatched ahead of "
            "a stream's end included — an upper bound on what clients "
            "receive (dllama_tokens_streamed_total counts those; "
            "dllama_decode_row_steps_total says what became of the rest)",
        )
        self.prompt_tokens = counter(
            "dllama_prompt_tokens_total",
            "Prompt tokens prefilled across all engine streams",
        )
        self.prefill_latency = histogram(
            "dllama_prefill_latency_seconds",
            "Wall time of one batched prefill (dispatch+fetch, whole prompt)",
        )
        self.decode_latency = histogram(
            "dllama_decode_latency_seconds",
            "PER-TOKEN decode wall time, observed once per device dispatch "
            "(a chunked dispatch contributes one observation at its per-token "
            "average; dllama_tokens_generated_total counts the tokens)",
        )
        # device-resident sampling (ISSUE 13): the happy-path witness —
        # tokens whose temperature/top-k/top-p draw ran INSIDE the decode
        # program (counter-PRNG coins, no logits fetch, no host sort);
        # dllama_host_sampler_fallback_total counts the complement
        self.device_sampled_tokens = counter(
            "dllama_device_sampled_tokens_total",
            "Tokens sampled on device by the fused decode-scan sampler "
            "(greedy argmax rows included); only int32 token ids crossed "
            "the host for these",
        )
        self.kv_occupancy = gauge(
            "dllama_kv_cache_occupancy",
            "KV-cache occupancy, 0..1: under the batched scheduler the joined "
            "rows' positions over rows x seq_len, set once a chunk dispatch; "
            "on the single-stream engine the stream's position / seq_len",
        )
        self.active_streams = gauge(
            "dllama_engine_streams",
            "Engine streams constructed (each owns one KV cache of HBM)",
        )
        # the batched scheduler's ledger (ISSUE 23): per CHUNK, never per
        # token. Every row-step a decode chunk computed (bucket rows x
        # steps) ends under exactly one fate, so the fates sum to the
        # device's decode work once the streams have left
        row_steps = counter(
            "dllama_decode_row_steps_total",
            "Row-steps computed by batched decode chunks (bucket rows x "
            "steps; a spec-verify step counts a row's emitted tokens, 1 "
            "for a row that emitted none), by what became of them: masked "
            "(bucket row not joined), orphaned (the row left or changed "
            "occupant before delivery: the chunk dispatched ahead), "
            "quarantined (row retired by a fault), unread (delivered to a "
            "stream's queue, still there when the stream left), consumed "
            "(popped by the stream)",
            labelnames=("fate",),
        )
        self.row_steps_masked = row_steps.labels(fate="masked")
        self.row_steps_orphaned = row_steps.labels(fate="orphaned")
        self.row_steps_quarantined = row_steps.labels(fate="quarantined")
        self.row_steps_unread = row_steps.labels(fate="unread")
        self.row_steps_consumed = row_steps.labels(fate="consumed")
        chunk_rows = histogram(
            "dllama_decode_chunk_rows",
            "Rows of each dispatched batched decode chunk: kind=active the "
            "joined rows, kind=bucket the power-of-two bucket dispatched "
            "(sum(active)/sum(bucket) is the mean row fill)",
            labelnames=("kind",),
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0),
        )
        self.chunk_rows_active = chunk_rows.labels(kind="active")
        self.chunk_rows_bucket = chunk_rows.labels(kind="bucket")
        chunk_sampler = counter(
            "dllama_decode_chunk_sampler_total",
            "Dispatched batched decode chunks by the arm their steps' sampler "
            "took, read from the temperature vector the program was handed: "
            "greedy (every row's temperature is 0: argmax alone, the softmax, "
            "the coin and the top-k are skipped) or sampled (some row "
            "samples: the whole sampler runs for every row)",
            labelnames=("path",),
        )
        self.chunk_sampler_greedy = chunk_sampler.labels(path="greedy")
        self.chunk_sampler_sampled = chunk_sampler.labels(path="sampled")
        self.chunk_host = histogram(
            "dllama_chunk_host_seconds",
            "Host time of one batched decode chunk NOT spent waiting on "
            "the device: build + dispatch + post-dispatch + delivery (the "
            "host gap between chunks)",
        )
        self.chunk_fetch_wait = histogram(
            "dllama_chunk_fetch_wait_seconds",
            "Time one batched decode chunk's fetch blocked on the device "
            "(the np.asarray of its token bundle)",
        )
        self.chunk_build = histogram(
            "dllama_chunk_build_seconds",
            "Time the scheduler's lock was held to build and dispatch one "
            "batched decode chunk (the first part of "
            "dllama_chunk_host_seconds): consumers and prompt pieces wait "
            "it out, so it should not grow with the chunk's rows",
        )
        # an expert layer that holds a share of the experts (ISSUE 26): the
        # programs return, with their tokens, how many of their (token,
        # choice) assignments fell on a held expert
        moe_assignments = counter(
            "dllama_moe_assignments_total",
            "(token, chosen expert) assignments of the expert layers, summed "
            "over layers: held=yes those that chose an expert this process "
            "holds (computed here), held=no those left to absent experts; "
            "yes / (yes + no) is the held share, held / routed when routing "
            "is even",
            labelnames=("held",),
        )
        self.moe_assigned_held = moe_assignments.labels(held="yes")
        self.moe_assigned_absent = moe_assignments.labels(held="no")
        self.moe_rows_per_expert = histogram(
            "dllama_moe_rows_per_expert",
            "Rows one held expert received in one layer of one forward step "
            "(a decode step, or a prefill chunk), observed once a program as "
            "the mean over its steps, layers and held experts",
            buckets=(0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0),
        )
        moe_piece_layers = counter(
            "dllama_moe_piece_layers_total",
            "Expert layers of the prompt pieces, one count a layer a piece, by "
            "the path the layer took: bucketed (each expert multiplied the "
            "real rows that chose it, in its own bucket) or every_row (some "
            "expert had more rows than its bucket, or the piece was too small "
            "to bucket, and every expert multiplied every row); returned by "
            "the prefill programs and read when a later decode chunk is "
            "delivered",
            labelnames=("path",),
        )
        self.moe_piece_bucketed = moe_piece_layers.labels(path="bucketed")
        self.moe_piece_every_row = moe_piece_layers.labels(path="every_row")
        moe_expert_rows = counter(
            "dllama_moe_expert_rows_total",
            "Rows the HELD experts of an arch that holds a share multiplied "
            "(rows=computed) against rows that chose them (rows=chosen), "
            "summed over layers and held experts, from the programs' own "
            "counts: a layer on a bucketed arm that fits computes the rows "
            "that chose (1.0), a layer on the every-row arm every row of the "
            "program in every held expert (routed / k times the chosen when "
            "routing is even). rows=launched: the rows the grouped launches "
            "multiplied, a bucket's pad rows up to its expert's last live "
            "row tile among them (whole tiles of 32 rows of a bucket of "
            "more, the whole bucket of one of at most 32; every row of the "
            "program in each expert some row chose on the every-row arm). "
            "phase=piece the prompt pieces (their arm comes "
            "back with their results), phase=decode the decode chunks (the "
            "layer-steps that took the every-row arm, no bucket under the "
            "step's rows or one that overflowed, come back with their tokens)",
            labelnames=("rows", "phase"),
        )
        self.moe_rows_computed = {p: moe_expert_rows.labels(rows="computed", phase=p)
                                  for p in ("decode", "piece")}
        self.moe_rows_chosen = {p: moe_expert_rows.labels(rows="chosen", phase=p)
                                for p in ("decode", "piece")}
        self.moe_rows_launched = {p: moe_expert_rows.labels(rows="launched", phase=p)
                                  for p in ("decode", "piece")}
        self.q40_padded_weight_bytes = gauge(
            "dllama_q40_padded_weight_bytes",
            "Bytes of the resident Q40 leaves of one role (the params leaf's "
            "name: ssm_in, experts_down, wcls, ...) that are tile padding: "
            "zero-scale rows and columns the int8 kernel's tiles read and "
            "multiply like any, set at load; 0 for a role whose matrices "
            "divide their tiles",
            labelnames=("role",),
        )
        self.recurrent_state_bytes = gauge(
            "dllama_recurrent_state_bytes",
            "Bytes of recurrent state and convolution tails the slab's rows "
            "hold (linear-attention or state-space layers; does not grow with "
            "a row's length)",
        )
        state_tokens = counter(
            "dllama_state_layer_tokens_total",
            "Tokens the recurrent layers advanced a row's state by, summed "
            "over the layers of that kind (mixer: linear = the gated delta "
            "rule, kernels kda_step / kda_chunk; ssm = the state-space "
            "recurrence, kernels ssd_step / ssd_chunk): phase=decode the "
            "row-steps of the decode chunks delivered (joined rows x steps), "
            "phase=prefill the real tokens of the prompt pieces dispatched",
            labelnames=("mixer", "phase"),
        )
        self.state_layer_tokens = {
            (mixer, phase): state_tokens.labels(mixer=mixer, phase=phase)
            for mixer in ("linear", "ssm") for phase in ("decode", "prefill")
        }
        kv_read = counter(
            "dllama_attn_kv_read_bytes_total",
            "Bytes of keys and values the decode chunks' attention read out of "
            "slab and pool, by layer kind: full (each row's own chunks where "
            "the row-bounded kernel serves; under the XLA scan every chunk up "
            "to the bucket's longest row, every row alike) and window "
            "(the window's positions of each row's ring); for an arch with EVA "
            "layers by store: eva_window (the window store's chunks up to the "
            "row's slot in its window) and eva_summary (the summaries' chunks "
            "up to the row's depth; under the XLA scan the bucket's farthest "
            "and deepest row's); for an arch of latent-"
            "attention layers: latent (every chunk up to the bucket's longest "
            "row, of rows that hold one latent a position and no key or value) "
            "and, where such a layer has an indexer (a learned sparse "
            "selection), index (the index keys its indexer scored, every chunk "
            "up to the bucket's longest row) and latent_selected (the latent "
            "rows the softmax ran over: the selected ones, part of what kind "
            "latent read while the selected attention is a masked pass); "
            "counted by the programs from their scans' bounds and returned "
            "with their tokens",
            labelnames=("kind",),
        )
        self.kv_read = {
            kind: kv_read.labels(kind=kind)
            for kind in ("full", "window", "eva_window", "eva_summary", "latent", "index",
                         "latent_selected")
        }
        kv_read_positions = counter(
            "dllama_attn_kv_read_positions_total",
            "Cache positions the batched decode steps' attention scans read, a "
            "position counted once however many layers read it (kind latent: an "
            "arch of latent-attention layers; index and latent_selected: its "
            "indexer's keys and the rows selected, the mean over the layers, "
            "which select each for itself): dllama_attn_kv_read_bytes_total "
            "of the kind over this is the bytes a row stores of one position, "
            "over all layers",
            labelnames=("kind",),
        )
        self.kv_read_positions = {
            kind: kv_read_positions.labels(kind=kind)
            for kind in ("latent", "index", "latent_selected")
        }
        self.dsa_visible_positions = counter(
            "dllama_dsa_visible_positions_total",
            "Cache positions the batched decode steps' queries could see (a "
            "row at position t sees t + 1) in an arch whose latent layers "
            "have an indexer, a position counted once however many layers: "
            "dllama_attn_kv_read_positions_total{kind=latent_selected} over "
            "this is the share of its context a query's attention ran over",
        )
        self.eva_summaries_written = counter(
            "dllama_eva_summaries_written_total",
            "Chunks an arch with EVA layers summarised: a prompt piece or a "
            "decode chunk that writes a chunk's last position pools the "
            "chunk's keys and values into one summary a head, in every layer; "
            "counted by the scheduler from the positions it dispatches",
        )
        self.kv_slab_bytes = gauge(
            "dllama_kv_slab_bytes",
            "Bytes of keys and values the slab's rows hold, by layer kind: a "
            "full layer's grow with --max-seq-len, a window layer's ring does "
            "not; an EVA arch's by store (eva_window, eva_summary)",
            labelnames=("kind",),
        )
        self.kv_pool_bytes = gauge(
            "dllama_kv_pool_bytes",
            "Bytes of the prefix-cache page pools by layer kind: full "
            "(--kv-pages pages) and window (the last pages of the prompts "
            "published lately: bounded by rows, not by --kv-pages); an EVA "
            "arch's: eva_summary (--kv-pages pages of summaries) and eva_window "
            "(keys and values of live prompts' last windows: bounded by rows)",
            labelnames=("kind",),
        )
        self.prefill_chunks_ahead = histogram(
            "dllama_prefill_chunks_ahead",
            "Decode chunks in flight on the device (one pending, one being "
            "fetched) when a prefill chunk was enqueued behind them: how "
            "long the decode chunk, the scheduler's clock, makes a prompt "
            "wait",
            buckets=(0.0, 1.0, 2.0),
        )
        # fault-tolerance surface (ISSUE 3): quarantines, retries, stalls
        self.rows_quarantined = counter(
            "dllama_rows_quarantined_total",
            "Batch rows retired after a failed or corrupted chunk "
            "(bounded retries exhausted); co-batched rows kept streaming",
        )
        batch_retries = counter(
            "dllama_batch_retries_total",
            "Batched dispatch/fetch attempts retried after a transient "
            "failure (bounded, with backoff)",
            labelnames=("stage",),
        )
        self.dispatch_retries = batch_retries.labels(stage="dispatch")
        self.fetch_retries = batch_retries.labels(stage="fetch")
        self.watchdog_stalls = counter(
            "dllama_watchdog_stalls_total",
            "Hung batched chunks the stall watchdog failed cleanly",
        )
        # multi-tenant serving (ISSUE 8): priority preemption evicts the
        # lowest-priority decode row to a clean requeue — count evictions
        # here (the serving layer counts the successful requeues)
        self.preemptions = counter(
            "dllama_preemptions_total",
            "Decode rows evicted by a higher-priority arrival and requeued "
            "(clean RowPreempted evictions; a chaos-failed eviction counts "
            "as a quarantine instead)",
        )
        # speculative decoding (--spec-draft): draft volume, acceptance and
        # per-step advance — the health read is accepted/draft (the
        # prompt-lookup hit rate) and the advance histogram's mass above 1
        # (how many weight reads the drafts actually saved)
        self.spec_draft_tokens = counter(
            "dllama_spec_draft_tokens_total",
            "Prompt-lookup draft tokens proposed to speculative verify steps",
        )
        self.spec_accepted_tokens = counter(
            "dllama_spec_accepted_tokens_total",
            "Draft tokens accepted by speculative verify (excludes the "
            "per-step bonus/correction token)",
        )
        self.spec_acceptance = histogram(
            "dllama_spec_acceptance_ratio",
            "Accepted/drafted ratio per speculative verify step that "
            "proposed at least one draft token (0..1)",
            buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0),
        )
        self.spec_step_advance = histogram(
            "dllama_spec_step_advance_tokens",
            "Positions advanced per row per speculative verify step "
            "(accepted drafts + 1; plain decode is identically 1)",
            buckets=(1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 32.0),
        )


class DeviceLedgerInstruments:
    """The completion ledger's metric surface (bound once per
    telemetry/device_ledger.py DeviceLedger: one a scheduler). Every series
    exists at 0 from the bind, so a window's delta can be read from the
    first scrape on."""

    def __init__(self):
        from distributed_llama_tpu.telemetry import device_ledger as dl

        self.span = span_factory()
        seconds = counter(
            "dllama_device_seconds_total",
            "Wall seconds since the scheduler bound its ledger, by what the "
            "device was doing: state=busy by the observed program that ran "
            "(decode_chunk, prefill_piece, spec_verify; from max(previous "
            "completion, dispatch) to the earliest instant a thread saw the "
            "program finished; launches that cannot be waited for fall into "
            "the next observed interval), state=idle by whether the scheduler "
            "had work in hand (work_waiting: a joined stream that needs "
            "another chunk, or a prompt not yet wholly dispatched) or not "
            "(no_work); the five series sum to the wall time",
            labelnames=("state", "program"),
        )
        self.busy = {p: seconds.labels(state="busy", program=p) for p in dl.OBSERVED}
        self.idle = {p: seconds.labels(state="idle", program=p) for p in dl.IDLE}
        programs = counter(
            "dllama_device_programs_total",
            "Device programs the scheduler launched, by program: the observed "
            "ones (decode_chunk, prefill_piece, spec_verify) and, counted at "
            "dispatch and never waited for, those whose outputs are all "
            "donated onward (publish, restore, window_tail, spill_slice, "
            "spill_reload, carry_put, sample_row, snapshot)",
            labelnames=("program",),
        )
        self.launches = {p: programs.labels(program=p) for p in dl.OBSERVED + dl.COUNTED}
        piece_rows = counter(
            "dllama_prefill_piece_rows_total",
            "Rows of the dispatched prompt pieces: kind=real the prompt's "
            "tokens, kind=pad what the power-of-two bucket added to them",
            labelnames=("kind",),
        )
        self.piece_rows_real = piece_rows.labels(kind="real")
        self.piece_rows_pad = piece_rows.labels(kind="pad")


class PrefixCacheInstruments:
    """The radix prefix cache's metric surface (bound once per PrefixCache;
    engine/prefix_cache.py + docs/PERF.md)."""

    # matched-prefix length is a token count, not a latency: power-of-two
    # buckets up to a 16k context
    MATCHED_TOKEN_BUCKETS = (
        1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 512.0,
        1024.0, 2048.0, 4096.0, 8192.0, 16384.0,
    )

    def __init__(self):
        self.enabled = _enabled
        self.hits = counter(
            "dllama_prefix_cache_hits_total",
            "Admission prefills that reused at least one published KV page "
            "(the matched prefix skipped recomputation)",
        )
        self.misses = counter(
            "dllama_prefix_cache_misses_total",
            "Admission prefills that matched no published prefix page",
        )
        self.evictions = counter(
            "dllama_prefix_cache_evictions_total",
            "KV pages reclaimed from the radix tree by the LRU evictor "
            "(leaf-first; refcounted pages are never evicted)",
        )
        self.pages = gauge(
            "dllama_prefix_cache_pages",
            "KV pages currently held by the radix tree (the pool size "
            "--kv-pages bounds this; free = pool - this)",
        )
        self.bytes = gauge(
            "dllama_prefix_cache_bytes",
            "Logical KV bytes held by the radix tree's pool pages (pages "
            "gauge x per-page bytes across all layers and both halves): "
            "what a later prompt can resume from (a live hit row on one "
            "chip holds a copy of its matched pages in its slab besides)",
        )
        self.pinned_pages = gauge(
            "dllama_prefix_cache_pinned_pages",
            "Pool pages ref-pinned against eviction — held for the "
            "lifetime of the rows that matched them (a tp row reads them "
            "in place through its page table; a one-chip row has copied "
            "them and keeps the pins all the same), plus publishes in "
            "flight",
        )
        self.restores = counter(
            "dllama_prefix_cache_restores_total",
            "Prefix hits whose matched pages were copied into the row's "
            "slab at admission (one chip: the row then decodes from its "
            "slab alone; a tp backend reads the pool in place and counts "
            "nothing here)",
        )
        self.restored_bytes = counter(
            "dllama_prefix_cache_restored_bytes_total",
            "KV bytes copied from pool pages into rows by those restores "
            "(matched pages x per-page bytes per hit): what a hit pays "
            "once so that no decode step reads the pool",
        )
        self.matched_tokens = histogram(
            "dllama_prefix_cache_matched_tokens",
            "Prompt tokens satisfied from the prefix cache per hit "
            "(page-granular)",
            buckets=self.MATCHED_TOKEN_BUCKETS,
        )
        # recurrent-state snapshots published with a prefix (ISSUE 26): a
        # hit resumes only from a page boundary that has one
        snapshots = counter(
            "dllama_state_snapshots_total",
            "Recurrent-state snapshots of linear-attention rows by event: "
            "taken (copied out of a row at a page boundary of its admission "
            "prefill), published (attached to the radix node that ends "
            "there), restored (copied into a row on a prefix hit), evicted "
            "(slot reclaimed: with its page, or LRU when the slots ran out)",
            labelnames=("event",),
        )
        self.snapshots_taken = snapshots.labels(event="taken")
        self.snapshots_published = snapshots.labels(event="published")
        self.snapshots_restored = snapshots.labels(event="restored")
        self.snapshots_evicted = snapshots.labels(event="evicted")
        window_tail = counter(
            "dllama_prefix_window_tail_total",
            "Prefix matches of an arch with window-attention layers by what the "
            "window layers' pool still held: hit (the pages before the matched "
            "chain's end were there: the whole chain served), shortened (the "
            "chain was cut back to the deepest block whose tail was kept; for "
            "EVA layers at the latest to its window's start, which needs "
            "summaries only), miss (no block's was: the prompt prefilled from 0)",
            labelnames=("outcome",),
        )
        self.window_tail_hit = window_tail.labels(outcome="hit")
        self.window_tail_shortened = window_tail.labels(outcome="shortened")
        self.window_tail_miss = window_tail.labels(outcome="miss")
        self.window_pages_evicted = counter(
            "dllama_prefix_window_pages_evicted_total",
            "Pages of the window layers' pool taken from the block that went "
            "longest without a hit using it (the block stays in the tree)",
        )
        # host-RAM / disk spill tier (ISSUE 11, engine/spill.py): the
        # capacity ladder below the HBM pool
        self.spill_pages = counter(
            "dllama_prefix_spill_pages_total",
            "Evicted prefix pages whose bytes spilled to the host-RAM "
            "arena instead of vanishing (data+scales verbatim for i8)",
        )
        self.spill_reloads = counter(
            "dllama_prefix_spill_reloads_total",
            "Spilled prefix pages re-uploaded into a device pool on a "
            "later admission match (re-upload ≪ re-prefill; CRC-verified)",
        )
        self.spill_dropped = counter(
            "dllama_prefix_spill_dropped_total",
            "Spilled prefix pages LOST from the capacity ladder: LRU "
            "overflow past the host/disk budgets, or a CRC mismatch "
            "detected at reload (the entry is dropped, the block "
            "prefills cold)",
        )
        self.spill_resident_pages = gauge(
            "dllama_prefix_spill_resident_pages",
            "Spilled pages currently resident in the arena (host RAM + "
            "disk tier), across all replicas",
        )
        self.spill_bytes = gauge(
            "dllama_prefix_spill_bytes",
            "Bytes currently resident in the host-RAM spill arena "
            "(the --host-spill-mb budget bounds this; disk-tier bytes "
            "are not included)",
        )


class SpillArenaInstruments:
    """What the shared host arena (engine/spill.py) counts itself: it is
    one object for every replica, and its spiller thread moves some of
    these with no scheduler's lock held."""

    def __init__(self):
        self.dropped = counter(
            "dllama_prefix_spill_dropped_total",
            "Spilled prefix pages LOST from the capacity ladder: LRU "
            "overflow past the host/disk budgets, or a CRC mismatch "
            "detected at reload (the entry is dropped, the block "
            "prefills cold)",
        )
        self.pending = gauge(
            "dllama_prefix_spill_pending_pages",
            "Evicted pages entered in the arena whose bytes are still on "
            "their way from the device (sliced under the scheduler's lock, "
            "fetched and checksummed by the arena's spiller thread off it); "
            "counted in dllama_prefix_spill_resident_pages and _bytes too",
        )
        self.skipped = counter(
            "dllama_prefix_spill_skipped_total",
            "Evicted pages never fetched from the device because the arena "
            "could not have kept them: the victims of one publish put in "
            "order overflow a host budget with no disk tier below it, so "
            "the first of them would be dropped as the last arrive (each is "
            "counted in spill_pages_total and spill_dropped_total as before)",
        )
        pending_reloads = counter(
            "dllama_prefix_spill_pending_reloads_total",
            "Reloads that met a page still on its way to the arena, by "
            "outcome: waited (the request's own thread waited for the "
            "transfer before it took the scheduler's lock), cold (still "
            "pending under the lock, where nothing waits: the block "
            "prefilled cold and its publish cancelled the pending entry)",
            labelnames=("outcome",),
        )
        self.pending_waited = pending_reloads.labels(outcome="waited")
        self.pending_cold = pending_reloads.labels(outcome="cold")


def note_compile_cache_hit() -> None:
    """Count one persistent-compilation-cache hit (a compile served from
    the persistent cache directory instead of a fresh XLA build — the
    cold-prefill attack). Called from the jax monitoring
    listener platform.enable_compilation_cache installs; cache events are
    rare, so the registry lookup per event is fine (no bind-once needed)."""
    if _enabled:
        REGISTRY.counter(
            "dllama_compile_cache_hits_total",
            "jit compiles served from the persistent XLA compilation cache",
        ).inc()


def bind_compile_counters() -> None:
    """Register ``dllama_compiles_total`` / ``dllama_compile_seconds_total``
    at 0 so a scrape before the first build already shows them (a window's
    delta of a series that does not exist cannot be read)."""
    if _enabled:
        note_compile(0.0, count=0)


def note_compile(seconds: float, count: int = 1) -> None:
    """Count one program built or loaded from the persistent cache (the
    ``backend_compile_duration`` monitoring event fires for both), and the
    seconds it took. Called from the listener
    platform.enable_compilation_cache installs; rare, so the registry
    lookup per event is fine."""
    if _enabled:
        REGISTRY.counter(
            "dllama_compiles_total",
            "Programs built by the backend compiler or loaded from the "
            "persistent compile cache; moving in steady state means a "
            "mid-traffic recompile (a new shape reached the engine)",
        ).inc(count)
        REGISTRY.counter(
            "dllama_compile_seconds_total",
            "Seconds spent building or loading programs (sum of the "
            "backend-compile durations counted by dllama_compiles_total)",
        ).inc(seconds)


def note_kernel_path(kernel: str, path: str) -> None:
    """Count one hot-path kernel DISPATCH DECISION by (kernel, path) —
    ``dllama_kernel_path_total`` (docs/OBSERVABILITY.md). Decisions happen
    at trace time (once per compiled program build, or once per eager
    call), not per token, so the rate is tiny and the registry lookup per
    event is fine (the note_compile_cache_hit pattern, no bind-once
    needed). The operational read: ``xla_fallback`` moving on a TPU
    deployment for a matrix of the model's own widths means a hot-path
    program silently took the slow path."""
    if _enabled:
        REGISTRY.counter(
            "dllama_kernel_path_total",
            "Kernel dispatch decisions by kernel (q40_matmul / "
            "paged_attention / decode_attention / all_reduce) and selected "
            "path (mxu_int8 / mxu_int8_fusedq / xla_segmented / slab_restored / "
            "pallas_rowbound / xla_scan / ici_ring / ring_xla / psum / "
            "xla_fallback); counted at trace time per program build",
            labelnames=("kernel", "path"),
        ).labels(kernel=kernel, path=path).inc()


class CollectiveInstruments:
    """The parallel backends' transfer-probe surface (TransferProbeMixin)."""

    def __init__(self):
        self.enabled = _enabled
        self.span = span_factory()
        self.allreduce_latency = histogram(
            "dllama_allreduce_latency_seconds",
            "Measured per-token collective (all-reduce/all-gather) cost from "
            "the transfer probe, replayed on the real mesh",
        )
        self.allreduce_bytes = counter(
            "dllama_allreduce_bytes_total",
            "Estimated logical payload bytes moved by the collectives the "
            "transfer probe replayed (per-token estimate x probe tokens)",
        )
        self.probe_runs = counter(
            "dllama_transfer_probe_runs_total",
            "Transfer-probe measurements taken (engine cadence: ~1/512 tokens)",
        )


class MeshInstruments:
    """The named-mesh topology surface (bound at backend/pod build):
    what shape the pod is and how many weight bytes are resident."""

    def __init__(self):
        self.enabled = _enabled
        self.mesh_devices = gauge(
            "dllama_mesh_devices",
            "Devices along each named mesh axis of the serving backend "
            "(pod axes 'data'/'model'; classic 1-D backends 'tp'/'sp'/'ep')",
            labelnames=("axis",),
        )
        self.resident_weight_bytes = gauge(
            "dllama_resident_weight_bytes",
            "Logical weight bytes resident per group: 'pod' = the ONE "
            "params tree every mesh slice shares, 'per_replica' = that "
            "tree attributed across the pod's data slices (the N-engine "
            "pool's equivalent figure is one full tree PER replica)",
            labelnames=("group",),
        )


class ServerInstruments:
    """The API server's metric surface (bound once per ApiState)."""

    def __init__(self):
        self.enabled = _enabled
        self.requests = counter(
            "dllama_http_requests_total",
            "HTTP requests by route and status code",
            labelnames=("route", "status"),
        )
        self.request_duration = histogram(
            "dllama_http_request_duration_seconds",
            "End-to-end completion-request wall time (monotonic clock)",
        )
        self.inflight = gauge(
            "dllama_http_requests_in_flight",
            "Completion requests currently being served",
        )
        self.tokens_streamed = counter(
            "dllama_tokens_streamed_total",
            "Tokens whose text was handed to a client: in an SSE content "
            "delta, or in a non-streamed answer (tokens swallowed by a "
            "matched stop string are not)",
        )
        self.hbm_peak = gauge(
            "dllama_hbm_peak_bytes",
            "Allocator peak of the fullest local device "
            "(memory_stats()['peak_bytes_in_use']), refreshed at each "
            "/metrics scrape; 0 where the backend reports none",
        )
        self.queue_wait = histogram(
            "dllama_slot_queue_wait_seconds",
            "Time a completion request waited for a free engine stream slot",
        )
        # fault-tolerance surface (ISSUE 3): admission control + deadlines
        self.admission_rejected = counter(
            "dllama_admission_rejected_total",
            "Completion requests rejected 429 because the bounded admission "
            "queue was full (clients should honor Retry-After)",
        )
        self.deadline_exceeded = counter(
            "dllama_deadline_exceeded_total",
            "Completion requests ended 504 because their deadline_ms expired "
            "(queued or mid-stream)",
        )
        self.draining = gauge(
            "dllama_server_draining",
            "1 while the server is draining (SIGTERM received: no new "
            "admissions, in-flight completions finishing)",
        )
        # multi-tenant fairness surface (ISSUE 8): per-tenant admission
        # accounting behind the weighted-fair queues (server/admission.py)
        self.tenant_admitted = counter(
            "dllama_tenant_admitted_total",
            "Completion requests admitted to a serving slot, by tenant "
            "(weighted-fair DRR dequeue; docs/SERVING.md)",
            labelnames=("tenant",),
        )
        self.tenant_rejected = counter(
            "dllama_tenant_rejected_total",
            "Completion requests rejected 429 at a full tenant (or global) "
            "admission queue, by tenant",
            labelnames=("tenant",),
        )
        self.tenant_queue_depth = gauge(
            "dllama_tenant_queue_depth",
            "Requests currently queued for admission, by tenant",
            labelnames=("tenant",),
        )
        self.tenant_active = gauge(
            "dllama_tenant_active",
            "Completion requests currently holding a serving slot, by tenant",
            labelnames=("tenant",),
        )
        self.preempt_requeues = counter(
            "dllama_preempted_requeued_total",
            "Preempted requests requeued through weighted-fair admission "
            "(each resumes from the prefix cache's published pages; pairs "
            "with dllama_preemptions_total on the eviction side)",
        )
        # replica-loss fault tolerance (ISSUE 9, server/replicas.py):
        # per-replica health plus the failover/restart/replay ledger
        self.replica_state = gauge(
            "dllama_replica_state",
            "Health of each data-parallel replica in the supervised pool: "
            "0 = healthy, 1 = suspect (skipped for new placements), "
            "2 = dead (failing over; the supervisor is restarting it)",
            labelnames=("replica",),
        )
        self.replica_failovers = counter(
            "dllama_replica_failovers_total",
            "Replicas declared dead by the pool (crash, or a stall the "
            "watchdog escalated); each failover requeues every in-flight "
            "request on the dead replica through fair admission",
        )
        self.replica_restarts = counter(
            "dllama_replica_restarts_total",
            "Dead replicas successfully rebuilt and returned to the pool "
            "by the jittered-backoff restart supervisor",
        )
        self.replayed_requests = counter(
            "dllama_replayed_requests_total",
            "Requests replayed on a surviving replica after their replica "
            "died mid-flight (pinned seed, sent SSE deltas suppressed — "
            "the stream is bit-identical to an unfaulted run)",
        )
        # global prefix-cache tier (ISSUE 11): placement routed by the
        # shared radix index (engine/prefix_cache.py SharedPrefixIndex)
        self.shared_prefix_hits = counter(
            "dllama_prefix_shared_hits_total",
            "Requests placed onto a replica because the shared radix "
            "index says it owns (part of) the prompt's published prefix "
            "chain — the cross-replica routing that keeps the Zipf head "
            "from being re-prefilled once per replica",
        )
        # silent-data-corruption detection (ISSUE 10, engine/integrity.py
        # + server/replicas.py): canary probes, shadow votes and restart
        # weight-checksum verifications all count as checks; mismatches
        # carry which check caught the corruption
        self.sdc_checks = counter(
            "dllama_sdc_checks_total",
            "Conclusive integrity checks performed: canary golden "
            "comparisons, cross-replica shadow votes, and rebuild "
            "weight-checksum verifications (a clean fleet moves this "
            "without ever moving the mismatch counter)",
        )
        self.sdc_mismatches = counter(
            "dllama_sdc_mismatches_total",
            "Integrity checks that detected silent data corruption, by "
            "which check caught it (canary = pinned-greedy golden "
            "mismatch, shadow = cross-replica divergence, checksum = a "
            "rebuilt replica's weights disagree with the load-time "
            "reference)",
            labelnames=("check",),
        )
        self.canary_latency = histogram(
            "dllama_canary_latency_seconds",
            "Wall time of one SDC canary probe (pinned greedy prompt "
            "through the replica's real batched path on a reserved lane)",
        )
        # request-scoped SLO attribution (ISSUE 16, telemetry/trace.py):
        # server-side TTFT/TPOT so client p99s decompose without trusting
        # the client clock, plus the per-stage breakdown the trace tree's
        # attribution sums are observed from (same timestamps — the
        # metric surface and /debug/trace can never disagree)
        self.ttft = histogram(
            "dllama_ttft_seconds",
            "Server-side time to first streamed token, by tenant "
            "(request arrival to the first SSE content delta; replays "
            "keep the original arrival instant)",
            labelnames=("tenant",),
        )
        self.tpot = histogram(
            "dllama_tpot_seconds",
            "Server-side mean time per output token after the first, by "
            "tenant ((last - first token instant) / (emitted - 1))",
            labelnames=("tenant",),
        )
        self.stage_seconds = histogram(
            "dllama_request_stage_seconds",
            "Per-request latency attribution by stage (queue = fair-"
            "admission wait, placement = replica/lane selection, prefill, "
            "decode = the streaming loop, replay = all stages of "
            "requeued re-attempts after a failover/preemption) and "
            "tenant; sums approximate dllama_http_request_duration_seconds",
            labelnames=("stage", "tenant"),
        )
        # zero-downtime fleet ops (ISSUE 18, server/fleet.py): the
        # blue-green rollout and SLO-elasticity ledger
        self.rollout_moved = counter(
            "dllama_rollout_replicas_moved_total",
            "Replicas moved to a new weight version by a blue-green "
            "rollout (drained, rebuilt on the new weights, checksum-"
            "verified and canary-certified against the new version's "
            "golden); rollback rebuilds do NOT count as moves",
        )
        self.rollout_aborts = counter(
            "dllama_rollout_aborts_total",
            "Rollouts aborted (checksum gate or canary certification "
            "failed on the new version, or the server began draining "
            "mid-rollout); each abort rolls every moved replica back to "
            "the old version and raises a typed RolloutAborted",
        )
        self.fleet_scale = counter(
            "dllama_fleet_scale_events_total",
            "Elastic replica-count changes applied by the FleetController "
            "(up = grew one replica under sustained queue pressure, "
            "down = drained and retired one idle replica); hysteresis "
            "keeps this counter quiet on a stable fleet",
            labelnames=("direction",),
        )
        self.weights_version_info = gauge(
            "dllama_weights_version",
            "Info gauge: 1 on the label of the pool's CURRENT weight "
            "version (the old version's label drops to 0 when a rollout "
            "completes, so a scrape always names exactly one live pool "
            "version; mid-rollout per-replica versions are in /readyz)",
            labelnames=("version",),
        )


class SamplerInstruments:
    """Host-sampler distribution counters (bound once per Sampler)."""

    def __init__(self):
        self.enabled = _enabled
        self.sampled = counter(
            "dllama_sampled_tokens_total",
            "Host-sampled tokens by method (greedy / topp); device-sampled "
            "tokens are counted by dllama_tokens_generated_total instead",
            labelnames=("method",),
        )
        # device-resident sampling (ISSUE 13): with the fused sampler every
        # decode token is drawn inside the device program — any host
        # Sampler.sample() call is by definition the fallback path
        # (--decode host, or a caller doing its own logits fetch); the
        # happy-path CI smoke gates --expect-zero on this
        self.fallback = counter(
            "dllama_host_sampler_fallback_total",
            "Tokens sampled by the HOST Sampler (the --decode host "
            "fallback): every one paid a full-vocab logits fetch and a "
            "host sort the fused device sampler exists to delete; 0 on "
            "the device-resident happy path",
        )
