"""KV-cached inference engine: prefill + decode with I/T stats.

Replaces the reference's Inference driver + TaskLoop
(reference: src/tasks.cpp:158-230, src/utils.cpp:152-231): instead of
re-spawning a thread pool per token, the whole token step is one jitted XLA
program with a donated KV cache, dispatched asynchronously.

The headline I/T (inference/transfer ms per token) split of the reference's
stats (src/tasks.hpp:9-11, src/apps/dllama/dllama.cpp:49-93) is preserved:
on a single chip transfer is 0 (no collectives exist); under TP the
per-token collective cost is MEASURED once per engine by timing the step's
exact collective sequence on the real mesh
(TensorParallelForward.measure_transfer_ms) and subtracted from the step
time — the collectives are fused inside one XLA program, so they cannot be
timed in situ the way the reference times its TASK_TYPE_TRANSFER tasks.

Concurrency: one engine owns the weights and the compiled programs; the
mutable decode state (KV cache, position, stats) lives in
:class:`EngineStream`. ``engine.new_stream()`` adds an independent stream
sharing the same weights — the API server interleaves several completions
this way (the reference is architecturally single-stream: one socket accept
drives one inference at a time, dllama-api.cpp:418-423). The engine itself
delegates the classic single-stream surface to a default stream, so CLI and
tests are unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu import lockcheck, prng, telemetry
from distributed_llama_tpu.engine import faults
from distributed_llama_tpu.engine import weights as weights_lib
from distributed_llama_tpu.telemetry import Stopwatch
from distributed_llama_tpu.models import llama
from distributed_llama_tpu.models.config import LlamaConfig


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (and >= 1): the one bucketing primitive
    behind the prefill/decode-row/page-id buckets."""
    b = 1
    while b < n:
        b *= 2
    return b


def _prefill_bucket(n: int) -> int:
    """Pad prompt lengths to power-of-two buckets (floor 8) so XLA compiles
    a handful of prefill programs instead of one per prompt length."""
    return max(8, next_pow2(n))


@dataclasses.dataclass
class TokenStats:
    """Per-step timing mirroring the reference's G/I/T printout
    (reference: src/apps/dllama/dllama.cpp:49-50, 88-93). A batched prefill
    is one entry covering ``n_tokens`` positions; decode steps have
    ``n_tokens == 1``."""

    generation_ms: float
    inference_ms: float
    transfer_ms: float
    n_tokens: int = 1


class EngineStream:
    """One independent generation stream: its own KV cache, position and
    stats, sharing the owning engine's weights and compiled programs.

    All per-request state lives here so several streams can decode
    concurrently on one engine (each dispatch is whole-program and
    asynchronous; interleaved dispatches from different streams simply queue
    on the device stream in order)."""

    def __init__(self, engine: "InferenceEngine", cache):
        self.engine = engine
        self.cache = cache
        self.pos = 0
        self.stats: list[TokenStats] = []
        # the prefill_device stats entry awaiting its compute-drain time
        # (added when generate_chunks fetches the fused first token)
        self._pending_prefill_entry: TokenStats | None = None
        # True while this stream's un-fetched prefill_device dispatch holds
        # the engine's pipeline depth up (released at the first-token fetch)
        self._depth_held = False
        # per-request deadline (time.monotonic seconds): enforced by the
        # serving layer per token; carried here so both stream kinds share
        # the surface (the batch scheduler additionally enforces it
        # between chunks — see engine/batch.py)
        self.deadline: float | None = None
        # prefix-cache opt-out surface parity with BatchStream (ISSUE 4):
        # the API server sets this per request on whichever stream kind the
        # slot wears; only the batch scheduler's paged prefix cache consumes
        # it — an independent EngineStream has no shared page pool to reuse
        self.prefix_cache_enabled = True
        # multi-tenant labels, surface parity with BatchStream (ISSUE 8):
        # the serving layer stamps them per request; only the batch
        # scheduler consumes them (preempt_below) — independent streams
        # have no shared rows to evict
        self.tenant: str | None = None
        self.priority: int | None = None
        # request trace surface parity with BatchStream (ISSUE 16): the
        # serving layer stamps it per request; only the batch scheduler
        # fans per-row spans into it — the independent-stream decode path
        # records its spans at the serving layer instead
        self.trace = None
        engine._streams.append(self)
        engine._tel.active_streams.set(len(engine._streams))

    @property
    def cfg(self) -> LlamaConfig:
        return self.engine.cfg

    # ------------------------------------------------------------------
    # Telemetry feeds (no-ops unless telemetry was enabled when the engine
    # was constructed; tel.enabled guards keep the disabled path to one
    # attribute check per DISPATCH — never per token, no registry access)
    # ------------------------------------------------------------------

    def _note_prefill(self, entry: "TokenStats") -> None:
        tel = self.engine._tel
        if tel.enabled:
            tel.prompt_tokens.inc(entry.n_tokens)
            tel.prefill_latency.observe(entry.generation_ms / 1000.0)
            tel.kv_occupancy.set(self.pos / self.engine.cfg.seq_len)

    def _note_decode(
        self, n_tokens: int, per_token_ms: float, device_sampled: bool = False
    ) -> None:
        tel = self.engine._tel
        if tel.enabled:
            tel.tokens_generated.inc(n_tokens)
            tel.decode_latency.observe(per_token_ms / 1000.0)
            tel.kv_occupancy.set(self.pos / self.engine.cfg.seq_len)
            if device_sampled:
                # the ISSUE 13 happy-path witness: tokens whose sampling ran
                # inside the device program (the host Sampler counts its own
                # fallback tokens — the two counters partition decode)
                tel.device_sampled_tokens.inc(n_tokens)

    # ------------------------------------------------------------------
    # Generation API
    # ------------------------------------------------------------------

    def reset(self) -> None:
        self.pos = 0
        # the engine's transfer-refresh cadence counts tokens across ALL
        # streams and its last measurement stays valid — one stream's reset
        # must be a NO-OP on the shared cadence (zeroing the engine-wide
        # watermark forced an early re-measurement for every stream under
        # concurrent serving). Clearing this stream's stats
        # shrinks the engine-wide token sum, so the watermark shifts down
        # by the same amount to keep (total - watermark) unchanged.
        cleared = sum(s.n_tokens for s in self.stats)
        with self.engine._depth_lock:
            self.engine._transfer_measured_at -= cleared
        self.stats.clear()
        self._release_depth()  # an abandoned un-fetched prefill must not pin the depth
        self._pending_prefill_entry = None
        self.deadline = None
        self.prefix_cache_enabled = True
        self.tenant = None
        self.priority = None

    def rollback(self, pos: int) -> None:
        """Rewind the stream to ``pos`` (prefix-cache reuse). Cache slots
        beyond ``pos`` are stale but unreachable: attention masks s <= pos and
        every slot is overwritten before the position pointer crosses it."""
        if not 0 <= pos <= self.pos:
            raise ValueError(f"cannot rollback to {pos} from {self.pos}")
        if 0 < pos < self.pos:
            llama.refuse_recurrent(self.cfg, f"rollback to position {pos} of a stream at {self.pos}")
        self.pos = pos

    def _forward_device(self, tokens: np.ndarray):
        """Dispatch one forward; returns DEVICE logits [T_padded, vocab].
        Advances pos and records stats (the timing covers dispatch only —
        callers append their fetch to the same stats entry implicitly by
        measuring around their np.asarray)."""
        engine = self.engine
        engine._faults.fire("engine.forward")
        n = tokens.shape[0]
        if n == 0:
            raise ValueError("empty token batch: at least one token required")
        if self.pos + n > engine.cfg.seq_len:
            raise ValueError(f"context overflow: pos {self.pos} + {n} > {engine.cfg.seq_len}")
        piece = engine.cfg.piece_limit or n
        if n > piece:
            # a window layer's ring takes a prompt in pieces that fit it beside
            # the window, an EVA layer's window store in pieces of at most a window
            return jnp.concatenate([
                self._forward_device(tokens[off : off + piece])[: min(piece, n - off)]
                for off in range(0, n, piece)
            ])
        if n == 1 or getattr(engine._tp_engine, "prefers_exact_mid_prefill", False):
            # backends that pad/chunk multi-token prompts themselves (sp:
            # fixed-width masked-scatter chunks at any position, seq_len
            # padding on the ring path) — engine bucket-padding on top
            # would only inflate the dispatch count
            padded = tokens
        else:
            bucket = _prefill_bucket(n)
            if self.pos + bucket > engine.cfg.seq_len:
                bucket = n  # exact-length compile near the context limit
            padded = np.zeros(bucket, dtype=np.int32)
            padded[:n] = tokens
        if engine._forward_takes_n_real:
            # the real-token count rides into the jitted forward (traced, no
            # recompile) so the capacity-bucketed MoE prefill can keep
            # bucket-pad rows out of its per-expert buckets
            logits, self.cache = engine._forward(
                engine.params, jnp.asarray(padded), self.cache,
                jnp.int32(self.pos), jnp.int32(n),
            )
        else:
            logits, self.cache = engine._forward(
                engine.params, jnp.asarray(padded), self.cache, jnp.int32(self.pos)
            )
        self.pos += n
        return logits

    def forward(self, tokens: list[int] | np.ndarray) -> np.ndarray:
        """Run tokens at the current position; returns f32 logits [T, vocab]
        (padded positions stripped). Advances pos by len(tokens)."""
        # an abandoned fused prefill (prefill_device whose token was never
        # fetched) must not pin the engine depth: this call's own fetch
        # drains the device queue anyway
        self._release_depth()
        tokens = np.asarray(tokens, dtype=np.int32)
        n = tokens.shape[0]
        sw = Stopwatch()
        with self.engine._tel.span("forward", tokens=n, pos=self.pos):
            logits = np.asarray(self._forward_device(tokens)[:n])
        entry = self.engine._split_stats(
            sw.elapsed_ms(), n_tokens=n, n_dispatches=self.engine._last_dispatches()
        )
        self.stats.append(entry)
        if n > 1:
            self._note_prefill(entry)
        else:
            self._note_decode(1, entry.generation_ms)
        return logits

    def prefill(self, tokens: list[int]) -> np.ndarray:
        """Process a prompt in one batched step; returns last-token logits.

        Only the LAST position's logits row cross the host boundary: a
        64-token prefill of a 32k-vocab model would otherwise ship 8 MB of
        f32 logits per prompt over PCIe where one 128 KB row will do."""
        self._release_depth()  # see forward()
        tokens = np.asarray(tokens, dtype=np.int32)
        n = tokens.shape[0]
        sw = Stopwatch()
        with self.engine._tel.span("prefill", tokens=n, pos=self.pos):
            logits = np.asarray(self._forward_device(tokens)[n - 1])
        entry = self.engine._split_stats(
            sw.elapsed_ms(), n_tokens=n, n_dispatches=self.engine._last_dispatches()
        )
        self.stats.append(entry)
        self._note_prefill(entry)
        return logits

    def prefill_device(
        self, tokens: list[int], temperature, topp, seed: int, topk: int = 0
    ):
        """Prefill + sample the first generated token ON DEVICE; returns the
        sampled token as a device scalar (NOT fetched). The coin is drawn
        from the counter PRNG at the last prompt token's absolute position,
        so a requeued/replayed request re-draws it exactly — no sampler
        state exists to ship (ISSUE 13).

        This removes the prompt→first-token host round trip entirely: the
        returned scalar feeds :meth:`generate_chunks` without ever visiting
        the host, so time-to-first-token is one device prefill + one chunk
        with no host sync (and no idle device) in between.

        The stats entry recorded here covers the ASYNC dispatch only; the
        prefill's device compute drains at the first-token fetch inside
        ``generate_chunks(emit_first=True)``, which adds that drain time back
        onto this entry (``_pending_prefill_entry``) so the P line still
        reports true prefill latency."""
        engine = self.engine
        tokens = np.asarray(tokens, dtype=np.int32)
        n = tokens.shape[0]
        sw = Stopwatch()
        # the dispatches below are never fetched here: mark the engine
        # non-quiescent so the transfer probe does not queue behind them and
        # time their compute (see _transfer_ms_per_token). The depth stays
        # RAISED until the fused first token is fetched (_fetch_fused_first)
        # — decrementing here would reopen the probe-poisoning window for
        # the whole prefill-to-first-fetch span.
        self._hold_depth()
        try:
            with engine._tel.span("prefill_dispatch", tokens=n, pos=self.pos):
                logits = self._forward_device(tokens)
                with engine._tel.span("device_sample", pos=self.pos - 1):
                    token = engine._sample_row(
                        logits, jnp.int32(n - 1),
                        jnp.uint32(prng.fold_seed(seed)),
                        jnp.int32(self.pos - 1), jnp.float32(temperature),
                        jnp.float32(topp), jnp.int32(topk),
                    )
            entry = engine._split_stats(
                sw.elapsed_ms(), n_tokens=n, n_dispatches=engine._last_dispatches()
            )
            self.stats.append(entry)
            self._pending_prefill_entry = entry
            # prompt tokens count now; the prefill LATENCY observation waits
            # for _fetch_fused_first, where the entry gains its true
            # device-compute drain time
            if engine._tel.enabled:
                engine._tel.prompt_tokens.inc(n)
        except BaseException:
            self._release_depth()
            raise
        return token

    def _hold_depth(self) -> None:
        """Raise the engine's in-flight depth on this stream's behalf until
        :meth:`_release_depth`. Idempotent: a second hold while the first is
        outstanding is absorbed (at most one un-fetched prefill can exist
        per stream, and the hold is released at its first-token fetch, a
        reset(), or the start of any fetching forward/prefill)."""
        engine = self.engine
        with engine._depth_lock:
            if not self._depth_held:
                engine._pipeline_depth += 1
                self._depth_held = True

    def _release_depth(self) -> None:
        engine = self.engine
        with engine._depth_lock:
            if self._depth_held:
                engine._pipeline_depth -= 1
                self._depth_held = False

    def decode_step(self, token: int) -> np.ndarray:
        """One autoregressive step; returns f32 logits [vocab]."""
        return self.forward([token])[0]

    def generate_on_device(
        self,
        first_token: int,
        n_steps: int,
        temperature: float = 0.0,
        topp: float = 0.9,
        seed: int = 0,
        topk: int = 0,
    ) -> np.ndarray:
        """Generate n_steps tokens in ONE device program (no per-token host
        round trip). Returns int32 [n_steps]. Under TP the loop is
        shard_map'd over the mesh with collectives riding every step."""
        engine = self.engine
        if self.pos + n_steps > engine.cfg.seq_len:
            raise ValueError(f"context overflow: pos {self.pos} + {n_steps}")
        from distributed_llama_tpu.models import sampling

        sw = Stopwatch()
        if engine._tp_engine is not None:
            tokens, self.cache = engine._tp_engine.decode_loop(
                engine.params,
                jnp.int32(first_token),
                self.cache,
                jnp.int32(self.pos),
                n_steps,
                float(temperature),
                float(topp),
                seed=seed,
                topk=topk,
            )
        else:
            tokens, self.cache = sampling.decode_loop(
                engine.cfg,
                engine.params,
                jnp.int32(first_token),
                self.cache,
                jnp.int32(self.pos),
                n_steps,
                float(temperature),
                float(topp),
                seed=seed,
                topk=topk,
            )
        tokens = np.asarray(tokens)
        per_token_ms = sw.elapsed_ms() / n_steps
        self.stats.extend([engine._split_stats(per_token_ms)] * n_steps)
        self.pos += n_steps
        self._note_decode(n_steps, per_token_ms, device_sampled=True)
        return tokens

    def _dispatch_chunk(
        self, first_token, n_steps: int, temperature, topp, topk, seed32
    ):
        """Dispatch one decode chunk WITHOUT fetching: returns the device
        token array. ``first_token`` may be a host int or a device scalar
        (the previous chunk's last token — the pipelined path never waits
        on it); ``seed32`` is the folded uint32 request seed the chunk's
        counter coins re-key from (no sampler state threads between
        chunks). Advances pos by n_steps."""
        from distributed_llama_tpu.models import sampling

        engine = self.engine
        engine._faults.fire("engine.decode_dispatch")
        with engine._tel.span("decode_chunk_dispatch", pos=self.pos, steps=n_steps):
            if engine._tp_engine is not None:
                tokens, self.cache = engine._tp_engine.decode_chunk(
                    engine.params, jnp.int32(first_token), self.cache, jnp.int32(self.pos),
                    n_steps, temperature, topp, topk, seed32,
                )
            else:
                tokens, self.cache = sampling.decode_chunk(
                    engine.cfg, engine.params, jnp.int32(first_token), self.cache,
                    jnp.int32(self.pos), n_steps, jnp.float32(temperature),
                    jnp.float32(topp), jnp.int32(topk), seed32,
                )
        self.pos += n_steps
        return tokens

    def decode_chunk(
        self, first_token: int, n_steps: int, temperature, topp, seed=0, topk=0
    ):
        """Decode ``n_steps`` tokens in one device dispatch with runtime-valued
        temperature/topp/topk (no recompile when a request changes them).
        Returns tokens np[n_steps]. Advances pos by n_steps."""
        sw = Stopwatch()
        tokens = self._dispatch_chunk(
            first_token, n_steps, temperature, topp, topk,
            jnp.uint32(prng.fold_seed(seed)),
        )
        tokens = np.asarray(tokens)
        per_token_ms = sw.elapsed_ms() / n_steps
        self.stats.extend([self.engine._split_stats(per_token_ms)] * n_steps)
        self._note_decode(n_steps, per_token_ms, device_sampled=True)
        return tokens

    def generate_chunks(
        self,
        first_token,
        temperature: float = 0.0,
        topp: float = 0.9,
        seed: int = 0,
        chunk: int = 32,
        limit: int | None = None,
        emit_first: bool = False,
        topk: int = 0,
    ):
        """Generator of on-device-decoded tokens: ``chunk`` tokens per device
        dispatch (no per-token host round trip), host code between chunks.
        ``first_token`` is consumed first, not yielded — a host int, or a
        device scalar from :meth:`prefill_device` (then the stream continues
        without any host round trip; set ``emit_first`` and the unseen first
        token is fetched and yielded after chunk 1 is dispatched, its fetch
        overlapping the chunk's compute). Every step's coin is re-keyed from
        ``(seed, position)`` by the counter PRNG, so the stream for a given
        seed is identical to ``generate_on_device(seed)`` regardless of
        chunk size — no sampler state threads between chunks.

        ``limit`` stops dispatching once ``pos`` reaches it (a stop *hint*:
        the final chunk may overshoot it — chunks keep a fixed size so XLA
        compiles one program, not one per remaining-budget value). Callers
        that stop consuming early (EOS, stop string, budget) MUST
        ``rollback(pos)`` to the stream position after the last token they
        consumed; overshot cache slots are unreachable after rollback.

        This is the user-facing fast path: the stepwise ``decode_step`` loop
        pays a host<->device round trip per token (the reference's regime,
        src/apps/dllama/dllama.cpp:45-59) and leaves the device idle while
        the host samples. The stream is additionally
        PIPELINED: chunk k+1 is dispatched (seeded by chunk k's last token,
        which never leaves the device) BEFORE chunk k's tokens are fetched,
        so the host-fetch latency overlaps the next chunk's compute. An
        early stop wastes at most one speculative chunk — already covered by
        the rollback contract above.
        """
        engine = self.engine
        seed32 = jnp.uint32(prng.fold_seed(seed))
        stop = engine.cfg.seq_len if limit is None else min(limit, engine.cfg.seq_len)
        if self.pos >= stop:
            if emit_first:
                yield self._fetch_fused_first(first_token)
            return
        k = min(chunk, engine.cfg.seq_len - self.pos)
        if isinstance(first_token, (int, np.integer)):
            first_token = int(first_token)
        # a speculative chunk is in flight for the rest of the loop: the
        # transfer estimate must not re-measure while one is queued, so the
        # depth must rise BEFORE the first dispatch (a concurrent stream's
        # probe could otherwise slip between dispatch and increment and time
        # this chunk's compute); the finally covers early consumer exits
        # (EOS/stop breaks close the generator)
        with engine._depth_lock:
            engine._pipeline_depth += 1
        try:
            pending = self._dispatch_chunk(
                first_token, k, temperature, topp, topk, seed32
            )
            pending_n = k
            if emit_first:
                # chunk 1 is already in flight: this scalar fetch overlaps
                # its compute instead of gating the prompt→first-token path
                yield self._fetch_fused_first(first_token)
            yield from self._generate_chunks_pipelined(
                pending, pending_n, stop, chunk, temperature, topp, topk, seed32
            )
        finally:
            with engine._depth_lock:
                engine._pipeline_depth -= 1

    def fetch_first_token(self, first_token) -> int:
        """Fetch a :meth:`prefill_device` token WITHOUT starting a decode
        stream (the 1-token-completion fast path: dispatching a speculative
        chunk would burn a whole chunk of device compute for a request that
        wants exactly one token). Drains the prefill and fixes up its stats
        entry like the streaming path does."""
        return self._fetch_fused_first(first_token)

    def _fetch_fused_first(self, first_token) -> int:
        """Fetch the device-sampled first token; the blocking fetch drains
        the prefill's device compute, so its elapsed time is added back onto
        the prefill's stats entry (prefill_device timed only the async
        dispatch — without this the P line would report ~dispatch overhead
        and the prefill compute would be misattributed to the first chunk).
        Also releases the depth hold prefill_device took: the prefill is
        drained now, so the probe-quiescence hazard it guarded is gone."""
        sw = Stopwatch()
        with self.engine._tel.span("first_token_fetch"):
            tok = int(np.asarray(first_token))
        self._release_depth()
        drained_ms = sw.elapsed_ms()
        entry = self._pending_prefill_entry
        if entry is not None:
            entry.generation_ms += drained_ms
            entry.inference_ms += drained_ms
            self._pending_prefill_entry = None
            # the deferred prefill-latency observation (see prefill_device):
            # the entry now carries dispatch + device-compute drain time.
            # The fused first token counts as GENERATED here — it belongs to
            # no decode chunk (generate_chunks consumes it, never yields it
            # from a chunk), and its latency is folded into the prefill entry
            tel = self.engine._tel
            if tel.enabled:
                tel.prefill_latency.observe(entry.generation_ms / 1000.0)
                tel.tokens_generated.inc(1)
                tel.device_sampled_tokens.inc(1)
                tel.kv_occupancy.set(self.pos / self.engine.cfg.seq_len)
        return tok

    def _generate_chunks_pipelined(
        self, pending, pending_n, stop, chunk, temperature, topp, topk, seed32
    ):
        engine = self.engine
        while True:
            # the timed window covers dispatch+fetch only — consumer time
            # between yields must not be attributed to the engine's stats
            sw = Stopwatch()
            # speculatively dispatch the next chunk off the device-resident
            # last token before fetching the pending one
            if self.pos < stop:
                k = min(chunk, engine.cfg.seq_len - self.pos)
                nxt = self._dispatch_chunk(
                    pending[-1], k, temperature, topp, topk, seed32
                )
            else:
                nxt, k = None, 0
            engine._faults.fire("engine.fetch")
            with engine._tel.span("decode_chunk_fetch", tokens=pending_n):
                try:
                    # start the device->host copy without blocking: enqueued
                    # here it overlaps the next chunk's compute
                    pending.copy_to_host_async()
                except Exception:
                    pass  # optional acceleration; np.asarray below is the contract
                toks = np.asarray(pending)
            per_token_ms = sw.elapsed_ms() / pending_n
            self.stats.extend([engine._split_stats(per_token_ms)] * pending_n)
            self._note_decode(pending_n, per_token_ms, device_sampled=True)
            for t in toks.tolist():
                yield int(t)
            if nxt is None:
                return
            pending, pending_n = nxt, k

    def stream_decode(
        self,
        first_token,
        on_token,
        temperature: float = 0.0,
        topp: float = 0.9,
        seed: int = 0,
        chunk: int = 32,
        limit: int | None = None,
        first_prev: int | None = None,
        spec_draft: int = 0,
        spec_ngram: int = 3,
        prompt_tokens=None,
        topk: int = 0,
    ) -> int:
        """Drive the chunked fast decode with host-side stop handling: the
        shared consumption loop of CLI generate/chat and the API server.

        ``on_token(prev_token, token) -> bool`` is called once per decoded
        token (False = stop). This method owns the early-stop rollback
        contract of :meth:`generate_chunks`: every decoded token counts one
        feed of its predecessor, so on exit the stream position is rewound to
        just after the last decoded token's feed. Returns the number of
        decoded tokens.

        ``first_prev`` (prefill→decode fusion): ``first_token`` is a device
        scalar from :meth:`prefill_device` that the caller has NOT seen yet —
        it is ALSO yielded to ``on_token`` as the first decoded token (its
        host value arrives with the first fetched chunk), with ``first_prev``
        (the prompt's last token) as its predecessor.

        ``spec_draft`` > 0 routes through self-speculative decoding
        (:meth:`_stream_decode_spec`): prompt-lookup drafts over
        ``prompt_tokens`` + the emitted output are verified k at a time in
        one weight read per step. Single-chip dense models only — other
        backends fall back to the chunked path, and so do MoE models (a
        T>1 verify window routes through the prefill expert path, which
        has no decode parity contract — same gate as the batch
        scheduler's). Greedy output is identical either way."""
        if spec_draft and spec_draft > 0:
            if self.engine._tp_engine is None and not self.engine.cfg.is_moe:
                return self._stream_decode_spec(
                    first_token, on_token, temperature, topp, seed, spec_draft,
                    spec_ngram, limit, first_prev, prompt_tokens, topk,
                )
            # once per engine, not per request: the operator asked for spec
            # on a backend without it — say so instead of silently serving
            # the plain path (the batch scheduler prints the same warning)
            if not getattr(self.engine, "_spec_fallback_warned", False):
                self.engine._spec_fallback_warned = True
                reason = (
                    "single-chip backend only for now"
                    if self.engine._tp_engine is not None
                    else "MoE verify windows have no decode parity contract"
                )
                print(f"⚠️ --spec-draft ignored: {reason}; plain chunked decode")
        start_pos = self.pos
        consumed = 0
        fused_first = first_prev is not None
        prev = first_prev if fused_first else int(first_token)
        try:
            for t in self.generate_chunks(
                first_token, temperature, topp, seed=seed, chunk=chunk,
                limit=limit, emit_first=fused_first, topk=topk,
            ):
                consumed += 1
                keep_going = on_token(prev, t)
                prev = t
                # with a fused first token, yield i corresponds to stream
                # position start_pos + i - 1 (the first yield was sampled
                # during prefill and occupies no new position until fed)
                fed = consumed - 1 if fused_first else consumed
                if keep_going is False:
                    break
                if limit is not None and start_pos + fed >= limit:
                    break
        finally:
            # the rollback must run even when on_token RAISES (an SSE client
            # disconnect mid-stream, a deadline expiry): without it the slot's
            # next request sees a position inflated by the overshot
            # speculative chunk and needlessly resets its prefix cache
            fed = max(consumed - 1, 0) if fused_first else consumed
            self.rollback(min(start_pos + fed, self.pos))
        # the stream is drained here (generator closed, last chunk fetched):
        # the one quiescent point of the fused serving flow — refresh the
        # transfer estimate on cadence for FUTURE entries (every stats entry
        # of this request was computed mid-flight and used the cached value;
        # without this hook a device-decode-only server would never measure)
        self.engine._maybe_refresh_transfer()
        return consumed

    def _stream_decode_spec(
        self,
        first_token,
        on_token,
        temperature: float,
        topp: float,
        seed: int,
        spec_draft: int,
        spec_ngram: int,
        limit: int | None,
        first_prev: int | None,
        prompt_tokens,
        topk: int = 0,
    ) -> int:
        """Self-speculative decode (``--spec-draft k``): per step the host
        drafts up to k tokens by prompt lookup over the request's own
        prompt + output, ONE verify forward scores draft + bonus positions
        in a single weight read, and the on-device accept/reject keeps the
        longest valid prefix — 1..k+1 tokens emitted per weight read
        instead of exactly 1. Greedy output is bit-identical to plain
        decode (tests/test_speculative.py); sampled output preserves the
        target distribution via Leviathan rejection sampling.

        Unlike :meth:`generate_chunks` this loop cannot pipeline: the next
        step's drafts depend on THIS step's emitted tokens, so each verify
        is dispatched and fetched synchronously (the fetch is k+2 int32s).
        The trade is deliberate — on accepting workloads one round trip
        buys several tokens. ``prompt_tokens`` seeds the lookup corpus
        (without it only the emitted output can match). Single chip only;
        the caller routes other backends to the chunked path."""
        from distributed_llama_tpu.engine.speculative import PromptLookupDrafter
        from distributed_llama_tpu.models import sampling

        engine = self.engine
        seed32 = jnp.uint32(prng.fold_seed(seed))
        stop = engine.cfg.seq_len if limit is None else min(limit, engine.cfg.seq_len)
        drafter = PromptLookupDrafter(spec_draft, max_ngram=spec_ngram)
        # the lookup corpus: prompt + everything emitted (first_token is
        # appended below — callers pass the prompt WITHOUT it)
        history = [int(t) for t in (prompt_tokens if prompt_tokens is not None else [])]
        tel = engine._tel
        start_pos = self.pos
        fused = first_prev is not None
        consumed = 0
        keep = True
        try:
            if fused:
                # the drafter needs the fused first token's host value
                # before anything can be proposed, so the scalar fetch
                # cannot overlap a chunk here — it IS the step boundary
                prev = self._fetch_fused_first(first_token)
                consumed = 1
                history.append(prev)
                keep = on_token(first_prev, prev)
            else:
                prev = int(first_token)
                history.append(prev)
            while keep is not False:
                fed = consumed - 1 if fused else consumed
                if start_pos + fed >= stop:
                    break
                # the verify window never writes past seq_len: shrink T at
                # the context tail (an exact-length compile, same policy as
                # the prefill buckets near the limit)
                T = min(spec_draft + 1, engine.cfg.seq_len - self.pos)
                if T < 1:
                    break
                draft = drafter.draft(history, limit=T - 1)
                feed = np.full(T, prev, np.int32)  # pad tokens are overwritten KV
                feed[1 : 1 + len(draft)] = draft
                engine._faults.fire("engine.spec_verify")
                sw = Stopwatch()
                with tel.span(
                    "spec_verify", pos=self.pos, window=T, drafted=len(draft)
                ):
                    out_dev, self.cache = sampling.spec_verify_step(
                        engine.cfg, engine.params, jnp.asarray(feed), self.cache,
                        jnp.int32(self.pos), jnp.int32(len(draft)),
                        jnp.float32(temperature), jnp.float32(topp),
                        jnp.int32(topk), seed32,
                    )
                    out = np.asarray(out_dev)  # [T+1]: n_emit, tokens...
                n_emit = max(1, min(int(out[0]), T))
                toks = [int(t) for t in out[1 : 1 + n_emit]]
                self.pos += n_emit
                entry = engine._split_stats(sw.elapsed_ms(), n_tokens=n_emit)
                self.stats.append(entry)
                if tel.enabled:
                    tel.tokens_generated.inc(n_emit)
                    tel.device_sampled_tokens.inc(n_emit)
                    tel.decode_latency.observe(sw.elapsed_ms() / n_emit / 1000.0)
                    tel.kv_occupancy.set(self.pos / engine.cfg.seq_len)
                    tel.spec_draft_tokens.inc(len(draft))
                    tel.spec_accepted_tokens.inc(n_emit - 1)
                    if draft:
                        tel.spec_acceptance.observe((n_emit - 1) / len(draft))
                    tel.spec_step_advance.observe(n_emit)
                for t in toks:
                    consumed += 1
                    history.append(t)
                    keep = on_token(prev, t)
                    prev = t
                    fed = consumed - 1 if fused else consumed
                    if keep is False or start_pos + fed >= stop:
                        break
        finally:
            # positions beyond the last consumed token (a rejected-draft
            # overshoot, or tokens emitted past an early stop) are stale:
            # rewind exactly like the chunked path's rollback contract
            fed = max(consumed - 1, 0) if fused else consumed
            self.rollback(min(start_pos + fed, self.pos))
        # end-of-stream quiescent point: same cadence hook as the chunked
        # path (a no-op on today's single-chip-only spec route, but the
        # contract belongs to every stream_decode exit)
        engine._maybe_refresh_transfer()
        return consumed

    # ------------------------------------------------------------------
    # Stats (reference: Inference::getStats, src/tasks.cpp:186-189)
    # ------------------------------------------------------------------

    def avg_stats(self) -> TokenStats:
        """Per-token averages, weighting batched-prefill entries by their
        token count (the reference accounts per position, dllama.cpp:88-93)."""
        if not self.stats:
            return TokenStats(0.0, 0.0, 0.0)
        n = sum(s.n_tokens for s in self.stats)
        return TokenStats(
            sum(s.generation_ms for s in self.stats) / n,
            sum(s.inference_ms for s in self.stats) / n,
            sum(s.transfer_ms for s in self.stats) / n,
            n_tokens=n,
        )

    def total_tokens(self) -> int:
        return sum(s.n_tokens for s in self.stats)


class InferenceEngine:
    """Single-program driver for one model instance.

    ``tp`` > 1 shards the same forward over a tensor-parallel mesh
    (see distributed_llama_tpu.parallel); tp=1 is the single-chip path.
    The engine exposes the classic single-stream surface (prefill/decode/
    stats) by delegating to a default :class:`EngineStream`;
    :meth:`new_stream` adds independent concurrent streams over the same
    weights.
    """

    def __init__(
        self,
        model_path: str,
        dtype=jnp.bfloat16,
        max_seq_len: int | None = None,
        cache_dtype=None,
        tp: int = 1,
        sp: int = 1,
        ep: int = 1,
        **cfg_overrides,
    ):
        from distributed_llama_tpu.formats.model_file import ModelFileReader
        from distributed_llama_tpu.models.config import config_from_spec

        quantized = dtype == "q40"
        self.tp = tp
        self.sp = sp
        self.ep = ep
        # instrument bundle bound ONCE per engine: real registry-backed
        # instruments when telemetry is enabled at construction, shared
        # no-op singletons otherwise (the zero-overhead-when-disabled
        # contract — hot paths hold attributes, never do registry lookups)
        self._tel = telemetry.EngineInstruments()
        if ep > 1 and sp > 1:
            raise ValueError("--ep and --sp do not compose (pick one FFN/context strategy)")
        # fault-injection plan bound ONCE per engine (the same bind-once
        # contract as telemetry: the no-op NULL_PLAN when no chaos plan is
        # installed — hot paths pay one attribute call per dispatch)
        self._faults = faults.active_plan()
        # the parallel backend is constructed BEFORE the weights load so the
        # q40 sharded load can place each shard's pack straight onto its
        # device via make_array_from_callback — each process reads only its
        # own shards' bytes (multi-host: O(model/tp) host RAM per process,
        # replacing the reference's root-scatter, src/transformer.cpp:432-451)
        reader = ModelFileReader(model_path)
        self.spec = reader.spec.clamp_seq_len(max_seq_len)
        self.cfg = config_from_spec(self.spec, **cfg_overrides)
        if cache_dtype is None:
            # "q40" is a weights-only format; the KV cache stays bf16
            cache_dtype = jnp.bfloat16 if quantized else dtype
        self.cache_dtype = cache_dtype
        if ep > 1:
            from distributed_llama_tpu.parallel import expert_parallel as epmod

            # expert parallelism (optionally composed with tensor
            # parallelism on a 2-D (tp, ep) mesh): expert banks sharded by
            # whole experts, all_to_all dispatch for prefill, dense-local
            # decode (see ExpertParallelForward); same duck-typed interface
            self._tp_engine = epmod.ExpertParallelForward(
                self.cfg, ep, tp=tp, quantized=quantized
            )
        elif sp > 1:
            from distributed_llama_tpu.parallel import context_parallel as spmod

            # sequence parallelism (optionally composed with tensor
            # parallelism on a 2-D (tp, sp) mesh): sequence-sharded KV cache,
            # ring-attention prefill (see SequenceParallelForward); reuses
            # the tp-engine slot — same duck-typed interface
            self._tp_engine = spmod.SequenceParallelForward(
                self.cfg, sp, tp=tp, quantized=quantized
            )
        elif tp > 1:
            from distributed_llama_tpu.parallel import tensor_parallel as tpmod

            self._tp_engine = tpmod.TensorParallelForward(
                self.cfg, tp, quantized=quantized, layered=True
            )
        else:
            self._tp_engine = None
        # every dtype loads per-shard under tp: each process reads only its
        # own shards' bytes and places them straight onto its devices.
        # ep>1 loads host-side instead: the expert banks must be re-stacked
        # on a leading expert axis before placement (stack_expert_leaves),
        # which direct-to-device tp placement would fight
        mesh = self._tp_engine.mesh if (tp > 1 and ep == 1) else None
        host_params = weights_lib.load_params(
            reader, self.cfg, dtype=dtype, tp=tp, mesh=mesh
        )
        reader.close()
        if quantized and self._tel.enabled:
            for role, nbytes in weights_lib.q40_padded_bytes(host_params).items():
                self._tel.q40_padded_weight_bytes.labels(role=role).set(nbytes)
        if self._tp_engine is not None:
            self.params = self._tp_engine.shard_params(host_params)
            self._forward = self._tp_engine.forward
        else:
            self.params = jax.device_put(host_params)
            self._forward = functools.partial(self._forward_single, self.cfg)
        self._init_runtime()

    @classmethod
    def from_shared(
        cls, cfg, backend, params, cache_dtype=jnp.bfloat16, spec=None
    ) -> "InferenceEngine":
        """An engine over a PRE-BUILT backend and an ALREADY-PLACED params
        tree — the one-process pod's slice engines (parallel/pod.py): N
        replicas' engines share one backend (compiled programs built once
        for the pod) and one params tree (weights resident once per model
        group), while everything per-slice — KV caches, slab, scheduler,
        streams, stats — stays per engine, preserving the replica failure
        domain. A slice REBUILD after failover goes through here too:
        scheduler + lanes are rebuilt, weights are never reloaded (and the
        PR 10 rebuild checksum gate passes against the same bytes)."""
        self = cls.__new__(cls)
        self.tp = getattr(backend, "tp", 1)
        self.sp = 1
        self.ep = 1
        self._tel = telemetry.EngineInstruments()
        self._faults = faults.active_plan()
        self.spec = spec
        self.cfg = cfg
        self.cache_dtype = cache_dtype
        self._tp_engine = backend
        self.params = params
        self._forward = backend.forward
        self._init_runtime()
        return self

    def _init_runtime(self) -> None:
        """Per-engine mutable state, shared by the loading constructor and
        :meth:`from_shared`."""
        # whether the forward accepts the real-token count of a bucket-padded
        # prompt (the capacity-bucketed MoE prefill's pad mask): the
        # single-chip path always does; backends opt in via the attribute
        self._forward_takes_n_real = self._tp_engine is None or getattr(
            self._tp_engine, "accepts_n_real", False
        )
        self._streams: list[EngineStream] = []
        # load-time weight checksum (ISSUE 10): computed lazily on first
        # read and cached — the replica pool records replica 0's value as
        # the pool reference at construction and verifies every rebuilt
        # replica against it before re-entering placement. Lazy, so
        # engines that never join a supervised pool pay nothing; ONE HBM
        # pass over the weights when they do (engine/integrity.py)
        self._weights_checksum: str | None = None
        # which weight VERSION these params are (ISSUE 18): tagged by the
        # serving layer's versioned factory; None outside a rollout-aware
        # pool. The blue-green orchestrator verifies a rebuilt replica's
        # engine against this version's checksum reference
        self.weights_version: str | None = None
        # the classic single-stream surface's stream is created LAZILY on
        # first use: batched serving (engine.batch) never touches it, and
        # eagerly allocating its KV cache would hold one full cache of HBM
        # dead next to the scheduler's slab
        self._default: EngineStream | None = None
        # once-per-engine "--spec-draft ignored" diagnostic latch (the spec
        # route is single-chip dense only; see EngineStream.stream_decode)
        self._spec_fallback_warned = False
        # measured lazily under TP/SP; _init_runtime runs from the
        # constructors BEFORE the engine is published to other threads
        # (the _depth_lock guarding these is itself created 6 lines down)
        self._transfer_ms: float | None = None  # dllama: noqa[LCK-004]
        self._transfer_measured_at = 0  # dllama: noqa[LCK-004]
        self._pipeline_depth = 0  # >0 while a speculative chunk is in flight
        # concurrent streams (API --parallel) bump the depth from several
        # threads; the counter must not lose updates or go negative (a stuck
        # >0 would freeze the transfer estimate, a negative one would let
        # probes run mid-flight)
        self._depth_lock = lockcheck.make_lock("InferenceEngine._depth_lock")
        # mesh-topology gauges (ISSUE 15): axis -> device count of the
        # backend's named mesh, so an operator can read the serving shape
        # off /metrics (the pod group additionally reports weight bytes)
        mesh = getattr(self._tp_engine, "mesh", None)
        if mesh is not None:
            tel = telemetry.MeshInstruments()
            if tel.enabled:
                for axis_name, size in dict(mesh.shape).items():
                    tel.mesh_devices.labels(axis=axis_name).set(size)

    def weights_checksum(self) -> str:
        """The loaded weights' integrity checksum (cached after the first
        computation — call it right after construction to RECORD the
        healthy value before any runtime corruption could land; a later
        :func:`integrity.params_checksum` over ``self.params`` is the
        VERIFY side). The cached value deliberately does NOT track
        ``self.params`` reassignment: it is the load-time record."""
        if self._weights_checksum is None:
            from distributed_llama_tpu.engine import integrity

            self._weights_checksum = integrity.params_checksum(self.params)
        return self._weights_checksum

    def _new_cache(self):
        if self._tp_engine is not None:
            return self._tp_engine.init_cache(self.cache_dtype)
        # per-layer cache list matching the per-layer params list, so
        # cache updates alias in place (see llama.init_cache)
        return llama.init_cache(self.cfg, dtype=self.cache_dtype, layered=True)

    def new_stream(self) -> EngineStream:
        """An independent generation stream (own KV cache + position) over
        this engine's weights. Each stream costs one KV cache of HBM."""
        return EngineStream(self, self._new_cache())

    @property
    def default_stream(self) -> EngineStream:
        if self._default is None:
            self._default = EngineStream(self, self._new_cache())
        return self._default

    # ------------------------------------------------------------------
    # Single-stream delegation (the classic engine surface)
    # ------------------------------------------------------------------

    @property
    def pos(self) -> int:
        return self.default_stream.pos

    @pos.setter
    def pos(self, value: int) -> None:
        self.default_stream.pos = value

    @property
    def cache(self):
        return self.default_stream.cache

    @cache.setter
    def cache(self, value) -> None:
        self.default_stream.cache = value

    @property
    def stats(self) -> list[TokenStats]:
        return self.default_stream.stats

    def reset(self) -> None:
        self.default_stream.reset()

    def rollback(self, pos: int) -> None:
        self.default_stream.rollback(pos)

    def forward(self, tokens) -> np.ndarray:
        return self.default_stream.forward(tokens)

    def prefill(self, tokens) -> np.ndarray:
        return self.default_stream.prefill(tokens)

    def prefill_device(self, tokens, temperature, topp, seed: int, topk: int = 0):
        return self.default_stream.prefill_device(
            tokens, temperature, topp, seed, topk
        )

    def decode_step(self, token: int) -> np.ndarray:
        return self.default_stream.decode_step(token)

    def fetch_first_token(self, first_token) -> int:
        return self.default_stream.fetch_first_token(first_token)

    def generate_on_device(self, *args, **kwargs) -> np.ndarray:
        return self.default_stream.generate_on_device(*args, **kwargs)

    def decode_chunk(self, *args, **kwargs):
        return self.default_stream.decode_chunk(*args, **kwargs)

    def generate_chunks(self, *args, **kwargs):
        return self.default_stream.generate_chunks(*args, **kwargs)

    def stream_decode(self, *args, **kwargs) -> int:
        return self.default_stream.stream_decode(*args, **kwargs)

    def avg_stats(self) -> TokenStats:
        return self.default_stream.avg_stats()

    def total_tokens(self) -> int:
        return self.default_stream.total_tokens()

    # ------------------------------------------------------------------
    # Shared internals
    # ------------------------------------------------------------------

    # decoded tokens between transfer re-measurements: the estimate follows
    # actual interconnect load over a session for the cost of one tiny
    # probe dispatch every ~512 tokens, instead of staying a
    # construction-time constant
    TRANSFER_REFRESH_TOKENS = 512

    def _transfer_ms_per_token(self) -> float:
        """Per-dispatch collective cost: 0 on a single chip; under TP/SP
        measured on the real mesh and re-measured periodically in situ.

        Refreshes happen only at QUIESCENT points (no dispatch in flight on
        ANY stream): inside the pipelined chunk loop a probe would queue
        behind the in-flight chunk and time its compute, poisoning the very
        split it feeds. The prefill/forward/decode_chunk paths all reach here
        right after their own fetch drained the stream, so every API request
        and every stepwise loop refreshes on cadence; generate_chunks reuses
        the last measurement."""
        if self._tp_engine is None:
            return 0.0
        # the depth check and the probe run under the SAME lock that
        # dispatchers raise the depth under, so a concurrent stream cannot
        # enqueue a chunk between the check and the measurement (the probe
        # would queue behind it and time its compute); dispatchers briefly
        # block on the lock during a refresh (~once per 512 tokens)
        with self._depth_lock:
            if self._pipeline_depth > 0:
                # never measure mid-flight (even the FIRST time — a caller
                # whose first op is generate_chunks would otherwise cache a
                # poisoned estimate); report 0 until a quiescent call measures
                return self._transfer_ms or 0.0
            # cadence counts tokens across ALL streams: API traffic on
            # non-default slots must still drive the periodic re-measurement
            n = sum(s.n_tokens for st in self._streams for s in st.stats)
            if (
                self._transfer_ms is None
                or n - self._transfer_measured_at >= self.TRANSFER_REFRESH_TOKENS
            ):
                try:
                    self._transfer_ms = self._tp_engine.measure_transfer_ms()
                except Exception:
                    # a failed probe (flaky interconnect, injected tp.transfer
                    # fault) must not kill the request that happened to
                    # trigger it: keep the previous estimate (0 before any
                    # measurement succeeded) and retry next cadence
                    if self._transfer_ms is None:
                        self._transfer_ms = 0.0
                self._transfer_measured_at = n
            return self._transfer_ms

    def _maybe_refresh_transfer(self) -> None:
        """Opportunistic cadence refresh at the end of a decode stream —
        the device-decode serving flow otherwise computes every stats entry
        mid-flight and would never measure. Only when the cadence is DUE
        (the extra drain fetch is a host sync): drain any
        leftover speculative chunk first so the probe cannot queue behind
        it and time its compute."""
        if self._tp_engine is None:
            return
        with self._depth_lock:
            n = sum(s.n_tokens for st in self._streams for s in st.stats)
            due = (
                self._transfer_ms is None
                or n - self._transfer_measured_at >= self.TRANSFER_REFRESH_TOKENS
            )
            if not due or self._pipeline_depth > 0:
                return
        np.asarray(jnp.zeros(2) + 1)  # fence: drains the device queue
        self._transfer_ms_per_token()  # re-checks depth under the lock

    def _last_dispatches(self) -> int:
        """How many device programs the most recent forward issued (the sp
        backend's chunked mid-context prefill issues several; every other
        path is exactly one)."""
        return getattr(self._tp_engine, "last_forward_dispatches", 1) or 1

    def _split_stats(
        self, per_entry_ms: float, n_tokens: int = 1, n_dispatches: int = 1
    ) -> TokenStats:
        """I/T split of one timed dispatch: the measured collective cost is an
        upper bound (XLA overlaps collectives with compute in the real
        program), so clamp it to the observed time — inference_ms must not go
        negative. An entry that covers several dispatches (the sp backend's
        chunked mid-context prefill) pays the collective sequence once per
        dispatch."""
        transfer = min(self._transfer_ms_per_token() * n_dispatches, per_entry_ms)
        return TokenStats(
            per_entry_ms, per_entry_ms - transfer, transfer, n_tokens=n_tokens
        )

    def _sample_row(self, logits, row, seed32, pos, temperature, topp, topk):
        """Sample from one row of device logits entirely on device (the
        prefill→decode fusion: no logits fetch), coin keyed on the row's
        absolute position. Under TP/SP the logits returned by the backend's
        forward are already full-vocab and replicated, so a replicated
        sample is correct on every backend (same counter → same token)."""
        return _sample_row_jit(logits, row, seed32, pos, temperature, topp, topk)

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
    def _forward_single(cfg: LlamaConfig, params, tokens, cache, pos, n_real=None):
        return llama.forward_tokens(cfg, params, tokens, cache, pos, n_real=n_real)


@jax.jit
def _sample_row_jit(logits, row, seed32, pos, temperature, topp, topk):
    from distributed_llama_tpu.models import sampling

    return sampling.sample_token(logits[row], seed32, pos, temperature, topp, topk)
