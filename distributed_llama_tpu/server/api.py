"""OpenAI-compatible API server: POST /v1/chat/completions, GET /v1/models.

Behavior parity with the reference's dllama-api
(reference: src/apps/dllama-api/dllama-api.cpp): SSE streaming chunks
(:168-185), per-request temperature/seed/max_tokens overrides (:351-380),
the NaiveCache longest-message-prefix KV reuse (:187-241), and the same
response JSON shapes (types.hpp:10-147).

Beyond the reference, completions are CONCURRENT: ``--parallel N`` (default
2) serves N in-flight completions on one engine, each on its own
:class:`~distributed_llama_tpu.engine.engine.EngineStream` (own KV cache +
prefix cache; weights and compiled programs shared). Requests are assigned
to the free stream whose chat-prefix cache matches best, so multi-turn
conversations keep their KV reuse under concurrency. The reference is
architecturally single-stream — one socket accept drives one inference at a
time (dllama-api.cpp:418-423).

With ``--batch-decode`` (the default from the CLI, on the single-chip and
tp backends with ``--decode device``), the N lanes are rows of one
:class:`~distributed_llama_tpu.engine.batch.BatchScheduler` slab instead of
independent streams: concurrent completions COALESCE into one batched
decode dispatch per chunk, reading each weight matrix once per step for
all of them — near-B× aggregate tok/s on the HBM-bound decode instead of
the fairness-only interleaving above (docs/PERF.md). SSE streaming,
per-request stop/seed/temperature and the chat-prefix NaiveCache are
unchanged: a BatchStream wears the EngineStream serving surface.

Intentional fixes over the reference:
* request ``stop`` sequences are actually honored (the reference parses them
  but its EosDetector is constructed once with only the tokenizer stops,
  dllama-api.cpp:396-399 — request stops never reach it);
* the delta prompt is prefilled in one batched forward instead of
  token-by-token;
* decode runs on device in chunks (sampling included) instead of paying a
  host<->device round trip per token — ``--decode host`` restores the
  reference's stepwise regime;
* a truncated prompt is surfaced to the caller (a ``warning`` key in the
  response / final SSE chunk), not just printed to server stdout.

Built on stdlib http.server — the reference hand-rolls HTTP on raw sockets
(dllama-api.cpp:38-147); there is no reason to reproduce that on a host
runtime that has an HTTP stack.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import math
import os
import random
import signal
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from distributed_llama_tpu import lockcheck, retry, telemetry
from distributed_llama_tpu.engine import faults, integrity
from distributed_llama_tpu.engine.faults import DeadlineExceeded
from distributed_llama_tpu.server.admission import (
    DEFAULT_TENANT,
    AdmissionRejected,
    FairAdmission,
    ServerDraining,
    parse_tenants,
)
from distributed_llama_tpu.server import fleet
from distributed_llama_tpu.server.replicas import (
    NoPlaceableReplica,
    Replica,
    ReplicaPool,
)
from distributed_llama_tpu.telemetry import Stopwatch, flight, trace
from distributed_llama_tpu.telemetry.capture import Capture, CaptureBusy, NoCapture
from distributed_llama_tpu.telemetry.trace import RequestTraceStore
from distributed_llama_tpu.tokenizer import (
    ChatItem,
    ChatTemplate,
    ChatTemplateType,
    EosDetector,
    EosDetectorResult,
    Sampler,
    Tokenizer,
    chat_stops,
    is_safe_piece,
)

MODEL_NAME = "Distributed Model"  # (reference: types.hpp:54, 80)


def new_request_id() -> str:
    """Request correlation id: threaded through response ids, error bodies,
    the X-Request-Id header, and server logs (the reference's responses are
    anonymous — a fixed "cmpl-j0" for every request, types.hpp:58)."""
    return uuid.uuid4().hex[:16]


class BadRequest(ValueError):
    """Client error in a request body — mapped to HTTP 400 by the handler."""


# AdmissionRejected (→429) and ServerDraining (→503) live with the
# weighted-fair admission machinery in server/admission.py (ISSUE 8) and
# are re-exported above for compatibility with existing imports.

# a preempted (or replica-loss-orphaned) request requeues through fair
# admission at most this many times before the server answers 503 +
# Retry-After: the deadline is the real bound, but a deadline-less victim
# under sustained higher-priority pressure (or cascading replica deaths)
# must not requeue forever on one handler thread
MAX_PREEMPT_REQUEUES = 3

# the requeue loop's shape, in the shared retry vocabulary (ISSUE 9
# satellite): N+1 total attempts, no sleep between them — the fair
# admission queue IS the backpressure
REQUEUE_POLICY = retry.BackoffPolicy(attempts=MAX_PREEMPT_REQUEUES + 1)

# the SDC canary's pinned probe prompt (ISSUE 10): any fixed string works —
# what matters is that the SAME prompt decodes greedily through the real
# batched path on every replica, so (tokens, fingerprint) has exactly one
# healthy value per weights+config (the pool golden, server/replicas.py)
CANARY_PROMPT = "integrity canary: count one two three four five"

# the canary row's priority sits below every real class, so a queued
# request preempts the probe instead of waiting behind it (the probe then
# reports "inconclusive" and retries next cycle)
CANARY_PRIORITY = -(1 << 30)


@dataclasses.dataclass
class CacheItem:
    end_pos: int
    role: str
    content: str


class NaiveCache:
    """Longest-message-prefix chat cache
    (reference: src/apps/dllama-api/dllama-api.cpp:187-232)."""

    def __init__(self):
        self.items: list[CacheItem] = []

    def push(self, end_pos: int, role: str, content: str) -> None:
        self.items.append(CacheItem(end_pos, role, content))

    def clear(self) -> None:
        self.items.clear()

    def resolve_delta_prompt(self, messages: list[dict]) -> tuple[int, list[dict]]:
        """Returns (start_pos, remaining_messages)."""
        if self.match_len(messages) == 0:
            self.clear()
            return 0, messages
        return self.items[-1].end_pos, messages[len(self.items):]

    def match_len(self, messages: list[dict]) -> int:
        """Number of cached messages this request would reuse (0 = no reuse).
        Non-mutating — the slot scheduler scores free streams with it."""
        n = len(self.items)
        if n == 0 or len(messages) <= n:
            return 0
        if all(
            self.items[i].role == messages[i]["role"]
            and self.items[i].content == messages[i]["content"]
            for i in range(n)
        ):
            return n
        return 0


@dataclasses.dataclass
class StreamSlot:
    """One concurrent completion lane: an engine stream plus its chat-prefix
    cache and (host-path) sampler. ``busy`` is guarded by the replica
    pool's condition lock (server/replicas.py)."""

    stream: object  # EngineStream
    cache: NaiveCache
    sampler: Sampler
    busy: bool = False
    tenant: str | None = None  # the occupying request's tenant (metrics)


class ApiState:
    def __init__(
        self, engine, tokenizer: Tokenizer, sampler: Sampler, args,
        engine_factory=None,
    ):
        self.engine = engine
        self.tokenizer = tokenizer
        self.sampler = sampler  # slot 0's sampler (kept as an attribute for tests)
        self.args = args
        stops = chat_stops(tokenizer)
        self.stops = stops
        template_type = getattr(args, "chat_template", None) or ChatTemplateType.UNKNOWN
        self.template = ChatTemplate(template_type, tokenizer.chat_template, stops[0])
        # N concurrent completion lanes PER REPLICA over R replicas
        # (ISSUE 9). Each replica is an independent failure domain — its
        # own engine, BatchScheduler slab and prefix-cache pool — behind
        # one admission front door; within a replica the lanes share its
        # slab (batched decode, one weight read per step). The reference
        # is single-threaded AND single-domain by construction
        # (dllama-api.cpp:418-423): one socket error kills everything.
        n = max(1, int(getattr(args, "parallel", 2) or 1))
        n_replicas = max(1, int(getattr(args, "replicas", 1) or 1))
        self._lanes = n
        self._engine_factory = engine_factory
        # versioned engine factories (ISSUE 18): the blue-green rollout
        # rebuilds replicas through a PER-VERSION zero-arg factory. The
        # boot factory registers under the boot version id; a rollout
        # target registers via register_weights_version (selfhost) or
        # register_weights_path (POST /admin/rollout with a "weights"
        # path, resolved through make_engine_for_path — installed by
        # serve(): args-clone + make_engine off the pod, group.sibling
        # on it, so a pod rollout places a SECOND params tree on the
        # same mesh/backend)
        self._boot_version = str(
            getattr(args, "weights_version", None) or "v0"
        )
        self._weights_versions: dict = {}
        if engine_factory is not None:
            self._weights_versions[self._boot_version] = engine_factory
        self.make_engine_for_path = None
        if n_replicas > 1 and engine_factory is None:
            print(
                "⚠️ replicas reduced to 1: no engine factory to build "
                "(or restart) additional replicas — serve() provides one"
            )
            n_replicas = 1
        # computed AFTER the factory clamp: a replicas>1 request that just
        # collapsed to 1 must not latch the bucket-1 batched scheduler a
        # single lane would never have chosen
        self._batch_wanted = (
            getattr(args, "batch_decode", False)
            and getattr(args, "decode", "device") == "device"
            # a single lane on a single replica keeps the proven
            # single-stream fast path (the bucket-1 batched program only
            # adds overhead); replicas REQUIRE the scheduler — it is the
            # failure domain being supervised
            and (n > 1 or n_replicas > 1)
        )
        # global prefix-cache tier (ISSUE 11): one shared radix index over
        # every replica's tree (placement routes to the owner of the
        # longest published chain) and one pool-wide host-RAM spill arena
        # (evicted pages reload instead of re-prefilling; an optional
        # mmap'd disk tier sits below it, echoing the reference's
        # disc-backed KV). Built BEFORE any replica so replica 0's
        # scheduler wires into them too.
        self._shared_index = None
        self._spill_arena = None
        if self._batch_wanted and getattr(args, "prefix_cache", True):
            page_sz = getattr(args, "kv_page_size", 64)
            if n_replicas > 1 and page_sz and page_sz >= 1:
                from distributed_llama_tpu.engine.prefix_cache import (
                    SharedPrefixIndex,
                )

                self._shared_index = SharedPrefixIndex(page_sz)
            spill_mb = getattr(args, "host_spill_mb", None)
            if spill_mb is None:
                # no default spill tier under an arch with recurrent state or
                # window layers, or whose latent layers have an indexer: a
                # state snapshot, a window layer's page and a page of latents
                # AND index keys have no spill form (asked for by flag, the
                # scheduler refuses by name)
                cfg = engine.cfg
                spill_mb = 64.0 if cfg.rewinds_by_position and not cfg.has_indexer else 0.0
            spill_mb = float(spill_mb)
            if spill_mb > 0:
                from distributed_llama_tpu.engine.spill import HostArena

                disk_dir = getattr(args, "spill_disk_dir", None)
                disk_mb = float(getattr(args, "spill_disk_mb", 0) or 0)
                self._spill_arena = HostArena(
                    int(spill_mb * (1 << 20)),
                    disk_path=(
                        os.path.join(disk_dir, "dllama-kv-spill.bin")
                        if disk_dir and disk_mb > 0 else None
                    ),
                    disk_budget_bytes=int(disk_mb * (1 << 20)),
                )
        # replica 0 FIRST: whether the batched path exists decides whether
        # more replicas make sense — discovering that after paying N-1
        # engine builds (full weight loads) would waste minutes and HBM
        replicas = [Replica(0, *self._build_replica(0, engine=engine))]
        self.batch = replicas[0].scheduler  # compat: tests/benches poke this
        if n_replicas > 1 and self.batch is None:
            # the sp/ep backends have no batched path, so no supervisable
            # scheduler: fall back to one replica rather than pretend
            print("⚠️ replicas reduced to 1: batch decode unavailable")
            n_replicas = 1
        replicas += [
            Replica(i, *self._build_replica(i)) for i in range(1, n_replicas)
        ]
        self.cache = replicas[0].slots[0].cache  # single-stream tests poke this
        # fault tolerance (ISSUE 3): bounded admission queue, per-request
        # deadlines, request-body cap, and the SIGTERM drain flag
        aq = getattr(args, "admission_queue", None)
        self.queue_limit = (
            max(0, int(aq)) if aq is not None else 2 * n * n_replicas
        )
        mb = getattr(args, "max_body_bytes", None)  # 0 is a valid cap — no falsy-or
        self.max_body_bytes = int(mb) if mb is not None else (1 << 20)
        self.default_deadline_ms = getattr(args, "deadline_ms", None)
        # multi-tenant weighted-fair admission (ISSUE 8): per-tenant
        # bounded queues with deficit-weighted dequeue into the serving
        # slots, priority classes first (server/admission.py). --tenants
        # declares weights/priorities; unknown tenants auto-register at
        # weight 1 / priority 0
        self.tenants = parse_tenants(getattr(args, "tenants", None))
        self.admission = FairAdmission(
            n * n_replicas, tenants=self.tenants, queue_limit=self.queue_limit
        )
        # server instrument bundle: bound BEFORE the pool so the pool's
        # replica-state gauges land in the same registry bundle
        self.tel = telemetry.ServerInstruments()
        # request-scoped tracing (ISSUE 16, telemetry/trace.py): the
        # bounded store behind GET /debug/trace/<id>. None with telemetry
        # off — every per-request hook downstream is then a single
        # `ctx is None` attribute check (the PR 1 zero-overhead contract)
        self.traces: RequestTraceStore | None = None
        if telemetry.is_enabled():
            sample = getattr(args, "trace_sample_rate", None)
            slow = getattr(args, "trace_slow_ttft_s", None)
            retention = getattr(args, "trace_retention", None)
            self.traces = RequestTraceStore(
                capacity=256 if retention is None else int(retention),
                sample_rate=1.0 if sample is None else float(sample),
                slow_ttft_s=1.0 if slow is None else float(slow),
            )
        # the capture control behind POST /debug/profile (ISSUE 23,
        # telemetry/capture.py): a short profiler trace of this process
        # with the spans on its timeline. None with telemetry off
        self.capture: Capture | None = (
            Capture(telemetry.TRACER) if telemetry.is_enabled() else None
        )
        # flight recorder (ISSUE 16, telemetry/flight.py): always on —
        # lifecycle events are rare; arm the fault-fire observer and the
        # optional on-death JSON artifact directory
        flight.install_fault_observer()
        dump_dir = getattr(args, "flight_dump_dir", None)
        if dump_dir:
            flight.RECORDER.dump_dir = str(dump_dir)
        # the supervised replica pool (ISSUE 9, server/replicas.py):
        # placement, health (healthy → suspect → dead off dispatch
        # round-trips + the stall watchdog), capacity resize on death,
        # and jittered-backoff restart supervision. Supervision needs the
        # factory (a restart rebuilds the engine); without one the single
        # replica keeps the PR 3 semantics (stall = StallTimeout, no
        # failover) — nothing to fail over TO.
        # no falsy-or on the replica flags: an explicit 0 is a legitimate
        # setting (0 restart base = immediate jitter-only retries) and must
        # not be silently rewritten to the default (the PR 3
        # admission_queue=0 bug class)
        suspect_s = getattr(args, "replica_suspect_s", None)
        restart_base = getattr(args, "replica_restart_backoff_s", None)
        self.pool = ReplicaPool(
            self._build_replica,
            replicas,
            admission=self.admission,
            tel=self.tel,
            supervise=engine_factory is not None and self.batch is not None,
            suspect_roundtrip_s=30.0 if suspect_s is None else float(suspect_s),
            restart_policy=retry.BackoffPolicy(
                attempts=retry.UNBOUNDED,
                base_s=0.5 if restart_base is None else float(restart_base),
                multiplier=2.0,
                max_s=30.0,
                jitter_s=0.5,
            ),
            shared_index=self._shared_index,
            spill_arena=self._spill_arena,
            weights_version=self._boot_version,
        )
        if self.batch is not None and getattr(args, "preempt", True):
            # priority preemption: a queued high-priority arrival may evict
            # the lowest-priority decode row ON ANY LIVE REPLICA to a clean
            # requeue (the hook runs OUTSIDE the admission lock — see
            # admission.acquire)
            self.admission.preempt_hook = self.pool.preempt_below
        # jittered Retry-After (ISSUE 8 satellite): a fixed value tells
        # every rejected client to come back on the same tick, and the
        # synchronized retry storm re-spikes the admission queue (loadgen's
        # bursty mode demonstrates it). Entropy-seeded ON PURPOSE — seeding
        # deterministically would re-synchronize replicas restored from the
        # same image, recreating the herd this exists to break up.
        self.retry_after_base_s = 1
        self.retry_after_jitter_s = max(
            0, int(getattr(args, "retry_after_jitter_s", 2) or 0)
        )
        self._retry_rng = random.Random()
        self.draining = False
        # silent-data-corruption detection (ISSUE 10, engine/integrity.py):
        # the pool's canary scheduler runs _canary_probe — a pinned greedy
        # prompt through each replica's REAL batched path on a directly
        # claimed lane, billed to the reserved internal tenant (no
        # admission permit, no fairness accounting) — and compares
        # (tokens, fingerprint) against the pool golden. 0 disables the
        # background thread; the probe stays armed for manual ticks and
        # the shadow-vote path either way.
        self.canary_prompt = (
            getattr(args, "sdc_canary_prompt", None) or CANARY_PROMPT
        )
        self.canary_tokens = int(getattr(args, "sdc_canary_tokens", 12) or 12)
        self.shadow_rate = float(getattr(args, "sdc_shadow_rate", 0.0) or 0.0)
        # entropy-seeded: this RNG only picks WHICH greedy requests get a
        # shadow re-execution — determinism here would shadow the same
        # schedule positions on every restored replica set
        self._shadow_rng = random.Random()
        # at most ONE shadow vote in flight: each vote serially re-decodes
        # on two replicas, and an unbounded thread-per-sample design would
        # let a hot request rate stack probes until they starve real
        # traffic of lanes; extra samples are simply dropped (it is a
        # sampling check — coverage comes from rate x uptime, not backlog)
        self._shadow_gate = threading.Semaphore(1)
        interval = getattr(args, "sdc_canary_interval_s", None)
        self.pool.start_canary(
            self._canary_probe,
            0.0 if interval is None else float(interval),
            fail_threshold=int(getattr(args, "sdc_canary_threshold", 2) or 2),
        )
        # bind-once fault-injection plan (engine/faults.py): the SSE writer
        # fires the server.send site through it (kind=disconnect models a
        # client vanishing mid-stream)
        self.faults = faults.active_plan()
        # zero-downtime fleet ops (ISSUE 18, server/fleet.py): the
        # blue-green rollout orchestrator and the SLO elasticity loop
        # share ONE non-blocking ops lock, so they never mutate the
        # fleet concurrently. Elasticity is opt-in: with no
        # --fleet-max-replicas the ceiling IS the boot count, and with
        # no --fleet-interval-s the controller only ticks manually.
        self._fleet_lock = lockcheck.make_lock("ApiState._fleet_lock")
        drain_s = getattr(args, "rollout_drain_s", None)
        self.rollout = fleet.RolloutOrchestrator(
            self,
            drain_timeout_s=15.0 if drain_s is None else float(drain_s),
            ops_lock=self._fleet_lock,
        )
        fleet_max = getattr(args, "fleet_max_replicas", None)
        self.fleet = fleet.FleetController(
            self,
            min_replicas=int(
                getattr(args, "fleet_min_replicas", None) or 1
            ),
            max_replicas=(
                int(fleet_max) if fleet_max is not None
                else len(self.pool.replicas)
            ),
            interval_s=float(
                getattr(args, "fleet_interval_s", None) or 0.0
            ),
            queue_high=getattr(args, "fleet_queue_high", None),
            ops_lock=self._fleet_lock,
        )
        # the info gauge names the pool's current version: exactly one
        # label at 1 (on_rollout_complete flips it)
        self.tel.weights_version_info.labels(
            version=self._boot_version
        ).set(1)

    # ------------------------------------------------------------------
    # Versioned weights registry (ISSUE 18, server/fleet.py)
    # ------------------------------------------------------------------

    def register_weights_version(
        self, version: str, factory, checksum: str | None = None,
    ) -> None:
        """Register a zero-arg engine factory for ``version`` — the
        rollout target's build path. ``checksum`` (optional) pre-seeds
        the version's reference; otherwise the first build's pristine
        load-time checksum records it."""
        self._weights_versions[str(version)] = factory
        if checksum is not None:
            self.pool.register_version(str(version), checksum)

    def has_weights_version(self, version: str) -> bool:
        return str(version) in self._weights_versions

    def register_weights_path(self, version: str, path: str) -> None:
        """Register ``version`` from a weight FILE path (the
        POST /admin/rollout ``"weights"`` field). Resolved through
        ``make_engine_for_path`` — installed by serve(): an args-clone +
        make_engine off the pod, ``group.sibling(path)`` on it (the
        second placed params tree)."""
        if self.make_engine_for_path is None:
            raise RuntimeError(
                "this server cannot load weight files at runtime "
                "(no path loader installed)"
            )
        self.register_weights_version(
            version, self.make_engine_for_path(str(path))
        )

    def on_rollout_complete(self, old_version: str, new_version: str) -> None:
        """Completion hook: drop the OLD version's factory — on the pod
        that releases the old placed params tree (the factory holds the
        old PodGroup; the last slice moved) — and flip the info gauge so
        a scrape names exactly one live pool version."""
        self._weights_versions.pop(old_version, None)
        self.tel.weights_version_info.labels(version=old_version).set(0)
        self.tel.weights_version_info.labels(version=new_version).set(1)

    @property
    def slots(self) -> list[StreamSlot]:
        """Every replica's serving lanes, flattened (the pre-pool surface:
        tests and shutdown paths iterate busy flags/streams through it)."""
        return self.pool.all_slots()

    def _make_scheduler(self, engine, replica_id: int):
        """Build one replica's BatchScheduler from the serving flags, or
        None when batching is off / the backend has no batched path."""
        if not self._batch_wanted:
            return None
        from distributed_llama_tpu.engine.batch import BatchScheduler

        args = self.args
        try:
            return BatchScheduler(
                engine, n_rows=self._lanes,
                chunk=getattr(args, "decode_chunk", 32),
                stall_timeout_s=getattr(args, "stall_timeout_s", None),
                # paged prefix cache (ISSUE 4): repeated prompt prefixes
                # (system prompts, replayed conversations) skip their
                # matched prefill; per-request `cache: off` opts out
                prefix_cache=getattr(args, "prefix_cache", True),
                kv_pages=getattr(args, "kv_pages", None),
                # no falsy-or: an explicit --kv-page-size 0 must reach
                # the scheduler's misconfiguration diagnostic, not be
                # silently rewritten to the default (the PR 3
                # admission_queue=0 bug class)
                page_size=getattr(args, "kv_page_size", 64),
                prefill_chunk=getattr(args, "prefill_chunk", 256),
                # self-speculative decode (ISSUE 6): batched verify
                # steps with prompt-lookup drafts; 0 (the default)
                # keeps the proven chunked dispatch
                spec_draft=getattr(args, "spec_draft", 0),
                spec_ngram=getattr(args, "spec_ngram", 3),
                replica_id=replica_id,
                # the global cache tier (ISSUE 11): every replica's tree
                # reports to the one shared index and spills into the one
                # pool-wide arena (both None when the tier is off)
                spill_arena=self._spill_arena,
                shared_index=self._shared_index,
            )
        except ValueError as e:  # backend without a batched path (sp/ep)
            print(f"⚠️ batch decode disabled: {e}")
            return None

    def _build_replica(self, idx: int, engine=None):
        """Build (or REBUILD — the pool supervisor calls this under the
        restart backoff) replica ``idx``: an engine, its scheduler, and
        its serving lanes. Returns ``(engine, scheduler_or_None, slots)``.
        Slot sampler seeds stay globally distinct across replicas so
        seedless sampled requests never correlate between lanes.

        Version-aware (ISSUE 18): the build resolves WHICH weights
        through the pool's rollout state machine (``target_version``) and
        that version's registered factory, so the orchestrator's cutover
        and the supervisor's death recovery both converge on the state
        machine's intent. The fresh engine's PRISTINE load-time checksum
        registers as the version's reference on first build — recorded
        before any runtime corruption (injected or real) could land."""
        pool = getattr(self, "pool", None)
        version = (
            pool.target_version(idx) if pool is not None
            else self._boot_version
        )
        if engine is None:
            factory = self._weights_versions.get(version)
            if factory is None:
                raise RuntimeError(
                    f"replica {idx} cannot be built: no engine factory "
                    f"for weights_version {version!r}"
                )
            engine = factory()
        try:
            engine.weights_version = version
        except AttributeError:
            pass  # slotted test doubles
        if pool is not None and version not in pool.weights_reference:
            try:
                pool.register_version(version, engine.weights_checksum())
            except Exception as e:
                print(f"⚠️ weight checksum unavailable: {e}")
        sched = self._make_scheduler(engine, idx)
        if sched is not None:
            streams = [sched.new_stream() for _ in range(self._lanes)]
            # ... and the program of all lanes busy at once, before any is
            sched.build_widest_decode_program()
        else:
            streams = [engine.default_stream] + [
                engine.new_stream() for _ in range(self._lanes - 1)
            ]
        base = self.sampler
        slots = [
            StreamSlot(
                s,
                NaiveCache(),
                base if idx == 0 and i == 0 and engine is self.engine
                else Sampler(
                    vocab_size=base.vocab_size, temperature=base.temperature,
                    topp=base.topp, topk=base.topk,
                    seed=base.seed + idx * self._lanes + i,
                    counter=base.counter,
                ),
            )
            for i, s in enumerate(streams)
        ]
        return engine, sched, slots

    def begin_drain(self) -> None:
        """Stop admitting new completions (SIGTERM): queued/new requests get
        503 + Retry-After, ``/readyz`` flips 503, in-flight requests finish.
        Idempotent."""
        self.draining = True
        self.admission.begin_drain()
        self.tel.draining.set(1)

    def retry_after(self) -> int:
        """Seconds for a 429/503 ``Retry-After`` header: base + uniform
        jitter, drawn PER RESPONSE, so a burst of rejected clients retries
        spread over the window instead of re-spiking the queue in sync."""
        return self.retry_after_base_s + self._retry_rng.randint(
            0, self.retry_after_jitter_s
        )

    def ready_payload(self) -> dict:
        """The ``/readyz`` JSON body (schema: docs/OBSERVABILITY.md
        "Readiness schema"). The plain 200/503 status contract is
        unchanged for existing probes — the body ADDS per-replica health
        state, queue depth, active rows and drain status for load
        balancers that read it (ISSUE 9 satellite)."""
        return {
            "status": "draining" if self.draining else "ready",
            "draining": self.draining,
            "queue_depth": self.admission.waiting(),
            # clamped: mid-failover the raw permit count is transiently
            # negative (resize removed a dead replica's capacity while its
            # victims still hold permits) — the schema promises >= 0
            "free_slots": max(0, self.admission.free_slots()),
            # fleet ops (ISSUE 18): the pool's CURRENT weight version and
            # the live rollout state machine ({"active": False} at rest;
            # per-replica versions ride each snapshot entry)
            "weights_version": self.pool.weights_version,
            "rollout": self.pool.rollout_status(),
            "replicas": self.pool.snapshot(),
        }

    def _canary_probe(self, rep, messages=None, tenant=None):
        """Execute one integrity probe on replica ``rep`` (ISSUE 10): a
        pinned greedy prompt (or ``messages`` — the shadow-vote path)
        through the replica's real batched decode on a directly claimed
        lane, prefix cache opted out (the probe must exercise THIS
        replica's weights, not shared pool pages) and priority below every
        real class (queued work preempts it). Returns the
        ``(tokens, fingerprint)`` pair the pool compares against its
        golden, or None when inconclusive — every lane busy, the probe
        preempted, or the replica lost mid-probe. ``tenant`` overrides
        the reserved billing identity (default the canary tenant; the
        rollout orchestrator certifies under ``_rollout``)."""
        tenant = tenant or integrity.CANARY_TENANT
        slot = self.pool.claim_slot(rep.idx, tenant=tenant)
        if slot is None:
            return None
        stream = slot.stream
        try:
            # the probe owns the lane for its duration: clear any previous
            # conversation's KV + chat cache (self-healing anyway, but the
            # stream position and the cache must agree)
            stream.reset()
            slot.cache.clear()
            stream.prefix_cache_enabled = False
            stream.tenant = tenant
            stream.priority = CANARY_PRIORITY
            msgs = messages or [
                {"role": "user", "content": self.canary_prompt}
            ]
            items = [ChatItem(m["role"], m["content"]) for m in msgs]
            prompt = self.template.generate(items, append_generation_prompt=True)
            toks = self.tokenizer.encode(prompt, add_bos=True)
            budget = stream.cfg.seq_len - len(toks) - 1
            n = max(1, min(self.canary_tokens, budget))
            if budget < 1:
                return None  # probe prompt does not fit this config
            first_dev = stream.prefill_device(toks, 0.0, self.args.topp, 0)
            out: list[int] = []

            def on_token(prev: int, t: int) -> bool:
                out.append(int(t))
                return len(out) < n

            stream.stream_decode(
                first_dev, on_token, 0.0, self.args.topp, seed=0,
                first_prev=toks[-1], limit=len(toks) + n,
            )
            if not out:
                return None
            # BatchStream carries the device logit fingerprints; fold the
            # deterministic prefix covering exactly the decoded tokens
            # (len(out) - 1: the fused first token precedes the chunks).
            # An independent EngineStream (no batched path) compares
            # tokens only — fingerprint None on both sides of the golden
            fp = (
                stream.run_fingerprint(len(out) - 1)
                if hasattr(stream, "run_fingerprint") else None
            )
            return tuple(out), fp
        except (faults.RowPreempted, faults.ReplicaLost, DeadlineExceeded):
            return None  # yielded to real work / the replica died mid-probe
        except faults.RowQuarantined:
            # a LOUD failure (non-finite logits, corrupt chunk): the
            # quarantine machinery already owns it; the canary's verdict
            # on silent corruption is simply inconclusive this cycle
            return None
        finally:
            try:
                stream.reset()
            except Exception:
                pass
            slot.cache.clear()
            stream.prefix_cache_enabled = True
            stream.tenant = None
            stream.priority = None
            self.pool.release(slot)

    def _maybe_shadow(self, params: dict) -> None:
        """Cross-replica shadow voting (ISSUE 10, ``--sdc-shadow-rate``):
        a sampled fraction of completed GREEDY requests re-executes on two
        live replicas off the request path (a daemon thread — the client's
        latency never pays for the vote); divergence marks both suspect
        and the canary resolves which one is corrupt."""
        if (
            self.shadow_rate <= 0.0
            or params["temperature"] != 0.0
            or len(self.pool.replicas) < 2
            or self._shadow_rng.random() >= self.shadow_rate
        ):
            return
        if not self._shadow_gate.acquire(blocking=False):
            return  # a vote is already in flight: drop this sample

        def vote():
            try:
                self.pool.shadow_vote(self._canary_probe, params["messages"])
            finally:
                self._shadow_gate.release()

        threading.Thread(
            target=vote, name="dllama-sdc-shadow", daemon=True
        ).start()

    def _route_tokens(self, params: dict):
        """Full-prompt token ids for shared-index placement (ISSUE 11):
        the same template+encode the admission prefill will run, computed
        once per request so ``place`` can rank replicas by the longest
        chain they actually own. None when the tier is off, the request
        opted out of the prefix cache, or nothing is published yet (the
        re-encode costs one pass over the message history — skip it
        until the index can possibly answer)."""
        if (
            self._shared_index is None
            or len(self._shared_index) == 0
            or params.get("cache", "on") == "off"
        ):
            return None
        items = [
            ChatItem(m["role"], m["content"]) for m in params["messages"]
        ]
        prompt = self.template.generate(items, append_generation_prompt=True)
        return self.tokenizer.encode(prompt, add_bos=True)

    def _acquire_slot(
        self, messages: list[dict], deadline: float | None = None,
        tenant: str = DEFAULT_TENANT, priority: int = 0, route_tokens=None,
        ctx=None,
    ) -> StreamSlot:
        """Take a free lane through weighted-fair admission: when all are
        busy the request queues BOUNDEDLY under its own tenant (excess get
        AdmissionRejected → 429), slots are granted priority-class-first
        then deficit-weighted round-robin across tenants, a high-priority
        arrival may preempt a lower-priority decode row (the admission
        hook), and a queued request whose deadline expires leaves with
        DeadlineExceeded → 504 instead of burning its remaining budget in
        line. Placement then picks the lane through the replica pool:
        best chat-prefix affinity first (multi-turn KV reuse survives
        concurrency), least-loaded HEALTHY replica on ties — suspect
        replicas are a fallback, dead ones never place (ISSUE 9)."""
        sw = Stopwatch()
        tel = self.tel
        try:
            with trace.span(ctx, "queue_wait"):
                self.admission.acquire(tenant, priority, deadline, trace=ctx)
        except AdmissionRejected:
            tel.admission_rejected.inc()
            tel.tenant_rejected.labels(tenant=tenant).inc()
            raise
        finally:
            tel.tenant_queue_depth.labels(tenant=tenant).set(
                self.admission.queue_depth(tenant)
            )
        if self.draining:
            # a SIGTERM that landed while this request queued: give the slot
            # back and bounce — the drain waiter counts acquirable slots
            self.admission.release()
            raise ServerDraining("server is draining; not admitting")
        queue_s = sw.elapsed_s()
        tel.queue_wait.observe(queue_s)
        tel.tenant_admitted.labels(tenant=tenant).inc()
        tel.tenant_active.labels(tenant=tenant).inc()
        if ctx is not None:
            ctx.add_stage("queue", queue_s)
        sw.restart()
        try:
            with trace.span(ctx, "placement"):
                slot = self.pool.place(
                    messages, deadline, route_tokens=route_tokens
                )
        except BaseException:
            # placement raced a replica death (or the deadline): give the
            # permit back — a raised ReplicaLost re-enters the requeue
            # loop and takes a fresh pass through fair admission
            self.admission.release()
            tel.tenant_active.labels(tenant=tenant).dec()
            raise
        if ctx is not None:
            ctx.add_stage("placement", sw.elapsed_s())
        slot.tenant = tenant
        return slot

    def _release_slot(self, slot: StreamSlot) -> None:
        tenant = slot.tenant or DEFAULT_TENANT
        self.pool.release(slot)
        self.admission.release()
        self.tel.tenant_active.labels(tenant=tenant).dec()

    def complete(
        self, body: dict, send_chunk, params: dict | None = None,
        request_id: str | None = None,
    ) -> dict | None:
        """Run one completion. ``send_chunk(str)`` streams SSE data lines when
        the request has stream=true (then returns None); otherwise returns the
        final JSON payload. Up to ``--parallel`` calls run concurrently, each
        on its own stream; excess calls queue.
        ``params``: the pre-validated result of :meth:`_parse` (the handler
        validates before sending SSE headers, so validation runs once).
        ``request_id``: correlation id threaded into response ids (one is
        generated when the caller has none)."""
        if params is None:
            params = self._parse(body)
        if request_id is None:
            request_id = new_request_id()
        # deadline: request deadline_ms, else the server default; converted
        # to a monotonic instant ONCE so queue wait, prefill and decode all
        # burn the same budget — ACROSS preemption requeues too. Enforced
        # here per token (feed), by the batch scheduler between chunks, and
        # by the bounded admission queue.
        deadline_ms = params.get("deadline_ms") or self.default_deadline_ms
        deadline = (
            time.monotonic() + float(deadline_ms) / 1000.0
            if deadline_ms else None
        )
        # canonicalize ONCE: past the admission registry's auto-register
        # cap, unknown names fold into the default bucket here — before
        # any per-tenant metric label is minted from the raw client string
        tenant = self.admission.resolve(params.get("tenant") or DEFAULT_TENANT)
        priority = params.get("priority")
        if priority is None:
            priority = self.admission.config(tenant).priority
        if self.draining:
            raise ServerDraining("server is draining; not admitting")
        # requeue-and-replay (ISSUE 8 preemption, ISSUE 9 replica loss):
        # an evicted request — or one whose WHOLE REPLICA died — re-enters
        # fair admission and RE-RUNS from its prompt on whatever live
        # replica placement picks; the re-run (same pinned seed) decodes
        # bit-identically, so suppressing the first `sent` SSE deltas
        # replays exactly the continuation the client is owed.
        # pin the sampling seed ONCE per request, not per attempt: seedless
        # sampled requests otherwise re-derive a fresh wall-clock seed in
        # _complete_on on every requeue, and the re-run samples a DIFFERENT
        # completion whose replayed prefix guarded_send would silently
        # splice onto the first run's already-sent deltas
        if params.get("seed") is None:
            params["seed"] = int(time.time_ns() % (1 << 31))
        # request trace (ISSUE 16): one context for the WHOLE requeue loop
        # — failover/preemption replays become sibling attempts in one
        # tree, never separate traces. None when telemetry is off.
        traces = self.traces
        ctx = (
            traces.begin(request_id, tenant) if traces is not None else None
        )
        attempts = 0
        sent = 0
        skip = 0

        def guarded_send(data: str):
            nonlocal sent, skip
            if skip > 0:
                skip -= 1  # an already-delivered delta, identical by the
                return False  # bit-parity contract — swallow the replay
            send_chunk(data)
            sent += 1

        route_tokens = self._route_tokens(params)

        def attempt_once():
            nonlocal attempts, skip
            skip = sent  # re-runs replay (and suppress) what was delivered
            if ctx is not None:
                # attempt > 0 is a requeue re-run: tagged `replayed` so the
                # tree distinguishes the original from its failover/
                # preemption replays, and its stage time folds into the
                # `replay` attribution bucket (trace.TraceContext)
                ctx.begin_attempt(replayed=attempts > 0)
            attempts += 1
            slot = self._acquire_slot(
                params["messages"], deadline, tenant, priority, route_tokens,
                ctx=ctx,
            )
            # the slot's OWN scheduler (its replica's), not replica 0's:
            # request-end bookkeeping must land on the scheduler that
            # actually served the row
            sched = getattr(slot.stream, "scheduler", None)
            if ctx is not None:
                ctx.set_replica(
                    sched.replica_id if sched is not None else 0
                )
            try:
                slot.stream.deadline = deadline
                # per-request prefix-cache opt-out (`cache: off` in the
                # body): the row neither matches nor publishes shared pages
                slot.stream.prefix_cache_enabled = (
                    params.get("cache", "on") != "off"
                )
                # label the row for preempt_below's victim selection
                slot.stream.tenant = tenant
                slot.stream.priority = priority
                # hand the row its trace so the scheduler's shared chunk
                # dispatches can fan per-row child spans into this tree
                slot.stream.trace = ctx
                return self._complete_on(
                    slot, params, guarded_send, request_id, deadline,
                    route_tokens=route_tokens, ctx=ctx,
                )
            finally:
                slot.stream.deadline = None
                slot.stream.prefix_cache_enabled = True
                slot.stream.tenant = None
                slot.stream.priority = None
                slot.stream.trace = None
                if sched is not None:
                    # drop an unconsumed eviction marker (the request beat
                    # its preemption to the finish line) so it cannot leak
                    # into the row's next request
                    sched.retract_preemption(slot.stream)
                self._release_slot(slot)

        def on_requeue(attempt: int, e: Exception) -> None:
            if isinstance(e, faults.ReplicaCorrupt) and sent > 0:
                # the replica died of SILENT CORRUPTION and this stream
                # already delivered deltas — which may themselves be
                # wrong. A suppressed replay assumes the sent prefix was
                # correct (the bit-parity contract) and would SPLICE a
                # corrupt prefix onto a healthy continuation; failing
                # loudly (typed `replica_corrupt`, the client restarts
                # from scratch) is the only honest exit. Raising here
                # aborts the requeue loop (retry.retry_call's on_retry
                # hatch). A victim with nothing streamed replays like any
                # replica loss — nothing corrupt ever reached the client.
                raise e
            if isinstance(e, NoPlaceableReplica):
                # a placement bounce: nothing ran, so nothing replays —
                # counting it would inflate replayed_requests exactly when
                # replays are FAILING (the OBSERVABILITY.md health read
                # compares the counter against the victim count)
                return
            if isinstance(e, faults.ReplicaLost):
                # failover replay: the victim's replica died mid-flight;
                # the next attempt places on a surviving replica. The
                # pool's ledger increments under its lock — a failover's
                # victims requeue CONCURRENTLY, and a lost increment would
                # read as "victims dying at the requeue cap"
                self.pool.count_replay()
                self.tel.replayed_requests.inc()
            else:
                self.tel.preempt_requeues.inc()

        try:
            result = retry.retry_call(
                attempt_once, REQUEUE_POLICY,
                retry_on=(faults.RowPreempted, faults.ReplicaLost),
                on_retry=on_requeue,
            )
        finally:
            if ctx is not None:
                # server-side SLO surface: TTFT/TPOT and the stage
                # breakdown observe the SAME timestamps the trace tree
                # reports, so /metrics and /debug/trace/<id> can never
                # disagree about what they measured. In the finally: a
                # failed request still attributes where its time went.
                if ctx.ttft_s is not None:
                    self.tel.ttft.labels(tenant=tenant).observe(ctx.ttft_s)
                if ctx.tpot_s is not None:
                    self.tel.tpot.labels(tenant=tenant).observe(ctx.tpot_s)
                for stg, seconds in dict(ctx.stages).items():
                    self.tel.stage_seconds.labels(
                        stage=stg, tenant=tenant
                    ).observe(seconds)
                traces.finish(ctx)
        # shadow voting samples completed greedy requests (ISSUE 10):
        # off-path, after the client already has its stream/result
        self._maybe_shadow(params)
        return result

    def _complete_on(
        self, slot: StreamSlot, params: dict, send_chunk, request_id: str,
        deadline: float | None = None, route_tokens=None, ctx=None,
    ) -> dict | None:
        engine, tokenizer = slot.stream, self.tokenizer
        stream = params["stream"]
        # stage attribution clock (ISSUE 16): prefill = entry → prefill
        # dispatch returned (tokenize + cache resolve + dispatch), decode =
        # the rest of the token loop. Measured only for traced requests.
        stage_sw = Stopwatch() if ctx is not None else None
        if deadline is not None and time.monotonic() >= deadline:
            raise DeadlineExceeded("deadline expired before prefill")

        start_pos, delta_messages = slot.cache.resolve_delta_prompt(params["messages"])
        if start_pos and not engine.cfg.rewinds_by_position:
            # a recurrent state or a window layer's ring cannot be rewound to
            # where the cached messages end: the conversation prefills again
            # from 0, through the prefix cache, which resumes it from its
            # deepest snapshot or kept window tail
            slot.cache.clear()
            start_pos, delta_messages = 0, params["messages"]
        engine.rollback(min(start_pos, engine.pos))
        if engine.pos != start_pos:  # cache said resume further than engine state
            engine.reset()
            slot.cache.clear()  # stale end_pos values no longer map to engine positions
            start_pos = 0
            delta_messages = params["messages"]

        if start_pos == 0 and route_tokens is not None:
            # a fresh admission prefills the FULL prompt — exactly the
            # template+encode _route_tokens already ran for shared-index
            # placement; reuse it instead of tokenizing the whole message
            # history a second time on the hot path (a continuing
            # conversation's delta prompt differs and re-encodes below)
            prompt_tokens = route_tokens
        else:
            items = [ChatItem(m["role"], m["content"]) for m in delta_messages]
            prompt = self.template.generate(items, append_generation_prompt=True)
            prompt_tokens = self.tokenizer.encode(prompt, add_bos=True)
        seq_len = engine.cfg.seq_len
        budget = seq_len - engine.pos
        warning = None
        if len(prompt_tokens) > budget:
            warning = (
                f"prompt truncated: {len(prompt_tokens)} tokens > "
                f"{budget} remaining context (seq_len {seq_len})"
            )
            print(f"⚠️ {warning}")
            prompt_tokens = prompt_tokens[:budget]
        prompt_end = start_pos + len(prompt_tokens)
        for m in delta_messages:
            slot.cache.push(prompt_end, m["role"], m["content"])

        max_pos = prompt_end + params["max_tokens"] if params["max_tokens"] > 0 else seq_len
        max_pos = min(max_pos, seq_len)
        # completion budget in emitted tokens (OpenAI max_tokens semantics);
        # zero budget (prompt fills the remaining context) emits nothing —
        # and must NOT take the fused path, whose depth hold is only
        # released at the first-token fetch that would never happen
        max_new = max_pos - prompt_end

        topp = params.get("topp", self.args.topp)
        topk = params.get("topk", getattr(self.args, "topk", 0) or 0)
        slot.sampler.set_temperature(params["temperature"])
        slot.sampler.topp = topp
        slot.sampler.set_topk(topk)
        # complete() pins params["seed"] (wall-clock for seedless requests)
        # BEFORE the first attempt, so requeue replays re-draw the same
        # coins — one defaulting site, there, not here
        seed = params["seed"]
        slot.sampler.set_seed(seed)

        device_decode = getattr(self.args, "decode", "device") == "device" and max_new > 0
        with trace.span(
            ctx, "prefill", tokens=len(prompt_tokens), start_pos=start_pos,
            fused=device_decode,
        ):
            if device_decode:
                # prefill→decode fusion: the first generated token is
                # sampled on device and never visits the host before chunk 1
                # is dispatched — the device goes from prefill straight into
                # decode instead of idling through a host fetch (docs/PERF.md)
                first_dev = engine.prefill_device(
                    prompt_tokens, params["temperature"], topp, seed, topk
                )
            else:
                logits = engine.prefill(prompt_tokens)
        if ctx is not None:
            ctx.add_stage("prefill", stage_sw.elapsed_s())
            stage_sw.restart()

        max_stop = max(len(s) for s in self.stops + params["stop"]) if (self.stops or params["stop"]) else 0
        detector = EosDetector(
            {tokenizer.chat_eos_id},
            self.stops + params["stop"],
            padding_left=max_stop,
            padding_right=max_stop,
        )

        buffer = []
        emitted = 0
        held = 0  # tokens fed whose text has not been handed over yet
        answered = 0  # tokens in a non-streamed answer, counted at its return
        finish_reason = "length"  # overwritten on EOS/stop exit

        def hand_over(text: str) -> None:
            """``text`` goes to the client: now as an SSE delta, or with the
            answer. One piece may carry several tokens (those a possible
            stop-string prefix held back); a matched stop string's tokens
            are never handed over."""
            nonlocal held, answered
            buffer.append(text)
            if stream:
                with trace.span(ctx, "sse_send", chars=len(text)):
                    sent = send_chunk(
                        self._chunk_json(text, stop=False, request_id=request_id)
                    )
                if sent is not False:  # False: a replayed delta, swallowed
                    self.tel.tokens_streamed.inc(held)
            else:
                answered += held
            held = 0

        def feed(prev: int, token: int) -> EosDetectorResult:
            nonlocal emitted, held
            if deadline is not None and time.monotonic() >= deadline:
                # per-token deadline enforcement (both decode paths; the
                # batch scheduler additionally retires the row between
                # chunks): the stream ends 504 / an SSE error event
                raise DeadlineExceeded(
                    f"deadline expired after {emitted} tokens"
                )
            emitted += 1
            held += 1
            if ctx is not None:
                # the TTFT/TPOT stamp: first mark is time-to-first-token,
                # the spread of the rest is time-per-output-token
                ctx.mark_token()
            piece = tokenizer.decode_piece(prev, token)
            res = detector.append(token, piece if is_safe_piece(piece) else b"")
            if res in (EosDetectorResult.NOT_EOS, EosDetectorResult.EOS):
                delta = detector.get_delta()
                if delta:
                    hand_over(delta.decode("utf-8", errors="replace"))
                detector.clear()
            return res

        res = EosDetectorResult.NOT_EOS
        decode_t0 = time.monotonic()
        try:
            if device_decode:  # implies max_new > 0 (see device_decode above)
                if max_new == 1:
                    # 1-token completion: fetch the fused token directly — a
                    # decode stream would dispatch a whole speculative chunk
                    # whose output is discarded
                    token = engine.fetch_first_token(first_dev)
                    res = feed(prompt_tokens[-1], token)
                    if res == EosDetectorResult.EOS:
                        finish_reason = "stop"
                else:
                    # fast path: chunked on-device decode+sampling (temperature
                    # and top-p are runtime values — no per-request recompile);
                    # the fused first token arrives with the stream
                    def on_token(prev: int, t: int) -> bool:
                        nonlocal res, finish_reason
                        res = feed(prev, t)
                        if res == EosDetectorResult.EOS:
                            finish_reason = "stop"
                            return False
                        return emitted < max_new

                    engine.stream_decode(
                        first_dev, on_token, params["temperature"], topp,
                        seed=seed, chunk=getattr(self.args, "decode_chunk", 32),
                        limit=max_pos, first_prev=prompt_tokens[-1],
                        # self-speculative decode (--spec-draft k): prompt-lookup
                        # drafts over this request's prompt + output, verified
                        # k at a time in one weight read; 0 = plain chunked path
                        spec_draft=getattr(self.args, "spec_draft", 0),
                        spec_ngram=getattr(self.args, "spec_ngram", 3),
                        prompt_tokens=prompt_tokens,
                        topk=topk,
                    )
            else:
                # --decode host: the per-token fallback regime — every token
                # pays a logits fetch + host sort, counted by
                # dllama_host_sampler_fallback_total; the counter-mode sampler
                # keys each coin on the consumed position, so the stream is
                # token-identical to the device path per seed
                if max_new > 0:
                    token = slot.sampler.sample(logits, pos=engine.pos - 1)
                    res = feed(prompt_tokens[-1], token)
                if res == EosDetectorResult.EOS:
                    finish_reason = "stop"
                elif emitted < max_new and engine.pos < seq_len:
                    while emitted < max_new and engine.pos < seq_len:
                        prev = token
                        logits = engine.decode_step(prev)
                        token = slot.sampler.sample(logits, pos=engine.pos - 1)
                        res = feed(prev, token)
                        if res == EosDetectorResult.EOS:
                            finish_reason = "stop"
                            break
        finally:
            if ctx is not None:
                # the whole token loop as one span (the scheduler fans per-row
                # batch_decode_chunk_row children into the same tree)
                ctx.add_span(
                    "decode_stream", decode_t0, time.monotonic() - decode_t0,
                    emitted=emitted, finish=finish_reason,
                )
                ctx.add_stage("decode", stage_sw.elapsed_s())
        if finish_reason == "length":
            # length-limited exit: flush text held back as a possible stop-
            # string prefix (MAYBE_EOS) so the response tail is not lost
            tail = detector.flush_delta()
            if tail:
                hand_over(tail.decode("utf-8", errors="replace"))

        content = "".join(buffer)
        if engine.pos >= seq_len:
            slot.cache.clear()  # (reference: dllama-api.cpp:330-334)
        else:
            slot.cache.push(engine.pos, "assistant", content)

        if stream:
            send_chunk(
                self._chunk_json("", stop=True, finish_reason=finish_reason,
                                 warning=warning, request_id=request_id)
            )
            send_chunk("[DONE]")
            return None
        self.tel.tokens_streamed.inc(answered)
        result = {
            "id": f"chatcmpl-{request_id}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": MODEL_NAME,
            "usage": {
                "prompt_tokens": len(prompt_tokens),
                "completion_tokens": emitted,
                "total_tokens": len(prompt_tokens) + emitted,
            },
            "choices": [
                {
                    "index": 0,
                    "message": {"role": "assistant", "content": content},
                    "finish_reason": finish_reason,
                }
            ],
        }
        if warning is not None:
            result["warning"] = warning
        return result

    def _chunk_json(
        self, delta_text: str, stop: bool, finish_reason: str = "stop",
        warning: str | None = None, request_id: str = "0",
    ) -> str:
        choice: dict = {"index": 0, "finish_reason": finish_reason if stop else ""}
        choice["delta"] = (
            {"role": "", "content": ""}
            if stop
            else {"role": "assistant", "content": delta_text}
        )
        payload = {
            "id": f"chatcmpl-{request_id}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": MODEL_NAME,
            "choices": [choice],
        }
        if warning is not None:
            payload["warning"] = warning
        return json.dumps(payload)

    def _parse(self, body: dict) -> dict:
        """Validate and normalize a request body. Raises
        :class:`BadRequest` with a client-facing message on any malformed
        field — the handler maps it to HTTP 400 (the reference crashes its
        handler thread on bad JSON instead, dllama-api.cpp:418-423)."""
        if not isinstance(body, dict):
            raise BadRequest("request body must be a JSON object")
        messages = body.get("messages")
        if not isinstance(messages, list) or not messages:
            raise BadRequest("'messages' must be a non-empty array")
        for i, m in enumerate(messages):
            if (
                not isinstance(m, dict)
                or not isinstance(m.get("role"), str)
                or not isinstance(m.get("content"), str)
            ):
                raise BadRequest(
                    f"messages[{i}] must be an object with string 'role' and 'content'"
                )
        # OpenAI allows stop to be a string, an array, or null
        stop = body.get("stop", ["<|eot_id|>"])
        if stop is None:
            stop = []
        elif isinstance(stop, str):
            stop = [stop]
        if not isinstance(stop, list) or not all(isinstance(s, str) for s in stop):
            raise BadRequest("'stop' must be a string, an array of strings, or null")
        try:
            temperature = float(body.get("temperature", self.args.temperature))
            # per-request sampler filters (OpenAI names): defaults are the
            # server's --topp/--topk; both ride the fused device sampler
            topp = float(body.get("top_p", self.args.topp))
            topk = int(body.get("top_k", getattr(self.args, "topk", 0) or 0))
            max_tokens = int(body.get("max_tokens", -1))
            seed = body.get("seed")
            if seed is not None:
                seed = int(seed)
            deadline_ms = body.get("deadline_ms")
            if deadline_ms is not None:
                deadline_ms = float(deadline_ms)
            priority = body.get("priority")
            if priority is not None:
                priority = int(priority)
        except (TypeError, ValueError) as e:
            raise BadRequest(f"invalid numeric field: {e}") from None
        # multi-tenant routing metadata (ISSUE 8, docs/SERVING.md): tenant
        # names feed the weighted-fair admission queues; priority defaults
        # to the tenant's configured class when the body omits it
        tenant = body.get("tenant", DEFAULT_TENANT)
        if (
            not isinstance(tenant, str) or not tenant or len(tenant) > 64
            or tenant.startswith("_")
        ):
            # leading underscore is reserved for internal tenants
            # (integrity.RESERVED_TENANTS: the SDC canary bills to
            # "_integrity", rollout certification probes to "_rollout"):
            # a client must not be able to impersonate either probe's
            # accounting bucket
            raise BadRequest(
                "'tenant' must be a non-empty string of at most 64 chars "
                "not starting with '_' (reserved)"
            )
        if deadline_ms is not None and not (
            math.isfinite(deadline_ms) and deadline_ms > 0
        ):
            # NaN must not pass: it poisons every monotonic comparison AND
            # Semaphore.acquire(timeout=nan) blocks forever
            raise BadRequest("'deadline_ms' must be a positive finite number of ms")
        cache = body.get("cache", "on")
        if cache not in ("on", "off"):
            raise BadRequest("'cache' must be \"on\" or \"off\"")
        if not (0.0 <= topp <= 1.0) or not math.isfinite(topp):
            raise BadRequest("'top_p' must be a number in [0, 1]")
        if topk < 0:
            raise BadRequest("'top_k' must be a non-negative integer (0 = off)")
        return {
            "cache": cache,
            "messages": [
                {"role": m["role"], "content": m["content"]} for m in messages
            ],
            "stream": bool(body.get("stream", False)),
            "temperature": temperature,
            "topp": topp,
            "topk": topk,
            "seed": seed,
            "max_tokens": max_tokens,
            "stop": [s for s in stop if s],
            "deadline_ms": deadline_ms,
            "tenant": tenant,
            "priority": priority,
        }


def _hbm_peak_bytes() -> int:
    """The allocator's peak on the fullest local device; 0 where the
    backend reports none (the CPU)."""
    import jax

    return max(
        (
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.local_devices()
        ),
        default=0,
    )


def make_handler(state: ApiState):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *a):
            print(f"🔷 {self.command} {self.path}")

        def do_GET(self):
            if self.path == "/v1/models":
                payload = json.dumps(
                    {
                        "object": "list",
                        "data": [
                            {"id": "dl", "object": "model", "created": 0, "owned_by": "user"}
                        ],
                    }
                ).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)
                state.tel.requests.labels(route="/v1/models", status="200").inc()
            elif self.path == "/healthz":
                # liveness: the HTTP loop and handler threads are alive. A
                # quarantined batch row or a watchdog-failed chunk does NOT
                # flip this — graceful degradation is healthy (ISSUE 3)
                self._send_json(200, {"status": "ok"})
                state.tel.requests.labels(route="/healthz", status="200").inc()
            elif self.path == "/readyz":
                # readiness: admitting new work. Flips 503 on SIGTERM drain
                # so load balancers stop routing here while in-flight
                # completions finish. The body carries the per-replica
                # health snapshot (ISSUE 9; schema in OBSERVABILITY.md) —
                # the 200/503 contract for plain probes is unchanged
                code = 503 if state.draining else 200
                self._send_json(code, state.ready_payload())
                state.tel.requests.labels(
                    route="/readyz", status=str(code)
                ).inc()
            elif self.path == "/metrics":
                # Prometheus text exposition of the process-global registry
                # (engine + server + collective instruments). Valid, possibly
                # sparse, output even when telemetry is disabled — scrapers
                # should not get a 404 from a healthy server.
                if state.tel.enabled:
                    state.tel.hbm_peak.set(_hbm_peak_bytes())
                payload = telemetry.prometheus_text().encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
                )
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)
                state.tel.requests.labels(route="/metrics", status="200").inc()
            elif self.path.startswith("/debug/trace/"):
                # per-request span tree (ISSUE 16): JSON by default,
                # ?format=chrome for a chrome://tracing / perfetto export.
                # 404 carries store stats so "why isn't my trace here" is
                # answerable (not sampled vs never started vs rotated out).
                rest = self.path[len("/debug/trace/"):]
                req_id, _, query = rest.partition("?")
                fmt = "chrome" if "format=chrome" in query else "json"
                traces = state.traces
                ctx = traces.get(req_id) if traces is not None else None
                if ctx is None:
                    self._send_json(
                        404,
                        {
                            "error": "trace not found",
                            "request_id": req_id,
                            "tracing_enabled": traces is not None,
                            "store": traces.stats() if traces else None,
                        },
                    )
                    state.tel.requests.labels(
                        route="/debug/trace", status="404"
                    ).inc()
                else:
                    self._send_json(
                        200,
                        ctx.chrome_trace() if fmt == "chrome" else ctx.tree(),
                    )
                    state.tel.requests.labels(
                        route="/debug/trace", status="200"
                    ).inc()
            elif self.path.rstrip("/") == "/debug/flight":
                # live flight-recorder view: every replica's lifecycle ring
                # plus retained auto-dumps (ISSUE 16, OBSERVABILITY.md)
                self._send_json(200, flight.RECORDER.snapshot())
                state.tel.requests.labels(
                    route="/debug/flight", status="200"
                ).inc()
            else:
                self.send_error(404)
                state.tel.requests.labels(route="other", status="404").inc()

        def _send_json(
            self, status: int, payload: dict, request_id: str | None = None,
            extra_headers: dict | None = None,
        ) -> None:
            data = json.dumps(payload).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            if request_id is not None:
                self.send_header("X-Request-Id", request_id)
            for k, v in (extra_headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(data)

        def _error_body(self, message: str, err_type: str, request_id: str) -> dict:
            return {
                "error": {
                    "message": message,
                    "type": err_type,
                    "request_id": request_id,
                }
            }

        def _admin_rollout(self, rid: str) -> str:
            """POST /admin/rollout: blue-green weight rollout (ISSUE 18,
            docs/SERVING.md "Live weight rollout"). Body:
            ``{"version": "v1"[, "weights": "/path/new.m"]
            [, "checksum": "<ref>"]}`` — ``weights`` registers the
            version from a file at runtime (pod: a second placed params
            tree); without it the version must already be registered.
            SYNCHRONOUS on this handler thread — the ThreadingHTTPServer
            keeps serving completions on its siblings throughout (that
            is the zero-downtime claim under test) and the response
            carries the outcome: 200 complete, 409 conflict (nothing
            started), 500 aborted-and-rolled-back (typed, with the
            final rollout status)."""
            try:
                length = int(self.headers.get("Content-Length", 0))
            except (TypeError, ValueError):
                length = 0
            raw = self.rfile.read(max(length, 0)) or b"{}"
            try:
                body = json.loads(raw)
            except json.JSONDecodeError as e:
                self._send_json(
                    400,
                    self._error_body(
                        f"malformed JSON: {e}", "invalid_request_error",
                        rid,
                    ),
                    request_id=rid,
                )
                return "400"
            if not isinstance(body, dict):
                body = {}
            version = body.get("version")
            if not isinstance(version, str) or not version:
                self._send_json(
                    400,
                    self._error_body(
                        "'version' must be a non-empty string",
                        "invalid_request_error", rid,
                    ),
                    request_id=rid,
                )
                return "400"
            try:
                weights = body.get("weights")
                if weights:
                    state.register_weights_path(version, weights)
                result = state.rollout.run(
                    version, checksum=body.get("checksum")
                )
            except fleet.RolloutConflict as e:
                self._send_json(
                    409,
                    self._error_body(str(e), "rollout_conflict", rid),
                    request_id=rid,
                )
                return "409"
            except fleet.RolloutAborted as e:
                payload = self._error_body(str(e), "rollout_aborted", rid)
                payload["rollout"] = state.pool.rollout_status()
                self._send_json(500, payload, request_id=rid)
                return "500"
            except Exception as e:
                self._send_json(
                    500,
                    self._error_body(
                        f"{type(e).__name__}: {e}", "server_error", rid
                    ),
                    request_id=rid,
                )
                return "500"
            self._send_json(200, result, request_id=rid)
            return "200"

        def _debug_profile(self, rid: str) -> str:
            """``POST /debug/profile``: the capture control
            (telemetry/capture.py). ``{"action": "start", "dir": ...,
            "max_seconds": N}`` / ``{"action": "stop"}``; 404 without
            ``--telemetry``, 409 while another capture runs (or on a stop
            with none running), 400 on a malformed body."""
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(max(length, 0)) or b"{}")
                action = body["action"]
                if action not in ("start", "stop"):
                    raise ValueError(f"unknown action {action!r}")
                directory = body["dir"] if action == "start" else None
                max_seconds = body.get("max_seconds")
            except (TypeError, ValueError, KeyError) as e:
                self._send_json(
                    400,
                    self._error_body(
                        f"malformed capture request: {e!r}",
                        "invalid_request_error", rid,
                    ),
                    request_id=rid,
                )
                return "400"
            if state.capture is None:
                self._send_json(
                    404,
                    self._error_body(
                        "the capture control needs --telemetry",
                        "not_found", rid,
                    ),
                    request_id=rid,
                )
                return "404"
            try:
                if action == "start":
                    out = state.capture.start(str(directory), max_seconds)
                else:
                    out = state.capture.stop()
            except (CaptureBusy, NoCapture) as e:
                self._send_json(
                    409, self._error_body(str(e), "capture_conflict", rid),
                    request_id=rid,
                )
                return "409"
            except ValueError as e:
                self._send_json(
                    400, self._error_body(str(e), "invalid_request_error", rid),
                    request_id=rid,
                )
                return "400"
            self._send_json(200, out, request_id=rid)
            return "200"

        def do_POST(self):
            # request-duration measurement uses a MONOTONIC clock (Stopwatch
            # wraps time.monotonic: a wall-clock step mid-request — NTP, DST —
            # must not corrupt the duration histogram), and every response
            # carries a correlation id so client-reported failures can be
            # matched to server logs
            rid = new_request_id()
            sw = Stopwatch()
            tel = state.tel
            status = "500"
            tel.inflight.inc()
            try:
                status = self._do_post_inner(rid)
            finally:
                tel.inflight.dec()
                tel.request_duration.observe(sw.elapsed_s())
                route = (
                    self.path
                    if self.path in ("/v1/chat/completions", "/debug/profile")
                    else "other"
                )
                tel.requests.labels(route=route, status=status).inc()

        def _do_post_inner(self, rid: str) -> str:
            """Handle one POST; returns the response status for metrics."""
            if self.path == "/admin/rollout":
                return self._admin_rollout(rid)
            if self.path == "/debug/profile":
                return self._debug_profile(rid)
            if self.path != "/v1/chat/completions":
                self.send_error(404)
                return "404"
            try:
                length = int(self.headers.get("Content-Length", 0))
            except (TypeError, ValueError):
                self._send_json(
                    400,
                    self._error_body(
                        "invalid Content-Length", "invalid_request_error", rid
                    ),
                    request_id=rid,
                )
                self.close_connection = True
                return "400"
            if length > state.max_body_bytes:
                # bounded request bodies (ISSUE 3 satellite): the seed's
                # rfile.read trusted ANY Content-Length — one request could
                # balloon host memory. Reject WITHOUT reading; the unread
                # body makes the connection unreusable, so close it.
                self._send_json(
                    413,
                    self._error_body(
                        f"request body {length} bytes exceeds the "
                        f"{state.max_body_bytes}-byte limit",
                        "request_too_large", rid,
                    ),
                    request_id=rid,
                )
                self.close_connection = True
                return "413"
            raw = self.rfile.read(max(length, 0)) or b"{}"
            try:
                body = json.loads(raw)
            except json.JSONDecodeError as e:
                self._send_json(
                    400,
                    self._error_body(f"malformed JSON: {e}", "invalid_request_error", rid),
                    request_id=rid,
                )
                return "400"
            try:
                # validate BEFORE any SSE bytes go out: a 400 must be a
                # clean HTTP error, not a broken event stream
                params = state._parse(body)
            except BadRequest as e:
                self._send_json(
                    400, self._error_body(str(e), "invalid_request_error", rid),
                    request_id=rid,
                )
                return "400"
            # SSE headers go out lazily with the FIRST event: a request
            # rejected by admission control (429), the drain gate (503) or
            # its own deadline (504) before any token still gets a clean
            # HTTP status instead of a 200 + broken event stream
            sse_started = False

            def send_chunk(data: str):
                nonlocal sse_started
                state.faults.fire("server.send")
                if not sse_started:
                    self.send_response(200)
                    self.send_header("Content-Type", "text/event-stream")
                    self.send_header("Cache-Control", "no-cache")
                    self.send_header("Connection", "close")
                    self.send_header("X-Request-Id", rid)
                    self.end_headers()
                    sse_started = True
                self.wfile.write(f"data: {data}\r\n\r\n".encode())
                self.wfile.flush()

            def _sse_terminal_error(message: str, err_type: str) -> None:
                # mid-stream failure: emit a terminal error event so the
                # client sees the failure, not a silent truncation
                try:
                    err = json.dumps(self._error_body(message, err_type, rid))
                    self.wfile.write(
                        f"data: {err}\r\n\r\ndata: [DONE]\r\n\r\n".encode()
                    )
                    self.wfile.flush()
                except OSError:
                    pass
                self.close_connection = True

            try:
                if body.get("stream"):
                    state.complete(body, send_chunk, params=params, request_id=rid)
                    self.close_connection = True
                else:
                    result = state.complete(
                        body, lambda s: None, params=params, request_id=rid
                    )
                    self._send_json(200, result, request_id=rid)
                return "200"
            except BrokenPipeError:
                # client went away mid-stream: the slot/batch row was
                # already released on the way out (engine stream_decode and
                # complete() run their finally blocks); the socket is dead
                self.close_connection = True
                return "499"
            except AdmissionRejected as e:
                # usually raised before any SSE byte (admission precedes
                # decoding) — but a preemption REQUEUE re-enters admission
                # mid-stream, so a full queue can also surface here after
                # deltas went out; then it must end the event stream, not
                # write a second status line into it. Retry-After is
                # JITTERED per response: a burst of 429s with one fixed
                # value retries back in lockstep and re-spikes the queue
                # (ISSUE 8 satellite)
                if sse_started:
                    _sse_terminal_error(str(e), "overloaded")
                else:
                    self._send_json(
                        429, self._error_body(str(e), "overloaded", rid),
                        request_id=rid,
                        extra_headers={"Retry-After": str(state.retry_after())},
                    )
                return "429"
            except ServerDraining as e:
                # same mid-stream possibility as AdmissionRejected: a
                # requeue can meet a drain that began after the SSE headers
                if sse_started:
                    _sse_terminal_error(str(e), "draining")
                else:
                    self._send_json(
                        503, self._error_body(str(e), "draining", rid),
                        request_id=rid,
                        extra_headers={"Retry-After": str(state.retry_after())},
                    )
                return "503"
            except (faults.RowPreempted, faults.ReplicaLost) as e:
                # preempted requests and replica-loss victims re-run
                # transparently inside state.complete(); reaching here
                # means the request was evicted (or orphaned by dying
                # replicas) MAX_PREEMPT_REQUEUES times in a row — shed it
                # like overload rather than spinning a handler thread
                # forever. Retry-After is jittered as usual.
                if isinstance(e, faults.ReplicaCorrupt):
                    # integrity-detected loss mid-stream: already-sent
                    # deltas are untrustworthy, so there was no replay —
                    # the typed kind tells the client to restart fresh
                    kind = "replica_corrupt"
                elif isinstance(e, faults.ReplicaLost):
                    kind = "replica_lost"
                else:
                    kind = "preempted"
                if sse_started:
                    _sse_terminal_error(str(e), kind)
                else:
                    self._send_json(
                        503, self._error_body(str(e), kind, rid),
                        request_id=rid,
                        extra_headers={"Retry-After": str(state.retry_after())},
                    )
                return "503"
            except DeadlineExceeded as e:
                state.tel.deadline_exceeded.inc()
                if sse_started:
                    _sse_terminal_error(str(e), "deadline_exceeded")
                else:
                    self._send_json(
                        504,
                        self._error_body(str(e), "deadline_exceeded", rid),
                        request_id=rid,
                    )
                return "504"
            except Exception as e:  # engine failure: surface it, keep serving
                print(f"🛑 request {rid} failed: {type(e).__name__}: {e}")
                if sse_started:
                    _sse_terminal_error(str(e), "server_error")
                else:
                    self._send_json(
                        500, self._error_body(str(e), "server_error", rid),
                        request_id=rid,
                    )
                return "500"

    return Handler


def drain_then_shutdown(state: ApiState, server, timeout_s: float) -> None:
    """Wait for every in-flight completion to finish (all admission
    permits back), capped at ``timeout_s``, then stop the HTTP server.
    Runs on its own thread so the SIGTERM handler returns immediately."""
    state.admission.drain_wait(timeout_s)
    server.shutdown()


def install_sigterm_drain(state: ApiState, server, timeout_s: float = 30.0):
    """SIGTERM → graceful drain: flip readiness (``/readyz`` 503), stop
    admitting (new completions get 503 + Retry-After), let in-flight
    chunks finish, then shut the server down. Returns the installed
    handler (tests invoke it directly). No-op outside the main thread
    (signal.signal's constraint)."""

    def handler(signum, frame):
        state.begin_drain()
        threading.Thread(
            target=drain_then_shutdown, args=(state, server, timeout_s),
            name="dllama-drain", daemon=True,
        ).start()

    try:
        signal.signal(signal.SIGTERM, handler)
    except ValueError:
        pass  # not the main thread (embedded/test server): caller drains
    return handler


def serve(args) -> None:
    from distributed_llama_tpu.apps.cli import make_engine
    from distributed_llama_tpu.platform import enable_compilation_cache

    # --telemetry / DLLAMA_TELEMETRY must take effect BEFORE the engine and
    # ApiState bind their instrument bundles (bind-once contract)
    if getattr(args, "telemetry", False):
        telemetry.enable()
    if telemetry.is_enabled():
        # no route exports the span ring, so the server records into it
        # only during a capture (POST /debug/profile); outside one a span
        # is a profiler annotation and nothing else
        telemetry.TRACER.recording = False
    # the persistent compile cache must be configured before make_engine's
    # first jit (platform.enable_compilation_cache: a cold 7B prefill
    # compile becomes a cache deserialization)
    enable_compilation_cache(getattr(args, "compile_cache_dir", None))
    # --faults installs the chaos plan BEFORE the engine/scheduler bind
    # their hooks (same bind-once contract; docs/ROBUSTNESS.md)
    spec = getattr(args, "faults", None)
    if spec:
        faults.install(faults.parse(spec, seed=getattr(args, "faults_seed", 0)))
        print(f"⚠️ fault plan active: {spec}")
    if getattr(args, "pod", None):
        # one-process pod serving (ISSUE 15, docs/SERVING.md): ONE
        # ('data','model') mesh, one weights tree shared by every slice.
        # The pod group IS the engine factory — a replica (re)build hands
        # out a fresh slice engine over the shared params, never a weight
        # reload — and the replica count is the pod's data extent (each
        # data slice is one supervised failure domain).
        from distributed_llama_tpu.apps.cli import make_pod_group

        group, tokenizer, sampler = make_pod_group(args)
        wanted = getattr(args, "replicas", None)
        if wanted == 1:
            # CONSOLIDATED pod: one supervised replica over the whole
            # mesh — every lane rides ONE batched-decode program (max
            # aggregate throughput; the whole pod is one failure domain).
            # The default (below) trades that for per-slice failover.
            args.replicas = 1
        else:
            if wanted not in (None, group.data):
                print(
                    f"⚠️ --replicas {wanted} ignored under --pod: one "
                    f"replica per data slice ({group.data}), or 1 for the "
                    "consolidated single-domain pod"
                )
            args.replicas = group.data
        engine = group.slice_engine()
        engine_factory = group
    else:
        engine, tokenizer, sampler = make_engine(args)

        def engine_factory():
            # replica (re)builds (ISSUE 9): a fresh engine from the same
            # flags — the restart supervisor calls this off the serving
            # path, and the persistent compile cache (configured above)
            # makes the re-jit a deserialization rather than a rebuild
            return make_engine(args)[0]

    state = ApiState(
        engine, tokenizer, sampler, args, engine_factory=engine_factory
    )
    # live weight rollout (ISSUE 18): how POST /admin/rollout turns a
    # weight-file path into a versioned engine factory. Pod: a SECOND
    # params tree placed on the same mesh/backend (group.sibling — the
    # group is itself the factory); classic: a flag-clone load of the
    # new file through make_engine
    if getattr(args, "pod", None):
        state.make_engine_for_path = group.sibling
    else:

        def factory_for_path(path):
            a = copy.copy(args)
            a.model = path

            def build():
                return make_engine(a)[0]

            return build

        state.make_engine_for_path = factory_for_path
    # threaded HTTP front (GET /v1/models and queued POSTs stay responsive);
    # up to --parallel completions run concurrently on their own engine
    # streams, excess requests queue BOUNDEDLY on the slot semaphore
    # (ApiState._acquire_slot: 429 beyond --admission-queue waiters)
    server = ThreadingHTTPServer(("0.0.0.0", args.port), make_handler(state))
    server.daemon_threads = True
    install_sigterm_drain(
        state, server, timeout_s=getattr(args, "drain_timeout_s", 30.0)
    )
    print(f"Server URL: http://127.0.0.1:{args.port}/v1/")
    if telemetry.is_enabled():
        print(f"Metrics:    http://127.0.0.1:{args.port}/metrics")
    server.serve_forever()
    if state._spill_arena is not None:
        # drained and shut down: join the arena's spiller thread
        state._spill_arena.close()


def main(argv=None) -> None:
    from distributed_llama_tpu.apps.cli import build_parser

    # the compile cache is configured by serve() AFTER parsing, so the
    # --compile-cache-dir flag can point it somewhere else
    parser = build_parser()
    parser.add_argument("--port", type=int, default=9990)
    parser.add_argument(
        "--parallel", type=int, default=2,
        help="concurrent in-flight completions PER REPLICA (each costs one "
        "KV cache of HBM; the reference serves exactly one, "
        "dllama-api.cpp:418-423)",
    )
    # replica-loss fault tolerance (ISSUE 9, docs/ROBUSTNESS.md)
    parser.add_argument(
        "--replicas", type=int, default=None,
        help="supervised data-parallel replicas behind one admission front "
        "door: each is an independent engine + batch scheduler failure "
        "domain (total slots = replicas x --parallel; default 1, or one "
        "per data slice under --pod — there, an explicit --replicas 1 "
        "picks the consolidated single-domain pod). A dead replica's "
        "in-flight requests replay bit-identically on survivors while the "
        "supervisor restarts it with jittered backoff; health rides "
        "dispatch round-trips + the stall watchdog (/readyz reports "
        "per-replica state)",
    )
    parser.add_argument(
        "--replica-suspect-s", type=float, default=30.0,
        help="dispatch round-trip duration past which a replica turns "
        "SUSPECT (skipped for new placements until a fast round-trip "
        "clears it)",
    )
    parser.add_argument(
        "--replica-restart-backoff-s", type=float, default=0.5,
        help="base restart backoff for a dead replica (exponential to "
        "30s, entropy-jittered so restored replicas never restart in "
        "lockstep)",
    )
    # silent-data-corruption detection (ISSUE 10, docs/ROBUSTNESS.md
    # "silent corruption" failure-domain row)
    parser.add_argument(
        "--sdc-canary-interval-s", type=float, default=0.0,
        help="period of the per-replica SDC canary: a pinned greedy "
        "prompt through each replica's real batched path on a reserved "
        "internal lane, compared (tokens + logit fingerprint) against "
        "the pool golden; consecutive mismatches walk the replica "
        "healthy→suspect→dead and its supervisor rebuild must pass "
        "weight-checksum verification. 0 disables the background canary",
    )
    parser.add_argument(
        "--sdc-canary-tokens", type=int, default=12,
        help="greedy tokens per canary probe (longer = more sensitive to "
        "deep-layer corruption, costlier per probe)",
    )
    parser.add_argument(
        "--sdc-canary-threshold", type=int, default=2,
        help="consecutive canary mismatches before the replica is "
        "declared corrupt-dead (1 = first mismatch kills; the default 2 "
        "walks suspect first)",
    )
    parser.add_argument(
        "--sdc-shadow-rate", type=float, default=0.0,
        help="fraction of completed greedy requests re-executed on two "
        "live replicas off-path and compared (cross-replica shadow "
        "voting): divergence marks both suspect and the canary resolves "
        "which is corrupt. 0 disables",
    )
    # zero-downtime fleet ops (ISSUE 18, docs/SERVING.md "Live weight
    # rollout"): blue-green rollout via POST /admin/rollout and
    # SLO-driven replica elasticity
    parser.add_argument(
        "--weights-version", type=str, default=None,
        help="version id of the BOOT weights (default v0): the key the "
        "pool's checksum reference and canary golden file under, and "
        "what /readyz reports per replica — a POST /admin/rollout moves "
        "the pool to a different registered version",
    )
    parser.add_argument(
        "--rollout-drain-s", type=float, default=15.0,
        help="per-replica drain cap during a blue-green rollout move; "
        "past it the lingering requests take the standard failover "
        "replay path and the move proceeds via the supervisor",
    )
    parser.add_argument(
        "--fleet-min-replicas", type=int, default=1,
        help="elasticity floor: the FleetController never shrinks the "
        "pool below this many replicas",
    )
    parser.add_argument(
        "--fleet-max-replicas", type=int, default=None,
        help="elasticity ceiling: sustained admission-queue pressure "
        "grows the pool up to this many replicas (each a full engine "
        "build through the rebuild checksum gate). Default: the boot "
        "replica count, i.e. elasticity off unless raised",
    )
    parser.add_argument(
        "--fleet-interval-s", type=float, default=0.0,
        help="FleetController tick period; each tick reads admission "
        "queue depth + fresh 429s and, after consecutive-tick "
        "hysteresis, grows or drains+retires one replica. 0 disables "
        "the background loop",
    )
    parser.add_argument(
        "--fleet-queue-high", type=int, default=None,
        help="queued-demand threshold that counts as scale-up pressure "
        "(default: one replica's worth of lanes)",
    )
    parser.add_argument(
        "--batch-decode", action=argparse.BooleanOptionalAction, default=True,
        help="coalesce concurrent completions into one batched decode "
        "dispatch per chunk (one weight read per step for all in-flight "
        "requests — near-Bx aggregate tok/s on the HBM-bound decode; "
        "single-chip and --tp backends, --decode device). "
        "--no-batch-decode restores independent per-request dispatches",
    )
    # paged prefix cache (ISSUE 4, 7, 40, docs/PERF.md)
    parser.add_argument(
        "--prefix-cache", action=argparse.BooleanOptionalAction, default=True,
        help="reuse published KV pages for repeated prompt prefixes "
        "(radix tree over token blocks; a hit skips the matched tokens' "
        "prefill and only the unmatched suffix prefills: the chat "
        "system-prompt workload's TTFT win. On one chip the matched pages "
        "are copied into the request's row once, at admission, and decode "
        "reads the row alone; under --tp the row reads them in place "
        "through its page table). Requests opt out per call with body "
        "field 'cache': \"off\". Batched serving on the single-chip and "
        "--tp backends",
    )
    parser.add_argument(
        "--kv-pages", type=int, default=None,
        help="page-pool HBM budget in pages for --prefix-cache. A live row "
        "pins the pages it matched for its lifetime, and the pool holds "
        "what later prompts resume from, so the default is "
        "--parallel x ceil(seq_len/page) plus 25%% headroom; a pool "
        "smaller than one slab's worth warns (concurrent long prompts "
        "contend for pinned pages), 0 disables the prefix cache. The LRU "
        "evictor reclaims unreferenced chains beyond the budget",
    )
    # tiered global prefix cache (ISSUE 11, docs/SERVING.md "Cache tiers
    # and placement"): host-RAM spill below the HBM pool, optional mmap'd
    # disk below that; with --replicas > 1 a shared radix index routes
    # each request to the replica owning its longest published chain
    parser.add_argument(
        "--host-spill-mb", type=float, default=None,
        help="host-RAM budget (MiB) for the prefix-page spill arena "
        "(default 64; none under an arch with recurrent state, which "
        "refuses the tier if asked for): "
        "evicted KV pages spill here (bytes verbatim, CRC-guarded) and "
        "re-upload on a later match instead of re-prefilling — "
        "cacheable-prefix capacity at fixed --kv-pages multiplies. "
        "Shared across replicas (a chain spilled by one replica reloads "
        "into another). 0 disables the tier (single-chip backend only; "
        "the sharded tp pool has no spill programs yet)",
    )
    parser.add_argument(
        "--spill-disk-dir", type=str, default=None,
        help="directory for the OPTIONAL mmap'd disk tier below the "
        "host-RAM arena (the reference's disc-backed KV, "
        "newMmapFileBuffer): host-budget overflow demotes LRU entries "
        "to a fixed-slot spill file instead of dropping them. Off by "
        "default",
    )
    parser.add_argument(
        "--spill-disk-mb", type=float, default=256.0,
        help="disk-tier budget (MiB) for --spill-disk-dir",
    )
    parser.add_argument(
        "--kv-page-size", type=int, default=64,
        help="positions per KV page (prefix-match granularity; smaller "
        "pages match finer but cost more host bookkeeping)",
    )
    parser.add_argument(
        "--prefill-chunk", type=int, default=256,
        help="tokens per prefill dispatch in batched serving: long prompts "
        "chunk so co-batched rows' decode interleaves between the chunks "
        "(Sarathi-style) instead of stalling behind the whole prompt "
        "(0 = monolithic prompt dispatch)",
    )
    # fault tolerance (docs/ROBUSTNESS.md)
    parser.add_argument(
        "--admission-queue", type=int, default=None,
        help="max completion requests queued for a free slot before the "
        "server answers 429 + Retry-After (default 2x --parallel; the "
        "alternative is an unbounded queue of burning client timeouts)",
    )
    parser.add_argument(
        "--max-body-bytes", type=int, default=1 << 20,
        help="request-body size cap; larger Content-Length gets 413 "
        "without reading the body (default 1 MiB)",
    )
    # multi-tenant fairness + priority preemption (ISSUE 8, docs/SERVING.md)
    parser.add_argument(
        "--tenants", type=str, default=None,
        help="tenant admission contracts: ';'-separated "
        "'name:weight=W,priority=P,queue=Q' entries, e.g. "
        "'gold:weight=4,priority=10;free:weight=1'. Weights set DRR "
        "admission shares under saturation; priority sets the default "
        "class for the tenant's requests (bodies may override with a "
        "'priority' field). Unknown tenants auto-register at weight 1, "
        "priority 0",
    )
    parser.add_argument(
        "--preempt", action=argparse.BooleanOptionalAction, default=True,
        help="allow a queued higher-priority request to evict the "
        "lowest-priority batched decode row to a clean requeue (the "
        "victim resumes through the prefix cache, bit-identically; "
        "batched serving only). --no-preempt queues strictly",
    )
    parser.add_argument(
        "--retry-after-jitter-s", type=int, default=2,
        help="max uniform jitter ADDED to the 1s Retry-After base on "
        "429/503 responses, drawn per response (desynchronizes client "
        "retry storms; 0 restores the fixed value)",
    )
    parser.add_argument(
        "--deadline-ms", type=float, default=None,
        help="default per-request deadline in ms (requests may set their "
        "own 'deadline_ms'); an expired request ends 504 / an SSE error "
        "event and its batch row leaves the shared dispatch",
    )
    parser.add_argument(
        "--stall-timeout-s", type=float, default=120.0,
        help="batched-decode watchdog: a chunk fetch in flight longer than "
        "this fails the batch cleanly instead of hanging every lane "
        "(0 disables)",
    )
    parser.add_argument(
        "--drain-timeout-s", type=float, default=30.0,
        help="SIGTERM drain: max seconds to wait for in-flight completions "
        "before shutting the listener down",
    )
    parser.add_argument(
        "--faults", type=str, default=None,
        help="chaos fault-plan spec (or DLLAMA_FAULTS env), e.g. "
        "'batch.fetch:kind=raise,after=2,count=1' — docs/ROBUSTNESS.md",
    )
    parser.add_argument(
        "--faults-seed", type=int, default=0,
        help="seed for probabilistic fault rules (p<1)",
    )
    # request tracing + flight recorder (ISSUE 16, docs/OBSERVABILITY.md)
    parser.add_argument(
        "--trace-sample-rate", type=float, default=1.0,
        help="fraction of finished request traces RETAINED for "
        "GET /debug/trace/<id> (every request records while telemetry is "
        "on; sampling decides retention). Slow requests are always kept — "
        "see --trace-slow-ttft-s. Requires --telemetry",
    )
    parser.add_argument(
        "--trace-slow-ttft-s", type=float, default=1.0,
        help="TTFT threshold (seconds) above which a finished trace is "
        "retained regardless of --trace-sample-rate (the trace you want "
        "most is the slow one you didn't sample); 0 disables the override",
    )
    parser.add_argument(
        "--trace-retention", type=int, default=256,
        help="max finished traces retained (bounded deque; oldest rotate "
        "out first)",
    )
    parser.add_argument(
        "--flight-dump-dir", type=str, default=None,
        help="directory for flight-recorder JSON artifacts auto-dumped on "
        "replica death, SDC detection, or a watchdog stall (the in-memory "
        "dump ring behind GET /debug/flight is always on)",
    )
    # mode is meaningless here but the shared parser requires it
    argv = argv if argv is not None else None
    import sys

    raw = list(sys.argv[1:] if argv is None else argv)
    if not raw or raw[0] not in ("inference", "generate", "chat", "worker"):
        raw = ["generate"] + raw
    args = parser.parse_args(raw)
    serve(args)


if __name__ == "__main__":
    main()
