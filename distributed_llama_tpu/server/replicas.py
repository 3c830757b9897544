"""Supervised data-parallel replica pool (ISSUE 9).

The reference system dies wholesale when any one of its 2^n nodes drops a
socket (reference: src/apps/dllama/dllama.cpp:418-423 — no failover path
exists), and PRs 1–8 inherited that blast radius one level up: one engine,
one scheduler, one process. This module generalizes the failure domain the
codebase already handles — a *row* (quarantine, PR 3) and a *request*
(preemption replay, PR 8) — to a whole **replica**: one
:class:`~distributed_llama_tpu.engine.batch.BatchScheduler` plus its
engine, its slab, its prefix-cache pool and its serving lanes.

:class:`ReplicaPool` owns N replicas behind ONE admission front-end
(server/admission.py ``FairAdmission``) and adds three things:

* **Placement** — an admitted request lands on the free lane with the best
  chat-prefix affinity, ties broken toward the least-loaded replica.
  Suspect replicas are skipped while any healthy one has room; dead
  replicas never place.
* **Health** — a per-replica state machine ``healthy → suspect → dead``
  driven by the scheduler's dispatch round-trips (a round-trip past
  ``suspect_roundtrip_s`` turns the replica suspect; a fast one clears
  it), the existing stall watchdog (a stall walks suspect then dead), and
  hard losses (a crashed dispatch marks the scheduler lost outright).
* **Supervision** — a dead replica's serving capacity leaves admission
  (``FairAdmission.resize``), its in-flight requests carry typed
  ``ReplicaLost`` errors that the serving layer REQUEUES through fair
  admission and replays bit-identically on survivors (server/api.py), and
  a supervisor thread rebuilds the replica under the shared
  jittered-backoff policy (distributed_llama_tpu/retry.py) — restart
  jitter is **entropy-seeded on purpose**: replicas restored from the
  same image with a deterministic seed would retry their rebuilds in
  lockstep, recreating the thundering herd (the ISSUE 8 Retry-After
  lesson, applied to supervision).

Lock discipline: ``ReplicaPool._cond`` ranks ABOVE the schedulers' conds
in the declared hierarchy (pyproject ``[tool.dllama.analysis.locks]``;
docs/ROBUSTNESS.md "Lock hierarchy"), so scheduler health hooks may call
into the pool while holding a scheduler cond, but nothing here may call
back into a scheduler while holding ``_cond`` (the preempt fan-out
snapshots the scheduler list first, then calls unlocked). The contract is
machine-checked: statically by LCK-003, dynamically by the
``DLT_LOCK_CHECK=1`` witness (distributed_llama_tpu/lockcheck.py).

Everything is testable in-process under ``JAX_PLATFORMS=cpu``: replicas
are ordinary schedulers over tiny synthetic models, and the chaos sites
``replica.crash`` / ``replica.hang`` / ``replica.slow`` (engine/faults.py,
``row=`` selects the replica id) drive the full failover story in
tests/test_replicas.py and the loadgen replica-kill scenario.
"""

from __future__ import annotations

import random
import threading
import time

from distributed_llama_tpu import lockcheck, retry
from distributed_llama_tpu.engine import faults, integrity
from distributed_llama_tpu.telemetry import Stopwatch, flight


class NoPlaceableReplica(faults.ReplicaLost):
    """Placement found no live replica inside its window. A subclass of
    ReplicaLost so the serving layer's requeue loop retries it through
    fair admission like any replica loss — but distinguishable, because a
    placement bounce must NOT count as a replay (nothing ever ran): the
    `dllama_replayed_requests_total` vs victim-count health read in
    OBSERVABILITY.md depends on the counter meaning actual replays."""


HEALTHY = "healthy"
SUSPECT = "suspect"
DEAD = "dead"

# dllama_replica_state gauge encoding (docs/OBSERVABILITY.md)
STATE_VALUES = {HEALTHY: 0, SUSPECT: 1, DEAD: 2}


class Replica:
    """One failure domain: an engine + (optionally) its BatchScheduler and
    the serving slots riding on it. ``generation`` increments per rebuild
    so health events from a replaced scheduler can never touch its
    successor. ``integrity``/``last_canary``/``canary_fails`` are the SDC
    canary's per-replica record (ISSUE 10): the /readyz snapshot reports
    the first two, and consecutive canary mismatches walk the replica
    down the health ladder. ``weights_version`` names the weight version
    this replica serves (ISSUE 18: a blue-green rollout runs a
    mixed-version pool mid-flight); ``cordoned`` excludes the replica
    from NEW placements without any health implication — the rollout's
    drain-before-rebuild gate."""

    __slots__ = (
        "idx", "engine", "scheduler", "slots", "state", "generation",
        "restarts", "integrity", "last_canary", "canary_fails",
        "weights_version", "cordoned",
    )

    def __init__(self, idx: int, engine, scheduler, slots):
        self.idx = idx
        self.engine = engine
        self.scheduler = scheduler
        self.slots = list(slots)
        self.state = HEALTHY
        self.generation = 0
        self.restarts = 0
        self.integrity = "unverified"
        self.last_canary: float | None = None
        self.canary_fails = 0
        self.weights_version = "v0"
        self.cordoned = False

    def active(self) -> int:
        return sum(1 for s in self.slots if s.busy)


class ReplicaPool:
    """N supervised replicas behind one placement front door.

    ``build_replica(idx)`` returns ``(engine, scheduler_or_None, slots)``
    — the serving layer's factory (server/api.py ``_build_replica``); the
    pool calls it again, under the restart backoff, to rebuild a dead
    replica. ``admission`` (a FairAdmission) is resized as capacity dies
    and returns. ``tel`` is a ServerInstruments bundle (null instruments
    when telemetry is off). ``supervise=False`` disables the restart loop
    and stall escalation (the standalone single-replica server keeps its
    PR 3 StallTimeout semantics)."""

    def __init__(
        self,
        build_replica,
        replicas,  # list[Replica] — already built (the serving layer owns construction order)
        admission=None,
        tel=None,
        supervise: bool = True,
        suspect_roundtrip_s: float = 30.0,
        place_timeout_s: float = 5.0,
        restart_policy: retry.BackoffPolicy | None = None,
        restart_seed: int | None = None,
        shared_index=None,
        spill_arena=None,
        weights_version: str = "v0",
    ):
        from distributed_llama_tpu import telemetry

        # global prefix-cache tier (ISSUE 11): the shared radix index the
        # replicas' trees report their chains to (placement routes to the
        # owner of the longest matched chain) and the pool-wide host-RAM
        # spill arena. A replica death drops its entries from BOTH — no
        # dangling routing, and a silently-corrupt replica's spilled
        # bytes never reload anywhere.
        self.shared_index = shared_index
        self.spill_arena = spill_arena
        self.shared_hits_total = 0
        self.build_replica = build_replica
        self.replicas: list[Replica] = list(replicas)
        self.admission = admission
        self.tel = tel if tel is not None else telemetry.ServerInstruments()
        self.supervise = bool(supervise)
        self.suspect_roundtrip_s = float(suspect_roundtrip_s)
        self.place_timeout_s = float(place_timeout_s)
        self.restart_policy = restart_policy or retry.BackoffPolicy(
            attempts=retry.UNBOUNDED, base_s=0.5, multiplier=2.0,
            max_s=30.0, jitter_s=0.5,
        )
        # entropy-seeded unless a test pins it: see the module docstring
        self._rng = (
            random.Random(restart_seed) if restart_seed is not None
            else random.Random()
        )
        self._cond = lockcheck.make_condition("ReplicaPool._cond")
        self._closed = False
        # plain ledger, readable with telemetry off (the registry metrics
        # mirror these; tests and the loadgen report read them directly)
        self.failovers_total = 0
        self.restarts_total = 0
        self.replayed_total = 0
        self.suspects_total = 0
        self.last_failover_victims = 0
        # silent-data-corruption detection (ISSUE 10, engine/integrity.py):
        # the canary/shadow/checksum ledger (plain, readable with
        # telemetry off), plus the PER-VERSION integrity anchors
        # (ISSUE 18): a blue-green rollout serves two weight versions at
        # once, so the single pool golden / load-time checksum of PRs
        # 9-10 become maps keyed by ``weights_version`` — one canary
        # golden and one checksum reference per LIVE version (within a
        # version every replica is still bit-identical: the replay
        # contract), and a retired version's entries leave with it. The
        # probe itself belongs to the serving layer
        # (ApiState._canary_probe): it needs the tokenizer/template.
        self.sdc_checks_total = 0
        self.sdc_mismatches_total = 0
        self.canary_probe = None
        self.canary_interval_s = 0.0
        self.canary_fail_threshold = 2
        self._canary_thread: threading.Thread | None = None
        self.weights_version = str(weights_version)
        self._canary_goldens: dict[str, object] = {}
        self.weights_reference: dict[str, str] = {}
        # the rollout state machine's authority (ISSUE 18): the version
        # each SLOT should run, overriding the pool version while a
        # rollout is mid-flight. Every rebuild — the orchestrator's
        # synchronous cutover AND the supervisor's death recovery —
        # consults target_version(), so a replica death mid-rollout
        # converges to the rollout's intent, never the dying replica's.
        self._slot_versions: dict[int, str] = {}
        self.rollout: dict | None = None
        self.rollout_moves_total = 0
        self.rollout_aborts_total = 0
        for r in self.replicas:
            r.weights_version = self.weights_version
        for r in self.replicas:
            if r.engine is not None:
                try:
                    self.weights_reference[self.weights_version] = (
                        r.engine.weights_checksum()
                    )
                except Exception as e:  # a reference is an optimization,
                    # never a construction blocker (fake/test replicas)
                    print(f"⚠️ weight checksum unavailable: {e}")
                break
        for r in self.replicas:
            self._adopt(r)

    # ------------------------------------------------------------------
    # Wiring
    # ------------------------------------------------------------------

    def _adopt(self, rep: Replica) -> None:
        """Arm a (re)built replica's scheduler with its pool identity:
        the replica-scoped chaos sites, the health hook, and — when the
        pool supervises — stall escalation to replica loss."""
        sched = rep.scheduler
        self.tel.replica_state.labels(replica=str(rep.idx)).set(
            STATE_VALUES[rep.state]
        )
        # a (re)built replica starts integrity-unverified: the next canary
        # pass re-certifies it against its VERSION's golden (not a fresh
        # one — a corrupt-from-rebuild replica must not self-certify)
        rep.integrity = "unverified"
        rep.last_canary = None
        rep.canary_fails = 0
        if sched is None:
            return
        sched.replica_id = rep.idx
        sched.lost_on_stall = self.supervise
        gen = rep.generation
        sched.health_hook = (
            lambda event, value, idx=rep.idx, g=gen:
            self._on_event(idx, g, event, value)
        )

    def close(self) -> None:
        """Stop supervision, the replicas' watchdogs and the shared spill
        arena's spiller thread (tests; a serving pool lives for the
        process)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        for r in self.replicas:
            if r.scheduler is not None:
                r.scheduler.close()
        if self.spill_arena is not None:
            self.spill_arena.close()

    # ------------------------------------------------------------------
    # Placement
    # ------------------------------------------------------------------

    def all_slots(self) -> list:
        """Every replica's slots, flattened (compat surface: tests and the
        serving layer iterate busy flags / streams through this)."""
        return [s for r in self.replicas for s in r.slots]

    def place(self, messages, deadline: float | None = None, route_tokens=None):
        """Claim a free slot for an admitted request: best chat-prefix
        affinity first (a continuing conversation resumes its own slot's
        KV), then the best :meth:`route_score` — the SHARED RADIX INDEX's
        published chain depth per replica (``route_tokens``, the
        cross-replica prefix routing of ISSUE 11) DISCOUNTED by its
        active load, so a marginally-deeper owner drowning in requests
        loses to a slightly-shallower idle one, while both still beat a
        cold replica — then the least-loaded replica, preferring an
        empty chat cache on ties. Healthy replicas
        only while any has room; suspect ones are the fallback; dead ones
        never place — and a dead replica's chains left the index with it,
        so routing never dangles. When nothing is placeable — a replica
        died between the admission grant and here — waits briefly
        (bounded by ``place_timeout_s`` and the request ``deadline``) and
        then raises :class:`faults.ReplicaLost`, which the serving
        layer's requeue loop converts into a fresh pass through fair
        admission."""
        shared: dict[int, int] = {}
        if self.shared_index is not None and route_tokens is not None:
            shared = self.shared_index.match(route_tokens)
        limit = time.monotonic() + self.place_timeout_s
        if deadline is not None:
            limit = min(limit, deadline)
        with self._cond:
            while True:
                picked = self._pick_slot_locked(messages, shared)
                if picked is not None:
                    rep, slot = picked
                    slot.busy = True
                    depth = shared.get(rep.idx, 0)
                    best_other = max(
                        (d for o, d in shared.items() if o != rep.idx),
                        default=0,
                    )
                    if (
                        depth > 0
                        and depth > best_other
                        and slot.cache.match_len(messages) == 0
                    ):
                        # the index actually DECIDED this placement: the
                        # picked replica owns strictly more of the chain
                        # than any alternative, and chat-slot affinity
                        # (the dominant sort key) didn't choose it first.
                        # Counting mere ownership overlap — e.g. a fully
                        # replicated Zipf head, where least-loaded decides
                        # — would read permanently healthy and hide a
                        # routing regression; counting affinity resumes
                        # would credit the index with what the private
                        # design could do anyway
                        self.shared_hits_total += 1
                        self.tel.shared_prefix_hits.inc()
                    return slot
                now = time.monotonic()
                if deadline is not None and now >= deadline:
                    # the request's own budget ran out in line here: that
                    # is a deadline (504), not a replica loss (503)
                    raise faults.DeadlineExceeded(
                        "deadline expired waiting for replica placement"
                    )
                if now >= limit or self._closed:
                    raise NoPlaceableReplica(
                        "no placeable replica: "
                        + ", ".join(
                            f"{r.idx}:{r.state}" for r in self.replicas
                        )
                    )
                self._cond.wait(timeout=limit - now)

    # matched-depth x load routing cost model (ROADMAP item 4 follow-up):
    # one active request on a replica outweighs this many owned prefix
    # blocks. Pure depth ranking queues behind a loaded owner for a
    # marginal extra block; pure least-loaded throws owned prefill away
    # for an idle cold replica — the discounted score beats both
    # (tests/test_replicas.py::test_depth_discounted_routing_...)
    ROUTE_LOAD_DISCOUNT = 2.0

    @classmethod
    def route_score(cls, depth_blocks: int, active: int) -> float:
        """Depth-discounted load score of placing on a replica that owns
        ``depth_blocks`` of the prompt's published chain while serving
        ``active`` requests. With no ownership anywhere the ranking
        degenerates to least-loaded (the pre-cost-model behavior); among
        owners, each active request discounts ROUTE_LOAD_DISCOUNT blocks
        of claimed depth."""
        return depth_blocks - cls.ROUTE_LOAD_DISCOUNT * active

    def _pick_slot_locked(self, messages, shared=None):
        shared = shared or {}
        # mid-rollout, placement soft-prefers the TARGET version (below
        # affinity and routing depth, above raw load): traffic shifts
        # toward certified upgraded replicas as they come back, without
        # ever starving the pool when only old-version lanes are free
        target = self.rollout["to"] if self.rollout else None
        for wanted in (HEALTHY, SUSPECT):
            cands = [
                (r, s)
                for r in self.replicas
                if r.state == wanted and not r.cordoned
                for s in r.slots
                if not s.busy
            ]
            if cands:
                return max(
                    cands,
                    key=lambda rs: (
                        rs[1].cache.match_len(messages),
                        self.route_score(
                            shared.get(rs[0].idx, 0), rs[0].active()
                        ),
                        1 if target and rs[0].weights_version == target
                        else 0,
                        -rs[0].active(),
                        0 if rs[1].cache.items else 1,
                    ),
                )
        return None

    def release(self, slot) -> None:
        with self._cond:
            slot.busy = False
            slot.tenant = None
            self._cond.notify_all()

    def preempt_below(self, priority: int) -> bool:
        """The admission preempt hook, fanned out: evict the GLOBALLY
        lowest-priority row across live replicas — replicas are ranked by
        their own minimum evictable priority first, so a priority-1 row
        on replica 1 is the victim even when replica 0 also holds an
        (evictable, but higher-priority) row. Races are tolerated: each
        scheduler's ``preempt_below`` re-validates under its own cond,
        and a replica whose candidate vanished simply yields to the next.
        Scheduler calls run UNLOCKED (the scheduler cond must never nest
        inside the pool cond — the health hooks order them the other
        way)."""
        with self._cond:
            scheds = [
                (r.idx, r.scheduler) for r in self.replicas
                if r.state != DEAD and r.scheduler is not None
            ]
        ranked = []
        for idx, sched in scheds:
            p = sched.min_preemptible_priority()
            if p is not None and p < priority:
                ranked.append((p, idx, sched))
        for _, _, sched in sorted(ranked, key=lambda t: (t[0], t[1])):
            if sched.preempt_below(priority):
                return True
        return False

    def count_replay(self) -> None:
        """One failover victim replayed (called by the serving layer's
        requeue loop). Locked: concurrent victim threads must not lose
        increments — the replayed-vs-victims health read depends on this
        ledger being exact."""
        with self._cond:
            self.replayed_total += 1

    # ------------------------------------------------------------------
    # Integrity: SDC canary scheduler + shadow voting (ISSUE 10).
    # The probe (ApiState._canary_probe) runs a pinned greedy prompt
    # through the replica's REAL batched path on a directly-claimed lane
    # — no admission permit (drain never waits on a probe), no tenant
    # accounting (billed to integrity.CANARY_TENANT) — and returns the
    # (tokens, fingerprint) pair, or None when inconclusive (lane busy,
    # canary preempted by real work, replica died mid-probe).
    # ------------------------------------------------------------------

    def claim_slot(self, idx: int, tenant: str | None = None):
        """Claim a free lane on replica ``idx`` directly, bypassing fair
        admission — the canary/shadow path. Prefers the lane with the
        emptiest chat cache (a probe resets its stream, so taking a lane
        that holds a live conversation's KV would cost that tenant its
        next-turn prefix reuse). Returns None when every lane is busy or
        the replica is dead/closed (the probe is skipped, not queued:
        integrity checks must never contend with real traffic)."""
        with self._cond:
            rep = self.replicas[idx]
            if rep.state == DEAD or self._closed:
                return None
            free = [s for s in rep.slots if not s.busy]
            if not free:
                return None
            slot = min(free, key=lambda s: len(s.cache.items))
            slot.busy = True
            slot.tenant = tenant
            return slot

    def start_canary(self, probe, interval_s: float, fail_threshold: int = 2):
        """Arm the canary: ``probe(replica, messages=None)`` is the
        serving layer's pinned-greedy executor. ``interval_s > 0`` starts
        the background scheduler thread; 0 arms manual :meth:`canary_tick`
        only (tests, and the shadow-vote path which reuses the probe)."""
        self.canary_probe = probe
        self.canary_fail_threshold = max(1, int(fail_threshold))
        self.canary_interval_s = (
            0.0 if interval_s is None else float(interval_s)
        )
        if self.canary_interval_s > 0 and self._canary_thread is None:
            self._canary_thread = threading.Thread(
                target=self._canary_loop, name="dllama-sdc-canary",
                daemon=True,
            )
            self._canary_thread.start()

    def _canary_loop(self) -> None:
        while True:
            with self._cond:
                if self._closed:
                    return
                # wait on a MONOTONIC deadline: the pool cond is notified
                # on every slot release and health event, so a bare
                # wait(timeout=interval) would wake — and tick — at
                # traffic frequency instead of the configured cadence
                deadline = time.monotonic() + self.canary_interval_s
                while not self._closed:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    self._cond.wait(timeout=left)
                if self._closed:
                    return
            try:
                self.canary_tick()
            except Exception as e:
                # the canary is a health INSTRUMENT: it must never take
                # the pool down with it
                print(f"⚠️ sdc canary tick failed: {type(e).__name__}: {e}")

    def canary_tick(self) -> int:
        """One canary pass over the live replicas; returns the number of
        CONCLUSIVE probes. The first conclusive result ever seen for a
        WEIGHT VERSION becomes that version's golden ("recorded at
        replica build" — the canary starts with the pool); every later
        probe compares (tokens, fingerprint) against its own replica's
        version golden, so a mixed-version rollout pool runs one golden
        per live version and never flaps across the divide. A mismatch walks the replica healthy→suspect, and
        ``canary_fail_threshold`` consecutive mismatches declare it DEAD
        **as corrupt** (victims get ReplicaCorrupt — the serving layer
        never splices a replay onto possibly-corrupt sent deltas); a
        match re-certifies integrity and clears a suspect replica the
        same way a fast dispatch round-trip does."""
        probe = self.canary_probe
        if probe is None:
            return 0
        with self._cond:
            if self._closed:
                return 0
            todo = [
                (r, r.generation) for r in self.replicas if r.state != DEAD
            ]
        conclusive = 0
        for rep, gen in todo:
            sw = Stopwatch()
            try:
                result = probe(rep)
            except Exception as e:
                print(
                    f"⚠️ canary probe on replica {rep.idx} failed: "
                    f"{type(e).__name__}: {e}"
                )
                result = None
            if result is not None:
                # conclusive probes only: a busy-lane skip returns in
                # microseconds and would flood the histogram with
                # healthy-looking near-zero samples exactly when probes
                # are NOT running
                self.tel.canary_latency.observe(sw.elapsed_s())
            kill_gen = None
            with self._cond:
                if self._closed:
                    return conclusive
                if rep.generation != gen or rep.state == DEAD:
                    continue  # replaced or died mid-probe: stale result
                if result is None:
                    continue
                conclusive += 1
                rep.last_canary = time.monotonic()
                self.sdc_checks_total += 1
                self.tel.sdc_checks.inc()
                golden = self._canary_goldens.get(rep.weights_version)
                if golden is None:
                    self._canary_goldens[rep.weights_version] = result
                    rep.integrity = "ok"
                    rep.canary_fails = 0
                    flight.record(
                        rep.idx, "canary", verdict="golden_set",
                        version=rep.weights_version,
                    )
                elif result == golden:
                    rep.integrity = "ok"
                    rep.canary_fails = 0
                    flight.record(
                        rep.idx, "canary", verdict="ok",
                        version=rep.weights_version,
                    )
                    if rep.state == SUSPECT:
                        # a full pinned greedy round trip through the real
                        # batched path matching the golden is at least as
                        # strong a recovery signal as a fast heartbeat
                        self._set_state_locked(rep, HEALTHY)
                else:
                    rep.integrity = "mismatch"
                    rep.canary_fails += 1
                    self.sdc_mismatches_total += 1
                    self.tel.sdc_mismatches.labels(check="canary").inc()
                    flight.record(
                        rep.idx, "canary", verdict="mismatch",
                        fails=rep.canary_fails,
                        threshold=self.canary_fail_threshold,
                        version=rep.weights_version,
                    )
                    if rep.canary_fails >= self.canary_fail_threshold:
                        kill_gen = gen
                    elif rep.state == HEALTHY:
                        self._set_state_locked(rep, SUSPECT)
            if kill_gen is not None:
                # outside the pool cond: mark_lost takes the scheduler
                # cond and hooks back into _on_event (lock order is
                # scheduler → pool, never the reverse)
                cause = (
                    f"silent data corruption: {rep.canary_fails} "
                    "consecutive canary mismatches against the "
                    f"{rep.weights_version} golden"
                )
                if rep.scheduler is not None:
                    rep.scheduler.mark_lost(cause, corrupt=True)
                else:
                    self._on_event(rep.idx, kill_gen, "lost", 0.0)
        return conclusive

    def shadow_vote(self, probe, messages) -> bool | None:
        """Cross-replica shadow vote (optional, N ≥ 2): re-execute a
        greedy request's prompt on two live replicas through the probe
        machinery and compare (tokens, fingerprint). Divergence proves
        one of them is silently corrupt; with only two opinions the
        minority is unknowable, so BOTH turn suspect and the next canary
        passes resolve them — the corrupt replica walks on to dead, the
        healthy one's matching canary clears it. Returns True (agree),
        False (diverged), None (inconclusive)."""
        with self._cond:
            live = [r for r in self.replicas if r.state != DEAD]
            if len(live) < 2 or self._closed:
                return None
            # a RANDOM pair (the entropy rng — which replicas a vote
            # covers must not be fleet-synchronized either): a fixed
            # live[:2] would leave replicas at index >= 2 structurally
            # outside shadow coverage forever
            pair = self._rng.sample(live, 2)
        votes = [probe(rep, messages) for rep in pair]
        if any(v is None for v in votes):
            return None
        with self._cond:
            self.sdc_checks_total += 1
            self.tel.sdc_checks.inc()
            if votes[0] == votes[1]:
                return True
            self.sdc_mismatches_total += 1
            self.tel.sdc_mismatches.labels(check="shadow").inc()
            for rep in pair:
                flight.record(
                    rep.idx, "shadow", verdict="diverged",
                    pair=[r.idx for r in pair],
                )
                if rep.state == HEALTHY:
                    self._set_state_locked(rep, SUSPECT)
            self._cond.notify_all()
            return False

    # ------------------------------------------------------------------
    # Health state machine (hook events arrive from scheduler threads,
    # possibly under the scheduler's cond — this side takes only _cond)
    # ------------------------------------------------------------------

    def _on_event(self, idx: int, generation: int, event: str, value: float) -> None:
        start_restart = False
        dump_death = False
        victim_traces: list[str] = []
        with self._cond:
            if idx >= len(self.replicas):
                return  # an echo from a retired slot (elastic shrink)
            rep = self.replicas[idx]
            if rep.generation != generation:
                return  # an echo from a replaced scheduler
            if event == "roundtrip":
                if value > self.suspect_roundtrip_s and rep.state == HEALTHY:
                    self._set_state_locked(rep, SUSPECT)
                elif value <= self.suspect_roundtrip_s and rep.state == SUSPECT:
                    self._set_state_locked(rep, HEALTHY)
            elif event == "stall":
                if rep.state == HEALTHY:
                    self._set_state_locked(rep, SUSPECT)
            elif event == "lost":
                if rep.state != DEAD:
                    self._set_state_locked(rep, DEAD)
                    # drop the dead replica's chains from the shared
                    # index (placement must never route to pages that no
                    # longer exist) and its spill-arena entries (a
                    # silently-corrupt replica may have spilled corrupt
                    # KV; its rebuild starts empty regardless) — both
                    # leaf locks, safe under _cond, atomic with the death
                    if self.shared_index is not None:
                        self.shared_index.drop_owner(idx)
                    if self.spill_arena is not None:
                        self.spill_arena.drop_owner(idx)
                    self.failovers_total += 1
                    # victims = occupied lanes on the dead replica, not the
                    # scheduler's joined count (a request between prefill
                    # chunks is in flight but not joined — it replays too)
                    self.last_failover_victims = rep.active()
                    self.tel.replica_failovers.inc()
                    if self.admission is not None:
                        self.admission.resize(-len(rep.slots))
                    start_restart = self.supervise and not self._closed
                    # flight recorder (ISSUE 16): name the failover's
                    # victims by their REQUEST traces — the dump links the
                    # death straight to the /debug/trace/<id> trees of the
                    # requests it replayed
                    for s in rep.slots:
                        t = getattr(getattr(s, "stream", None), "trace", None)
                        if t is not None:
                            victim_traces.append(t.request_id)
                    flight.record(
                        idx, "failover",
                        victims=self.last_failover_victims,
                        victim_trace_ids=victim_traces,
                        generation=generation,
                    )
                    dump_death = True
            self._cond.notify_all()
        if dump_death:
            # the auto-dump on replica death — outside the pool cond (the
            # optional artifact write spawns a thread)
            flight.RECORDER.dump(
                idx, "replica_death",
                victims=self.last_failover_victims,
                victim_trace_ids=victim_traces,
            )
        if start_restart:
            threading.Thread(
                target=self._restart_loop, args=(idx, generation),
                name=f"dllama-replica-restart-{idx}", daemon=True,
            ).start()

    def _set_state_locked(self, rep: Replica, state: str) -> None:
        if state == SUSPECT and rep.state != SUSPECT:
            self.suspects_total += 1
        if state != rep.state:
            # flight recorder (ISSUE 16): the health-state walk is the
            # spine of every post-mortem dump. The recorder lock is a
            # leaf — safe under the pool cond.
            flight.record(
                rep.idx, "state", frm=rep.state, to=state,
                generation=rep.generation,
            )
        rep.state = state
        self.tel.replica_state.labels(replica=str(rep.idx)).set(
            STATE_VALUES[state]
        )

    def mark_dead(self, idx: int, cause: str) -> None:
        """Operator/test entry point: declare replica ``idx`` dead through
        its scheduler's own loss path (in-flight requests get ReplicaLost,
        the hook fires back into the pool)."""
        rep = self.replicas[idx]
        if rep.scheduler is not None:
            rep.scheduler.mark_lost(cause)
        else:
            self._on_event(idx, rep.generation, "lost", 0.0)

    # ------------------------------------------------------------------
    # Restart supervision
    # ------------------------------------------------------------------

    def _restart_loop(self, idx: int, generation: int) -> None:
        """Rebuild a dead replica under the jittered backoff policy. The
        build (engine load + scheduler construction, possibly jit
        compiles) runs OUTSIDE the pool lock; the swap-in is atomic under
        it. A closed pool aborts the loop (the on_retry hatch)."""

        def build():
            if self._closed:
                raise RuntimeError("pool closed; not restarting")
            engine, scheduler, slots = self.build_replica(idx)
            try:
                self._verify_rebuild(idx, engine)
            except BaseException:
                # a corrupt rebuild never re-enters placement: tear down
                # its watchdog and let the backoff loop try again
                if scheduler is not None:
                    scheduler.close()
                raise
            return engine, scheduler, slots

        def on_retry(attempt, exc):
            if self._closed:
                raise exc
            print(
                f"⚠️ replica {idx} restart attempt {attempt + 1} failed: "
                f"{type(exc).__name__}: {exc}"
            )

        try:
            engine, scheduler, slots = retry.retry_call(
                build, self.restart_policy, on_retry=on_retry, rng=self._rng,
            )
        except Exception as e:
            print(f"🛑 replica {idx} restart abandoned: {e}")
            return
        with self._cond:
            rep = (
                self.replicas[idx] if idx < len(self.replicas) else None
            )
            if rep is None or self._closed or rep.generation != generation:
                dead = scheduler
            else:
                dead = rep.scheduler
                rep.engine, rep.scheduler, rep.slots = (
                    engine, scheduler, list(slots)
                )
                rep.generation += 1
                rep.restarts += 1
                # death recovery converges to the rollout state machine's
                # intent: the supervisor rebuilds whatever version THIS
                # SLOT should run, not whatever the dying replica ran
                rep.weights_version = self.target_version(idx)
                self.restarts_total += 1
                self._set_state_locked(rep, HEALTHY)
                self._adopt(rep)
                self.tel.replica_restarts.inc()
                if self.admission is not None:
                    self.admission.resize(len(rep.slots))
            self._cond.notify_all()
        if dead is not None:
            dead.close()

    def _verify_rebuild(self, idx: int, engine) -> None:
        """Weight-checksum verification of a rebuilt replica (ISSUE 10):
        the rebuild re-read the weights through the same host RAM / disk /
        cores that may have corrupted the replica in the first place, so
        it must prove byte-level agreement with the load-time reference
        of the VERSION this slot should run (ISSUE 18: per-version map —
        a rollout cutover verifies against the new version's reference,
        the supervisor against whatever the state machine says) BEFORE
        re-entering placement. A mismatch raises
        :class:`integrity.ChecksumMismatch` — the restart loop counts it
        as a failed attempt and retries under backoff."""
        version = self.target_version(idx)
        want = self.weights_reference.get(version)
        if engine is None or want is None:
            return
        got = integrity.params_checksum(engine.params)
        with self._cond:
            self.sdc_checks_total += 1
        self.tel.sdc_checks.inc()
        if got != want:
            with self._cond:
                self.sdc_mismatches_total += 1
            self.tel.sdc_mismatches.labels(check="checksum").inc()
            flight.record(
                idx, "checksum", verdict="mismatch", got=got,
                want=want, version=version,
            )
            raise integrity.ChecksumMismatch(
                f"replica {idx} rebuild checksum {got} != {version} "
                f"reference {want}; refusing to re-enter placement"
            )
        flight.record(idx, "checksum", verdict="ok", version=version)

    # ------------------------------------------------------------------
    # Rollout + elasticity primitives (ISSUE 18). The pool owns the
    # MECHANISMS — per-slot target versions, cordon, drain, synchronous
    # rebuild, grow/retire, per-version checksum references and canary
    # goldens — while server/fleet.py owns the POLICY (the rollout state
    # machine and the FleetController loop). Same lock discipline as the
    # rest of the pool: builds run unlocked, swaps are atomic under
    # ``_cond`` and generation-guarded, and nothing calls into a
    # scheduler while holding the pool cond.
    # ------------------------------------------------------------------

    def target_version(self, idx: int) -> str:
        """The weight version slot ``idx`` SHOULD run: the rollout state
        machine's per-slot override when one is set, else the pool
        version. Every rebuild path — the orchestrated cutover and the
        supervisor's death recovery alike — builds and verifies this
        version, so a replica death mid-rollout converges to the
        rollout's intent, never the dying replica's past."""
        with self._cond:
            return self._slot_versions.get(idx, self.weights_version)

    def set_slot_version(self, idx: int, version: str) -> None:
        """Pin slot ``idx``'s target version (the rollout's first act per
        move — set BEFORE the drain so a death at any later point
        rebuilds on the intended version)."""
        with self._cond:
            self._slot_versions[idx] = str(version)

    def register_version(self, version: str, checksum: str | None) -> None:
        """Record a weight version's load-time checksum reference — the
        rebuild gate for every replica built on that version. ``None``
        leaves any existing entry alone (a reference is an optimization,
        never a blocker — fake/test engines have no params)."""
        if checksum is None:
            return
        with self._cond:
            self.weights_reference[str(version)] = str(checksum)

    def retire_version(self, version: str) -> None:
        """Drop a version's integrity anchors (checksum reference and
        canary golden) once no replica serves it: a rolled-back target
        must not leave a stale golden to flap against later, and a
        completed rollout's old version leaves with its last replica."""
        with self._cond:
            self.weights_reference.pop(version, None)
            self._canary_goldens.pop(version, None)

    def set_cordon(self, idx: int, cordoned: bool) -> None:
        """Exclude/include replica ``idx`` from NEW placements. No health
        implication: cordoned lanes stay claimable for certification
        probes and keep streaming their in-flight requests to the end."""
        with self._cond:
            self.replicas[idx].cordoned = bool(cordoned)
            self._cond.notify_all()

    def drain_replica(self, idx: int, timeout_s: float = 30.0) -> bool:
        """Cordon replica ``idx`` and wait for its in-flight requests to
        finish (or the replica to die — its victims are already in the
        replay path, which frees the slot either way). Returns False at
        the cap; the cordon stays on regardless (the caller owns lifting
        it, and owns escalation on a missed drain)."""
        self.set_cordon(idx, True)
        deadline = time.monotonic() + float(timeout_s)
        with self._cond:
            while True:
                rep = self.replicas[idx]
                if rep.state == DEAD or rep.active() == 0:
                    return True
                left = deadline - time.monotonic()
                if left <= 0 or self._closed:
                    return False
                self._cond.wait(timeout=left)

    def rebuild_replica(self, idx: int, mutate=None) -> bool:
        """Synchronously rebuild replica ``idx`` on ``target_version(idx)``
        through the same factory + checksum gate as the supervisor — the
        rollout's cutover (death recovery stays with the supervisor's
        backoff loop). The build runs unlocked; the swap is atomic under
        the cond and generation-guarded, so racing a concurrent
        supervisor rebuild is safe: whoever swaps second sees the bumped
        generation, discards its build, and returns False — the caller
        re-observes with :meth:`wait_state`. ``mutate`` is the chaos
        hook (``server.rollout`` ``kind=corrupt``): applied to the fresh
        engine BEFORE checksum verification, so an injected corruption
        trips exactly the gate a real one would. Raises on build/verify
        failure — the caller owns rollback."""
        with self._cond:
            gen = self.replicas[idx].generation
        engine, scheduler, slots = self.build_replica(idx)
        try:
            if mutate is not None and engine is not None:
                mutate(engine)
            self._verify_rebuild(idx, engine)
        except BaseException:
            if scheduler is not None:
                scheduler.close()
            raise
        with self._cond:
            rep = self.replicas[idx]
            if self._closed or rep.generation != gen:
                dead = scheduler
                swapped = False
            else:
                was_dead = rep.state == DEAD
                dead = rep.scheduler
                rep.engine, rep.scheduler, rep.slots = (
                    engine, scheduler, list(slots)
                )
                rep.generation += 1
                rep.weights_version = self.target_version(idx)
                self._set_state_locked(rep, HEALTHY)
                self._adopt(rep)
                if was_dead and self.admission is not None:
                    # death already resized this capacity out; coming
                    # back through THIS path (not the supervisor's)
                    # re-adds it — admission stays exact either way
                    self.admission.resize(len(rep.slots))
                swapped = True
            self._cond.notify_all()
        if dead is not None:
            # on a lost race this is OUR scheduler (never adopted); on a
            # win it is the replaced one — closed outside the cond
            dead.close()
        return swapped

    def grow_replica(self):
        """Append one replica (elastic scale-up) built through the same
        factory + checksum gate as a rebuild. Joins at the END of the
        list so existing indices stay dense and stable (the shared
        index's owner ids, chaos ``row=`` selectors and the flight
        recorder all key on idx). Returns the new index, or None when
        the pool closed or a concurrent grow raced us."""
        with self._cond:
            if self._closed:
                return None
            idx = len(self.replicas)
        engine, scheduler, slots = self.build_replica(idx)
        try:
            self._verify_rebuild(idx, engine)
        except BaseException:
            if scheduler is not None:
                scheduler.close()
            raise
        rep = Replica(idx, engine, scheduler, slots)
        with self._cond:
            if self._closed or len(self.replicas) != idx:
                dead = scheduler
            else:
                dead = None
                rep.weights_version = self.target_version(idx)
                self.replicas.append(rep)
                self._adopt(rep)
                if self.admission is not None:
                    self.admission.resize(len(rep.slots))
                self._cond.notify_all()
        if dead is not None:
            dead.close()
            return None
        return idx

    def retire_replica(self, drain_timeout_s: float = 10.0) -> bool:
        """Drain and remove the LAST replica (elastic scale-down; the
        last index retires so survivors keep dense idx addressing).
        Refuses (False) on a 1-replica pool. A missed drain still
        retires: the leftover in-flight work takes the failover path
        (typed ReplicaLost → requeue → bit-identical replay on a
        survivor) — the scale-down contract IS the failover contract,
        just scheduled instead of suffered."""
        with self._cond:
            if self._closed or len(self.replicas) <= 1:
                return False
            idx = len(self.replicas) - 1
        drained = self.drain_replica(idx, timeout_s=drain_timeout_s)
        with self._cond:
            if self._closed or len(self.replicas) - 1 != idx:
                return False  # raced a concurrent grow/retire
            rep = self.replicas.pop()
            # orphan any in-flight supervisor rebuild of this slot: its
            # swap-in is generation-guarded and the slot is gone
            rep.generation += 1
            self._slot_versions.pop(idx, None)
            was_dead = rep.state == DEAD
            if self.shared_index is not None:
                self.shared_index.drop_owner(idx)
            if self.spill_arena is not None:
                self.spill_arena.drop_owner(idx)
            if self.admission is not None and not was_dead:
                # a dead replica's capacity already left at death
                self.admission.resize(-len(rep.slots))
            flight.record(
                idx, "retire", drained=drained, state=rep.state,
            )
            self._cond.notify_all()
        if rep.scheduler is not None:
            if not drained and not was_dead:
                # undrained work replays through fair admission — marked
                # lost OUTSIDE the pool cond (scheduler → pool order);
                # the pool hook finds the slot gone and returns
                rep.scheduler.mark_lost(
                    f"replica {idx} retired (elastic scale-down)"
                )
            rep.scheduler.close()
        return True

    def certify_replica(self, idx: int, result) -> bool:
        """Compare one conclusive probe ``result`` against the replica's
        VERSION golden, setting the golden when this is the version's
        first conclusive probe — the rollout's first upgraded replica
        records the new version's golden exactly as the boot canary
        recorded v0's. Counts an SDC check either way; a mismatch counts
        as one and returns False (the rollout aborts — this gate never
        walks health states itself)."""
        with self._cond:
            rep = self.replicas[idx]
            version = rep.weights_version
            rep.last_canary = time.monotonic()
            self.sdc_checks_total += 1
            self.tel.sdc_checks.inc()
            golden = self._canary_goldens.get(version)
            if golden is None:
                self._canary_goldens[version] = result
                rep.integrity = "ok"
                rep.canary_fails = 0
                flight.record(
                    idx, "canary", verdict="golden_set", version=version,
                )
                return True
            if result == golden:
                rep.integrity = "ok"
                rep.canary_fails = 0
                flight.record(
                    idx, "canary", verdict="ok", version=version,
                )
                return True
            rep.integrity = "mismatch"
            self.sdc_mismatches_total += 1
            self.tel.sdc_mismatches.labels(check="canary").inc()
            flight.record(
                idx, "canary", verdict="mismatch", version=version,
            )
            return False

    def rollout_status(self) -> dict:
        """The /readyz ``rollout`` field: ``{"active": False}`` at rest,
        else a copy of the live state machine (active/from/to/moved/
        total)."""
        with self._cond:
            if self.rollout is None:
                return {"active": False}
            return dict(self.rollout)

    # ------------------------------------------------------------------
    # Introspection (/readyz, tests)
    # ------------------------------------------------------------------

    def snapshot(self) -> list[dict]:
        """Per-replica health for the /readyz JSON body
        (docs/OBSERVABILITY.md "Readiness schema")."""
        now = time.monotonic()
        with self._cond:
            return [
                {
                    "replica": r.idx,
                    "state": r.state,
                    "active_rows": r.active(),
                    "slots": len(r.slots),
                    "restarts": r.restarts,
                    # rollout read (ISSUE 18): which weights this replica
                    # serves, its rebuild generation, and whether it is
                    # cordoned out of new placements (drain-in-progress)
                    "weights_version": r.weights_version,
                    "generation": r.generation,
                    "cordoned": r.cordoned,
                    # prefix-cache occupancy (ISSUE 11): device pages held
                    # / pinned and this replica's spill-arena depth. Racy
                    # integer reads of the scheduler's tree on purpose —
                    # a snapshot must not take the scheduler cond (lock
                    # order is scheduler → pool, never the reverse)
                    "cache": self._cache_read(r),
                    # SDC canary read (ISSUE 10): "unverified" until the
                    # first conclusive probe of this generation, then
                    # "ok"/"mismatch"; age None while unprobed. A
                    # balancer can shed a replica whose canary is stale
                    # or failing before the pool walks it to dead
                    "integrity": r.integrity,
                    "last_canary_age_s": (
                        None if r.last_canary is None
                        else round(now - r.last_canary, 3)
                    ),
                }
                for r in self.replicas
            ]

    @staticmethod
    def _cache_read(rep: Replica):
        """Per-replica prefix-cache occupancy for /readyz, or None when
        the replica has no prefix cache (batching off, misconfigured
        pool, no scheduler)."""
        prefix = getattr(rep.scheduler, "_prefix", None)
        if prefix is None:
            return None
        return {
            "pages": prefix.pages_in_use(),
            "pinned": prefix.pinned_pages(),
            "spill_depth": prefix.spill_depth(),
        }

    def states(self) -> list[str]:
        with self._cond:
            return [r.state for r in self.replicas]

    def wait_state(self, idx: int, state: str, timeout_s: float = 30.0) -> bool:
        """Block until replica ``idx`` reaches ``state`` (tests: the
        restarted-and-serving-again acceptance gate)."""
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while self.replicas[idx].state != state:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cond.wait(timeout=left)
            return True
