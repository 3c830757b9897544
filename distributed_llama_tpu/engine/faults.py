"""Deterministic fault injection for chaos testing (ISSUE 3).

The reference distributed-llama assumes a fault-free world: a worker socket
error or a hung dispatch kills the whole root process (reference:
src/apps/dllama/dllama.cpp:418-423 — no error path at all). This module is
the opposite posture made testable: a process-wide :class:`FaultPlan` with
NAMED injection sites threaded through the engine, the batch scheduler, the
parallel backends and the API server, so chaos tests can provoke the exact
failure they want — deterministically, from a seed — and assert the system
degrades instead of collapsing.

Injection sites (the strings passed to :meth:`FaultPlan.fire`):

==================  =========================================================
``batch.dispatch``  raise inside the batched chunk dispatch
                    (engine/batch.py ``_dispatch_locked``; retried with
                    backoff before the rows are retired)
``batch.fetch``     raise/delay/hang inside the batched chunk fetch
                    (``_fetch``; a raise models a transfer error and is
                    retried, a hang trips the stall watchdog)
``batch.row``       corrupt ONE row of a fetched chunk (``kind=nan`` with a
                    ``row=``) — stands in for NaN logits from a single
                    sequence; the scheduler quarantines only that row
``engine.forward``  raise at any single-stream forward dispatch
``engine.decode_dispatch``  raise at a single-stream decode-chunk dispatch
``engine.fetch``    raise/delay at the single-stream chunk fetch
``engine.spec_verify``  raise at a speculative-decode verify step: fired at
                    the single-stream verify dispatch, and per row while a
                    batched verify's results are validated — a ``row=``
                    rule there quarantines ONLY the targeted row, its
                    co-batched survivors delivered bit-identically
                    (engine/batch.py ``_fetch``)
``engine.paged_attn``  raise at a zero-copy paged-attention dispatch: fired
                    per joined row while a paged batched chunk is built —
                    a ``row=`` rule quarantines ONLY the targeted row AND
                    releases its page pins (the aliased pages stay live
                    for every other row; survivors bit-identical)
``engine.fused_step``  raise mid-superstep (ISSUE 17): fired per joined
                    row as a batched chunk — plain decode or spec verify —
                    is about to launch the fused per-layer programs
                    (rmsnorm→Q80→matmul epilogue, paged attention,
                    the matmul+all-reduce seam). A ``row=`` rule
                    quarantines ONLY the victim and releases its page
                    pins; co-batched survivors stream bit-identically
                    (engine/batch.py ``_fire_fused_step_locked``)
``engine.sdc``      silent-data-corruption injection (ISSUE 10): a
                    ``kind=corrupt`` rule fired per batched-chunk dispatch
                    deterministically perturbs this replica's state into
                    FINITE-but-wrong values — ``message=weights`` (the
                    default) flips a weight slice in place so every
                    subsequent decode emits plausible wrong tokens,
                    ``message=logits`` perturbs the next fetched chunk's
                    token columns in-vocab. Neither NaNs nor raises: the
                    class the quarantine path cannot see, detectable only
                    by integrity checks (engine/integrity.py canaries /
                    fingerprints / shadow votes). ``row=`` selects the
                    REPLICA id, like the replica.* sites
``engine.spill``    spill-tier reload fault (ISSUE 11): fired per
                    candidate block while an admission match pulls
                    spilled prefix pages back from the host-RAM arena
                    (engine/spill.py). A raise aborts the reload —
                    already-uploaded blocks stay, deeper blocks fall
                    back to a COLD prefill; ``kind=corrupt`` flips the
                    arena entry's bytes in place (a silent host-RAM/disk
                    bit flip), which the per-entry CRC verification must
                    catch and drop — stale KV is never uploaded, the
                    block prefills cold. ``row=`` selects the REPLICA id
``engine.preempt``  raise during a priority preemption's eviction
                    (engine/batch.py ``preempt_below``): the victim row is
                    QUARANTINED instead of cleanly requeued — its request
                    fails typed, its page pins release through the row's
                    normal unwind, co-batched survivors stay bit-identical,
                    and the preemptor still admits once the quarantined
                    row's slot frees
``replica.crash``   whole-replica loss (ISSUE 9): fired per batched-chunk
                    AND per prefill-chunk dispatch — a raise marks the
                    ENTIRE scheduler lost (every in-flight request on it
                    gets a typed ``ReplicaLost``; the serving layer
                    requeues them through fair admission onto a surviving
                    replica and the supervisor restarts the dead one).
                    ``row=`` selects the REPLICA id, not a batch row
``replica.hang``    ``kind=hang`` sleep inside the batched chunk fetch:
                    the stall watchdog trips and — on a supervised replica
                    (``lost_on_stall``) — escalates the stall to a whole-
                    replica loss instead of per-row StallTimeout.
                    ``row=`` selects the replica id
``replica.slow``    ``kind=delay`` inside the batched chunk fetch: the
                    dispatch round-trip exceeds the replica pool's suspect
                    threshold and the replica turns SUSPECT (skipped for
                    new placements until a fast round-trip clears it).
                    ``row=`` selects the replica id
``tp.transfer``     raise/delay inside the transfer probe (the engine keeps
                    its last estimate instead of dying)
``server.send``     raise ``BrokenPipeError`` from the SSE chunk writer
                    (``kind=disconnect``) — models a client disconnect
``server.rollout``  blue-green rollout chaos (ISSUE 18): fired by the
                    rollout orchestrator once per replica MOVE, ``row=``
                    selecting the replica id. ``kind=corrupt`` perturbs
                    the freshly built new-version engine BEFORE the
                    checksum gate (the gate trips → automatic rollback);
                    ``kind=raise`` fails the move at the canary
                    certification step (a new-version golden mismatch →
                    rollback); ``kind=delay``/``hang`` widens the
                    cutover window so a composed ``replica.crash`` can
                    kill a replica MID-rollout (the supervisor rebuilds
                    on whatever version the state machine pins)
==================  =========================================================

Zero overhead when disabled — the same bind-once trick as telemetry:
components bind ``self._faults = faults.active_plan()`` at construction and
get the shared :data:`NULL_PLAN` singleton (no-op ``fire``/``fires``) when
no plan is installed. Hot paths pay one attribute-bound no-op call per
*dispatch*, never per token, and never touch this module's globals.
Install a plan BEFORE constructing the engine/scheduler/server.

Configuration
-------------
* env: ``DLLAMA_FAULTS="batch.fetch:kind=raise,after=2,count=1"`` (read once
  at import; ``DLLAMA_FAULTS_SEED`` seeds probabilistic rules), or
* flag: ``dllama-tpu-api --faults "<spec>"``, or
* code: ``faults.install(faults.parse(spec, seed=0))``.

A spec is ``;``-separated rules, each ``site:key=val,key=val`` (or a JSON
array of rule objects). Fields: ``kind`` (``raise`` | ``nan`` | ``delay`` |
``hang`` | ``disconnect``), ``after`` (skip the first N hits of the site),
``count`` (fire on this many subsequent hits; -1 = forever), ``p``
(per-hit probability, drawn from the seeded RNG), ``row`` (restrict to one
batch row), ``delay_ms`` (for ``delay``/``hang``). Full format and
semantics: docs/ROBUSTNESS.md.

Determinism: site-hit counters are lock-protected and count every hook
invocation, so ``after``/``count`` rules fire on exactly the same hits on
every run. ``p < 1`` rules draw from one seeded RNG in hit order — fully
reproducible for single-pump sites (the batch scheduler dispatch/fetch),
reproducible up to thread interleaving elsewhere.

Every actual injection increments ``dllama_faults_injected_total{site}``
(when telemetry is enabled) and the plan's plain ``injected_total``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import threading
import time

from distributed_llama_tpu import lockcheck


class InjectedFault(RuntimeError):
    """Raised at an injection site by a ``kind=raise`` rule."""


class DeadlineExceeded(RuntimeError):
    """A request ran past its deadline: the row left the batch and the
    stream ends (the API server maps this to 504 / an SSE error event)."""


class RowQuarantined(RuntimeError):
    """This request's batch row was retired after a failed or corrupted
    chunk (bounded retries exhausted); co-batched rows keep streaming."""


class StallTimeout(RuntimeError):
    """The watchdog declared an in-flight batched chunk stalled and failed
    the batch cleanly (the hung fetch's late result is discarded)."""


class RowPreempted(RuntimeError):
    """This request's batch row was evicted by a higher-priority arrival
    (engine/batch.py ``preempt_below``). NOT a failure: the serving layer
    catches it and REQUEUES the request through weighted-fair admission —
    the re-run prefills through the prefix cache's published pages and,
    at the same seed, streams bit-identically to an uncontended run
    (already-sent SSE deltas are suppressed on replay)."""


class NonFiniteLogits(RowQuarantined):
    """A decode step produced NaN/Inf logits for this row (ISSUE 10): the
    device-side finiteness flag fetched with every batched chunk — or the
    host sampler's pre-sampling validation — caught it BEFORE a sampled
    token could launder the corruption into a plausible in-vocab id. The
    row is quarantined exactly like any corrupt chunk."""


class ReplicaLost(RuntimeError):
    """This request's WHOLE replica (engine + BatchScheduler) died — a
    crashed dispatch, or a hang the stall watchdog escalated (ISSUE 9).
    Like :class:`RowPreempted`, not a request failure: the serving layer
    requeues the request through weighted-fair admission onto a surviving
    replica and REPLAYS it — pinned sampling seed, already-sent SSE deltas
    suppressed, stream bit-identical to an unfaulted run — while the
    replica supervisor restarts the dead replica with jittered backoff
    (server/replicas.py; docs/ROBUSTNESS.md failure-domain table)."""


class ReplicaCorrupt(ReplicaLost):
    """This request's replica was declared dead for SILENT DATA CORRUPTION
    (a canary/shadow integrity mismatch, ISSUE 10) — wrong-but-finite
    outputs, not a crash. Crucially different from a plain
    :class:`ReplicaLost` for a stream that already sent deltas: those
    deltas may themselves be corrupt, so a suppressed replay would SPLICE
    a wrong prefix onto a correct continuation. The serving layer replays
    a ReplicaCorrupt victim only while nothing has streamed; otherwise the
    stream ends with a typed ``replica_corrupt`` error — loud failure
    instead of laundered corruption (server/api.py ``complete``)."""


KINDS = ("raise", "nan", "delay", "hang", "disconnect", "corrupt")

# The registered injection sites — the single source of truth the static
# analyzer's FLT-001 rule cross-checks against every fire()/fires() call
# site in the tree (an unregistered site can't be targeted by --faults
# specs; a registered-but-never-fired site is dead and gets flagged too).
# Keep this tuple and the docstring table above in sync when adding hooks.
SITES = (
    "batch.dispatch",
    "batch.fetch",
    "batch.row",
    "engine.forward",
    "engine.decode_dispatch",
    "engine.fetch",
    "engine.spec_verify",
    "engine.paged_attn",
    "engine.fused_step",
    "engine.preempt",
    "engine.sdc",
    "engine.spill",
    "replica.crash",
    "replica.hang",
    "replica.slow",
    "tp.transfer",
    "server.send",
    "server.rollout",
)

# a "hang" sleeps this long unless the rule sets delay_ms — far beyond any
# stall timeout, short enough that a daemon-threaded test process still exits
HANG_DEFAULT_MS = 60_000.0

# Fire observers (ISSUE 16): called on every ACTUAL injection with
# ``(site, rule, row)`` — the flight recorder's feed
# (telemetry/flight.py), so a dump can always name the chaos site behind
# a death. Observers run under the plan's lock and must only append to
# leaf-locked state; a failing observer is swallowed (chaos bookkeeping
# must never alter the injection it observes).
_fire_observers: list = []


def add_fire_observer(fn) -> None:
    if fn not in _fire_observers:
        _fire_observers.append(fn)


def remove_fire_observer(fn) -> None:
    if fn in _fire_observers:
        _fire_observers.remove(fn)


@dataclasses.dataclass
class FaultRule:
    """One injection rule. See the module docstring for field semantics."""

    site: str
    kind: str = "raise"
    after: int = 0
    count: int = 1
    p: float = 1.0
    row: int | None = None
    delay_ms: float = 0.0
    message: str = ""

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r} (one of {KINDS})")
        if not self.site:
            raise ValueError("fault rule needs a site")


class FaultPlan:
    """A seeded set of :class:`FaultRule`\\ s plus the per-site hit
    counters that make ``after``/``count``/``p`` deterministic."""

    enabled = True

    def __init__(self, rules, seed: int = 0):
        self.rules: list[FaultRule] = list(rules)
        self.seed = int(seed)
        self._lock = lockcheck.make_lock("FaultPlan._lock")
        self._hits: dict[str, int] = {}
        self._fired: dict[int, int] = {}
        self._rng = random.Random(self.seed)
        self.injected_total = 0  # plain count: readable with telemetry off

    def reset(self) -> None:
        """Rewind the hit/fired counters and the RNG (same plan, fresh run)."""
        with self._lock:
            self._hits.clear()
            self._fired.clear()
            self._rng = random.Random(self.seed)

    def _match(
        self, site: str, row: int | None = None, rows=None
    ) -> FaultRule | None:
        with self._lock:
            hit = self._hits.get(site, 0)
            self._hits[site] = hit + 1
            for i, r in enumerate(self.rules):
                if r.site != site:
                    continue
                if hit < r.after:
                    continue
                fired = self._fired.get(i, 0)
                if r.count >= 0 and fired >= r.count:
                    continue
                if row is not None and r.row is not None and r.row != row:
                    continue
                if rows is not None and r.row is not None and r.row not in rows:
                    # the targeted row is not riding this hit (e.g. not in
                    # the current batch bucket): hold the rule WITHOUT
                    # consuming its count — it fires when the victim shows up
                    continue
                if r.p < 1.0 and self._rng.random() >= r.p:
                    continue
                self._fired[i] = fired + 1
                self.injected_total += 1
                for obs in _fire_observers:
                    try:
                        obs(site, r, row)
                    except Exception:
                        pass
                # resolved per injection, NOT bound at construction: an
                # env-installed plan exists before a --telemetry flag
                # enables the registry, and injections are rare enough
                # that the lookup costs nothing (telemetry off → null)
                from distributed_llama_tpu import telemetry

                telemetry.counter(
                    "dllama_faults_injected_total",
                    "Faults actually injected by the active chaos plan, "
                    "by site",
                    labelnames=("site",),
                ).labels(site=site).inc()
                return r
        return None

    def fire(self, site: str, row: int | None = None) -> FaultRule | None:
        """The hook call sites thread through the hot paths: raises for
        ``raise``/``disconnect`` rules, sleeps for ``delay``/``hang``,
        returns the matched rule (or None) otherwise."""
        rule = self._match(site, row=row)
        if rule is None:
            return None
        if rule.kind == "raise":
            raise InjectedFault(rule.message or f"injected fault at {site}")
        if rule.kind == "disconnect":
            raise BrokenPipeError(
                rule.message or f"injected client disconnect at {site}"
            )
        if rule.kind in ("delay", "hang"):
            ms = rule.delay_ms or (HANG_DEFAULT_MS if rule.kind == "hang" else 0.0)
            time.sleep(ms / 1000.0)
        return rule

    def fires(self, site: str, row: int | None = None, rows=None) -> FaultRule | None:
        """Non-raising variant for data-corruption sites (``kind=nan``):
        the call site applies the corruption itself from the returned rule.
        ``rows`` names the rows riding this hit — a row-targeted rule holds
        (count unconsumed) until its victim is present."""
        return self._match(site, row=row, rows=rows)


class _NullPlan:
    """Disabled-mode bind target: stateless no-op singleton (the faults
    analogue of telemetry's null instruments)."""

    __slots__ = ()
    enabled = False
    injected_total = 0

    def fire(self, site: str, row: int | None = None) -> None:
        return None

    def fires(self, site: str, row: int | None = None, rows=None) -> None:
        return None


NULL_PLAN = _NullPlan()

_active: FaultPlan | None = None


def install(plan: FaultPlan) -> FaultPlan:
    """Make ``plan`` the process-wide active plan. Components bind at
    construction — install BEFORE building the engine/scheduler/server."""
    global _active
    _active = plan
    return plan


def clear() -> None:
    global _active
    _active = None


def active_plan() -> FaultPlan | _NullPlan:
    """The bind-once entry point: the active plan, or the no-op singleton."""
    return _active if _active is not None else NULL_PLAN


_INT_FIELDS = ("after", "count", "row")
_FLOAT_FIELDS = ("p", "delay_ms")


def parse(spec: str, seed: int = 0) -> FaultPlan:
    """Parse a fault-plan spec: ``;``-separated ``site:key=val,key=val``
    rules, or a JSON array/object of rule fields (docs/ROBUSTNESS.md)."""
    spec = (spec or "").strip()
    rules: list[FaultRule] = []
    if spec.startswith("[") or spec.startswith("{"):
        data = json.loads(spec)
        if isinstance(data, dict):
            data = [data]
        rules = [FaultRule(**d) for d in data]
    else:
        for part in spec.split(";"):
            part = part.strip()
            if not part:
                continue
            site, _, kvs = part.partition(":")
            kw: dict = {"site": site.strip()}
            for kv in filter(None, (x.strip() for x in kvs.split(","))):
                k, _, v = kv.partition("=")
                k, v = k.strip(), v.strip()
                if k in _INT_FIELDS:
                    kw[k] = int(v)
                elif k in _FLOAT_FIELDS:
                    kw[k] = float(v)
                elif k in ("kind", "message"):
                    kw[k] = v
                else:
                    raise ValueError(f"unknown fault-rule field {k!r}")
            rules.append(FaultRule(**kw))
    if not rules:
        raise ValueError(f"empty fault plan: {spec!r}")
    return FaultPlan(rules, seed=seed)


_ENV_VAR = "DLLAMA_FAULTS"
_env_spec = os.environ.get(_ENV_VAR, "").strip()
if _env_spec:
    install(parse(_env_spec, seed=int(os.environ.get("DLLAMA_FAULTS_SEED", "0") or 0)))
