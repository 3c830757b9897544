"""Runtime lock-order witness tests (ISSUE 20).

The static LCK-003 rule proves the lexical acquisition graph respects the
pyproject hierarchy; these tests prove the runtime half: the witness
wrappers see the orders that only exist dynamically (callbacks, the
supervisor and canary threads) and the whole replica-failover story runs
clean under them. The seeded-inversion test is the discriminator — the
witness that never fires is indistinguishable from no witness at all.
"""

import os
import threading
import time

import pytest

from distributed_llama_tpu import lockcheck
from distributed_llama_tpu.lockcheck import LockOrderViolation

RANKS = {"Sched._cond": 20, "Pool._cond": 40, "Leaf._lock": 80}


@pytest.fixture
def witness():
    lockcheck.configure(ranks=RANKS, mode="raise")
    lockcheck.reset()
    yield lockcheck
    lockcheck.configure()
    lockcheck.reset()


# ----------------------------------------------------------------------
# Factories
# ----------------------------------------------------------------------


def test_factories_are_plain_primitives_when_off():
    lockcheck.configure(mode="off")
    try:
        assert isinstance(lockcheck.make_lock("Pool._cond"), type(threading.Lock()))
        assert isinstance(lockcheck.make_rlock("Pool._cond"), type(threading.RLock()))
        assert isinstance(lockcheck.make_condition("Pool._cond"), threading.Condition)
        assert not lockcheck.enabled()
    finally:
        lockcheck.configure()


def test_unranked_name_stays_plain_even_when_armed(witness):
    assert isinstance(lockcheck.make_lock("Nobody._lock"), type(threading.Lock()))


def test_repo_construction_sites_are_witnessed_when_armed():
    """The real package's locks come out wrapped under the pyproject rank
    table (no configure(ranks=...) override): the table the analyzer
    enforces is the table the witness loads."""
    lockcheck.configure(mode="raise")  # ranks: from pyproject
    try:
        from distributed_llama_tpu.telemetry import flight

        fr = flight.FlightRecorder(capacity=4)
        assert "FlightRecorder._lock" in repr(fr._lock)
    finally:
        lockcheck.configure()


# ----------------------------------------------------------------------
# Order checking
# ----------------------------------------------------------------------


def test_ascending_acquisition_is_clean(witness):
    sched = lockcheck.make_condition("Sched._cond")
    pool = lockcheck.make_condition("Pool._cond")
    leaf = lockcheck.make_lock("Leaf._lock")
    with sched:
        with pool:
            with leaf:
                pass
    assert lockcheck.violations() == []


def test_inversion_raises_and_is_recorded(witness):
    sched = lockcheck.make_condition("Sched._cond")
    pool = lockcheck.make_condition("Pool._cond")
    with pool:
        with pytest.raises(LockOrderViolation, match="lock-order inversion"):
            with sched:
                pass
    assert len(lockcheck.violations()) == 1
    assert "Sched._cond" in lockcheck.violations()[0]


def test_warn_mode_records_without_raising(witness):
    lockcheck.configure(ranks=RANKS, mode="warn")
    pool = lockcheck.make_lock("Pool._cond")
    leaf = lockcheck.make_lock("Leaf._lock")
    with leaf:
        with pool:  # inversion: recorded, not raised
            pass
    assert len(lockcheck.violations()) == 1
    lockcheck.reset()
    assert lockcheck.violations() == []


def test_reentrant_rlock_is_not_a_violation(witness):
    r = lockcheck.make_rlock("Pool._cond")
    with r:
        with r:  # same object, reentrant: exempt by design
            pass
    assert lockcheck.violations() == []


def test_plain_lock_self_reacquire_reports_instead_of_hanging(witness):
    lk = lockcheck.make_lock("Leaf._lock")
    lk.acquire()
    try:
        with pytest.raises(LockOrderViolation, match="self-deadlock"):
            lk.acquire()  # blocking re-acquire: a guaranteed hang
    finally:
        lk.release()
    # a non-blocking probe is a legitimate pattern, not a violation
    lockcheck.reset()
    lk.acquire()
    assert lk.acquire(blocking=False) is False
    lk.release()
    assert lockcheck.violations() == []


def test_trylock_failure_does_not_corrupt_the_stack(witness):
    lk = lockcheck.make_lock("Leaf._lock")
    holder_ready = threading.Event()
    release = threading.Event()

    def holder():
        with lk:
            holder_ready.set()
            release.wait(timeout=5)

    t = threading.Thread(target=holder)
    t.start()
    holder_ready.wait(timeout=5)
    assert lk.acquire(blocking=False) is False  # contended probe fails
    release.set()
    t.join()
    with lk:  # and the probing thread's stack is still coherent
        pass
    assert lockcheck.violations() == []


# ----------------------------------------------------------------------
# Condition integration
# ----------------------------------------------------------------------


def test_condition_wait_releases_and_reclaims(witness):
    cond = lockcheck.make_condition("Pool._cond")
    sched = lockcheck.make_lock("Sched._cond")
    with cond:
        cond.wait(timeout=0.05)  # times out; entries must be re-pushed
        with pytest.raises(LockOrderViolation):
            sched.acquire()  # rank 20 under rank 40: still checked
    sched.acquire()  # after the with: stack drained, clean acquire
    sched.release()


def test_condition_wait_notify_across_threads(witness):
    cond = lockcheck.make_condition("Pool._cond")
    woke = threading.Event()

    def waiter():
        with cond:
            cond.wait(timeout=5)
            woke.set()

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cond:
        cond.notify_all()
    t.join(timeout=5)
    assert woke.is_set()
    assert lockcheck.violations() == []


def test_waiter_releases_the_lock_for_other_threads(witness):
    """The faithful-release property: while one thread WAITS on the
    witnessed condition, another thread must be able to take it (a witness
    that pinned the entry would turn every wait into a false inversion for
    the notifier)."""
    cond = lockcheck.make_condition("Pool._cond")
    entered = threading.Event()
    results = []

    def waiter():
        with cond:
            entered.set()
            results.append(cond.wait(timeout=5))

    t = threading.Thread(target=waiter)
    t.start()
    entered.wait(timeout=5)
    deadline = time.monotonic() + 5
    acquired = False
    while time.monotonic() < deadline and not acquired:
        with cond:
            cond.notify_all()
            acquired = True
    t.join(timeout=5)
    assert acquired and results == [True]
    assert lockcheck.violations() == []


# ----------------------------------------------------------------------
# The discriminating seeded inversion, on the REAL rank table
# ----------------------------------------------------------------------


def test_seeded_inversion_on_real_ranks_fires_and_shipped_order_passes():
    """Construct two real package locks (FaultPlan rank 70, FlightRecorder
    rank 85 from pyproject): the shipped ascending order runs clean; the
    deliberately inverted order is caught. A witness that cannot fail this
    way proves nothing when the chaos smoke runs clean."""
    lockcheck.configure(mode="raise")
    lockcheck.reset()
    try:
        from distributed_llama_tpu.engine import faults
        from distributed_llama_tpu.telemetry import flight

        plan = faults.FaultPlan(rules=[])
        rec = flight.FlightRecorder(capacity=4)
        with plan._lock:  # rank 70 -> 85: the shipped order
            with rec._lock:
                pass
        assert lockcheck.violations() == []
        with rec._lock:  # seeded inversion: 85 held, 70 acquired
            with pytest.raises(LockOrderViolation):
                with plan._lock:
                    pass
        assert len(lockcheck.violations()) == 1
    finally:
        lockcheck.configure()
        lockcheck.reset()


# ----------------------------------------------------------------------
# The chaos smoke: a replica kill storm runs clean under the witness
# ----------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.slow  # runs in CI's dedicated DLT_LOCK_CHECK=1 step, which
# invokes this file without the tier-1 `-m 'not slow'` filter
def test_replica_kill_storm_runs_clean_under_witness(tmp_path):
    """The acceptance smoke: the full failover machinery — crash, victim
    replay, supervisor restart — crosses every dynamic lock edge the AST
    cannot see (scheduler health hooks into the pool, the restart thread,
    admission resize), all under witnessed locks. Warn mode so a violation
    surfaces as a readable ledger assert instead of killing a server
    thread mid-flight."""
    from distributed_llama_tpu.engine import faults

    from tests.test_faults import post_raw, serve_state
    from tests.test_fair_sched import SseStream
    from tests.test_replicas import _SLOW, make_replica_state

    lockcheck.configure(mode="warn")  # ranks: the real pyproject table
    lockcheck.reset()
    faults.clear()
    try:
        faults.install(faults.parse(
            f"replica.crash:kind=raise,row=0,after=16,count=1;{_SLOW}"
        ))
        state = make_replica_state(tmp_path, "witness", replicas=2, parallel=2)
        url, server = serve_state(state)
        try:
            body = {"messages": [{"role": "user",
                                  "content": "tell me a very long story"}],
                    "max_tokens": 96}
            streams = [SseStream(url, dict(body)) for _ in range(4)]
            texts = [s.read_first_delta() + s.read_rest() for s in streams]
            assert all(s.error_type is None for s in streams)
            assert all(texts)
            pool = state.pool
            assert pool.failovers_total == 1
            assert pool.wait_state(0, "healthy", timeout_s=60)
        finally:
            server.shutdown()
            state.pool.close()
        assert lockcheck.violations() == [], lockcheck.violations()
    finally:
        faults.clear()
        lockcheck.configure()
        lockcheck.reset()


# ----------------------------------------------------------------------
# A WAIT under a lock (ISSUE 38): the spill download sat under
# BatchScheduler._cond behind the PrefixCache's page_fetch callback, where
# the lexical LCK-002 could not see it; a trace found it. Both halves now
# have a test: the witness sees a blocking call behind any callback, and
# the static rule sees the eviction's two entry points by name.
# ----------------------------------------------------------------------


def test_note_blocking_reports_a_wait_under_a_witnessed_lock(witness):
    cond = lockcheck.make_condition("Sched._cond")
    lockcheck.note_blocking("device_get")  # nothing held: fine
    assert lockcheck.violations() == []
    with cond:
        with pytest.raises(LockOrderViolation, match="blocking call `device_get`"):
            lockcheck.note_blocking("device_get")
    assert len(lockcheck.violations()) == 1
    lockcheck.configure(mode="off")
    with cond:
        lockcheck.note_blocking("device_get")  # zero-cost and silent when off


def _cache_under(fetch, land):
    import numpy as np

    from distributed_llama_tpu.engine.prefix_cache import PrefixCache
    from distributed_llama_tpu.engine.spill import HostArena

    arena = HostArena(1 << 20)
    cache = PrefixCache(2, 4, page_bytes=12, spill=arena, page_fetch=fetch, page_land=land)
    return cache, arena, (lambda base: np.arange(base, base + 8))


def _land(handles):
    import numpy as np

    lockcheck.note_blocking("page_land")  # what BatchScheduler._land_pages says
    return [[np.full(3, h, np.float32)] for h in handles]


def test_a_blocking_page_fetch_under_the_scheduler_lock_is_reported(witness):
    """The fault as it was: the eviction hook fetches the bytes itself, so
    the wait happens under the scheduler's lock, behind the callback."""
    cond = lockcheck.make_condition("Sched._cond")
    cache, arena, prompt = _cache_under(lambda pids: _land(pids), _land)
    with cond:
        cache.publish(prompt(1), 8, [])  # fills the pool: no eviction yet
        assert lockcheck.violations() == []
        # the evictor treats a failed spill as a page that vanishes, so the
        # violation does not surface as a raise: the ledger has it
        cache.publish(prompt(101), 8, [])
    assert any(
        "`page_land` while `Sched._cond`" in v for v in lockcheck.violations()
    ), lockcheck.violations()
    arena.close()


def test_the_shipped_eviction_waits_on_the_spiller_thread_only(witness):
    """The shipped split: slices enqueued under the lock, the blocking land
    on the arena's spiller thread, which holds no lock: a clean ledger, and
    the pages land."""
    lockcheck.configure(ranks=RANKS, mode="warn")  # a spiller's violation must not die silently
    cond = lockcheck.make_condition("Sched._cond")
    cache, arena, prompt = _cache_under(lambda pids: list(pids), _land)
    with cond:
        cache.publish(prompt(1), 8, [])
        cache.publish(prompt(101), 8, [])  # evicts both pages
        assert arena.pending_pages() + arena.depth() >= 2
    assert arena.flush(10)
    assert arena.depth(0) == 2 and lockcheck.violations() == []
    arena.close()


def _lck002(tmp_path, source):
    import textwrap

    from distributed_llama_tpu.analysis import all_rules, analyze
    from distributed_llama_tpu.analysis.config import load_config

    cfg = load_config(start=os.path.dirname(os.path.abspath(lockcheck.__file__)))
    cfg.baseline = ""
    f = tmp_path / "sched.py"
    f.write_text(textwrap.dedent(source))
    findings, _ = analyze([str(f)], cfg, rules=all_rules({"LCK-002"}))
    return [x.format() for x in findings]


def test_the_lint_names_the_evictions_fetch_entry_points(tmp_path):
    """Under the repo's own config: the blocking half called under the lock
    is a finding, a wait inside the enqueue-only half (a ``_locked`` name:
    its body is a lock-held region) is a finding, and the shipped shape is
    clean."""
    bad = _lck002(tmp_path, """
        import jax

        class Sched:
            def _publish_row(self, pids):
                with self._cond:
                    return self._land_pages(self._slice_pages_locked(pids))

            def _slice_pages_locked(self, pids):
                return jax.device_get([self.pool[p] for p in pids])

            def _match(self, tokens):
                with self._cond:
                    self.prefix.await_pending(tokens)
        """)
    assert len(bad) == 3, bad
    assert any("_land_pages" in x for x in bad) and any("jax.device_get" in x for x in bad)
    assert any("await_pending" in x for x in bad)
    good = _lck002(tmp_path, """
        import jax

        class Sched:
            def _publish_row(self, pids):
                with self._cond:
                    self.arena.put_pending(self._slice_pages_locked(pids), self._land_pages)

            def _slice_pages_locked(self, pids):
                return [self.pool[p] for p in pids]

            @staticmethod
            def _land_pages(handles):
                return jax.device_get(handles)

            def _match(self, tokens):
                self.prefix.await_pending(tokens)
                with self._cond:
                    return self.prefix.match(tokens)
        """)
    assert good == []
