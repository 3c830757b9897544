"""Global prefix-cache tier (ISSUE 11): host-RAM/disk spill below the HBM
pool and cross-replica sharing through the shared radix index.

Four layers, mirroring the subsystem:

* :class:`HostArena` / :class:`DiskTier` units — byte-verbatim round
  trips, LRU budgets, disk demotion, CRC corruption detection, per-owner
  drops (numpy only, deterministic).
* :class:`SharedPrefixIndex` units — contiguous per-owner chain matching,
  withdraw, and the atomic dead-replica drop.
* Scheduler-level spill→reload — the acceptance criteria: a stream served
  through a host-reloaded prefix is BYTE-IDENTICAL to the same request
  served cold (bf16, f32 AND i8; for i8 the page's data and scales round
  trip verbatim), the pinned-pages-never-in-arena invariant, and the
  ``engine.spill`` chaos contract (a failed or corrupt reload falls back
  to a cold prefill — stale KV is never served).
* Pool-level routing — placement follows the shared index to the owning
  replica (counted as a shared hit), cross-replica arena reloads, and a
  replica death dropping its chains from index and arena with no
  dangling routing.
* The eviction's way to the host (ISSUE 38): a publish slices its victims
  under the scheduler's lock and returns; the arena's spiller thread
  fetches, checksums and lands them. PENDING is a state: never served,
  never lost track of, and the arena ends as sequential puts leave it.
"""

import os
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.engine import InferenceEngine, faults
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.engine.prefix_cache import PrefixCache, SharedPrefixIndex
from distributed_llama_tpu.engine.spill import DiskTier, HostArena, SpillCorrupt
from distributed_llama_tpu.server import replicas as reps

from tests.model_utils import random_tensors, tiny_spec, write_model_file
from tests.test_replicas import fake_pool

PAGE = 4
PROMPT = [1, 5, 9, 2, 7, 3, 11, 4, 6, 8]  # 10 tokens = 2 full pages + 2


@pytest.fixture(autouse=True)
def _clear_fault_plan():
    faults.clear()
    yield
    faults.clear()


def build_engine(tmp_path, name="model.m", seed=0, seq_len=96, cache_dtype=None):
    spec = tiny_spec(seq_len=seq_len)
    path = str(tmp_path / name)
    write_model_file(path, spec, random_tensors(spec, seed=seed))
    return InferenceEngine(path, dtype=jnp.float32, cache_dtype=cache_dtype)


def build_sched(engine, kv_pages=6, spill_mb=32, arena=None, **kw):
    return BatchScheduler(
        engine, n_rows=1, chunk=4, prefix_cache=True, kv_pages=kv_pages,
        page_size=PAGE,
        host_spill_bytes=0 if arena is not None else spill_mb << 20,
        spill_arena=arena, **kw,
    )


def decode_tokens(stream, prompt, n=6, seed=3):
    stream.reset()
    first = stream.prefill_device(prompt, 0.0, 0.9, seed)
    got = []

    def on_token(prev, tok):
        got.append(tok)
        return len(got) < n

    stream.stream_decode(first, on_token, 0.0, 0.9, seed=seed,
                         limit=stream.pos + n, first_prev=prompt[-1])
    return got


def download_page(sched, pid):
    """One pool page's host byte arrays, as the eviction moves them: sliced
    under the scheduler's lock, landed off it."""
    with sched._cond:
        handles = sched._slice_pages_locked([pid])
    return sched._land_pages(handles)[0]


def churn(stream, base, rounds=3):
    """Publish ``rounds`` fresh 2-page prefixes: evicts (and spills)
    everything unpinned in a 6-page pool."""
    for k in range(rounds):
        decode_tokens(stream, [base + 10 * k + j for j in range(10)])
    stream.reset()


def arrays_like(seed=0, n=3, ro=False):
    rng = np.random.RandomState(seed)
    out = [rng.randn(2, PAGE, 3).astype(np.float32) for _ in range(n)]
    if ro:
        for a in out:
            a.setflags(write=False)  # np.asarray(jax_array) views are RO
    return out


# ----------------------------------------------------------------------
# HostArena / DiskTier units
# ----------------------------------------------------------------------


class TestHostArena:
    def test_put_take_roundtrip_verbatim(self):
        arena = HostArena(1 << 20)
        arrays = arrays_like(ro=True)
        arena.put(0, (1, 2, 3, 4), arrays)
        assert arena.depth() == 1 and arena.depth(0) == 1
        got = arena.take(0, (1, 2, 3, 4))
        for a, b in zip(got, arrays):
            np.testing.assert_array_equal(a, b)
        # take MOVES: the entry is gone (the exclusivity invariant)
        assert arena.take(0, (1, 2, 3, 4)) is None
        assert arena.depth() == 0 and arena.reloaded_total == 1

    def test_peek_shared_copies_and_leaves_the_owner_entry(self):
        arena = HostArena(1 << 20)
        arrays = arrays_like()
        arena.put(0, (1, 2, 3, 4), arrays)
        # replica 1 reloads replica 0's spill by COPY
        got = arena.peek_shared((1, 2, 3, 4), exclude_owner=1)
        for a, b in zip(got, arrays):
            np.testing.assert_array_equal(a, b)
        assert arena.depth(0) == 1  # still there for the next replica
        # the owner itself never peeks its own entry through the shared path
        assert arena.peek_shared((1, 2, 3, 4), exclude_owner=0) is None

    def test_budget_lru_eviction_counts_drops(self):
        arrays = arrays_like()
        nbytes = sum(a.nbytes for a in arrays)
        arena = HostArena(2 * nbytes)
        arena.put(0, (1,), arrays_like(1))
        arena.put(0, (2,), arrays_like(2))
        arena.take(0, (1,))  # touch → (2,) becomes LRU... but take removed (1,)
        arena.put(0, (1,), arrays_like(1))
        arena.put(0, (3,), arrays_like(3))  # over budget: (2,) is LRU
        assert arena.dropped_total == 1
        assert arena.take(0, (2,)) is None
        assert arena.take(0, (1,)) is not None
        assert arena.take(0, (3,)) is not None

    def test_crc_mismatch_raises_and_drops(self):
        arena = HostArena(1 << 20)
        arena.put(0, (9, 9, 9, 9), arrays_like(ro=True))
        arena.corrupt((9, 9, 9, 9))
        with pytest.raises(SpillCorrupt):
            arena.take(0, (9, 9, 9, 9))
        assert arena.corrupt_total == 1
        assert arena.take(0, (9, 9, 9, 9)) is None  # dropped, not retried

    def test_drop_owner_removes_only_that_owner(self):
        arena = HostArena(1 << 20)
        arena.put(0, (1, 2), arrays_like(1))
        arena.put(1, (1, 2), arrays_like(1))
        arena.put(1, (3, 4), arrays_like(2))
        arena.drop_owner(1)
        assert arena.depth(1) == 0
        assert arena.depth(0) == 1
        assert arena.peek_shared((1, 2), exclude_owner=1) is not None

    def test_disk_demotion_and_reload(self, tmp_path):
        arrays = arrays_like()
        nbytes = sum(a.nbytes for a in arrays)
        arena = HostArena(
            nbytes,  # host holds exactly one entry
            disk_path=str(tmp_path / "spill.bin"),
            disk_budget_bytes=8 * nbytes,
        )
        arena.put(0, (1,), arrays_like(1))
        arena.put(0, (2,), arrays_like(2))  # (1,) demotes to disk
        assert arena.dropped_total == 0
        assert len(arena.disk) == 1
        assert arena.depth(0) == 2  # resident = host + disk
        got = arena.take(0, (1,))  # reload FROM DISK
        for a, b in zip(got, arrays_like(1)):
            np.testing.assert_array_equal(a, b)
        assert arena.take(0, (1,)) is None  # removed from disk too

    def test_disk_corruption_detected(self, tmp_path):
        arrays = arrays_like()
        nbytes = sum(a.nbytes for a in arrays)
        arena = HostArena(
            nbytes, disk_path=str(tmp_path / "spill.bin"),
            disk_budget_bytes=8 * nbytes,
        )
        arena.put(0, (1,), arrays_like(1))
        arena.put(0, (2,), arrays_like(2))  # (1,) on disk
        arena.corrupt((1,))  # flips the disk byte
        with pytest.raises(SpillCorrupt):
            arena.take(0, (1,))
        assert arena.take(0, (1,)) is None

    def test_disk_lru_overflow_counts_drops(self, tmp_path):
        arrays = arrays_like()
        nbytes = sum(a.nbytes for a in arrays)
        arena = HostArena(
            nbytes, disk_path=str(tmp_path / "spill.bin"),
            disk_budget_bytes=nbytes,  # one disk slot
        )
        arena.put(0, (1,), arrays_like(1))
        arena.put(0, (2,), arrays_like(2))  # (1,) → disk
        arena.put(0, (3,), arrays_like(3))  # (2,) → disk, (1,) dropped
        assert arena.dropped_total == 1
        assert arena.take(0, (1,)) is None
        assert arena.take(0, (2,)) is not None


class TestDiskTier:
    def test_roundtrip_and_slot_reuse(self, tmp_path):
        arrays = arrays_like()
        nbytes = sum(a.nbytes for a in arrays)
        disk = DiskTier(str(tmp_path / "t2.bin"), 2 * nbytes)
        import zlib

        crc = 0
        for a in arrays:
            crc = zlib.crc32(np.ascontiguousarray(a).tobytes(), crc)
        assert disk.put((0, (1,)), arrays, crc)
        got = disk.take((0, (1,)))
        for a, b in zip(got, arrays):
            np.testing.assert_array_equal(a, b)
        assert len(disk) == 0
        # the freed slot is reusable
        assert disk.put((0, (2,)), arrays, crc)
        assert disk.put((0, (3,)), arrays, crc)

    def test_template_mismatch_rejected(self, tmp_path):
        arrays = arrays_like()
        nbytes = sum(a.nbytes for a in arrays)
        disk = DiskTier(str(tmp_path / "t.bin"), 4 * nbytes)
        assert disk.put((0, (1,)), arrays, 0)
        other = [np.zeros((5,), np.int8)]
        assert not disk.put((0, (2,)), other, 0)


# ----------------------------------------------------------------------
# SharedPrefixIndex units
# ----------------------------------------------------------------------


class TestSharedPrefixIndex:
    def test_match_longest_contiguous_chain_per_owner(self):
        idx = SharedPrefixIndex(PAGE)
        t = list(range(1, 13))  # 12 tokens = 2 full matchable blocks of 4
        idx.publish(0, tuple(t[:4]))
        idx.publish(1, tuple(t[:4]))
        idx.publish(1, tuple(t[:8]))
        # 12-token prompt: max_blocks = (12-1)//4 = 2
        assert idx.match(t) == {0: 1, 1: 2}
        # an owner missing an INNER block never re-enters deeper
        idx.withdraw(1, tuple(t[:4]))
        assert idx.match(t) == {0: 1}

    def test_match_strictly_shorter_than_prompt(self):
        idx = SharedPrefixIndex(PAGE)
        t = list(range(1, 9))  # 8 tokens: only block 1 matchable
        idx.publish(0, tuple(t[:4]))
        idx.publish(0, tuple(t[:8]))
        assert idx.match(t) == {0: 1}  # the last token always prefills

    def test_drop_owner_is_total(self):
        idx = SharedPrefixIndex(PAGE)
        t = list(range(1, 13))
        idx.publish(0, tuple(t[:4]))
        idx.publish(1, tuple(t[:4]))
        idx.publish(1, tuple(t[:8]))
        idx.drop_owner(1)
        assert idx.match(t) == {0: 1}
        assert idx.owners(tuple(t[:8])) == set()


# ----------------------------------------------------------------------
# Scheduler-level spill → reload (the acceptance criteria)
# ----------------------------------------------------------------------


class TestSpillReload:
    def _parity(self, tmp_path, cache_dtype):
        """Cold stream == host-reloaded stream, and the reload actually
        happened (not a silent cold re-prefill)."""
        engine = build_engine(tmp_path, cache_dtype=cache_dtype)
        sched = build_sched(engine)
        s = sched.new_stream()
        cold = decode_tokens(s, PROMPT)
        prefix = sched._prefix
        churn(s, 100)
        assert prefix.spill.spilled_total >= 2, "eviction did not spill"
        assert prefix.walk(PROMPT) == []  # truly evicted from the device
        rel0 = prefix.spill.reloaded_total
        warm = decode_tokens(s, PROMPT)
        assert warm == cold, "host-reloaded stream diverged from cold"
        assert prefix.spill.reloaded_total - rel0 >= 2, "no pages reloaded"
        assert len(prefix.walk(PROMPT)) == 2  # the reload IS a device hit now
        s.reset()
        sched.check_prefix()

    def test_reload_parity_f32(self, tmp_path):
        self._parity(tmp_path, None)

    def test_reload_parity_bf16(self, tmp_path):
        self._parity(tmp_path, jnp.bfloat16)

    def test_reload_parity_i8(self, tmp_path):
        self._parity(tmp_path, "i8")

    def test_every_evict_and_rerequest_round_reloads_the_whole_chain(self, tmp_path):
        """The spill tier keeps serving: a chain that was reloaded, evicted
        again and asked for again comes back from the host every time, and
        ``dllama_prefix_spill_reloads_total`` counts each page of it."""
        from distributed_llama_tpu import telemetry

        telemetry.reset()
        telemetry.enable()
        try:
            sched = build_sched(build_engine(tmp_path))
            s = sched.new_stream()
            cold = decode_tokens(s, PROMPT)
            chain = len(sched._prefix.walk(PROMPT))
            reloads = telemetry.REGISTRY.counter("dllama_prefix_spill_reloads_total")
            for r in range(3):
                churn(s, 100 + 40 * r)
                assert sched._prefix.walk(PROMPT) == []  # off the device again
                assert decode_tokens(s, PROMPT) == cold
                assert reloads.value == (r + 1) * chain
            s.reset()
            sched.check_prefix()
        finally:
            telemetry.disable()
            telemetry.reset()

    def test_i8_spill_reload_byte_parity_data_and_scales(self, tmp_path):
        """The spilled entry's int8 data AND f32 scales round-trip
        verbatim: bytes downloaded from the pool before eviction ==
        bytes resident in the pool after the reload."""
        engine = build_engine(tmp_path, cache_dtype="i8")
        sched = build_sched(engine)
        s = sched.new_stream()
        decode_tokens(s, PROMPT)
        s.reset()
        prefix = sched._prefix
        nodes = prefix.walk(PROMPT)
        assert len(nodes) == 2
        before = [
            [a.copy() for a in download_page(sched, nd.page_id)]
            for nd in nodes
        ]
        # every flat entry must carry scales arrays (2 per half)
        from distributed_llama_tpu.ops import kv_cache as kvc

        per_layer = 2 * kvc.pool_page_arrays_per_half(sched._pool[0][0])
        assert len(before[0]) == per_layer * len(sched._pool)
        churn(s, 200)
        assert prefix.walk(PROMPT) == []
        decode_tokens(s, PROMPT)  # reload
        s.reset()
        nodes = prefix.walk(PROMPT)
        assert len(nodes) == 2
        for want, nd in zip(before, nodes):
            got = download_page(sched, nd.page_id)
            assert len(got) == len(want)
            for a, b in zip(got, want):
                np.testing.assert_array_equal(
                    np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8)
                )
        sched.check_prefix()

    def test_pinned_pages_never_resident_in_arena(self, tmp_path):
        """check()'s spill-exclusivity extension: a pinned chain with a
        same-owner arena entry is the double-residency bug class."""
        engine = build_engine(tmp_path)
        sched = build_sched(engine)
        s = sched.new_stream()
        decode_tokens(s, PROMPT)  # cold: publishes 2 pages
        decode_tokens(s, PROMPT)  # hit: the row pins the chain (row lifetime)
        prefix = sched._prefix
        sched.check_prefix()
        # engineer the violation: an arena entry for the pinned chain
        nodes = prefix.walk(PROMPT)
        assert nodes and nodes[0].refs > 0  # the live row pins it
        key = prefix.chain_key(nodes[0])
        prefix.spill.put(prefix.owner_id, key, [np.zeros(3, np.float32)])
        with pytest.raises(AssertionError, match="spill arena"):
            sched.check_prefix()
        prefix.spill.drop(prefix.owner_id, key)
        sched.check_prefix()
        s.reset()

    def test_reload_fault_raise_falls_back_cold(self, tmp_path):
        """engine.spill kind=raise: the reload aborts, the request
        prefills cold and streams bit-identically — a spill-tier failure
        degrades, never corrupts or kills."""
        engine = build_engine(tmp_path)
        plan = faults.install(
            faults.parse("engine.spill:kind=raise,count=-1", seed=0)
        )
        sched = build_sched(engine)
        sched._faults = plan
        s = sched.new_stream()
        cold = decode_tokens(s, PROMPT)
        prefix = sched._prefix
        churn(s, 100)
        rel0 = prefix.spill.reloaded_total
        again = decode_tokens(s, PROMPT)
        assert again == cold
        assert prefix.spill.reloaded_total == rel0, "raise must abort reload"
        # (the injected raise fires BEFORE the entry is taken, so the
        # spilled bytes survive the aborted reload; the cold prefill's
        # publish then supersedes them — check_prefix asserts the
        # exclusivity either way)
        s.reset()
        sched.check_prefix()  # pins released, tree coherent

    def test_reload_corrupt_crc_gate_falls_back_cold(self, tmp_path):
        """engine.spill kind=corrupt flips the arena entry's bytes in
        place (a silent host-RAM bit flip). The CRC verification must
        catch it, drop the entry and prefill cold — the stream stays
        bit-identical, stale KV is never uploaded."""
        engine = build_engine(tmp_path)
        plan = faults.install(
            faults.parse("engine.spill:kind=corrupt,count=-1", seed=0)
        )
        sched = build_sched(engine)
        sched._faults = plan
        s = sched.new_stream()
        cold = decode_tokens(s, PROMPT)
        prefix = sched._prefix
        churn(s, 100)
        rel0 = prefix.spill.reloaded_total
        drops0 = prefix.spill.corrupt_total
        again = decode_tokens(s, PROMPT)
        assert again == cold, "corrupt reload must not change the stream"
        assert prefix.spill.corrupt_total > drops0, "CRC gate never fired"
        assert prefix.spill.reloaded_total == rel0, "corrupt bytes uploaded"
        s.reset()
        sched.check_prefix()

    def test_disk_tier_reload_through_scheduler(self, tmp_path):
        """Host budget of ~one entry + a disk tier: churned pages demote
        to the mmap'd file and still reload bit-identically."""
        engine = build_engine(tmp_path)
        probe = build_sched(engine, kv_pages=6)
        ps = probe.new_stream()
        decode_tokens(ps, PROMPT)
        ps.reset()
        churn(ps, 300, rounds=2)
        assert probe._prefix.spill.flush(10)
        entry_bytes = probe._prefix.spill.resident_bytes // max(
            probe._prefix.spill.depth(), 1
        )
        arena = HostArena(
            int(entry_bytes * 1.5),
            disk_path=str(tmp_path / "disk" / "spill.bin"),
            disk_budget_bytes=64 << 20,
        )
        sched = build_sched(engine, arena=arena)
        s = sched.new_stream()
        cold = decode_tokens(s, PROMPT)
        churn(s, 100)
        assert arena.flush(10)
        assert len(arena.disk) >= 1, "nothing demoted to the disk tier"
        warm = decode_tokens(s, PROMPT)
        assert warm == cold
        s.reset()
        sched.check_prefix()


# ----------------------------------------------------------------------
# Cross-replica sharing: two schedulers, one arena + one index
# ----------------------------------------------------------------------


class TestCrossReplica:
    def test_peer_reloads_a_spilled_chain_by_copy(self, tmp_path):
        """Replica 0 prefills + spills the head; replica 1 reloads it
        from the SHARED arena without ever prefilling it — and 0's entry
        survives for the next reader (replication, not theft)."""
        engine = build_engine(tmp_path)
        idx = SharedPrefixIndex(PAGE)
        arena = HostArena(32 << 20)
        sched0 = BatchScheduler(
            engine, n_rows=1, chunk=4, prefix_cache=True, kv_pages=6,
            page_size=PAGE, spill_arena=arena, shared_index=idx,
            replica_id=0,
        )
        sched1 = BatchScheduler(
            engine, n_rows=1, chunk=4, prefix_cache=True, kv_pages=6,
            page_size=PAGE, spill_arena=arena, shared_index=idx,
            replica_id=1,
        )
        s0, s1 = sched0.new_stream(), sched1.new_stream()
        cold = decode_tokens(s0, PROMPT)
        assert idx.match(PROMPT) == {0: 2}
        churn(s0, 100)  # replica 0 evicts + spills the head
        assert arena.depth(0) >= 2
        assert idx.match(PROMPT) == {}  # evicted chains left the index
        rel0 = arena.reloaded_total
        peer = decode_tokens(s1, PROMPT)  # replica 1: reload by COPY
        assert peer == cold
        assert arena.reloaded_total - rel0 >= 2
        assert arena.depth(0) >= 2, "peer reload must not steal 0's spill"
        assert idx.match(PROMPT) == {1: 2}  # replica 1 now owns it
        s1.reset()
        sched0.check_prefix()
        sched1.check_prefix()

    def test_own_reload_moves_the_entry_out(self, tmp_path):
        engine = build_engine(tmp_path)
        arena = HostArena(32 << 20)
        sched = build_sched(engine, arena=arena)
        s = sched.new_stream()
        decode_tokens(s, PROMPT)
        churn(s, 100)
        chains = (tuple(PROMPT[:4]), tuple(PROMPT[:8]))
        assert all(arena.has(0, c) for c in chains)
        decode_tokens(s, PROMPT)  # own reload = MOVE (exclusivity)
        # the reload may have spilled OTHER chains to make room, but the
        # reloaded chains themselves must have left the arena
        assert not any(arena.has(0, c) for c in chains)
        s.reset()
        sched.check_prefix()


# ----------------------------------------------------------------------
# Pool-level routing (fake replicas; the real-serving path rides the
# loadgen spill smoke in CI)
# ----------------------------------------------------------------------


class TestSharedRouting:
    def route_tokens(self):
        return list(range(1, 13))  # 12 tokens = 2 matchable PAGE-blocks

    def test_place_routes_to_the_chain_owner(self):
        idx = SharedPrefixIndex(PAGE)
        pool = fake_pool(n_replicas=2, shared_index=idx)
        t = self.route_tokens()
        idx.publish(1, tuple(t[:4]))
        slot = pool.place([], route_tokens=t)
        assert slot in pool.replicas[1].slots
        assert pool.shared_hits_total == 1
        # no ownership info → least-loaded (replica 0 is now emptier)
        slot2 = pool.place([], route_tokens=list(range(50, 62)))
        assert slot2 in pool.replicas[0].slots
        assert pool.shared_hits_total == 1  # not a shared hit

    def test_chat_affinity_still_beats_shared_routing(self):
        from tests.test_replicas import FakeCache

        idx = SharedPrefixIndex(PAGE)
        pool = fake_pool(n_replicas=2, shared_index=idx)
        t = self.route_tokens()
        idx.publish(1, tuple(t[:4]))
        # a continuing conversation's slot on replica 0 wins regardless
        pool.replicas[0].slots[0].cache = FakeCache(match=2, items=["x"])
        slot = pool.place([{"role": "user", "content": "x"}], route_tokens=t)
        assert slot is pool.replicas[0].slots[0]
        # and an affinity-decided placement is never a "shared hit", even
        # when the chosen replica ALSO owns chain depth: a conversation
        # resuming its own slot is what the private design could do too
        idx2 = SharedPrefixIndex(PAGE)
        pool2 = fake_pool(n_replicas=2, shared_index=idx2)
        idx2.publish(0, tuple(t[:4]))
        from tests.test_replicas import FakeCache as FC

        pool2.replicas[0].slots[0].cache = FC(match=2, items=["x"])
        got = pool2.place([{"role": "user", "content": "x"}], route_tokens=t)
        assert got is pool2.replicas[0].slots[0]
        assert pool2.shared_hits_total == 0

    def test_dead_replica_chains_leave_index_and_arena(self):
        idx = SharedPrefixIndex(PAGE)
        arena = HostArena(1 << 20)
        pool = fake_pool(
            n_replicas=2, shared_index=idx, spill_arena=arena,
        )
        t = self.route_tokens()
        idx.publish(1, tuple(t[:4]))
        arena.put(1, tuple(t[:4]), [np.zeros(4, np.float32)])
        pool._on_event(1, pool.replicas[1].generation, "lost", 0.0)
        assert pool.replicas[1].state == reps.DEAD
        # no dangling routing: the index forgot replica 1 atomically
        assert idx.match(t) == {}
        assert arena.depth(1) == 0
        slot = pool.place([], route_tokens=t)
        assert slot in pool.replicas[0].slots
        assert pool.shared_hits_total == 0

    def test_readyz_snapshot_carries_cache_occupancy(self, tmp_path):
        """The /readyz per-replica cache read: pages/pinned/spill_depth
        from a real scheduler."""
        engine = build_engine(tmp_path)
        sched = build_sched(engine)
        s = sched.new_stream()
        decode_tokens(s, PROMPT)
        churn(s, 100)
        rep = reps.Replica(0, engine, sched, [])
        pool = reps.ReplicaPool(lambda i: None, [rep], supervise=False)
        snap = pool.snapshot()[0]
        cache = snap["cache"]
        assert cache["pages"] == sched._prefix.pages_in_use()
        assert cache["pinned"] == sched._prefix.pinned_pages()
        assert cache["spill_depth"] >= 2
        s.reset()


# ----------------------------------------------------------------------
# The eviction's way to the host (ISSUE 38): pending pages, the spiller
# ----------------------------------------------------------------------


class HeldLand:
    """A ``land`` that waits on an Event before it hands the handles' bytes
    over: what a device that is still busy with a decode chunk looks like
    to the spiller thread."""

    def __init__(self, inner=lambda handles: [list(h) for h in handles]):
        self.inner = inner
        self.release = threading.Event()
        self.calls = 0

    def __call__(self, handles):
        self.calls += 1
        assert self.release.wait(30), "the test never released the fetch"
        return self.inner(handles)


def pages_of(kind, n, seed=0):
    """``n`` spill entries in the scheduler's flat layout for one layer:
    ``[k, v]``, or ``[k data, k scales, v data, v scales]`` for i8."""
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        if kind == "i8":
            page = []
            for _half in range(2):
                page += [rng.randint(-127, 128, (PAGE, 2, 8)).astype(np.int8),
                         rng.randn(PAGE, 2).astype(np.float32)]
        else:
            dt = jnp.bfloat16 if kind == "bf16" else np.float32
            page = [np.asarray(rng.randn(PAGE, 2, 8), dtype=dt) for _half in range(2)]
        out.append(page)
    return out


def arena_pair(tmp_path, nbytes, m, disk):
    def one(name):
        return HostArena(
            m * nbytes,
            disk_path=str(tmp_path / name / "spill.bin") if disk else None,
            disk_budget_bytes=3 * nbytes if disk else 0,
        )
    return one("ref"), one("new")


def ladder_state(arena, chains):
    """Which of ``chains`` the arena holds, and their bytes (read by COPY,
    in a fixed order, so that both arenas' clocks move alike)."""
    out = {}
    for chain in chains:
        got = arena.peek_shared(chain, exclude_owner=99)
        if got is not None:
            out[chain] = [np.asarray(a).view(np.uint8).tobytes() for a in got]
    return out


class TestPendingArena:
    @pytest.mark.parametrize("disk", [False, True], ids=["host_only", "disk_tier"])
    @pytest.mark.parametrize("k,m", [(5, 2), (2, 4), (7, 3)])
    @pytest.mark.parametrize("kind", ["f32", "bf16", "i8"])
    def test_a_batch_of_victims_ends_as_sequential_puts_leave_it(
        self, tmp_path, kind, k, m, disk
    ):
        """(b): k victims against a budget of m entries (two older entries
        already there): the pages fetched by the spiller leave exactly the
        keys, bytes, ``dropped_total`` and ``spilled_total`` that k
        sequential ``put``s leave, whether a victim past the budget is
        skipped (no disk tier) or demoted (disk tier)."""
        old = pages_of(kind, 2, seed=1)
        new = pages_of(kind, k, seed=2)
        nbytes = sum(a.nbytes for a in new[0])
        ref, arena = arena_pair(tmp_path, nbytes, m, disk)
        chains = [(100 + i,) for i in range(2)] + [(i,) for i in range(k)]
        for a in (ref, arena):
            for chain, page in zip(chains[:2], old):
                a.put(0, chain, page)
        for chain, page in zip(chains[2:], new):
            ref.put(0, chain, page)
        land = HeldLand()
        keep = arena.keeps(k, nbytes)
        assert keep == (k if disk else min(k, m))
        arena.put_pending(
            0, chains[2 + k - keep :], new[k - keep :], nbytes, land, skipped=k - keep
        )
        # budget, counters and displacement moved at once; nothing is readable
        assert arena.pending_pages() == keep and arena.resident_bytes <= m * nbytes
        assert arena.dropped_total == ref.dropped_total or disk
        assert all(arena.take(0, c) is None for c in chains[2 + k - keep :])
        arena.check()
        land.release.set()
        assert arena.flush(10) and arena.pending_pages() == 0
        arena.check()
        assert arena.skipped_total == k - keep
        assert ladder_state(arena, chains) == ladder_state(ref, chains)
        for name in ("dropped_total", "spilled_total", "resident_bytes"):
            assert getattr(arena, name) == getattr(ref, name), name
        assert arena.depth() == ref.depth()
        if disk:
            assert sorted(arena.disk.keys()) == sorted(ref.disk.keys())
        arena.close()

    def test_pending_is_never_served_and_a_peer_passes_it_over(self):
        arena = HostArena(1 << 20)
        land = HeldLand()
        page = pages_of("f32", 1)[0]
        arena.put_pending(0, [(1, 2)], [page], sum(a.nbytes for a in page), land)
        assert arena.has(0, (1, 2)) and arena.is_pending(0, (1, 2))
        assert arena.depth(0) == 1  # on the books, as a landed entry is
        assert arena.take(0, (1, 2)) is None  # the owner: a counted miss
        assert arena.peek_shared((1, 2), exclude_owner=1) is None  # a peer
        assert arena.pending_reloads == {"waited": 0, "cold": 2}
        assert arena.reloaded_total == 0
        land.release.set()
        assert arena.flush(10)
        got = arena.take(0, (1, 2))
        for a, b in zip(got, page):
            np.testing.assert_array_equal(a, b)
        arena.close()

    def test_a_reader_waits_off_the_lock_for_its_own_prefixes_only(self):
        arena = HostArena(1 << 20)
        land = HeldLand()
        page = pages_of("f32", 1)[0]
        arena.put_pending(0, [(1, 2, 3, 4)], [page], 1, land)
        # another prompt's reader does not wait; nor one the chain is not
        # strictly shorter than
        assert arena.wait_pending([9, 9, 9, 9, 9], timeout=5) is False
        assert arena.wait_pending([1, 2, 3, 4], timeout=5) is False
        threading.Timer(0.2, land.release.set).start()
        assert arena.wait_pending([1, 2, 3, 4, 5], timeout=10) is True
        assert arena.take(0, (1, 2, 3, 4)) is not None  # it landed meanwhile
        assert arena.pending_reloads == {"waited": 1, "cold": 0}
        arena.close()

    def test_a_failed_fetch_is_a_counted_drop(self, capsys):
        def land(handles):
            raise RuntimeError("device lost")

        arena = HostArena(1 << 20)
        arena.put(0, (7,), pages_of("f32", 1)[0])
        arena.put_pending(0, [(1,), (2,)], [None, None], 8, land)
        assert arena.flush(10)
        assert arena.dropped_total == 2 and arena.pending_pages() == 0
        assert not arena.has(0, (1,)) and not arena.has(0, (2,))
        assert arena.take(0, (7,)) is not None  # what had landed is untouched
        assert arena.resident_bytes == 0
        assert "fetch failed" in capsys.readouterr().out
        arena.check()
        arena.close()

    def test_drop_owner_and_close_leave_no_pending_entry_and_no_thread(self, tmp_path):
        """(e): a replica's death drops its pending pages with its landed
        ones, and their bytes are discarded when they arrive; close() lets
        go of what is pending, counted, and joins the spiller."""
        page = pages_of("f32", 1)[0]
        nbytes = sum(a.nbytes for a in page)
        arena = HostArena(
            nbytes, disk_path=str(tmp_path / "d" / "spill.bin"), disk_budget_bytes=8 * nbytes
        )
        land = HeldLand()
        # two pages on a budget of one: the first waits for the disk
        arena.put_pending(1, [(1,), (2,)], [page, page], nbytes, land)
        arena.put_pending(0, [(6,), (3,)], [page, page], nbytes, land)
        assert arena.pending_pages() == 4 and arena.depth(1) == 2
        arena.check()
        arena.drop_owner(1)
        assert arena.pending_pages() == 2 and arena.depth(1) == 0
        arena.check()
        land.release.set()
        assert arena.flush(10)
        # owner 0's (6,) was displaced while pending: it landed on the disk
        assert arena.depth(1) == 0 and arena.disk.keys() == [(0, (6,))]
        assert arena.take(0, (3,)) is not None and arena.take(0, (6,)) is not None
        held = HeldLand()
        arena.put_pending(0, [(4,)], [page], nbytes, held)
        dropped = arena.dropped_total
        closer = threading.Thread(target=arena.close)
        closer.start()
        held.release.set()
        closer.join(10)
        assert not closer.is_alive() and not arena._spiller.is_alive()
        assert arena.pending_pages() == 0 and not arena.has(0, (4,))
        assert arena.dropped_total == dropped + 1
        # closed: a later eviction's pages vanish, counted, and nothing hangs
        arena.put_pending(0, [(5,)], [page], nbytes, held)
        assert arena.dropped_total == dropped + 2 and not arena.has(0, (5,))
        arena.check()

    def test_stress_many_threads_one_arena(self):
        """More threads than cores on one arena with a short switch
        interval: evictors, readers, a dying replica. The books balance."""
        page = pages_of("f32", 1)[0]
        nbytes = sum(a.nbytes for a in page)
        arena = HostArena(6 * nbytes)
        seconds = 3.0
        errors = []

        def worker(owner):
            rng = np.random.RandomState(owner)
            end = time.monotonic() + seconds
            try:
                while time.monotonic() < end:
                    chain = (int(rng.randint(12)),)
                    op = rng.randint(6)
                    if op <= 1:
                        n = int(rng.randint(1, 4))
                        chains = [(int(rng.randint(12)),) for _ in range(n)]
                        arena.put_pending(
                            owner, chains, [page] * n, nbytes,
                            lambda hs: [list(h) for h in hs],
                        )
                    elif op == 2:
                        got = arena.take(owner, chain)
                        if got is not None:
                            np.testing.assert_array_equal(got[0], page[0])
                    elif op == 3:
                        arena.peek_shared(chain, exclude_owner=owner)
                    elif op == 4:
                        arena.drop(owner, chain)
                    else:
                        arena.drop_owner(owner)
            except Exception as e:  # surfaced below
                errors.append(e)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=worker, args=(i,)) for i in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(seconds + 30)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        assert not errors, errors
        assert arena.flush(30)
        arena.check()
        assert arena.pending_pages() == 0 and arena.resident_bytes <= 6 * nbytes
        arena.close()
        assert not arena._spiller.is_alive()


def one_by_one_victims(cache, k):
    """The evictor as it was before ISSUE 38, kept as the reference: the
    least-recently-used unreferenced leaf, k times over, each from a fresh
    walk; ties go to the walk's first."""
    taken = []
    for _ in range(k):
        victim = None
        for node in cache._walk():
            if node.children or node.refs > 0:
                continue
            if victim is None or node.last_use < victim.last_use:
                victim = node
        if victim is None:
            break
        del victim.parent.children[victim.key]
        taken.append(victim)
    return taken


def with_tree_restored(cache, fn):
    """``fn()`` on a tree that is put back exactly as it was, the children's
    order included (the walk's order breaks ties)."""
    saved = [(n, dict(n.children)) for n in [cache.root, *cache._walk()]]
    try:
        return fn()
    finally:
        for node, children in saved:
            node.children = children


class TestVictimsTogether:
    @pytest.mark.parametrize("seed", range(6))
    def test_one_walk_yields_the_order_of_one_by_one_eviction(self, seed):
        """Random trees with shared heads, pins and EQUAL clocks: an
        interior node becomes a leaf when its last child goes, and equal
        ``last_use`` goes by the walk's order."""
        rng = np.random.RandomState(seed)
        cache = PrefixCache(64, PAGE)
        heads = [[int(t) for t in rng.randint(1, 5, 4 * PAGE)] for _ in range(3)]
        for _ in range(14):
            head = heads[rng.randint(3)][: PAGE * int(rng.randint(1, 5))]
            tokens = head + [int(t) for t in rng.randint(5, 50, PAGE * int(rng.randint(0, 4)))]
            cache.publish(np.asarray(tokens), len(tokens), [])
        nodes = list(cache._walk())
        for node in nodes:
            node.last_use = int(rng.randint(0, 6))  # many ties
        for node in (nodes[i] for i in rng.choice(len(nodes), 4, replace=False)):
            cache._ref(node)
        for k in (1, 3, len(nodes)):
            want = with_tree_restored(cache, lambda: one_by_one_victims(cache, k))
            got = cache._pick_victims(k)
            assert [id(n) for n in got] == [id(n) for n in want], k
        cache.check()

    def test_a_publish_takes_its_victims_in_one_batch_and_hands_the_pages_on_in_order(self):
        fetched, landed = [], HeldLand(lambda hs: [[np.full(3, h, np.float32)] for h in hs])
        arena = HostArena(2 * 12)  # two entries of 12 bytes

        def fetch(pids):
            fetched.append(list(pids))
            return list(pids)

        cache = PrefixCache(4, PAGE, page_bytes=12, spill=arena, page_fetch=fetch,
                            page_land=landed)
        a = list(range(1, 1 + 4 * PAGE))
        ids_a, _ = cache.publish(np.asarray(a), len(a), [])
        assert sorted(ids_a) == [0, 1, 2, 3] and fetched == []
        b = list(range(101, 101 + 3 * PAGE))
        ids_b, blocks_b = cache.publish(np.asarray(b), len(b), [])
        # three victims, deepest first; the arena keeps two, so ONE is not even sliced
        assert fetched == [[ids_a[2], ids_a[1]]] and blocks_b == [0, 1, 2]
        assert ids_b == [ids_a[3], ids_a[2], ids_a[1]]  # freed in eviction order
        assert arena.skipped_total == 1 and arena.dropped_total == 1
        assert arena.pending_pages() == 2 and arena.spilled_total == 3
        assert cache.tel is not None and landed.calls <= 1
        cache.check()
        landed.release.set()
        assert arena.flush(10)
        assert arena.has(0, tuple(a[: 3 * PAGE])) and arena.has(0, tuple(a[: 2 * PAGE]))
        assert not arena.has(0, tuple(a))
        cache.check()
        arena.close()

    def test_republishing_a_pending_chain_cancels_it(self):
        """(d): the chain comes back to the tree while its evicted bytes are
        still on their way: the pending entry is cancelled, nothing lands
        AFTER the tree holds the chain again, and check() passes at every
        step."""
        landed = HeldLand(lambda hs: [[np.full(3, h, np.float32)] for h in hs])
        arena = HostArena(1 << 10)
        cache = PrefixCache(2, PAGE, page_bytes=12, spill=arena,
                            page_fetch=lambda pids: list(pids), page_land=landed)
        a = list(range(1, 1 + 2 * PAGE))
        b = list(range(101, 101 + 2 * PAGE))
        cache.publish(np.asarray(a), len(a), [])
        cache.check()
        cache.publish(np.asarray(b), len(b), [])  # evicts both of a's pages
        chains_a = [tuple(a[:PAGE]), tuple(a)]
        assert all(arena.is_pending(0, c) for c in chains_a)
        cache.check()
        cache.publish(np.asarray(a), len(a), [])  # a again, evicting b's
        assert not any(arena.has(0, c) for c in chains_a)  # cancelled
        assert all(arena.is_pending(0, c) for c in [tuple(b[:PAGE]), tuple(b)])
        cache.check()
        landed.release.set()
        assert arena.flush(10)
        assert not any(arena.has(0, c) for c in chains_a), "landed after the tree took the chain back"
        assert arena.has(0, tuple(b)) and not arena.is_pending(0, tuple(b))
        cache.check()
        arena.close()


class TestEvictionOffTheLock:
    def _sched_with_held_fetch(self, tmp_path, **kw):
        sched = build_sched(build_engine(tmp_path), **kw)
        held = HeldLand(sched._land_pages)
        sched._prefix.page_land = held
        return sched, held

    def test_a_publish_returns_and_the_lock_is_free_while_the_fetch_is_held(self, tmp_path):
        """(a): the evictions' fetch is held on an Event; the publish that
        evicted returns, and another thread takes ``_cond``, before it is
        set."""
        sched, held = self._sched_with_held_fetch(tmp_path)
        s = sched.new_stream()
        decode_tokens(s, PROMPT)
        churn(s, 100)  # returns: three publishes, the last two evicting
        arena = sched._prefix.spill
        assert arena.pending_pages() >= 2 and not held.release.is_set()
        assert arena.reloaded_total == 0 and held.calls == 1  # the spiller sits in the first batch
        took = threading.Event()

        def other_lane():
            with sched._cond:
                took.set()

        t = threading.Thread(target=other_lane)
        t.start()
        t.join(5)
        assert took.is_set(), "the scheduler's lock is held across the fetch"
        sched.check_prefix()
        held.release.set()
        assert arena.flush(10) and arena.pending_pages() == 0
        assert all(arena.has(0, c) for c in (tuple(PROMPT[:4]), tuple(PROMPT[:8])))
        sched.check_prefix()
        sched.close()
        assert not arena._spiller.is_alive()

    @pytest.mark.parametrize("cache_dtype", [None, jnp.bfloat16, "i8"], ids=["f32", "bf16", "i8"])
    def test_a_reload_that_meets_a_pending_page_waits_off_the_lock(self, tmp_path, cache_dtype):
        """(c), first outcome: the request's own thread waits for the
        transfer BEFORE it takes the scheduler's lock, then reloads the
        evicted bytes bit for bit."""
        sched = build_sched(build_engine(tmp_path, cache_dtype=cache_dtype))
        held = HeldLand(sched._land_pages)
        sched._prefix.page_land = held
        s = sched.new_stream()
        cold = decode_tokens(s, PROMPT)
        s.reset()
        before = [download_page(sched, nd.page_id) for nd in sched._prefix.walk(PROMPT)]
        churn(s, 100)
        arena = sched._prefix.spill
        assert arena.is_pending(0, tuple(PROMPT[:8]))
        threading.Timer(0.3, held.release.set).start()
        warm = decode_tokens(s, PROMPT)
        assert warm == cold
        assert arena.pending_reloads == {"waited": 1, "cold": 0}
        assert arena.reloaded_total == 2
        s.reset()
        after = [download_page(sched, nd.page_id) for nd in sched._prefix.walk(PROMPT)]
        for want, got in zip(before, after):
            for a, b in zip(want, got):
                np.testing.assert_array_equal(
                    np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8)
                )
        sched.check_prefix()
        sched.close()

    def test_a_page_still_pending_under_the_lock_is_a_counted_cold_prefill(self, tmp_path):
        """(c), second outcome: nothing waits under the scheduler's lock, so
        a page that is still pending there is a miss: the request prefills
        cold (bit-identical), the miss is counted, the cold prefill's
        publish cancels the pending entry, and its bytes never land."""
        sched, held = self._sched_with_held_fetch(tmp_path)
        sched._prefix.await_pending = lambda tokens: None  # the wait timed out
        s = sched.new_stream()
        cold = decode_tokens(s, PROMPT)
        churn(s, 100)
        arena = sched._prefix.spill
        chains = (tuple(PROMPT[:4]), tuple(PROMPT[:8]))
        assert all(arena.is_pending(0, c) for c in chains)
        again = decode_tokens(s, PROMPT)
        assert again == cold
        assert arena.pending_reloads["cold"] >= 1 and arena.reloaded_total == 0
        assert not any(arena.has(0, c) for c in chains)  # the publish cancelled them
        sched.check_prefix()
        held.release.set()
        assert arena.flush(10)
        assert not any(arena.has(0, c) for c in chains)
        s.reset()
        sched.check_prefix()
        sched.close()

    def test_the_tier_counts_what_is_pending_skipped_and_met(self, tmp_path):
        from distributed_llama_tpu import telemetry

        telemetry.reset()
        telemetry.enable()
        try:
            probe = build_sched(build_engine(tmp_path))
            entry = probe._prefix.page_bytes
            probe.close()
            # an arena of ONE entry under a pool whose publishes evict two
            sched = build_sched(build_engine(tmp_path, name="m2.m"), arena=HostArena(entry))
            held = HeldLand(sched._land_pages)
            sched._prefix.page_land = held
            s = sched.new_stream()
            decode_tokens(s, PROMPT)
            for k in range(3):  # three pages each: an admission's reload evicts
                decode_tokens(s, [100 + 20 * k + j for j in range(14)])  # one, its publish two
            s.reset()
            reg = telemetry.REGISTRY
            assert reg.gauge("dllama_prefix_spill_pending_pages").value == 1
            skipped = reg.counter("dllama_prefix_spill_skipped_total").value
            assert skipped >= 2
            assert reg.counter("dllama_prefix_spill_pages_total").value == (
                sched._prefix.spill.spilled_total
            )
            held.release.set()
            assert sched._prefix.spill.flush(10)
            assert reg.gauge("dllama_prefix_spill_pending_pages").value == 0
            assert reg.counter("dllama_prefix_spill_dropped_total").value == (
                sched._prefix.spill.dropped_total
            )
            spans = [e["name"] for e in telemetry.chrome_trace()["traceEvents"]]
            assert "prefix_spill_fetch" in spans
            sched._prefix.spill.close()
        finally:
            telemetry.disable()
            telemetry.reset()


class OneVictimAtATime(PrefixCache):
    """The tier as it was before ISSUE 38, kept as the reference: every
    victim of a publish is spilled by itself and has landed before the next
    one goes (nothing batched, nothing skipped)."""

    def _evict(self, victims):
        for victim in victims:
            super()._evict([victim])
            assert self.spill.flush(10)


class TestSameLadderAsOneByOne:
    @pytest.mark.parametrize("disk", [False, True], ids=["host_only", "disk_tier"])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_publishes_hits_and_reloads_leave_the_tree_and_the_arena_alike(
        self, tmp_path, seed, disk
    ):
        """The same sequence of publishes, hits and reloads over a pool of 8
        pages and an arena of 3 entries, through the batched evictor and
        through the one-victim-at-a-time reference: the same page ids and
        tree at every step's end and, with no disk tier, the same arena keys,
        bytes and counters. With a disk tier a pending page displaced from
        the host is written to the disk when it LANDS, so a reload's takes
        in between can find the disk's slots otherwise filled: there the
        ladder is held to holding only the right bytes under each key."""
        rng = np.random.RandomState(seed)
        docs = [[int(t) for t in rng.randint(1, 40, PAGE * int(rng.randint(2, 6)) + 1)]
                for _ in range(7)]
        docs += [d[: 2 * PAGE] + [int(t) for t in rng.randint(40, 80, 2 * PAGE + 1)]
                 for d in docs[:3]]  # shared heads
        sides = []
        for name, cls in (("ref", OneVictimAtATime), ("new", PrefixCache)):
            arena = HostArena(
                3 * 16,
                disk_path=str(tmp_path / name / "spill.bin") if disk else None,
                disk_budget_bytes=2 * 16 if disk else 0,
            )
            pool = {}  # the device pool: page id -> its 16 bytes
            cache = cls(
                8, PAGE, page_bytes=16, spill=arena,
                page_fetch=lambda pids, pool=pool: [[pool[p].copy()] for p in pids],
                page_land=lambda handles: handles,
            )
            sides.append((cache, arena, pool))
        chains = set()
        for step in range(40):
            tokens = np.asarray(docs[rng.randint(len(docs))])
            state = []
            for cache, arena, pool in sides:
                assert arena.flush(10)  # what a request's wait before the lock does
                cache.reload(tokens, lambda pid, arrays, pool=pool: pool.__setitem__(pid, arrays[0]))
                chain = cache.match(tokens)
                ids, blocks = cache.publish(tokens, len(tokens), chain)
                for pid, b in zip(ids, blocks):  # the publish's device copy
                    key = tuple(int(t) for t in tokens[: (b + 1) * PAGE])
                    chains.add(key)
                    pool[pid] = np.frombuffer(
                        np.random.RandomState(hash(key) % 2**31).bytes(16), np.uint8
                    ).copy()
                cache.release(chain)
                cache.check()
                assert arena.flush(10)
                tree = sorted((cache.chain_key(n), n.page_id) for n in cache._walk())
                held = ladder_state(arena, sorted(chains))
                for key, (data,) in held.items():
                    assert data == np.random.RandomState(hash(key) % 2**31).bytes(16), key
                ladder = (held, arena.dropped_total, arena.spilled_total, arena.reloaded_total,
                          arena.depth())
                state.append((ids, tree, sorted(cache.free), None if disk else ladder))
            assert state[0] == state[1], step
        assert sides[1][1].skipped_total > 0 or disk  # the batches did overflow the budget
        assert sides[1][1].reloaded_total > 2 * 40  # ladder_state's reads, and real reloads
        for _, arena, _ in sides:
            arena.close()
