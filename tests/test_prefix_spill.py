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
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.engine import InferenceEngine, faults
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.engine.prefix_cache import SharedPrefixIndex
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
            [a.copy() for a in sched._download_page(nd.page_id)]
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
            got = sched._download_page(nd.page_id)
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
