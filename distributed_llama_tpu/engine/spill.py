"""Host-RAM (and optional disk) spill tiers below the device page pool.

The reference engine can already place its KV cache on an mmap'd
disc-backed buffer (``--kv-cache-storage disc``, ``newMmapFileBuffer`` —
reference: src/utils.cpp:50-67, src/app.cpp:105-106): capacity there is
bounded by the disc, not RAM. Our HBM page pool (PR 4/7) was strictly less
capable — ``--kv-pages`` was the end of the ladder, and the LRU evictor
DISCARDED pages that cost real prefill compute. This module adds the
missing rungs: an evicted page's bytes land in a bounded host-RAM arena
(re-uploading host bytes is orders of magnitude cheaper than re-prefilling
them), and the arena can demote its own LRU overflow to an mmap'd disk
file, echoing the reference's bottom rung.

Tier contract (engine/prefix_cache.py drives it):

* **Spill** — ``PrefixCache._evict`` has the scheduler slice the victims'
  pages into fresh device buffers (launches that are only enqueued) and
  enters them here as PENDING (:meth:`HostArena.put_pending`), keyed by
  ``(owner replica, full token-prefix chain)``: budgeted, ordered and
  displaced exactly as a landed ``put`` would be, and nothing waits for
  the device. The arena's spiller thread then fetches the bytes (data AND
  scales, verbatim, for i8 ``QuantizedKV``), checksums them and lands
  them. The chain key makes entries exact: KV at a page's positions
  depends on every token before them, so only a request with the
  identical prefix may reload the bytes.
* **Pending** — an entry whose bytes are still on their way is never
  served (``take`` and ``peek_shared`` miss on it; a reader may wait OFF
  its scheduler's lock with :meth:`HostArena.wait_pending`) and never
  lost track of: ``drop``/``drop_owner``/LRU displacement cancel it, and a
  cancelled entry's bytes are discarded when they arrive.
* **Reload** — an admission match that ran out of device-resident chain
  consults the arena: the owner's own entry is MOVED back to the device
  (``take`` — an entry must never be resident in the arena while its
  pages are live and pinned on the device, the :meth:`PrefixCache.check`
  invariant), another replica's entry is COPIED (``peek_shared`` — the
  cross-replica sharing path: the Zipf head spilled by replica A uploads
  into replica B without B ever prefilling it).
* **Integrity** — every entry carries a CRC of its bytes, verified on
  every read. Host RAM and disk are exactly the substrates silent
  corruption lives in (PR 10), and a corrupt reload would serve wrong KV
  to every future match of the chain: a CRC mismatch raises
  :class:`SpillCorrupt`, the caller drops the entry and falls back to a
  cold prefill (chaos-enforced via the ``engine.spill`` fault site).

Thread model: one arena is shared by every replica's scheduler (and the
pool's death handler), so the arena takes its own LEAF lock — it never
calls back into a scheduler or the pool. Its one thread (the spiller,
started by the first pending batch, joined by :meth:`HostArena.close`)
calls the batch's ``land`` callable with NO lock held: that is the one
place a spill waits for the device. Numpy-only on purpose: the device
programs that slice, fetch and upload page bytes belong to the scheduler
(engine/batch.py); this module stores and checks bytes.
"""

from __future__ import annotations

import collections
import os
import threading
import time
import zlib

import numpy as np

from distributed_llama_tpu import lockcheck, telemetry


class SpillCorrupt(RuntimeError):
    """A spilled entry's bytes no longer match their spill-time CRC: host
    RAM or disk corrupted them in place. The entry is already dropped when
    this raises — the caller's only correct move is a cold prefill."""


def _crc(arrays) -> int:
    c = 0
    for a in arrays:
        # over the bytes where they lie: no copy, and zlib releases the GIL
        # for the length of a buffer this size
        c = zlib.crc32(np.ascontiguousarray(a).reshape(-1).view(np.uint8), c)
    return c


def _nbytes(arrays) -> int:
    return sum(int(a.nbytes) for a in arrays)


class _Entry:
    """One page's bytes. PENDING while ``batch`` is set: ``handle`` is what
    the batch's ``land`` will turn into ``arrays`` (the scheduler's device
    buffers, opaque here), ``nbytes`` is what the spiller was told and
    ``crc`` is not known yet."""

    __slots__ = ("arrays", "nbytes", "crc", "last_use", "handle", "batch")

    def __init__(self, arrays, nbytes: int, crc: int, last_use: int,
                 handle=None, batch=None):
        self.arrays = arrays
        self.nbytes = nbytes
        self.crc = crc
        self.last_use = last_use
        self.handle = handle
        self.batch = batch


class _Batch:
    """One eviction's pending pages, fetched together by the spiller."""

    __slots__ = ("items", "land", "done")

    def __init__(self, land):
        self.items: list[tuple[tuple, _Entry]] = []
        self.land = land  # handles -> one list of host arrays a handle; blocks
        self.done = threading.Event()  # set once every page landed or was let go


class DiskTier:
    """Fixed-slot mmap'd spill file (the reference's ``newMmapFileBuffer``
    rung). Every spilled page serializes to the same byte length (one
    page's KV across all layers/halves is shape-static per config), so
    the file is a flat slot array: a free list, a key→slot map, and the
    per-slot CRC/LRU bookkeeping live on the host; the bytes live in the
    mmap. The first ``put`` fixes the entry template (shapes/dtypes);
    capacity = ``budget_bytes // entry_bytes`` slots."""

    def __init__(self, path: str, budget_bytes: int, on_drop=None):
        self.path = path
        self.budget = int(budget_bytes)
        self.on_drop = on_drop  # called with the evicted key (LRU overflow)
        self._mm = None
        self._template: list[tuple[tuple, np.dtype]] | None = None
        self.entry_bytes = 0
        self._slots: dict[tuple, tuple[int, int, int]] = {}  # key -> (slot, crc, last_use)
        self._free: list[int] = []
        self._clock = 0
        self.dropped_total = 0

    def _open(self, arrays) -> bool:
        self._template = [(a.shape, a.dtype) for a in arrays]
        self.entry_bytes = _nbytes(arrays)
        n_slots = self.budget // max(self.entry_bytes, 1)
        if n_slots < 1:
            return False  # budget below one entry: disk tier inert
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        self._mm = np.memmap(
            self.path, dtype=np.uint8, mode="w+",
            shape=(n_slots * self.entry_bytes,),
        )
        self._free = list(range(n_slots))
        return True

    def put(self, key: tuple, arrays, crc: int) -> bool:
        """Write one entry; evicts the LRU slot when full. Returns False
        when the entry cannot be stored (zero-capacity budget or a
        template mismatch — heterogeneous configs never share a file)."""
        if self._mm is None and self._template is None:
            if not self._open(arrays):
                return False
        if self._mm is None:
            return False
        if [(a.shape, a.dtype) for a in arrays] != self._template:
            return False
        old = self._slots.pop(key, None)
        if old is not None:
            self._free.append(old[0])
        if not self._free:
            lru = min(self._slots, key=lambda k: self._slots[k][2])
            self._free.append(self._slots.pop(lru)[0])
            self.dropped_total += 1
            if self.on_drop is not None:
                self.on_drop(lru)
        slot = self._free.pop()
        off = slot * self.entry_bytes
        for a in arrays:
            b = np.ascontiguousarray(a).view(np.uint8).reshape(-1)
            self._mm[off : off + b.size] = b
            off += b.size
        self._clock += 1
        self._slots[key] = (slot, crc, self._clock)
        return True

    def take(self, key: tuple, copy_only: bool = False):
        """Read (and unless ``copy_only`` remove) an entry; CRC-verified.
        Returns the array list or None; raises :class:`SpillCorrupt` on a
        CRC mismatch (the entry is dropped first)."""
        rec = self._slots.get(key)
        if rec is None:
            return None
        slot, crc, _ = rec
        off = slot * self.entry_bytes
        arrays = []
        for shape, dtype in self._template:
            n = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
            raw = np.array(self._mm[off : off + n])  # copy out of the mmap
            arrays.append(raw.view(dtype).reshape(shape))
            off += n
        if _crc(arrays) != crc:
            del self._slots[key]
            self._free.append(slot)
            raise SpillCorrupt(f"disk spill entry CRC mismatch for {key[0]}")
        if not copy_only:
            del self._slots[key]
            self._free.append(slot)
        else:
            self._clock += 1
            self._slots[key] = (slot, crc, self._clock)
        return arrays

    def drop(self, key: tuple) -> None:
        rec = self._slots.pop(key, None)
        if rec is not None:
            self._free.append(rec[0])

    def keys(self):
        return list(self._slots)

    def __len__(self) -> int:
        return len(self._slots)


class HostArena:
    """Bounded host-RAM spill arena shared across a pool's replicas.

    Keys are ``(owner, chain)``: ``owner`` is the spilling replica id and
    ``chain`` the full token-prefix tuple whose last page the entry holds.
    A budget overflow demotes the LRU entry to the :class:`DiskTier` when
    one is configured, else drops it (counted — silent truncation is how
    capacity claims rot). An entry is LANDED (its bytes are here) or
    PENDING (:meth:`put_pending`: its bytes are on their way through the
    spiller thread): both count against the budget, age and are displaced
    alike; only a landed entry is ever read. All methods are thread-safe;
    the internal lock is a LEAF (never calls out)."""

    def __init__(
        self, budget_bytes: int, disk_path: str | None = None,
        disk_budget_bytes: int = 0,
    ):
        self.budget = int(budget_bytes)
        self.disk = (
            DiskTier(disk_path, disk_budget_bytes, on_drop=self._on_disk_drop_locked)
            if disk_path and disk_budget_bytes > 0 else None
        )
        self._lock = lockcheck.make_lock("HostArena._lock")
        self._entries: dict[tuple, _Entry] = {}
        # chain -> owners with a resident entry (host OR disk): the
        # cross-replica peek and the corrupt-chaos hook look up by chain
        self._chains: dict[tuple, set[int]] = {}
        self._clock = 0
        self.resident_bytes = 0
        self.spilled_total = 0
        self.reloaded_total = 0
        self.dropped_total = 0
        self.corrupt_total = 0
        # pending entries by key, wherever they wait: in ``_entries`` (on
        # the host's budget) or in ``_disk_bound`` (displaced to the disk
        # tier before their bytes arrived: written there when they land)
        self._pending: dict[tuple, _Entry] = {}
        self._disk_bound: dict[tuple, _Entry] = {}
        self.skipped_total = 0
        self.pending_reloads = {"waited": 0, "cold": 0}
        # the spiller: batches in arrival order, the head one being fetched
        self._queue: collections.deque[_Batch] = collections.deque()
        self._wake = threading.Condition(self._lock)
        self._spiller: threading.Thread | None = None
        self._closed = False
        # bound once; the registry dedupes by name, so ``dropped`` is the
        # same series PrefixCacheInstruments.spill_dropped exposes
        self.tel = telemetry.SpillArenaInstruments()
        self._tel_dropped = self.tel.dropped

    def _on_disk_drop_locked(self, key: tuple) -> None:
        # invoked by the disk tier's own LRU eviction, under self._lock
        # (every disk call happens there)
        self.dropped_total += 1
        self._tel_dropped.inc()
        self._unchain_locked(key)

    # ------------------------------------------------------------------
    # Write side
    # ------------------------------------------------------------------

    def put(self, owner: int, chain: tuple, arrays) -> None:
        """Spill one page's byte arrays (verbatim — the caller flattened
        data+scales for i8). Re-putting a key replaces the old entry."""
        arrays = [np.ascontiguousarray(a) for a in arrays]
        entry = _Entry(arrays, _nbytes(arrays), _crc(arrays), 0)
        key = (int(owner), tuple(chain))
        with self._lock:
            self._drop_locked(key)
            self._insert_locked(key, entry)

    def _insert_locked(self, key: tuple, entry: _Entry) -> None:
        self._clock += 1
        entry.last_use = self._clock
        self._entries[key] = entry
        self._chains.setdefault(key[1], set()).add(key[0])
        self.resident_bytes += entry.nbytes
        self.spilled_total += 1
        self._fit_locked(key)

    def _fit_locked(self, newest: tuple) -> None:
        while self.resident_bytes > self.budget and self._entries:
            # demote the LRU entry (the freshly-put one only when it
            # is alone and over-budget by itself) — to disk when a
            # tier is configured, else a counted drop
            self._demote_lru_locked(
                keep=newest if len(self._entries) > 1 else None
            )

    def keeps(self, n: int, entry_bytes: int) -> int:
        """Of ``n`` pages of ``entry_bytes`` each, put one after the other,
        how many of the LAST are still somewhere in the ladder after the
        n-th put. With a disk tier, all (an overflow is a demotion); without
        one, what the host budget holds: each put is the newest entry, so
        the overflow takes every older entry first and then the batch's own
        first ones, as they arrive. The evictor slices and fetches only
        these and hands the others to :meth:`put_pending` as ``skipped``."""
        if self.disk is not None or entry_bytes <= 0:
            return n
        return min(n, self.budget // entry_bytes)

    def put_pending(
        self, owner: int, chains: list, handles: list, entry_bytes: int, land,
        skipped: int = 0,
    ) -> None:
        """Enter one eviction's pages, in the evictor's (LRU) order, as
        PENDING and leave their bytes to the spiller thread: ``handles[i]``
        is whatever ``land(handles)`` turns into page i's host arrays (it
        blocks on the device, so it runs on the spiller with no lock held).
        Budget, order, displacement and every counter move NOW, exactly as
        ``put`` would move them, so nothing in flight exceeds the budget and
        the arena ends as ``skipped + len(chains)`` sequential puts leave it;
        ``skipped`` is how many victims BEFORE these the caller left on the
        device because :meth:`keeps` said they would be dropped on arrival.
        Never waits."""
        owner = int(owner)
        with self._lock:
            if skipped:
                # those puts alone overflow the budget: every older entry
                # goes before the first of them does, then they go
                while self._entries:
                    self._demote_lru_locked(keep=None)
                self.spilled_total += skipped
                self.skipped_total += skipped
                self.tel.skipped.inc(skipped)
                self._count_drops_locked(skipped)
            if self._closed:
                # no spiller any more: the pages vanish, counted
                self.spilled_total += len(chains)
                self._count_drops_locked(len(chains))
                return
            batch = _Batch(land)
            for chain, handle in zip(chains, handles):
                key = (owner, tuple(chain))
                entry = _Entry(None, int(entry_bytes), 0, 0, handle=handle, batch=batch)
                batch.items.append((key, entry))
                self._drop_locked(key)
                self._pending[key] = entry
                self._insert_locked(key, entry)
            self._queue.append(batch)
            if self._spiller is None:
                self._spiller = threading.Thread(
                    target=self._spill_loop, name="dllama-spill", daemon=True
                )
                self._spiller.start()
            self.tel.pending.set(len(self._pending))
            self._wake.notify_all()

    def _count_drops_locked(self, n: int) -> None:
        self.dropped_total += n
        self._tel_dropped.inc(n)

    def _lose_locked(self, key: tuple) -> None:
        """A counted drop of an entry the ladder was still holding."""
        self._count_drops_locked(1)
        self._drop_locked(key)

    def _settle_locked(self, key: tuple, entry: _Entry) -> None:
        """``entry`` is pending no more: it landed, or it left the ladder
        before its bytes arrived (its device buffer is let go of now, and
        the spiller passes it over)."""
        if entry.batch is not None:
            entry.batch = entry.handle = None
            del self._pending[key]
            self.tel.pending.set(len(self._pending))

    def _demote_lru_locked(self, keep: tuple | None) -> None:
        lru = min(
            (k for k in self._entries if k != keep),
            key=lambda k: self._entries[k].last_use,
        )
        entry = self._entries.pop(lru)
        self.resident_bytes -= entry.nbytes
        demoted = False
        if self.disk is not None:
            if entry.batch is not None:
                # its bytes are still on their way: it leaves the host's
                # budget now and is written to the disk when they land (in
                # the batch's order; a read of the disk tier in between
                # finds the slot still free, where a landed demotion would
                # have filled it: the disk's own LRU may differ by that)
                self._disk_bound[lru] = entry
                demoted = True
            else:
                demoted = self.disk.put(lru, entry.arrays, entry.crc)
        if not demoted:
            self._count_drops_locked(1)
            self._settle_locked(lru, entry)
            self._unchain_locked(lru)

    def _unchain_locked(self, key: tuple) -> None:
        owners = self._chains.get(key[1])
        if owners is not None:
            owners.discard(key[0])
            if not owners:
                del self._chains[key[1]]

    def _drop_locked(self, key: tuple) -> None:
        entry = self._entries.pop(key, None)
        if entry is not None:
            self.resident_bytes -= entry.nbytes
        else:
            entry = self._disk_bound.pop(key, None)
        if entry is not None:
            self._settle_locked(key, entry)
        if self.disk is not None:
            self.disk.drop(key)
        if entry is not None or self.disk is not None:
            self._unchain_locked(key)

    # ------------------------------------------------------------------
    # The spiller thread: the one place a spill waits for the device
    # ------------------------------------------------------------------

    def _spill_loop(self) -> None:
        while True:
            with self._lock:
                while not self._queue and not self._closed:
                    self._wake.wait()
                if not self._queue:
                    return
                # the batch stays at the head while its bytes are fetched:
                # flush() and close() wait for an EMPTY queue
                batch = self._queue[0]
                live = [(k, e, e.handle) for k, e in batch.items if e.batch is batch]
            landed, error = [], None
            if live:
                lockcheck.note_blocking("HostArena spill fetch")
                try:
                    with telemetry.trace_span("prefix_spill_fetch", pages=len(live)):
                        for arrays in batch.land([h for _, _, h in live]):
                            arrays = [np.ascontiguousarray(a) for a in arrays]
                            landed.append((arrays, _nbytes(arrays), _crc(arrays)))
                except Exception as e:
                    # spilling is an optimization: a failed fetch is a counted
                    # drop of its pages, which then prefill cold
                    print(f"⚠️ page spill fetch failed; {len(live)} pages dropped: {e}")
                    error = e
            with self._lock:
                for i, (key, entry, _) in enumerate(live):
                    if entry.batch is not batch:
                        continue  # cancelled while its bytes were on their way
                    if error is not None:
                        self._lose_locked(key)
                    else:
                        self._land_locked(key, entry, *landed[i])
                self._queue.popleft()
                batch.done.set()
                self._wake.notify_all()

    def _land_locked(self, key: tuple, entry: _Entry, arrays, nbytes: int, crc: int) -> None:
        self._settle_locked(key, entry)
        entry.arrays, entry.crc = arrays, crc
        if self._disk_bound.get(key) is entry:
            del self._disk_bound[key]
            if not self.disk.put(key, arrays, crc):
                self._count_drops_locked(1)
                self._unchain_locked(key)
            return
        # what the evictor said a page weighs is what the budget was charged
        self.resident_bytes += nbytes - entry.nbytes
        entry.nbytes = nbytes
        self._fit_locked(key)

    def flush(self, timeout: float | None = None) -> bool:
        """Wait until the spiller has nothing pending (tests, shutdown and
        tools; never under a scheduler's lock). False on timeout."""
        lockcheck.note_blocking("HostArena.flush")
        with self._lock:
            return self._wake.wait_for(lambda: not self._queue, timeout)

    def wait_pending(self, tokens, timeout: float = 5.0) -> bool:
        """A reader's wait, holding NO lock, for the pages of ``tokens``'
        prefixes (any owner's) that are still on their way: a request's own
        thread calls this BEFORE it takes its scheduler's lock to reload, so
        that a chain evicted a moment ago reloads instead of prefilling
        cold. Bounded: one batch's transfer at a time, ``timeout`` seconds in
        all. Whatever is still pending once the reader holds its lock is a
        miss there (``take`` counts it ``cold``). True if it waited."""
        if not self._pending:  # the common case, read without the lock: a
            return False       # page that turns pending after this is a miss
        ids = tuple(tokens.tolist() if hasattr(tokens, "tolist") else map(int, tokens))
        deadline = time.monotonic() + timeout
        waited = False
        while True:
            with self._lock:
                batch = next(
                    (e.batch for (_, chain), e in self._pending.items()
                     if len(chain) < len(ids) and ids[: len(chain)] == chain),
                    None,
                )
                if batch is None or time.monotonic() >= deadline:
                    if waited:
                        self.pending_reloads["waited"] += 1
                        self.tel.pending_waited.inc()
                    return waited
            waited = True
            lockcheck.note_blocking("HostArena.wait_pending")
            batch.done.wait(max(0.0, deadline - time.monotonic()))

    def close(self) -> None:
        """Stop the spiller (shutdown, tests): what is still pending is let
        go, counted as dropped, and the thread is joined. The arena stays
        readable; a later ``put_pending`` drops its pages at once."""
        with self._lock:
            self._closed = True
            for key in list(self._pending):
                self._lose_locked(key)
            self._wake.notify_all()
            spiller = self._spiller
        if spiller is not None:
            spiller.join(timeout=30.0)

    # ------------------------------------------------------------------
    # Read side
    # ------------------------------------------------------------------

    def _verified_locked(self, key: tuple, remove: bool):
        if key in self._pending:
            return None  # bytes the arena does not hold yet are never served
        entry = self._entries.get(key)
        if entry is not None:
            if _crc(entry.arrays) != entry.crc:
                self._drop_locked(key)
                self.corrupt_total += 1
                self._tel_dropped.inc()
                raise SpillCorrupt(
                    f"host spill entry CRC mismatch (owner {key[0]})"
                )
            arrays = entry.arrays
            if remove:
                self._drop_locked(key)
            else:
                self._clock += 1
                entry.last_use = self._clock
                arrays = [a.copy() for a in arrays]
            return arrays
        if self.disk is not None:
            try:
                arrays = self.disk.take(key, copy_only=not remove)
            except SpillCorrupt:
                self.corrupt_total += 1
                self._tel_dropped.inc()
                self._unchain_locked(key)
                raise
            if arrays is not None and remove:
                self._unchain_locked(key)
            return arrays
        return None

    def take(self, owner: int, chain: tuple):
        """MOVE the owner's entry back out (the same-replica reload path:
        the device copy supersedes the arena's, restoring the pinned-
        pages-never-in-arena invariant). None on miss; SpillCorrupt on a
        failed CRC (entry dropped)."""
        key = (int(owner), tuple(chain))
        with self._lock:
            if key in self._pending:
                self._count_cold_locked()
            arrays = self._verified_locked(key, remove=True)
            if arrays is not None:
                self.reloaded_total += 1
            return arrays

    def _count_cold_locked(self) -> None:
        # a reload met a page whose bytes are still on their way, under its
        # scheduler's lock, where nothing waits: the block prefills cold
        self.pending_reloads["cold"] += 1
        self.tel.pending_cold.inc()

    def peek_shared(self, chain: tuple, exclude_owner: int):
        """COPY another replica's entry for ``chain`` (cross-replica
        sharing: the reader uploads the bytes into its own pool while the
        spiller's entry stays for the next replica). None when no other
        owner holds the chain LANDED: a pending entry is passed over."""
        with self._lock:
            owners = self._chains.get(tuple(chain), set())
            for owner in sorted(owners):
                if owner == exclude_owner:
                    continue
                try:
                    arrays = self._verified_locked((owner, tuple(chain)), remove=False)
                except SpillCorrupt:
                    continue  # that copy is gone; try the next owner
                if arrays is not None:
                    self.reloaded_total += 1
                    return arrays
            if any((o, tuple(chain)) in self._pending for o in owners if o != exclude_owner):
                self._count_cold_locked()
            return None

    def has(self, owner: int, chain: tuple) -> bool:
        """Whether the owner's entry is anywhere in the ladder: landed on
        the host or the disk, or pending."""
        key = (int(owner), tuple(chain))
        with self._lock:
            return key[0] in self._chains.get(key[1], set())

    def check(self) -> None:
        """Structural invariants (tests, :meth:`PrefixCache.check`): an
        entry is pending or landed, never both or neither, and is kept in
        exactly one place."""
        with self._lock:
            for key, entry in self._pending.items():
                assert entry.batch is not None and entry.arrays is None, key[0]
                places = (self._entries.get(key) is entry) + (self._disk_bound.get(key) is entry)
                assert places == 1, f"pending entry kept in {places} places"
            for key, entry in self._entries.items():
                assert (entry.batch is None) == (entry.arrays is not None), key[0]
                assert (entry.batch is not None) == (self._pending.get(key) is entry), key[0]
                assert key[0] in self._chains.get(key[1], ()), "entry left its chain's owners"
            assert all(self._pending.get(k) is e for k, e in self._disk_bound.items())
            assert self.resident_bytes == sum(e.nbytes for e in self._entries.values())

    def is_pending(self, owner: int, chain: tuple) -> bool:
        with self._lock:
            return (int(owner), tuple(chain)) in self._pending

    def pending_pages(self) -> int:
        return len(self._pending)

    def drop(self, owner: int, chain: tuple) -> None:
        """Remove one entry without reading it (a fresh device publish of
        the chain supersedes the spilled copy)."""
        with self._lock:
            self._drop_locked((int(owner), tuple(chain)))

    def drop_owner(self, owner: int) -> None:
        """A replica died: its spilled bytes are no longer trustworthy
        (a silently-corrupt replica may have spilled corrupt KV, PR 10)
        and its rebuild starts with an empty cache anyway — remove every
        entry it owns, pending ones too (their bytes are discarded when
        they arrive), atomically with the death."""
        owner = int(owner)
        with self._lock:
            for key in [k for k in (*self._entries, *self._disk_bound) if k[0] == owner]:
                self._drop_locked(key)
            if self.disk is not None:
                for key in self.disk.keys():
                    if key[0] == owner:
                        self.disk.drop(key)
                        self._unchain_locked(key)

    def corrupt(self, chain: tuple) -> None:
        """Chaos hook (the ``engine.spill`` site's ``kind=corrupt``): flip
        one byte of every resident copy of ``chain`` IN PLACE — silent by
        construction; only the CRC verification can see it."""
        with self._lock:
            for owner in list(self._chains.get(tuple(chain), set())):
                entry = self._entries.get((owner, tuple(chain)))
                if entry is not None and entry.arrays:
                    # downloaded arrays may be read-only views of device
                    # buffers: corrupt a writable copy in the entry
                    flipped = entry.arrays[0].copy()
                    flipped.view(np.uint8).reshape(-1)[0] ^= 0xFF
                    entry.arrays[0] = flipped
                elif self.disk is not None:
                    rec = self.disk._slots.get((owner, tuple(chain)))
                    if rec is not None:
                        off = rec[0] * self.disk.entry_bytes
                        self.disk._mm[off] ^= 0xFF

    def depth(self, owner: int | None = None) -> int:
        """Resident entries (host + disk, landed or pending), optionally
        for one owner — the /readyz per-replica ``spill_depth`` read."""
        with self._lock:
            if owner is None:
                return sum(len(v) for v in self._chains.values())
            return sum(1 for v in self._chains.values() if int(owner) in v)
