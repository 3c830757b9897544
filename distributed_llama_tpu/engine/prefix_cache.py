"""Radix tree over token blocks: prompt-prefix KV reuse across requests.

The serving workload the ROADMAP targets is dominated by shared prefixes —
the same system prompt and conversation history arrive over and over, and
the reference engine (like our own pre-page scheduler) re-prefills every
one of them from position 0. Prefill is the expensive phase (one weight
read per prompt chunk plus attention over the whole prefix), so reusing
prefill compute across requests is the biggest remaining serving win. This
is the RadixAttention idea (SGLang, Zheng et al. 2024) over PagedAttention
pages (vLLM, Kwon et al. 2023), adapted to the TPU-friendly static-shape
slab of engine/batch.py.

Design
------
* The prompt's token stream is split into fixed-size **blocks** of ``page``
  positions. Each radix-tree node owns exactly one block: its edge key is
  the block's token tuple (exact-match keys — no hash collisions to
  reason about) and its payload is one physical page id in the device page
  pool (:func:`~distributed_llama_tpu.models.llama.init_page_pool`).
* Pages are **immutable once published**: the scheduler copies a row's
  completed prefill KV *into* fresh pool pages (publish — the ONLY copy in
  the system). A matched row never copies pages back out: decode/verify/
  prefill attention reads the matched prefix **zero-copy through a per-row
  page table** over the pool (ops.attention paged variants), so each
  cached byte exists exactly once and effective batch + cacheable-prefix
  capacity both rise at fixed HBM. Writes still never touch tree pages —
  a row's private suffix lives in its slab row.
* **Refcounts** pin a matched chain for the **lifetime of the aliasing
  row** (admission match → row reset/quarantine/rollback-truncation), not
  just the admission window: eviction recycling a page that a live row's
  attention reads through its table would serve another prompt's KV.
  ``refs == 0`` nodes are evictable; eviction is leaf-first LRU
  (``last_use`` clock), so a chain ages out from its deepest, least-shared
  end while shared system-prompt roots survive. :meth:`check` extends to
  alias tracking — callers pass the live rows' page tables and it asserts
  none of those pages were freed or left unpinned.
* The pool size (``--kv-pages``) IS the HBM budget: allocation evicts
  LRU-unreferenced leaves only when the free list runs dry, and fails
  softly (the scheduler simply skips publishing) when everything is
  pinned. A publish or reload learns how many pages it is short of and
  takes its victims TOGETHER: one O(pages-in-tree) host scan yields them
  in the order one-by-one eviction would (:meth:`_pick_victims`); a
  last_use-ordered leaf index is the known follow-up if ``--kv-pages``
  grows to the tens of thousands.
* **Tiered capacity below HBM** (ISSUE 11, engine/spill.py): with a
  :class:`~distributed_llama_tpu.engine.spill.HostArena` attached,
  eviction no longer discards the page — its bytes (data+scales verbatim
  for i8) spill to bounded host RAM (and optionally an mmap'd disk file,
  echoing the reference's disc-backed KV). Under the scheduler's lock the
  victims' pages are only SLICED into fresh device buffers (enqueued, not
  waited for) and entered in the arena as pending; the arena's spiller
  thread fetches, checksums and lands them (ISSUE 38: the lock used to be
  held across a device wait for every page). A later admission match
  that runs out of device-resident chain RELOADS the spilled pages
  (:meth:`reload` — the publish machinery in reverse: alloc a pool page,
  upload the host bytes, re-insert the node). Re-upload is orders of
  magnitude cheaper than re-prefill, so effective cacheable-prefix
  capacity at fixed ``--kv-pages`` multiplies. Every spilled entry is
  CRC-verified on reload; a mismatch (host RAM/disk corrupted it) drops
  the entry and the block prefills cold — stale KV is never served.
* **Cross-replica sharing** (:class:`SharedPrefixIndex`): each replica's
  tree reports its published/evicted chains to one shared host-side
  index; the replica pool routes a request to the replica owning the
  LONGEST matched chain (server/replicas.py ``place``), so the Zipf head
  of a chat workload is prefilled once GLOBALLY instead of once per
  replica. The arena is shared too: a chain spilled by replica A reloads
  into replica B's pool by copy (A's entry stays), which is how hot head
  nodes replicate across pools when routing alone cannot keep up. A
  replica death atomically drops its chains from the index (and its
  arena entries — a silently-corrupt replica's spills are suspect).

Thread model: the owning :class:`~distributed_llama_tpu.engine.batch.
BatchScheduler` calls every method under its condition lock; the tree
itself is lock-free on purpose (one lock, one owner — no ordering hazards
between tree state and slab/pool dispatches); the one method meant to be
called WITHOUT it is :meth:`PrefixCache.await_pending`, which touches the
arena only. The shared index and the arena have their own LEAF locks
(multiple schedulers and the replica pool reach them concurrently);
neither ever calls back out.
"""

from __future__ import annotations

import heapq
import itertools
import threading

from distributed_llama_tpu import lockcheck, telemetry
from distributed_llama_tpu.engine.spill import SpillCorrupt
from distributed_llama_tpu.telemetry import flight


class SharedPrefixIndex:
    """Host-side map ``token-prefix chain -> owning replicas`` over the
    per-replica radix trees (the routing half of the global cache tier).

    Each :class:`PrefixCache` reports node inserts (publish/reload) and
    removals (evict/unpublish) here; :meth:`match` answers "which replica
    owns the longest published chain of this prompt" for placement.
    Per-owner chains stay contiguous from the root by construction (the
    trees publish contiguous chains and evict leaf-first), and the match
    walk enforces contiguity anyway (an owner absent at block i is
    ignored at every deeper block)."""

    def __init__(self, page: int):
        self.page = int(page)
        self._lock = lockcheck.make_lock("SharedPrefixIndex._lock")
        self._owners: dict[tuple, set[int]] = {}

    def publish(self, owner: int, chain: tuple) -> None:
        with self._lock:
            self._owners.setdefault(tuple(chain), set()).add(int(owner))

    def withdraw(self, owner: int, chain: tuple) -> None:
        with self._lock:
            owners = self._owners.get(tuple(chain))
            if owners is not None:
                owners.discard(int(owner))
                if not owners:
                    del self._owners[tuple(chain)]

    def drop_owner(self, owner: int) -> None:
        """A replica died: every chain it owned leaves the index in one
        locked pass — placement must never route to a dead replica's
        pages (the no-dangling-routing contract)."""
        owner = int(owner)
        with self._lock:
            for chain in [c for c, o in self._owners.items() if owner in o]:
                self._owners[chain].discard(owner)
                if not self._owners[chain]:
                    del self._owners[chain]

    def match(self, tokens) -> dict[int, int]:
        """Per-replica depth of the longest contiguous owned chain of
        ``tokens`` (full blocks strictly shorter than the prompt, the
        tree-match bound): ``{replica: n_blocks}``, empty on no match."""
        page = self.page
        max_blocks = (len(tokens) - 1) // page
        # one int-conversion pass OUTSIDE the lock, keys grown
        # incrementally: the cumulative-prefix keys still hash O(depth)
        # each (flat-dict tradeoff), but nothing re-walks the prompt per
        # block while holding the lock every publish/evict also takes
        ids = [int(t) for t in tokens[: max_blocks * page]]
        depths: dict[int, int] = {}
        alive: set[int] | None = None
        key: tuple = ()
        with self._lock:
            for i in range(max_blocks):
                key = key + tuple(ids[i * page : (i + 1) * page])
                owners = self._owners.get(key)
                if not owners:
                    break
                alive = set(owners) if alive is None else alive & owners
                if not alive:
                    break
                for o in alive:
                    depths[o] = i + 1
        return depths

    def owners(self, chain: tuple) -> set[int]:
        with self._lock:
            return set(self._owners.get(tuple(chain), set()))

    def __len__(self) -> int:
        with self._lock:
            return len(self._owners)


class PageNode:
    """One radix-tree node: a ``page``-token block bound to one pool page."""

    __slots__ = ("key", "page_id", "parent", "children", "refs", "last_use", "snap",
                 "wpage", "w_use")

    def __init__(self, key, page_id: int, parent: "PageNode | None"):
        self.key = key  # tuple of the block's token ids (edge label)
        self.page_id = page_id
        self.parent = parent
        self.children: dict[tuple, PageNode] = {}
        self.refs = 0
        self.last_use = 0
        # slot of the recurrent-state snapshot taken where this block ENDS
        # (archs with linear-attention layers); None = a row cannot resume here
        self.snap: int | None = None
        # page of the WINDOW layers' pool holding this block's keys and values
        # in those layers (archs with window attention), and when a hit last
        # used it (or its publish attached it); None = aged out or never kept
        self.wpage: int | None = None
        self.w_use = 0


class PrefixCache:
    """Host-side index of the device page pool (see module docstring)."""

    def __init__(
        self, n_pages: int, page: int, page_bytes: int = 0,
        spill=None, page_fetch=None, page_land=None, owner_id: int = 0,
        shared_index=None,
        snap_slots: int = 0, window_pages: int = 0, window_tail: int = 0,
        window_align: int = 0,
    ):
        if n_pages < 1:
            raise ValueError(f"need at least one pool page, got {n_pages}")
        if page < 1:
            raise ValueError(f"page size must be positive, got {page}")
        self.page = page
        self.capacity = n_pages
        # tiered capacity + cross-replica sharing (ISSUE 11): ``spill`` is
        # the shared HostArena (engine/spill.py). The spill side is two
        # callables of the owning scheduler: ``page_fetch(page_ids)``
        # slices those pool pages into fresh device buffers, one handle a
        # page, ENQUEUED ONLY (it runs under the scheduler's lock), and
        # ``page_land(handles)`` turns handles into host byte arrays,
        # BLOCKING (the arena's spiller thread calls it, off every lock).
        # The upload side is a reload() argument. ``page_bytes`` is what
        # one page's entry weighs in the arena. ``shared_index`` is the
        # pool-wide SharedPrefixIndex this tree
        # reports its chains to, ``owner_id`` this replica's identity in
        # both. All optional: a bare PrefixCache keeps the PR 4 contract.
        self.spill = spill
        self.page_fetch = page_fetch
        self.page_land = page_land
        self.owner_id = int(owner_id)
        self.shared_index = shared_index
        # logical KV bytes per page across all layers/halves
        # (llama.page_pool_bytes) — feeds the bytes gauge and the
        # copy-traffic-saved counter; 0 = unknown (host-only unit tests)
        self.page_bytes = int(page_bytes)
        self.free: list[int] = list(range(n_pages))
        # recurrent-state snapshot slots (the scheduler owns the device
        # store; this is its index): a slot is free, held by a row between
        # its snapshot and its publish, or attached to ONE tree node
        self.snap_slots = int(snap_slots)
        self.snap_free: list[int] = list(range(self.snap_slots))
        # the window layers' pool (archs with window attention; the scheduler
        # owns the device pool, this is its index): a hit that ends where a
        # block ends needs those layers' keys and values of the
        # ``window_tail`` blocks before it and nothing older, so only the last
        # blocks of a published prompt get a page here, the pages have their
        # own recency order (:meth:`attach_window_pages`), and the pool's size does
        # not follow ``n_pages``. ``window_align`` (an arch with EVA layers):
        # windows are ALIGNED blocks of that many pages, and a hit needs the
        # pages from its window's start to its end, none at a window's start
        self.window_pages = int(window_pages)
        self.window_tail = int(window_tail)
        self.window_align = int(window_align)
        self.wfree: list[int] = list(range(self.window_pages))
        self.root = PageNode(None, -1, None)
        self._clock = 0
        # running count of refs>0 nodes, maintained at the 0<->1 ref
        # transitions: the gauge updates on every match/release/publish
        # under the scheduler cond lock, so an O(tree) walk there would
        # serialize dispatch behind page-count bookkeeping at large
        # --kv-pages (check() cross-validates this counter against a walk)
        self._pinned = 0
        self.tel = telemetry.PrefixCacheInstruments()
        self.tel.pages.set(0)
        self.tel.bytes.set(0)
        self.tel.pinned_pages.set(0)

    # ------------------------------------------------------------------
    # Introspection (tests + metrics)
    # ------------------------------------------------------------------

    def pages_in_use(self) -> int:
        return self.capacity - len(self.free)

    def pinned_pages(self) -> int:
        """Pages whose refcount is held — by a live aliasing row (row
        lifetime) or a publish in flight. Never evictable. O(1): a running
        counter kept at the ref 0<->1 transitions."""
        return self._pinned

    def _ref(self, node: PageNode) -> None:
        node.refs += 1
        if node.refs == 1:
            self._pinned += 1

    def _unref(self, node: PageNode) -> None:
        node.refs -= 1
        if node.refs == 0:
            self._pinned -= 1

    def _walk(self):
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            yield node
            stack.extend(node.children.values())

    @staticmethod
    def chain_key(node: PageNode) -> tuple:
        """Full token-prefix tuple ending at ``node``'s block (root→node
        key concatenation) — the spill-arena / shared-index key: KV bytes
        are exact only for the identical whole prefix."""
        keys = []
        while node is not None and node.key is not None:
            keys.append(node.key)
            node = node.parent
        # edge keys are tuples of Python ints (publish and reload make them
        # from one converted list): a chain of a hundred blocks is a copy
        return tuple(itertools.chain.from_iterable(reversed(keys)))

    def walk(self, tokens) -> list[PageNode]:
        """The :meth:`match` walk WITHOUT refs, counters or clock ticks —
        the reload path peeks at where the device-resident chain ends
        before deciding what to pull back from the spill arena."""
        page = self.page
        max_blocks = (len(tokens) - 1) // page
        chain: list[PageNode] = []
        node = self.root
        for i in range(max_blocks):
            child = node.children.get(tuple(tokens[i * page : (i + 1) * page]))
            if child is None:
                break
            chain.append(child)
            node = child
        return chain

    def _set_pages_gauges(self) -> None:
        used = self.pages_in_use()
        self.tel.pages.set(used)
        self.tel.bytes.set(used * self.page_bytes)

    def _set_pinned_gauge(self) -> None:
        self.tel.pinned_pages.set(self.pinned_pages())

    def check(self, row_pages=None, held_snapshots: int = 0) -> None:
        """Structural invariants (tests + the eviction stress): every tree
        page is allocated exactly once and disjoint from the free list.

        ``row_pages``: iterable of live rows' aliased page-id sequences
        (their zero-copy page tables). Each referenced page must still be
        mapped in the tree AND ref-pinned — a page freed or unpinned while
        a live row reads KV through it is the aliasing bug class this
        extension exists to catch."""
        seen: dict[int, PageNode] = {}
        for node in self._walk():
            assert 0 <= node.page_id < self.capacity, node.page_id
            assert node.page_id not in seen, f"page {node.page_id} aliased"
            assert node.refs >= 0, f"negative refcount on page {node.page_id}"
            seen[node.page_id] = node
        free = set(self.free)
        assert not (seen.keys() & free), (
            f"tree/free overlap: {sorted(seen.keys() & free)}"
        )
        assert len(seen) + len(free) == self.capacity, (
            f"page leak: {len(seen)} in tree + {len(free)} free "
            f"!= {self.capacity}"
        )
        walked_pinned = sum(1 for n in seen.values() if n.refs > 0)
        assert self._pinned == walked_pinned, (
            f"pinned counter drift: running {self._pinned} "
            f"!= walked {walked_pinned}"
        )
        # a snapshot slot is free, held by a row (``held_snapshots`` of
        # them), or attached to exactly one node
        snaps = [n.snap for n in seen.values() if n.snap is not None]
        assert len(set(snaps)) == len(snaps), f"snapshot slot attached twice: {sorted(snaps)}"
        assert not (set(snaps) & set(self.snap_free)), "attached snapshot slot on the free list"
        assert len(snaps) + len(self.snap_free) + held_snapshots == self.snap_slots, (
            f"snapshot slot leak: {len(snaps)} attached + {len(self.snap_free)} free + "
            f"{held_snapshots} held != {self.snap_slots}"
        )
        wpages = [n.wpage for n in seen.values() if n.wpage is not None]
        assert len(set(wpages)) == len(wpages), f"window page attached twice: {sorted(wpages)}"
        assert not (set(wpages) & set(self.wfree)), "attached window page on the free list"
        assert len(wpages) + len(self.wfree) == self.window_pages, (
            f"window page leak: {len(wpages)} attached + {len(self.wfree)} free "
            f"!= {self.window_pages}"
        )
        for ids in row_pages or ():
            for pid in ids:
                assert pid not in free, (
                    f"page {pid} freed while a live row's page table "
                    "references it"
                )
                node = seen.get(pid)
                assert node is not None, (
                    f"page {pid} left the tree while a live row's page "
                    "table references it"
                )
                assert node.refs > 0, (
                    f"page {pid} unpinned while a live row aliases it "
                    "(eviction could recycle it mid-read)"
                )
        if self.spill is not None:
            # spill-tier exclusivity (ISSUE 11): only EVICTED pages live in
            # the arena. A chain is in the tree, PENDING in the arena (its
            # bytes on their way, ISSUE 38) or landed there: at most one. A
            # pinned (row-aliased or publish-held) page that
            # also had an arena entry under this owner would mean eviction
            # spilled a live page, a reload forgot to retire its source
            # entry, or a pending entry was not cancelled when its chain
            # came back — either way two copies of "the" bytes with no
            # single owner of truth
            self.spill.check()
            for node in seen.values():
                if node.refs > 0:
                    key = self.chain_key(node)
                    assert not self.spill.has(self.owner_id, key), (
                        f"pinned page {node.page_id} is simultaneously "
                        + ("pending in" if self.spill.is_pending(self.owner_id, key)
                           else "resident in")
                        + f" the spill arena (chain of {len(key)} tokens)"
                    )

    # ------------------------------------------------------------------
    # Match / release (admission)
    # ------------------------------------------------------------------

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def match(self, tokens, resumable: bool = False) -> list[PageNode]:
        """Longest chain of full-block matches STRICTLY shorter than the
        prompt (at least the last token always prefills — its logits seed
        the first sampled token). Acquires one ref per matched node; the
        pins last for the LIFETIME of the aliasing row (its attention
        reads the pages through its table every step), so the caller
        :meth:`release`\\ s the chain at row reset/quarantine — not after
        admission. ``resumable``: the row also needs its recurrent state
        where the chain ends, so the chain stops at the deepest matched
        block that has a snapshot and the matched pages past it are
        dropped (none has one: a miss). With a window pool the row needs the
        window layers' pages of the blocks before the chain's end
        (:meth:`tail_start`): the chain stops at the deepest block where all
        of them are still kept (a shorter hit, or a miss; counted by
        outcome), and those become the most recently used."""
        page = self.page
        chain = self.walk(tokens)
        if resumable:
            deepest = max((i for i, nd in enumerate(chain) if nd.snap is not None), default=-1)
            chain = chain[: deepest + 1]
        t = self._tick()
        if (self.window_tail or self.window_align) and chain:
            walked = len(chain)
            chain = chain[: self._deepest_with_tail(chain)]
            for nd in chain[self.tail_start(len(chain)):]:
                nd.w_use = t
            (self.tel.window_tail_hit if len(chain) == walked else
             self.tel.window_tail_shortened if chain else self.tel.window_tail_miss).inc()
        for nd in chain:
            self._ref(nd)
            nd.last_use = t
        if chain:
            self.tel.hits.inc()
            self.tel.matched_tokens.observe(len(chain) * page)
        else:
            self.tel.misses.inc()
        self._set_pinned_gauge()
        return chain

    def tail_start(self, b: int) -> int:
        """The first block whose window page a hit that ends where block ``b``
        begins needs (it needs those up to ``b - 1``): the ``window_tail``
        blocks before ``b`` for a sliding window, the blocks from the start
        of ``b``'s ALIGNED window for an EVA arch (none where ``b`` starts
        one: the summaries, which every block of the chain has, are enough)."""
        if self.window_align:
            return b // self.window_align * self.window_align
        return max(0, b - self.window_tail)

    def _deepest_with_tail(self, chain: list[PageNode]) -> int:
        """The largest ``b <= len(chain)`` such that the blocks
        ``tail_start(b) .. b - 1`` all have their window page; 0 when there
        is none."""
        run = 0  # blocks with a window page, counted back from block b
        best = 0
        for b, nd in enumerate(chain, start=1):
            run = run + 1 if nd.wpage is not None else 0
            if run >= b - self.tail_start(b):
                best = b
        return best

    def release(self, chain: list[PageNode]) -> None:
        for nd in chain:
            self._unref(nd)
        if chain:
            self._set_pinned_gauge()

    # ------------------------------------------------------------------
    # Publish (after a completed admission prefill)
    # ------------------------------------------------------------------

    def publish(
        self, tokens, n_total: int, parent_chain: list[PageNode]
    ) -> tuple[list[int], list[int]]:
        """Insert the full blocks of ``tokens[:n_total]`` beyond
        ``parent_chain`` into the tree. Returns ``(page_ids, block_idx)``
        of the NEWLY allocated pages — the scheduler copies those blocks
        out of the row; blocks already present (a concurrent request
        published them first) are refreshed, not re-copied. Allocation
        evicts LRU-unreferenced leaves for what the free list lacks and
        stops early (partial publish) when nothing more is evictable."""
        node = parent_chain[-1] if parent_chain else self.root
        page = self.page
        n_blocks = n_total // page
        ids = self._token_ids(tokens, n_blocks * page)
        new_ids: list[int] = []
        new_blocks: list[int] = []
        t = self._tick()
        # pin the whole growing chain for the duration of the walk: the
        # eviction below must not take a traversed node, and an unpinned
        # just-inserted node is a refcount-0 leaf — an evictor would detach
        # the very chain being built, double-allocating its page and
        # leaking the rest (reproduced: capacity-1 pool, 2-block publish)
        pinned: list[PageNode] = list(parent_chain)
        for nd in pinned:
            self._ref(nd)
        try:
            i = len(parent_chain)
            while i < n_blocks:
                # blocks a concurrent request published first
                child = node.children.get(tuple(ids[i * page : (i + 1) * page]))
                if child is None:
                    break
                self._ref(child)
                pinned.append(child)
                child.last_use = t
                node = child
                i += 1
            # every deeper block is new. What they need beyond the free
            # list comes from the LRU leaves, taken together: one walk, one
            # batch for the spill tier
            self._evict(self._pick_victims(n_blocks - i - len(self.free)))
            for i in range(i, n_blocks):
                if not self.free:
                    break  # budget exhausted and everything pinned
                pid = self.free.pop()
                child = PageNode(tuple(ids[i * page : (i + 1) * page]), pid, node)
                node.children[child.key] = child
                new_ids.append(pid)
                new_blocks.append(i)
                self._note_insert(child, tuple(ids[: (i + 1) * page]))
                self._ref(child)
                pinned.append(child)
                child.last_use = t
                node = child
        finally:
            for nd in pinned:
                self._unref(nd)
        self._set_pages_gauges()
        self._set_pinned_gauge()
        return new_ids, new_blocks

    # ------------------------------------------------------------------
    # Recurrent-state snapshots (archs with linear-attention layers)
    # ------------------------------------------------------------------

    def snapshot_slot(self) -> int | None:
        """A slot for a snapshot about to be taken: a free one, else the
        least recently used attached one (its node stays, a row can no
        longer resume there). None when the store has no slot to give."""
        if self.snap_free:
            return self.snap_free.pop()
        victim = min(
            (nd for nd in self._walk() if nd.snap is not None),
            key=lambda nd: nd.last_use, default=None,
        )
        if victim is None:
            return None
        slot, victim.snap = victim.snap, None
        self.tel.snapshots_evicted.inc()
        return slot

    def snapshot_attach(self, tokens, n_tokens: int, slot: int) -> bool:
        """Attach the snapshot in ``slot``, taken after ``tokens[:n_tokens]``
        (a whole number of blocks), to the node that ends there. False, and
        the slot goes back, when that block is not in the tree (a partial
        publish) or already has a snapshot."""
        # walk() stops one block short of its input's end (a match is strictly
        # shorter than the prompt): one token more reaches the last block
        chain = self.walk(list(tokens[:n_tokens]) + [0])
        if len(chain) * self.page != n_tokens or not chain or chain[-1].snap is not None:
            self.snap_free.append(slot)
            return False
        chain[-1].snap = slot
        self.tel.snapshots_published.inc()
        return True

    # ------------------------------------------------------------------
    # Window pages (archs with window-attention layers)
    # ------------------------------------------------------------------

    def attach_window_pages(
        self, tokens, n_total: int, first_block: int
    ) -> tuple[list[int], list[int]]:
        """Give the blocks ``first_block ..`` of the published prompt
        ``tokens[:n_total]`` that are in the tree and have no window page one
        each. Returns ``(window page ids, block indices)``: the scheduler
        copies those blocks' window-layer keys and values out of the row's
        rings. A page comes from the free list, else from the block whose
        page went longest without a hit using it (that block stays in the
        tree: a hit can no longer END within ``window_tail`` blocks after it,
        and is served shorter); never from a block this call has just given
        one (a pool smaller than one prompt's tail keeps its first blocks)."""
        chain = self.walk(list(tokens[:n_total]) + [0])  # one token more reaches the last block
        t = self._tick()
        wanted = [i for i in range(max(0, first_block), len(chain)) if chain[i].wpage is None]
        victims: list[PageNode] = []
        if len(wanted) > len(self.wfree):
            # one walk of the tree for the whole call, oldest use last (popped first)
            victims = sorted((nd for nd in self._walk() if nd.wpage is not None and nd.w_use < t),
                             key=lambda nd: nd.w_use, reverse=True)
        ids: list[int] = []
        blocks: list[int] = []
        for i in range(max(0, first_block), len(chain)):
            node = chain[i]
            if node.wpage is None:
                if self.wfree:
                    node.wpage = self.wfree.pop()
                elif victims:
                    victim = victims.pop()
                    node.wpage, victim.wpage = victim.wpage, None
                    self.tel.window_pages_evicted.inc()
                else:
                    break
                ids.append(node.wpage)
                blocks.append(i)
            node.w_use = t
        return ids, blocks

    def detach_window_pages(self, tokens, blocks: list[int]) -> None:
        """Unwind an :meth:`attach_window_pages` whose device copy failed to
        dispatch: the pages were never written."""
        chain = self.walk(list(tokens) + [0])
        for i in blocks:
            if i < len(chain):
                self._drop_window_page(chain[i])

    def _drop_window_page(self, node: PageNode) -> None:
        if node.wpage is not None:
            self.wfree.append(node.wpage)
            node.wpage = None

    def _drop_snapshot(self, node: PageNode) -> None:
        if node.snap is not None:
            self.snap_free.append(node.snap)
            node.snap = None
            self.tel.snapshots_evicted.inc()

    def unpublish(self, tokens, new_ids: list[int], new_blocks: list[int]) -> None:
        """Unwind a :meth:`publish` whose device copy failed to dispatch:
        detach the inserted sub-chain and return its pages to the free
        list. The pages were never written — leaving them mapped would
        serve garbage (or a recycled prefix's stale) KV to every future
        match. ``new_blocks`` is a contiguous tail by construction (once
        publish creates a node, every deeper block is new too), so
        detaching the FIRST new node drops the whole sub-chain."""
        if not new_ids:
            return
        page = self.page
        node = self.root
        for i in range(new_blocks[0]):
            node = node.children[tuple(tokens[i * page : (i + 1) * page])]
        first = new_blocks[0]
        key = tuple(tokens[first * page : (first + 1) * page])
        detached = node.children.pop(key)
        # freshly-inserted nodes can't have been matched (both happen under
        # the scheduler lock), so their refs are 0 — but keep the running
        # pinned counter exact against any future lifecycle change
        stack = [detached]
        while stack:
            nd = stack.pop()
            if nd.refs > 0:
                self._pinned -= 1
            self._drop_snapshot(nd)
            self._drop_window_page(nd)
            if self.shared_index is not None:
                # the publish already announced these chains; an unwound
                # publish must retract them or placement routes to pages
                # that were never written
                self.shared_index.withdraw(self.owner_id, self.chain_key(nd))
            stack.extend(nd.children.values())
        self.free.extend(new_ids)
        self._set_pages_gauges()
        self._set_pinned_gauge()

    # ------------------------------------------------------------------
    # Allocation / LRU eviction
    # ------------------------------------------------------------------

    @staticmethod
    def _token_ids(tokens, n: int) -> list[int]:
        """``tokens[:n]`` as Python ints, converted ONCE: a chain key is a
        slice of this (a deep chain's key is thousands of tokens, and a
        publish or reload makes one a block)."""
        head = tokens[:n]
        return head.tolist() if hasattr(head, "tolist") else [int(t) for t in head]

    def _pick_victims(self, k: int) -> list[PageNode]:
        """The ``k`` nodes (fewer when fewer are evictable) that reclaiming
        the least-recently-used unreferenced LEAF ``k`` times over would
        take, in that order, from ONE walk of the tree: children keep their
        ancestors alive (evicting an interior page would strand the chain
        below it), so an interior node becomes a candidate when its last
        child has been picked. Equal ``last_use`` goes by the walk's order,
        as the one-by-one scan did. Nothing is detached here; the order
        holds for as long as the caller changes the tree only by evicting
        these and by inserting pinned nodes."""
        if k <= 0:
            return []
        order: dict[PageNode, int] = {}
        heap = []
        for i, node in enumerate(self._walk()):
            order[node] = i
            if not node.children and node.refs == 0:
                heap.append((node.last_use, i, node))
        heapq.heapify(heap)
        picked_under: dict[PageNode, int] = {}
        victims: list[PageNode] = []
        while heap and len(victims) < k:
            node = heapq.heappop(heap)[2]
            victims.append(node)
            parent = node.parent
            if parent is not self.root and parent.refs == 0:
                picked_under[parent] = picked_under.get(parent, 0) + 1
                if picked_under[parent] == len(parent.children):
                    heapq.heappush(heap, (parent.last_use, order[parent], parent))
        return victims

    def _evict_one(self) -> bool:
        """Reclaim the least-recently-used unreferenced leaf. Returns False
        when every leaf is pinned."""
        victims = self._pick_victims(1)
        self._evict(victims)
        return bool(victims)

    def _evict(self, victims: list[PageNode]) -> None:
        """Detach ``victims`` (:meth:`_pick_victims`' order) and free their
        pages; the first victim's page is the next one allocated. With a
        spill arena attached their pages are sliced into fresh device
        buffers first: launches that are ENQUEUED, never waited for, which
        read the pool as it is before any later publish can recycle a page
        id (device ordering keeps that exact), so the ids go back on the
        free list at once. The bytes reach the arena on its spiller thread."""
        if not victims:
            return
        spilling = self.spill is not None and self.page_fetch is not None
        # of victims put in this order the arena ends holding the last
        # ``kept``: only those are sliced and fetched
        kept = self.spill.keeps(len(victims), self.page_bytes) if spilling else 0
        first = len(victims) - kept
        keys = [
            self.chain_key(v) if self.shared_index is not None or i >= first else None
            for i, v in enumerate(victims)
        ]
        if spilling:
            try:
                handles = self.page_fetch([v.page_id for v in victims[first:]])
            except Exception as e:
                # spilling is an optimization: a failed launch degrades
                # to the PR 4 behavior (the pages simply vanish)
                print(f"⚠️ page spill failed; evicting without it: {e}")
            else:
                self.spill.put_pending(
                    self.owner_id, keys[first:], handles, self.page_bytes,
                    self.page_land, skipped=first,
                )
                self.tel.spill_pages.inc(len(victims))
            self._set_spill_gauges()
        for victim, key in zip(victims, keys):
            if self.shared_index is not None:
                self.shared_index.withdraw(self.owner_id, key)
            del victim.parent.children[victim.key]
            self._drop_snapshot(victim)  # snapshot and page go together
            self._drop_window_page(victim)
            self.tel.evictions.inc()
        self.free[:0] = [v.page_id for v in reversed(victims)]
        self._set_pages_gauges()

    # ------------------------------------------------------------------
    # Spill tier (ISSUE 11, engine/spill.py): reload = publish in reverse
    # ------------------------------------------------------------------

    def _note_insert(self, node: PageNode, key: tuple) -> None:
        """A node entered the tree (publish or reload) under chain ``key``:
        announce the chain to the shared index, and retire any own arena
        entry, landed or pending (a pending one is cancelled: its bytes are
        discarded when they arrive) — the fresh device copy supersedes it
        (the exclusivity invariant check() asserts)."""
        if self.spill is None and self.shared_index is None:
            return
        if self.spill is not None:
            self.spill.drop(self.owner_id, key)
            self._set_spill_gauges()
        if self.shared_index is not None:
            self.shared_index.publish(self.owner_id, key)

    def _set_spill_gauges(self) -> None:
        self.tel.spill_resident_pages.set(self.spill.depth())
        self.tel.spill_bytes.set(self.spill.resident_bytes)

    def await_pending(self, tokens) -> None:
        """Called by a request's own thread BEFORE it takes the scheduler's
        lock to reload: wait (bounded; off every lock) for pages of this
        prompt's prefixes that an eviction sent on their way to the arena a
        moment ago, so that they reload instead of prefilling cold. The one
        method of this class that runs without the scheduler's lock: it
        reads no tree state."""
        if self.spill is not None:
            self.spill.wait_pending(tokens)

    def spill_depth(self) -> int:
        """Arena entries owned by this replica (the /readyz read)."""
        return 0 if self.spill is None else self.spill.depth(self.owner_id)

    def spill_take(self, chain: tuple):
        """One reload read: the owner's own entry MOVES back out of the
        arena; another replica's entry is COPIED (cross-replica sharing —
        the spiller keeps serving other readers). A CRC mismatch drops
        the corrupt entry and counts it, then the PEER lookup still runs
        — a bit flip in one replica's copy must not defeat the redundancy
        the shared arena exists for; only when no intact copy survives
        anywhere does the read miss (cold prefill, never stale KV)."""
        if self.spill is None:
            return None
        arrays = None
        try:
            arrays = self.spill.take(self.owner_id, chain)
        except SpillCorrupt as e:
            # own copy corrupt + dropped (counted); try the peers. The
            # flight recorder keeps the CRC verdict (ISSUE 16): a later
            # replica death dump shows whether its spilled KV was rotting
            flight.record(
                self.owner_id, "spill_crc_drop", error=str(e),
            )
        if arrays is None:
            arrays = self.spill.peek_shared(chain, exclude_owner=self.owner_id)
        self._set_spill_gauges()
        return arrays

    def spill_corrupt(self, chain: tuple) -> None:
        """Chaos hook (``engine.spill`` ``kind=corrupt``): flip bytes of
        the resident entries for ``chain`` in place."""
        if self.spill is not None:
            self.spill.corrupt(chain)

    def reload(self, tokens, upload, pre=None) -> int:
        """Extend the device-resident chain of ``tokens`` from the spill
        arena — the :meth:`publish` machinery in reverse: per missing
        block (deepest-first from where :meth:`walk` ends, bounded like
        match at full blocks strictly shorter than the prompt) take the
        spilled bytes, allocate a pool page (may itself evict+spill), run
        the caller's ``upload(page_id, arrays)`` device copy, and insert
        the node. ``pre(chain_key)`` is the scheduler's ``engine.spill``
        chaos hook. A block whose bytes are still PENDING in the arena is a
        miss here (nothing waits under the scheduler's lock; the caller
        waited before it took the lock, :meth:`await_pending`): it
        prefills cold, counted, and its publish cancels the pending entry.
        ANY failure — arena miss, CRC drop, allocation dry,
        an upload raise, an injected fault — stops the reload cleanly:
        blocks already uploaded stay (they hold verified bytes), deeper
        blocks fall back to the cold prefill, pins taken for the walk are
        released. Returns the number of pages reloaded."""
        if self.spill is None:
            return 0
        page = self.page
        max_blocks = (len(tokens) - 1) // page
        nodes = self.walk(tokens)
        if len(nodes) >= max_blocks:
            return 0
        node = nodes[-1] if nodes else self.root
        # pin the growing chain exactly like publish: a mid-reload
        # eviction must never detach the chain being
        # rebuilt (or the just-walked parents)
        pinned: list[PageNode] = list(nodes)
        for nd in pinned:
            self._ref(nd)
        n_reloaded = 0
        ids = self._token_ids(tokens, max_blocks * page)
        # the victims of every block still to come, in order, picked in one
        # walk when the free list first runs dry; each is evicted (and
        # spilled) only when its block's turn comes, so the arena sees this
        # reload's puts and takes in the order it always did
        victims = None
        try:
            for i in range(len(nodes), max_blocks):
                chain = tuple(ids[: (i + 1) * page])
                try:
                    if pre is not None:
                        pre(chain)
                    # alloc BEFORE taking the entry: spill_take MOVES the
                    # owner's bytes out of the arena, so an allocation
                    # failure after it would permanently lose them — and
                    # a dry pool is likeliest exactly under the pinned
                    # pressure the spill tier exists for. The chain being
                    # reloaded is not in the tree, so the eviction this
                    # may trigger cannot touch it.
                    if not self.free:
                        if victims is None:
                            victims = iter(self._pick_victims(max_blocks - i))
                        self._evict(list(itertools.islice(victims, 1)))
                    if not self.free:
                        break  # everything pinned: no room to reload into
                    pid = self.free.pop()
                    arrays = self.spill_take(chain)
                    if arrays is None:
                        self.free.append(pid)
                        break
                    try:
                        upload(pid, arrays)
                    except Exception:
                        self.free.append(pid)
                        # only the upload failed — the bytes themselves
                        # are verified-good: restore the entry so a later
                        # match can retry instead of cold-prefilling the
                        # chain forever
                        self.spill.put(self.owner_id, chain, arrays)
                        raise
                except Exception as e:
                    # an injected engine.spill raise or a failed upload
                    # dispatch: the remaining blocks prefill cold
                    # (interpreter exits are not Exception and propagate)
                    print(f"⚠️ spill reload aborted; prefilling cold: {e}")
                    flight.record(
                        self.owner_id, "spill_reload_abort",
                        reloaded=n_reloaded, error=type(e).__name__,
                    )
                    break
                key = tuple(ids[i * page : (i + 1) * page])
                child = PageNode(key, pid, node)
                node.children[key] = child
                child.last_use = self._tick()
                self._note_insert(child, chain)
                self._ref(child)
                pinned.append(child)
                node = child
                n_reloaded += 1
                self.tel.spill_reloads.inc()
        finally:
            for nd in pinned:
                self._unref(nd)
        if n_reloaded:
            self._set_pages_gauges()
            self._set_pinned_gauge()
        return n_reloaded
