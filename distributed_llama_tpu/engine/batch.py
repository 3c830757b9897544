"""Batched multi-stream decode: one weight read per step for B requests.

Decode is HBM-bound — the weight bytes dominate every step (docs/PERF.md) —
so ``--parallel N`` serving built on N independent single-sequence dispatches
buys fairness, not tokens: the dispatches queue on the device stream and
each one re-reads every weight matrix (measured 97.3 tok/s aggregate vs
95.8 single-stream, round 5). Batching the step over B sequences amortizes
each weight read across all active requests — the Orca/vLLM
continuous-batching insight — for near-B× aggregate throughput at modest B
with no new hardware.

Architecture
------------
* :class:`BatchScheduler` owns ONE slab KV cache
  (``llama.init_batch_cache``: per-layer ``(keys, values)`` halves with a
  leading ``[B_max]`` batch axis) and coalesces every joined stream's next
  chunk into ONE batched dispatch
  (``sampling.decode_chunk_batched`` / the tp backend's
  ``batched_decode_chunk``).
* :class:`BatchStream` is one slab row wearing the
  :class:`~distributed_llama_tpu.engine.engine.EngineStream` serving
  surface (``prefill_device`` / ``stream_decode`` / ``rollback`` / ...), so
  the API server's ``StreamSlot``s submit into the shared scheduler without
  changing the completion flow (SSE streaming, per-request stop/seed and
  the chat-prefix NaiveCache all ride on top unchanged).
* Requests join and leave BETWEEN chunks without recompiling: dispatches
  run at fixed power-of-two row buckets (1/2/4/8..., mirroring
  ``_prefill_bucket``) with an active-row mask — an inactive row decodes
  garbage into a DROPPED cache write (``kv_cache.update_row_batched``), so
  a retired slot's cache stays byte-identical for its next prefix reuse.
* Prefill stays per-request: ``_slab_prefill`` runs the ordinary
  single-sequence forward on the stream's slab row (extracted and
  re-inserted inside the jitted program; the donated slab aliases in
  place), reusing the whole blocked-attention/i8/bucketing machinery.
  Long prompts dispatch in ``prefill_chunk``-token pieces with the
  scheduler lock released between them, so other rows' decode chunks
  interleave with a long prefill (Sarathi-style; ISSUE 4 satellite).
* With ``prefix_cache=True`` the scheduler also owns a page pool
  (``llama.init_page_pool`` single-chip, the tp engine's sharded pool on
  multi-chip) and a radix tree over token blocks (``engine/
  prefix_cache.py``): an admission prefill (row position 0) finds its
  longest published prefix and only the unmatched suffix prefills into
  the slab row; completed full pages are published back. Where the
  matched prefix lives while the row decodes differs by backend. On ONE
  CHIP the matched pages are **copied into the row once, at admission**
  (``_restore_pages``: block b at slots ``[b*page, (b+1)*page)``, the
  pool's own bytes), and the row's programs are handed an EMPTY read
  alias (zero table, ``matched`` 0): every decode, verify and prefill
  step reads ONE source, the slab, as for a cold row (ISSUE 40: read in
  place, every chunk of every decode step paid a pool gather and a
  per-position select beside the slab read as soon as one live row had
  no hit). The TP backend still binds the pages to the row as
  ``(page_ids, matched_len)`` and its attention reads those positions
  **in place through the page table over the sharded pool**
  (ops.attention paged variants). Either way the matched chain stays
  ref-pinned for the ROW'S LIFETIME (released at reset/quarantine/
  rollback-truncation), so eviction can never recycle a page a live tp
  row is attending over — chaos-enforced, and a prefix-hit stream is
  bit-identical to the cold prefill (tests/test_prefix_cache.py,
  tests/test_paged_attention.py, tests/test_prefix_restore.py).
* Per-row seeds, temperatures, top-p and top-k ride the batched program;
  sampling is fused into the scan on counter-PRNG coins keyed
  ``(seed, position)`` (ISSUE 13), so a row's token stream is
  bit-identical to the single-stream chunked decode for the same request
  seed (tests/test_batch_decode.py), requests with different sampling
  settings share one compiled program, and no sampler state exists for
  the scheduler to thread — a requeued or failed-over row re-draws its
  coins from its seed and positions alone.
  (MoE models: the batched step uses dense expert mixing — parity holds up
  to expert-sum reordering, and expert HBM reads amortize only once
  B ≥ E/k; see ``llama.forward_step_batched``.)
* What a chunk needs of each row reaches the device as WHOLE vectors, and
  the host issues the same handful of device operations a chunk at 1 row
  and at 32: the token each row's next chunk feeds first lives on the
  device in ONE carry vector (int32 ``[B_max]``) that the chunk program
  takes donated and returns advanced, a join writes its row's entry
  (:func:`_carry_put`), and the row parameters are filled into host numpy
  buffers that cross with the dispatch. No per-row device operation is
  issued under the scheduler's lock (tests/test_batch_decode.py counts).

Thread model: request threads call into their own :class:`BatchStream`;
whichever thread needs tokens first becomes the dispatcher for everyone
(dispatch under the scheduler condition lock — cheap, asynchronous — then
the blocking fetch outside it). Joins/leaves take the same lock, so the
active set is coherent per dispatch; an epoch counter per stream keeps a
late fetch from delivering a previous request's tokens to a new occupant.
A decode dispatch never waits for the device with the lock held, and the
device's queue keeps one order: at most one decode chunk on it, and the
prompt pieces that were queued when a chunk was delivered run before the
next chunk is enqueued (``_dispatch_locked``).
"""

from __future__ import annotations

import collections
import functools
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu import lockcheck, retry, telemetry
from distributed_llama_tpu.engine import faults, integrity
from distributed_llama_tpu.engine.engine import TokenStats, _prefill_bucket, next_pow2
from distributed_llama_tpu.engine.speculative import PromptLookupDrafter
from distributed_llama_tpu.models import llama
from distributed_llama_tpu.models.config import LlamaConfig
from distributed_llama_tpu.ops import kv_cache as kvc
from distributed_llama_tpu.telemetry import Stopwatch, device_ledger, flight


# pool pages per recurrent-state snapshot slot: `--kv-pages` // 16 slots. A
# prompt's pages share ONE snapshot (taken at its last page boundary), so the
# pool needs a slot for every prompt it can hold, not for every page: at the
# default page of 64 tokens 16 pages are 1024 tokens, half of the context a
# row serves by default, so a pool of P pages has a slot for each of the P/16
# prompts of that length it holds. Shorter prompts run out of slots before
# pages (a row then publishes without one and nothing resumes there); a slot
# is a row's whole state, some tens of pages' worth, so one a page would
# multiply the pool's memory.
SNAPSHOT_PAGES = 16


def decode_bucket(n: int, b_max: int) -> int:
    """Power-of-two row bucket covering rows 0..n-1 (capped at b_max): one
    compiled batched program per bucket, holes masked inactive."""
    return min(next_pow2(n), b_max)


def _page_bucket(n: int) -> int:
    """Power-of-two padding for page-id arrays: one compiled gather/publish
    program per bucket, padded entries dropped by out-of-bounds indices."""
    return next_pow2(n)


def eva_tail_pages(rows: int, window_pages: int) -> int:
    """Pages of an EVA arch's window pool (keys and values of whole pages),
    the rule stated once: a live prompt ends anywhere in its aligned window of
    ``window_pages`` pages, so its tail is half a window in the mean and a
    whole one at worst; the pool holds the mean for every row and one whole
    window beside them. A tail that has aged out costs a hit its last pages:
    it resumes at the window's start, from summaries alone."""
    return rows * (window_pages // 2) + window_pages


def _padded_pages(ids: list[int], blocks: list[int], drop: int):
    """``(page ids, source blocks)`` of a publish as int32 arrays padded to
    their bucket; a padded entry names page ``drop``, past the pool, and its
    write is dropped."""
    bucket = _page_bucket(len(ids))
    padded = np.full(bucket, drop, np.int32)
    src = np.zeros(bucket, np.int32)
    padded[: len(ids)] = ids
    src[: len(blocks)] = blocks
    return padded, src


@jax.jit
def _slice_page(pool, pid):
    """One pool page's bytes across every layer/half as a flat list of
    FRESH device buffers (the spill-entry layout of
    kv_cache.download_pool_page) — ONE compiled program per spilled page,
    launched under the scheduler cond and never waited for there: the
    buffers outlive the pool's next donation, and the arena's spiller
    thread fetches them (``pid`` crosses as a numpy scalar with the
    dispatch)."""
    out = []
    for halves in filter(None, pool):  # (keys, values), or a latent layer's one
        for half in halves:
            out.extend(kvc.slice_pool_page(half, pid))
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _carry_put(carry, row, token):
    """Write ``token`` (a joining row's first decode token: the fused
    device scalar of ``prefill_device``, or a host int) into entry ``row``
    of the scheduler's carry vector. The donated carry aliases in place;
    on the device queue the write lands behind a chunk still in flight, so
    it also overwrites what a previous occupant's orphaned chunk left."""
    return carry.at[row].set(token)


@functools.partial(jax.jit, donate_argnums=(0,))
def _upload_page(pool, pid, page_kvs):
    """Write one spilled page's host byte arrays into pool page ``pid``
    across every layer — the spill-tier reload, :func:`_publish_pages` in
    reverse (ISSUE 11). ``page_kvs`` is per layer a pair of flat
    array lists (``[data]``, or ``[data, scales]`` for i8 — the
    download's verbatim layout). The donated pool aliases in place."""
    kvs = iter(page_kvs)  # one entry per layer that HAS keys and values
    return [
        None if half is None else tuple(
            kvc.upload_pool_page(p, pid, h) for p, h in zip(half, next(kvs))
        )
        for half in pool
    ]


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("base",), donate_argnums=(2,))
def _publish_pages(page: int, slab, pool, page_ids, src_page, row, base: int = 0):
    """Copy slab row ``row``'s blocks ``src_page`` into pool pages
    ``page_ids`` across every layer (the post-prefill publish;
    ``kvc.BlockSlots(page, base=base)`` says where a block sits). The donated
    pool aliases in place; the slab is read-only here, and read as it is
    stored: one gather a half takes half, row and slots out of the fused leaf
    (``kvc.publish_leaf_pages``), so nothing but the pages published is read
    or formed (a latent layer's leaf and pool entry hold one array each, two
    where the layer has an indexer: its index keys ride every page)."""
    return [
        None if half is None
        else kvc.publish_latent_pages(half, leaf, row, src_page, page_ids, page)
        if kvc.is_latent_leaf(leaf)
        else kvc.publish_leaf_pages(*half, leaf, row, src_page, page_ids, page, base=base)
        for leaf, half in zip(slab, pool)
    ]


@functools.partial(jax.jit, donate_argnums=(0,))
def _restore_pages(slab, pool, page_ids, second, row):
    """:func:`_publish_pages` in reverse, for a prefix hit on one chip: a
    chain's pool pages into blocks ``0 .. n - 1`` of slab row ``row`` across
    every layer that has a pool half. ``page_ids`` is ``[2, h]``, ``h`` the
    largest power of two that is at most ``n``: the chain's first ``h`` pages
    go to blocks ``0 .. h - 1`` and its last ``h`` to blocks ``second ..
    n - 1`` (``second = n - h``), two contiguous writes a leaf
    (``kvc.restore_row_blocks``). Every entry is a page of the chain at its
    own block (where the runs overlap a block is written twice with the same
    bytes: a hit copies ``2 h`` pages' bytes, between one and two times its
    own), so no shape has padding and nothing but those blocks of that row
    changes; one program a power of two. The bytes are the pool's own, so the
    row is a cold prefill's; from here on its programs are handed an empty
    read alias and read the slab alone. Only the slab is donated."""
    telemetry.note_kernel_path("paged_attention", "slab_restored")
    out = []
    for leaf, half in zip(slab, pool):
        if half is not None and kvc.is_latent_leaf(leaf):
            leaf = kvc.restore_latent_blocks(leaf, half, row, 0, page_ids[0])
            leaf = kvc.restore_latent_blocks(leaf, half, row, second, page_ids[1])
        elif half is not None:
            leaf = kvc.restore_row_blocks(leaf, half[0], half[1], row, 0, page_ids[0])
            leaf = kvc.restore_row_blocks(leaf, half[0], half[1], row, second, page_ids[1])
        out.append(leaf)
    return out


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("ring",), donate_argnums=(2,))
def _publish_window_pages(page: int, slab, wpool, page_ids, src_page, row, *, ring: int):
    """:func:`_publish_pages` for the window layers: slab row ``row``'s blocks
    ``src_page``, read out of the rings at their positions' slots
    (``kvc.BlockSlots(page, ring)``), into pages ``page_ids`` of the window
    pool, each half by one gather on the fused leaf as it is stored.
    ``wpool`` mirrors the slab's layer list (None for a layer of another
    kind). Only the pool is donated."""
    return [
        None if half is None
        else kvc.publish_leaf_pages(*half, leaf, row, src_page, page_ids, page, ring=ring)
        for leaf, half in zip(slab, wpool)
    ]


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("ring",), donate_argnums=(1,))
def _restore_window_tail(page: int, slab, wpool, page_ids, dst_page, row, *, ring: int):
    """:func:`_publish_window_pages` in reverse: pages ``page_ids`` of the
    window pool into the slots of row ``row``'s rings where blocks
    ``dst_page`` sit (a prefix hit resumes behind them). Only the slab is
    donated."""
    return [
        leaf if half is None else kvc.restore_row_pages(
            leaf, half[0], half[1], row, dst_page, page_ids, page, ring=ring
        )
        for leaf, half in zip(slab, wpool)
    ]


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("base",), donate_argnums=(1,))
def _restore_summaries(page: int, slab, pool, page_ids, dst_page, row, *, base: int):
    """:func:`_publish_pages` in reverse, for an EVA arch: the summaries of
    blocks ``dst_page`` (``page`` of them a block) out of pool pages
    ``page_ids`` into row ``row``, behind its window store (a hit copies them;
    the pool is not read in place). Only the slab is donated."""
    return [
        kvc.restore_row_pages(leaf, half[0], half[1], row, dst_page, page_ids, page, base=base)
        for leaf, half in zip(slab, pool)
    ]


@functools.partial(jax.jit, donate_argnums=(1,))
def _snapshot_take(slab, store, row, slot):
    """Copy slab row ``row``'s recurrent state (every linear layer's state
    and convolution tail) into snapshot slot ``slot``. ``store`` mirrors
    the slab's layer list: a state leaf of ``[slots, ...]`` arrays for a
    linear layer, None for a softmax one. The donated store aliases in
    place; the slab is read-only here."""
    return [
        None if snap is None else kvc.fused_put_row(snap, kvc.fused_take_row(leaf, row), slot)
        for leaf, snap in zip(slab, store)
    ]


@functools.partial(jax.jit, donate_argnums=(0,))
def _snapshot_restore(slab, store, row, slot):
    """:func:`_snapshot_take` in reverse: slot ``slot`` into slab row
    ``row`` (a prefix hit resumes there). Only the slab is donated."""
    return [
        leaf if snap is None else kvc.fused_put_row(leaf, kvc.fused_take_row(snap, slot), row)
        for leaf, snap in zip(slab, store)
    ]


def _moe_of_piece(counts, paths, n_real):
    """What a prefill chunk's expert layers did, as ONE int32 [3] (one fetch
    reads it all): the expert choices of its REAL tokens that fell on a held
    expert (0 for an arch that holds every expert), how many of the layers
    ran every expert over every row, and how many ran each expert over its
    own bucket; [4] for an arch that holds a share: and the rows the held
    experts' launches multiplied. None for an arch with no expert layer."""
    if not paths:
        return None
    held = jnp.int32(0)
    if counts:
        held = jnp.sum(jnp.where(jnp.arange(counts[0].shape[0]) < n_real, counts[0], 0))
    return jnp.concatenate([held[None], paths[0]])


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
def _slab_prefill_single(cfg: LlamaConfig, params, tokens, slab, row, pos, n_real):
    """Prefill ``tokens`` into slab row ``row`` (single chip): the row is
    extracted as an ordinary single-stream fused cache, run through the
    normal forward (blocked attention, i8 quantization, MoE bucketing,
    coalesced K/V updates — all reused), and written back; the donated slab
    aliases every other row in place. Returns (logits [T, vocab], new slab)."""
    row_cache = [kvc.fused_take_row(leaf, row) for leaf in slab]
    counts = [] if cfg.n_routed_experts else None
    paths = [] if cfg.is_moe else None
    logits, new_rows = llama.forward_tokens(
        cfg, params, tokens, row_cache, pos, n_real=n_real, held_counts=counts,
        piece_paths=paths,
    )
    new_slab = [
        kvc.fused_put_row(leaf, new_leaf, row)
        for leaf, new_leaf in zip(slab, new_rows)
    ]
    return logits, new_slab, _moe_of_piece(counts, paths, n_real)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
def _slab_prefill_single_paged(
    cfg: LlamaConfig, params, tokens, slab, pool, row, pos, n_real, table, matched
):
    """:func:`_slab_prefill_single` with a read alias: the row's attention
    reads positions below ``matched`` from the page pool through ``table``.
    The scheduler hands it ``matched`` 0 and a zero table (a hit's pages were
    copied into the row: the select takes the slab's byte at every
    position); the program is the one every pool-enabled prefill runs. The
    pool is read-only — only the slab is donated."""
    row_cache = [kvc.fused_take_row(leaf, row) for leaf in slab]
    counts = [] if cfg.n_routed_experts else None
    paths = [] if cfg.is_moe else None
    logits, new_rows = llama.forward_tokens(
        cfg, params, tokens, row_cache, pos, n_real=n_real,
        paged=(pool, table, matched), held_counts=counts, piece_paths=paths,
    )
    new_slab = [
        kvc.fused_put_row(leaf, new_leaf, row)
        for leaf, new_leaf in zip(slab, new_rows)
    ]
    return logits, new_slab, _moe_of_piece(counts, paths, n_real)


class BatchStream:
    """One slab row of a :class:`BatchScheduler`, wearing the EngineStream
    serving surface. All mutable request state (position, queue, sampler
    settings) lives here, but for the token its next chunk feeds first,
    which stays on the device in the scheduler's carry vector; the
    scheduler snapshots the rest per batched dispatch under its lock."""

    def __init__(self, scheduler: "BatchScheduler", row: int):
        self.scheduler = scheduler
        self.row = row
        self.pos = 0
        self.stats: list[TokenStats] = []
        # register with the engine's stream list: the TP transfer-refresh
        # cadence counts tokens across ALL streams' stats, and batched
        # serving must keep driving the periodic re-measurement
        engine = scheduler.engine
        engine._streams.append(self)
        engine._tel.active_streams.set(len(engine._streams))
        self._queue: collections.deque[int] = collections.deque()
        # tokens queued since the join: at _leave, delivered - len(queue) is
        # what the stream consumed (the row-step ledger, counted per leave,
        # never per pop)
        self._delivered = 0
        self._joined = False
        # the position at which this row's request stops asking for tokens
        # (stream_decode sets it): a joined row below it needs another chunk,
        # which is what the completion ledger calls work in hand
        self._stop_pos = engine.cfg.seq_len
        self._epoch = 0  # bumped per join/leave: stale fetches can't deliver
        self._seed32 = 0  # folded uint32 request seed (stateless counter PRNG)
        self._temperature = 0.0
        self._topp = 0.9
        self._topk = 0
        self._pending_prefill_entry: TokenStats | None = None
        self._depth_held = False
        # per-request deadline (time.monotonic seconds) set by the serving
        # layer: the scheduler retires an expired row BETWEEN chunks and its
        # next_token raises DeadlineExceeded (ISSUE 3)
        self.deadline: float | None = None
        # multi-tenant serving (ISSUE 8): the serving layer labels the row
        # with its request's tenant and priority for the lifetime of the
        # request (cleared between requests). ``priority is not None``
        # marks the row an active preemption candidate: preempt_below may
        # evict it for a strictly-higher-priority arrival
        self.tenant: str | None = None
        self.priority: int | None = None
        # request trace (ISSUE 16): the serving layer hands the row its
        # request's TraceContext for the request's lifetime (cleared
        # between requests). The scheduler's shared dispatch/fetch paths
        # fan per-row child spans into it — one attribute check when None
        self.trace = None
        # per-request prefix-cache opt-out (the API body's `cache: off`):
        # False skips BOTH the admission match and the post-prefill publish
        # for this row (ISSUE 4); serving restores True between requests
        self.prefix_cache_enabled = True
        # the admission match binds the matched radix chain to this row:
        # positions below ``matched_len`` were not prefilled but taken from
        # pages ``_alias_ids`` of the shared pool: copied into the slab row
        # at admission on one chip (ISSUE 40), read in place through the
        # row's page table on the tp backend (ISSUE 7). ``_alias_chain``
        # holds the ref-pinned PageNodes for the row's lifetime; the
        # scheduler releases them at reset/quarantine and truncates them on
        # rollback below ``matched_len`` (all under its cond lock)
        self._alias_chain: list = []
        self._alias_ids: list[int] = []
        self.matched_len = 0
        # an arch with linear-attention layers: the slot holding this row's
        # state snapshot between taking it (at the last page boundary of the
        # admission prefill) and publishing it with the chain; and whether
        # the device state may have run past ``pos`` (a decode chunk computes
        # whole chunks), after which only a rewind to 0 is possible
        self._snap_slot: int | None = None
        self._snap_at: int | None = None
        self._state_ahead = False
        # speculative decode (scheduler spec mode): this row's host-side
        # prompt-lookup corpus (prompt + emitted tokens, extended at chunk
        # delivery) and its lazily-built drafter. ``_spec_on`` False rides
        # the shared verify dispatches with ZERO drafts — a plain decode
        # step on the same weight read, which is how spec and non-spec
        # requests mix in one slab
        self._history: list[int] = []
        self._drafter: PromptLookupDrafter | None = None
        self._spec_on = False
        # per-chunk device logit fingerprints (ISSUE 10), in delivery
        # order (the fetch-ownership design delivers chunk N strictly
        # before N+1), reset at _join so one request = one sequence. A
        # RUNNING fold would be race-dependent — the pipelined chunk
        # dispatched ahead of the stream's last consumed token may or may
        # not deliver before the stream leaves — so readers fold a
        # deterministic PREFIX via run_fingerprint(n_tokens). The
        # spec-verify path does not feed it (stays empty)
        self._chunk_fps: list[int] = []
        # a chunk failure retires ONLY this row (faults.RowQuarantined /
        # StallTimeout / DeadlineExceeded, set by the scheduler under its
        # lock); next_token raises it, surviving co-batched rows keep
        # streaming — this replaces the seed's poison-every-stream behavior
        self._fetch_error: BaseException | None = None

    @property
    def cfg(self):
        return self.scheduler.engine.cfg

    @property
    def engine(self):
        return self.scheduler.engine

    # ------------------------------------------------------------------
    # EngineStream-compatible lifecycle
    # ------------------------------------------------------------------

    def reset(self) -> None:
        self.scheduler._leave(self)
        # release the row's page pins: the next occupant matches its own
        # chain, and the old pages become evictable once no other row
        # holds them
        self.scheduler._release_row_pins(self)
        self.pos = 0
        # same cadence no-op contract as EngineStream.reset(): clearing this
        # stream's stats shrinks the engine-wide token sum, so the transfer
        # watermark shifts down by the same amount
        cleared = sum(s.n_tokens for s in self.stats)
        engine = self.engine
        with engine._depth_lock:
            engine._transfer_measured_at -= cleared
        self.stats.clear()
        self._release_depth()
        self._pending_prefill_entry = None
        self._fetch_error = None
        self.deadline = None
        self.prefix_cache_enabled = True
        self.tenant = None
        self.priority = None
        self._history = []
        self._drafter = None
        self._spec_on = False

    def run_fingerprint(self, n_tokens: int | None = None) -> int:
        """FNV-1a fold of this request's chunk fingerprints (ISSUE 10).
        ``n_tokens`` folds only the chunks that produced the first
        ``n_tokens`` DECODED tokens (the fused first token is sampled
        pre-chunk and carries no fingerprint) — deterministic no matter
        how many speculative chunks the pipeline delivered beyond them,
        which is what lets the integrity canary compare the value against
        a golden. ``None`` folds everything delivered so far."""
        fps = self._chunk_fps
        if n_tokens is not None:
            fps = fps[: -(-max(0, n_tokens) // self.scheduler.chunk)]
        out = integrity.FP_BASIS
        for fp in fps:
            out = integrity.fold_run_fingerprint(out, fp)
        return out

    def rollback(self, pos: int) -> None:
        """Rewind to ``pos`` (prefix-cache reuse / early-stop contract).
        Slab slots beyond ``pos`` — including any written by an in-flight
        speculative chunk — are stale but unreachable: attention masks
        s <= pos and the next prefill overwrites them before the position
        pointer crosses. A rollback BELOW the matched prefix truncates the
        alias to ``pos`` (the rolled-back-onto tokens are a shared prefix,
        so the bytes below ``pos``, in the slab row or in the pool, stay
        valid) and releases the pins of pages the shortened chain no longer
        reaches — the next prefill writes the slab at ``pos`` and must be
        read from the slab, not the pool."""
        if not 0 <= pos <= self.pos:
            raise ValueError(f"cannot rollback to {pos} from {self.pos}")
        if 0 < pos < self.pos:
            # a recurrent state is where the device left it, not at ``pos``:
            # a rewind to 0 starts the row over (the next prefill zeroes the
            # state), anything else would go on with a stale state
            llama.refuse_recurrent(
                self.cfg, f"rollback to position {pos} of a row at {self.pos}"
            )
        self._rewind(pos)

    def _rewind(self, pos: int) -> None:
        """Move the position pointer back. After a decode the device state
        of a recurrent arch has run past it (``_state_ahead``: a decode chunk
        computes whole chunks); the next prefill or decode of this row
        refuses unless it starts over at 0."""
        self.pos = pos
        if self.matched_len > pos:
            self.scheduler._truncate_alias(self, pos)

    # ------------------------------------------------------------------
    # Prefill (per-request, on this stream's slab row)
    # ------------------------------------------------------------------

    def prefill(self, tokens) -> np.ndarray:
        """Batched-prompt prefill into this slab row; returns the last
        token's logits row (only that row crosses the host boundary)."""
        self._release_depth()
        tokens = np.asarray(tokens, dtype=np.int32)
        n = tokens.shape[0]
        engine = self.engine
        sw = Stopwatch()
        with engine._tel.span("prefill", tokens=n, pos=self.pos, batch_row=self.row):
            logits, last = self.scheduler._prefill_row(self, tokens)
            out = np.asarray(logits[last])
        entry = engine._split_stats(sw.elapsed_ms(), n_tokens=n)
        self.stats.append(entry)
        if engine._tel.enabled:
            engine._tel.prompt_tokens.inc(n)
            engine._tel.prefill_latency.observe(entry.generation_ms / 1000.0)
        return out

    def prefill_device(self, tokens, temperature, topp, seed: int, topk: int = 0):
        """Prefill + sample the first token ON DEVICE (the prefill→decode
        fusion of EngineStream.prefill_device, on this slab row): returns
        the device token scalar — nothing visits the host until the fused
        first-token fetch overlaps chunk 1's compute. The coin is keyed on
        the last prompt token's absolute position, so a requeue/failover
        re-run draws it identically with no sampler state shipped."""
        engine = self.engine
        tokens = np.asarray(tokens, dtype=np.int32)
        n = tokens.shape[0]
        sw = Stopwatch()
        self._hold_depth()
        try:
            with engine._tel.span(
                "prefill_dispatch", tokens=n, pos=self.pos, batch_row=self.row
            ):
                logits, last = self.scheduler._prefill_row(self, tokens)
                with engine._tel.span(
                    "device_sample", pos=self.pos - 1, batch_row=self.row
                ):
                    from distributed_llama_tpu import prng

                    token = engine._sample_row(
                        logits, jnp.int32(last),
                        jnp.uint32(prng.fold_seed(seed)),
                        jnp.int32(self.pos - 1), jnp.float32(temperature),
                        jnp.float32(topp), jnp.int32(topk),
                    )
                    self.scheduler._ledger.counted("sample_row")
            entry = engine._split_stats(sw.elapsed_ms(), n_tokens=n)
            self.stats.append(entry)
            self._pending_prefill_entry = entry
            if engine._tel.enabled:
                engine._tel.prompt_tokens.inc(n)
        except BaseException:
            self._release_depth()
            raise
        return token

    def fetch_first_token(self, first_token) -> int:
        """Fetch a :meth:`prefill_device` token without starting a decode
        stream (the 1-token-completion fast path)."""
        return self._fetch_fused_first(first_token)

    def _fetch_fused_first(self, first_token) -> int:
        """Blocking fetch of the device-sampled first token; the drain time
        joins the prefill's stats entry (the dispatch-only timing would
        otherwise under-report prefill latency — same contract as
        EngineStream._fetch_fused_first)."""
        engine = self.engine
        sw = Stopwatch()
        with engine._tel.span("first_token_fetch", batch_row=self.row):
            tok = int(np.asarray(first_token))
        self._release_depth()
        drained_ms = sw.elapsed_ms()
        entry = self._pending_prefill_entry
        if entry is not None:
            entry.generation_ms += drained_ms
            entry.inference_ms += drained_ms
            self._pending_prefill_entry = None
            tel = engine._tel
            if tel.enabled:
                tel.prefill_latency.observe(entry.generation_ms / 1000.0)
                tel.tokens_generated.inc(1)
                tel.device_sampled_tokens.inc(1)
        return tok

    def _hold_depth(self) -> None:
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

    # ------------------------------------------------------------------
    # Decode (through the shared batched dispatch)
    # ------------------------------------------------------------------

    def stream_decode(
        self,
        first_token,
        on_token,
        temperature: float = 0.0,
        topp: float = 0.9,
        seed: int = 0,
        chunk: int | None = None,
        limit: int | None = None,
        first_prev: int | None = None,
        spec_draft: int = 0,
        spec_ngram: int = 3,
        prompt_tokens=None,
        topk: int = 0,
    ) -> int:
        """EngineStream.stream_decode over the shared batched dispatch: this
        stream joins the scheduler's active set and consumes its row of
        every batched chunk; other streams' chunks ride the same weight
        reads. ``chunk`` is accepted for signature parity but the scheduler's
        shared chunk size governs (all coalesced rows must step together).
        Owns the early-stop rollback contract; returns tokens consumed.

        With the scheduler in spec mode (``spec_draft`` on the
        BatchScheduler), every dispatch is a batched VERIFY step and rows
        advance a variable number of positions per chunk; ``spec_draft`` 0
        on the call keeps this row's drafts empty (a plain decode step
        riding the shared verify read), which is how spec and non-spec
        requests mix in one slab. ``spec_ngram`` is accepted for signature
        parity — the scheduler's shared drafter config governs."""
        engine = self.engine
        sched = self.scheduler
        start_pos = self.pos
        stop = engine.cfg.seq_len if limit is None else min(limit, engine.cfg.seq_len)
        fused_first = first_prev is not None
        spec_mode = sched.spec_draft > 0
        prev = first_prev if fused_first else int(first_token)
        consumed = 0
        keep = True
        if spec_mode:
            # the drafter needs host token values: fetch the fused first
            # token BEFORE joining (the plain path's fetch-overlap trick is
            # traded for draft context — one round trip buys up to k+1
            # tokens per subsequent step)
            if fused_first:
                tok = self._fetch_fused_first(first_token)
                consumed = 1
                keep = on_token(prev, tok)
                prev = tok
            self._history = [int(t) for t in (prompt_tokens or [])]
            self._history.append(prev)
            self._spec_on = bool(spec_draft and spec_draft > 0)
            first_token = prev  # host int: the next verify window's feed[0]
        self._stop_pos = stop  # the chunks must carry the row's position this far
        sched._join(self, first_token, temperature, topp, seed, topk)
        # the consumer's side of the pump on the profiler's timeline, one
        # span per refill of this row's queue (a span that began before a
        # capture did is in no trace): a device gap under no scheduler span
        # but under this one is the caller handing its tokens on
        # (detokenize, SSE write) before it asks for more
        handing_on = None
        try:
            if fused_first and not spec_mode:
                # dispatch chunk 1 before the fused fetch so the scalar
                # fetch overlaps the chunk's compute (the prefill_device
                # round-trip elision, batched)
                sched.kick()
                tok = self._fetch_fused_first(first_token)
                consumed += 1
                keep = on_token(prev, tok)
                prev = tok
            while keep is not False:
                fed = consumed - 1 if fused_first else consumed
                if start_pos + fed >= stop:
                    break
                if handing_on is not None and not self._queue:
                    handing_on.__exit__(None, None, None)  # dry: off to the pump
                    handing_on = None
                tok = sched.next_token(self)
                if handing_on is None:
                    handing_on = engine._tel.span("decode_stream", batch_row=self.row)
                    handing_on.__enter__()
                consumed += 1
                keep = on_token(prev, tok)
                prev = tok
        finally:
            if handing_on is not None:
                handing_on.__exit__(None, None, None)
            sched._leave(self)
            fed = max(consumed - 1, 0) if fused_first else consumed
            self._rewind(min(start_pos + fed, self.pos))
        return consumed

    # ------------------------------------------------------------------
    # Stats (EngineStream parity)
    # ------------------------------------------------------------------

    def avg_stats(self) -> TokenStats:
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


class BatchScheduler:
    """Owns the ``[B_max]`` slab cache and coalesces joined streams into
    one batched decode dispatch per chunk. Supported on the single-chip and
    tensor-parallel backends (the sp/ep backends keep their single-stream
    programs)."""

    def __init__(
        self,
        engine,
        n_rows: int,
        chunk: int = 32,
        retries: int = 2,
        retry_backoff_s: float = 0.05,
        stall_timeout_s: float | None = None,
        prefix_cache: bool = False,
        kv_pages: int | None = None,
        page_size: int = 64,
        prefill_chunk: int = 0,
        spec_draft: int = 0,
        spec_ngram: int = 3,
        replica_id: int = 0,
        host_spill_bytes: int = 0,
        spill_dir: str | None = None,
        spill_disk_bytes: int = 0,
        spill_arena=None,
        shared_index=None,
    ):
        tp_engine = engine._tp_engine
        if tp_engine is not None and not hasattr(tp_engine, "batched_decode_chunk"):
            raise ValueError(
                "batched decode is supported on the single-chip and tp "
                "backends only (sp/ep keep single-stream dispatches)"
            )
        if n_rows < 1:
            raise ValueError(f"need at least one batch row, got {n_rows}")
        if not engine.cfg.rewinds_by_position:
            # paths that move or rewind a row by position refuse by name
            if spec_draft and int(spec_draft) > 0:
                llama.refuse_recurrent(
                    engine.cfg, f"speculative decode (--spec-draft {spec_draft})"
                )
            if tp_engine is not None:
                llama.refuse_recurrent(engine.cfg, "a sharded (tp/pod) backend")
            if spill_arena is not None or host_spill_bytes > 0:
                llama.refuse_recurrent(
                    engine.cfg, "the host spill tier (a state snapshot or a window layer's "
                    "page has no spill form)"
                )
        if engine.cfg.has_indexer and (spill_arena is not None or host_spill_bytes > 0):
            llama.refuse_latent(
                engine.cfg, "the host spill tier (a page of a layer with an indexer is two arrays, "
                "latents and index keys, which no spill entry has carried)"
            )
        if tp_engine is not None:
            llama.refuse_latent(engine.cfg, "a sharded (tp/pod) backend")
        if spec_draft and int(spec_draft) > 0:
            llama.refuse_latent(engine.cfg, f"speculative decode (--spec-draft {spec_draft})")
        self.engine = engine
        self.b_max = n_rows
        self.chunk = int(chunk)
        # the completion ledger (ISSUE 41, telemetry/device_ledger.py): when
        # each dispatched program finished, so that a window's device time
        # divides by program; NULL_LEDGER (no thread, no queue) with
        # telemetry off. Bound before the programs this constructor builds
        # (it counts their launches too). ``_prompts_open``: prompts inside
        # _prefill_row, which with the joined rows that need another chunk is
        # the work the ledger's idle is told apart by
        self._ledger = device_ledger.bind(engine._tel.enabled)
        if self._ledger.enabled:
            # a scheduler that is dropped without close() takes its watcher with it
            weakref.finalize(self, self._ledger.close)
        self._prompts_open = 0
        # Sarathi-style chunked prefill (ISSUE 4 satellite): a long prompt
        # is dispatched in prefill_chunk-token pieces with the scheduler
        # lock RELEASED between dispatches, so decode chunks for other rows
        # interleave instead of stalling behind the whole prompt. 0 = one
        # monolithic dispatch (the pre-ISSUE-4 behavior).
        self.prefill_chunk = max(
            0, 0 if prefill_chunk is None else int(prefill_chunk)
        )
        if engine.cfg.piece_limit:
            # a window layer's ring takes a prompt in pieces that fit it
            # beside the window, an EVA layer's window store in pieces of at
            # most a window (a monolithic dispatch does neither)
            self.prefill_chunk = min(self.prefill_chunk or engine.cfg.piece_limit,
                                     engine.cfg.piece_limit)
        # radix-tree prefix cache over pool pages (ISSUE 4 tentpole): an
        # admission prefill finds its longest published prefix and prefills
        # only the unmatched suffix. On one chip the matched pages are COPIED
        # into the row at admission (``_hit_restores``: the row is then a
        # cold prefill's and every later step reads its slab alone, ISSUE
        # 40); the tp backend binds them to the row's page table and its
        # attention reads them in place out of the sharded pool (ISSUE 7)
        self._prefix = None
        self._pool = None
        self._wpool = None
        self._hit_restores = False
        self._own_arena = None  # a spill arena this scheduler made itself
        if prefix_cache:
            # misconfiguration disables ONLY the prefix cache (with the
            # real reason printed) — it must never take batched decode
            # down with it (a raised ValueError here would be caught by
            # the server's backend-fallback handler and silently cost the
            # whole one-weight-read-per-step serving path)
            page_ok = 1 <= page_size <= engine.cfg.seq_len
            page_fault = f"page size {page_size} must be in [1, seq_len {engine.cfg.seq_len}]"
            if engine.cfg.has_eva and page_ok and (
                page_size % engine.cfg.eva_chunk or engine.cfg.window % page_size
            ):
                # a page of summaries is whole chunks, a window whole pages
                page_ok, page_fault = False, (
                    f"page size {page_size} must be whole chunks of {engine.cfg.eva_chunk} "
                    f"positions and divide the window of {engine.cfg.window} (EVA attention)"
                )
            slab_pages = n_rows * -(-engine.cfg.seq_len // page_size) if page_ok else 0
            if kv_pages is None and page_ok:
                # default HBM budget: a live row pins the pages it matched
                # for its lifetime, so size the pool to hold every row's
                # worth of prefix plus headroom for prefixes outliving their
                # rows (--parallel x ceil(seq_len/page) + 25%, at least one
                # row)
                kv_pages = slab_pages + max(
                    slab_pages // 4, -(-engine.cfg.seq_len // page_size)
                )
            if not page_ok:
                print(f"⚠️ prefix cache disabled: {page_fault}")
            elif kv_pages < 1:
                print("⚠️ prefix cache disabled: --kv-pages 0")
            else:
                if kv_pages < slab_pages:
                    print(
                        f"⚠️ --kv-pages {kv_pages} is smaller than one "
                        f"slab's worth ({slab_pages} pages for {n_rows} "
                        f"rows x seq_len {engine.cfg.seq_len}): a live row "
                        "pins the pages it matched, so concurrent long "
                        "prompts will contend for pages (pinned-page soft "
                        "failures)"
                    )
                from distributed_llama_tpu.engine.prefix_cache import PrefixCache

                # host-RAM spill tier (ISSUE 11, engine/spill.py): evicted
                # pages' bytes land in a bounded arena (shared across a
                # replica pool when the serving layer passes one) and
                # reload on a later match — re-upload ≪ re-prefill.
                # Single-chip pools only for now: the sharded tp pool's
                # per-shard download/upload programs are the known
                # follow-up, and spill must never take the cache down
                arena = spill_arena
                if arena is None and host_spill_bytes > 0:
                    import os as _os

                    from distributed_llama_tpu.engine.spill import HostArena

                    arena = HostArena(
                        int(host_spill_bytes),
                        disk_path=(
                            _os.path.join(spill_dir, "dllama-kv-spill.bin")
                            if spill_dir and spill_disk_bytes > 0 else None
                        ),
                        disk_budget_bytes=int(spill_disk_bytes),
                    )
                    self._own_arena = arena  # close() stops its spiller
                if arena is not None and tp_engine is not None:
                    print(
                        "⚠️ host-RAM spill disabled: the sharded tp page "
                        "pool has no download/upload programs yet "
                        "(single-chip backend only)"
                    )
                    arena = None
                # recurrent-state snapshots share the pool's budget: one slot
                # for every SNAPSHOT_PAGES pool pages (a snapshot is taken
                # once an admission, at its last page boundary)
                snap_slots = (
                    max(2, kv_pages // SNAPSHOT_PAGES) if engine.cfg.is_recurrent else 0
                )
                window_tail = self._window_keep = 0
                cfg = engine.cfg
                if cfg.has_window:
                    # the window layers' pool: a hit needs the ``window_tail``
                    # pages before its end; a publish can read the row's last
                    # ``_window_keep`` whole pages back out of its rings (what
                    # the last piece and its padding have not overwritten),
                    # which are the boundaries a prompt that shares this one's
                    # head and differs near its end hits. The pool holds that
                    # tail and a hit's own for every row: it follows
                    # --parallel, not --kv-pages
                    window_tail = -(-cfg.window // page_size)
                    self._window_keep = max(
                        0, (cfg.ring_len - _prefill_bucket(self.prefill_chunk)) // page_size - 1
                    )
                window_pages = n_rows * (self._window_keep + window_tail)
                window_align = 0
                # where a block sits in a row's leaf, for the copies between
                # the row and the window pool (a window layer's ring)
                self._wslots = kvc.BlockSlots(page_size, ring=cfg.ring_len)
                if cfg.has_eva:
                    # an EVA arch's pools: a page under --kv-pages holds a
                    # block's SUMMARIES (what a later prompt needs of every
                    # block it shares); the keys and values, which it needs
                    # only from its last window's start to its end, go to the
                    # window pool, whose size follows --parallel
                    window_align = cfg.window // page_size
                    window_pages = eva_tail_pages(n_rows, window_align)
                    self._wslots, self._sslots = (
                        kvc.eva_block_slots(kind, page_size, cfg.window, cfg.eva_chunk)
                        for kind in ("window", "summary")
                    )
                self._prefix = PrefixCache(
                    kv_pages, page_size,
                    page_bytes=llama.page_pool_bytes(
                        engine.cfg, page_size, engine.cache_dtype
                    ),
                    spill=arena,
                    page_fetch=self._slice_pages_locked if arena is not None else None,
                    page_land=self._land_pages,
                    owner_id=replica_id,
                    shared_index=shared_index,
                    snap_slots=snap_slots,
                    window_pages=window_pages, window_tail=window_tail,
                    window_align=window_align,
                )
                if tp_engine is None:
                    self._pool = llama.init_page_pool(
                        engine.cfg, kv_pages, page_size, dtype=engine.cache_dtype
                    )
                    # an EVA arch copies its own two kinds of page (_eva_restore)
                    self._hit_restores = not cfg.has_eva
                    if window_pages:
                        self._wpool = llama.init_window_pool(
                            engine.cfg, window_pages, page_size, dtype=engine.cache_dtype
                        )
                else:
                    # the sharded pool (per-shard [P, page, K/tp, hd]
                    # halves): PR 4 deferred multi-chip; the zero-copy read
                    # made it a plain per-shard local program
                    self._pool = tp_engine.init_page_pool(
                        kv_pages, page_size, dtype=engine.cache_dtype
                    )
                # static per-row page-table width: every table the
                # scheduler builds covers ceil(S/page) entries (one
                # compiled paged program per bucket/chunk shape)
                self._n_table = -(-engine.cfg.seq_len // page_size)
        # self-speculative decode (ISSUE 6): spec_draft > 0 turns every
        # batched dispatch into a VERIFY step — per-row prompt-lookup
        # drafts scored in one weight read, rows advancing a variable
        # number of positions per step. Misconfiguration soft-disables
        # (spec is a perf mode; it must never take batched serving down)
        self.spec_draft = 0
        self.spec_ngram = max(1, int(spec_ngram))
        if spec_draft and int(spec_draft) > 0:
            if tp_engine is not None:
                print(
                    "⚠️ speculative decode disabled: the batched verify "
                    "forward is single-chip only for now (the tp verify "
                    "needs the sharded multi-token program)"
                )
            elif engine.cfg.is_moe:
                print(
                    "⚠️ speculative decode disabled: MoE verify windows "
                    "would route T>1 rows through the prefill expert path "
                    "(no decode parity contract)"
                )
            else:
                self.spec_draft = int(spec_draft)
        # fault tolerance (ISSUE 3): bounded retry with exponential backoff
        # for transient dispatch/fetch failures, an optional stall watchdog,
        # and the bind-once fault-injection plan (NULL_PLAN when no chaos
        # plan is installed — one no-op attribute call per dispatch)
        self.retries = max(0, int(retries))
        self.retry_backoff_s = float(retry_backoff_s)
        # the shared backoff vocabulary (distributed_llama_tpu/retry.py):
        # same schedule the old inline loops slept — base * 2**attempt
        self._retry_policy = retry.BackoffPolicy(
            attempts=self.retries + 1, base_s=self.retry_backoff_s
        )
        self.stall_timeout_s = stall_timeout_s
        self._faults = faults.active_plan()
        # replica-loss fault domain (ISSUE 9): this scheduler IS one
        # data-parallel replica when a server/replicas.py pool owns it.
        # ``replica_id`` scopes the replica.* chaos sites (a rule's row=
        # field selects the replica), ``health_hook(event, value)`` feeds
        # the pool's health state machine ("roundtrip" per chunk fetch,
        # "stall"/"lost" on death) and must only take LEAF locks — never
        # this cond — and ``lost_on_stall`` escalates a watchdog stall
        # from per-row StallTimeout to a whole-replica loss (the victims
        # then REQUEUE onto surviving replicas instead of failing 500)
        self.replica_id = int(replica_id)
        self.health_hook = None
        self.lost_on_stall = False
        # armed by an engine.sdc kind=corrupt message=logits rule: each
        # pending unit perturbs ONE fetched chunk's token columns in-vocab
        # (finite, wrong, invisible to the vocab/finite validation — the
        # class only the canary's golden comparison can see)
        self._sdc_logits_pending = 0
        # a chunk whose dispatch→delivery takes longer than this leaves a
        # `slow_chunk` flight event naming the phase that took it (a chunk
        # is 0.3–0.6 s in every benchmark cell; PERF.md PR 22's 13.7 s
        # freeze of all 16 streams then says host or device)
        self.slow_chunk_s = 2.0
        self._lost = False
        self.lost_cause: str | None = None
        self.lost_victims = 0
        # priority preemption (ISSUE 8): clean evictions performed by
        # preempt_below — a plain counter so tests/loadgen read it with
        # telemetry off (the registry's dllama_preemptions_total mirrors it)
        self.preempted_total = 0
        if tp_engine is None:
            self._slab = llama.init_batch_cache(
                engine.cfg, n_rows, dtype=engine.cache_dtype
            )
        else:
            self._slab = tp_engine.init_batch_cache(n_rows, dtype=engine.cache_dtype)
        # the token each row's next decode chunk feeds first, on the device:
        # written at a join, advanced by every chunk program, never fetched.
        # The write's program is built now, not at the first join
        self._carry = _carry_put(
            jnp.zeros((n_rows,), jnp.int32), np.int32(0), np.int32(0)
        )
        if self._prefix is not None and self._prefix.spill is not None:
            # the spill download's program too: the first eviction may come
            # in the middle of serving, under the scheduler's lock
            self._land_pages([_slice_page(self._pool, np.int32(0))])
        # the logits of the newest prompt piece dispatched, and, from each
        # delivery on, of the newest one that was on the device's queue then:
        # the next decode chunk is not enqueued in front of it
        # (_dispatch_locked). Two references: at most two pieces' logits stay
        # allocated past their request's own use
        self._last_piece = None
        self._pieces_first = None
        # their entries on the completion ledger, kept beside them
        self._last_piece_entry = self._pieces_first_entry = device_ledger.NULL_ENTRY
        # row buckets whose plain decode program has been dispatched (built)
        self._decode_built: set[int] = set()
        # (device int32 [3], tokens) of prefill chunks whose expert layers'
        # counts are not read yet
        self._moe_pending: list = []
        # bytes a slot of a latent leaf's arrays holds, by the array's name (other archs: none)
        self._kv_bytes_by_kind: dict[str, int] = {}
        if engine.cfg.kv_read_kinds:
            for kind, nbytes in llama.kv_slab_bytes(engine.cfg, n_rows, engine.cache_dtype).items():
                engine._tel.kv_slab_bytes.labels(kind=kind).set(nbytes)
            # bytes one position's keys and values take in one layer: what the
            # programs' counts of positions read are multiplied by
            self._kv_position_bytes = llama.page_pool_bytes(
                engine.cfg, 1, engine.cache_dtype, layers=1
            )
        if engine.cfg.has_latent:
            # ... read off the slab's own leaf: what a row really stores of a
            # position (it would rise 15-fold if keys and values were kept)
            # (a leaf with an indexer: of each of its two arrays, by name)
            self._kv_bytes_by_kind = {
                name: a.nbytes // (a.shape[0] * a.shape[2]) for name, a in self._slab[0].items()
            }
            self._kv_position_bytes = self._kv_bytes_by_kind[kvc.LATENT]
            if self._prefix is not None:
                for name, nbytes in self._kv_bytes_by_kind.items():
                    engine._tel.kv_pool_bytes.labels(kind=name).set(
                        kv_pages * page_size * nbytes * engine.cfg.n_layers
                    )
        if self._hit_restores:
            # every shape of a hit's copy is built now, not at the first hit
            # inside a measured window (page 0 into row 0, which starts over
            # before it reads a slot)
            h = 1
            while h <= engine.cfg.seq_len // page_size:
                self._slab = _restore_pages(
                    self._slab, self._pool, *self._restore_runs([0] * h), np.int32(0)
                )
                h *= 2
        if engine.cfg.has_window:
            if self._wpool is not None:
                for kind, pages in (("full", kv_pages), ("window", self._prefix.window_pages)):
                    engine._tel.kv_pool_bytes.labels(kind=kind).set(
                        pages * page_size * self._kv_position_bytes
                        * len(engine.cfg.layers_of(kind))
                    )
                # both programs are built now, not at the first publish or hit
                # inside a measured window (ids past the pool drop; page 0
                # holds zeros, and row 0 starts over before it reads a slot)
                tail = np.zeros(self._prefix.window_tail, np.int32)
                self._slab = _restore_window_tail(
                    page_size, self._slab, self._wpool, tail, tail, jnp.int32(0),
                    ring=self._wslots.ring,
                )
                for bucket in sorted({_page_bucket(n) for n in range(1, self._window_keep + 1)}):
                    ids = np.full(bucket, self._prefix.window_pages, np.int32)
                    self._wpool = _publish_window_pages(
                        page_size, self._slab, self._wpool, ids, np.zeros(bucket, np.int32),
                        jnp.int32(0), ring=self._wslots.ring,
                    )
        if engine.cfg.has_eva and self._wpool is not None:
            cfg = engine.cfg
            per_page = page_size * self._kv_position_bytes * cfg.n_layers
            engine._tel.kv_pool_bytes.labels(kind="eva_summary").set(
                kv_pages * per_page // cfg.eva_chunk)
            engine._tel.kv_pool_bytes.labels(kind="eva_window").set(
                self._prefix.window_pages * per_page)
            # the programs of a hit and of a publish's tail are built now,
            # not inside a measured window (row 0 starts over before it
            # reads a slot; ids past a pool drop)
            self._slab = self._eva_restore(self._slab, 0, [0], [0], 0)
            for bucket in sorted({_page_bucket(n) for n in range(1, self._prefix.window_align)}):
                self._wpool = _publish_window_pages(
                    page_size, self._slab, self._wpool,
                    np.full(bucket, self._prefix.window_pages, np.int32),
                    np.zeros(bucket, np.int32), jnp.int32(0), ring=self._wslots.ring,
                )
        self._snaps = None
        # layers that keep a state a row (0: none): what a token through them is counted by
        self._state_layers = len(engine.cfg.layers_of(engine.cfg.state_mixer or ""))
        if engine.cfg.is_recurrent:
            engine._tel.recurrent_state_bytes.set(
                llama.recurrent_state_bytes(engine.cfg, n_rows)
            )
            if self._prefix is not None:
                self._snaps = [
                    None if engine.cfg.is_softmax_layer(l)
                    else llama.init_state_leaf(engine.cfg, (self._prefix.snap_slots,))
                    for l in range(engine.cfg.n_layers)
                ]
                # both programs are built now, not at the first hit inside
                # a measured window (slot 0 and row 0 hold zeros)
                self._snaps = _snapshot_take(self._slab, self._snaps, jnp.int32(0), jnp.int32(0))
                self._slab = _snapshot_restore(self._slab, self._snaps, jnp.int32(0), jnp.int32(0))
        # backends whose slab shards its BATCH axis across the mesh (the
        # pod's 'data' axis) dispatch the whole slab every chunk: a sub-
        # bucket's rows would straddle the wrong shards. The floor is set
        # by init_batch_cache above; 1 everywhere else (classic bucketing)
        self._bucket_floor = (
            min(n_rows, max(1, int(getattr(tp_engine, "decode_bucket_floor", 1))))
            if tp_engine is not None else 1
        )
        self._streams: list[BatchStream] = []
        self._cond = lockcheck.make_condition("BatchScheduler._cond")
        # one dispatched-but-unfetched chunk at a time: (mode, tokens_dev,
        # epoch snapshot, bucket, active count, stopwatch, spec draft lens,
        # build-start and dispatched instants, the chunk's ledger entry)
        self._pending = None
        self._fetching = False
        # fetch generation: bumped when a thread takes the pending chunk; the
        # watchdog kills a stalled generation by flipping _fetching off, and
        # the (eventually-returning) hung fetch sees its generation is dead
        # and discards its delivery
        self._fetch_gen = 0
        self._fetch_started: float | None = None
        self._shutdown = False
        self._watchdog: threading.Thread | None = None
        if stall_timeout_s is not None and stall_timeout_s > 0:
            self._watchdog = threading.Thread(
                target=self._watchdog_loop, name="dllama-batch-watchdog",
                daemon=True,
            )
            self._watchdog.start()

    def close(self) -> None:
        """Stop the watchdog thread and, where this scheduler made its own
        spill arena, that arena's spiller (tests; a serving scheduler lives
        for the process)."""
        with self._cond:
            self._shutdown = True
            self._cond.notify_all()
        if self._own_arena is not None:
            self._own_arena.close()
        self._ledger.close()

    # ------------------------------------------------------------------
    # Replica loss (ISSUE 9): the whole-scheduler failure domain. A crash
    # (injected or real) at a dispatch, or a stall the watchdog escalates,
    # retires EVERY in-flight request with a typed ReplicaLost — the
    # serving layer requeues them through fair admission onto surviving
    # replicas and replays them bit-identically; the pool supervisor
    # restarts this replica with jittered backoff (server/replicas.py).
    # ------------------------------------------------------------------

    def mark_lost(self, cause: str, corrupt: bool = False) -> None:
        """Declare this replica dead (pool/tests entry point). Idempotent.
        ``corrupt=True`` marks an integrity-detected loss (canary/shadow
        mismatch, ISSUE 10): victims get :class:`faults.ReplicaCorrupt`,
        which the serving layer replays ONLY while nothing has streamed —
        deltas already sent by a silently-corrupt replica may themselves
        be wrong, and a suppressed replay would splice onto them."""
        with self._cond:
            self._mark_lost_locked(cause, corrupt=corrupt)

    def _mark_lost_locked(self, cause: str, corrupt: bool = False) -> None:
        """The one death path (cond held): every stream gets ReplicaLost
        (a mid-prefill request raises it at its next chunk boundary, a
        decoding one at its next ``next_token``), page pins release, the
        dispatched-but-unfetched chunk is dropped with its depth hold, the
        watchdog stands down, and the health hook reports the loss. The
        hook only takes LEAF locks (pool/admission/registry), so calling
        it under this cond cannot deadlock."""
        if self._lost:
            return
        self._lost = True
        self.lost_cause = cause
        self.lost_victims = sum(1 for s in self._streams if s._joined)
        # flight recorder (ISSUE 16): the death certificate — cause,
        # victim count, and the victims' request-trace ids, before the
        # pool's hook records the failover (leaf lock, safe under cond)
        flight.record(
            self.replica_id, "replica_lost", cause=cause,
            corrupt=bool(corrupt), victims=self.lost_victims,
            victim_trace_ids=[
                s.trace.request_id for s in self._streams
                if s.trace is not None
            ],
        )
        err_cls = faults.ReplicaCorrupt if corrupt else faults.ReplicaLost
        for s in self._streams:
            s._fetch_error = err_cls(
                f"replica {self.replica_id} lost: {cause}"
            )
            self._release_pins_locked(s)
        if self._pending is not None:
            # the speculative chunk dies with the replica: nobody will
            # fetch it, so its depth hold releases here
            self._count_lost_chunk(self._pending)
            self._pending = None
            with self.engine._depth_lock:
                self.engine._pipeline_depth -= 1
        self._shutdown = True  # a dead replica's watchdog has no duties
        self._cond.notify_all()
        hook = self.health_hook
        if hook is not None:
            hook("lost", float(self.lost_victims))

    @property
    def lost(self) -> bool:
        return self._lost

    def _watchdog_loop(self) -> None:
        """Detect a hung chunk fetch and fail the batch CLEANLY: joined rows
        get a typed StallTimeout (their requests end 500/504-class instead
        of hanging forever), the dead fetch generation is retired so a late
        completion delivers nothing, and the scheduler is immediately
        serviceable for new requests."""
        interval = max(min(self.stall_timeout_s / 4.0, 1.0), 0.005)
        tel = self.engine._tel
        while not self._shutdown:
            time.sleep(interval)
            with self._cond:
                stalled = (
                    self._fetching
                    and self._fetch_started is not None
                    and time.monotonic() - self._fetch_started > self.stall_timeout_s
                )
                if not stalled:
                    continue
                # take the hung fetch's completion duties: it can no longer
                # claim ownership (_fetch claims under this lock), so ITS
                # depth hold is released here — otherwise a never-returning
                # fetch would pin pipeline_depth > 0 and freeze the transfer
                # probe for the rest of the process
                self._fetching = False
                self._fetch_started = None
                released = 1
                if self._pending is not None:
                    # drop the speculative chunk queued behind the hung
                    # program: every row that wanted it is being retired, and
                    # leaving it would make the LAST _leave's idle-drain
                    # fetch it SYNCHRONOUSLY on a request thread — blocking
                    # that client's error response behind the hang
                    self._count_lost_chunk(self._pending)
                    self._pending = None
                    released += 1
                with self.engine._depth_lock:
                    self.engine._pipeline_depth -= released
                tel.watchdog_stalls.inc()
                flight.record(
                    self.replica_id, "watchdog_stall",
                    timeout_s=self.stall_timeout_s,
                    lost_on_stall=self.lost_on_stall,
                )
                if not self.lost_on_stall:
                    # unsupervised stall: rows die with StallTimeout and no
                    # replica-death dump follows — snapshot the evidence
                    # here (the supervised path dumps via the pool's
                    # failover hook). Outside-the-lock would be nicer, but
                    # dump() only spawns a writer thread when dump_dir is
                    # set; the snapshot itself is a leaf-locked copy.
                    flight.RECORDER.dump(
                        self.replica_id, "watchdog_stall",
                        timeout_s=self.stall_timeout_s,
                    )
                if self.lost_on_stall:
                    # supervised replica (ISSUE 9): a stalled chunk is a
                    # replica-level loss — victims requeue onto surviving
                    # replicas instead of dying with StallTimeout, and
                    # the supervisor restarts this replica. The hook's
                    # "stall" event walks the pool's health machine
                    # through suspect before "lost" declares death.
                    hook = self.health_hook
                    if hook is not None:
                        hook("stall", self.stall_timeout_s)
                    self._mark_lost_locked(
                        "chunk fetch exceeded the "
                        f"{self.stall_timeout_s:.1f}s stall timeout"
                    )
                    continue
                for s in self._streams:
                    if s._joined and s._fetch_error is None:
                        s._fetch_error = faults.StallTimeout(
                            "batched chunk fetch exceeded the "
                            f"{self.stall_timeout_s:.1f}s stall timeout"
                        )
                        self._release_pins_locked(s)
                self._cond.notify_all()

    def new_stream(self) -> BatchStream:
        """Hand out the next slab row as an EngineStream-like serving lane."""
        with self._cond:
            if len(self._streams) >= self.b_max:
                raise ValueError(f"all {self.b_max} batch rows are allocated")
            s = BatchStream(self, len(self._streams))
            self._streams.append(s)
            return s

    # ------------------------------------------------------------------
    # Prefill dispatch (serialized with batched chunks via the cond lock:
    # every dispatch consumes and replaces the donated slab)
    # ------------------------------------------------------------------

    def _prefill_row(self, stream: BatchStream, tokens: np.ndarray):
        """Prefill ``tokens`` into ``stream``'s slab row. On an ADMISSION
        prefill (row position 0, prefix cache active, request not opted
        out) the radix tree is consulted first: the matched chain is BOUND
        to the row (its pages copied into the slab row on one chip, its
        page table on the tp backend: :meth:`_match_alias`) and only the
        unmatched suffix is dispatched; the completed prefill's full pages
        are then published back into the tree. Returns
        ``(logits, last)`` — the final dispatch's device logits and the
        index of the last REAL token's row within them."""
        engine = self.engine
        n = tokens.shape[0]
        if self._lost:
            # a request placed on this replica just before it died: fail
            # typed BEFORE touching the slab — the serving layer requeues
            # it onto a surviving replica (no bytes were dispatched)
            raise faults.ReplicaLost(
                f"replica {self.replica_id} lost: {self.lost_cause}"
            )
        if n == 0:
            raise ValueError("empty token batch: at least one token required")
        if stream.pos + n > engine.cfg.seq_len:
            raise ValueError(
                f"context overflow: pos {stream.pos} + {n} > {engine.cfg.seq_len}"
            )
        if stream.pos == 0:
            stream._state_ahead = False  # the prefill program zeroes the state
        elif stream._state_ahead:
            llama.refuse_recurrent(
                engine.cfg, f"a continuation prefill at position {stream.pos} after a decode"
            )
        admission = (
            self._prefix is not None
            and stream.pos == 0
            and stream.prefix_cache_enabled
        )
        chain: list = []
        suffix = tokens
        stream._snap_at = None
        self._prompt_open(+1)
        try:
            if admission:
                chain = self._match_alias(stream, tokens)
                if chain:
                    suffix = tokens[len(chain) * self._prefix.page :]
                if self._snaps is not None:
                    # the state where the last full page ends is what a later
                    # prompt resumes from: a prefill chunk must end there
                    stream._snap_at = n // self._prefix.page * self._prefix.page
            try:
                logits, last = self._dispatch_prefill_chunks(stream, suffix)
            except BaseException:
                # a failed suffix prefill fails the request: unwind the alias
                # bind (release the chain pins, reset the position) so the
                # row is clean for its next occupant and the pages evictable
                self._drop_row_snapshot(stream)
                if chain:
                    self._release_row_pins(stream)
                    stream.pos = 0
                raise
            if admission:
                self._publish_row(stream, tokens, chain)
        finally:
            self._prompt_open(-1)
        return logits, last

    def _prompt_open(self, step: int) -> None:
        """A prompt enters (+1) or leaves (-1) :meth:`_prefill_row`: with the
        ledger on, the scheduler's work in hand changes with it."""
        if self._ledger.enabled:
            with self._cond:
                self._prompts_open += step
                self._note_work_locked()

    def _note_work_locked(self) -> None:
        """Tell the ledger whether the scheduler has work in hand (cond
        held; called where that can change: a prompt enters or leaves, a row
        joins or leaves, a chunk moves its rows' positions on): a prompt not
        yet wholly dispatched, or a joined row whose request needs another
        chunk. Idle time of the device from then on is ``work_waiting``."""
        if self._ledger.enabled:
            if self._prompts_open > 0 or any(
                s._joined and s.pos < s._stop_pos for s in self._streams
            ):
                self._ledger.work_began()
            else:
                self._ledger.work_ended()

    def _dispatch_prefill_chunks(self, stream: BatchStream, tokens: np.ndarray):
        """Dispatch a (suffix-offset) prompt at ``stream.pos``, chunked at
        ``prefill_chunk`` tokens: the scheduler lock is released between
        chunk dispatches so other rows' decode chunks interleave with a
        long prefill (Sarathi-style) instead of queueing behind the whole
        prompt. ``stream._snap_at``: an absolute position at which a chunk
        must end; the row's recurrent state is snapshotted there (into
        ``stream._snap_slot``) before the next chunk moves it on. Returns
        (device logits of the final dispatch, index of the last real
        token's logits row)."""
        engine = self.engine
        n = tokens.shape[0]
        step = self.prefill_chunk if self.prefill_chunk > 0 else n
        cut = stream._snap_at
        logits = None
        off = 0
        c = n
        while off < n:
            if stream._fetch_error is not None:
                # a preemption (or watchdog/quarantine) that landed between
                # prefill chunks: stop dispatching this prompt — the chunk
                # boundaries are the prefill's yield points for eviction
                # exactly as they are for deadlines below
                err = stream._fetch_error
                stream._fetch_error = None
                raise err
            if (
                stream.deadline is not None
                and time.monotonic() >= stream.deadline
            ):
                # the chunk boundaries are the prefill's deadline points
                # (PR 3 enforced pre-prefill and between decode chunks
                # only): an expired request must not keep dispatching its
                # remaining prompt against co-batched rows' decode
                raise faults.DeadlineExceeded(
                    f"deadline expired mid-prefill (row {stream.row}, "
                    f"{off}/{n} prompt tokens dispatched)"
                )
            c = min(step, n - off)
            if cut is not None and stream.pos < cut < stream.pos + c:
                c = cut - stream.pos
            bucket = _prefill_bucket(c)
            if stream.pos + bucket > engine.cfg.seq_len:
                bucket = c  # exact-length compile near the context limit
            padded = np.zeros(bucket, dtype=np.int32)
            padded[:c] = tokens[off : off + c]
            tr = stream.trace
            t0 = time.monotonic() if tr is not None else 0.0
            with self._cond:
                # decode chunks already on the device's queue: this prompt
                # piece runs behind them (the decode chunk is the
                # scheduler's clock)
                ahead = (self._pending is not None) + self._fetching
                engine._tel.prefill_chunks_ahead.observe(ahead)
                with engine._tel.span(
                    "prefill_chunk_dispatch", tokens=c, row=stream.row,
                    chunks_ahead=ahead,
                ):
                    try:
                        # whole-replica crash site (ISSUE 9): prefill chunk
                        # dispatches are round-trips too — a crash mid-prompt
                        # must fail over exactly like one mid-decode
                        self._faults.fire("replica.crash", row=self.replica_id)
                    except Exception as e:
                        self._mark_lost_locked(f"injected crash at prefill: {e}")
                    if self._lost:
                        err = stream._fetch_error or faults.ReplicaLost(
                            f"replica {self.replica_id} lost: {self.lost_cause}"
                        )
                        stream._fetch_error = None
                        raise err
                    moe = None
                    if self._pool is not None:
                        # pool-enabled scheduler: every prefill runs the paged
                        # program — an unaliased row dispatches with matched 0
                        # (pure slab reads, byte-identical to the plain one),
                        # so one compiled program serves hits and misses
                        table, matched = self._alias_row_arrays_locked(stream)
                        if engine._tp_engine is None:
                            logits, self._slab, moe = _slab_prefill_single_paged(
                                engine.cfg, engine.params, jnp.asarray(padded),
                                self._slab, self._pool, jnp.int32(stream.row),
                                jnp.int32(stream.pos), jnp.int32(c), table, matched,
                            )
                            self._note_prefill_moe_locked(moe, c)
                        else:
                            logits, self._slab = engine._tp_engine.slab_forward_paged(
                                engine.params, jnp.asarray(padded), self._slab,
                                self._pool, stream.row, stream.pos, c, table,
                                matched,
                            )
                    elif engine._tp_engine is None:
                        logits, self._slab, moe = _slab_prefill_single(
                            engine.cfg, engine.params, jnp.asarray(padded), self._slab,
                            jnp.int32(stream.row), jnp.int32(stream.pos), jnp.int32(c),
                        )
                        self._note_prefill_moe_locked(moe, c)
                    else:
                        logits, self._slab = engine._tp_engine.slab_forward(
                            engine.params, jnp.asarray(padded), self._slab,
                            stream.row, stream.pos, c,
                        )
                    self._note_summaries(stream.pos, c)
                    self._note_state_tokens("prefill", c)
                    stream.pos += c
                    # the smallest output nothing donates onward is what the
                    # ledger's watcher waits for
                    self._last_piece = logits
                    self._last_piece_entry = self._ledger.dispatched(
                        "prefill_piece", logits if moe is None else moe, trace=tr,
                        bucket=bucket, rows=c, row=stream.row,
                        request="" if tr is None else tr.request_id,
                    )
                    self._ledger.piece_rows(c, bucket - c)
                    if stream.pos == cut:
                        self._take_snapshot_locked(stream)
            off += c
            if tr is not None:
                # one child span per dispatched prompt chunk: the trace
                # shows exactly how a long prompt interleaved with other
                # rows' decode between these boundaries (ISSUE 16)
                tr.add_span(
                    "prefill_chunk", t0, time.monotonic() - t0,
                    tokens=c, off=off - c, of=n, row=stream.row,
                )
        return logits, c - 1

    # ------------------------------------------------------------------
    # Prefix cache (ISSUE 4 + 7): admission-time match/alias-bind +
    # publish. Tree state, slab, pool and every row's alias state mutate
    # under the cond lock; the device programs themselves are async
    # dispatches whose ordering the device stream guarantees (a paged read
    # dispatched before a publish reads the pool version it was built
    # against — releasing pins mid-flight is therefore safe: any eviction/
    # republish only manifests as a LATER device program).
    # ------------------------------------------------------------------

    def _note_summaries(self, pos: int, n: int) -> None:
        """Count the chunks that ``n`` positions written from ``pos`` complete
        (an EVA arch: the program that writes a chunk's last position pools
        and writes its summary, in every layer)."""
        c = self.engine.cfg.eva_chunk
        if c:
            self.engine._tel.eva_summaries_written.inc((pos + n) // c - pos // c)

    def _note_prefill_moe_locked(self, moe, n_tokens: int) -> None:
        """Keep what a prefill chunk's expert layers did (``_moe_of_piece``,
        on the device) for a later decode chunk's delivery to count (cond
        held). Telemetry off, nothing is kept and nothing is read."""
        if moe is not None and self.engine._tel.enabled:
            self._moe_pending.append((moe, n_tokens))

    def _routing_layers(self) -> int:
        """The layers that route: not an arch's leading dense ones."""
        cfg = self.engine.cfg
        return cfg.n_layers - min(cfg.first_dense, cfg.n_layers)

    def _count_moe(self, held: int, tokens: int, forwards: int) -> None:
        """``tokens`` tokens made ``held`` of their expert choices, over all
        layers, on experts held here, in ``forwards`` forward steps."""
        cfg, tel = self.engine.cfg, self.engine._tel
        layers = self._routing_layers()
        tel.moe_assigned_held.inc(held)
        tel.moe_assigned_absent.inc(tokens * layers * cfg.n_active_experts - held)
        tel.moe_rows_per_expert.observe(held / (forwards * layers * cfg.n_experts))

    def _count_expert_rows(
        self, phase: str, chosen: int, every_row: int, layers: int, rows: int, launched: int
    ) -> None:
        """``dllama_moe_expert_rows_total``: of ``layers`` expert layers of
        programs of ``rows`` rows, in which ``chosen`` rows in all chose a held
        expert, ``every_row`` ran every held expert over every row of the
        program and the rest each expert over the rows that chose it. The
        step's own counts: the chosen rows come summed over the layers, so
        the bucketed layers are given their even part of them. ``launched``:
        the rows the layers' grouped launches multiplied, a bucket's pad rows
        up to its last live row tile among them."""
        tel, held = self.engine._tel, self.engine.cfg.n_experts
        tel.moe_rows_launched[phase].inc(launched)
        tel.moe_rows_chosen[phase].inc(chosen)
        tel.moe_rows_computed[phase].inc(
            every_row * held * rows + chosen * (layers - every_row) / max(layers, 1))

    def _count_prefill_moe(self, wait: bool) -> None:
        """Count what the expert layers of the prefill chunks dispatched so
        far did (telemetry on: nothing is pending otherwise): the held-expert
        sums of an arch that holds a share, and the layers by the path they
        took. ``wait``: the read is a device-to-host fetch on the delivery
        path and WAITS for those chunks, the ones queued on the device
        behind the pending decode chunk too: while prompt work is queued,
        the next decode dispatch comes later. That is a cost of counting,
        not a rule of the scheduler, and it has a measured side (PERF.md §6
        and §7, PR 26: against a read of the ready sums only, `out_tok_s`
        502-541 over 9 runs against 435-503 over 11, `stall` 10 % longer),
        which the cells of the archs that hold a share were accepted with. A
        rule "no decode dispatch while prefill chunks are queued", for every
        architecture and whether or not anything is counted, is ROADMAP
        Speed's to propose and measure. Without ``wait`` only the chunks
        already complete are read (they complete in dispatch order) and the
        rest are left for the next delivery: no wait is added."""
        with self._cond:
            pending = self._moe_pending
            n = len(pending)
            if not wait:
                n = next((i for i, (moe, _) in enumerate(pending) if not moe.is_ready()), n)
            pending, self._moe_pending = pending[:n], pending[n:]
        cfg, tel = self.engine.cfg, self.engine._tel
        for moe, n_tokens in pending:
            try:
                held, every_row, bucketed, *launched = (int(v) for v in np.asarray(moe))
            except Exception:  # the chunk failed on the device: its request says so
                continue
            if cfg.n_routed_experts:
                self._count_moe(held, n_tokens, 1)
                # the rows of the padded program the piece ran as (but for a piece cut to its
                # tokens at the context's very end)
                self._count_expert_rows("piece", held, every_row, every_row + bucketed,
                                        _prefill_bucket(n_tokens), *launched)
            tel.moe_piece_every_row.inc(every_row)
            tel.moe_piece_bucketed.inc(bucketed)

    def _take_snapshot_locked(self, stream: BatchStream) -> None:
        """Copy ``stream``'s recurrent state, as the prefill chunk just
        dispatched leaves it, into a snapshot slot the row holds until its
        publish (cond held; device ordering puts the copy after that chunk
        and before the next). No slot to be had: the row publishes its
        pages without one, and no later prompt resumes there."""
        self._drop_snapshot_locked(stream)
        slot = self._prefix.snapshot_slot()
        if slot is None:
            return
        with self.engine._tel.span("state_snapshot", batch_row=stream.row, pos=stream.pos):
            self._snaps = _snapshot_take(
                self._slab, self._snaps, jnp.int32(stream.row), jnp.int32(slot)
            )
        self._ledger.counted("snapshot")
        stream._snap_slot = slot
        self._prefix.tel.snapshots_taken.inc()

    def _drop_snapshot_locked(self, stream: BatchStream) -> None:
        if stream._snap_slot is not None:
            self._prefix.snap_free.append(stream._snap_slot)
            stream._snap_slot = None

    def _drop_row_snapshot(self, stream: BatchStream) -> None:
        if stream._snap_slot is not None:
            with self._cond:
                self._drop_snapshot_locked(stream)

    def _slice_pages_locked(self, pids: list[int]) -> list:
        """The PrefixCache eviction hook (cond held; the ``_locked`` name
        puts this body under LCK-002, which could not see the wait that sat
        here behind the callback until ISSUE 38): pool pages ``pids`` sliced
        across every layer and half into fresh device buffers, one handle
        (a flat list in the spill-entry layout) a page. ENQUEUE ONLY: one
        launch a page, each dispatched before any later publish can recycle
        its page id (device ordering keeps the read exact) and none waited
        for. A publish's victims past what the arena can keep are never
        sliced (``HostArena.keeps``), so this is at most the arena's budget
        of launches and of device bytes in flight."""
        self._ledger.counted("spill_slice", len(pids))
        return [_slice_page(self._pool, np.int32(pid)) for pid in pids]

    @staticmethod
    def _land_pages(handles: list) -> list[list[np.ndarray]]:
        """Host byte arrays of :meth:`_slice_pages_locked`'s handles: ONE
        blocking transfer of them all. The arena's spiller thread calls
        this with no lock held; under ``_cond`` it is an LCK-002 finding
        (``blocking_calls``) and, behind any callback, the witness's."""
        lockcheck.note_blocking("BatchScheduler._land_pages")
        return jax.device_get(handles)

    def _page_pytree(self, arrays: list) -> list:
        """Regroup a flat spill entry back into the per-layer tuples of
        array lists :func:`_upload_page` consumes ((k, v); a latent layer's
        one). Raises on a layout mismatch (a spill entry from an incompatible
        config must fall back to a cold prefill, never upload misshapen
        bytes)."""
        layers: list[tuple] = []
        i = 0
        for halves in filter(None, self._pool):
            entry = []
            for half in halves:
                n = kvc.pool_page_arrays_per_half(half)
                entry.append(list(arrays[i : i + n]))
                i += n
            layers.append(tuple(entry))
        if i != len(arrays):
            raise ValueError(
                f"spill entry layout mismatch: {len(arrays)} arrays, "
                f"expected {i}"
            )
        return layers

    def _reload_spilled_locked(self, tokens: np.ndarray) -> int:
        """Pull spilled pages of this prompt's prefix back into the pool
        BEFORE the radix match (cond held): the match then binds the
        reloaded chain exactly like always-resident pages. The
        ``engine.spill`` chaos site fires per candidate block (``row=``
        selects the REPLICA id, like engine.sdc): a raise aborts the
        reload — already-uploaded blocks stay, deeper blocks prefill cold
        — and ``kind=corrupt`` flips arena bytes in place so the CRC gate
        must catch them (stale KV is never served)."""
        prefix = self._prefix

        def pre(chain_key):
            rule = self._faults.fires("engine.spill", row=self.replica_id)
            if rule is None:
                return
            if rule.kind == "corrupt":
                # silent in-arena corruption (a host RAM / disk bit flip):
                # nothing raises here — the reload's CRC verification is
                # the only thing standing between this and served-wrong-KV
                prefix.spill_corrupt(chain_key)
            else:
                raise faults.InjectedFault(
                    rule.message or "injected fault at engine.spill"
                )

        def upload(pid, arrays):
            with self.engine._tel.span("prefix_spill_reload", page=int(pid)):
                # the closure runs SYNCHRONOUSLY inside prefix.reload,
                # still under _reload_spilled_locked's cond — the AST
                # can't see through the callback boundary
                self._pool = _upload_page(  # dllama: noqa[LCK-004]
                    self._pool, np.int32(pid), self._page_pytree(arrays)
                )
                self._ledger.counted("spill_reload")

        return prefix.reload(tokens, upload, pre=pre)

    def _match_alias(self, stream: BatchStream, tokens: np.ndarray) -> list:
        """Walk the radix tree for the prompt's longest published prefix
        and bind it to the row: the row records the chain's page ids and
        advances its position past the matched tokens. On one chip the
        chain's pages of every layer that has a pool half are then COPIED
        into the slab row at their own positions (:func:`_restore_pages`,
        enqueued and never waited for): the row is a cold prefill's from
        there on, and the suffix prefill's and every later step's programs
        are handed an empty read alias (:meth:`_alias_arrays_locked`). On
        the tp backend no bytes move and the attention reads the pages in
        place through the row's table. The chain's
        refs stay held for the row's lifetime. With a spill arena, pages
        of this prefix that were evicted to host RAM (by this replica or
        a peer) are re-uploaded first, so the match sees the full
        reloadable chain."""
        prefix = self._prefix
        tr = stream.trace
        t0 = time.monotonic() if tr is not None else 0.0
        reloaded = 0
        # pages of this prompt that an eviction sent on their way to the
        # host a moment ago: this thread waits for them HERE, before the
        # lock, so that they reload; under the lock a pending page is a miss
        prefix.await_pending(tokens)
        with self._cond:
            # unwind any stale alias left by a caller that skipped reset
            self._release_pins_locked(stream)
            if prefix.spill is not None and not self._lost:
                # a dead replica must not re-announce chains to the shared
                # index after the pool dropped its ownership
                reloaded = self._reload_spilled_locked(tokens)
            chain = prefix.match(tokens, resumable=self._snaps is not None)
            if tr is not None:
                # admission-time cache outcome in the request's own tree:
                # how much prompt the match skipped, and how many spilled
                # pages had to re-upload to get there (ISSUE 16)
                tr.add_span(
                    "prefix_match", t0, time.monotonic() - t0,
                    matched_tokens=len(chain) * prefix.page,
                    pages=len(chain), reloaded_pages=reloaded,
                )
            if not chain:
                return []
            stream._alias_chain = chain
            stream._alias_ids = [nd.page_id for nd in chain]
            stream.matched_len = len(chain) * prefix.page
            stream.pos = stream.matched_len
            if self._snaps is not None:
                # the chain ends at a block with a snapshot: the row's
                # recurrent state resumes from it, as its attention resumes
                # from the pages
                with self.engine._tel.span(
                    "state_restore", batch_row=stream.row, pos=stream.pos
                ):
                    self._slab = _snapshot_restore(
                        self._slab, self._snaps, jnp.int32(stream.row),
                        jnp.int32(chain[-1].snap),
                    )
                self._ledger.counted("snapshot")
                prefix.tel.snapshots_restored.inc()
            if self._hit_restores:
                # copied into the row, not aliased: the full layers' pages at
                # their own blocks. Enqueued, never waited for; the ids and
                # the scalars are host buffers that cross with the dispatch
                with self.engine._tel.span(
                    "prefix_restore", batch_row=stream.row, pages=len(chain)
                ):
                    self._slab = _restore_pages(
                        self._slab, self._pool, *self._restore_runs(stream._alias_ids),
                        np.int32(stream.row),
                    )
                self._ledger.counted("restore")
                prefix.tel.restores.inc()
                prefix.tel.restored_bytes.inc(len(chain) * prefix.page_bytes)
            if self.engine.cfg.has_eva:
                # copied into the row, not aliased: every block's summaries,
                # and the keys and values of the hit's own window
                first = prefix.tail_start(len(chain))
                with self.engine._tel.span(
                    "window_tail_restore", batch_row=stream.row, pos=stream.pos
                ):
                    self._slab = self._eva_restore(
                        self._slab, stream.row, [nd.page_id for nd in chain],
                        [nd.wpage for nd in chain[first:]], first,
                    )
            elif self._wpool is not None:
                # the window layers' keys and values of the positions before
                # the hit's end, into the row's rings: those layers resume
                # from there as the full ones resume from the pages
                tail = chain[-prefix.window_tail:]
                first = len(chain) - len(tail)
                tail = [tail[0]] * (prefix.window_tail - len(tail)) + tail
                # host buffers of window_tail ints: they cross with the dispatch
                pages = np.fromiter((nd.wpage for nd in tail), np.int32, len(tail))
                blocks = np.fromiter(
                    (max(first, len(chain) - len(tail) + i) for i in range(len(tail))),
                    np.int32, len(tail),
                )
                with self.engine._tel.span(
                    "window_tail_restore", batch_row=stream.row, pos=stream.pos
                ):
                    self._slab = _restore_window_tail(
                        prefix.page, self._slab, self._wpool, pages, blocks,
                        jnp.int32(stream.row), ring=self._wslots.ring,
                    )
                self._ledger.counted("window_tail")
        return chain

    def _publish_row(self, stream: BatchStream, tokens: np.ndarray, chain: list) -> None:
        """Publish the admission prefill's completed full pages back into
        the tree (blocks beyond the matched chain): the row's private
        suffix KV becomes immutable shared pages. The matched chain's refs
        are NOT released here: a tp row keeps reading those pages through
        its table until it resets, quarantines or rolls back below them
        (a one-chip row has copied them; releasing its pins after the copy
        would change the eviction order and is its own change)."""
        prefix = self._prefix
        page = prefix.page
        with self._cond:
            if self._lost:
                # the replica died between the last suffix chunk and here:
                # a publish now would re-announce chains to the shared
                # index AFTER the pool dropped this replica's ownership
                # (dangling routing); the request's own ReplicaLost
                # surfaces at its next chunk boundary
                self._drop_snapshot_locked(stream)
                return
            new_ids, new_blocks = prefix.publish(tokens, tokens.shape[0], chain)
            if stream._snap_slot is not None:
                # the snapshot goes with the chain: to the block that ends
                # where it was taken (or back, if that block is not there)
                slot, stream._snap_slot = stream._snap_slot, None
                prefix.snapshot_attach(tokens, tokens.shape[0] // page * page, slot)
            if self._wpool is not None:
                self._publish_window_tail_locked(stream, tokens, len(chain))
            if new_ids:
                ids, src = _padded_pages(new_ids, new_blocks, prefix.capacity)
                with self.engine._tel.span(
                    "prefix_publish", pages=len(new_ids), batch_row=stream.row
                ):
                    try:
                        if self.engine.cfg.has_eva:
                            # the pool's page of a block: its summaries
                            self._pool = _publish_pages(
                                self._sslots.page, self._slab, self._pool, jnp.asarray(ids),
                                jnp.asarray(src), jnp.int32(stream.row), base=self._sslots.base,
                            )
                        elif self.engine._tp_engine is None:
                            self._pool = _publish_pages(
                                page, self._slab, self._pool, jnp.asarray(ids),
                                jnp.asarray(src), jnp.int32(stream.row),
                            )
                        else:
                            self._pool = self.engine._tp_engine.publish_pages(
                                self._slab, self._pool, ids, src, stream.row,
                            )
                        self._ledger.counted("publish")
                    except BaseException as e:
                        # the copy never dispatched: the just-inserted
                        # nodes map blocks to pages holding garbage (or
                        # a recycled prefix's stale bytes) — detach them
                        # or every future match serves wrong KV. The
                        # REQUEST is fine (its prefill completed):
                        # publishing is an optimization, so swallow
                        # everything except interpreter exits
                        prefix.unpublish(tokens, new_ids, new_blocks)
                        if not isinstance(e, Exception):
                            raise
                        print(f"⚠️ prefix publish failed; pages unwound: {e}")

    @staticmethod
    def _restore_runs(ids: list[int]):
        """``(page_ids [2, h], second)`` of :func:`_restore_pages` for a chain
        of pages ``ids``: its first and its last ``h`` pages, ``h`` the largest
        power of two that is at most their number, and the block at which the
        second run starts."""
        h = next_pow2(len(ids) + 1) // 2
        return np.asarray([ids[:h], ids[-h:]], np.int32), np.int32(len(ids) - h)

    def _eva_restore(self, slab, row: int, pages: list[int], tail: list[int], first: int):
        """The (donated) slab with an EVA hit copied into its row ``row``: the
        summaries of blocks 0 .. len(pages) - 1 out of pool pages ``pages``,
        and the keys and values of blocks ``first ..`` (the hit's own window
        up to its end) out of window-pool pages ``tail``. Each copy has ONE
        shape, the most a row can hold, padded with its first entry (a block
        written twice reads the same)."""

        def padded(ids: list[int], start: int, n: int):
            blocks = list(range(start, start + len(ids)))
            return (np.asarray([ids[0]] * (n - len(ids)) + ids, np.int32),
                    np.asarray([start] * (n - len(ids)) + blocks, np.int32))

        slab = _restore_summaries(
            self._sslots.page, slab, self._pool, *padded(pages, 0, self._n_table),
            jnp.int32(row), base=self._sslots.base,
        )
        self._ledger.counted("restore")
        if tail:
            slab = _restore_window_tail(
                self._wslots.page, slab, self._wpool,
                *padded(tail, first, self._prefix.window_align), jnp.int32(row),
                ring=self._wslots.ring,
            )
            self._ledger.counted("window_tail")
        return slab

    def _publish_window_tail_locked(self, stream: BatchStream, tokens: np.ndarray, hit: int) -> None:
        """Copy the window layers' keys and values of the prompt's last whole
        pages out of the row's rings into the window layers' pool (cond held;
        the publish has just put the prompt's blocks into the tree). What the
        rings still hold of this prompt: nothing older than the last piece and
        its padding left (``_window_keep`` pages back from the prompt's end),
        and after a hit at block ``hit`` nothing before the tail it restored.
        A failed copy leaves pages of garbage attached: they are detached."""
        prefix = self._prefix
        n_blocks = tokens.shape[0] // prefix.page
        if prefix.window_align:
            # an EVA row's window store holds its current window whole (pad
            # rows write nothing; a hit restored what lay before it)
            first = prefix.tail_start(n_blocks)
        else:
            first = max(n_blocks - self._window_keep, hit - prefix.window_tail)
        ids, blocks = prefix.attach_window_pages(tokens, tokens.shape[0], first)
        if not ids:
            return
        padded, src = _padded_pages(ids, blocks, prefix.window_pages)
        with self.engine._tel.span("window_tail_publish", pages=len(ids), batch_row=stream.row):
            try:
                self._wpool = _publish_window_pages(
                    prefix.page, self._slab, self._wpool, padded, src, jnp.int32(stream.row),
                    ring=self._wslots.ring,
                )
                self._ledger.counted("window_tail")
            except BaseException as e:
                prefix.detach_window_pages(tokens, blocks)
                if not isinstance(e, Exception):
                    raise
                print(f"⚠️ window-tail publish failed; pages detached: {e}")

    # ------------------------------------------------------------------
    # Alias lifetime (ISSUE 7): pins released at reset/quarantine,
    # truncated on rollback; the read alias (page tables, empty where hits
    # are copied into rows) materialized per dispatch under the cond lock.
    # ------------------------------------------------------------------

    def _release_pins_locked(self, stream: BatchStream) -> None:
        """Release ``stream``'s page pins and clear its table (cond held).
        Idempotent — quarantine and the subsequent reset both call it."""
        if stream._alias_chain and self._prefix is not None:
            self._prefix.release(stream._alias_chain)
        if self._prefix is not None:
            self._drop_snapshot_locked(stream)
        stream._alias_chain = []
        stream._alias_ids = []
        stream.matched_len = 0

    def _release_row_pins(self, stream: BatchStream) -> None:
        if not stream._alias_chain and stream._snap_slot is None:
            # nothing pinned (the common miss/reset case): no lock needed —
            # only this row's owner thread binds/clears its alias state
            stream._alias_ids = []
            stream.matched_len = 0
            return
        with self._cond:
            self._release_pins_locked(stream)

    def _truncate_alias(self, stream: BatchStream, pos: int) -> None:
        """Shrink ``stream``'s alias to ``pos`` after a rollback below its
        matched prefix: positions < pos keep their bytes (a rollback lands
        on a shared TOKEN prefix, so what the pages held, in the row's slab
        or read in place, stays the right KV), pages wholly at or beyond
        ``pos`` lose their pins. The next prefill writes the slab from
        ``pos`` up, and every read takes it from there."""
        with self._cond:
            if stream.matched_len <= pos:
                return
            if self._prefix is not None:
                keep = -(-pos // self._prefix.page)  # pages covering [0, pos)
                drop = stream._alias_chain[keep:]
                if drop:
                    self._prefix.release(drop)
                stream._alias_chain = stream._alias_chain[:keep]
                stream._alias_ids = stream._alias_ids[:keep]
            stream.matched_len = pos

    def _fire_paged_attn_locked(self, joined):
        """The ``engine.paged_attn`` fault site (chaos contract), fired per
        joined row while a paged batched chunk — plain decode OR spec
        verify — is built: a row-targeted raise quarantines ONLY the
        victim, releases its page pins (the aliased pages stay live for
        every other reader) and drops it from the dispatch; survivors
        proceed bit-identically. Returns the surviving rows (those already
        retired by an earlier failure filtered out too — they ride the
        bucket masked-inactive: no cache write, no advance, no delivery)."""
        if self._pool is not None:
            for s in joined:
                try:
                    self._faults.fire("engine.paged_attn", row=s.row)
                except Exception as e:
                    err = faults.RowQuarantined(
                        "batch row retired: paged-attention dispatch failed "
                        "for this row"
                    )
                    err.__cause__ = e
                    s._fetch_error = err
                    self._release_pins_locked(s)
                    self.engine._tel.rows_quarantined.inc()
        return [s for s in joined if s._fetch_error is None]

    def _fire_fused_step_locked(self, joined):
        """The ``engine.fused_step`` fault site (ISSUE 17 chaos contract):
        fired per joined row while a batched chunk — plain decode OR spec
        verify — is about to launch the fused per-layer superstep programs
        (rmsnorm→Q80→matmul epilogue, paged attention, the
        matmul+all-reduce seam). A row-targeted raise mid-superstep
        quarantines ONLY the victim, releases any page pins it holds, and
        drops it from the dispatch; the survivors' streams must be
        bit-identical to a fault-free run — one row's fused program
        failing must never corrupt the shared dispatch."""
        for s in joined:
            try:
                self._faults.fire("engine.fused_step", row=s.row)
            except Exception as e:
                err = faults.RowQuarantined(
                    "batch row retired: fused superstep dispatch failed "
                    "for this row"
                )
                err.__cause__ = e
                s._fetch_error = err
                if self._pool is not None:
                    self._release_pins_locked(s)
                self.engine._tel.rows_quarantined.inc()
        return [s for s in joined if s._fetch_error is None]

    def _alias_arrays_locked(self, rows, live):
        """Per-dispatch page tables [len(rows), n_table] + matched lengths
        (cond held; ``live`` is :meth:`_row_dispatch_arrays_locked`'s
        liveness mask — the ONE definition — not re-derived here). Where a
        hit's pages were copied into its row (``_hit_restores``: one chip)
        every row's read alias is EMPTY: zero tables and matched 0, so
        ``paged_segments`` yields no pool-only and no mixed chunk and the
        scan reads the slab alone, as for a cold row. Where rows alias the
        pool (the tp backend): LIVE rows without an alias (a miss, or
        retired mid-build) get matched 0 — the paged program reads their
        slab rows only, byte-identical to the unpaged dispatch.
        Bucket-padding rows (not joined: outputs
        discarded, cache writes dropped) instead get the max LIVE matched
        length, so a partially-occupied bucket never drags
        ``paged_segments``' pool-only bound down to the mixed path (which
        reads pool AND slab for every row) — their zero tables read pool
        page 0 garbage, which nothing observes."""
        tables = np.zeros((len(rows), self._n_table), np.int32)
        matched = np.zeros(len(rows), np.int32)
        if self._hit_restores:
            return tables, matched
        for b, s in enumerate(rows):
            if live[b] and s._alias_ids:
                tables[b, : len(s._alias_ids)] = s._alias_ids
                matched[b] = s.matched_len
        if live.any():
            matched[~live] = matched[live].max()
        return tables, matched

    def _row_dispatch_arrays_locked(self, rows):
        """Per-row arrays shared by the plain-decode and spec-verify chunk
        builders (cond held): the liveness mask (which is the program's
        ``active``) plus positions / sampling params / folded seeds, inert
        defaults in non-live slots (bucket padding, or rows retired
        mid-build), and the read-alias arrays when the pool is on
        (None otherwise). A slot that is not live has temperature 0.0: its
        token is never read and never fed to a live row, and the program
        skips the sampler's softmax and top-k in a step in which no row
        samples (``sampling.fused_sample_batched``), so a padding row must
        not be the one that asks for a sample. All of them host numpy
        buffers: they cross to the device with the dispatch, as whole
        vectors, and building them issues no device operation whatever the
        bucket. One definition so a lifecycle change to what counts as a
        live row can never reach one dispatch path and skip the other."""
        n = len(rows)
        live = np.fromiter(
            (s._joined and s._fetch_error is None for s in rows), bool, n
        )
        pos = np.zeros(n, np.int32)
        temps = np.zeros(n, np.float32)
        topps = np.full(n, 0.9, np.float32)
        topks = np.zeros(n, np.int32)
        seeds = np.zeros(n, np.uint32)
        for b in np.flatnonzero(live):
            s = rows[b]
            pos[b] = s.pos
            temps[b] = s._temperature
            topps[b] = s._topp
            topks[b] = s._topk
            seeds[b] = s._seed32
        tables = matched = None
        if self._pool is not None:
            tables, matched = self._alias_arrays_locked(rows, live)
        return live, pos, temps, topps, topks, seeds, tables, matched

    def _alias_row_arrays_locked(self, stream: BatchStream):
        """Single-row form of :meth:`_alias_arrays_locked` (the chunked
        prefill dispatch): empty where the hit was copied into the row."""
        table = np.zeros(self._n_table, np.int32)
        matched = 0
        if not self._hit_restores:
            table[: len(stream._alias_ids)] = stream._alias_ids
            matched = stream.matched_len
        return jnp.asarray(table), jnp.int32(matched)

    def check_prefix(self) -> None:
        """Tree invariants extended with alias tracking: no page freed or
        unpinned while any live row's table references it (tests, bench
        chaos gate)."""
        with self._cond:
            if self._prefix is not None:
                self._prefix.check(
                    row_pages=[
                        list(s._alias_ids) for s in self._streams if s._alias_ids
                    ],
                    held_snapshots=sum(s._snap_slot is not None for s in self._streams),
                )

    # ------------------------------------------------------------------
    # Join/leave (between chunks; the cond lock makes the active set
    # coherent per dispatch)
    # ------------------------------------------------------------------

    def _join(
        self, stream: BatchStream, first_token, temperature, topp, seed, topk
    ) -> None:
        from distributed_llama_tpu import prng

        if stream._state_ahead:
            llama.refuse_recurrent(
                self.engine.cfg, f"a second decode of row {stream.row} without a prefill from 0"
            )
        with self._cond:
            if self.spec_draft == 0:
                # behind whatever chunk is in flight on the device queue: the
                # row's next chunk feeds this token first. (A verify step
                # feeds from the row's history: _dispatch_spec_locked)
                if not isinstance(first_token, jax.Array):
                    first_token = np.int32(first_token)
                self._carry = _carry_put(
                    self._carry, np.int32(stream.row), first_token
                )
                self._ledger.counted("carry_put")
            stream._temperature = float(temperature)
            stream._topp = float(topp)
            stream._topk = int(topk)
            stream._seed32 = prng.fold_seed(seed)
            stream._queue.clear()
            stream._delivered = 0
            stream._epoch += 1
            stream._joined = True
            # a decode chunk computes whole chunks: from here on the row's
            # recurrent state may be past ``pos``
            stream._state_ahead = not self.engine.cfg.rewinds_by_position
            stream._chunk_fps = []
            if not isinstance(
                stream._fetch_error, (faults.RowPreempted, faults.ReplicaLost)
            ):
                # stale errors from a previous occupancy clear; a PREEMPTION
                # or REPLICA LOSS that landed between this request's prefill
                # and its decode join must survive the join (the first
                # next_token raises it and the request requeues). Cross-
                # request staleness is impossible: the serving layer
                # retracts an unconsumed preemption when each request ends
                # (retract_preemption), and a lost replica never seats a
                # new request (placement skips dead replicas)
                stream._fetch_error = None
            self._note_work_locked()
            self._cond.notify_all()

    # ------------------------------------------------------------------
    # Priority preemption (ISSUE 8): evict the lowest-priority active row
    # for a strictly-higher-priority arrival. The victim is retired with a
    # typed RowPreempted exactly like a deadline expiry — between chunks,
    # co-batched rows untouched — and the serving layer REQUEUES it: its
    # admission prefill published its prefix pages, so the re-run prefills
    # through the prefix cache and (same seed) streams bit-identically to
    # an uncontended run.
    # ------------------------------------------------------------------

    def min_preemptible_priority(self) -> int | None:
        """Lowest priority among this scheduler's currently evictable rows
        (None when nothing is evictable): the replica pool ranks replicas
        by this so a multi-replica preemption evicts the GLOBALLY
        lowest-priority victim, not the first replica's local minimum
        (server/replicas.py ``preempt_below``)."""
        with self._cond:
            prios = [
                s.priority for s in self._streams
                if s.priority is not None and s._fetch_error is None
            ]
            return min(prios) if prios else None

    def preempt_below(self, priority: int) -> bool:
        """Evict the lowest-priority active row whose priority is strictly
        below ``priority`` (ties: least-progressed row — the cheapest
        restart). Returns True when a row was cleanly evicted. The
        ``engine.preempt`` chaos site fires on the chosen victim: an
        injected raise QUARANTINES it (typed failure, survivors
        bit-identical) instead of requeueing it.

        Page pins are NOT released here, on purpose: the victim may be
        mid-admission-prefill, and dropping its alias table under the
        cond while its final suffix chunk is still dispatching would make
        that chunk attend over never-written slab positions (matched
        reads 0) and then publish the corrupted KV into the shared radix
        tree. Leaving the table intact keeps every in-flight dispatch —
        and any subsequent publish — byte-correct; the pins release
        through the victim's own unwind exactly like a deadline expiry's:
        the prefill-boundary raise unwinds the alias bind in
        _prefill_row, and a mid-decode victim's pins fall at the row's
        next reset/_match_alias (stale-alias reclaim)."""
        engine = self.engine
        with self._cond:
            victims = [
                s for s in self._streams
                if s.priority is not None
                and s.priority < priority
                and s._fetch_error is None
            ]
            if not victims:
                return False
            victim = min(victims, key=lambda s: (s.priority, s.pos))
            injected: Exception | None = None
            try:
                self._faults.fire("engine.preempt", row=victim.row)
            except Exception as e:
                injected = e
            if injected is None:
                err: BaseException = faults.RowPreempted(
                    f"row {victim.row} (tenant {victim.tenant!r}, priority "
                    f"{victim.priority}) preempted by a priority-{priority} "
                    "arrival; requeued through fair admission"
                )
                self.preempted_total += 1
                engine._tel.preemptions.inc()
            else:
                err = faults.RowQuarantined(
                    "batch row retired: preemptive eviction failed for "
                    "this row"
                )
                err.__cause__ = injected
                engine._tel.rows_quarantined.inc()
            victim._fetch_error = err
            self._cond.notify_all()
            return injected is None

    def retract_preemption(self, stream: BatchStream) -> None:
        """Drop an UNCONSUMED preemption marker at request end (the victim
        finished before its next_token could raise): without this, a
        RowPreempted surviving _join could leak into the row's next
        request and requeue it spuriously."""
        with self._cond:
            if isinstance(stream._fetch_error, faults.RowPreempted):
                stream._fetch_error = None

    def _leave(self, stream: BatchStream) -> None:
        with self._cond:
            if not stream._joined and not stream._queue:
                return
            tel = self.engine._tel
            if tel.enabled and stream._delivered:
                # the ledger's last two fates, once per leave: what was
                # queued and never popped is unread, the rest was consumed
                unread = len(stream._queue)
                tel.row_steps_unread.inc(unread)
                tel.row_steps_consumed.inc(stream._delivered - unread)
            stream._delivered = 0
            stream._joined = False
            stream._queue.clear()
            stream._epoch += 1
            self._note_work_locked()
            self._cond.notify_all()
        # a request that stopped at its fused first token (immediate EOS)
        # may leave its kicked chunk dispatched-but-unfetched; if no joined
        # stream remains to fetch it, drain it now — otherwise the engine
        # pipeline depth stays held across the idle period and the transfer
        # probe treats the engine as permanently mid-flight
        self._drain_if_idle()

    def _begin_fetch_locked(self) -> int:
        """Mark a fetch in flight (cond lock held) and return its
        generation — the token the watchdog invalidates on a stall."""
        self._fetching = True
        self._fetch_gen += 1
        self._fetch_started = time.monotonic()
        return self._fetch_gen

    def _drain_if_idle(self) -> None:
        pend = gen = None
        with self._cond:
            if (
                self._pending is not None
                and not self._fetching
                and not any(s._joined for s in self._streams)
            ):
                pend = self._pending
                self._pending = None
                gen = self._begin_fetch_locked()
        if pend is not None:
            self._fetch(pend, gen)

    def kick(self) -> None:
        """Dispatch a batched chunk now if none is in flight (used to start
        chunk 1 before the fused first-token fetch so the fetch overlaps
        the chunk's compute)."""
        with self._cond:
            if self._pending is None:
                self._dispatch_locked()

    # ------------------------------------------------------------------
    # The pump: dispatch under the lock, fetch outside it
    # ------------------------------------------------------------------

    def next_token(self, stream: BatchStream) -> int:
        """Next decoded token for ``stream``; whichever thread runs dry
        first dispatches/fetches the shared batched chunk for everyone.
        Raises the stream's typed failure (RowQuarantined / StallTimeout)
        when its row was retired, and DeadlineExceeded once the request's
        deadline passes — the expired row leaves the batch between chunks
        (stream_decode's finally) without touching its co-batched rows."""
        while True:
            pend = gen = None
            with self._cond:
                if stream._fetch_error is not None:
                    err = stream._fetch_error
                    stream._fetch_error = None
                    raise err
                if (
                    stream.deadline is not None
                    and time.monotonic() >= stream.deadline
                ):
                    raise faults.DeadlineExceeded(
                        f"request deadline expired mid-decode (row "
                        f"{stream.row}); the row leaves the batch"
                    )
                if stream._queue:
                    return stream._queue.popleft()
                if not stream._joined:
                    raise RuntimeError("next_token on a stream that left the batch")
                piece = piece_entry = None
                if self._pending is None:
                    # a no-op while another thread is mid-fetch: the next
                    # chunk goes out once that one is delivered
                    piece = self._dispatch_locked()
                    piece_entry = self._pieces_first_entry
                    if stream._fetch_error is not None:
                        continue  # the dispatch retired this row: re-loop
                        # raises the typed error without a wait cycle
                if self._pending is not None and not self._fetching:
                    pend = self._pending
                    self._pending = None
                    gen = self._begin_fetch_locked()
                elif piece is None:
                    # another thread is mid-fetch: wait for its notify
                    with self.engine._tel.span("sched_wait", row=stream.row):
                        self._cond.wait(timeout=0.1)
                    continue
            if pend is None:
                # queued prompt pieces go first: wait for them with the lock
                # free (prompts that arrive meanwhile are dispatched at once)
                with self.engine._tel.span("sched_wait", row=stream.row):
                    try:
                        piece.block_until_ready()
                    except Exception:
                        pass  # a failed piece fails its own request
                    piece_entry.observed(time.monotonic())
                continue
            self._fetch(pend, gen)

    def _run_dispatch_locked(self, joined, dispatch_fn, fail_msg: str):
        """The shared dispatch frame of the chunk and spec-verify paths
        (cond lock held): raise the pipeline depth (released when the fetch
        drains), run ``dispatch_fn`` under the bounded retry-with-backoff
        loop (``batch.dispatch`` fault hook fired per attempt), and on
        exhausted retries retire every joined row CLEANLY with a typed
        ``fail_msg`` quarantine — no position advanced, the scheduler keeps
        serving. Returns ``dispatch_fn``'s result, or None after retiring
        the rows. KeyboardInterrupt/SystemExit release the depth and
        propagate (they must abort, not retry into quarantines)."""
        engine = self.engine
        try:
            # whole-replica crash site (ISSUE 9): NOT transient — no retry,
            # no per-row quarantine. The scheduler is lost wholesale and
            # every in-flight request requeues onto a surviving replica.
            self._faults.fire("replica.crash", row=self.replica_id)
        except Exception as e:
            self._mark_lost_locked(f"injected crash at dispatch: {e}")
            return None
        # silent-data-corruption site (ISSUE 10): kind=corrupt perturbs
        # this replica's weights (or the next fetched chunk's tokens) into
        # FINITE wrong values — nothing raises, nothing quarantines; only
        # the integrity layer (canary golden / shadow vote) can see it
        self._fire_sdc_locked()
        with engine._depth_lock:
            engine._pipeline_depth += 1  # released when the fetch drains
        result = None
        error: Exception | None = None

        def attempt_once():
            self._faults.fire("batch.dispatch")
            return dispatch_fn()

        try:
            # transient failures (an injected dispatch raise, a flaky
            # runtime) retry on the shared backoff policy
            # (distributed_llama_tpu/retry.py — same base*2**attempt
            # schedule the old inline loop slept). Briefly blocking joins
            # is the cost of a coherent active set: the bounded
            # retries*backoff sleep inside retry_call is the one
            # sanctioned block under this lock.
            result = retry.retry_call(  # dllama: noqa[LCK-002]
                attempt_once, self._retry_policy,
                on_retry=lambda a, e: engine._tel.dispatch_retries.inc(),
            )
        except Exception as e:
            error = e
        except BaseException:
            with engine._depth_lock:
                engine._pipeline_depth -= 1
            raise
        if error is not None:
            with engine._depth_lock:
                engine._pipeline_depth -= 1
            tel = engine._tel
            tel.rows_quarantined.inc(len(joined))
            flight.record(
                self.replica_id, "rows_quarantined",
                rows=[s.row for s in joined], where="dispatch",
                error=type(error).__name__,
            )
            for s in joined:
                err = faults.RowQuarantined(fail_msg)
                err.__cause__ = error
                s._fetch_error = err
                self._release_pins_locked(s)
            self._cond.notify_all()
            return None
        return result

    def _count_lost_chunk(self, pend) -> None:
        """Close the ledger on a dispatched chunk nobody will deliver (the
        watchdog or a replica loss dropped it, or its fetch generation was
        retired): its joined rows' steps end as ``quarantined`` — every one
        of those rows was handed a typed failure."""
        tel = self.engine._tel
        if tel.enabled:
            steps = self.chunk if pend[0] == "chunk" else 1
            tel.row_steps_quarantined.inc(pend[4] * steps)

    def _fire_sdc_locked(self) -> None:
        """The ``engine.sdc`` chaos site (ISSUE 10), fired per batched
        dispatch with ``row=`` selecting the REPLICA id. A ``kind=corrupt``
        rule injects the silent-data-corruption class every other site
        cannot model: ``message=weights`` (the default) deterministically
        perturbs one weight slice of this replica's engine IN PLACE
        (every later decode emits plausible wrong tokens until the canary
        kills the replica and the supervisor rebuilds + checksum-verifies
        it); ``message=logits`` arms a one-chunk in-vocab token
        perturbation applied at the next fetch delivery."""
        rule = self._faults.fires("engine.sdc", row=self.replica_id)
        if rule is None or rule.kind != "corrupt":
            return
        if (rule.message or "weights") == "logits":
            self._sdc_logits_pending += 1
            return
        engine = self.engine
        engine.params, desc = integrity.corrupt_params(
            engine.params, seed=getattr(self._faults, "seed", 0)
        )
        print(
            f"🧬 engine.sdc injected on replica {self.replica_id}: "
            f"corrupted {desc}"
        )

    def _dispatch_locked(self):
        """Build and dispatch one batched chunk from the joined streams
        (cond lock held; the dispatch itself is asynchronous). Rows inside
        the bucket that are not joined ride along masked-inactive: their
        cache writes DROP and their outputs are discarded. In spec mode the
        chunk is a batched VERIFY step instead (``_dispatch_spec_locked``).
        Returns None, or the device array the dispatch is waiting for when
        it declined on account of queued prompt pieces."""
        engine = self.engine
        if self._lost:
            return  # every stream already carries its ReplicaLost
        if self._fetching:
            # one decode chunk on the device queue at a time: the next is
            # dispatched when this one's tokens are in the queues. A chunk
            # enqueued behind the one being fetched would stand in front of
            # every prompt piece that arrives meanwhile (a whole chunk more
            # before a first token), would decode a second chunk for rows
            # whose requests end with this one, and — a verify step — would
            # draft from tokens nobody has seen yet
            return None
        piece = self._pieces_first
        if piece is not None:
            try:
                ready = piece.is_ready()
            except Exception:
                ready = True  # a failed piece fails its own request
            if not ready:
                # prompt pieces that were on the device's queue when the last
                # chunk was delivered run before this chunk wherever it is
                # enqueued: enqueued now, it would only stand in front of the
                # prompts that arrive while they run. The caller waits for
                # the piece outside the lock (next_token) and asks again;
                # pieces dispatched after that delivery are not waited for
                return piece
            self._pieces_first = None
        if self.spec_draft > 0:
            self._dispatch_spec_locked()
            return
        joined = [s for s in self._streams if s._joined]
        if not joined:
            return
        joined = self._fire_paged_attn_locked(joined)
        joined = self._fire_fused_step_locked(joined)
        if not joined:
            self._cond.notify_all()
            return
        bucket = self._built_bucket(
            decode_bucket(
                max(max(s.row for s in joined) + 1, self._bucket_floor), self.b_max
            )
        )
        rows = self._streams[:bucket]
        t_build = time.monotonic()
        with engine._tel.span("sched_build", bucket=bucket, active=len(joined)):
            active, pos, temps, topps, topks, seeds, tables, matched = (
                self._row_dispatch_arrays_locked(rows)
            )
        sw = Stopwatch()

        def dispatch():
            with engine._tel.span(
                "batch_decode_chunk", bucket=bucket, active=len(joined),
                steps=self.chunk,
            ):
                return self._decode_chunk_program(
                    active, pos, temps, topps, topks, seeds, tables, matched
                )

        out = self._run_dispatch_locked(
            joined, dispatch,
            f"batched chunk dispatch failed after {self.retries + 1} "
            "attempts; this row's request was retired",
        )
        if out is None:
            return
        # the packed [chunk + 2, B] bundle: token rows 0..chunk-1 plus the
        # per-row fingerprint/finite rows (engine/integrity.py) — with the
        # stateless counter PRNG those int32 rows are the ONLY bytes the
        # chunk ever sends host-ward (no advanced keys return)
        with engine._tel.span("sched_post_dispatch", active=len(joined)):
            for s in joined:
                # the next chunk seeds from this chunk's last token, which
                # the program left in the carry (no fetch, no per-row slice
                # on the critical path); its coins re-key from (seed,
                # position) — nothing else carries over
                self._note_summaries(s.pos, self.chunk)
                s.pos += self.chunk
        self._decode_built.add(bucket)
        self._note_dispatched(bucket, joined, self.chunk)
        # from the vector the program was handed: the arm its steps took
        if np.any(temps != 0.0):
            engine._tel.chunk_sampler_sampled.inc()
        else:
            engine._tel.chunk_sampler_greedy.inc()
        self._pending = (
            "chunk", out, [(s, s._epoch) for s in joined], bucket,
            len(joined), sw, None, t_build, time.monotonic(),
            self._ledger.dispatched(
                "decode_chunk", out, bucket=bucket, rows=len(joined), gen=self._fetch_gen + 1
            ),
        )
        # after the entry took the claim the gap in FRONT of this chunk was
        # waited under: a row whose request ends inside the chunk needs no other
        self._note_work_locked()

    def _decode_chunk_program(self, active, pos, temps, topps, topks, seeds, tables, matched):
        """Enqueue the plain decode program over the rows the vectors cover
        (cond held) and return its token bundle. Slab and carry are donated
        and come back advanced; the row vectors are host buffers that cross
        with the call. On one chip the program is the one WITHOUT pages
        whether or not a pool is on: a hit was copied into its row at
        admission (``_restore_pages``, an EVA arch's ``_eva_restore``), so no
        decode step reads a page, and an attention that is handed no pages
        may bound each row's reads by the row (``ops.decode_attention``: a
        scan handed pages keeps the XLA loop). ``tables`` and ``matched``
        are the tp backend's, which reads the sharded pool in place."""
        from distributed_llama_tpu.models import sampling

        engine = self.engine
        if engine._tp_engine is None:
            out, self._slab, self._carry = sampling.decode_chunk_batched(
                engine.cfg, engine.params, self._carry, self._slab, pos,
                active, self.chunk, temps, topps, topks, seeds,
            )
        elif self._pool is not None:
            out, self._slab, self._carry = engine._tp_engine.batched_decode_chunk_paged(
                engine.params, self._carry, self._slab, self._pool, pos, active,
                self.chunk, temps, topps, topks, seeds, tables, matched,
            )
        else:
            out, self._slab, self._carry = engine._tp_engine.batched_decode_chunk(
                engine.params, self._carry, self._slab, pos, active, self.chunk,
                temps, topps, topks, seeds,
            )
        return out

    def build_widest_decode_program(self) -> None:
        """Run the plain decode program over ALL rows once, every row
        inactive (nothing is written, the carry stays), so that it is built
        before the server takes traffic. The first chunk that needs it comes
        when more than half the rows are busy at once, in the middle of
        serving, and a build stalls every lane for its length (seconds from
        the compile cache, tens of seconds in a fresh checkout); which
        buckets the traffic before that happened to visit must not decide
        it (PERF.md §6, PR 42: a warm-up whose streams ended sooner no longer
        held nine lanes busy, and the 16-row program was built inside the
        measured window). The bucket is NOT noted as built: a smaller bucket
        still builds its own program (``_built_bucket`` would have every one
        ride this). Call with every row's stream made and none joined."""
        if self.spec_draft > 0:
            return  # its chunks run the verify program
        with self._cond:
            rows = self._streams[: decode_bucket(self.b_max, self.b_max)]
            out = self._decode_chunk_program(*self._row_dispatch_arrays_locked(rows))
        jax.block_until_ready(out)

    def _built_bucket(self, bucket: int) -> int:
        """The row bucket to dispatch when ``bucket`` rows would do: itself
        if its program has run before or no larger one has, else the
        smallest larger bucket whose program has. Building a program stalls
        EVERY lane for the length of a compile (seconds from the compile
        cache, tens of seconds without), while a larger bucket only computes
        masked rows for one chunk; so under load a bucket first met on the
        way DOWN (rows 16-31 idle together for a moment) rides the program
        the way up has already built, and which buckets a warm-up happened
        to visit no longer decides whether a request stalls."""
        if bucket in self._decode_built:
            return bucket
        return min((b for b in self._decode_built if b > bucket), default=bucket)

    def _note_dispatched(self, bucket: int, joined: list, steps: int) -> None:
        """Per dispatched chunk: its joined and bucket rows, the ledger's
        ``masked`` fate — the bucket rows that ran inactive — and how full
        the slab is: the joined rows' positions over rows x seq_len."""
        tel = self.engine._tel
        if tel.enabled:
            tel.chunk_rows_active.observe(len(joined))
            tel.chunk_rows_bucket.observe(bucket)
            tel.row_steps_masked.inc((bucket - len(joined)) * steps)
            tel.kv_occupancy.set(
                sum(s.pos for s in joined) / (self.b_max * self.engine.cfg.seq_len)
            )

    def _dispatch_spec_locked(self) -> None:
        """Build and dispatch one batched speculative VERIFY step (cond
        lock held): per joined row, up to ``spec_draft`` prompt-lookup
        draft tokens from the row's own history ride behind its previous
        token in a [bucket, k+1] feed window; one
        ``sampling.spec_verify_chunk_batched`` dispatch scores every row's
        window in a single weight read and accepts/rejects on device. Rows
        advance a VARIABLE number of positions — applied at fetch time,
        because the advance (and the next window's drafts) depend on the
        fetched results (``_dispatch_locked`` never calls this behind an
        in-flight fetch). That is also why a verify step
        keeps a feed of its own and does not ride the device carry of the
        plain chunks: the window is built on the host from host ints, and
        its first token is the last entry of the row's ``_history`` (the
        join's first token, then every delivered step's last emitted
        one)."""
        engine = self.engine
        joined = [s for s in self._streams if s._joined]
        if not joined:
            return
        joined = self._fire_paged_attn_locked(joined)
        joined = self._fire_fused_step_locked(joined)
        if not joined:
            self._cond.notify_all()
            return
        bucket = decode_bucket(
            max(max(s.row for s in joined) + 1, self._bucket_floor), self.b_max
        )
        rows = self._streams[:bucket]
        T = self.spec_draft + 1
        S = engine.cfg.seq_len
        feed = np.zeros((bucket, T), np.int32)
        lens = np.zeros(bucket, np.int32)
        t_build = time.monotonic()
        with engine._tel.span("sched_build", bucket=bucket, active=len(joined)):
            active, pos, temps, topps, topks, seeds, tables, matched = (
                self._row_dispatch_arrays_locked(rows)
            )
        for s, ok in zip(rows, active):
            if not ok:
                continue
            feed[s.row, :] = s._history[-1]  # pad tokens: overwritten KV
            # never draft past seq_len: the window writes pos..pos+T-1 and
            # out-of-bounds slots drop, but accepted positions must stay
            # inside the cache
            budget = max(0, min(self.spec_draft, S - s.pos - 1))
            if budget > 0 and s._spec_on:
                if s._drafter is None:
                    s._drafter = PromptLookupDrafter(
                        self.spec_draft, max_ngram=self.spec_ngram
                    )
                d = s._drafter.draft(s._history, limit=budget)
                if d:
                    feed[s.row, 1 : 1 + len(d)] = d
                    lens[s.row] = len(d)
        sw = Stopwatch()
        from distributed_llama_tpu.models import sampling

        def dispatch():
            with engine._tel.span(
                "spec_verify_chunk", bucket=bucket, active=len(joined),
                window=T,
            ):
                if self._pool is not None:
                    out, self._slab = (
                        sampling.spec_verify_chunk_batched_paged(
                            engine.cfg, engine.params, jnp.asarray(feed),
                            self._slab, pos, active, self._pool,
                            jnp.asarray(lens), temps, topps, topks, seeds,
                            tables, matched,
                        )
                    )
                else:
                    out, self._slab = sampling.spec_verify_chunk_batched(
                        engine.cfg, engine.params, jnp.asarray(feed),
                        self._slab, pos, active, jnp.asarray(lens), temps,
                        topps, topks, seeds,
                    )
            return out

        out = self._run_dispatch_locked(
            joined, dispatch,
            f"batched verify dispatch failed after {self.retries + 1} "
            "attempts; this row's request was retired",
        )
        if out is None:
            return
        # pos and the next feed wait for the fetch (the advance is variable
        # and data-dependent); sampler coins re-key from (seed, position)
        engine._tel.spec_draft_tokens.inc(int(lens.sum()))
        # a verify step is one weight read: a masked row is 1 row-step
        self._note_dispatched(bucket, joined, 1)
        self._pending = (
            "spec", out, [(s, s._epoch) for s in joined], bucket, len(joined),
            sw, lens.copy(), t_build, time.monotonic(),
            self._ledger.dispatched(
                "spec_verify", out, bucket=bucket, rows=len(joined), gen=self._fetch_gen + 1
            ),
        )

    def _fetch(self, pend, gen: int) -> None:
        """Blocking fetch of a dispatched chunk (no scheduler lock held);
        delivers each joined row's column into its stream queue. Transient
        fetch failures retry with backoff; a chunk whose tokens come back
        corrupted for ONE row (the NaN-logits class of failure — detected
        as out-of-vocab ids, injectable via the ``batch.row`` site)
        quarantines only that row, and the surviving rows' streams are
        delivered untouched — bit-identical to a fault-free run. The epoch
        check keeps a late fetch from feeding a row's NEXT occupant; the
        generation check keeps a watchdog-killed fetch from delivering at
        all."""
        engine = self.engine
        (mode, tokens_dev, snapshot, bucket, n_active, sw, spec_lens, t_build,
         t_dispatched, ledger_entry) = pend
        toks = None
        error: Exception | None = None
        t_fetch = time.monotonic()  # since t_dispatched: queued behind a fetch
        waited = 0.0  # seconds the last attempt blocked on the device

        def attempt_once():
            nonlocal waited
            self._faults.fire("batch.fetch")
            # replica chaos (ISSUE 9): `slow` (kind=delay) stretches this
            # round-trip past the pool's suspect threshold, `hang`
            # (kind=hang) sleeps into the stall watchdog — escalated to a
            # whole-replica loss under lost_on_stall
            self._faults.fire("replica.slow", row=self.replica_id)
            self._faults.fire("replica.hang", row=self.replica_id)
            try:
                tokens_dev.copy_to_host_async()
            except Exception:
                pass  # optional acceleration; np.asarray is the contract
            with engine._tel.span("batch_decode_fetch", bucket=bucket):
                t = time.monotonic()
                out = np.asarray(tokens_dev)  # [chunk, bucket]
                done = time.monotonic()
                ledger_entry.observed(done)  # perhaps before the ledger's watcher woke
                waited = done - t
                return out

        try:
            # Exception only (retry_call's contract): a KeyboardInterrupt/
            # SystemExit mid-fetch must abort the process, not be retried
            # into quarantines
            toks = retry.retry_call(
                attempt_once, self._retry_policy,
                on_retry=lambda a, e: engine._tel.fetch_retries.inc(),
            )
        except Exception as e:
            error = e
        except BaseException:
            # a KeyboardInterrupt/SystemExit mid-fetch: release the in-flight
            # accounting (unless the watchdog already took it) and propagate
            with self._cond:
                owned = self._fetching and self._fetch_gen == gen
                if owned:
                    self._fetching = False
                    self._fetch_started = None
                    with engine._depth_lock:
                        engine._pipeline_depth -= 1
                self._cond.notify_all()
            raise
        # phase 1 of the completion claim (the watchdog declares stalls
        # under the same lock, so exactly one side — this fetch or the
        # watchdog — releases the depth hold and settles the rows):
        # clearing _fetch_started makes this fetch un-stallable, but
        # _fetching stays TRUE until the delivery block below — otherwise
        # another thread could take the pending speculative chunk N+1 and
        # deliver its tokens ahead of chunk N's during the stats window
        with self._cond:
            owned = self._fetching and self._fetch_gen == gen
            if owned:
                self._fetch_started = None
                with engine._depth_lock:
                    engine._pipeline_depth -= 1
        if not owned:
            # the watchdog retired this generation mid-fetch: the joined
            # rows already hold StallTimeout errors, the depth hold was
            # released on our behalf, and a newer fetch may be in flight —
            # deliver nothing
            self._count_lost_chunk(pend)
            with self._cond:
                self._cond.notify_all()
            return
        hook = self.health_hook
        if hook is not None and error is None:
            # dispatch→fetch round-trip heartbeat: the pool's health state
            # machine turns the replica SUSPECT past its threshold and
            # back HEALTHY on a fast round-trip (server/replicas.py)
            hook("roundtrip", sw.elapsed_s())
        t_fetched = time.monotonic()
        tel = engine._tel
        with tel.span("sched_deliver", bucket=bucket, active=n_active):
            if mode == "spec":
                self._deliver_spec(toks, snapshot, sw, spec_lens, error)
            else:
                self._deliver_chunk(toks, snapshot, bucket, n_active, sw, error)
        t_end = time.monotonic()
        if tel.enabled:
            tel.chunk_fetch_wait.observe(waited)
            tel.chunk_host.observe((t_dispatched - t_build) + (t_end - t_fetched))
            tel.chunk_build.observe(t_dispatched - t_build)
        if t_end - t_build > self.slow_chunk_s:
            # rare by construction (a chunk is a fraction of a second): say
            # where this one's time went — on the host (building and
            # dispatching it, waiting for a thread to fetch it, delivering
            # it) or in the fetch, blocked on the device
            phases = {
                "dispatch": t_dispatched - t_build, "queued": t_fetch - t_dispatched,
                "fetch": t_fetched - t_fetch, "deliver": t_end - t_fetched,
            }
            phase = max(phases, key=phases.get)
            flight.record(
                self.replica_id, "slow_chunk", phase=phase,
                where="device" if phase == "fetch" else "host",
                seconds=round(t_end - t_build, 6), mode=mode, bucket=bucket,
                active=n_active,
                **{f"{k}_s": round(v, 6) for k, v in phases.items()},
            )
        # a chunk kicked WHILE this fetch was in flight may already be
        # orphaned (its kicker stopped at the fused first token and its
        # _leave-time drain skipped because _fetching was still true):
        # re-check the idle-drain condition now that the fetch is done —
        # the one-pending-slot invariant bounds the recursion.
        self._drain_if_idle()

    def _deliver_chunk(self, toks, snapshot, bucket, n_active, sw, error) -> None:
        """Deliver one fetched plain decode chunk: validate each snapshot
        row's column, queue it to the row's stream if the stream is still
        the one that was joined at dispatch, and close the row-step ledger
        on the rest. Runs with fetch ownership already claimed
        (``_fetch``)."""
        engine = self.engine
        per_token_ms = sw.elapsed_ms() / self.chunk
        # the I/T split may trigger a transfer re-measurement (a device
        # round trip under TP) — run it BEFORE taking the scheduler
        # lock so a probe never blocks every lane's join/dispatch
        entry = engine._split_stats(per_token_ms)
        tel = engine._tel
        bad_rows: set[int] = set()
        nonfinite_rows: set[int] = set()
        fps = None
        if toks is not None:
            # unpack the [chunk + 2, B] bundle: tokens + per-row logit
            # fingerprint + finiteness flag (ONE fetch moved all three)
            extra = list(integrity.chunk_extra_rows(toks, self.chunk))
            toks, fps, finite = integrity.split_chunk_outputs(toks, self.chunk)
            if engine.cfg.n_routed_experts and extra:
                # the expert share's routing sums came with the tokens, the layer-steps
                # of the chunk that ran every held expert over every row (no bucket under
                # the step's rows, or one that overflowed: models.moe._held_experts), and
                # the rows the held experts' launches multiplied
                held, every_row, launched = extra.pop(0), int(extra.pop(0)[0]), int(extra.pop(0)[0])
                if tel.enabled:
                    self._count_moe(int(held.sum()), n_active * self.chunk, self.chunk)
                    self._count_expert_rows("decode", int(held.sum()), every_row,
                                            self._routing_layers() * self.chunk, bucket, launched)
                    self._count_prefill_moe(wait=True)
            elif engine.cfg.is_moe and tel.enabled:
                # every expert held: nothing is read here but the layers'
                # paths, and those only where the piece is already complete
                self._count_prefill_moe(wait=False)
            if engine.cfg.kv_read_kinds and extra and tel.enabled:
                # ... and the cache positions each row's layers read, by kind
                for kind, row in zip(engine.cfg.kv_read_kinds, extra):
                    if kind == "dsa_visible":
                        # what the rows' queries could see, against what was selected and read
                        tel.dsa_visible_positions.inc(int(row.sum()) // engine.cfg.n_layers)
                        continue
                    # an index key's bytes are its own; a selected row is a latent row
                    nbytes = self._kv_bytes_by_kind.get(kind, self._kv_position_bytes)
                    tel.kv_read[kind].inc(int(row.sum()) * nbytes)
                    if kind in tel.kv_read_positions:
                        # a row's sum runs over its layers: positions, once each
                        tel.kv_read_positions[kind].inc(int(row.sum()) // engine.cfg.n_layers)
            with self._cond:
                if self._sdc_logits_pending > 0:
                    # engine.sdc message=logits: shift every token column
                    # in-vocab — finite, wrong, and INVISIBLE to the
                    # validation below; only a canary/shadow token
                    # comparison can see it (the fingerprint keeps its
                    # honest pre-corruption value on purpose: the logits
                    # themselves were clean)
                    self._sdc_logits_pending -= 1
                    toks = (toks + 1) % engine.cfg.vocab_size
            rule = self._faults.fires(
                "batch.row", rows=[s.row for s, _ in snapshot]
            )
            if (
                rule is not None
                and rule.row is not None
                and 0 <= rule.row < toks.shape[1]
            ):
                toks = toks.copy()
                toks[:, rule.row] = -1  # rejected by the validation below
            vocab = engine.cfg.vocab_size
            for s, _ in snapshot:
                # the device-side finiteness flag closes the sampled-path
                # hole (ISSUE 10 satellite): NaN logits pushed through the
                # softmax sampler can yield a perfectly in-vocab id the
                # vocab check below would wave through
                if not finite[s.row]:
                    nonfinite_rows.add(s.row)
                    continue
                col = toks[:, s.row]
                if not ((col >= 0) & (col < vocab)).all():
                    bad_rows.add(s.row)
        delivered = orphaned = quarantined = 0
        with self._cond:
            # phase 2: deliver and release fetch ownership in ONE block, so
            # the pending chunk N+1 can only be taken (and its tokens
            # queued) strictly after chunk N's tokens are in the queues
            self._fetching = False
            self._pieces_first = self._last_piece
            self._pieces_first_entry = self._last_piece_entry
            for s, epoch in snapshot:
                if not (s._joined and s._epoch == epoch):
                    # the row left (or its slot has a new occupant) while
                    # this chunk, dispatched ahead, was on the device
                    orphaned += 1
                    continue
                if toks is None or s.row in bad_rows or s.row in nonfinite_rows:
                    # the row's tokens are lost/corrupt and its position
                    # already advanced at dispatch: retire THIS row with
                    # a typed error instead of emitting a silent token
                    # hole — and instead of the seed's poison-everyone
                    if s.row in nonfinite_rows:
                        err: faults.RowQuarantined = faults.NonFiniteLogits(
                            "batch row retired: decode produced non-finite "
                            "logits for this row (caught by the device-side "
                            "finiteness flag before a sampled token could "
                            "launder it in-vocab)"
                        )
                    else:
                        err = faults.RowQuarantined(
                            "batch row retired: chunk "
                            + (
                                f"fetch failed after {self.retries + 1} attempts"
                                if toks is None
                                else "produced corrupt tokens (NaN-logits "
                                "class failure)"
                            )
                        )
                    err.__cause__ = error
                    s._fetch_error = err
                    self._release_pins_locked(s)
                    tel.rows_quarantined.inc()
                    flight.record(
                        self.replica_id, "rows_quarantined", rows=[s.row],
                        where="fetch", error=type(err).__name__,
                    )
                    quarantined += 1
                    continue
                s._queue.extend(int(t) for t in toks[:, s.row])
                s._delivered += self.chunk
                s._chunk_fps.append(int(fps[s.row]))
                s.stats.extend([entry] * self.chunk)
                delivered += 1
                if s.trace is not None:
                    # per-row child of the SHARED dispatch (ISSUE 16): one
                    # batched chunk fans out into each traced request's own
                    # tree, spanning dispatch → this delivery
                    s.trace.add_span(
                        "batch_decode_chunk_row", sw._t0, sw.elapsed_s(),
                        row=s.row, chunk=self.chunk, bucket=bucket,
                        co_batched=n_active,
                    )
            self._cond.notify_all()
        if tel.enabled:
            tel.row_steps_orphaned.inc(self.chunk * orphaned)
            tel.row_steps_quarantined.inc(self.chunk * quarantined)
            if delivered:
                tel.tokens_generated.inc(self.chunk * delivered)
                tel.device_sampled_tokens.inc(self.chunk * delivered)
                tel.decode_latency.observe(per_token_ms / 1000.0)
            self._note_state_tokens("decode", self.chunk * n_active)

    def _note_state_tokens(self, phase: str, tokens: int) -> None:
        """``tokens`` went through every recurrent layer (a decode chunk's
        joined rows x steps, a prompt piece's real tokens): counted by the
        layers' kind (``dllama_state_layer_tokens_total``)."""
        tel = self.engine._tel
        if self._state_layers and tel.enabled:
            tel.state_layer_tokens[self.engine.cfg.state_mixer, phase].inc(
                tokens * self._state_layers
            )

    def _deliver_spec(self, toks, snapshot, sw, lens, error) -> None:
        """Deliver one fetched batched VERIFY step: row ``b``'s column is
        ``[n_emit, tokens...]`` — apply its VARIABLE position advance,
        extend its lookup history, and queue the emitted tokens. Runs with
        fetch ownership already claimed (``_fetch``); corrupt or
        chaos-targeted rows quarantine individually, survivors delivered
        bit-identically (the ``engine.spec_verify`` site's contract)."""
        engine = self.engine
        tel = engine._tel
        vocab = engine.cfg.vocab_size
        step_ms = sw.elapsed_ms()
        bad: dict[int, BaseException | None] = {}
        emits: dict[int, list[int]] = {}
        entries: dict[int, TokenStats] = {}
        if toks is not None:
            for s, _ in snapshot:
                # the chaos hook: a row-targeted raise quarantines ONLY this
                # row while its column is validated (outside the cond lock,
                # like the batch.row corruption hook)
                try:
                    self._faults.fire("engine.spec_verify", row=s.row)
                except Exception as e:
                    bad[s.row] = e
                    continue
                # validate against the row's OWN draft budget, not the
                # global window: a corrupt n_emit in (lens+1, T] would pass
                # a T bound (the token tail is zero-padded, in-vocab) and
                # advance pos past the dispatch-side seq_len clamp
                n_emit = int(toks[s.row, 0])
                if not 1 <= n_emit <= int(lens[s.row]) + 1:
                    bad[s.row] = None
                    continue
                col = toks[s.row, 1 : 1 + n_emit]
                if not ((col >= 0) & (col < vocab)).all():
                    bad[s.row] = None  # NaN-logits class corruption
                    continue
                emits[s.row] = [int(t) for t in col]
                # the I/T split may probe the device under TP — build every
                # row's stats entry BEFORE taking the scheduler lock, same
                # rule as the plain chunk delivery
                entries[s.row] = engine._split_stats(step_ms, n_tokens=n_emit)
        delivered_rows = 0
        delivered_tokens = 0
        # the ledger in verify steps: a row that emitted counts its variable
        # advance (on delivery, or as orphaned), any other row 1 step
        orphaned = quarantined = 0
        with self._cond:
            self._fetching = False
            self._pieces_first = self._last_piece
            self._pieces_first_entry = self._last_piece_entry
            for s, epoch in snapshot:
                if not (s._joined and s._epoch == epoch):
                    orphaned += len(emits.get(s.row, ())) or 1
                    continue
                if toks is None or s.row not in emits:
                    err = faults.RowQuarantined(
                        "batch row retired: verify "
                        + (
                            f"fetch failed after {self.retries + 1} attempts"
                            if toks is None
                            else "step failed or produced corrupt tokens"
                        )
                    )
                    err.__cause__ = error if toks is None else bad.get(s.row)
                    s._fetch_error = err
                    self._release_pins_locked(s)
                    tel.rows_quarantined.inc()
                    flight.record(
                        self.replica_id, "rows_quarantined", rows=[s.row],
                        where="spec_verify", error=type(err).__name__,
                    )
                    quarantined += 1
                    continue
                col = emits[s.row]
                n_emit = len(col)
                s.pos += n_emit  # the variable advance (deferred from dispatch)
                s._history.extend(col)  # its last entry: the next window's feed[0]
                s._queue.extend(col)
                s._delivered += n_emit
                s.stats.append(entries[s.row])
                delivered_rows += 1
                delivered_tokens += n_emit
                if s.trace is not None:
                    # per-row child of the shared verify step (ISSUE 16);
                    # drafter_total = the request's lifetime drafted tokens
                    s.trace.add_span(
                        "spec_verify_row", sw._t0, sw.elapsed_s(),
                        row=s.row, drafted=int(lens[s.row]), emitted=n_emit,
                        drafter_total=(
                            s._drafter.drafted_total
                            if s._drafter is not None else 0
                        ),
                    )
                if tel.enabled:
                    tel.spec_accepted_tokens.inc(n_emit - 1)
                    if int(lens[s.row]) > 0:
                        tel.spec_acceptance.observe((n_emit - 1) / int(lens[s.row]))
                    tel.spec_step_advance.observe(n_emit)
            self._note_work_locked()  # the advance was applied here, not at dispatch
            self._cond.notify_all()
        if tel.enabled:
            tel.row_steps_orphaned.inc(orphaned)
            tel.row_steps_quarantined.inc(quarantined)
        if tel.enabled and delivered_tokens:
            tel.tokens_generated.inc(delivered_tokens)
            tel.device_sampled_tokens.inc(delivered_tokens)
            tel.decode_latency.observe(
                step_ms * delivered_rows / delivered_tokens / 1000.0
            )
