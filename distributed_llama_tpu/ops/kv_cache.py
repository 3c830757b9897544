"""Quantized (int8) KV cache: the TPU-native answer to the reference's
disc-backed KV storage.

The reference offloads its KV cache to disc files to run contexts larger
than RAM (reference: src/utils.cpp:50-67, src/transformer.cpp:312-318,
``--kv-cache-storage disc`` at src/app.cpp:105-106). On TPU the cache lives
in HBM and a disc round trip per token is not a design point — the
TPU-native lever for the same capability (longer contexts in the same
memory) is a narrower cache dtype: int8 rows with per-(slot, head) f32
scales halve the cache bytes vs bf16 (scales add hd/4 overhead, ~3% at
hd=128) AND halve the attention HBM read stream, which is the
second-largest bandwidth consumer after the weights.

Layout: each cache half is a :class:`QuantizedKV` pytree of
``data`` int8 [S, K, hd] and ``scales`` f32 [S, K, 1]. The scales keep a
trailing unit axis ON PURPOSE: both leaves are rank-3 and shard identically
on (sequence, kv-head) axes, so every existing cache PartitionSpec —
``P(None, "tp", None)`` under tensor parallelism, ``P("sp", "tp", None)``
under sequence parallelism — applies to a QuantizedKV as a pytree prefix
with no spec surgery anywhere.

Dequantization never materializes: the score einsum runs on int8 data cast
to bf16 in-register (int8 magnitudes are exact in bf16) and the per-slot
scale folds into the score afterwards; the value einsum folds the scale
into the softmax weights BEFORE the mix, so the cache bytes crossing HBM
stay int8 in both reads.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

I8_SENTINELS = ("i8", "int8")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedKV:
    """One cache half (keys or values): int8 rows + per-(slot, head) scales.

    Also the container of a FUSED per-layer cache (keys and values stacked
    on a leading 2-axis, see the fused-layout note below): indexing slices
    both leaves, so ``fused[0]``/``fused[1]`` are the (keys, values) halves
    exactly like a ``(keys, values)`` tuple's elements."""

    data: jax.Array  # int8 [S, K, hd]
    scales: jax.Array  # f32 [S, K, 1]

    @property
    def shape(self):  # mirror the raw-array cache half
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    def __getitem__(self, idx):
        return QuantizedKV(self.data[idx], self.scales[idx])

    def __iter__(self):  # unpack a fused leaf like a (keys, values) tuple
        return iter((self[0], self[1]))

    def tree_flatten(self):
        return (self.data, self.scales), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def is_quantized_cache_dtype(dtype) -> bool:
    return isinstance(dtype, str) and dtype in I8_SENTINELS


def init_half(shape, dtype, zeros=jnp.zeros):
    """One cache half of [S, K, hd]: a plain array, or a QuantizedKV when
    ``dtype`` is the "i8" sentinel. ``zeros`` is injectable so sharded
    builders (make_array_from_callback closures) reuse the same layout."""
    if is_quantized_cache_dtype(dtype):
        return QuantizedKV(
            zeros(shape, jnp.int8), zeros(shape[:-1] + (1,), jnp.float32)
        )
    return zeros(shape, dtype)


def quantize_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[T, K, hd] f32/bf16 -> (int8 [T, K, hd], f32 scales [T, K, 1]),
    symmetric per-(row, head): scale = max|x| / 127."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scales = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scales), -127, 127).astype(jnp.int8)
    return q, scales


def update_rows(half, rows: jax.Array, pos) -> "QuantizedKV | jax.Array":
    """Write ``rows`` [T, K, hd] at slots pos..pos+T-1 (the dense/TP decode
    and prefill write). Quantizes on the fly for an i8 half; aliases in
    place per leaf either way."""
    if isinstance(half, QuantizedKV):
        q, s = quantize_rows(rows)
        return QuantizedKV(
            jax.lax.dynamic_update_slice(half.data, q, (pos, 0, 0)),
            jax.lax.dynamic_update_slice(half.scales, s, (pos, 0, 0)),
        )
    return jax.lax.dynamic_update_slice(half, rows.astype(half.dtype), (pos, 0, 0))


def scatter_rows(half, slot: jax.Array, rows: jax.Array):
    """Masked scatter of ``rows`` [T, K, hd] at per-row slot indices (the
    sequence-parallel chunk write): out-of-bounds slots drop."""
    if isinstance(half, QuantizedKV):
        q, s = quantize_rows(rows)
        return QuantizedKV(
            half.data.at[slot].set(q, mode="drop"),
            half.scales.at[slot].set(s, mode="drop"),
        )
    return half.at[slot].set(rows.astype(half.dtype), mode="drop")


def select_row_update(half, row: jax.Array, lpos, owner):
    """Owner-masked single-row write (the sequence-parallel decode step):
    every shard writes at ``lpos``; non-owners re-write the row they already
    had. ``row``: [1, K, hd]."""
    if isinstance(half, QuantizedKV):
        q, s = quantize_rows(row)
        old_q = jax.lax.dynamic_slice(half.data, (lpos, 0, 0), q.shape)
        old_s = jax.lax.dynamic_slice(half.scales, (lpos, 0, 0), s.shape)
        return QuantizedKV(
            jax.lax.dynamic_update_slice(
                half.data, jnp.where(owner, q, old_q), (lpos, 0, 0)
            ),
            jax.lax.dynamic_update_slice(
                half.scales, jnp.where(owner, s, old_s), (lpos, 0, 0)
            ),
        )
    K, hd = half.shape[1], half.shape[2]
    old = jax.lax.dynamic_slice(half, (lpos, 0, 0), (1, K, hd))
    return jax.lax.dynamic_update_slice(
        half, jnp.where(owner, row.astype(half.dtype), old), (lpos, 0, 0)
    )


def slice_rows(half, start, n: int):
    """Read ``n`` cache slots [start, start+n) (the blocked-attention chunk
    read). ``start`` may be traced; ``n`` is static."""
    if isinstance(half, QuantizedKV):
        S, K, hd = half.data.shape
        return QuantizedKV(
            jax.lax.dynamic_slice(half.data, (start, 0, 0), (n, K, hd)),
            jax.lax.dynamic_slice(half.scales, (start, 0, 0), (n, K, 1)),
        )
    S, K, hd = half.shape
    return jax.lax.dynamic_slice(half, (start, 0, 0), (n, K, hd))


# ---------------------------------------------------------------------------
# Batched slab cache (engine.batch): one [B, S, K, hd] slab per half per
# layer serves B concurrent decode streams — the leading batch axis is the
# ONLY layout difference from the single-stream [S, K, hd] half, so every
# dtype (bf16/f32 arrays, i8 QuantizedKV) batches with the same pytree
# shape rules (scales gain the batch axis too: [B, S, K, 1]).
# ---------------------------------------------------------------------------


def update_row_batched(half, rows: jax.Array, slot: jax.Array):
    """Per-row single-slot write of the batched decode step: row ``b`` of
    ``rows`` [B, K, hd] lands at cache slot ``slot[b]`` of slab row ``b``.
    A slot index >= S DROPS the write — the batch scheduler retires a
    stream by pointing its slot out of bounds, so an inactive row's garbage
    decode never touches the retired cache (its prefix stays reusable)."""
    b_idx = jnp.arange(rows.shape[0])
    if isinstance(half, QuantizedKV):
        q, s = quantize_rows(rows)
        return QuantizedKV(
            half.data.at[b_idx, slot].set(q, mode="drop"),
            half.scales.at[b_idx, slot].set(s, mode="drop"),
        )
    return half.at[b_idx, slot].set(rows.astype(half.dtype), mode="drop")


def scatter_verify_rows(half, b_idx: jax.Array, slots: jax.Array, rows: jax.Array):
    """Per-half multi-token verify scatter (the tuple-slab counterpart of
    :func:`fused_update_verify_batched`): ``rows`` [B, T, K, hd] land at
    ``half[b, slots[b, t]]``; out-of-bounds slots drop."""
    if isinstance(half, QuantizedKV):
        q, s = quantize_rows(rows)
        return QuantizedKV(
            half.data.at[b_idx, slots].set(q, mode="drop"),
            half.scales.at[b_idx, slots].set(s, mode="drop"),
        )
    return half.at[b_idx, slots].set(rows.astype(half.dtype), mode="drop")


def slice_rows_batched(half, start, n: int, rows: int | None = None):
    """Read ``n`` slots [start, start+n) of the first ``rows`` slab rows
    (the batched blocked-attention chunk read). ``start`` may be traced;
    ``n``/``rows`` are static. ``rows`` defaults to every slab row — a
    dispatch bucket smaller than the slab reads only its own rows."""
    if isinstance(half, QuantizedKV):
        B, S, K, hd = half.data.shape
        b = B if rows is None else rows
        return QuantizedKV(
            jax.lax.dynamic_slice(half.data, (0, start, 0, 0), (b, n, K, hd)),
            jax.lax.dynamic_slice(half.scales, (0, start, 0, 0), (b, n, K, 1)),
        )
    B, S, K, hd = half.shape
    b = B if rows is None else rows
    return jax.lax.dynamic_slice(half, (0, start, 0, 0), (b, n, K, hd))


def slab_take_row(half, row):
    """Extract slab row ``row`` as a single-stream [S, K, hd] cache half
    (the slab prefill reuses the whole single-stream attention path on it)."""
    if isinstance(half, QuantizedKV):
        B, S, K, hd = half.data.shape
        return QuantizedKV(
            jax.lax.dynamic_slice(half.data, (row, 0, 0, 0), (1, S, K, hd))[0],
            jax.lax.dynamic_slice(half.scales, (row, 0, 0, 0), (1, S, K, 1))[0],
        )
    B, S, K, hd = half.shape
    return jax.lax.dynamic_slice(half, (row, 0, 0, 0), (1, S, K, hd))[0]


def slab_put_row(half, row_half, row):
    """Write a single-stream cache half back into slab row ``row``. With the
    slab donated, XLA aliases the untouched rows in place."""
    if isinstance(half, QuantizedKV):
        return QuantizedKV(
            jax.lax.dynamic_update_slice(half.data, row_half.data[None], (row, 0, 0, 0)),
            jax.lax.dynamic_update_slice(half.scales, row_half.scales[None], (row, 0, 0, 0)),
        )
    return jax.lax.dynamic_update_slice(half, row_half[None], (row, 0, 0, 0))


# ---------------------------------------------------------------------------
# Page pool (engine.prefix_cache): immutable prefix KV pages shared across
# requests. A pool half is [P, page, K, hd] — the same dtype/pytree rules as
# the slab (i8 pools carry [P, page, K, 1] scales), so published pages hold
# the EXACT cache bytes of the row they came from. A prefix hit takes them
# back one of two ways, both bit-identical to a cold prefill (the prefix-hit
# == cold-prefill parity contract): on one chip the scheduler COPIES the
# matched pages into the row's slab once, at admission
# (:func:`restore_row_blocks`), and every later read is a slab read; the tp
# backend reads them IN PLACE through per-row page tables
# (pool_chunk/select_kv below, consumed by ops.attention's paged variants).
# ---------------------------------------------------------------------------


def init_page_pool_half(n_pages: int, page: int, kl: int, hd: int, dtype):
    """One pool half of ``n_pages`` fixed-size pages: [P, page, K, hd] (or a
    QuantizedKV of int8 data + [P, page, K, 1] scales for the i8 sentinel)."""
    return init_half((n_pages, page, kl, hd), dtype)


def pool_page_size(pool_half) -> int:
    """Static page size of a pool half ([P, page, K, hd] — shapes are known
    at trace time, so paged-vs-plain branching stays Python-level)."""
    return (pool_half.data if isinstance(pool_half, QuantizedKV) else pool_half).shape[1]


def gather_pool_pages(pool_half, ids):
    """Read pool pages ``ids`` [..., n] -> [..., n*page, K, hd]: the
    page-table read. The gathered positions are CONSUMED by the attention
    einsums in-register — nothing is written back to the slab. Out-of-bounds
    ids clamp (jnp gather default); callers mask those positions out by
    ``matched``."""
    if isinstance(pool_half, QuantizedKV):
        d = pool_half.data[ids]  # [..., n, page, K, hd]
        s = pool_half.scales[ids]
        return QuantizedKV(
            d.reshape(d.shape[:-4] + (-1,) + d.shape[-2:]),
            s.reshape(s.shape[:-4] + (-1,) + s.shape[-2:]),
        )
    v = pool_half[ids]
    return v.reshape(v.shape[:-4] + (-1,) + v.shape[-2:])


def pool_chunk(pool_half, tables, i, pages_per_chunk: int):
    """One attention chunk's KV read THROUGH the page tables: pages
    ``tables[:, i*ppc : (i+1)*ppc]`` of every row -> [B, ppc*page, K, hd].
    ``i`` may be traced (the blocked fori_loop index)."""
    B = tables.shape[0]
    ids = jax.lax.dynamic_slice(tables, (0, i * pages_per_chunk), (B, pages_per_chunk))
    return gather_pool_pages(pool_half, ids)


def pool_chunk_row(pool_half, table, i, pages_per_chunk: int):
    """Single-row form of :func:`pool_chunk`: ``table`` [n_table] ->
    [ppc*page, K, hd]."""
    ids = jax.lax.dynamic_slice(table, (i * pages_per_chunk,), (pages_per_chunk,))
    return gather_pool_pages(pool_half, ids)


def select_kv(sel, pool_kv, slab_kv):
    """Per-position source select of a mixed chunk: ``sel`` [..., n] True
    takes the pool byte, False the slab byte. Pages hold the EXACT bytes the
    copy design would have gathered into the slab, so the selected chunk is
    byte-identical to the copied one — the bit-parity contract of the
    zero-copy read."""
    m = sel[..., None, None]
    if isinstance(slab_kv, QuantizedKV):
        return QuantizedKV(
            jnp.where(m, pool_kv.data, slab_kv.data),
            jnp.where(m, pool_kv.scales, slab_kv.scales),
        )
    return jnp.where(m, pool_kv, slab_kv)


def virtual_row(half, pool_half, table, matched):
    """Full virtual [S, K, hd] view of one cache row: pool bytes below
    ``matched``, the slab row beyond. The einsum-fallback read for caches
    too small/odd to block — it materializes the select, so the blocked
    segmented read is the production path."""
    S = half.shape[0]
    pooled = gather_pool_pages(pool_half, table)[:S]
    sel = jnp.arange(S) < matched
    return select_kv(sel, pooled, half)


def virtual_rows_batched(half_b, pool_half, tables, matched):
    """Batched :func:`virtual_row`: [B, S, K, hd] virtual slab with per-row
    page tables and matched lengths."""
    S = half_b.shape[1]
    pooled = gather_pool_pages(pool_half, tables)[:, :S]
    sel = jnp.arange(S)[None, :] < matched[:, None]
    return select_kv(sel, pooled, half_b)


def slice_pool_page(pool_half, pid) -> list:
    """Pool page ``pid`` as a FLAT slice list — the spill-entry layout
    (engine/spill.py): ``[data]`` for plain halves, ``[data, scales]``
    for i8 ``QuantizedKV``. Traceable (``pid`` may be a tracer), so the
    scheduler fuses every layer's slices into ONE download program; the
    flat layout lets the arena checksum and byte-account without knowing
    the dtype."""
    if isinstance(pool_half, QuantizedKV):
        return [pool_half.data[pid], pool_half.scales[pid]]
    return [pool_half[pid]]


def download_pool_page(pool_half, pid: int) -> list[np.ndarray]:
    """Host byte arrays of pool page ``pid`` — the unfused (per-half)
    spill download, verbatim bytes (the reload byte-parity contract).
    Blocking (np.asarray): tests and tools; the scheduler's production
    path fuses :func:`slice_pool_page` across layers instead."""
    return [np.asarray(a) for a in slice_pool_page(pool_half, pid)]


def upload_pool_page(pool_half, pid, arrays: list):
    """Write one downloaded page's arrays back into pool page ``pid`` —
    the spill-tier reload (publish in reverse). Inverse of
    :func:`download_pool_page`'s flat layout; traced under jit (``pid``
    may be a tracer), callers donate the pool."""
    if isinstance(pool_half, QuantizedKV):
        return QuantizedKV(
            pool_half.data.at[pid].set(arrays[0]),
            pool_half.scales.at[pid].set(arrays[1]),
        )
    return pool_half.at[pid].set(arrays[0])


def pool_page_arrays_per_half(pool_half) -> int:
    """How many flat arrays :func:`download_pool_page` yields for this
    half (2 for i8 data+scales, 1 otherwise) — the spill entry's layout
    contract."""
    return 2 if isinstance(pool_half, QuantizedKV) else 1


class BlockSlots(NamedTuple):
    """How blocks of a row sit in its leaf: entry j of block b at slot ``base
    + (b * page + j) % ring`` (``ring`` 0: nothing wraps). A full layer's leaf
    holds a position at its own slot (``page`` positions a block, no ring, no
    base), a window layer's ring at the position modulo the ring's length; an
    EVA layer's leaf holds two stores (:func:`eva_block_slots`)."""

    page: int
    ring: int = 0
    base: int = 0


def eva_block_slots(kind: str, page: int, window: int, chunk: int) -> BlockSlots:
    """Where a block of ``page`` positions sits in an EVA layer's leaf: the
    layout that the copies between leaf and pools follow, stated here for both
    stores. ``"window"``: the block's keys and values, position p at slot ``p
    % window``. ``"summary"``: its ``page // chunk`` summaries, chunk m at slot
    ``window + m``, behind the window store."""
    if kind == "window":
        return BlockSlots(page, ring=window)
    return BlockSlots(page // chunk, base=window)


def block_slots(blocks, page: int, ring: int = 0, base: int = 0):
    """The slots of blocks ``blocks`` (an int array) under :class:`BlockSlots`."""
    slots = (blocks[:, None] * page + jnp.arange(page)[None, :]).reshape(-1)
    if ring:
        slots = slots % ring
    return slots + base if base else slots


def _publish_blocks(pool_half, src, at: tuple, src_page, page_ids, page: int, ring: int, base: int):
    """``src[at + (slots,)]``, the slots of blocks ``src_page``
    (:func:`block_slots`), into pool pages ``page_ids``: ONE gather on ``src``
    as it is stored, so nothing larger than the pages read forms. A
    ``page_ids`` entry at or beyond P DROPS its write."""
    slots = block_slots(src_page, page, ring, base)
    n = src_page.shape[0]

    def put(pool, a):
        vals = a[at + (slots,)]  # [n * page, K, x]
        return pool.at[page_ids].set(vals.reshape((n, page) + vals.shape[1:]), mode="drop")

    if isinstance(pool_half, QuantizedKV):
        return QuantizedKV(put(pool_half.data, src.data), put(pool_half.scales, src.scales))
    return put(pool_half, src)


def publish_row_pages(pool_half, slab_half, row, src_page, page_ids, page: int,
                      ring: int = 0, base: int = 0):
    """Copy slab row ``row``'s blocks ``src_page[i]`` (:func:`block_slots`)
    into pool pages ``page_ids[i]`` (the prefix-cache publish: the row's
    completed prefill KV becomes an immutable shared page). A ``page_ids``
    entry at or beyond P DROPS its write, so padded entries are inert.
    ``slab_half`` is a half ``[B, S, K, hd]`` that stands alone (the tp and
    pod engines' shards); for a half of a fused leaf see
    :func:`publish_leaf_pages`. Returns the updated pool half (callers donate
    the pool)."""
    return _publish_blocks(pool_half, slab_half, (row,), src_page, page_ids, page, ring, base)


def publish_leaf_pages(pool_k, pool_v, slab_leaf, row, src_page, page_ids, page: int,
                       ring: int = 0, base: int = 0):
    """:func:`publish_row_pages` out of both halves of a fused slab leaf ``[2,
    B, S, K, hd]`` (:func:`restore_row_pages` in reverse), bit for bit what it
    writes from ``slab_leaf[0]`` and ``slab_leaf[1]``: half, row and slots are
    addressed in one read of the leaf as it is stored. (A ``slab_leaf[0]``
    handed to :func:`publish_row_pages` is no view: the v5e compiler
    materialises the whole half for it, every layer and every publish.)
    Returns the updated ``(keys, values)`` pool halves."""
    return tuple(
        _publish_blocks(pool, slab_leaf, (half, row), src_page, page_ids, page, ring, base)
        for half, pool in enumerate((pool_k, pool_v))
    )


def restore_row_pages(slab_leaf, pool_k, pool_v, row, dst_page, page_ids, page: int,
                      ring: int = 0, base: int = 0):
    """:func:`publish_row_pages` in reverse: pool pages ``page_ids[i]`` (keys
    and values) into the slots of fused slab leaf ``[2, B, R, K, hd]``'s row
    ``row`` where blocks ``dst_page[i]`` sit (:func:`block_slots`). Returns
    the updated leaf (callers donate the slab)."""
    slots = block_slots(dst_page, page, ring, base)

    def both(k, v):
        kv = jnp.stack([k[page_ids], v[page_ids]])  # [2, n, page, K, x]
        return kv.reshape((2, -1) + kv.shape[3:])

    if isinstance(slab_leaf, QuantizedKV):
        return QuantizedKV(
            slab_leaf.data.at[:, row, slots].set(both(pool_k.data, pool_v.data)),
            slab_leaf.scales.at[:, row, slots].set(both(pool_k.scales, pool_v.scales)),
        )
    return slab_leaf.at[:, row, slots].set(both(pool_k, pool_v))


def restore_row_blocks(slab_leaf, pool_k, pool_v, row, first, page_ids):
    """Pool pages ``page_ids[i]`` (keys and values) into block ``first + i`` of
    fused slab leaf ``[2, B, S, K, hd]``'s row ``row``: consecutive blocks,
    so ONE contiguous ``dynamic_update_slice`` a leaf (two for i8) and no
    scatter (:func:`restore_row_pages` is the form for blocks that wrap a ring
    or sit behind a base). Every entry is real: nothing but those blocks of
    that row changes. Needs ``(first + len(page_ids)) * page <= S`` (a start
    that would pass the row's end is clamped). Returns the updated leaf
    (callers donate the slab)."""

    def put(a, k, v):
        kv = jnp.stack([k[page_ids], v[page_ids]])  # [2, n, page, K, x]
        run = kv.reshape((2, 1, kv.shape[1] * kv.shape[2]) + kv.shape[3:])
        return jax.lax.dynamic_update_slice(a, run, (0, row, first * kv.shape[2], 0, 0))

    if isinstance(slab_leaf, QuantizedKV):
        return QuantizedKV(
            put(slab_leaf.data, pool_k.data, pool_v.data),
            put(slab_leaf.scales, pool_k.scales, pool_v.scales),
        )
    return put(slab_leaf, pool_k, pool_v)


# ---------------------------------------------------------------------------
# Latent rows (a latent-attention layer's cache): a position is ONE row of D
# values, the normed latent and behind it the rotated key slice every head
# shares; there is no head axis and there are no key and value halves. A
# layer's leaf is ``{LATENT: [.., D, S]}``, POSITIONS MINOR: D (576 as
# published) is no multiple of the 128 lanes of a tile, and for a leaf stored
# [.., S, D] the v5e compiler chooses the positions-minor layout itself and
# copies the whole leaf into it and back in every step of every layer
# (tests/test_chip_compile.py holds the program to in-place writes alone).
# Stored so, a scan's chunk [D, n] is the right-hand side of the score
# product as it lies and of the value mix transposed, which the MXU takes. The
# leaf is a dict, as a linear layer's state is, so :func:`fused_take_row` and
# :func:`fused_put_row` move its rows. The layer's pool entry is the 1-tuple
# ``([P, page * D],)`` (:func:`init_latent_pool`): a page's rows as a row
# reads them, copied and transposed at publish and restore;
# :func:`slice_pool_page` and :func:`upload_pool_page` take such a half as they
# take any plain one.
# ---------------------------------------------------------------------------

LATENT = "latent"
# ... and, in a layer with an indexer (a learned sparse selection in front of
# the attention), a second array beside it: the rotated, normed INDEX KEY of
# every position, ``{LATENT: [.., D, S], INDEX: [.., I, S]}`` (I = 128 as
# published), positions minor for the same reasons; the pool entry is then the
# 2-tuple of flat halves ``([P, page * D], [P, page * I])``, in that order
# (:func:`leaf_arrays`; a dict that has crossed a jit boundary comes back with
# its keys sorted, so no loop here goes by the dict's own order).
INDEX = "index"


def leaf_arrays(leaf: dict) -> tuple[str, ...]:
    """The names of a latent leaf's arrays in the order of its pool entry's halves."""
    return (LATENT, INDEX) if INDEX in leaf else (LATENT,)


def init_latent(lead: tuple[int, ...], slots: int, dim: int, dtype, index_dim: int = 0) -> dict:
    """A latent layer's cache leaf: ``slots`` positions of ``dim`` values and,
    for a layer with an indexer, of ``index_dim`` index-key values."""
    leaf = {LATENT: jnp.zeros(lead + (dim, slots), dtype)}
    if index_dim:
        leaf[INDEX] = jnp.zeros(lead + (index_dim, slots), dtype)
    return leaf


def is_latent_leaf(cache_l) -> bool:
    return isinstance(cache_l, dict) and LATENT in cache_l


def latent_update_rows(leaf: dict, rows: dict, pos) -> dict:
    """T positions' rows (``rows[name]`` [T, D] for each array of the leaf)
    at slots pos..pos+T-1 of a single-row latent leaf [D, S]: one
    dynamic_update_slice an array."""
    return {name: jax.lax.dynamic_update_slice(leaf[name], rows[name].astype(leaf[name].dtype).T, (0, pos))
            for name in leaf_arrays(leaf)}


def latent_update_row_batched(leaf: dict, rows: dict, slot: jax.Array) -> dict:
    """The batched decode write: row ``b``'s latent row [D] (and index key)
    at slot ``slot[b]`` of slab row ``b`` of [B_max, D, S]; a slot >= S writes
    nothing. A row's write is the 128 positions around its slot read, the one
    column replaced, and written back with one ``dynamic_update_slice`` (a
    lane tile wide: 147 KB a row at the published width). Not a scatter, and
    not a single column: either wants the D values minor in its operand, the
    scans read positions minor, and the compiler then converts the whole leaf
    between the two in every step of every layer (tests/test_chip_compile.py)."""
    out = {}
    for name in leaf_arrays(leaf):
        a, new = leaf[name], rows[name].astype(leaf[name].dtype)
        D, S = a.shape[1], a.shape[2]
        W = 128 if S % 128 == 0 else S
        for b in range(new.shape[0]):
            start = jnp.minimum(slot[b], S - 1) // W * W
            old = jax.lax.dynamic_slice(a, (b, 0, start), (1, D, W))
            here = (start + jnp.arange(W) == slot[b])[None, None, :]
            a = jax.lax.dynamic_update_slice(a, jnp.where(here, new[b][None, :, None], old), (b, 0, start))
        out[name] = a
    return out


def init_latent_pool(n_pages: int, page: int, dim: int, dtype):
    """A latent layer's pool half: [P, page * D], a page one row (its ``page``
    positions' rows of D values behind one another). Flat, because a scatter
    of pages into [P, page, D] makes the compiler copy the WHOLE pool into
    another layout and back at every publish (D = 576 is no multiple of a
    tile's 128 lanes; 64 x 576 is): 0.75 ms a copy, twice a layer a publish,
    on the chip (PERF.md section 6, PR 43)."""
    return jnp.zeros((n_pages, page * dim), dtype)


def publish_latent_pages(pool_halves: tuple, leaf: dict, row, src_page, page_ids, page: int) -> tuple:
    """:func:`publish_row_pages` for a latent leaf [B, D, S]: row ``row``'s
    blocks ``src_page[i]`` into pool pages ``page_ids[i]`` of each array's
    half [P, page * D] (:func:`init_latent_pool`, :func:`leaf_arrays`); an id
    at or beyond P drops its write."""
    slots = block_slots(src_page, page)
    out = []
    for name, pool_half in zip(leaf_arrays(leaf), pool_halves):
        own = jax.lax.dynamic_index_in_dim(leaf[name], row, 0, keepdims=False)  # [D, S]
        vals = jnp.take(own, slots, axis=1).T  # [n * page, D]
        out.append(pool_half.at[page_ids].set(vals.reshape((src_page.shape[0], -1)), mode="drop"))
    return tuple(out)


def restore_latent_blocks(leaf: dict, pool_halves: tuple, row, first, page_ids) -> dict:
    """:func:`restore_row_blocks` for a latent leaf [B, D, S]: pool pages
    ``page_ids[i]`` of each array's half [P, page * D] into block ``first +
    i`` of row ``row``, one contiguous ``dynamic_update_slice`` an array."""
    out = {}
    for name, pool_half in zip(leaf_arrays(leaf), pool_halves):
        a = leaf[name]
        D = a.shape[1]
        run = pool_half[page_ids].reshape((-1, D)).T[None]  # [1, D, n * page]
        page = pool_half.shape[1] // D
        out[name] = jax.lax.dynamic_update_slice(a, run, (row, 0, first * page))
    return out


# ---------------------------------------------------------------------------
# Rings (a window layer's cache): position p sits at slot p % R of [.., R, K,
# hd], so the leaf does not grow with the row's length. A write is a scatter at
# the positions' slots (a piece may wrap), a read gathers the slots of the
# positions a query can see; the caller masks by position.
# ---------------------------------------------------------------------------


def ring_update_rows(leaf, k_rows: jax.Array, v_rows: jax.Array, pos):
    """T tokens' keys and values at positions pos..pos+T-1 into a fused
    single-row ring leaf [2, R, K, hd], one scatter (two for i8)."""
    R = leaf.shape[1]
    slots = (pos + jnp.arange(k_rows.shape[0])) % R
    if isinstance(leaf, QuantizedKV):
        kq, ks = quantize_rows(k_rows)
        vq, vs = quantize_rows(v_rows)
        return QuantizedKV(
            leaf.data.at[:, slots].set(jnp.stack([kq, vq])),
            leaf.scales.at[:, slots].set(jnp.stack([ks, vs])),
        )
    return leaf.at[:, slots].set(jnp.stack([k_rows, v_rows]).astype(leaf.dtype))


def ring_take(leaf, idx: jax.Array, rows: int | None = None):
    """Slots ``idx`` of a fused ring leaf -> ``(kc, vc)``. Single row: leaf
    [2, R, K, hd], idx [n] -> [n, K, hd] each. Slab: leaf [2, B_max, R, K,
    hd], idx [B, n] -> [B, n, K, hd] each, row b its own slots (``rows`` =
    B: only the first B slab rows are read)."""
    def take(a):
        if rows is None:
            return a[:, idx]
        return a[:, jnp.arange(rows)[:, None], idx]

    if isinstance(leaf, QuantizedKV):
        d, s = take(leaf.data), take(leaf.scales)
        return QuantizedKV(d[0], s[0]), QuantizedKV(d[1], s[1])
    c = take(leaf)
    return c[0], c[1]


# ---------------------------------------------------------------------------
# Fused (coalesced) per-layer cache: keys and values stacked on a LEADING
# 2-axis — [2, S, K, hd] single-stream, [2, B, S, K, hd] slab — so each
# layer's K/V write is ONE dynamic_update_slice / scatter instead of the
# historical (keys, values) pair. The leading axis is fully covered by
# every write (index 0, extent 2), so XLA aliases the donated leaf in
# place exactly like the tuple halves did. Reads: ``fused[0]``/``fused[1]``
# are contiguous, but they are views WITHOUT a copy only while one fusion
# consumes them (the full-S einsum of a small cache). A half that is the
# operand of a loop with a dynamic bound — the chunk loops of the blocked
# attention compile to ``while`` — is a buffer of its own: XLA materialised
# both halves of the 16-row slab (2 x 67 MB) in every layer of every decode
# step, two thirds of a step on the chip (PERF.md §5). So a blocked read
# slices its chunk out of the LEAF inside the loop and splits the chunk
# (:func:`slab_chunk`), and only one loop a layer reads the slab
# (``ops.attention._segmented_batched_scan``). i8 fuses the same way
# (QuantizedKV with [2, ...] data+scales: 2 updates per layer instead of
# 4). The tensor/sequence/expert-parallel backends keep tuple halves
# (their cache PartitionSpecs shard the unfused rank), so every update
# helper here keeps its tuple form too.
# ---------------------------------------------------------------------------


def init_fused(shape, dtype, zeros=jnp.zeros):
    """One fused per-layer cache leaf: keys+values as [2, *shape]."""
    if is_quantized_cache_dtype(dtype):
        return QuantizedKV(
            zeros((2,) + shape, jnp.int8),
            zeros((2,) + shape[:-1] + (1,), jnp.float32),
        )
    return zeros((2,) + shape, dtype)


def is_fused_leaf(cache_l) -> bool:
    """Fused leaves are a single array/QuantizedKV; tuple = split halves."""
    return not isinstance(cache_l, (tuple, list))


def fused_update_rows(leaf, k_rows: jax.Array, v_rows: jax.Array, pos):
    """The coalesced write of :func:`update_rows` pairs: T tokens' keys AND
    values land at slots pos..pos+T-1 of a fused leaf in one
    dynamic_update_slice (two — data+scales — for i8)."""
    if isinstance(leaf, QuantizedKV):
        kq, ks = quantize_rows(k_rows)
        vq, vs = quantize_rows(v_rows)
        return QuantizedKV(
            jax.lax.dynamic_update_slice(leaf.data, jnp.stack([kq, vq]), (0, pos, 0, 0)),
            jax.lax.dynamic_update_slice(leaf.scales, jnp.stack([ks, vs]), (0, pos, 0, 0)),
        )
    stacked = jnp.stack([k_rows, v_rows]).astype(leaf.dtype)
    return jax.lax.dynamic_update_slice(leaf, stacked, (0, pos, 0, 0))


def fused_update_row_batched(leaf, k_rows: jax.Array, v_rows: jax.Array, slot: jax.Array):
    """Coalesced batched decode write: row ``b``'s key AND value land at
    slab slot ``slot[b]`` in one scatter (slot >= S drops, retiring rows
    exactly like :func:`update_row_batched`)."""
    b_idx = jnp.arange(k_rows.shape[0])
    if isinstance(leaf, QuantizedKV):
        kq, ks = quantize_rows(k_rows)
        vq, vs = quantize_rows(v_rows)
        return QuantizedKV(
            leaf.data.at[:, b_idx, slot].set(jnp.stack([kq, vq]), mode="drop"),
            leaf.scales.at[:, b_idx, slot].set(jnp.stack([ks, vs]), mode="drop"),
        )
    stacked = jnp.stack([k_rows, v_rows]).astype(leaf.dtype)
    return leaf.at[:, b_idx, slot].set(stacked, mode="drop")


def fused_update_verify_batched(leaf, k_rows: jax.Array, v_rows: jax.Array, slots: jax.Array):
    """Coalesced multi-token verify write (speculative decode): row ``b``'s
    T keys AND values land at its per-row slots ``slots[b, t]`` in ONE
    scatter per layer. ``k_rows``/``v_rows``: [B, T, K, hd]; out-of-bounds
    slots drop (inactive rows and context-limit clamps write nothing)."""
    b_idx = jnp.arange(k_rows.shape[0])[:, None]
    if isinstance(leaf, QuantizedKV):
        kq, ks = quantize_rows(k_rows)
        vq, vs = quantize_rows(v_rows)
        return QuantizedKV(
            leaf.data.at[:, b_idx, slots].set(jnp.stack([kq, vq]), mode="drop"),
            leaf.scales.at[:, b_idx, slots].set(jnp.stack([ks, vs]), mode="drop"),
        )
    stacked = jnp.stack([k_rows, v_rows]).astype(leaf.dtype)
    return leaf.at[:, b_idx, slots].set(stacked, mode="drop")


def fused_take_row(leaf, row):
    """Extract slab row ``row`` of a fused [2, B, S, K, hd] leaf as a fused
    single-stream [2, S, K, hd] leaf (the slab prefill's row view). A linear
    layer's state leaf (a dict of ``[B, ...]`` arrays) gives its row's."""
    if isinstance(leaf, dict):
        return {k: jax.lax.dynamic_index_in_dim(v, row, 0, keepdims=False) for k, v in leaf.items()}
    if isinstance(leaf, QuantizedKV):
        _, B, S, K, hd = leaf.data.shape
        return QuantizedKV(
            jax.lax.dynamic_slice(leaf.data, (0, row, 0, 0, 0), (2, 1, S, K, hd))[:, 0],
            jax.lax.dynamic_slice(leaf.scales, (0, row, 0, 0, 0), (2, 1, S, K, 1))[:, 0],
        )
    _, B, S, K, hd = leaf.shape
    return jax.lax.dynamic_slice(leaf, (0, row, 0, 0, 0), (2, 1, S, K, hd))[:, 0]


def fused_put_row(slab_leaf, row_leaf, row):
    """Write a fused single-stream row back into fused slab row ``row`` —
    one dynamic_update_slice covers both halves (a state leaf: one per
    array)."""
    if isinstance(slab_leaf, dict):
        return {
            k: jax.lax.dynamic_update_index_in_dim(v, row_leaf[k], row, 0)
            for k, v in slab_leaf.items()
        }
    if isinstance(slab_leaf, QuantizedKV):
        return QuantizedKV(
            jax.lax.dynamic_update_slice(
                slab_leaf.data, row_leaf.data[:, None], (0, row, 0, 0, 0)
            ),
            jax.lax.dynamic_update_slice(
                slab_leaf.scales, row_leaf.scales[:, None], (0, row, 0, 0, 0)
            ),
        )
    return jax.lax.dynamic_update_slice(slab_leaf, row_leaf[:, None], (0, row, 0, 0, 0))


def slab_facts(cache_l):
    """``(S, einsum operand dtype, einsum precision)`` of a layer's slab in
    either stored form (fused leaf or ``(keys, values)`` tuple) — read off
    shapes and dtypes, so nothing of the slab is sliced to learn them."""
    half = cache_l if is_fused_leaf(cache_l) else cache_l[0]
    return half.shape[-3], compute_dtype(half), einsum_precision(half)


def slab_chunk(cache_l, start, n: int, rows: int):
    """One chunk of the batched blocked attention, read out of a layer's
    slab AS IT IS STORED: slots [start, start+n) of the first ``rows`` slab
    rows -> ``(kc, vc)`` of [rows, n, K, hd]. ``cache_l`` is a fused leaf
    [2, B_max, S, K, hd] (array or :class:`QuantizedKV`) or a ``(keys,
    values)`` tuple (the tp backend's sharded slab); which one is seen from
    the input (:func:`is_fused_leaf`). A fused leaf is sliced ONCE over its
    leading 2-axis and the CHUNK is split by static index, so no half of
    the whole slab is ever formed (see the fused-layout note above: a half
    that feeds a loop is a copy). ``start`` may be traced."""
    if not is_fused_leaf(cache_l):
        keys, values = cache_l
        return (
            slice_rows_batched(keys, start, n, rows=rows),
            slice_rows_batched(values, start, n, rows=rows),
        )

    def both(a):
        return jax.lax.dynamic_slice(
            a, (0, 0, start, 0, 0), (2, rows, n) + a.shape[3:]
        )

    if isinstance(cache_l, QuantizedKV):
        d, s = both(cache_l.data), both(cache_l.scales)
        return QuantizedKV(d[0], s[0]), QuantizedKV(d[1], s[1])
    c = both(cache_l)
    return c[0], c[1]


def scores_einsum_verify(qg: jax.Array, keys, prec) -> jax.Array:
    """Batched multi-token verify scores: scores[b,t,k,m,s] =
    q[b,t,k,m,:] . key_row[b,s,k,:] (same i8 scale-folding contract as
    :func:`scores_einsum_batched`, with a T axis riding along)."""
    if isinstance(keys, QuantizedKV):
        raw = jnp.einsum(
            "btkmh,bskh->btkms",
            qg,
            keys.data.astype(qg.dtype),
            preferred_element_type=jnp.float32,
        )
        return raw * jnp.transpose(keys.scales[..., 0], (0, 2, 1))[:, None, :, None, :]
    return jnp.einsum(
        "btkmh,bskh->btkms", qg, keys, precision=prec,
        preferred_element_type=jnp.float32,
    )


def mix_einsum_verify(weights: jax.Array, values, cdt, prec) -> jax.Array:
    """Batched multi-token verify value mix: att[b,t,k,m,h] =
    sum_s w[b,t,k,m,s] * v[b,s,k,h]; the i8 scale folds into the weights
    BEFORE the mix (the value read stays int8)."""
    if isinstance(values, QuantizedKV):
        wv = weights * jnp.transpose(values.scales[..., 0], (0, 2, 1))[:, None, :, None, :]
        return jnp.einsum(
            "btkms,bskh->btkmh",
            wv.astype(cdt),
            values.data.astype(cdt),
            preferred_element_type=jnp.float32,
        )
    return jnp.einsum(
        "btkms,bskh->btkmh", weights.astype(cdt), values, precision=prec,
        preferred_element_type=jnp.float32,
    )


def compute_dtype(half):
    """The einsum operand dtype for a cache half: the storage dtype for
    plain caches (bf16 reads stay bf16, f32 parity stays f32); bf16 for i8
    (int8 magnitudes are exact in bf16, and the MXU wants bf16)."""
    return jnp.bfloat16 if isinstance(half, QuantizedKV) else half.dtype


def einsum_precision(half):
    """f32 caches (parity tests) keep true-f32 multiplies via HIGHEST."""
    dt = half.dtype if not isinstance(half, QuantizedKV) else None
    return jax.lax.Precision.HIGHEST if dt == jnp.float32 else None


def scores_einsum(qg: jax.Array, keys, prec) -> jax.Array:
    """scores[t,k,m,s] = q[t,k,m,:] . key_row[s,k,:] with f32 accumulation;
    for an i8 half the per-(slot, head) scale folds in AFTER the int8 dot
    (the HBM read is int8)."""
    if isinstance(keys, QuantizedKV):
        raw = jnp.einsum(
            "tkmh,skh->tkms",
            qg,
            keys.data.astype(qg.dtype),
            preferred_element_type=jnp.float32,
        )
        return raw * jnp.transpose(keys.scales[..., 0])[None, :, None, :]
    return jnp.einsum(
        "tkmh,skh->tkms", qg, keys, precision=prec,
        preferred_element_type=jnp.float32,
    )


def mix_einsum(weights: jax.Array, values, cdt, prec) -> jax.Array:
    """att[t,k,m,h] = sum_s w[t,k,m,s] * value_row[s,k,h]; for an i8 half
    the scale folds into the weights BEFORE the mix, so the value read
    stays int8."""
    if isinstance(values, QuantizedKV):
        wv = weights * jnp.transpose(values.scales[..., 0])[None, :, None, :]
        return jnp.einsum(
            "tkms,skh->tkmh",
            wv.astype(cdt),
            values.data.astype(cdt),
            preferred_element_type=jnp.float32,
        )
    return jnp.einsum(
        "tkms,skh->tkmh", weights.astype(cdt), values, precision=prec,
        preferred_element_type=jnp.float32,
    )


def scores_einsum_batched(qg: jax.Array, keys, prec) -> jax.Array:
    """Batched-slab scores: row ``b`` of qg [B, K, M, hd] scores ONLY its
    own cache row — scores[b,k,m,s] = q[b,k,m,:] . key_row[b,s,k,:]. Same
    i8 scale-folding contract as :func:`scores_einsum`."""
    if isinstance(keys, QuantizedKV):
        raw = jnp.einsum(
            "bkmh,bskh->bkms",
            qg,
            keys.data.astype(qg.dtype),
            preferred_element_type=jnp.float32,
        )
        return raw * jnp.transpose(keys.scales[..., 0], (0, 2, 1))[:, :, None, :]
    return jnp.einsum(
        "bkmh,bskh->bkms", qg, keys, precision=prec,
        preferred_element_type=jnp.float32,
    )


def mix_einsum_batched(weights: jax.Array, values, cdt, prec) -> jax.Array:
    """Batched-slab value mix: att[b,k,m,h] = sum_s w[b,k,m,s] * v[b,s,k,h];
    the i8 scale folds into the weights BEFORE the mix (the value read stays
    int8), mirroring :func:`mix_einsum`."""
    if isinstance(values, QuantizedKV):
        wv = weights * jnp.transpose(values.scales[..., 0], (0, 2, 1))[:, :, None, :]
        return jnp.einsum(
            "bkms,bskh->bkmh",
            wv.astype(cdt),
            values.data.astype(cdt),
            preferred_element_type=jnp.float32,
        )
    return jnp.einsum(
        "bkms,bskh->bkmh", weights.astype(cdt), values, precision=prec,
        preferred_element_type=jnp.float32,
    )
