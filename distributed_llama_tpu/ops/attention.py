"""Online-softmax (flash-style) attention building blocks.

Shared by the dense/TP blocked attention (:func:`blocked_attention`, used by
``models.llama.attention``) and sequence parallelism's ring/sharded
attention (``parallel.context_parallel``). The reference computes attention
as a per-head scalar loop over every past position
(reference: src/llama2-tasks.cpp:54-94); here a chunk of key/value rows is
scored at once and partials merge with the standard flash-attention
(max, exp-sum, weighted-sum) algebra — no full [T, S] score tensor ever
materializes, and a dynamic chunk bound skips cache slots beyond the live
context entirely.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from distributed_llama_tpu.ops import decode_attention
from distributed_llama_tpu.ops import kv_cache as kvc

# Trace-time collector of what a batched decode step's attention reads: while
# one is open (:func:`collect_kv_reads`), every layer's scan appends (kind,
# int32 [B]): the positions of each row's cache it read (``full``: the row's
# OWN chunks where the row-bounded kernel serves, the chunks up to the
# bucket's longest row, every row alike, where the XLA scan does; ``latent``
# where the layer keeps latents (and, where it has an indexer, ``index``: the
# index keys scored, ``latent_selected``: the rows its softmax ran over,
# ``dsa_visible``: the positions the row could see); ``window``: the window;
# ``eva_window`` / ``eva_summary``: an EVA scan's chunks of each store,
# by row or by bucket likewise).
# The forward that opened it sums by kind and returns the sums with its
# tokens, as the expert share's counts are (``models.moe.collect_held``).
_kv_reads: list | None = None


@contextlib.contextmanager
def collect_kv_reads(enabled: bool = True):
    global _kv_reads
    before, _kv_reads = _kv_reads, [] if enabled else None
    try:
        yield _kv_reads
    finally:
        _kv_reads = before


def note_kv_read(kind: str, rows: int, positions) -> None:
    """``positions``: one count for every row alike, or int32 [rows]."""
    if _kv_reads is not None:
        _kv_reads.append((kind, jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (rows,))))


def _note_path(kernel: str, path: str) -> None:
    """Which scan an attention call took, counted where the program is traced
    (``dllama_kernel_path_total``; ``decode_attention``: a batched decode
    step's scan, ``paged_attention``: a scan that reads pages)."""
    from distributed_llama_tpu import telemetry

    telemetry.note_kernel_path(kernel, path)


def chunk_attention(
    q: jax.Array,  # [Tq, K, M, hd] f32 (grouped: K kv-heads × M q-per-kv)
    k: jax.Array,  # [Tk, K, hd] — cache dtype (NOT pre-cast to f32)
    v: jax.Array,  # [Tk, K, hd]
    q_positions: jax.Array,  # [Tq] global positions
    k_positions: jax.Array,  # [Tk]
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Masked scores of one (q-chunk, kv-chunk) pair → (m, l, o) partials.

    m: running max [Tq, K, M]; l: exp-sum [Tq, K, M]; o: weighted V sum
    [Tq, K, M, hd]. Entirely local — no collectives. The einsums run with
    k/v in their storage dtype and f32 accumulation: pre-casting a bf16
    cache slice to f32 would materialize 2x the cache bytes per layer per
    token (the same fix as llama.attention's score/value einsums).
    """
    mask = (k_positions[None, :] <= q_positions[:, None])[:, None, None, :]
    return masked_chunk_attention(q, k, v, mask)


def masked_chunk_attention(q, k, v, mask):
    """:func:`chunk_attention` under any ``mask`` (broadcastable to [Tq, K, M,
    Tk]; True = the query sees the key): the (m, l, o) partials of one chunk.
    A query that sees nothing of the chunk gets the EMPTY partial."""
    hd = q.shape[-1]
    # compute dtype follows the cache half (bf16 for an i8 half); f32 caches
    # (parity tests) keep true-f32 multiplies, mirroring llama.attention —
    # otherwise TPU's default bf16 demotion makes f32 runs diverge from the
    # dense f32 path
    cdt = kvc.compute_dtype(k)
    prec = kvc.einsum_precision(k)
    scores = kvc.scores_einsum(q.astype(cdt), k, prec) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)  # [Tq, K, M]
    # fully-masked rows (no kv visible in this chunk) keep m = -inf: the
    # EMPTY partial. merge_partials treats it as an exact identity, which
    # is what makes a multi-token verify step bit-identical to the plain
    # decode it replaces (the extra chunks its larger dynamic bound scans
    # are fully masked for the early queries — a finite sentinel here would
    # rescale their l/o by exp(m) and perturb the final quotient in ulps).
    # The exp below still needs a finite reference, hence the local safe_m.
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - safe_m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = kvc.mix_einsum(p, v, cdt, prec)
    return m, l, o


def merge_partials(m1, l1, o1, m2, l2, o2):
    """Merge two online-softmax partials (standard flash-attention merge).

    An EMPTY partial (m = -inf, l = 0, o = 0 — a fully-masked chunk) merges
    as an exact identity: its scale factor is forced to 0 and the other
    side's to exp(0) = 1, so the survivor's l/o pass through bit-unchanged
    instead of being rescaled by a finite sentinel max."""
    m = jnp.maximum(m1, m2)
    safe = jnp.where(jnp.isfinite(m), m, 0.0)
    a1 = jnp.where(jnp.isfinite(m1), jnp.exp(m1 - safe), 0.0)
    a2 = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - safe), 0.0)
    l = l1 * a1 + l2 * a2
    o = o1 * a1[..., None] + o2 * a2[..., None]
    return m, l, o


def paged_segments(matched, chunk: int, n_chunks):
    """Segment bounds of a paged blocked scan: chunks [0, a) hold positions
    below EVERY row's ``matched`` (pool-only reads), chunks [a, b) mix pool
    and slab per position, chunks [b, n_chunks) are past every row's matched
    length (slab-only — zero pool traffic once decode is deep). ``matched``
    may be a scalar (single row) or a [B] vector."""
    a = jnp.minimum(jax.lax.div(jnp.min(matched), chunk), n_chunks)
    b = jnp.clip(jax.lax.div(jnp.max(matched) + chunk - 1, chunk), a, n_chunks)
    return a, b


def _segmented_batched_scan(partial, cache, paged, chunk: int, n_chunks, init, rows: int):
    """The batched paged chunk scan shared by decode and verify attention:
    run ``partial(kc, vc, start, carry)`` over every chunk, reading each
    chunk from the slab (``paged`` None), or through the pool-only / mixed /
    slab-only segment split (:func:`paged_segments`) with per-position
    byte selects in the mixed span. One definition so a fix to the segment
    logic can never reach one caller and skip the other.

    ``cache`` is the layer's slab as it is stored (a fused leaf or a
    ``(keys, values)`` tuple) and between the step's cache write and these
    reads nothing of the slab's size may form. Two rules keep it so, both
    read off the v5e compiler's output (tests/test_chip_compile.py holds
    them; PERF.md §5 has the history):

    - each chunk is sliced out of the cache INSIDE the loop
      (:func:`kv_cache.slab_chunk`). The loops' bounds are dynamic, so they
      compile to ``while`` loops, and a ``while`` operand is a buffer of its
      own: handing the loops ``leaf[0]``/``leaf[1]`` made XLA copy both
      halves of the whole slab in every layer of every decode step;
    - ONE loop per call reads the slab. A leaf that feeds two ``while``
      loops is re-laid out for them (heads outside positions, one copy of
      the whole leaf a layer a step), so the mixed and the slab-only
      segment share a loop and a ``cond`` on the chunk index picks the
      segment's arithmetic; the pool-only loop never touches the slab.

    Parity scope: every segment keeps its own instance of ``partial`` (the
    pool-only loop and the two ``cond`` branches) — decode must not pay a
    pool gather on slab-only chunks — which means a backend whose
    per-instance codegen differs could perturb the merge by ulps (the
    mechanism that forced :func:`blocked_attention`'s paged prefill to a
    single mixed body). Chunk indices, chunk bytes and merge order are
    those of the unpaged scan. Bit-parity vs the copy path is
    test-enforced on the CPU mesh; the hit-vs-cold parity tests are the
    tripwire on any new backend. A decode step over a fused bf16 or f32 slab
    without pages does NOT come here (``decode_attention.slab_decode_scan``,
    per-row bounds), so a verify step and the decode it replaces agree to a
    tolerance there, not to the bit (:func:`batched_verify_attention`)."""

    def slab_only(kc, vc, i, carry):
        return partial(kc, vc, i * chunk, carry)

    def body_slab(i, carry):
        return slab_only(*kvc.slab_chunk(cache, i * chunk, chunk, rows), i, carry)

    if paged is None:
        return jax.lax.fori_loop(0, n_chunks, body_slab, init)

    pool_k, pool_v, tables, matched = paged
    ppc = chunk // kvc.pool_page_size(pool_k)
    a, b = paged_segments(matched, chunk, n_chunks)

    def body_pool(i, carry):
        kc = kvc.pool_chunk(pool_k, tables, i, ppc)
        vc = kvc.pool_chunk(pool_v, tables, i, ppc)
        return partial(kc, vc, i * chunk, carry)

    def mixed(kc_s, vc_s, i, carry):
        kc_p = kvc.pool_chunk(pool_k, tables, i, ppc)
        vc_p = kvc.pool_chunk(pool_v, tables, i, ppc)
        sel = (i * chunk + jnp.arange(chunk))[None, :] < matched[:, None]
        return partial(
            kvc.select_kv(sel, kc_p, kc_s), kvc.select_kv(sel, vc_p, vc_s),
            i * chunk, carry,
        )

    def body_mixed_then_slab(i, carry):
        kc_s, vc_s = kvc.slab_chunk(cache, i * chunk, chunk, rows)
        return jax.lax.cond(i < b, mixed, slab_only, kc_s, vc_s, i, carry)

    carry = jax.lax.fori_loop(0, a, body_pool, init)
    return jax.lax.fori_loop(a, n_chunks, body_mixed_then_slab, carry)


def blocked_partials(
    qg: jax.Array,  # [T, K, M, hd] f32 grouped queries
    keys,  # local cache slice [Sl, K, hd] (array or QuantizedKV)
    values,
    q_pos: jax.Array,  # [T] absolute positions (ascending)
    base: jax.Array,  # absolute position of local slot 0
    chunk: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Online-softmax partials of T queries over a LOCAL cache slice with a
    DYNAMIC chunk bound: slots past the last live position (q_pos[-1]) are
    never read. The (m, l, o) triple feeds a cross-shard merge (sequence
    parallelism's pmax/psum) or a local normalization. A shard whose slice
    holds no live slots returns (-inf, 0, 0) — a zero contribution after
    any merge. Requires Sl % chunk == 0."""
    T, K, M, hd = qg.shape
    Sl = keys.shape[0]
    live = jnp.clip(q_pos[-1] + 1 - base, 0, Sl)
    n_chunks = jax.lax.div(live + chunk - 1, chunk)

    def body(i, carry):
        m, l, o = carry
        start = i * chunk
        kc = kvc.slice_rows(keys, start, chunk)
        vc = kvc.slice_rows(values, start, chunk)
        k_pos = base + start + jnp.arange(chunk)
        ms, ls, os_ = chunk_attention(qg, kc, vc, q_pos, k_pos)
        return merge_partials(m, l, o, ms, ls, os_)

    m0 = jnp.full((T, K, M), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((T, K, M), jnp.float32)
    o0 = jnp.zeros((T, K, M, hd), jnp.float32)
    return jax.lax.fori_loop(0, n_chunks, body, (m0, l0, o0))


def _decode_partial(qg, pos, chunk: int, cdt, prec, sees=None):
    """The per-chunk online-softmax arithmetic of the batched decode scan,
    handed to :func:`_segmented_batched_scan` once per segment. ``sees(slots
    [chunk], pos [B]) -> [B, chunk]``: which of a chunk's slots each row's
    query sees; None = causal (slot s is position s)."""
    hd = qg.shape[-1]
    if sees is None:
        def sees(k_pos, pos):
            return k_pos[None, :] <= pos[:, None]

    def partial(kc, vc, start, carry):
        m, l, o = carry
        k_pos = start + jnp.arange(chunk)
        scores = kvc.scores_einsum_batched(qg.astype(cdt), kc, prec) / jnp.sqrt(
            jnp.float32(hd)
        )  # [B, K, M, chunk]
        mask = sees(k_pos, pos)[:, None, None, :]
        scores = jnp.where(mask, scores, -jnp.inf)
        ms = jnp.max(scores, axis=-1)
        # keep m = -inf for fully-masked chunks (the exact-identity empty
        # partial — see merge_partials); exp still needs a finite reference
        safe_m = jnp.where(jnp.isfinite(ms), ms, 0.0)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(mask, p, 0.0)
        ls = jnp.sum(p, axis=-1)
        os_ = kvc.mix_einsum_batched(p, vc, cdt, prec)
        return merge_partials(m, l, o, ms, ls, os_)

    return partial


def _verify_partial(qg, pos, chunk: int, cdt, prec):
    """The per-chunk online-softmax arithmetic of the batched verify scan
    (speculative decode: T-query windows at pos[b]..pos[b]+T-1), the
    T-query form of :func:`_decode_partial`."""
    B, T, K, M, hd = qg.shape
    q_pos = pos[:, None] + jnp.arange(T)[None, :]  # [B, T]

    def partial(kc, vc, start, carry):
        m, l, o = carry
        k_pos = start + jnp.arange(chunk)
        scores = kvc.scores_einsum_verify(qg.astype(cdt), kc, prec) / jnp.sqrt(
            jnp.float32(hd)
        )  # [B, T, K, M, chunk]
        mask = (k_pos[None, None, :] <= q_pos[:, :, None])[:, :, None, None, :]
        scores = jnp.where(mask, scores, -jnp.inf)
        ms = jnp.max(scores, axis=-1)
        safe_m = jnp.where(jnp.isfinite(ms), ms, 0.0)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(mask, p, 0.0)
        ls = jnp.sum(p, axis=-1)
        os_ = kvc.mix_einsum_verify(p, vc, cdt, prec)
        return merge_partials(m, l, o, ms, ls, os_)

    return partial


# A bucket of ONE row over a slab this short keeps the XLA loop: a single
# row's bound is the bucket's, so the per-row bound saves nothing, and on the
# chip the program around the kernel lost more than the kernel won (XLA
# prefetches `down` into VMEM beside it and waits for it in the open):
# mistral7b.single_stream tpot_p50_ms 3.661 on the loop against 3.889 on the
# kernel, one pair, 16 x 2048 slots (PERF.md section 6, PR 44). Longer slabs
# were not paired at one row; there the kernel alone wins more a chunk.
ONE_ROW_LOOP_SLOTS = 2048


def _causal_tables(pos, S: int, chunk: int):
    """What each row of a causal decode step visits (the tables of
    ``decode_attention.slab_decode_scan``): row ``b`` walks chunks 0 ..
    pos[b] // chunk of its own row and sees of chunk ``i`` the first
    ``clip(pos[b] + 1 - chunk i, 0, chunk)`` slots."""
    first = chunk * jnp.arange(S // chunk, dtype=jnp.int32)
    live = jnp.clip(pos + 1, 0, S)
    starts = jnp.broadcast_to(first, (pos.shape[0], first.shape[0]))
    visible = jnp.clip(live[:, None] - first[None, :], 0, chunk)
    return starts, visible, jax.lax.div(live + chunk - 1, chunk)


def batched_decode_attention(
    qg: jax.Array,  # [B, K, M, hd] f32 grouped queries (one token per row)
    cache,  # the layer's slab: fused leaf [2, B_max, S, K, hd] or (keys, values)
    pos: jax.Array,  # [B] per-row absolute positions (inactive rows: 0)
    chunk: int,
    paged=None,  # (pool_k, pool_v, tables [B, n_table], matched [B])
) -> jax.Array:
    """Blocked causal attention of B independent single-token queries, each
    over its OWN slab cache row, masked by its OWN position: row ``b`` sees
    slots 0..pos[b]. Returns [B, K, M, hd] f32. Requires S % chunk == 0
    (callers fall back to the full-S einsum otherwise, exactly like the
    single-stream path). The slab may hold MORE rows than B (a dispatch
    bucket below B_max): only the first B rows are read. ``cache`` is passed
    as the layer stores it (array or QuantizedKV leaf, or the tp backend's
    ``(keys, values)`` halves) and only chunk-sized pieces of it are ever
    sliced out.

    Two scans serve, chosen from the input. A fused array leaf read without
    pages takes the row-bounded kernel (``decode_attention.slab_decode_scan``):
    each row reads the chunks up to ITS position and nothing past them, so a
    short row beside long ones, an inactive lane (position 0: one chunk) and
    a row whose request ended cost their own K/V. Everything else (``paged``,
    an i8 leaf, the tp backend's tuple, and a bucket of ONE row over a slab
    of at most ``ONE_ROW_LOOP_SLOTS`` slots) takes the XLA scan: one fori_loop
    covers all rows with a shared DYNAMIC chunk bound (max over pos), so
    slots beyond the longest live context are never read; rows shorter than
    the bound are masked per chunk and fully-masked chunks contribute zero
    via the online-softmax merge. Chunk indices, chunk size and merge order
    are the same in both.

    With ``paged`` set (zero-copy prefix aliasing), row ``b``'s positions
    below ``matched[b]`` are read from the shared page pool THROUGH its page
    table instead of the slab: the scan splits into pool-only, mixed and
    slab-only segments (:func:`paged_segments`) visiting the SAME chunk
    indices in the same merge order with byte-identical KV (pages hold the
    exact bytes the copy design gathered), so the output is bit-identical
    to the copy path's. Requires chunk % page == 0 (callers fall back to
    the virtual-row einsum otherwise)."""
    B, K, M, hd = qg.shape
    S, cdt, prec = kvc.slab_facts(cache)
    short_one_row = B == 1 and S <= ONE_ROW_LOOP_SLOTS
    if paged is None and not short_one_row and decode_attention.supports(cache, chunk):
        _note_path("decode_attention", "pallas_rowbound")
        starts, visible, n_steps = _causal_tables(pos, S, chunk)
        note_kv_read("full", B, n_steps * chunk)
        return decode_attention.slab_decode_scan(qg, cache, starts, visible, n_steps, chunk)
    _note_path("decode_attention", "xla_scan")
    if paged is not None:
        _note_path("paged_attention", "xla_segmented")
    live = jnp.clip(jnp.max(pos) + 1, 0, S)
    n_chunks = jax.lax.div(live + chunk - 1, chunk)
    note_kv_read("full", B, n_chunks * chunk)
    partial = _decode_partial(qg, pos, chunk, cdt, prec)
    m0 = jnp.full((B, K, M), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, K, M), jnp.float32)
    o0 = jnp.zeros((B, K, M, hd), jnp.float32)
    m, l, o = _segmented_batched_scan(
        partial, cache, paged, chunk, n_chunks, (m0, l0, o0), rows=B
    )
    return o / jnp.maximum(l, 1e-30)[..., None]


def _window_softmax(scores, mask, values, cdt, prec, mix):
    """Softmax over the gathered window's masked scores, then the value mix
    (``mix``: the single-row or the batched einsum). A query always sees its
    own position, so no row is empty."""
    weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return mix(weights, values, cdt, prec)


def window_attention(
    qg: jax.Array,  # [T, K, M, hd] f32 grouped queries at pos..pos+T-1
    ring,  # fused single-row ring leaf [2, R, K, hd], the piece's K/V written
    pos: jax.Array,  # scalar: absolute position of query row 0
    window: int,
) -> jax.Array:
    """Window attention of T query rows of ONE row over its ring: query ``t``
    sees key ``s`` iff ``t - window < s <= t``. The positions pos - window + 1
    .. pos + T - 1 are gathered from their slots (``s % R``) and masked by
    position; nothing else of the ring is read, so the cost does not grow
    with the context. The ring has to hold them all beside each other:
    ``T + window - 1 <= R``. Returns [T, K, M, hd] f32."""
    T, K, M, hd = qg.shape
    R = ring.shape[1]
    span = T + window - 1
    if span > R:
        raise ValueError(
            f"a piece of {T} tokens and a window of {window} positions do not fit a ring of "
            f"{R} slots: a prompt is written in pieces of at most {R - window + 1} tokens"
        )
    k_pos = pos - window + 1 + jnp.arange(span)
    kc, vc = kvc.ring_take(ring, k_pos % R)
    q_pos = pos + jnp.arange(T)
    mask = (k_pos[None, :] >= 0) & (k_pos[None, :] <= q_pos[:, None]) & (
        k_pos[None, :] > q_pos[:, None] - window
    )
    cdt, prec = kvc.compute_dtype(kc), kvc.einsum_precision(kc)
    scores = kvc.scores_einsum(qg.astype(cdt), kc, prec) / jnp.sqrt(jnp.float32(hd))
    return _window_softmax(scores, mask[:, None, None, :], vc, cdt, prec, kvc.mix_einsum)


def batched_window_attention(
    qg: jax.Array,  # [B, K, M, hd] f32 grouped queries (one token per row)
    cache,  # the layer's ring slab: fused leaf [2, B_max, R, K, hd]
    pos: jax.Array,  # [B] per-row absolute positions (inactive rows: 0)
    window: int,
) -> jax.Array:
    """Window attention of B independent single-token queries, each over its
    own ring row: row ``b`` gathers the slots of positions pos[b] - window + 1
    .. pos[b], whatever the other rows' positions are (the full layers' shared
    chunk bound would visit every chunk between the shortest and the longest
    row). A decode step reads ``window`` positions a row in such a layer,
    however long the context. Returns [B, K, M, hd] f32."""
    B, K, M, hd = qg.shape
    R = cache.shape[2]
    k_pos = pos[:, None] - window + 1 + jnp.arange(window)[None, :]  # [B, W]
    kc, vc = kvc.ring_take(cache, k_pos % R, rows=B)
    note_kv_read("window", B, window)
    cdt, prec = kvc.compute_dtype(kc), kvc.einsum_precision(kc)
    scores = kvc.scores_einsum_batched(qg.astype(cdt), kc, prec) / jnp.sqrt(jnp.float32(hd))
    mask = (k_pos >= 0)[:, None, None, :]
    return _window_softmax(scores, mask, vc, cdt, prec, kvc.mix_einsum_batched)


def latent_attention_scan(
    q: jax.Array,  # [B, Q, D] f32 absorbed queries: Q = heads (decode) or tokens x heads (a piece)
    q_pos: jax.Array,  # [B, Q] the position each query sits at (it sees positions up to there)
    latents: jax.Array,  # a latent leaf's array [B_max, D, S], positions minor, the new rows in it
    chunk: int,
    scale: float,
    selected: jax.Array | None = None,  # bool [B, Q / heads, S]: the positions a token's heads read
) -> jax.Array:
    """Causal softmax attention ABSORBED over a cache of latents: a position is
    one row of D values which is key and value of every head at once (``score =
    q . row``, ``out = sum p row``; the caller keeps the first ``rank`` columns
    of the result and folds the rest of the up-projection into ``q`` before and
    into the output after: ``models.llama.latent_project``). The first B rows
    of ``latents`` are read a chunk [D, chunk] at a time up to the farthest
    query, online softmax, as the full layers' scans do; nothing else of the
    leaf is read and, the chunk being the score product's right-hand side as
    it lies and the mix's transposed, nothing of the leaf's size forms
    (tests/test_chip_compile.py). One loop serves a decode step's rows and a
    piece's tokens. ``selected`` (a layer with an indexer,
    :func:`dsa_selection`): the softmax runs over the marked positions alone,
    every head of a token over the same ones; the chunks are read all the
    same, the MASKED form of the selected attention. Returns ([B, Q, D] f32,
    the positions read a row)."""
    B, Q, D = q.shape
    S = latents.shape[2]
    cdt, prec = latents.dtype, kvc.einsum_precision(latents)
    n_chunks = jax.lax.div(jnp.clip(jnp.max(q_pos) + 1, 0, S) + chunk - 1, chunk)
    qc = q.astype(cdt)

    def body(i, carry):
        c = jax.lax.dynamic_slice(latents, (0, 0, i * chunk), (B, D, chunk))
        scores = scale * jnp.einsum(
            "bqd,bds->bqs", qc, c, precision=prec, preferred_element_type=jnp.float32
        )
        mask = (i * chunk + jnp.arange(chunk))[None, None, :] <= q_pos[:, :, None]
        if selected is not None:
            G = selected.shape[1]
            sel = jax.lax.dynamic_slice(selected, (0, 0, i * chunk), (B, G, chunk))
            mask = (mask.reshape(B, G, Q // G, chunk) & sel[:, :, None, :]).reshape(B, Q, chunk)
        scores = jnp.where(mask, scores, -jnp.inf)
        ms = jnp.max(scores, axis=-1)
        # a fully-masked chunk keeps m = -inf, the empty partial (merge_partials)
        safe_m = jnp.where(jnp.isfinite(ms), ms, 0.0)
        p = jnp.where(mask, jnp.exp(scores - safe_m[..., None]), 0.0)
        os_ = jnp.einsum(
            "bqs,bds->bqd", p.astype(cdt), c, precision=prec, preferred_element_type=jnp.float32
        )
        return merge_partials(*carry, ms, jnp.sum(p, axis=-1), os_)

    m0 = jnp.full((B, Q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Q), jnp.float32)
    o0 = jnp.zeros((B, Q, D), jnp.float32)
    m, l, o = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, o0))
    return o / jnp.maximum(l, 1e-30)[..., None], n_chunks * chunk


# ---------------------------------------------------------------------------
# A learned sparse selection in front of latent attention (DeepSeek Sparse
# Attention; GLM-5's ``glm_moe_dsa``). Every position caches ONE index key of I
# values beside its latent row. A query at t brings J index heads ``q_I[j]`` and
# J weights ``w[j]`` and scores every position it can see: ``I[t, s] = sum_j
# w[t, j] * relu(q_I[t, j] . k_I[s])``. Its attention then runs over the k
# positions of largest score (over every visible one while there are at most
# k), chosen EXACTLY: the k-th largest score is found bit by bit, which costs
# 32 counts over the scores and no sort, and a tie at that score is broken
# towards the earlier position.
# ---------------------------------------------------------------------------


def dsa_index_scores(
    q_idx: jax.Array,  # [B, G, J, I] f32: a query token's index heads (G tokens a row)
    w_idx: jax.Array,  # [B, G, J] f32: the heads' weights
    q_pos: jax.Array,  # [B, G] the position each token sits at
    index_keys: jax.Array,  # a latent leaf's index array [B_max, I, S], positions minor
    chunk: int,
) -> tuple[jax.Array, jax.Array]:
    """The indexer's scores of every token against every position it sees,
    [B, G, S] f32 (``-inf`` at the positions it does not see), the keys read a
    chunk [I, chunk] at a time up to the farthest token; and the positions
    read a row. Keys in the cache's dtype, products accumulated in float32."""
    B, G, J, I = q_idx.shape
    S = index_keys.shape[2]
    cdt, prec = index_keys.dtype, kvc.einsum_precision(index_keys)
    n_chunks = jax.lax.div(jnp.clip(jnp.max(q_pos) + 1, 0, S) + chunk - 1, chunk)
    qc = q_idx.reshape(B, G * J, I).astype(cdt)

    def body(i, scores):
        c = jax.lax.dynamic_slice(index_keys, (0, 0, i * chunk), (B, I, chunk))
        dots = jnp.einsum("bqi,bis->bqs", qc, c, precision=prec, preferred_element_type=jnp.float32)
        part = jnp.sum(w_idx[..., None] * jax.nn.relu(dots.reshape(B, G, J, chunk)), axis=2)
        seen = (i * chunk + jnp.arange(chunk))[None, None, :] <= q_pos[:, :, None]
        return jax.lax.dynamic_update_slice(scores, jnp.where(seen, part, -jnp.inf), (0, 0, i * chunk))

    scores = jax.lax.fori_loop(0, n_chunks, body, jnp.full((B, G, S), -jnp.inf, jnp.float32))
    return scores, n_chunks * chunk


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order (-inf least)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def dsa_select(scores: jax.Array, k: int) -> jax.Array:
    """bool [.., S]: the ``k`` largest of ``scores`` [.., S] along the last
    axis, exactly ``k`` of them where S >= k (every one otherwise). The k-th
    largest value's bits are found from the top bit down (the largest
    threshold that at least ``k`` scores reach); everything above it is kept
    and, of the scores equal to it, the earliest positions until ``k`` are."""
    key = _ordered_bits(scores)
    kth = jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32)
    for bit in range(31, -1, -1):
        cand = kth | jnp.uint32(1 << bit)
        reach = jnp.sum((key >= cand).astype(jnp.int32), axis=-1, keepdims=True)
        kth = jnp.where(reach >= k, cand, kth)
    above = key > kth
    ties = key == kth
    room = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties.astype(jnp.int32), axis=-1) <= room))


def dsa_selection(
    q_idx: jax.Array, w_idx: jax.Array, q_pos: jax.Array, index_keys: jax.Array, chunk: int, k: int,
) -> tuple[jax.Array, jax.Array]:
    """bool [B, G, S], the positions each token's attention reads: the ``k``
    it sees with the largest indexer score (:func:`dsa_index_scores`,
    :func:`dsa_select`), every position it sees while no token of the step
    sees more than ``k`` (the indexer is then not run: its scores could not
    cut). And the index keys read a row."""
    S = index_keys.shape[2]
    visible = jnp.arange(S)[None, None, :] <= q_pos[:, :, None]

    def indexed():
        with jax.named_scope("dsa_index"):
            scores, read = dsa_index_scores(q_idx, w_idx, q_pos, index_keys, chunk)
        with jax.named_scope("dsa_select"):
            return dsa_select(scores, k) & visible, read

    return jax.lax.cond(jnp.max(q_pos) < k, lambda: (visible, jnp.int32(0)), indexed)


# ---------------------------------------------------------------------------
# EVA attention: the sequence is cut into ALIGNED windows of W positions; a
# query at t (window b = t // W) reads the keys of positions b*W .. t exactly
# and, of every earlier window, one learned summary per chunk of c positions,
# in ONE softmax. A layer's leaf holds a row's window store (position p at
# slot p % W) and behind it its summaries (chunk m at slot W + m). Writers put
# a chunk's summary there when its last position is written, in prefill and in
# decode alike; the READ mask decides what a query sees (window slots up to t
# % W, summaries of chunks below (W / c) * b), so nothing happens at a
# window's end and rows that cross one at different steps need no branch.
# ---------------------------------------------------------------------------


def eva_summarise(keys, values, phi, mu):
    """One summary a chunk and head: ``keys``/``values`` [..., c, K, hd] (a
    chunk's rotated keys and its values), ``phi``/``mu`` [K, hd] the layer's
    learned vectors. ``w = softmax_j(<k_j, phi> / sqrt(hd))`` over the chunk,
    ``k~ = sum_j w_j k_j + mu``, ``v~ = sum_j w_j v_j``; float32 throughout.
    Returns ([..., K, hd], [..., K, hd])."""
    keys, values = keys.astype(jnp.float32), values.astype(jnp.float32)
    hd = keys.shape[-1]
    logits = jnp.sum(keys * phi, axis=-1) / jnp.sqrt(jnp.float32(hd))  # [..., c, K]
    w = jax.nn.softmax(logits, axis=-2)[..., None]
    return jnp.sum(w * keys, axis=-3) + mu, jnp.sum(w * values, axis=-3)


def _eva_steps(win_slots, sum_slots, window: int, chunk: int):
    """The steps of ONE loop over an EVA leaf that reads the first
    ``win_slots`` slots of the window store and then the first ``sum_slots``
    summaries, a chunk a step: ``(n_steps, n_win, n_sum, start)`` with
    ``start(i)`` the first slot of step ``i``'s chunk."""
    n_win = jax.lax.div(win_slots + chunk - 1, chunk)
    n_sum = jax.lax.div(sum_slots + chunk - 1, chunk)

    def start(i):
        return jnp.where(i < n_win, i * chunk, window + (i - n_win) * chunk)

    return n_win + n_sum, n_win, n_sum, start


def eva_prefill_attention(
    qg: jax.Array,  # [T, K, M, hd] f32 grouped queries at pos..pos+T-1
    k: jax.Array,  # [T, K, hd] f32 rotated keys of the piece
    v: jax.Array,  # [T, K, hd]
    leaf,  # the row's leaf [2, W + C, K, hd] as it was BEFORE this piece
    pos: jax.Array,  # scalar: absolute position of row 0 of the piece
    n_real,  # real rows of a padded piece (None: all)
    phi: jax.Array,  # [K, hd]
    mu: jax.Array,  # [K, hd]
    window: int,
    c: int,
    chunk: int,
):
    """EVA attention of one piece of ONE row, and the leaf after it. The
    piece may start anywhere and cross a window's end (a prefix hit resumes at
    a page, not at a window): a query reads (1) of the OLD leaf the window
    slots below ``pos`` if it is in the window ``pos`` is in, and the
    summaries of earlier windows' chunks that ended before ``pos``; (2) the
    piece's own keys up to itself that share its window; (3) the summaries of
    chunks that END inside the piece and belong to a window before its own
    (there are some only when the piece crosses a window's end). Nothing is
    read back that the piece itself overwrites. Pad rows (at and past
    ``n_real``) write nothing: a pad's slot may hold a position of the
    current window that later rows still read. Needs T <= W (no two rows of
    the piece in one slot). Returns ([T, K, M, hd] f32, new leaf)."""
    T, K, M, hd = qg.shape
    W, per = window, window // c
    dt = leaf.dtype
    n_real = T if n_real is None else n_real
    if T > W:
        raise ValueError(f"a piece of {T} tokens does not fit a window store of {W} slots")
    rows = jnp.arange(T)
    q_pos = pos + rows
    b_t = q_pos // W  # [T] the queries' windows
    # what later reads will find in the leaf: the cache's own rounding
    kr, vr = k.astype(dt), v.astype(dt)

    # the chunks that end inside the piece's real rows, pooled from the
    # piece's keys and, where a chunk began before the piece, the old leaf's
    n_c = T // c + 1
    m_i = pos // c + jnp.arange(n_c)
    ends = c * m_i + c - 1 < pos + n_real  # chunk pos // c is the first that can
    kp = c * m_i[:, None] + jnp.arange(c)[None, :]  # [n_c, c] positions
    inside = (kp >= pos)[..., None, None]
    at = jnp.clip(kp - pos, 0, T - 1)
    old_k, old_v = kvc.ring_take(leaf, kp % W)
    sk, sv = eva_summarise(
        jnp.where(inside, kr[at], old_k), jnp.where(inside, vr[at], old_v), phi, mu
    )
    sk, sv = sk.astype(dt), sv.astype(dt)

    # (2) + (3): the piece's own keys and the summaries it completes
    same_window = (rows[None, :] <= rows[:, None]) & (b_t[None, :] == b_t[:, None])
    earlier_window = ends[None, :] & (m_i[None, :] < per * b_t[:, None])
    fresh = jnp.concatenate([same_window, earlier_window], axis=1)[:, None, None, :]
    part = masked_chunk_attention(
        qg, jnp.concatenate([kr, sk]), jnp.concatenate([vr, sv]), fresh
    )

    # (1): the old leaf, a chunk of slots at a time and only as far as it is live
    b0 = pos // W
    n_steps, _, _, start = _eva_steps(
        pos % W, jnp.minimum(per * ((pos + T - 1) // W), pos // c), W, chunk
    )

    def body(i, carry):
        kc, vc = jax.lax.dynamic_slice(leaf, (0, start(i), 0, 0), (2, chunk, K, hd))
        g = start(i) + jnp.arange(chunk)
        in_window = (b_t[:, None] == b0) & (g[None, :] < pos % W)
        m = g - W
        summary = (m[None, :] < per * b_t[:, None]) & ((c * m + c - 1)[None, :] < pos)
        mask = jnp.where((g < W)[None, :], in_window, summary)[:, None, None, :]
        return merge_partials(*carry, *masked_chunk_attention(qg, kc, vc, mask))

    m_, l_, o_ = jax.lax.fori_loop(0, n_steps, body, part)
    att = o_ / jnp.maximum(l_, 1e-30)[..., None]

    # one scatter: the real rows' keys and values at their positions' slots,
    # the completed chunks' summaries at theirs; everything else is dropped
    drop = leaf.shape[1]
    slots = jnp.concatenate([
        jnp.where(rows < n_real, q_pos % W, drop), jnp.where(ends, W + m_i, drop)
    ])
    both = jnp.stack([jnp.concatenate([kr, sk]), jnp.concatenate([vr, sv])])
    return att, leaf.at[:, slots].set(both, mode="drop")


def eva_decode_write(leaf, k, v, pos, active, phi, mu, window: int, c: int):
    """One decode step's writes into an EVA layer's slab leaf [2, B_max, W +
    C, K, hd]: row ``b``'s key and value at slot ``pos[b] % W`` and, where
    that position ends a chunk, the chunk's summary (pooled from the window
    store's own ``c`` slots, the new key among them) at slot ``W + pos[b] //
    c``. Inactive rows, and rows past the leaf's summaries, write nothing."""
    W = window
    B, drop = k.shape[0], leaf.shape[2]
    live = active & (pos // c < drop - W)
    leaf = kvc.fused_update_row_batched(leaf, k, v, jnp.where(live, pos % W, drop))
    back = (pos[:, None] - (c - 1) + jnp.arange(c)[None, :]) % W  # [B, c]
    kc, vc = kvc.ring_take(leaf, back, rows=B)
    sk, sv = eva_summarise(kc, vc, phi, mu)
    ends = live & (pos % c == c - 1)
    return kvc.fused_update_row_batched(leaf, sk, sv, jnp.where(ends, W + pos // c, drop))


def _eva_sees(window: int, c: int):
    """The read mask of an EVA leaf's slots for queries at ``pos`` [B]."""
    W, per = window, window // c

    def sees(g, pos):
        in_window = g[None, :] <= (pos % W)[:, None]
        summary = (g - W)[None, :] < (per * (pos // W))[:, None]
        return jnp.where((g < W)[None, :], in_window, summary)

    return sees


def _eva_tables(pos, slots: int, window: int, c: int, chunk: int):
    """What each row of an EVA decode step visits (the tables of
    ``decode_attention.slab_decode_scan``): the window store's chunks up to
    the row's slot ``pos % W``, then the summaries' up to the chunks of its
    earlier windows (:func:`_eva_sees` by row; the leaf's own summaries at
    most), each chunk seen as a prefix. Returns ``(starts, visible, n_win,
    n_sum)``; a row walks ``n_win + n_sum`` steps."""
    W = window
    first = chunk * jnp.arange(slots // chunk, dtype=jnp.int32)[None, :]
    win = (pos % W + 1)[:, None]
    summ = jnp.minimum((W // c) * (pos // W), slots - W)[:, None]
    n_win = jax.lax.div(win + chunk - 1, chunk)
    in_window = first < n_win * chunk
    behind = first - n_win * chunk  # the summaries' chunks follow the row's window chunks
    starts = jnp.where(in_window, first, jnp.minimum(W + behind, slots - chunk))
    visible = jnp.clip(jnp.where(in_window, win - first, summ - behind), 0, chunk)
    return starts, visible, n_win[:, 0], jax.lax.div(summ[:, 0] + chunk - 1, chunk)


def eva_batched_decode_attention(
    qg: jax.Array,  # [B, K, M, hd] f32 grouped queries (one token per row)
    cache,  # the layer's slab leaf [2, B_max, W + C, K, hd], this step's writes in it
    pos: jax.Array,  # [B] per-row absolute positions (inactive rows: 0)
    window: int,
    c: int,
    chunk: int,
) -> jax.Array:
    """EVA attention of B independent single-token queries: row ``b`` sees
    window slots 0 .. pos[b] % W (its aligned window up to itself) and the
    summaries of the chunks of its earlier windows. A fused array leaf of
    whole chunks takes the row-bounded kernel: each row reads the window
    store's chunks up to ITS slot and the summaries' up to ITS depth.
    Otherwise ONE XLA loop reads the leaf (a leaf that feeds two is re-laid
    out: :func:`_segmented_batched_scan`), first the window store's chunks up
    to the longest row's slot, then the summaries' up to the deepest row's; a
    row sees of them what its own position allows. Returns [B, K, M, hd] f32."""
    B, K, M, hd = qg.shape
    W = window
    slots, cdt, prec = kvc.slab_facts(cache)
    if decode_attention.supports(cache, chunk) and W % chunk == 0:
        _note_path("decode_attention", "pallas_rowbound")
        starts, visible, n_win, n_sum = _eva_tables(pos, slots, W, c, chunk)
        note_kv_read("eva_window", B, n_win * chunk)
        note_kv_read("eva_summary", B, n_sum * chunk)
        return decode_attention.slab_decode_scan(
            qg, cache, starts, visible, n_win + n_sum, chunk
        )
    _note_path("decode_attention", "xla_scan")
    n_steps, n_win, n_sum, start = _eva_steps(
        jnp.max(pos % W) + 1, jnp.max((W // c) * (pos // W)), W, chunk
    )
    note_kv_read("eva_window", B, n_win * chunk)
    note_kv_read("eva_summary", B, n_sum * chunk)
    partial = _decode_partial(qg, pos, chunk, cdt, prec, sees=_eva_sees(W, c))

    def body(i, carry):
        return partial(*kvc.slab_chunk(cache, start(i), chunk, B), start(i), carry)

    m0 = jnp.full((B, K, M), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, K, M), jnp.float32)
    o0 = jnp.zeros((B, K, M, hd), jnp.float32)
    m, l, o = jax.lax.fori_loop(0, n_steps, body, (m0, l0, o0))
    return o / jnp.maximum(l, 1e-30)[..., None]


def batched_verify_attention(
    qg: jax.Array,  # [B, T, K, M, hd] f32 grouped queries (T = draft k + 1)
    cache,  # the layer's slab: fused leaf [2, B_max, S, K, hd] or (keys, values)
    pos: jax.Array,  # [B] per-row positions of query t=0 (inactive rows: 0)
    chunk: int,
    paged=None,  # (pool_k, pool_v, tables [B, n_table], matched [B])
) -> jax.Array:
    """Blocked causal attention of B independent T-token verify windows
    (speculative decode): row ``b``'s query ``t`` sits at absolute position
    ``pos[b] + t`` and sees slots 0..pos[b]+t of its OWN slab row. One
    fori_loop covers all rows with a shared dynamic chunk bound
    (max(pos) + T), so slots beyond the longest live window are never
    read; fully-masked chunks merge as exact identities (empty partials),
    which keeps each query's output the single-token decode step's at the
    same position: to the bit where that step takes this XLA scan too, and
    to the tolerance tests/test_kernel_parity.py holds the pair to (8 f32
    eps of the largest output) where it takes the row-bounded kernel (a
    fused bf16 or f32 slab read without pages, :func:`batched_decode_attention`):
    verify stays on the XLA scan, another dot in another order of summation.
    Returns [B, T, K, M, hd] f32.
    Requires S % chunk == 0 (callers fall back to the full-S einsum).

    ``paged``: the zero-copy prefix read, segmented exactly like
    :func:`batched_decode_attention` — the verify window always sits at
    pos >= matched, so every paged position is causally visible to every
    query offset and the per-chunk math is unchanged."""
    B, T, K, M, hd = qg.shape
    S, cdt, prec = kvc.slab_facts(cache)
    if paged is not None:
        _note_path("paged_attention", "xla_segmented")
    live = jnp.clip(jnp.max(pos) + T, 0, S)
    n_chunks = jax.lax.div(live + chunk - 1, chunk)
    partial = _verify_partial(qg, pos, chunk, cdt, prec)
    m0 = jnp.full((B, T, K, M), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, T, K, M), jnp.float32)
    o0 = jnp.zeros((B, T, K, M, hd), jnp.float32)
    m, l, o = _segmented_batched_scan(
        partial, cache, paged, chunk, n_chunks, (m0, l0, o0), rows=B
    )
    return o / jnp.maximum(l, 1e-30)[..., None]


def blocked_attention(
    qg: jax.Array,  # [T, K, M, hd] f32 grouped queries
    keys,  # cache half [S, K, hd] (array or QuantizedKV)
    values,
    pos: jax.Array,  # scalar: absolute position of query row 0
    chunk: int,
    paged=None,  # (pool_k, pool_v, table [n_table], matched scalar)
) -> jax.Array:
    """Causal attention of T query rows over a KV cache, blocked along the
    key axis with a DYNAMIC chunk bound: only chunks holding positions
    <= pos+T-1 are read at all, so attention cost is O(live context), not
    O(seq_len) — the full-S masked einsum it replaces reads (and scores)
    every allocated slot every call. Returns [T, K, M, hd] f32.

    Requires S % chunk == 0 (callers fall back to the full einsum
    otherwise). The boundary chunk's causal edge is masked inside
    :func:`chunk_attention` by position comparison.

    ``paged``: zero-copy prefix aliasing for the slab-row prefill — cache
    positions below ``matched`` are read from the page pool through the
    row's page table. ONE fori_loop covers every chunk with a per-position
    pool-vs-slab byte select: splitting the scan into pool/mixed/slab
    segment loops (as the batched decode does) compiles the shared body
    once PER SEGMENT LOOP, and XLA's per-loop codegen perturbs the o-merge
    FMA by ulps — a single loop is the only structure whose chunk-1..n
    math is bit-identical to the non-paged single-loop scan. The extra
    pool read on suffix-only chunks is a prefill-only cost (decode's hot
    path keeps the segmented scan). Requires chunk % page == 0."""
    T = qg.shape[0]
    q_pos = pos + jnp.arange(T)
    if paged is None:
        # same chunk scan as the sequence-parallel local-slice partials, with
        # the whole cache as the "local slice" (base 0) and a local normalize
        m, l, o = blocked_partials(qg, keys, values, q_pos, 0, chunk)
        return o / jnp.maximum(l, 1e-30)[..., None]

    pool_k, pool_v, table, matched = paged
    K, M, hd = qg.shape[1:]
    Sl = keys.shape[0]
    ppc = chunk // kvc.pool_page_size(pool_k)
    live = jnp.clip(q_pos[-1] + 1, 0, Sl)
    n_chunks = jax.lax.div(live + chunk - 1, chunk)

    def body_mixed(i, carry):
        kc_s = kvc.slice_rows(keys, i * chunk, chunk)
        vc_s = kvc.slice_rows(values, i * chunk, chunk)
        kc_p = kvc.pool_chunk_row(pool_k, table, i, ppc)
        vc_p = kvc.pool_chunk_row(pool_v, table, i, ppc)
        sel = (i * chunk + jnp.arange(chunk)) < matched
        kc = kvc.select_kv(sel, kc_p, kc_s)
        vc = kvc.select_kv(sel, vc_p, vc_s)
        ms, ls, os_ = chunk_attention(qg, kc, vc, q_pos, i * chunk + jnp.arange(chunk))
        m, l, o = carry
        return merge_partials(m, l, o, ms, ls, os_)

    m0 = jnp.full((T, K, M), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((T, K, M), jnp.float32)
    o0 = jnp.zeros((T, K, M, hd), jnp.float32)
    m, l, o = jax.lax.fori_loop(0, n_chunks, body_mixed, (m0, l0, o0))
    return o / jnp.maximum(l, 1e-30)[..., None]
