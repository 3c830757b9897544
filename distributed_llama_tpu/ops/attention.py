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

from distributed_llama_tpu.ops import kv_cache as kvc

# Trace-time collector of what a batched decode step's attention reads: while
# one is open (:func:`collect_kv_reads`), every layer's scan appends (kind,
# int32 [B]): the positions of each row's cache it read (``full``: the chunks
# up to the bucket's longest row, every row alike; ``window``: the window).
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
    if _kv_reads is not None:
        _kv_reads.append((kind, jnp.full((rows,), positions, jnp.int32)))


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
    hd = q.shape[-1]
    # compute dtype follows the cache half (bf16 for an i8 half); f32 caches
    # (parity tests) keep true-f32 multiplies, mirroring llama.attention —
    # otherwise TPU's default bf16 demotion makes f32 runs diverge from the
    # dense f32 path
    cdt = kvc.compute_dtype(k)
    prec = kvc.einsum_precision(k)
    scores = kvc.scores_einsum(q.astype(cdt), k, prec) / jnp.sqrt(jnp.float32(hd))
    mask = (k_positions[None, :] <= q_positions[:, None])[:, None, None, :]
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
    tripwire on any new backend."""

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


def _decode_partial(qg, pos, chunk: int, cdt, prec):
    """The per-chunk online-softmax arithmetic of the batched decode scan,
    handed to :func:`_segmented_batched_scan` once per segment."""
    hd = qg.shape[-1]

    def partial(kc, vc, start, carry):
        m, l, o = carry
        k_pos = start + jnp.arange(chunk)
        scores = kvc.scores_einsum_batched(qg.astype(cdt), kc, prec) / jnp.sqrt(
            jnp.float32(hd)
        )  # [B, K, M, chunk]
        mask = (k_pos[None, :] <= pos[:, None])[:, None, None, :]
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


def batched_decode_attention(
    qg: jax.Array,  # [B, K, M, hd] f32 grouped queries (one token per row)
    cache,  # the layer's slab: fused leaf [2, B_max, S, K, hd] or (keys, values)
    pos: jax.Array,  # [B] per-row absolute positions (inactive rows: 0)
    chunk: int,
    paged=None,  # (pool_k, pool_v, tables [B, n_table], matched [B])
) -> jax.Array:
    """Blocked causal attention of B independent single-token queries, each
    over its OWN slab cache row, masked by its OWN position: row ``b`` sees
    slots 0..pos[b]. One fori_loop covers all rows with a shared DYNAMIC
    chunk bound (max over pos), so slots beyond the longest live context are
    never read; rows shorter than the bound are masked per chunk and fully-
    masked chunks contribute zero via the online-softmax merge. Returns
    [B, K, M, hd] f32. Requires S % chunk == 0 (callers fall back to the
    full-S einsum otherwise, exactly like the single-stream path). The
    slab may hold MORE rows than B (a dispatch bucket below B_max): only
    the first B rows are read. ``cache`` is passed as the layer stores it
    (array or QuantizedKV leaf, or the tp backend's ``(keys, values)``
    halves) and only chunk-sized pieces of it are ever sliced out.

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
    if paged is not None:
        from distributed_llama_tpu import telemetry

        telemetry.note_kernel_path("paged_attention", "xla_segmented")
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
    which keeps each query's output bit-identical to the single-token
    decode step at the same position. Returns [B, T, K, M, hd] f32.
    Requires S % chunk == 0 (callers fall back to the full-S einsum).

    ``paged``: the zero-copy prefix read, segmented exactly like
    :func:`batched_decode_attention` — the verify window always sits at
    pos >= matched, so every paged position is causally visible to every
    query offset and the per-chunk math is unchanged."""
    B, T, K, M, hd = qg.shape
    S, cdt, prec = kvc.slab_facts(cache)
    if paged is not None:
        from distributed_llama_tpu import telemetry

        telemetry.note_kernel_path("paged_attention", "xla_segmented")
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
