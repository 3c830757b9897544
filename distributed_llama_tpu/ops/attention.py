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

import functools
import os as _os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_llama_tpu.ops import kv_cache as kvc


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
    """The per-chunk online-softmax arithmetic of the batched decode scan —
    ONE definition consumed by both the XLA segmented scan and the fused
    Pallas kernel body, so the two paths emit the identical op sequence on
    identical chunk bytes (the mechanism behind their bit-parity)."""
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
    (speculative decode: T-query windows at pos[b]..pos[b]+T-1) — ONE
    definition consumed by both the XLA segmented scan and the fused Pallas
    kernel body, exactly like :func:`_decode_partial`: identical op
    sequence on identical chunk bytes is the bit-parity mechanism."""
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
    if paged is not None and _fused_paged_eligible(qg, cache, paged, chunk):
        from distributed_llama_tpu import telemetry

        telemetry.note_kernel_path("paged_attention", "pallas_fused")
        return fused_paged_decode_attention(qg, *cache, pos, chunk, paged)
    if paged is not None:
        from distributed_llama_tpu import telemetry

        # the hit path fell back to the chain of segmented-scan programs —
        # visible in /metrics so a silent slow path can be alerted on
        telemetry.note_kernel_path("paged_attention", "xla_segmented")
    live = jnp.clip(jnp.max(pos) + 1, 0, S)
    n_chunks = jax.lax.div(live + chunk - 1, chunk)
    partial = _decode_partial(qg, pos, chunk, cdt, prec)
    m0 = jnp.full((B, K, M), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, K, M), jnp.float32)
    o0 = jnp.zeros((B, K, M, hd), jnp.float32)
    m, l, o = _segmented_batched_scan(
        partial, cache, paged, chunk, n_chunks, (m0, l0, o0), rows=B
    )
    return o / jnp.maximum(l, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# Fused paged decode-attention (ROADMAP item 1): ONE Pallas program replaces
# the chain of separate XLA programs the segmented scan compiles on the
# prefix-hit path (per-segment fori_loops, per-chunk pool gathers, select,
# einsums, merges — each a separate HLO loop body with its own HBM round
# trips for the m/l/o carries). The kernel walks the SAME chunk indices in
# the SAME three segments (pool-only / mixed / slab-only — zero pool
# traffic on slab-only chunks, exactly like the scan), assembles each
# chunk's KV bytes with explicit async DMA into VMEM scratch (slab slice,
# or per-page copies routed through the row's page table), and runs the
# SHARED per-chunk arithmetic (:func:`_decode_partial`) with the online-
# softmax carries resident on-chip — so the merge math is the identical op
# sequence on identical bytes and the output is BIT-IDENTICAL to the
# eager composition of those per-chunk partials (the EXACT-EMPTY-PARTIAL
# semantics ride along for free; test-enforced across bf16/f32/i8 and
# bucket shapes in tests/test_kernel_parity.py). The XLA scan is the same
# math but its fori_loop codegen may reassociate the merge by ulps at
# verify widths T>1 (the mechanism _segmented_batched_scan documents) —
# parity vs the scan is bit-exact at the pinned decode/verify test shapes
# and within-ulp in general (bench.py --kernels records the divergence).
#
# Compiled-mode notes: the KV halves sit in ANY (HBM) memory space and
# are DMA'd chunk by chunk into VMEM scratch; page ids and loop bounds read
# from SMEM, queries and mask positions from VMEM — the Mosaic-shaped
# structure. The page/slab DMAs are DOUBLE-BUFFERED: chunk i+1's
# copies start into the other scratch slot before chunk i's einsums run, so
# the loads fly under the compute (``DLT_FUSED_DB=0`` keeps the serial
# start+wait schedule — the A/B baseline in bench.py --kernels; the
# schedule only reorders copy issue around unchanged compute, so both arms
# are bit-identical by construction). The same kernel body serves the
# speculative-decode verify hit path (T-query windows per row — decode is
# its T=1 degenerate case; :func:`fused_paged_verify_attention`). The
# gate in this tree is interpret-mode parity on the CPU mesh; the v5e
# compiler still refuses the body (see :func:`_fused_paged_enabled`).
# ---------------------------------------------------------------------------


def _fused_paged_enabled() -> bool:
    """OFF unless ``DLT_FUSED_PAGED=1`` — on every platform, so tier-1
    exercises the segmented scan the chip runs. The v5e compiler refuses
    the kernel body ("'tpu.matmul' op Not implemented: Up to 1 batch dim
    supported": the shared per-chunk einsums carry batch dims B and K;
    tests/test_chip_compile.py holds the xfail that notices the day it
    lowers), so asking for it on a chip fails at compile of the decode
    program. Its parity tests select it explicitly (interpret mode).
    Read per dispatch decision (trace time)."""
    env = _os.environ.get("DLT_FUSED_PAGED")
    return env is not None and env != "0"


def _fused_paged_eligible(qg, cache, paged, chunk: int) -> bool:
    """Shape/dtype gate for the fused kernel: slab and pool halves must
    agree on quantization class, chunks must be whole pages, and the slab
    must block evenly (callers already guarantee the last two on the
    production path — the checks make the fallback safe, not rare)."""
    if not _fused_paged_enabled():
        return False
    pool_k, pool_v, tables, matched = paged
    halves = (cache,) if kvc.is_fused_leaf(cache) else tuple(cache)
    quant = isinstance(halves[0], kvc.QuantizedKV)
    if any(
        isinstance(h, kvc.QuantizedKV) is not quant
        for h in halves + (pool_k, pool_v)
    ):
        return False
    page = kvc.pool_page_size(pool_k)
    return chunk % page == 0 and kvc.slab_facts(cache)[0] % chunk == 0


def _double_buffer_default() -> bool:
    """``DLT_FUSED_DB`` gates the double-buffered DMA schedule (default ON:
    the schedule only reorders copy issue/wait around unchanged compute, so
    both arms produce identical bytes by construction — pinned by the A/B
    arm in bench.py --kernels and tests/test_kernel_parity.py).
    ``DLT_FUSED_DB=0`` keeps the serial start+wait schedule. Read per
    dispatch decision (trace time)."""
    env = _os.environ.get("DLT_FUSED_DB")
    return env != "0" if env is not None else True


def _fused_paged_attention(
    qg, keys, values, pos, chunk: int, paged, interpret, double_buffer, verify: bool
):
    """Shared builder behind :func:`fused_paged_decode_attention` and
    :func:`fused_paged_verify_attention` — decode is the T=1 degenerate
    case of the verify window, so ONE kernel body serves both and a parity
    fix can never reach one entry point and skip the other."""
    from distributed_llama_tpu.ops.q40 import _interpret_default

    pool_k, pool_v, tables, matched = paged
    if verify:
        B, T, K, M, hd = qg.shape
        lead = (B, T, K, M)
    else:
        B, K, M, hd = qg.shape
        T = 1  # decode: one query per row, live bound max(pos) + 1
        lead = (B, K, M)
    S = keys.shape[1]
    quant = isinstance(keys, kvc.QuantizedKV)
    page = kvc.pool_page_size(pool_k)
    ppc = chunk // page
    n_table = tables.shape[1]
    nh = 2 if quant else 1
    cdt = kvc.compute_dtype(keys)
    prec = kvc.einsum_precision(keys)
    if interpret is None:
        interpret = _interpret_default()
    if double_buffer is None:
        double_buffer = _double_buffer_default()
    nslots = 2 if double_buffer else 1

    def halves(h):
        return (h.data, h.scales) if quant else (h,)

    def scratch_for(h, n_rows: int):
        """VMEM chunk-scratch shapes mirroring one source's halves — one
        buffer per DMA slot (two double-buffered, one serial)."""
        if quant:
            return [
                pltpu.VMEM((nslots, n_rows, chunk, K, hd), h.data.dtype),
                pltpu.VMEM((nslots, n_rows, chunk, K, 1), h.scales.dtype),
            ]
        return [pltpu.VMEM((nslots, n_rows, chunk, K, hd), h.dtype)]

    def kernel(*refs):
        pos_s, matched_s, pos_ref, matched_ref, tables_ref, qg_ref = refs[:6]
        body = refs[6 : 6 + 4 * nh]
        out_ref = refs[6 + 4 * nh]
        scr = refs[7 + 4 * nh :]
        slab_k, slab_v = body[:nh], body[nh : 2 * nh]
        pk, pv = body[2 * nh : 3 * nh], body[3 * nh : 4 * nh]
        sk_scr, sv_scr = scr[:nh], scr[nh : 2 * nh]
        pk_scr, pv_scr = scr[2 * nh : 3 * nh], scr[3 * nh : 4 * nh]
        sem = scr[4 * nh]

        pos_ = pos_ref[:]
        matched_ = matched_ref[:]
        mk_partial = _verify_partial if verify else _decode_partial
        partial = mk_partial(qg_ref[:], pos_, chunk, cdt, prec)
        # loop bounds are SCALAR work: read the SMEM copies (Mosaic loads
        # vectors from VMEM only, and a vector reduce cannot feed a loop
        # bound) — integer math, so the bounds equal the scan's exactly
        pos_max = functools.reduce(jnp.maximum, [pos_s[b] for b in range(B)])
        m_lo = functools.reduce(jnp.minimum, [matched_s[b] for b in range(B)])
        m_hi = functools.reduce(jnp.maximum, [matched_s[b] for b in range(B)])
        live = jnp.clip(pos_max + T, 0, S)
        n_chunks = jax.lax.div(live + chunk - 1, chunk)
        a = jnp.minimum(jax.lax.div(m_lo, chunk), n_chunks)
        b_seg = jnp.clip(jax.lax.div(m_hi + chunk - 1, chunk), a, n_chunks)

        def slab_copies(i, slot):
            # one sliced DMA per half: the first B slab rows' chunk window
            # (a dispatch bucket below B_max reads only its own rows,
            # mirroring kvc.slice_rows_batched(rows=B))
            return [
                pltpu.make_async_copy(
                    r.at[pl.ds(0, B), pl.ds(i * chunk, chunk)],
                    s.at[slot],
                    sem.at[slot],
                )
                for r, s in zip(slab_k + slab_v, sk_scr + sv_scr)
            ]

        def pool_copies(i, slot):
            # page-table-routed copies: page p of chunk i for row b comes
            # from pool page tables[b, i*ppc + p]. The table window start
            # clamps exactly like the scan's lax.dynamic_slice on tables.
            base = jnp.clip(i * ppc, 0, n_table - ppc)
            cs = []
            for b in range(B):
                for p in range(ppc):
                    pid = tables_ref[b, base + p]
                    cs.extend(
                        pltpu.make_async_copy(
                            r.at[pid],
                            s.at[slot, b, pl.ds(p * page, page)],
                            sem.at[slot],
                        )
                        for r, s in zip(pk + pv, pk_scr + pv_scr)
                    )
            return cs

        def start_loads(i, slot):
            # chunk i's sources by segment: slab from chunk a up, pool
            # below chunk b_seg — slab-only chunks issue ZERO pool
            # traffic, exactly like the scan's segment split
            @pl.when(i >= a)
            def _():
                for c in slab_copies(i, slot):
                    c.start()

            @pl.when(i < b_seg)
            def _():
                for c in pool_copies(i, slot):
                    c.start()

        def wait_loads(i, slot):
            # recreate the started descriptors (same refs, same sem slot);
            # every copy of the chunk is drained before any scratch read.
            # slots alternate, so chunk i+1's in-flight copies signal the
            # OTHER slot's semaphore and can never satisfy these waits.
            @pl.when(i >= a)
            def _():
                for c in slab_copies(i, slot):
                    c.wait()

            @pl.when(i < b_seg)
            def _():
                for c in pool_copies(i, slot):
                    c.wait()

        def read(scrs, slot):
            if quant:
                return kvc.QuantizedKV(scrs[0][slot], scrs[1][slot])
            return scrs[0][slot]

        def with_loads(compute):
            """Wrap a segment body with the DMA schedule. Double-buffered:
            start chunk i+1's copies into the other slot FIRST, so they fly
            under chunk i's einsums; segment membership is resolved per
            chunk index, so the prefetch crosses segment (and fori_loop)
            boundaries without special cases. Serial: start+wait the
            chunk's own copies, nothing in flight during compute."""

            def body_fn(i, carry):
                slot = jax.lax.rem(i, nslots)
                if double_buffer:
                    @pl.when(i + 1 < n_chunks)
                    def _():
                        start_loads(i + 1, jax.lax.rem(i + 1, nslots))
                else:
                    start_loads(i, slot)
                wait_loads(i, slot)
                return compute(i, slot, carry)

            return body_fn

        def compute_pool(i, slot, carry):
            return partial(read(pk_scr, slot), read(pv_scr, slot), i * chunk, carry)

        def compute_mixed(i, slot, carry):
            sel = (i * chunk + jnp.arange(chunk))[None, :] < matched_[:, None]
            kc = kvc.select_kv(sel, read(pk_scr, slot), read(sk_scr, slot))
            vc = kvc.select_kv(sel, read(pv_scr, slot), read(sv_scr, slot))
            return partial(kc, vc, i * chunk, carry)

        def compute_slab(i, slot, carry):
            return partial(read(sk_scr, slot), read(sv_scr, slot), i * chunk, carry)

        if double_buffer:
            # warm-up: chunk 0's copies have no prior compute to hide under
            @pl.when(n_chunks > 0)
            def _():
                start_loads(0, 0)

        m0 = jnp.full(lead, -jnp.inf, jnp.float32)
        l0 = jnp.zeros(lead, jnp.float32)
        o0 = jnp.zeros(lead + (hd,), jnp.float32)
        carry = jax.lax.fori_loop(0, a, with_loads(compute_pool), (m0, l0, o0))
        carry = jax.lax.fori_loop(a, b_seg, with_loads(compute_mixed), carry)
        m, l, o = jax.lax.fori_loop(b_seg, n_chunks, with_loads(compute_slab), carry)
        out_ref[:] = o / jnp.maximum(l, 1e-30)[..., None]

    # the KV halves stay in HBM (ANY) and reach VMEM scratch by DMA; what
    # the body reads directly sits where Mosaic can load it: scalars (loop
    # bounds, page ids) in SMEM, vectors (mask positions, queries) in VMEM
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_specs = (
        [smem_spec, smem_spec, vmem_spec, vmem_spec, smem_spec, vmem_spec]
        + [any_spec] * (4 * nh)
    )
    scratch = (
        scratch_for(keys, B) + scratch_for(values, B)
        + scratch_for(pool_k, B) + scratch_for(pool_v, B)
        + [pltpu.SemaphoreType.DMA((nslots,))]
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(lead + (hd,), jnp.float32),
        in_specs=in_specs,
        out_specs=vmem_spec,
        scratch_shapes=scratch,
        interpret=interpret,
        name="paged_attention_fused",
    )(
        pos.astype(jnp.int32), matched.astype(jnp.int32),
        pos.astype(jnp.int32), matched.astype(jnp.int32),
        tables.astype(jnp.int32), qg,
        *halves(keys), *halves(values), *halves(pool_k), *halves(pool_v),
    )


def fused_paged_decode_attention(
    qg: jax.Array,  # [B, K, M, hd] f32 grouped queries (one token per row)
    keys,  # slab cache half [B, S, K, hd] (array or QuantizedKV)
    values,
    pos: jax.Array,  # [B] per-row absolute positions
    chunk: int,
    paged,  # (pool_k, pool_v, tables [B, n_table], matched [B])
    interpret: bool | None = None,
    double_buffer: bool | None = None,
) -> jax.Array:
    """The fused Pallas form of the paged :func:`batched_decode_attention`
    hit path — same segment split, same chunk order, same merge arithmetic,
    bit-identical output. ``double_buffer`` (default: env ``DLT_FUSED_DB``,
    on) overlaps chunk i+1's page/slab DMAs with chunk i's einsums.
    Returns [B, K, M, hd] f32."""
    return _fused_paged_attention(
        qg, keys, values, pos, chunk, paged, interpret, double_buffer, verify=False
    )


def fused_paged_verify_attention(
    qg: jax.Array,  # [B, T, K, M, hd] f32 grouped queries (T = draft k + 1)
    keys,  # slab cache half [B, S, K, hd] (array or QuantizedKV)
    values,
    pos: jax.Array,  # [B] per-row positions of query t=0
    chunk: int,
    paged,  # (pool_k, pool_v, tables [B, n_table], matched [B])
    interpret: bool | None = None,
    double_buffer: bool | None = None,
) -> jax.Array:
    """The fused Pallas form of the paged :func:`batched_verify_attention`
    hit path (speculative decode) — the same kernel as the decode form with
    the T-query verify arithmetic (:func:`_verify_partial`) in the chunk
    body, so each query's output stays bit-identical to the single-token
    decode step at the same position. Returns [B, T, K, M, hd] f32."""
    return _fused_paged_attention(
        qg, keys, values, pos, chunk, paged, interpret, double_buffer, verify=True
    )


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
    query offset and the per-chunk math is unchanged. The paged hit path
    dispatches to the fused Pallas kernel under the same eligibility gate
    as decode (:func:`_fused_paged_eligible`, ``DLT_FUSED_PAGED``)."""
    B, T, K, M, hd = qg.shape
    S, cdt, prec = kvc.slab_facts(cache)
    if paged is not None and _fused_paged_eligible(qg, cache, paged, chunk):
        from distributed_llama_tpu import telemetry

        telemetry.note_kernel_path("paged_attention", "pallas_fused_verify")
        return fused_paged_verify_attention(qg, *cache, pos, chunk, paged)
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
