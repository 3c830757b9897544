"""The slab scan of a batched decode step with PER-ROW bounds: one Pallas
kernel behind the causal and the EVA decode attention of a fused slab leaf.

The XLA scan (``ops.attention._segmented_batched_scan``) runs one loop for
all rows of the bucket, bounded by the bucket's farthest row, and slices
``[rows, chunk, K, hd]`` of every row in every step: a short row beside long
ones, an inactive lane, a row whose request has ended all read as far as the
longest. Here row ``b`` visits ITS ``n_steps[b]`` chunks only: chunk ``i``
starts at slot ``starts[b, i]`` of the row and the query sees its first
``visible[b, i]`` slots (in both scans what a row sees of a chunk is a prefix
of it, so the kernel need know neither rule). A row's chunk (keys AND values,
``leaf[:, b, start:start+chunk]``) comes out of the leaf AS STORED by an
async copy into VMEM, double-buffered across chunks and rows; the scores,
the mask and the online-softmax merge of ``ops.attention.merge_partials``
happen there, so the three small launches a chunk of the XLA scan's merge
are gone with the bytes no query sees.

Chunk indices, chunk size and merge order are the XLA scan's; operands are
in the leaf's dtype with f32 accumulation (a f32 leaf: f32 at ``highest``),
as ``kv_cache.slab_facts`` gives them there. Bit-identity to the XLA scan is
NOT promised on the chip (another dot, another order of summation): the two
agree to the tolerance tests/test_kernel_parity.py holds verify and decode to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows of the flattened chunk ([chunk * K, hd], a position's K heads beside
# each other) scored at once: [H, 1024] f32 of scores is 32 vregs at 32 heads
SUB_ROWS = 1024


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def supports(leaf, chunk: int) -> bool:
    """Whether :func:`slab_decode_scan` takes this slab: a fused ARRAY leaf
    ``[2, B_max, slots, K, hd]`` (not a ``QuantizedKV``, not the tp backend's
    ``(keys, values)`` tuple) of whole chunks, heads of whole lane rows (the
    tests' toy heads keep the XLA scan) and a flattened chunk that tiles in
    score blocks of whole positions: the kernel builds one block's kv-head
    bias and adds it to every block, so a block must start at kv head 0
    (K of 1, 8, 32 do; 10, 12 or 40 heads at several blocks a chunk keep the
    XLA scan)."""
    if not isinstance(leaf, jax.Array) or leaf.ndim != 5 or leaf.shape[0] != 2:
        return False
    _, _, slots, K, hd = leaf.shape
    if leaf.dtype not in (jnp.bfloat16, jnp.float32) or hd % LANES:
        return False
    sub = _sub_rows(chunk, K)
    return slots % chunk == 0 and (chunk * K) % sub == 0 and sub % K == 0 and chunk % 16 == 0


def _sub_rows(chunk: int, K: int) -> int:
    return min(SUB_ROWS, chunk * K)


def _scan_kernel(
    starts_ref, visible_ref, n_steps_ref,  # scalar prefetch: [B * n_max], [B * n_max], [B]
    q_ref,  # VMEM [B, H, hd] in the leaf's dtype, row k * M + m a query head
    leaf_ref,  # HBM [2, B_max, slots, K, hd], as stored
    o_ref,  # VMEM [B, H, hd] f32
    kv_buf,  # VMEM [2 (buffer), 2 (keys, values), chunk, K, hd]
    sems,  # DMA [2]
    s_buf,  # VMEM [H, chunk * K] f32: a chunk's masked scores
    bias_buf,  # VMEM [H, sub] f32: 0 where a column's kv head is the row's, else -inf
    *, n_max: int, chunk: int, M: int, prec,
):
    B, H, hd = q_ref.shape
    K = kv_buf.shape[3]
    rows = chunk * K
    sub = bias_buf.shape[1]
    n_sub = rows // sub
    root = jnp.sqrt(jnp.float32(hd))

    col = jax.lax.broadcasted_iota(jnp.int32, (H, sub), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (H, sub), 0)
    bias_buf[...] = jnp.where(col % K == row // M, 0.0, -jnp.inf).astype(jnp.float32)

    def copy(b, i, buf):
        start = starts_ref[b * n_max + i]
        return pltpu.make_async_copy(
            leaf_ref.at[:, b, pl.ds(start, chunk)], kv_buf.at[buf], sems.at[buf]
        )

    def chunk_partial(q, buf, vis, masked: bool):
        """(m, l, o) of one chunk in buffer ``buf``: the arithmetic of
        ``ops.attention._decode_partial`` over all heads at once. Column
        ``s * K + k`` of the flattened chunk is position ``s`` of kv head
        ``k``; a query head scores every column and keeps its own head's."""
        k2d = kv_buf.at[buf, 0].reshape(rows, hd)
        v2d = kv_buf.at[buf, 1].reshape(rows, hd)

        def scores(j, m):
            at = pl.multiple_of(j * sub, sub)
            s = jax.lax.dot_general(
                q, k2d[pl.ds(at, sub), :], (((1,), (1,)), ((), ())),
                precision=prec, preferred_element_type=jnp.float32,
            ) / root + bias_buf[...]
            if masked:
                # a select, not an add: slots past the bound may hold anything
                s = jnp.where(col < vis * K - at, s, -jnp.inf)
            s_buf[:, pl.ds(at, sub)] = s
            return jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))

        m = jax.lax.fori_loop(0, n_sub, scores, jnp.full((H, 1), -jnp.inf, jnp.float32))
        safe = jnp.where(jnp.isfinite(m), m, 0.0)

        def mix(j, carry):
            l, o = carry
            at = pl.multiple_of(j * sub, sub)
            p = jnp.exp(s_buf[:, pl.ds(at, sub)] - safe)
            v = v2d[pl.ds(at, sub), :]
            if masked:
                # p is 0 there, and 0 * anything must stay 0
                vrow = jax.lax.broadcasted_iota(jnp.int32, (sub, hd), 0)
                v = jnp.where(vrow < vis * K - at, v, jnp.zeros_like(v))
            o = o + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=jnp.float32,
            )
            return l + jnp.sum(p, axis=-1, keepdims=True), o

        l, o = jax.lax.fori_loop(
            0, n_sub, mix,
            (jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, hd), jnp.float32)),
        )
        return m, l, o

    def merge(m1, l1, o1, m2, l2, o2):
        # ops.attention.merge_partials: an empty partial is an exact identity
        m = jnp.maximum(m1, m2)
        safe = jnp.where(jnp.isfinite(m), m, 0.0)
        a1 = jnp.where(jnp.isfinite(m1), jnp.exp(m1 - safe), 0.0)
        a2 = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - safe), 0.0)
        return m, l1 * a1 + l2 * a2, o1 * a1 + o2 * a2

    def one_row(b, carry):
        buf0, started = carry
        n = n_steps_ref[b]
        # the next row's first chunk rides behind this row's last
        rides = (b + 1 < B) & (n_steps_ref[jnp.minimum(b + 1, B - 1)] > 0)
        q = q_ref[b]

        @pl.when((n > 0) & (started == 0))
        def _():
            copy(b, 0, buf0).start()

        def step(i, acc):
            buf = (buf0 + i) % 2
            more = i + 1 < n

            @pl.when(more)
            def _():
                copy(b, i + 1, 1 - buf).start()

            @pl.when(jnp.logical_not(more) & rides)
            def _():
                copy(b + 1, 0, 1 - buf).start()

            copy(b, i, buf).wait()
            vis = visible_ref[b * n_max + i]
            part = jax.lax.cond(
                vis < chunk,
                lambda: chunk_partial(q, buf, vis, True),
                lambda: chunk_partial(q, buf, vis, False),
            )
            return merge(*acc, *part)

        m, l, o = jax.lax.fori_loop(
            0, n, step,
            (
                jnp.full((H, 1), -jnp.inf, jnp.float32),
                jnp.zeros((H, 1), jnp.float32),
                jnp.zeros((H, hd), jnp.float32),
            ),
        )
        o_ref[b] = o / jnp.maximum(l, 1e-30)
        return (buf0 + n) % 2, ((n > 0) & rides).astype(jnp.int32)

    jax.lax.fori_loop(0, B, one_row, (jnp.int32(0), jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _scan(qg, leaf, starts, visible, n_steps, chunk: int, interpret: bool):
    B, K, M, hd = qg.shape
    H = -(-K * M // 16) * 16  # whole tiles of query heads; a pad row sees no kv head
    n_max = starts.shape[1]
    dt = leaf.dtype
    prec = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
    sub = _sub_rows(chunk, K)
    item = jnp.dtype(dt).itemsize
    vmem = 2 * 2 * chunk * K * hd * item + H * chunk * K * 4 + H * sub * 4
    kernel = functools.partial(_scan_kernel, n_max=n_max, chunk=chunk, M=M, prec=prec)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, 2, chunk, K, hd), dt),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, chunk * K), jnp.float32),
                pltpu.VMEM((H, sub), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem * 1.25) + (8 << 20),
        ),
        interpret=interpret,
        name="slab_decode_scan",
    )(
        starts.reshape(-1).astype(jnp.int32), visible.reshape(-1).astype(jnp.int32),
        n_steps.astype(jnp.int32),
        jnp.pad(qg.reshape(B, K * M, hd).astype(dt), ((0, 0), (0, H - K * M), (0, 0))), leaf,
    )
    return out[:, : K * M].reshape(B, K, M, hd)


def slab_decode_scan(
    qg: jax.Array,  # [B, K, M, hd] f32 grouped queries (one token per row)
    leaf: jax.Array,  # fused slab leaf [2, B_max, slots, K, hd] as stored, B <= B_max
    starts: jax.Array,  # int32 [B, n_max]: first slot of row b's chunk i
    visible: jax.Array,  # int32 [B, n_max]: how many of that chunk's slots the query sees
    n_steps: jax.Array,  # int32 [B]: the chunks row b visits, at most n_max
    chunk: int,
) -> jax.Array:
    """Softmax attention of B single-token queries, each over the chunks of
    its OWN slab row that the three tables name; returns [B, K, M, hd] f32.
    An empty chunk (``visible`` 0) merges as the exact identity and a row
    with ``n_steps`` 0 returns zeros. Nothing of the leaf is read but the
    named chunks: ``starts[b, i] + chunk`` must lie inside the row."""
    if not supports(leaf, chunk):
        raise ValueError(f"slab_decode_scan does not take a leaf of {getattr(leaf, 'shape', None)}")
    return _scan(qg, leaf, starts, visible, n_steps, chunk, _interpret_default())
