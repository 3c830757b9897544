"""The all-reduce seam + ICI ring all-reduce kernel.

Every tensor-parallel layer pays exactly two all-reduces (after wo and
after down — ``models.llama.block_tail``/``ffn``; the reference's two
gather+merge TCP hops per layer, src/llama2-tasks.cpp:115-131/196-212).
XLA lowers ``lax.psum`` to its own fused all-reduce, which SERIALIZES
after the matmul producing its operand: the collective cannot start until
the full [T, dim] product lands, and nothing overlaps the wire time. The
ring kernel here (`ring_all_reduce`, per SNIPPETS.md [1] /
docs.jax.dev pallas distributed) instead runs reduce-scatter + all-gather
as explicit bidirectional ``make_async_remote_copy`` steps, so on TPU the
per-chunk sends overlap the remaining chunks' adds — and, fused into the
same Mosaic program as a consumer, the matmul epilogue — instead of
fencing behind them.

Determinism contract (the reason this is NOT a naive rotate-and-add
ring): each output chunk's sum is accumulated ONCE, on the shard the
reduce-scatter assigns it, in a FIXED ring order, then broadcast verbatim
by the all-gather — so every shard holds byte-identical results, exactly
like ``psum`` (a rotate-and-add ring would give each shard a different
f32 association of the same addends, and replicated sampling would
diverge across shards).

Three implementations behind one seam (:func:`all_reduce`):

* ``psum``     — ``jax.lax.psum``, the default on every platform.
* ``ring_xla`` — the ring SCHEDULE via ``lax.ppermute`` steps: the same
                 chunk walk without Pallas, runnable on the CPU test mesh
                 (remote DMA has no interpret mode), and the parity
                 reference for the kernel's schedule.
* ``ring``     — the Pallas remote-DMA kernel, TPU compiled mode only.

``DLT_ALLREDUCE`` pins an implementation (``psum`` / ``ring_xla`` /
``ring``); unset, EVERY platform takes psum. An arm asked for by name is
the arm that runs: a ring kernel that fails to trace or lower fails the
program, it never degrades to psum. The ring kernels compile for a
described v5e 2x2 (tests/test_chip_compile.py) but have not run on a
chip: the two receive slots are reused without a capacity handshake, so
a neighbour running ahead could overwrite an unread slot (ROADMAP,
Speed: collectives on a real mesh). Every selection is counted in
``dllama_kernel_path_total{kernel="all_reduce"}`` so the implementation
actually serving is visible in /metrics.
"""

from __future__ import annotations

import os as _os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _note(path: str) -> None:
    from distributed_llama_tpu import telemetry

    telemetry.note_kernel_path("all_reduce", path)


def default_impl() -> str:
    """psum unless ``DLT_ALLREDUCE`` pins otherwise, on every platform:
    the ring kernel is an explicit opt-in until a chip run has judged it
    (module docstring)."""
    return _os.environ.get("DLT_ALLREDUCE") or "psum"


def all_reduce(x: jax.Array, axis_name: str | None, impl: str | None = None) -> jax.Array:
    """THE all-reduce seam: sum ``x`` over ``axis_name`` replicated-
    identically on every shard. ``axis_name=None`` is the single-chip
    no-op, mirroring the psum call sites it replaces."""
    if axis_name is None:
        return x
    if impl is None:
        impl = default_impl()
    if impl in ("ring", "ring_xla"):
        n = lax.axis_size(axis_name)
        if n <= 1 or x.shape[-1] < n:
            impl = "psum"  # tiny/odd payloads: the ring buys nothing
    if impl == "ring":
        # asked for by name: a kernel that fails to trace or lower fails
        # the program — it never degrades to psum behind the caller's back
        out = ring_all_reduce(x, axis_name, n)
        _note("ici_ring")
        return out
    if impl == "ring_xla":
        _note("ring_xla")
        return ring_all_reduce_xla(x, axis_name, n)
    _note("psum")
    return lax.psum(x, axis_name)


# ---------------------------------------------------------------------------
# Fused matmul + all-reduce (decode superstep, part b)
# ---------------------------------------------------------------------------
#
# Under ``psum`` (and the unfused ring) the wo/down matmul and the
# all-reduce are strictly sequential: the collective's first byte cannot
# leave until the LAST output column lands. But the ring schedule only
# needs ONE chunk to start its first hop — so the fused kernel below
# computes each output chunk's int8 matmul ON DEMAND inside the
# reduce-scatter walk and starts both directions' remote copies BEFORE
# computing the next step's chunks: the next tile's MXU work runs while
# the copies are in flight, which is the overlap the ISSUE's superstep
# buys over psum. The seam (:func:`matmul_all_reduce`) engages the fused
# kernel only under ``DLT_ALLREDUCE=ring`` + the int8 q40 path + an
# eligible shape; ineligible shapes take the unfused matmul + all_reduce
# arms (whose ring_xla/psum parity is pinned on the CPU mesh), and a
# kernel that fails to build fails the program.


def _fused_ring_eligible(x: jax.Array, qm, n: int) -> bool:
    """Shape/VMEM gate for the fused kernel: the 2n column chunks must be
    lane-aligned (w % 128), the n tiling must match the standalone int8
    kernel's (same f32 accumulation order → bit-parity by construction),
    and every operand must fit VMEM simultaneously (the kernel takes no
    grid — decode payloads only)."""
    from distributed_llama_tpu.quants import QK
    from distributed_llama_tpu.ops.q40 import BLOCK_N, _largest_divisor_tile

    T = x.shape[0]
    np_, dp = qm.n_padded, qm.d_padded
    if T > 8 or dp % (2 * n) or qm.qs.ndim != 2:
        return False
    w = dp // (2 * n)
    if w % 128 or _largest_divisor_tile(np_, BLOCK_N, 512) is None:
        return False
    vmem = (
        np_ // 2 * dp  # qs (uint8)
        + np_ // QK * dp * 4  # scales (f32)
        + T * np_  # xq (int8)
        + 2 * T * np_ // QK * 4  # sx + xsum (f32)
        + 3 * 2 * n * T * w * 4  # out + comm/scratch slots (f32)
    )
    return vmem < 10 * 2**20  # ~16 MB/core VMEM, leave headroom


def _neighbor_barrier(neighbor) -> None:
    """Both ring neighbours have entered the kernel before the first remote
    copy lands in their scratch (the rendezvous ``collective_id`` names)."""
    barrier = pltpu.get_barrier_semaphore()
    for nb in neighbor:
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=nb,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
    pltpu.semaphore_wait(barrier, 2)


def _make_fused_matmul_ring_kernel(axis_name: str, n: int, nj: int, w: int):
    """The fused int8-matmul + bidirectional-ring kernel factory.

    Ring schedule and chunk layout are IDENTICAL to
    :func:`_make_ring_kernel` (index 2c+d = ring d's chunk at position c);
    the difference is that ``local_chunk`` COMPUTES its chunk — the
    Q40×Q80 per-block int8 dot over output columns [k*w, (k+1)*w) plus the
    +8-bias correction — instead of loading a precomputed product, and the
    reduce-scatter step starts both remote copies BEFORE computing the
    next chunks so the MXU work overlaps the in-flight DMAs.

    The per-chunk matmul replicates the standalone kernel's accumulation
    structure exactly (``nj`` sequential block_n tiles, each adding its
    lo-half then hi-half per-block sums into the f32 accumulator — the
    ``_q40_matmul_int8`` grid order) so fused and unfused paths agree
    bitwise, not just approximately."""
    from distributed_llama_tpu.quants import QK

    def kernel(xq_ref, sx_ref, xsum_ref, qs_ref, scales_ref, out_ref,
               comm_ref, scratch_ref, send_sem, recv_sem):
        my = lax.axis_index(axis_name)
        neighbor = (jnp.mod(my + 1, n), jnp.mod(my - 1, n))  # cw, ccw
        _neighbor_barrier(neighbor)
        np2 = qs_ref.shape[0]  # packed rows = n_pad/2
        bn2 = np2 // nj  # packed rows per block_n tile
        nbt = bn2 // QK  # quant blocks per tile per half
        T = xq_ref.shape[1]

        def compute_chunk(k):
            """out[:, k*w:(k+1)*w] of THIS shard's x @ dequant(qm): the
            int8 block-dot epilogue, on demand."""
            cols = pl.ds(k * w, w)

            def half(xb, sxh, nib, swh):
                wb = nib.reshape(nbt, QK, w)
                P = jax.lax.dot_general(
                    xb, wb, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.int32,
                )  # [nbt, T, w]
                scaled = P.astype(jnp.float32) * swh[:, None, :]
                return jnp.sum(scaled * jnp.transpose(sxh)[:, :, None], axis=0)

            def tile(j, acc):
                # operands arrive in ops.q40.q80_kernel_operands layout
                qs = qs_ref[pl.ds(j * bn2, bn2), cols].astype(jnp.int32)
                lo = (qs & 0xF).astype(jnp.int8)
                hi = (qs >> 4).astype(jnp.int8)
                acc += half(
                    xq_ref[pl.ds(j * nbt, nbt)],
                    sx_ref[j],
                    lo,
                    scales_ref[pl.ds(j * nbt, nbt), cols],
                )
                acc += half(
                    xq_ref[pl.ds((nj + j) * nbt, nbt)],
                    sx_ref[nj + j],
                    hi,
                    scales_ref[pl.ds((nj + j) * nbt, nbt), cols],
                )
                return acc

            acc = lax.fori_loop(0, nj, tile, jnp.zeros((T, w), jnp.float32))
            # the +8 nibble-bias correction for THESE columns (per-shard:
            # the cross-shard sum of per-shard corrections is the global
            # correction, so the ring's adds need no special casing)
            corr = jax.lax.dot_general(
                xsum_ref[:], scales_ref[:, cols],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            return acc - 8.0 * corr

        def start_hop(d, slot, value):
            scratch_ref[d, slot] = value
            rdma = pltpu.make_async_remote_copy(
                src_ref=scratch_ref.at[d, slot],
                dst_ref=comm_ref.at[d, slot],
                send_sem=send_sem.at[d],
                recv_sem=recv_sem.at[d],
                device_id=neighbor[d],
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            return rdma

        def rs_step(s, carry):
            p_cw, p_ccw = carry
            slot = s % 2
            r0 = start_hop(0, slot, p_cw)
            r1 = start_hop(1, slot, p_ccw)
            # THE overlap: this step's chunk matmuls run on the MXU while
            # both remote copies are in flight
            add_cw = compute_chunk(2 * jnp.mod(my - s, n))
            add_ccw = compute_chunk(2 * jnp.mod(my + s, n) + 1)
            r0.wait()
            r1.wait()
            return comm_ref[0, slot] + add_cw, comm_ref[1, slot] + add_ccw

        p_cw, p_ccw = lax.fori_loop(
            1, n, rs_step, (compute_chunk(2 * my), compute_chunk(2 * my + 1))
        )
        out_ref[2 * jnp.mod(my + 1, n)] = p_cw
        out_ref[2 * jnp.mod(my - 1, n) + 1] = p_ccw

        def ag_step(s, carry):
            c_cw, c_ccw = carry
            slot = s % 2
            r0 = start_hop(0, slot, c_cw)
            r1 = start_hop(1, slot, c_ccw)
            r0.wait()
            r1.wait()
            got_cw, got_ccw = comm_ref[0, slot], comm_ref[1, slot]
            out_ref[2 * jnp.mod(my - s + 1, n)] = got_cw
            out_ref[2 * jnp.mod(my + s - 1, n) + 1] = got_ccw
            return got_cw, got_ccw

        lax.fori_loop(1, n, ag_step, (p_cw, p_ccw))

    return kernel


def fused_matmul_ring_all_reduce(
    x: jax.Array, qm, axis_name: str, n: int, role: str | None = None
) -> jax.Array:
    """psum_over_shards(x @ dequant(qm)) as ONE Pallas program: Q80
    quantize (outside — elementwise, XLA fuses it into the caller), then
    the int8 matmul computed chunk-by-chunk INSIDE the bidirectional ring
    reduce-scatter, remote copies overlapping the next chunks' MXU work.
    TPU compiled mode only, exactly like :func:`ring_all_reduce` (remote
    DMA has no interpret mode); callers reach it through the
    :func:`matmul_all_reduce` seam, which guards eligibility."""
    from distributed_llama_tpu.quants import QK
    from distributed_llama_tpu.ops.q40 import (
        BLOCK_N,
        _largest_divisor_tile,
        kernel_name,
        q80_kernel_operands,
        quantize_q80,
    )

    np_, dp = qm.n_padded, qm.d_padded
    T = x.shape[0]
    w = dp // (2 * n)
    bn = _largest_divisor_tile(np_, BLOCK_N, 512)
    nj = np_ // bn
    if x.shape[-1] != np_:
        x = jnp.pad(x, ((0, 0), (0, np_ - x.shape[-1])))
    xq, sx = quantize_q80(x)
    qsum = jnp.sum(xq.astype(jnp.float32).reshape(T, np_ // QK, QK), axis=-1)
    xsum = sx * qsum
    xqb, sxw = q80_kernel_operands(xq, sx, bn)
    slot = (2, 2, T, w)
    out = pl.pallas_call(
        _make_fused_matmul_ring_kernel(axis_name, n, nj, w),
        out_shape=jax.ShapeDtypeStruct((2 * n, T, w), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 5,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM(slot, jnp.float32),  # recv slots (remote writes)
            pltpu.VMEM(slot, jnp.float32),  # send staging
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=1
        ),
        name=kernel_name("q40_int8_ring", role),
    )(xqb, sxw, xsum, qm.qs, qm.scales)
    flat = jnp.concatenate(list(out), axis=-1)  # [T, dp]
    return flat[:, : qm.d] if dp != qm.d else flat


def matmul_all_reduce(
    x: jax.Array, w, axis_name: str | None, impl: str | None = None,
    role: str | None = None,
) -> jax.Array:
    """THE matmul+all-reduce seam: ``sum_over_shards(x @ w)``, replicated
    identically on every shard — what ``models.llama.block_tail``/``ffn``
    route the wo/down projections through. ``axis_name=None`` is the
    single-chip plain matmul. Dispatch ladder: the fused int8+ring Pallas
    kernel when ``DLT_ALLREDUCE=ring`` + an eligible shape (noted
    ``fused_ring``); otherwise the unfused matmul followed by
    :func:`all_reduce` under the chosen impl (psum / ring_xla / ring).
    Arm parity (tests/test_kernel_parity.py): the psum arm is exactly the
    unfused composition; ring-schedule arms agree within summation-order
    tolerance (a ring accumulates each chunk in ring order — a different
    f32 association than psum); the fused kernel replicates the unfused
    int8 matmul's tile accumulation order per chunk, so its divergence
    from the psum arm is the same association-only delta."""
    from distributed_llama_tpu.models.llama import _matmul

    if axis_name is None:
        return _matmul(x, w, role)
    if impl is None:
        impl = default_impl()
    if impl == "ring":
        from distributed_llama_tpu.ops.q40 import QuantizedMatrix

        n = lax.axis_size(axis_name)
        if (
            n > 1
            and isinstance(w, QuantizedMatrix)
            and _fused_ring_eligible(x, w, n)
        ):
            out = fused_matmul_ring_all_reduce(x, w, axis_name, n, role)
            _note("fused_ring")
            return out
    return all_reduce(_matmul(x, w, role), axis_name, impl)


# ---------------------------------------------------------------------------
# Ring schedule via ppermute (the CPU-mesh realization + parity reference)
# ---------------------------------------------------------------------------


def _ring_chunks(x: jax.Array, n: int):
    """Split the last axis into n equal chunks (zero-padded), stacked on a
    leading axis: [n, ..., ceil(d/n)]."""
    d = x.shape[-1]
    pad = (-d) % n
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return jnp.stack(jnp.split(x, n, axis=-1)), pad


def ring_all_reduce_xla(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """Ring all-reduce as N-1 reduce-scatter + N-1 all-gather ppermute
    steps — the exact chunk schedule of the Pallas kernel, expressed in
    XLA collectives. Each chunk c accumulates in the fixed ring order
    (c, c+1, ..., c+n-1) on its owner, so all shards end byte-identical.
    Runnable on the CPU test mesh; the parity gate vs psum lives in
    tests/test_kernel_parity.py."""
    orig = x.shape[-1]
    chunks, pad = _ring_chunks(x, n)
    me = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    # reduce-scatter: at step s, each shard forwards the partial it holds
    # and folds its local copy of the chunk arriving next; after n-1 steps
    # shard i owns the full sum of chunk (i + 1) mod n
    partial = jnp.take(chunks, me % n, axis=0)
    for s in range(1, n):
        partial = lax.ppermute(partial, axis_name, perm)
        partial = partial + jnp.take(chunks, (me - s) % n, axis=0)

    # all-gather: circulate the owned chunks; shard i receives chunk
    # (i - s + 1) mod n at step s and writes it at its global index
    out = jnp.zeros_like(chunks)
    cur = partial
    out = lax.dynamic_update_index_in_dim(out, cur, (me + 1) % n, 0)
    for s in range(1, n):
        cur = lax.ppermute(cur, axis_name, perm)
        out = lax.dynamic_update_index_in_dim(out, cur, (me - s + 1) % n, 0)

    flat = jnp.concatenate(list(out), axis=-1)
    return flat[..., :orig] if pad else flat


# ---------------------------------------------------------------------------
# Pallas remote-DMA ring kernel (TPU compiled mode)
# ---------------------------------------------------------------------------
#
# Bidirectional ring per the pallas distributed guide: the chunk axis is
# split into two halves, one walked clockwise and one counter-clockwise, so
# both ICI directions carry payload and the per-step wire time halves. Each
# direction runs the same reduce-scatter (+ all-gather) schedule as
# ring_all_reduce_xla. The remote copies are started as soon as a partial
# is ready — on TPU the next chunk's local add (and the surrounding
# program's epilogue) proceeds while the copy is in flight, which is the
# overlap psum structurally cannot give. make_async_remote_copy has no
# interpret mode, so this path is TPU-compiled-only; the schedule itself
# is pinned by the ring_xla parity tests and the two share their chunk
# arithmetic by construction.


def _make_ring_kernel(axis_name: str, n: int):
    """Kernel factory for the bidirectional ring. Chunk layout: ref index
    ``2*c + d`` holds ring ``d``'s chunk at ring position ``c`` (d = 0
    clockwise, d = 1 counter-clockwise), so both ICI directions carry half
    the payload. The two rings advance TOGETHER each step with both remote
    copies in flight concurrently — each direction's wire time hides under
    the other's wait+add, which is where the bidirectional win actually
    comes from (two sequential half-payload rings would just re-serialize
    it). Per ring, the schedule is IDENTICAL to
    :func:`ring_all_reduce_xla`'s (that parity is what the CPU-mesh tests
    pin): reduce-scatter accumulates chunk c in fixed ring order on its
    owner, then the all-gather circulates the owned chunks verbatim."""

    def kernel(chunks_ref, out_ref, comm_ref, scratch_ref, send_sem, recv_sem):
        my = lax.axis_index(axis_name)
        neighbor = (jnp.mod(my + 1, n), jnp.mod(my - 1, n))  # cw, ccw
        _neighbor_barrier(neighbor)

        def start_hop(d, slot, value):
            """Stage ``value`` and start its copy to ring ``d``'s
            neighbor; the caller waits AFTER both rings' copies are in
            flight."""
            scratch_ref[d, slot] = value
            rdma = pltpu.make_async_remote_copy(
                src_ref=scratch_ref.at[d, slot],
                dst_ref=comm_ref.at[d, slot],
                send_sem=send_sem.at[d],
                recv_sem=recv_sem.at[d],
                device_id=neighbor[d],
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            return rdma

        def both_hops(slot, v_cw, v_ccw):
            r0 = start_hop(0, slot, v_cw)
            r1 = start_hop(1, slot, v_ccw)  # both directions in flight
            r0.wait()
            r1.wait()
            return comm_ref[0, slot], comm_ref[1, slot]

        def local_chunk(d, c):
            return chunks_ref[2 * c + d]

        def rs_step(s, carry):
            p_cw, p_ccw = carry
            got_cw, got_ccw = both_hops(s % 2, p_cw, p_ccw)
            return (
                got_cw + local_chunk(0, jnp.mod(my - s, n)),
                got_ccw + local_chunk(1, jnp.mod(my + s, n)),
            )

        p_cw, p_ccw = lax.fori_loop(
            1, n, rs_step, (local_chunk(0, my), local_chunk(1, my))
        )
        out_ref[2 * jnp.mod(my + 1, n)] = p_cw
        out_ref[2 * jnp.mod(my - 1, n) + 1] = p_ccw

        def ag_step(s, carry):
            c_cw, c_ccw = carry
            got_cw, got_ccw = both_hops(s % 2, c_cw, c_ccw)
            out_ref[2 * jnp.mod(my - s + 1, n)] = got_cw
            out_ref[2 * jnp.mod(my + s - 1, n) + 1] = got_ccw
            return got_cw, got_ccw

        lax.fori_loop(1, n, ag_step, (p_cw, p_ccw))

    return kernel


def ring_all_reduce(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """Bidirectional Pallas remote-DMA ring all-reduce over ``axis_name``
    (TPU compiled mode only; the decode payloads are small enough that
    every operand sits in VMEM)."""
    orig_shape = x.shape
    d = x.shape[-1]
    # 2n chunks: index 2c+0 rides the clockwise ring, 2c+1 the counter ring
    pad = (-d) % (2 * n)
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    flat = x.reshape(-1, x.shape[-1])
    chunks = jnp.stack(jnp.split(flat, 2 * n, axis=-1))  # [2n, rows, d/2n]
    slot = (2, 2) + chunks.shape[1:]
    out = pl.pallas_call(
        _make_ring_kernel(axis_name, n),
        out_shape=jax.ShapeDtypeStruct(chunks.shape, chunks.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM(slot, chunks.dtype),  # recv slots (remote writes)
            pltpu.VMEM(slot, chunks.dtype),  # send staging
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # has_side_effects/collective_id are CORRECTNESS-critical for a
        # cross-device DMA kernel (DCE/reordering and the rendezvous id)
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=0
        ),
        name="ring_all_reduce",
    )(chunks)
    flat_out = jnp.concatenate(list(out), axis=-1)
    flat_out = flat_out[..., :d] if pad else flat_out
    return flat_out.reshape(orig_shape)
