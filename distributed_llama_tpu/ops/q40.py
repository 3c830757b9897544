"""Fused Q40 matmul: weights stay 4-bit in HBM, nibbles unpacked in VMEM,
int8 MXU dot against Q80 activations, scales folded in after the dot.

This replaces the reference's production kernel path — hand-written NEON/AVX2
`matmulQ40vQ80` (reference: src/funcs.cpp:287-396) — with a Pallas TPU kernel.
The reference's entire throughput story is "keep weights 4-bit so a Pi's
memory bus can feed the cores"; the TPU version is the same story at HBM
scale: a bf16 7B model is ~13.5 GB of HBM traffic per decoded token, the Q40
form is ~4.2 GB, so the bandwidth-bound decode roofline rises ~3×.

Layout (``pack_q40_tpu``): for a matmul ``y[T,d] = x[T,n] @ W[n,d]``, with
n padded to ``n_pad`` (zero-scale rows) and ``half = n_pad/2``:
  * ``qs``     uint8 [n_pad/2, d] — W[i,j] in the low nibble and
               W[i+half,j] in the high nibble ("half-split" pairing),
               values biased by +8 (the file format's bias, reference:
               src/quants.cpp:171-182)
  * ``scales`` f32 [n_pad/32, d] — per-(32-input-block, output-column) scale

The repack from the file's row-major block form is *exact*: nibbles are
reordered, never re-quantized. Half-split pairing is what makes the matmul
gather-free: the kernel contracts the low nibbles against x[:, :half] and
the high nibbles against x[:, half:] — two CONTIGUOUS windows of x (a
matmul contraction is permutation-invariant when both operands are permuted
alike). The previous even/odd-row pairing needed strided x[:, 0::2] splits,
which XLA lowers to gathers costing ~6 ms/token on a 7B decode.

What a launch costs on a v5e (tools/q40_sweep.py, PERF.md §6, PR 31): its
bytes plus about a third of a microsecond a grid step up to 32 rows (in the
benchmark's cells 88-89 % of the roofline counted at 18 B per 32 weights,
which the f32 scales held here, 20 B, cap at 90 %); from 64 rows the
scale-product epilogue binds and the time doubles with the rows.

On the CPU backend (tests) the kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_llama_tpu.quants import QK

# Tile targets, measured on a v5e (``_BLOCK_D_BY_ROWS`` below has the numbers
# and caps the output tile by rows; tools/q40_sweep.py is their provenance)
BLOCK_N = 1024  # input tile, at every T (multiple of 512: the x window needs
# bn/2 % 128 == 0 and the scales tile bn/64 % 8 == 0)
BLOCK_D = 4096  # output tile (multiple of 128): a grid step costs about a
# third of a microsecond beside its DMA, so fewer and larger steps win


def _interpret_default() -> bool:
    """Pallas interpret mode exactly where Mosaic cannot compile: the CPU
    backend (the tier-1 tests). Every accelerator run takes the compiled
    kernel — chip_smoke.py asserts ``tpu_custom_call`` in the lowered text."""
    return jax.default_backend() == "cpu"


def _note_path(kernel: str, path: str) -> None:
    """Count one kernel-dispatch decision (trace-time — once per compiled
    program, not per token; docs/OBSERVABILITY.md `dllama_kernel_path_total`)."""
    from distributed_llama_tpu import telemetry

    telemetry.note_kernel_path(kernel, path)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedMatrix:
    """Q40 weight for ``x @ W``: packed nibbles + block scales.

    Registered as a pytree so it can live inside the params tree like a
    plain array. The packed arrays may be PADDED up to tile-friendly sizes
    (padding carries zero *scales*, so padded rows/columns dequantize to
    exact zeros); ``n``/``d`` are the logical (unpadded) matmul dims.
    """

    qs: jax.Array  # uint8 [..., n_pad/2, d_pad]
    scales: jax.Array  # f32 [..., n_pad/32, d_pad]
    n_logical: int = 0  # 0 = unpadded (use packed size)
    d_logical: int = 0

    @property
    def n(self) -> int:
        return self.n_logical or self.qs.shape[-2] * 2

    @property
    def d(self) -> int:
        return self.d_logical or self.qs.shape[-1]

    @property
    def n_padded(self) -> int:
        return self.qs.shape[-2] * 2

    @property
    def d_padded(self) -> int:
        return self.qs.shape[-1]

    @property
    def shape(self) -> tuple[int, ...]:
        return (*self.qs.shape[:-2], self.n, self.d)

    @property
    def dtype(self):
        return jnp.bfloat16  # activation dtype the matmul expects

    def tree_flatten(self):
        return (self.qs, self.scales), (self.n_logical, self.d_logical)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _n_padded(n: int) -> int:
    """Padded input dim: 512-multiples for kernel-eligible matrices (the
    scales-tile sublane rule needs block_n % 512 == 0), 64-multiples below
    that (half-split block alignment; such matrices take the XLA fallback)."""
    m = 512 if n > 512 else 64
    return -(-n // m) * m


def _d_padded(d: int) -> int:
    """Padded output dim: only pad dims that exceed the tile target — small
    matrices take small tiles (or the XLA fallback) without a blow-up."""
    return _least_width_of_no_more_tiles(d) if d > 1024 else d


def _pack_halves(vals_t: np.ndarray, scales_t: np.ndarray, n: int, d: int) -> QuantizedMatrix:
    """Pack BIASED nibble values [n, d] into the half-split layout after
    zero-scale padding. Padded regions contribute exact zeros to the matmul
    (scale 0), so no output slicing is needed for chained layers — only
    logits consumers must trim to d_logical."""
    n_pad, d_pad = _n_padded(n), _d_padded(d)
    if n_pad != n or d_pad != d:
        vals_t = np.pad(vals_t, ((0, n_pad - n), (0, d_pad - d)))
        scales_t = np.pad(
            scales_t, ((0, n_pad // 32 - scales_t.shape[0]), (0, d_pad - d))
        )
    half = n_pad // 2
    packed = (vals_t[:half] | (vals_t[half:] << 4)).astype(np.uint8)
    return QuantizedMatrix(
        qs=jnp.asarray(packed), scales=jnp.asarray(scales_t),
        n_logical=n, d_logical=d,
    )


def pack_q40_tpu(file_qs: np.ndarray, file_scales: np.ndarray, shape: tuple[int, int]) -> QuantizedMatrix:
    """Repack file-form Q40 (row-major [d_out, d_in] blocks, reference:
    converter/writer.py:29-53) into the transposed TPU layout — exactly.

    ``file_qs``: uint8 [n_blocks, 16]; ``file_scales``: f16 [n_blocks];
    ``shape``: the file tensor's (d_out, d_in). Returns the packed form for
    computing ``x[T, d_in] @ W.T[d_in, d_out]``.
    """
    d_out, d_in = shape
    if d_in % QK:
        raise ValueError(f"d_in {d_in} not divisible by {QK}")
    blocks_per_row = d_in // QK

    from distributed_llama_tpu import native

    if native.available():  # native/q40_native.cpp — same output, much faster
        raw = np.empty((d_out * blocks_per_row, 2 + QK // 2), np.uint8)
        raw[:, :2] = (
            np.ascontiguousarray(file_scales).astype(np.float16).view(np.uint8).reshape(-1, 2)
        )
        raw[:, 2:] = np.asarray(file_qs).reshape(-1, QK // 2)
        return _pack_raw_native(native, raw.reshape(-1), d_out, d_in)
    qs = file_qs.reshape(d_out, blocks_per_row, QK // 2)
    # biased nibble values 0..15 in file order: low nibble = value j,
    # high = value j+16 within the 32-block
    lo = qs & 0xF
    hi = qs >> 4
    vals = np.concatenate([lo, hi], axis=-1).reshape(d_out, d_in)  # uint8 biased
    scales = file_scales.reshape(d_out, blocks_per_row).astype(np.float32)
    return _pack_halves(
        np.ascontiguousarray(vals.T), np.ascontiguousarray(scales.T), d_in, d_out
    )


def _pack_raw_native(native, raw: np.ndarray, d_out: int, d_in: int):
    """Native half-split repack: the C++ side writes directly into the
    padded packed/scales arrays (padding rows are zero-scale)."""
    n_pad = _n_padded(d_in)
    packed, scales = native.q40_repack_tpu(raw, d_out, d_in, n_pad)
    d_pad = _d_padded(d_out)
    if d_pad != d_out:
        packed = np.pad(packed, ((0, 0), (0, d_pad - d_out)))
        scales = np.pad(scales, ((0, 0), (0, d_pad - d_out)))
    return QuantizedMatrix(
        qs=jnp.asarray(packed), scales=jnp.asarray(scales),
        n_logical=d_in, d_logical=d_out,
    )


def pack_q40_raw(raw: np.ndarray | bytes, shape: tuple[int, int]) -> QuantizedMatrix:
    """Repack a tensor directly from its raw `.m` bytes (the loader path).
    The native repacker serves wherever a toolchain could build it
    (``native.available()``); numpy otherwise."""
    d_out, d_in = shape
    from distributed_llama_tpu import native

    if native.available():
        return _pack_raw_native(native, np.frombuffer(raw, np.uint8), d_out, d_in)
    from distributed_llama_tpu.quants import q40_from_bytes

    qs, scales = q40_from_bytes(raw, d_out * d_in)
    return pack_q40_tpu(qs, scales, shape)


def quantize_q40_tpu(w: np.ndarray) -> QuantizedMatrix:
    """Quantize a float matrix W [n, d] (already in x@W orientation) directly
    to the TPU layout. Quantization blocks run along the input dim n,
    mirroring the file format's along-row blocks after transpose (half-split
    pairing is on input rows, so d has no parity constraint)."""
    from distributed_llama_tpu.quants import quantize_q40

    n, d = w.shape
    qs_file, scales_file = quantize_q40(np.ascontiguousarray(w.T))  # blocks along n
    return pack_q40_tpu(
        qs_file.reshape(-1, QK // 2), scales_file.reshape(-1), (d, n)
    )


def concat_shard_packs(mats: list[QuantizedMatrix], axis: str) -> QuantizedMatrix:
    """Assemble per-shard packs into ONE host-layout matrix whose equal-size
    blocks along the sharded axis are the shards, so a ``device_put`` with a
    ``NamedSharding`` places each shard's pack on its device verbatim.

    ``axis``: "out" for output-dim (column) shards (qkv / gate_up / wcls —
    RowMatmulSlice layout, reference: src/commands.cpp:11-43), "in" for
    input-dim (row) shards (wo / down — ColMatmulSlice, :45-73).

    The returned aux dims (n_logical/d_logical) are the PER-SHARD logical
    dims: the matrix is only ever consumed inside shard_map, where each
    device sees exactly one shard's block.
    """
    m0 = mats[0]
    for m in mats[1:]:
        if m.qs.shape != m0.qs.shape or (m.n, m.d) != (m0.n, m0.d):
            raise ValueError("shard packs must be identically shaped")
    ax = -1 if axis == "out" else -2
    qs = np.concatenate([np.asarray(m.qs) for m in mats], axis=ax)
    scales = np.concatenate([np.asarray(m.scales) for m in mats], axis=ax)
    return QuantizedMatrix(qs, scales, n_logical=m0.n, d_logical=m0.d)


def dequantize_tpu(qm: QuantizedMatrix) -> np.ndarray:
    """Reference unpacking of the TPU layout → f32 [n, d].
    Trims any tile padding back to the logical dims."""
    qs = np.asarray(qm.qs)
    scales = np.asarray(qm.scales)
    # half-split: low nibbles are logical rows [0, half), high [half, n_pad)
    lo = (qs & 0xF).astype(np.int8) - 8
    hi = (qs >> 4).astype(np.int8) - 8
    vals = np.concatenate([lo, hi], axis=0)
    scale_full = np.repeat(scales, QK, axis=0)
    return (vals.astype(np.float32) * scale_full)[: qm.n, : qm.d]


def kernel_name(kind: str, role: str | None) -> str:
    """The name a kernel launch carries into the compiled program and the
    device trace: its kind, and the ROLE of the matrix it multiplies
    (``wqkv``, ``wo``, ``gate_up``, ``down``, ``experts``, ``logits``) where
    the call site says it — ``wo`` and ``down`` share an output shape, and
    without the role a trace cannot tell them apart. A static string: it
    changes a program's text, never its arithmetic or how often it
    compiles."""
    return f"{kind}_{role}" if role else kind


# The output tile by rows, (rows up to, block_d), measured for THIS kernel on a
# v5e: one launch's device time in a profiler capture, tools/q40_sweep.py
# (PERF.md §6, PR 31, has the whole table; us at block_d 512 / 1024 / 2048 /
# 4096 for one Mixtral expert's gate|up, 4096 -> 28672, whose bytes take 81 us):
#
#   T=1    161 / 126 / 109 / 100      T=64   391 / 250 / 223 / 216
#   T=16   164 / 129 / 111 / 102      T=128  708 / 435 / 430 / refused
#   T=32   211 / 157 / 128 / 125      T=256 1359 / 875 / 843 / refused
#
# A grid step costs its DMA plus about a third of a microsecond, so up to 32
# rows, where the launch is bound by its bytes, the widest tile wins (224
# steps of 512 columns at 16 rows took 1.6 times as long as 28 of 4096; the
# other widths read alike). From 64 rows the scale-product epilogue binds
# and the tile matters less. Each entry is one halving below the widest tile
# the v5e compiler accepts for every served width at those rows ("Ran out of
# memory in memory space vmem" past it); the compiler accepts 128 columns up
# to 640 rows at every served width and nothing from 1024, so a launch of
# more rows takes the XLA fallback (tests/test_chip_compile.py asks at 1024).
_BLOCK_D_BY_ROWS = ((32, 4096), (64, 2048), (256, 1024), (640, 128))


def _int8_tiles(qm: QuantizedMatrix, T: int, block_n: int, block_d: int):
    """The ONE dispatch decision of the Q40 matmul, from shape alone: the
    (bn, bd) tiles the int8 kernel runs with, dividing the padded dims, or
    None → the XLA fallback (a matrix too small or odd to tile, or more rows
    than any tile of ``_BLOCK_D_BY_ROWS`` holds). Only the output tile
    follows T: the input tile sets the contraction order, so results do not
    depend on T. block_n granule 512: the x window (T, bn/2) needs
    bn/2 % 128 == 0 and the scales tile (bn/64, bd) needs bn/64 % 8 == 0
    (mosaic sublane/lane tiling rules)."""
    cap = next((bd for rows, bd in _BLOCK_D_BY_ROWS if T <= rows), None)
    if cap is None:
        return None
    block_n = _largest_divisor_tile(qm.n_padded, block_n, 512)
    block_d = _largest_divisor_tile(qm.d_padded, min(block_d, cap), 128)
    if block_n is None or block_d is None:
        return None
    return block_n, block_d


def q40_matmul(
    x: jax.Array,
    qm: QuantizedMatrix,
    block_n: int = BLOCK_N,
    block_d: int = BLOCK_D,
    interpret: bool | None = None,
    role: str | None = None,
) -> jax.Array:
    """y[T, d] = x[T, n] @ dequant(qm), f32 accumulation — the ONE Q40
    matmul entry point (``models.llama._matmul`` routes every quantized
    weight through here). Shape alone picks the implementation
    (:func:`_int8_tiles`):

    * the int8 MXU kernel — activations quantized to Q80 (per-32-block
      int8 + f32 scale), per-block exact int32 accumulation on the MXU,
      scale-product epilogue;
    * the XLA fallback for matrices too small/odd to tile and for T past
      the kernel's VMEM fit.

    Every dispatch decision is counted in ``dllama_kernel_path_total``
    (mxu_int8 / mxu_int8_fusedq / xla_fallback) so a silent fallback to the
    slow path is visible in /metrics. ``role`` names the matrix in the
    kernel's trace name (:func:`kernel_name`)."""
    tiles = _int8_tiles(qm, x.shape[0], block_n, block_d)
    if tiles is None:
        _note_path("q40_matmul", "xla_fallback")
        return _q40_matmul_fallback_jit(x, qm)
    if interpret is None:
        interpret = _interpret_default()
    _note_path("q40_matmul", "mxu_int8")
    return _q40_matmul_int8(x, qm, *tiles, interpret, role)


# ---------------------------------------------------------------------------
# int8 MXU path: Q40 weights × Q80 activations
# ---------------------------------------------------------------------------
#
# Dequantizing a weight tile to floats costs every weight element a cast, a
# mask or shift and a scale multiply on the 8×128 VPU before the MXU sees it
# (the kernel PR 30 deleted did). This kernel keeps the arithmetic on the
# MXU's native int8 systolic array instead (reference: matmulQ40vQ80,
# src/funcs.cpp:287-396 — the reference's production combination for
# exactly this reason), and the VPU touches a weight only as a quarter of a
# 32-bit word (``_nibbles``):
#
#   * activations quantize to Q80 — per-32-block int8 + f32 scale, the
#     reference's buffer format — ONE cheap pass over the [T, n] x (tiny
#     next to the [n, d] weight);
#   * the kernel contracts BIASED int8 nibbles against int8 activations
#     with exact int32 accumulation, one 32-deep dot PER QUANT BLOCK: the
#     pack layout is restructured (reshape, not relayout — the half-split
#     windows already group whole blocks) so the blocks ride the MXU batch
#     axis while the 128-multiple output tile fills the 128-wide lane axis
#     of the contraction;
#   * the scale product sx[t,b]·sw[b,d] folds in AFTER the integer dot (a
#     [T, nb, bd]-sized epilogue — 32× less VPU work than scaling every
#     weight element, and exact: int32 block sums are exact, so the only
#     new noise is the Q80 activation rounding itself, ~0.4% per element
#     against Q40's own ~3%);
#   * the nibbles' +8 bias comes off AFTER the launch as a rank-reduced MXU
#     correction, 8 * sum(x per 32-block) @ scales, instead of two more VPU
#     passes over every weight element; it is computed from the DEQUANTIZED
#     Q80 block sums (the same values the kernel consumed, so the
#     cancellation is exact in f32).


def quantize_q80(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Quantize activations [T, n_pad] to Q80: (int8 values [T, n_pad],
    f32 scales [T, n_pad/32]), one scale per 32 consecutive elements,
    matching the weight scales' block order directly
    (symmetric, scale = max|x|/127 — the reference's Q80 rule,
    src/quants.cpp:98-122)."""
    T = x.shape[0]
    np_ = x.shape[-1]
    xf = x.astype(jnp.float32)
    xb = xf.reshape(T, np_ // QK, QK)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    sx = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xb / sx[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(T, np_), sx


def _nibbles(qs_ref) -> tuple[jax.Array, jax.Array]:
    """The (low, high) nibbles of a packed u8 ``[bn/2, bd]`` tile as two int8
    tiles of the same shape, values 0..15. Mosaic has no 8-bit shift on v5e,
    so the tile is read as 32-bit words of four packed bytes each, masked and
    shifted there (3 vector operations a packed register; widening every
    byte to an int32 of its own first took about 20) and read back as int8.
    A per-byte mask does not care which four rows share a word, so the round
    trip is the identity whatever the packing (interpret mode included)."""
    w = pltpu.bitcast(qs_ref[:], jnp.uint32)  # [bn/8, bd]
    lo = w & jnp.uint32(0x0F0F0F0F)
    hi = (w >> 4) & jnp.uint32(0x0F0F0F0F)
    return pltpu.bitcast(lo, jnp.int8), pltpu.bitcast(hi, jnp.int8)


def _make_q40_int8_kernel():
    """int8 MXU kernel factory: one (d-tile, n-tile) grid step runs one
    exact int32 block-dot per quant block and folds the scale products into
    the f32 accumulator.

    Block layout per half-split window (bn2 = block_n/2 packed rows,
    nbt = bn2/32 blocks): 32 CONSECUTIVE rows per block → reshape
    [bn2, bd] → [nbt, 32, bd] — a pure reshape of the resident tile (the
    layout restructuring is free) feeding ONE batched ``dot_general`` with
    the blocks on the batch axis, 32-deep int8 contraction, and the
    128-multiple output tile on the lane axis; int32 accumulation is
    exact."""

    def kernel(xlo_ref, xhi_ref, sxlo_ref, sxhi_ref, qs_ref, slo_ref,
               shi_ref, out_ref, acc_ref):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        # nibbles stay BIASED (0..15, exact in int8); the -8 is the caller's
        # rank-reduced MXU correction (_int8_core)
        lo, hi = _nibbles(qs_ref)
        bn2, bd = lo.shape
        nbt = bn2 // QK

        def half(xq_ref, sx_ref, w_nibbles, sw_ref):
            xb = xq_ref[:]  # [nbt, T, QK]
            wb = w_nibbles.reshape(nbt, QK, bd)
            # exact per-block int32 accumulation on the MXU int8 path
            P = jax.lax.dot_general(
                xb, wb, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.int32,
            )  # [nbt, T, bd]
            # scale-product epilogue: sum_b sx[t,b] * sw[b,d] * P[b,t,d] —
            # [T, nbt, bd]-sized VPU work against a scale multiply per
            # weight element
            scaled = P.astype(jnp.float32) * sw_ref[:][:, None, :]
            return jnp.sum(scaled * jnp.transpose(sx_ref[:])[:, :, None], axis=0)

        acc_ref[:] += half(xlo_ref, sxlo_ref, lo, slo_ref)
        acc_ref[:] += half(xhi_ref, sxhi_ref, hi, shi_ref)

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            out_ref[:] = acc_ref[:]

    return kernel


def q80_kernel_operands(xq: jax.Array, sx: jax.Array, block_n: int):
    """Q80 activations in the layout the int8 kernels read: values blocked
    ``[n/32, T, QK]`` and scales window-major ``[2*nj, T, nbt]`` (nbt blocks
    per half-split window of block_n). Mosaic refuses the flat forms: an
    in-kernel int8 ``[T, bn/2] -> [T, nbt, QK]`` reshape ("unsupported shape
    cast") and a ``(T, nbt)`` tile of ``[T, n/32]`` (nbt = 16 lanes breaks
    the (8, 128) block rule; with the window on a leading axis the tile's
    last two dims ARE the array's, which the rule allows)."""
    T, np_ = xq.shape
    nbt = block_n // 2 // QK
    xqb = xq.reshape(T, np_ // QK, QK).transpose(1, 0, 2)
    sxw = sx.reshape(T, np_ // QK // nbt, nbt).transpose(1, 0, 2)
    return xqb, sxw


def _int8_core(
    xq: jax.Array,
    sx: jax.Array,
    qm: QuantizedMatrix,
    block_n: int,
    block_d: int,
    interpret: bool,
    role: str | None = None,
) -> jax.Array:
    """The int8 kernel launch + bias epilogue on ALREADY-QUANTIZED Q80
    activations (xq int8 [T, n_pad], sx f32 [T, n_pad/32]) — shared by the
    standalone matmul, the fused rmsnorm→Q80 entry, and the fused
    matmul+all-reduce seam (ops.collectives), so every fusion is
    arithmetic-identical to the standalone path by construction. Not
    jitted: callers own the program boundary."""
    d, dp = qm.d, qm.d_padded
    np_ = qm.n_padded
    T = xq.shape[0]
    nj = np_ // block_n
    grid = (dp // block_d, nj)
    nbt = block_n // 2 // QK
    xqb, sxw = q80_kernel_operands(xq, sx, block_n)
    out = pl.pallas_call(
        _make_q40_int8_kernel(),
        grid=grid,
        in_specs=[
            # Q80 activations are NOT split on the host: the lo/hi halves
            # arrive as two BlockSpec views over the same array — window j
            # for the low nibbles, window nj + j (the upper half) for the
            # high nibbles. Contiguous, gather-free.
            pl.BlockSpec((nbt, T, QK), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((nbt, T, QK), lambda i, j, nj=nj: (nj + j, 0, 0)),
            pl.BlockSpec((None, T, nbt), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, T, nbt), lambda i, j, nj=nj: (nj + j, 0, 0)),
            pl.BlockSpec((block_n // 2, block_d), lambda i, j: (j, i)),
            pl.BlockSpec((nbt, block_d), lambda i, j: (j, i)),
            pl.BlockSpec((nbt, block_d), lambda i, j, nj=nj: (nj + j, i)),
        ],
        out_specs=pl.BlockSpec((T, block_d), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((T, dp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((T, block_d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        name=kernel_name("q40_int8", role),
    )(xqb, xqb, sxw, sxw, qm.qs, qm.scales, qm.scales)
    # bias correction on the DEQUANTIZED Q80 block sums: sum_{i in b} of
    # sx[t,b]*xq[t,i] — f32-exact given the int sums are exact. True-f32
    # multiplies (HIGHEST): the correction is ~5x the output's magnitude and
    # cancels against the kernel's sum, so TPU's default bf16 demotion would
    # leak error; the dot is rank-n/32, three passes cost nothing measurable
    qsum = jnp.sum(xq.astype(jnp.float32).reshape(T, np_ // QK, QK), axis=-1)
    xsum = sx * qsum
    corr = jax.lax.dot_general(
        xsum, qm.scales,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    out = out - 8.0 * corr
    return out[:, :d] if dp != d else out


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret", "role"))
def _q40_matmul_int8(
    x: jax.Array,
    qm: QuantizedMatrix,
    block_n: int,
    block_d: int,
    interpret: bool,
    role: str | None = None,
) -> jax.Array:
    """The int8 MXU path of :func:`q40_matmul`: Q80-quantize x, run the
    per-block int8 kernel, subtract the +8 bias as the rank-reduced MXU
    correction computed from the DEQUANTIZED Q80 sums (exactly the values
    the kernel consumed, so the f32 cancellation is exact)."""
    np_ = qm.n_padded
    if x.shape[-1] != np_:
        x = jnp.pad(x, ((0, 0), (0, np_ - x.shape[-1])))
    xq, sx = quantize_q80(x)
    return _int8_core(xq, sx, qm, block_n, block_d, interpret, role)


# ---------------------------------------------------------------------------
# Grouped int8 matmul over a STACKED bank of experts (ISSUE 26)
# ---------------------------------------------------------------------------
#
# An expert layer that holds 20 small experts of which a 32-row decode step
# touches about 11 would, as a launch per expert, put 40 kernel sites and 20
# conditionals into every layer of every program. Here the experts' packs
# are stacked on a leading axis ([E, n/2, d] nibbles, [E, n/32, d] scales)
# and ONE launch walks (expert, d-tile, n-tile); a scalar-prefetched table
# says which experts some row chose. An expert none chose is neither read
# nor computed: its grid steps point at the block the walk already holds
# (a block index that does not change starts no DMA) and write zeros.


def stack_bank(mats: list[QuantizedMatrix]) -> QuantizedMatrix:
    """Packs of equal shape stacked into one bank (host-side, at load)."""
    first = mats[0]
    return QuantizedMatrix(
        np.stack([np.asarray(m.qs) for m in mats]),
        np.stack([np.asarray(m.scales) for m in mats]),
        first.n_logical, first.d_logical,
    )


def _make_q40_grouped_kernel():
    """:func:`_make_q40_int8_kernel` with the expert on the first grid axis
    and the nibbles' +8 bias taken off the exact int32 block sums in place
    (``P - 8 * sum of the block's Q80 values``), so no per-expert correction
    matmul over the whole bank's scales follows the launch."""

    def kernel(sel_ref, on_ref, xlo_ref, xhi_ref, sxlo_ref, sxhi_ref, qslo_ref, qshi_ref,
               qs_ref, slo_ref, shi_ref, out_ref, acc_ref):
        del sel_ref  # read by the index maps
        e, j = pl.program_id(0), pl.program_id(2)

        @pl.when(j == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        @pl.when(on_ref[e] != 0)
        def _():
            lo, hi = _nibbles(qs_ref)
            bn2, bd = lo.shape
            nbt = bn2 // QK

            def half(xq_ref, sx_ref, qsum_ref, w_nibbles, sw_ref):
                wb = w_nibbles.reshape(nbt, QK, bd)
                P = jax.lax.dot_general(
                    xq_ref[:], wb, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.int32,
                )  # [nbt, T, bd]
                unbiased = P.astype(jnp.float32) - 8.0 * jnp.transpose(qsum_ref[:])[:, :, None]
                scaled = unbiased * sw_ref[:][:, None, :]
                return jnp.sum(scaled * jnp.transpose(sx_ref[:])[:, :, None], axis=0)

            acc_ref[:] += half(xlo_ref, sxlo_ref, qslo_ref, lo, slo_ref)
            acc_ref[:] += half(xhi_ref, sxhi_ref, qshi_ref, hi, shi_ref)

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            out_ref[:] = acc_ref[:]

    return kernel


# Rows of a row tile of a held expert's bucket (tools/q40_sweep.py's ``tiled``
# cases, PERF.md §6, PR 54): a launch's time is linear in its rows from 16 up,
# so a bucket of more rows than this is multiplied tile by tile and the tiles
# past its expert's own rows are skipped
GROUPED_ROW_TILE = 32


def grouped_row_tile(rows: int) -> int:
    """Rows of one row tile of a ``rows``-row bucket in the grouped launch: the
    bucket itself up to ``GROUPED_ROW_TILE`` rows (one tile, every decode
    step's), else ``GROUPED_ROW_TILE``."""
    return GROUPED_ROW_TILE if rows > GROUPED_ROW_TILE and rows % GROUPED_ROW_TILE == 0 else rows


def grouped_live_tiles(counts: jax.Array, rows: int, shared: bool) -> tuple[int, jax.Array]:
    """The grouped launch's one rule of what it multiplies: the rows of a row
    tile, and bool [E, R] which of each expert's ``R`` row tiles hold a live
    row. ``counts`` [E]: the rows that chose each expert, its live rows the
    first ``counts[e]`` of its ``rows``. Rows every expert ``shared`` are one
    tile of all of them; an expert's own bucket is cut by
    :func:`grouped_row_tile`."""
    tm = rows if shared else grouped_row_tile(rows)
    return tm, jnp.arange(rows // tm) * tm < counts.astype(jnp.int32)[:, None]


@functools.partial(jax.jit, static_argnames=("interpret", "role"))
def q40_grouped_matmul(
    x: jax.Array,
    bank: QuantizedMatrix,
    counts: jax.Array,
    interpret: bool | None = None,
    role: str | None = None,
) -> jax.Array:
    """``x @ expert`` for every expert of ``bank`` that ``counts`` [E] gives a
    row, zeros for the others, f32 [E, T, d_padded]. ``x`` is [T, n] (every
    expert multiplies the same rows) or [E, T, n], expert ``e``'s live rows
    its first ``counts[e]`` and the rest zeros (a bucket filled from slot 0
    up; a bool reads as one row). Activations are Q80 as in
    :func:`q40_matmul`'s int8 path. The launch is named
    ``q40_int8_grouped_<role>``.

    The grid's first axis walks (expert, row tile) pairs, expert-major
    (:func:`grouped_live_tiles`: one tile an expert but for a bucket of more
    than ``GROUPED_ROW_TILE`` rows). A tile at or past its expert's count is
    neither read nor multiplied and comes back as zeros. A live tile runs the
    same body at the same input tile whatever the rows of a tile, so a live
    row's result does not depend on how its bucket is cut."""
    E = bank.qs.shape[0]
    T = x.shape[-2]
    np_, dp = bank.n_padded, bank.d_padded
    shared = x.ndim == 2
    tm, live = grouped_live_tiles(counts, T, shared)
    R = T // tm
    tiles = _int8_tiles(bank, tm, BLOCK_N, BLOCK_D)
    if tiles is None:
        # packs too small or odd to tile (the tests' toy widths): every
        # expert through the XLA fallback, the unchosen ones zeroed
        _note_path("q40_grouped_matmul", "xla_fallback")
        xs = x if x.ndim == 3 else jnp.broadcast_to(x, (E,) + x.shape)
        outs = jax.vmap(
            lambda xe, qs, sc: _q40_matmul_fallback(
                xe, QuantizedMatrix(qs, sc, bank.n_logical, dp)
            )
        )(xs, bank.qs, bank.scales)
        return jnp.where(jnp.repeat(live, tm, axis=1)[..., None], outs, 0.0)
    block_n, block_d = tiles
    if interpret is None:
        interpret = _interpret_default()
    _note_path("q40_grouped_matmul", "mxu_int8")
    if x.shape[-1] != np_:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, np_ - x.shape[-1]),))
    flat = x.reshape(-1, np_)
    xq, sx = quantize_q80(flat)
    qsum = jnp.sum(xq.astype(jnp.float32).reshape(-1, np_ // QK, QK), axis=-1)
    # the block sums travel in the scales' window-major layout
    operands = jax.vmap(lambda a, b: q80_kernel_operands(a, b, block_n))
    xq3 = xq.reshape(-1, T, np_)
    xqb, sxw = operands(xq3, sx.reshape(-1, T, np_ // QK))
    _, qsw = operands(xq3, qsum.reshape(-1, T, np_ // QK))
    if shared:
        xqb, sxw, qsw = xqb[0], sxw[0], qsw[0]
    nj, ni = np_ // block_n, dp // block_d
    nbt = block_n // 2 // QK
    on = live.reshape(E * R).astype(jnp.int32)
    idx = jnp.arange(E * R, dtype=jnp.int32)
    last_on = jax.lax.cummax(jnp.where(on != 0, idx, -1))
    # the pair whose blocks a grid step holds: its own, or the last live one
    # before it (the first live one, for those ahead of it): a block index
    # that does not change starts no DMA
    sel = jnp.where(last_on >= 0, last_on, jnp.argmax(on).astype(jnp.int32))

    def pair(p):
        """(expert, row tile) of pair ``p``. One tile an expert pays for no
        division in the launch's nine index maps: 0.6 us of a decode step's
        launch of 104 (PERF.md section 6, PR 54)."""
        if R == 1:
            return p, 0
        return jax.lax.div(p, jnp.int32(R)), jax.lax.rem(p, jnp.int32(R))

    def held(p, j, sel_ref, on_ref):
        """(expert, row tile, n-tile) whose blocks pair ``p`` holds at step ``j``."""
        return *pair(sel_ref[p]), jnp.where(on_ref[p] != 0, j, nj - 1)

    def w_map(half):
        def index(p, i, j, sel_ref, on_ref):
            e, _, jj = held(p, j, sel_ref, on_ref)
            return e, half * nj + jj if half is not None else jj, jnp.where(on_ref[p] != 0, i, ni - 1)
        return index

    def x_map(half):
        if shared:
            return lambda p, i, j, sel_ref, on_ref: (half * nj + j, 0, 0)

        def index(p, i, j, sel_ref, on_ref):
            e, r, jj = held(p, j, sel_ref, on_ref)
            return e, half * nj + jj, r, 0
        return index

    x_block = (nbt, tm, QK) if shared else (None, nbt, tm, QK)
    s_block = (None, tm, nbt) if shared else (None, None, tm, nbt)
    out = pl.pallas_call(
        _make_q40_grouped_kernel(),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E * R, ni, nj),
            in_specs=[
                pl.BlockSpec(x_block, x_map(0)),
                pl.BlockSpec(x_block, x_map(1)),
                pl.BlockSpec(s_block, x_map(0)),
                pl.BlockSpec(s_block, x_map(1)),
                pl.BlockSpec(s_block, x_map(0)),
                pl.BlockSpec(s_block, x_map(1)),
                pl.BlockSpec((None, block_n // 2, block_d), w_map(None)),
                pl.BlockSpec((None, nbt, block_d), w_map(0)),
                pl.BlockSpec((None, nbt, block_d), w_map(1)),
            ],
            out_specs=pl.BlockSpec(
                (None, tm, block_d), lambda p, i, j, sel_ref, on_ref: (*pair(p), i)
            ),
            scratch_shapes=[pltpu.VMEM((tm, block_d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, T, dp), jnp.float32),
        interpret=interpret,
        # the unbiased block sums are one more [bn/64, T, bd] f32 temporary
        # than the ungrouped kernel holds: at 256 rows more than the
        # compiler's default scoped limit of 16 MiB (16.3 already at 512
        # columns; the chip's VMEM is 128)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20,
        ),
        name=kernel_name("q40_int8_grouped", role),
    )(sel, on, xqb, xqb, sxw, sxw, qsw, qsw, bank.qs, bank.scales, bank.scales)
    return out


# ---------------------------------------------------------------------------
# Fused rmsnorm → Q80 quantize → int8 matmul (decode superstep, part a)
# ---------------------------------------------------------------------------
#
# At T=1 the standalone Q80 quantize is one whole extra program per matmul
# (dispatch overhead ≈ the quantize's own arithmetic), and XLA cannot fuse
# across the pallas_call boundary. Folding the rmsnorm AND the quantize
# into the same jitted program as the kernel launch deletes that boundary:
# rmsnorm → cast → pad → quantize → kernel is ONE program, with the
# quantize fused into the rmsnorm epilogue by XLA (both are elementwise
# over [T, n]).


def rmsnorm_ref(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMS-normalize over the last axis (f32 math, result in x.dtype) —
    THE reference rmsnorm: ``models.llama.rmsnorm`` delegates here and the
    fused entry below inlines these exact ops, so the fused/unfused paths
    are bit-identical by construction (test-enforced in
    tests/test_kernel_parity.py)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (weight.astype(jnp.float32) * (xf * jax.lax.rsqrt(ms + eps))).astype(x.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_d", "interpret", "eps", "role")
)
def _rmsnorm_q40_matmul_int8(
    x: jax.Array,
    weight: jax.Array,
    qm: QuantizedMatrix,
    block_n: int,
    block_d: int,
    interpret: bool,
    eps: float,
    role: str | None = None,
) -> jax.Array:
    # the EXACT unfused op sequence — rmsnorm_ref ops, the bf16 activation
    # cast models.llama._matmul would apply, end-padding, quantize — in one
    # program; any arithmetic drift here breaks the fused-vs-unfused
    # bit-parity gate
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xn = (weight.astype(jnp.float32) * (xf * jax.lax.rsqrt(ms + eps))).astype(x.dtype)
    xb = xn.astype(jnp.bfloat16)
    np_ = qm.n_padded
    if xb.shape[-1] != np_:
        xb = jnp.pad(xb, ((0, 0), (0, np_ - xb.shape[-1])))
    xq, sx = quantize_q80(xb)
    return _int8_core(xq, sx, qm, block_n, block_d, interpret, role)


def rmsnorm_q40_matmul(
    x: jax.Array,
    weight: jax.Array,
    qm: QuantizedMatrix,
    eps: float = 1e-5,
    block_n: int = BLOCK_N,
    block_d: int = BLOCK_D,
    interpret: bool | None = None,
    role: str | None = None,
) -> jax.Array:
    """y = rmsnorm(x, weight) @ dequant(qm) as ONE fused program wherever
    the int8 kernel serves (noted ``mxu_int8_fusedq``); otherwise the
    unfused reference sequence into the XLA fallback. Bit-identical to the
    unfused sequence either way."""
    tiles = _int8_tiles(qm, x.shape[0], block_n, block_d)
    if tiles is None:
        # the standalone rmsnorm is its own program ahead of the matmul's —
        # counted so dllama_kernel_path_total sums to programs-per-step
        # (the fused path absorbs it; docs/OBSERVABILITY.md)
        _note_path("rmsnorm", "xla_standalone")
        xb = rmsnorm_ref(x, weight, eps).astype(jnp.bfloat16)
        return q40_matmul(xb, qm, block_n, block_d, interpret, role)
    if interpret is None:
        interpret = _interpret_default()
    _note_path("q40_matmul", "mxu_int8_fusedq")
    return _rmsnorm_q40_matmul_int8(x, weight, qm, *tiles, interpret, eps, role)


def _largest_divisor_tile(dim: int, target: int, granule: int) -> int | None:
    """Largest multiple of ``granule`` that divides ``dim`` and is ≤ target."""
    if dim % granule:
        return None
    best = None
    for k in range(1, target // granule + 1):
        b = k * granule
        if dim % b == 0:
            best = b
    return best


@jax.jit
def _q40_matmul_fallback_jit(x: jax.Array, qm: QuantizedMatrix) -> jax.Array:
    return _q40_matmul_fallback(x, qm)


def _q40_matmul_fallback(x: jax.Array, qm: QuantizedMatrix) -> jax.Array:
    np_, dp = qm.n_padded, qm.d_padded
    lo = (qm.qs & 0xF).astype(jnp.int8) - 8
    hi = (qm.qs >> 4).astype(jnp.int8) - 8
    # half-split: low nibbles are rows [0, half), high [half, n_pad)
    w_int = jnp.concatenate([lo, hi], axis=-2)
    w = w_int.astype(jnp.float32).reshape(-1, QK, dp) * qm.scales[..., None, :]
    w = w.reshape(np_, dp)
    if x.shape[-1] != np_:
        x = jnp.pad(x, ((0, 0), (0, np_ - x.shape[-1])))
    out = jax.lax.dot_general(
        x.astype(jnp.float32),
        w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    return out[:, : qm.d] if dp != qm.d else out


def _least_width_of_no_more_tiles(d: int) -> int:
    """:func:`_d_padded` for a width over 1024 (down here so that every kernel
    body above keeps its line: a program's text carries them). The next
    multiple of 1024 always tiles; the answer is the LEAST multiple of 128
    from ``d`` up that needs no more output tiles than that one does at any
    row class served with a tile of 1024 or more (``_BLOCK_D_BY_ROWS``), so a
    launch reads fewer zero-scale columns and takes no more grid steps: 1536
    stays 1536 (one tile, or two of 768), 1344 takes 1536 (1408 is eleven
    tiles of 128), 2560 keeps 3072 (a fourth tile of 640 at 256 rows)."""
    caps = [cap for _, cap in _BLOCK_D_BY_ROWS if cap >= 1024]

    def tiles(width: int) -> list[int]:
        return [width // _largest_divisor_tile(width, cap, 128) for cap in caps]

    top = -(-d // 1024) * 1024
    most = tiles(top)
    for width in range(-(-d // 128) * 128, top, 128):
        if all(a <= b for a, b in zip(tiles(width), most)):
            return width
    return top
