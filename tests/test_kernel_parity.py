"""Interpret-mode parity gates for the Pallas kernels (ISSUE 14 + the
ISSUE 17 decode-megakernel fusions).

All runnable on the CPU test substrate (conftest pins JAX_PLATFORMS=cpu +
an 8-device virtual mesh):

* int8 MXU Q40×Q80 matmul: tolerance vs the dequantize-then-matmul
  reference (the int8 path adds ONLY the Q80 activation rounding, ~0.5% —
  far under Q40's own ~3% noise), plus path-dispatch/telemetry checks.
* fused rmsnorm→Q80 epilogue (``rmsnorm_q40_matmul``): BIT-parity vs the
  standalone rmsnorm + int8 matmul it replaces — the fused program inlines
  the identical op sequence, so any drift is a bug, not tolerance.
* the paged segmented scan: the spec-hit == plain-decode transitivity
  across bf16/f32/i8 and bucket shapes, and what the dispatch counts.
* the row-bounded decode scan (``ops/decode_attention.py``): the kernel
  against the XLA scan it stands in for, causal and EVA tables, ragged
  rows, NaN past every row's own bound, what it counts.
* ring all-reduce + the matmul_all_reduce seam: the ring schedule
  (ppermute realization — remote DMA has no interpret mode) vs psum
  under the CPU mesh mocks. The fused matmul+ring kernel is TPU-compiled
  only (tests/test_chip_compile.py compiles it for a described v5e).
"""


import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.ops import attention as att
from distributed_llama_tpu.ops import decode_attention
from distributed_llama_tpu.ops import kv_cache as kvc
from distributed_llama_tpu.ops.q40 import (
    QuantizedMatrix,
    _d_padded,
    _n_padded,
    dequantize_tpu,
    q40_grouped_matmul,
    q40_matmul,
    quantize_q40_tpu,
    quantize_q80,
    rmsnorm_q40_matmul,
    rmsnorm_ref,
    stack_bank,
)


class TestInt8Matmul:
    def _qm(self, n=1024, d=256, seed=2):
        rng = np.random.RandomState(seed)
        w = rng.randn(n, d).astype(np.float32) / np.sqrt(n)
        return quantize_q40_tpu(w), rng

    @pytest.mark.parametrize("T", [1, 8])
    def test_int8_matches_dequant(self, T):
        qm, rng = self._qm()
        x = jnp.asarray(rng.randn(T, qm.n).astype(np.float32))
        want = np.asarray(x @ jnp.asarray(dequantize_tpu(qm)))
        i8 = np.asarray(q40_matmul(x, qm, interpret=True))
        scale = np.abs(want).max()
        # int8 adds only the Q80 activation rounding (~0.5% per element)
        np.testing.assert_allclose(i8 / scale, want / scale, atol=2e-2)

    def test_q80_block_quantization_contract(self):
        """Standard-only Q80: per-32-block int8 values + f32 scales with
        scale = max|block|/127 (floored) — the layout the int8 kernel's
        scale-product epilogue and the fused ring kernel both assume."""
        rng = np.random.RandomState(7)
        x = rng.randn(3, 1024).astype(np.float32)
        xq, sx = quantize_q80(jnp.asarray(x))
        assert xq.dtype == jnp.int8 and sx.dtype == jnp.float32
        blocks = x.reshape(3, -1, 32)
        want_s = np.maximum(np.abs(blocks).max(-1) / 127.0, 1e-8)
        np.testing.assert_allclose(np.asarray(sx), want_s, rtol=1e-6)
        deq = np.asarray(xq).reshape(3, -1, 32) * np.asarray(sx)[..., None]
        np.testing.assert_allclose(deq.reshape(3, -1), x, atol=np.abs(x).max() / 120)

    def test_dispatch_fallback_small_shapes(self):
        """Matrices too small to tile take the XLA fallback."""
        rng = np.random.RandomState(3)
        w = rng.randn(64, 96).astype(np.float32)
        qm = quantize_q40_tpu(w)
        x = jnp.asarray(rng.randn(2, 64).astype(np.float32))
        want = x @ jnp.asarray(dequantize_tpu(qm))
        got = q40_matmul(x, qm)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_kernel_path_counter(self):
        """Every dispatch decision lands in dllama_kernel_path_total — the
        silent-fallback witness (TEL-001's table row in OBSERVABILITY.md)."""
        from distributed_llama_tpu import telemetry

        telemetry.enable()
        try:
            telemetry.reset()
            qm, rng = self._qm(n=1024, d=256, seed=9)
            x = jnp.asarray(rng.randn(1, qm.n).astype(np.float32))
            q40_matmul(x, qm, interpret=True)
            small = quantize_q40_tpu(rng.randn(64, 96).astype(np.float32))
            q40_matmul(jnp.asarray(rng.randn(1, 64).astype(np.float32)), small)
            ctr = telemetry.REGISTRY.counter(
                "dllama_kernel_path_total", labelnames=("kernel", "path")
            )
            for path in ("mxu_int8", "xla_fallback"):
                assert ctr.labels(kernel="q40_matmul", path=path).value >= 1, path
        finally:
            telemetry.reset()
            telemetry.disable()


class TestFusedRmsnormQuantize:
    """Tentpole (a) of the decode megakernel: the rmsnorm→Q80→int8-matmul
    fusion deletes one program per matmul at T=1 and must be BIT-identical
    to the standalone chain — the fused program inlines the exact op
    sequence (rmsnorm f32 math, the caller's bf16 cast, pad, quantize_q80,
    the shared _int8_core), so equality is by construction, not
    tolerance."""

    def _case(self, T, n, d, xdt, seed=3):
        rng = np.random.RandomState(seed)
        qm = quantize_q40_tpu(rng.randn(n, d).astype(np.float32) / np.sqrt(n))
        x = jnp.asarray(rng.randn(T, n).astype(np.float32)).astype(xdt)
        wgt = jnp.asarray(rng.rand(n).astype(np.float32) + 0.5)
        return x, wgt, qm

    @pytest.mark.parametrize("xdt", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("T,n,d", [(1, 1024, 256), (8, 512, 128)])
    def test_bit_parity_vs_standalone(self, xdt, T, n, d):
        x, wgt, qm = self._case(T, n, d, xdt)
        fused = rmsnorm_q40_matmul(x, wgt, qm, interpret=True)
        unfused = q40_matmul(
            rmsnorm_ref(x, wgt).astype(jnp.bfloat16), qm, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))

    def test_untiled_shapes_fall_back(self):
        """Shapes the int8 kernel can't tile take the standalone chain —
        dispatch owns eligibility, exactly like q40_matmul's fallback
        contract."""
        rng = np.random.RandomState(5)
        qm = quantize_q40_tpu(rng.randn(64, 96).astype(np.float32))
        x = jnp.asarray(rng.randn(2, 64).astype(np.float32))
        wgt = jnp.asarray(rng.rand(64).astype(np.float32) + 0.5)
        want = q40_matmul(rmsnorm_ref(x, wgt).astype(jnp.bfloat16), qm)
        got = rmsnorm_q40_matmul(x, wgt, qm)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_kernel_path_counter_fusedq(self):
        from distributed_llama_tpu import telemetry

        telemetry.enable()
        try:
            telemetry.reset()
            x, wgt, qm = self._case(1, 1024, 256, jnp.float32)
            rmsnorm_q40_matmul(x, wgt, qm, interpret=True)
            ctr = telemetry.REGISTRY.counter(
                "dllama_kernel_path_total", labelnames=("kernel", "path")
            )
            assert ctr.labels(kernel="q40_matmul", path="mxu_int8_fusedq").value >= 1
        finally:
            telemetry.reset()
            telemetry.disable()


def _parent_nibbles(qs_ref):
    """The unpack as PR 30's kernel bodies wrote it, on the same tile: every
    packed byte widened to an int32 of its own, masked and shifted there,
    narrowed back to int8."""
    qs = qs_ref[:].astype(jnp.int32)
    return (qs & 0xF).astype(jnp.int8), (qs >> 4).astype(jnp.int8)


# the output tile PR 30's table (``_shrink_block_d`` and the VMEM fit) gave a row count
PARENT_BLOCK_D = {1: 2048, 16: 512, 32: 1024, 256: 512}


class TestPackedUnpackBitParity:
    """PR 31 unpacks the nibbles on packed 32-bit words (``q40._nibbles``) and
    takes the output tile from its own table. Integers in, the same integers
    out, and the output tile is not in the contraction: every launch is
    bit-equal to the parent's, body and tile, at every row count a cell
    dispatches."""

    # an eighth of Mixtral's expert gate|up (4096 -> 28672; its 3584 columns
    # are their own pack since PR 51, tiled by 3584, 1792 and 896) and Solar's
    # held gate|up (4096 -> 2560, whose padded 3072 columns tile by 1536) over
    # a quarter of the hidden size: two input windows, several output tiles
    @pytest.mark.parametrize("T", sorted(PARENT_BLOCK_D))
    @pytest.mark.parametrize("n,d", [(1024, 3584), (1024, 2560)], ids=["mixtral", "solar"])
    @pytest.mark.parametrize("body", ["dense", "grouped"])
    def test_bit_equal_to_the_parents_kernel(self, monkeypatch, body, n, d, T):
        from distributed_llama_tpu.ops import q40

        rng = np.random.RandomState(T + d)
        x = jnp.asarray(rng.randn(T, n).astype(np.float32)).astype(jnp.bfloat16)
        packs = [quantize_q40_tpu(rng.randn(n, d).astype(np.float32) / np.sqrt(n))
                 for _ in range(1 if body == "dense" else 3)]
        bn, _ = q40._int8_tiles(packs[0], T, q40.BLOCK_N, q40.BLOCK_D)
        assert bn == q40.BLOCK_N
        parent_tiles = (bn, q40._largest_divisor_tile(packs[0].d_padded, PARENT_BLOCK_D[T], 128))
        if body == "dense":
            args = (x, packs[0])
            entry = q40_matmul
            parent = lambda x, qm: q40._q40_matmul_int8.__wrapped__(x, qm, *parent_tiles, True)
        else:
            args = (x.astype(jnp.float32), stack_bank(packs), jnp.asarray([True, False, True]))
            entry = q40_grouped_matmul
            # reads the patched ``_int8_tiles``
            parent = functools.partial(q40_grouped_matmul.__wrapped__, interpret=True)
        got = entry(*args, interpret=True)
        # the parent: its unpack and its tile, the jitted entries' own bodies
        # under a new jit, so that no cached program of this tree's is reused
        monkeypatch.setattr(q40, "_nibbles", _parent_nibbles)
        monkeypatch.setattr(q40, "_int8_tiles", lambda *a: parent_tiles)
        want = jax.jit(parent)(*args)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("rows,cols", [(512, 2048), (512, 1536), (256, 128)])
    def test_the_unpack_returns_the_parents_integers(self, rows, cols):
        """The helper alone, through a launch in interpret mode on tiles of the
        served shapes, over every byte value."""
        from jax.experimental import pallas as pl

        from distributed_llama_tpu.ops import q40

        qs = jnp.asarray(np.random.RandomState(cols).randint(0, 256, (rows, cols)).astype(np.uint8))

        def run(unpack):
            def kernel(qs_ref, lo_ref, hi_ref):
                lo_ref[:], hi_ref[:] = unpack(qs_ref)
            shape = jax.ShapeDtypeStruct(qs.shape, jnp.int8)
            return pl.pallas_call(kernel, out_shape=(shape, shape), interpret=True)(qs)

        for got, want in zip(run(q40._nibbles), run(_parent_nibbles)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert set(np.unique(np.asarray(qs))) == set(range(256))


def _repadded(qm: QuantizedMatrix, columns: int) -> QuantizedMatrix:
    """The same matrix (or bank) with its pack padded out to ``columns``
    zero-scale columns: the pack as the rule before PR 51 stored it."""
    pad = ((0, 0),) * (qm.qs.ndim - 1) + ((0, columns - qm.d_padded),)
    return QuantizedMatrix(np.pad(np.asarray(qm.qs), pad), np.pad(np.asarray(qm.scales), pad), qm.n, qm.d)


class TestColumnPaddingBitParity:
    """PR 51 pads a pack's columns to what its tiles need (``q40._d_padded``:
    1536 stays 1536, 1344 takes 1536) where every width over 1024 took the
    next multiple of 1024. Output columns are independent in both kernels
    (exact int8 dots, f32 sums over input blocks and input tiles, never over
    columns) and the input tile does not move, so every real column of every
    launch carries the bits it carried under 2048 columns, whatever tile the
    narrower pack takes (one of 1536, or two of 768 at 128 and 256 rows)."""

    N = 2048  # GLM's depth: two input tiles, so the sum over input tiles is in the comparison

    def _packs(self, d, count):
        rng = np.random.RandomState(d + count)
        packs = [quantize_q40_tpu(rng.randn(self.N, d).astype(np.float32) / np.sqrt(self.N))
                 for _ in range(count)]
        assert {p.d_padded for p in packs} == {1536} and {p.n_padded for p in packs} == {self.N}
        return packs, rng

    def _close_to_dequant(self, got, x, pack):
        want = np.asarray(x, np.float32) @ dequantize_tpu(pack)
        scale = np.abs(want).max()
        np.testing.assert_allclose(np.asarray(got) / scale, want / scale, atol=2e-2)

    @pytest.mark.parametrize("T", [1, 32, 256])
    @pytest.mark.parametrize("d", [1536, 1344])
    def test_the_dense_launch_gives_the_bits_of_2048_columns(self, d, T):
        """The dense entry takes the nibbles' +8 bias off AFTER the launch, by
        an XLA dot over the pack's scales (``_int8_core``), and that dot's
        last bit is its backend's: the CPU's picks its blocking by the
        matrix's width between 8 and 32 rows (the chip's is held to the bit
        by tools/q40_pad_parity.py). So the LAUNCH is held to the bit on rows
        whose Q80 blocks sum to zero (the second half of each block the
        first's negative: the correction is then exactly zero and the entry's
        output is the launch's), and the entry on any rows to a float32
        rounding of the correction."""
        (pack,), rng = self._packs(d, 1)
        before = _repadded(pack, 2048)
        half = rng.randn(T, self.N // 32, 16).astype(np.float32)
        zero_sum = jnp.asarray(np.concatenate([half, -half], axis=-1).reshape(T, self.N)).astype(jnp.bfloat16)
        xq, _ = quantize_q80(zero_sum)
        assert not np.asarray(xq, np.int32).reshape(T, -1, 32).sum(-1).any()
        got = q40_matmul(zero_sum, pack, interpret=True)
        assert got.shape == (T, d)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(q40_matmul(zero_sum, before, interpret=True)))
        self._close_to_dequant(got, zero_sum, pack)
        x = jnp.asarray(rng.randn(T, self.N).astype(np.float32)).astype(jnp.bfloat16)
        got = np.asarray(q40_matmul(x, pack, interpret=True))
        np.testing.assert_allclose(got, np.asarray(q40_matmul(x, before, interpret=True)),
                                   rtol=0, atol=1e-5 * np.abs(got).max())
        self._close_to_dequant(got, x, pack)

    @pytest.mark.parametrize("T", [32, 128])
    @pytest.mark.parametrize("rows", ["shared", "per_expert"])
    @pytest.mark.parametrize("d", [1536, 1344])
    def test_the_grouped_launch_gives_the_bits_of_2048_columns(self, d, rows, T):
        packs, rng = self._packs(d, 3)
        bank, on = stack_bank(packs), jnp.asarray([T, 0, T])  # the rows that chose each expert
        x = jnp.asarray(rng.randn(*((T,) if rows == "shared" else (3, T)), self.N).astype(np.float32))
        got = q40_grouped_matmul(x, bank, on, interpret=True)
        before = q40_grouped_matmul(x, _repadded(bank, 2048), on, interpret=True)
        assert got.shape == (3, T, 1536) and before.shape == (3, T, 2048)
        np.testing.assert_array_equal(np.asarray(got)[..., :d], np.asarray(before)[..., :d])
        # the padding's columns and the expert no row chose: exact zeros
        assert not np.asarray(got)[..., d:].any() and not np.asarray(got)[1].any()
        for e in (0, 2):
            self._close_to_dequant(got[e, :, :d], x if rows == "shared" else x[e], packs[e])


def _noted(fn, *args) -> dict[str, int]:
    """What tracing ``fn(*args)`` once notes in ``dllama_kernel_path_total``,
    as ``{"kernel/path": count}``. ``jax.eval_shape``: the arguments are
    shapes and no kernel runs."""
    from distributed_llama_tpu import telemetry

    telemetry.enable()
    try:
        telemetry.reset()
        jax.eval_shape(fn, *args)
        series = telemetry.REGISTRY.snapshot().get("dllama_kernel_path_total", {"series": []})["series"]
        return {f"{s['labels']['kernel']}/{s['labels']['path']}": int(s["value"]) for s in series}
    finally:
        telemetry.reset()
        telemetry.disable()


def _qm_shape(n: int, d: int, experts: int = 0) -> QuantizedMatrix:
    lead = (experts,) if experts else ()
    np_, dp = _n_padded(n), _d_padded(d)
    return QuantizedMatrix(
        jax.ShapeDtypeStruct(lead + (np_ // 2, dp), jnp.uint8),
        jax.ShapeDtypeStruct(lead + (np_ // 32, dp), jnp.float32), n, d,
    )


def _x_shape(T: int, n: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((T, n), jnp.bfloat16)


# (n, d) of every Q40 matrix the benchmark's configurations multiply by, at
# their published widths (benchmark/configs/*.json). Mistral-7B and
# Mixtral-8x7B share attention and FFN widths (an expert IS the FFN).
SERVED_MATRICES = {
    "mistral7b_mixtral8x7b": dict(
        wqkv=(4096, 6144), wo=(4096, 4096), gate_up=(4096, 28672),
        down=(14336, 4096), logits=(4096, 32000),
    ),
    "solar_open2": dict(
        qkvg=(4096, 18432), wo=(8192, 4096), lin_in=(4096, 24896),
        shared_gate_up=(4096, 2560), shared_down=(1280, 4096), logits=(4096, 24576),
    ),
}
# Solar-Open2's held experts: one bank of 20 a layer, through the grouped launch
SOLAR_BANKS = dict(held_gate_up=(4096, 2560), held_down=(1280, 4096))


class TestDispatchTable:
    """The kernel layer's dispatch is a function of shape: every matrix of
    the served models takes the int8 kernel at every row count a cell
    dispatches (decode buckets and the 256-row prefill chunk), a row count
    past the kernel's VMEM fit takes the XLA fallback, and no environment
    variable can say otherwise."""

    @pytest.mark.parametrize("T", [1, 16, 32, 64, 256, 2049])
    @pytest.mark.parametrize("family", sorted(SERVED_MATRICES))
    def test_served_widths_by_row_count(self, family, T):
        kernel = T <= 2048
        for role, (n, d) in SERVED_MATRICES[family].items():
            qm, x = _qm_shape(n, d), _x_shape(T, n)
            assert _noted(lambda x, qm: q40_matmul(x, qm, role=role), x, qm) == {
                "q40_matmul/" + ("mxu_int8" if kernel else "xla_fallback"): 1}, (role, T)
            w = jax.ShapeDtypeStruct((n,), jnp.float32)
            want = ({"q40_matmul/mxu_int8_fusedq": 1} if kernel else
                    {"rmsnorm/xla_standalone": 1, "q40_matmul/xla_fallback": 1})
            assert _noted(
                lambda x, w, qm: rmsnorm_q40_matmul(x, w, qm, role=role), x, w, qm
            ) == want, (role, T)
        if family == "solar_open2":
            on = jax.ShapeDtypeStruct((20,), jnp.bool_)
            for role, (n, d) in SOLAR_BANKS.items():
                assert _noted(
                    lambda x, bank, on: q40_grouped_matmul(x, bank, on, role=role),
                    jax.ShapeDtypeStruct((T, n), jnp.float32), _qm_shape(n, d, experts=20), on,
                ) == {"q40_grouped_matmul/" + ("mxu_int8" if kernel else "xla_fallback"): 1}, (role, T)

    def test_one_decode_layer_notes_four_matmuls_and_the_paged_scan(self):
        """One decode layer at the 7B shape (norm+qkv, paged attention, wo,
        norm+gate_up, down): the fused entry serves both normed matmuls, so
        no standalone rmsnorm program is noted."""
        H, K, M, hd, B, S, chunk, page = 4096, 8, 4, 128, 1, 2048, 512, 64
        f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
        qm, x, wgt = _qm_shape(H, H), _x_shape(1, H), f32(H)
        kv, pool = f32(B, S, K, hd), f32(8, page, K, hd)
        ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)

        def layer(x, wgt, qm, qg, keys, values, pos, pool_k, pool_v, tables, matched):
            return (
                rmsnorm_q40_matmul(x, wgt, qm, role="wqkv"),
                att.batched_decode_attention(
                    qg, (keys, values), pos, chunk, paged=(pool_k, pool_v, tables, matched)),
                q40_matmul(x, qm, role="wo"),
                rmsnorm_q40_matmul(x, wgt, qm, role="gate_up"),
                q40_matmul(x, qm, role="down"),
            )

        assert _noted(
            layer, x, wgt, qm, f32(B, K, M, hd), kv, kv, ints(B), pool, pool,
            ints(B, S // page), ints(B),
        ) == {
            "q40_matmul/mxu_int8_fusedq": 2, "q40_matmul/mxu_int8": 2,
            "paged_attention/xla_segmented": 1, "decode_attention/xla_scan": 1,
        }

    def test_ops_reads_one_environment_variable(self):
        """``DLT_ALLREDUCE`` (the all-reduce arm, until a 4-chip cell
        decides it) is the kernel layer's only switch."""
        import pathlib

        import distributed_llama_tpu.ops as ops

        found = set()
        for path in pathlib.Path(ops.__file__).parent.glob("*.py"):
            found |= set(re.findall(r"\b(?:DLT|DLLAMA)_[A-Z0-9_]+", path.read_text()))
        assert found == {"DLT_ALLREDUCE"}


def _mk_half(rng, shape, dtype):
    a = rng.randn(*shape).astype(np.float32)
    if dtype == "i8":
        q, s = kvc.quantize_rows(jnp.asarray(a).reshape(-1, *shape[-2:]))
        return kvc.QuantizedKV(
            q.reshape(shape), s.reshape(shape[:-1] + (1,))
        )
    return jnp.asarray(a).astype(dtype)


class TestPagedScan:
    """The segmented paged scan (the one paged-attention path): its
    EXACT-EMPTY-PARTIAL merge semantics across query widths, and what the
    dispatch counts."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, "i8"])
    @pytest.mark.parametrize("B,S,chunk,page", [(3, 64, 16, 8), (2, 96, 24, 8)])
    def test_verify_decode_transitivity(self, dtype, B, S, chunk, page):
        """Spec-hit == plain-decode: query t of a verify window at position
        pos+t is the same MATH as a plain decode at that position, so the
        two agree within a few f32 roundings. Bit equality ACROSS query
        widths is not XLA's to promise and the claim is withdrawn
        (ops/attention.py): it emits one dot and one loop body per width T
        and rounds them differently — 1 ulp measured on the XLA of jax 0.9,
        and at chunk=24 even op-by-op dispatch differs."""
        rng = np.random.RandomState(4)
        K, M, hd, P_, T = 2, 2, 8, 16, 3
        qg = jnp.asarray(rng.randn(B, T, K, M, hd).astype(np.float32))
        keys = _mk_half(rng, (B, S, K, hd), dtype)
        values = _mk_half(rng, (B, S, K, hd), dtype)
        pool_k = _mk_half(rng, (P_, page, K, hd), dtype)
        pool_v = _mk_half(rng, (P_, page, K, hd), dtype)
        tables = jnp.asarray(rng.randint(0, P_, (B, S // page)).astype(np.int32))
        matched = jnp.asarray(
            rng.randint(0, S // page + 1, B).astype(np.int32) * page
        )
        # verify windows sit at pos >= matched (the spec-decode invariant)
        pos = jnp.maximum(
            matched, jnp.asarray(rng.randint(0, S - T, B), jnp.int32)
        )
        paged = (pool_k, pool_v, tables, matched)
        ref = att.batched_verify_attention(qg, (keys, values), pos, chunk, paged=paged)
        t = 1
        dec = att.batched_decode_attention(
            qg[:, t], (keys, values), pos + t, chunk, paged=paged
        )
        # a handful of roundings per merge, at most S/chunk merges
        atol = 8 * np.finfo(np.float32).eps * float(jnp.max(jnp.abs(dec)))
        np.testing.assert_allclose(np.asarray(ref[:, t]), np.asarray(dec), rtol=0, atol=atol)

    @pytest.mark.parametrize("T", [None, 2], ids=["decode", "verify"])
    def test_paged_dispatch_counts_the_segmented_scan(self, T):
        from distributed_llama_tpu import telemetry

        telemetry.enable()
        try:
            telemetry.reset()
            rng = np.random.RandomState(1)
            B, S, K, M, hd, chunk, page, P_ = 2, 32, 2, 1, 8, 8, 4, 8
            lead = (B,) if T is None else (B, T)
            qg = jnp.asarray(rng.randn(*lead, K, M, hd).astype(np.float32))
            keys = _mk_half(rng, (B, S, K, hd), jnp.float32)
            values = _mk_half(rng, (B, S, K, hd), jnp.float32)
            paged = (
                _mk_half(rng, (P_, page, K, hd), jnp.float32),
                _mk_half(rng, (P_, page, K, hd), jnp.float32),
                jnp.zeros((B, S // page), jnp.int32),
                jnp.asarray([8, 0], jnp.int32),
            )
            pos = jnp.asarray([20, 5], jnp.int32)
            attend = att.batched_decode_attention if T is None else att.batched_verify_attention
            attend(qg, (keys, values), pos, chunk, paged=paged)
            ctr = telemetry.REGISTRY.counter(
                "dllama_kernel_path_total", labelnames=("kernel", "path")
            )
            assert ctr.labels(kernel="paged_attention", path="xla_segmented").value == 1
            # the plain slab scan is not a paged dispatch
            attend(qg, (keys, values), pos, chunk)
            assert ctr.labels(kernel="paged_attention", path="xla_segmented").value == 1
        finally:
            telemetry.reset()
            telemetry.disable()

    def test_non_paged_path_untouched(self):
        """paged=None is the plain slab scan (the cold path the parity
        suites pin separately)."""
        rng = np.random.RandomState(2)
        B, S, K, M, hd, chunk = 2, 32, 2, 1, 8, 8
        qg = jnp.asarray(rng.randn(B, K, M, hd).astype(np.float32))
        keys = _mk_half(rng, (B, S, K, hd), jnp.float32)
        values = _mk_half(rng, (B, S, K, hd), jnp.float32)
        pos = jnp.asarray([20, 5], jnp.int32)
        out = att.batched_decode_attention(qg, (keys, values), pos, chunk)
        assert out.shape == (B, K, M, hd)


# the slabs of the row-bounded scan's tests: chunks of 32 slots, heads of 128
# (the kernel takes whole lane rows; toy heads keep the XLA scan)
ROW_CHUNK, ROW_HD = 32, 128
EVA_W, EVA_C, EVA_SUMMARIES = 64, 4, 32  # a window of two chunks, one chunk of summaries


def _row_inputs(dtype, B, B_max, slots, K, M, seed=0):
    """``(qg [B, K, M, hd] f32, leaf [2, B_max, slots, K, hd])``. A bf16 case
    draws small whole numbers: every product and every sum of a score is then
    exact in f32 whatever the order of summation, so both scans round the
    same weights to bf16 (a score that differs in its last bit can flip that
    rounding, 2**-8 of a weight: not a fault of either scan, and far over the
    f32 tolerance); a f32 case draws normals."""
    rng = np.random.RandomState(seed)
    if dtype == jnp.bfloat16:
        draw = lambda *shape: rng.randint(-2, 3, shape).astype(np.float32)  # noqa: E731
    else:
        draw = lambda *shape: rng.randn(*shape).astype(np.float32)  # noqa: E731
    return jnp.asarray(draw(B, K, M, ROW_HD)), jnp.asarray(draw(2, B_max, slots, K, ROW_HD)).astype(dtype)


def _attend_rows(kind, qg, leaf, pos, chunk=ROW_CHUNK):
    if kind == "eva":
        return att.eva_batched_decode_attention(qg, leaf, pos, EVA_W, EVA_C, chunk)
    return att.batched_decode_attention(qg, leaf, pos, chunk)


def _xla_scan(kind, qg, leaf, pos, chunk=ROW_CHUNK):
    """The scan the kernel stands in for, on the same inputs: what the two
    callers run when the kernel does not take the leaf (for causal tables
    :func:`_segmented_batched_scan`, for EVA the caller's own loop)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decode_attention, "supports", lambda *a: False)
        return _attend_rows(kind, qg, leaf, pos, chunk)


def _assert_close_as_verify_and_decode(got, want):
    atol = 8 * np.finfo(np.float32).eps * float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=atol)


def _past_own_bound(kind, pos, B_max, slots):
    """bool [B_max, slots]: the slots row ``b``'s query does not see, and
    every slot of a row past the bucket."""
    B = len(pos)
    pos = np.concatenate([np.asarray(pos), np.full(B_max - B, -1)])[:, None]
    g = np.arange(slots)[None, :]
    if kind == "eva":
        seen = np.where(g < EVA_W, g <= pos % EVA_W, g - EVA_W < (EVA_W // EVA_C) * (pos // EVA_W))
    else:
        seen = g <= pos
    return ~seen | (np.arange(B_max)[:, None] >= B)


# per kind: a full bucket (a row at 0, at a chunk's last slot, at the slab's
# end, at a chunk's first slot) and a bucket below B_max
ROW_POSITIONS = {
    "causal": {"full": [0, 31, 127, 32], "below": [64, 0, 127]},
    "eva": {"full": [0, 63, 191, 64], "below": [130, 0, 95]},
}
ROW_SLOTS = {"causal": 128, "eva": EVA_W + EVA_SUMMARIES}


class TestRowBoundedDecodeScan:
    """``decode_attention.slab_decode_scan`` (interpret mode) behind the
    causal and the EVA decode attention: the XLA scan's result to the
    tolerance verify and decode are held to, from each row's OWN chunks."""

    @pytest.mark.parametrize("bucket", ["full", "below"])
    @pytest.mark.parametrize("kind", ["causal", "eva"])
    @pytest.mark.parametrize("K,M", [(8, 4), (32, 1)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    def test_kernel_equals_the_xla_scan(self, dtype, K, M, kind, bucket):
        pos = ROW_POSITIONS[kind][bucket]
        qg, leaf = _row_inputs(dtype, len(pos), 4, ROW_SLOTS[kind], K, M)
        pos = jnp.asarray(pos, jnp.int32)
        assert decode_attention.supports(leaf, ROW_CHUNK)
        _assert_close_as_verify_and_decode(
            _attend_rows(kind, qg, leaf, pos), _xla_scan(kind, qg, leaf, pos)
        )

    @pytest.mark.parametrize("kind", ["causal", "eva"])
    def test_a_chunk_of_several_score_blocks(self, kind):
        """32 kv heads over a chunk of 64 slots: the flattened chunk is two
        blocks of ``SUB_ROWS`` rows, scored and mixed one after the other."""
        chunk, slots = 64, {"causal": 256, "eva": EVA_W + 64}[kind]
        assert chunk * 32 == 2 * decode_attention.SUB_ROWS
        pos = jnp.asarray({"causal": [200, 0, 63], "eva": [130, 0, 64]}[kind], jnp.int32)
        qg, leaf = _row_inputs(jnp.float32, 3, 3, slots, 32, 1, seed=3)
        _assert_close_as_verify_and_decode(
            _attend_rows(kind, qg, leaf, pos, chunk), _xla_scan(kind, qg, leaf, pos, chunk)
        )

    @pytest.mark.parametrize("kind", ["causal", "eva"])
    @pytest.mark.parametrize("K,M,chunk", [(12, 2, 32), (40, 1, 16)])
    def test_kv_heads_that_do_not_divide_a_score_block_in_one_block(self, K, M, chunk, kind):
        """12 or 40 kv heads (Llama-2-13B's) over a chunk that is ONE score
        block: the block starts at kv head 0 and the kernel takes it, with
        the query heads padded to whole tiles (24 -> 32, 40 -> 48)."""
        slots = {"causal": 128, "eva": EVA_W + EVA_SUMMARIES}[kind]
        pos = jnp.asarray({"causal": [100, 0, chunk - 1], "eva": [191, 0, 64]}[kind], jnp.int32)
        qg, leaf = _row_inputs(jnp.float32, 3, 3, slots, K, M, seed=4)
        assert decode_attention.supports(leaf, chunk) and chunk * K <= decode_attention.SUB_ROWS
        _assert_close_as_verify_and_decode(
            _attend_rows(kind, qg, leaf, pos, chunk), _xla_scan(kind, qg, leaf, pos, chunk)
        )

    @pytest.mark.parametrize("kind", ["causal", "eva"])
    @pytest.mark.parametrize("K,chunk", [(12, 256), (40, 128), (10, 512)])
    def test_kv_heads_that_do_not_divide_a_score_block_keep_the_xla_scan(self, K, chunk, kind):
        """The kernel adds ONE block's kv-head bias to every score block of a
        chunk, which is right only where a block starts at kv head 0
        (``SUB_ROWS % K == 0``). 10, 12 or 40 heads over several blocks tile
        the chunk but not a block: ``supports`` says no, the caller keeps the
        XLA scan (counted as such) and attends over each head's own columns
        (a dense softmax over the visible slots says so)."""
        from distributed_llama_tpu import telemetry

        rows = chunk * K
        assert rows > decode_attention.SUB_ROWS and rows % decode_attention.SUB_ROWS == 0
        assert decode_attention.SUB_ROWS % K
        slots = {"causal": 2 * chunk, "eva": 2 * chunk}[kind]
        W = chunk  # EVA: a window of one chunk, then a chunk of summaries
        pos = [chunk + 5, 0] if kind == "causal" else [W + 5, 0]
        qg, leaf = _row_inputs(jnp.float32, 2, 2, slots, K, 1, seed=5)
        assert not decode_attention.supports(leaf, chunk)
        telemetry.enable()
        try:
            telemetry.reset()
            ctr = telemetry.REGISTRY.counter("dllama_kernel_path_total", labelnames=("kernel", "path"))
            if kind == "eva":
                got = att.eva_batched_decode_attention(qg, leaf, jnp.asarray(pos, jnp.int32), W, EVA_C, chunk)
            else:
                got = att.batched_decode_attention(qg, leaf, jnp.asarray(pos, jnp.int32), chunk)
            assert ctr.labels(kernel="decode_attention", path="xla_scan").value == 1
            assert ctr.labels(kernel="decode_attention", path="pallas_rowbound").value == 0
        finally:
            telemetry.reset()
            telemetry.disable()
        g = np.arange(slots)
        for b, p in enumerate(pos):
            if kind == "eva":
                seen = np.where(g < W, g <= p % W, g - W < (W // EVA_C) * (p // W))
            else:
                seen = g <= p
            keys, values = (np.asarray(leaf[h, b], np.float64)[seen] for h in (0, 1))  # [n, K, hd]
            s = np.einsum("kd,nkd->kn", np.asarray(qg[b, :, 0], np.float64), keys) / np.sqrt(ROW_HD)
            w = np.exp(s - s.max(axis=1, keepdims=True))
            want = np.einsum("kn,nkd->kd", w / w.sum(axis=1, keepdims=True), values)
            np.testing.assert_allclose(np.asarray(got[b, :, 0]), want, rtol=0, atol=1e-5)

    @pytest.mark.parametrize("kind", ["causal", "eva"])
    @pytest.mark.parametrize("K,M", [(8, 4), (32, 1)])
    def test_bf16_weights_of_a_real_softmax(self, K, M, kind):
        """Normal draws in a bf16 slab: the scores are no whole numbers, so
        the weights that both scans round to bf16 before the value mix
        (``p.astype(v.dtype)``) are a real softmax's. A score's last bit may
        flip one such rounding (2**-8 of a weight), so the two agree to a
        bf16-sized bound, 2**-8 of the largest output, and no closer; a wrong
        cast or mask in the mix pass is far outside it."""
        pos = ROW_POSITIONS[kind]["full"]
        rng = np.random.RandomState(7)
        qg = jnp.asarray(rng.randn(len(pos), K, M, ROW_HD).astype(np.float32))
        leaf = jnp.asarray(rng.randn(2, 4, ROW_SLOTS[kind], K, ROW_HD).astype(np.float32)).astype(jnp.bfloat16)
        pos = jnp.asarray(pos, jnp.int32)
        got, want = _attend_rows(kind, qg, leaf, pos), _xla_scan(kind, qg, leaf, pos)
        bound = 2.0**-8 * float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=bound)
        # ... and the kernel is no farther from the f32 softmax of the same
        # bf16 slab than the XLA scan is, to that same bound
        exact = _xla_scan(kind, qg, leaf.astype(jnp.float32), pos)
        assert float(jnp.max(jnp.abs(got - exact))) <= float(jnp.max(jnp.abs(want - exact))) + bound

    @pytest.mark.parametrize("kind", ["causal", "eva"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    def test_nan_past_a_rows_own_bound_is_never_read(self, dtype, kind):
        """THE test that the bound is per row: NaN in every slot past each
        row's own bound (a short row's chunks that the bucket's longest row
        visits, the rest of its last chunk) and in every row past the bucket;
        the output is finite and equals the clean slab's, bit for bit."""
        pos = {"causal": [64, 0, 100], "eva": [130, 0, 95]}[kind]
        qg, leaf = _row_inputs(dtype, len(pos), 4, ROW_SLOTS[kind], 8, 4, seed=1)
        poison = _past_own_bound(kind, pos, 4, ROW_SLOTS[kind])[None, :, :, None, None]
        poisoned = jnp.where(poison, jnp.nan, leaf.astype(jnp.float32)).astype(dtype)
        assert bool(jnp.isnan(poisoned).any(axis=(0, 2, 3, 4)).all())  # every row holds some
        pos = jnp.asarray(pos, jnp.int32)
        got = _attend_rows(kind, qg, poisoned, pos)
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_array_equal(np.asarray(got), np.asarray(_attend_rows(kind, qg, leaf, pos)))
        _assert_close_as_verify_and_decode(got, _xla_scan(kind, qg, leaf, pos))

    def test_a_row_of_no_steps_and_an_empty_chunk(self):
        """A row with ``n_steps`` 0 returns zeros and reads nothing; a chunk
        of which the query sees nothing merges as the exact identity."""
        qg, leaf = _row_inputs(jnp.float32, 3, 3, 128, 8, 4, seed=2)
        first = ROW_CHUNK * jnp.arange(4, dtype=jnp.int32)
        starts = jnp.broadcast_to(first, (3, 4))
        visible = jnp.asarray([[32, 32, 7, 0], [32, 32, 32, 32], [32, 0, 0, 0]], jnp.int32)
        n_steps = jnp.asarray([3, 0, 1], jnp.int32)
        got = decode_attention.slab_decode_scan(qg, leaf, starts, visible, n_steps, ROW_CHUNK)
        want = _xla_scan("causal", qg, leaf, jnp.asarray([70, 0, 31], jnp.int32))
        _assert_close_as_verify_and_decode(got[0], want[0])
        _assert_close_as_verify_and_decode(got[2], want[2])
        assert not np.asarray(got[1]).any()
        # an empty chunk in the middle of row 0's walk changes no bit of it
        holed = decode_attention.slab_decode_scan(
            qg, leaf, starts.at[0].set(jnp.asarray([0, 96, 32, 64])),
            visible.at[0].set(jnp.asarray([32, 0, 32, 7])), n_steps.at[0].set(4), ROW_CHUNK,
        )
        np.testing.assert_array_equal(np.asarray(holed[0]), np.asarray(got[0]))

    def test_what_the_kernel_does_not_take_keeps_the_xla_scan(self):
        _, leaf = _row_inputs(jnp.bfloat16, 1, 2, 128, 8, 4)
        assert decode_attention.supports(leaf, ROW_CHUNK)
        assert not decode_attention.supports((leaf[0], leaf[1]), ROW_CHUNK)  # the tp backend's halves
        assert not decode_attention.supports(kvc.init_fused((2, 128, 8, ROW_HD), jnp.int8), ROW_CHUNK)
        assert not decode_attention.supports(leaf[:, :, :100], ROW_CHUNK)  # not whole chunks
        assert not decode_attention.supports(leaf[..., :64], ROW_CHUNK)  # a toy head
        with pytest.raises(ValueError, match="does not take"):
            decode_attention.slab_decode_scan(
                jnp.zeros((1, 8, 4, 64)), leaf[..., :64], jnp.zeros((1, 4), jnp.int32),
                jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32), ROW_CHUNK,
            )

    @pytest.mark.parametrize("slots,kernel", [(128, False), (4096, True)])
    def test_a_bucket_of_one_row_keeps_the_xla_scan_over_a_short_slab(self, monkeypatch, slots, kernel):
        """One row's bound is the bucket's, so the kernel's per-row bound
        saves nothing there, and on the chip the program around it lost more
        than it won (``att.ONE_ROW_LOOP_SLOTS``, the pair in PERF.md): up to
        that many slots a bucket of ONE row takes the XLA scan, past them and
        at two rows the kernel; the answer is the same either way."""
        paths = []
        monkeypatch.setattr(att, "_note_path", lambda kernel, path: paths.append(path))
        assert att.ONE_ROW_LOOP_SLOTS == 2048
        qg, leaf = _row_inputs(jnp.float32, 2, 2, slots, 8, 4, seed=6)
        pos = jnp.asarray([slots - 29, 3], jnp.int32)
        one = att.batched_decode_attention(qg[:1], leaf, pos[:1], ROW_CHUNK)
        two = att.batched_decode_attention(qg, leaf, pos, ROW_CHUNK)
        assert paths == ["pallas_rowbound" if kernel else "xla_scan", "pallas_rowbound"]
        _assert_close_as_verify_and_decode(one[0], two[0])
        _assert_close_as_verify_and_decode(two, _xla_scan("causal", qg, leaf, pos))

    @pytest.mark.parametrize("kind", ["causal", "eva"])
    def test_a_row_is_counted_for_its_own_reads(self, kind):
        """``note_kv_read`` under the kernel: each row's OWN chunks (two rows
        of different length read different amounts, an inactive row, handed
        in at position 0, one chunk); under the XLA scan the bucket's bound."""
        pos = {"causal": [100, 0, 40], "eva": [191, 0, 70]}[kind]
        own = {
            "causal": {"full": [128, 32, 64]},
            "eva": {"eva_window": [64, 32, 32], "eva_summary": [32, 0, 32]},
        }[kind]
        qg, leaf = _row_inputs(jnp.float32, 3, 3, ROW_SLOTS[kind], 8, 4)
        with att.collect_kv_reads() as reads:
            _attend_rows(kind, qg, leaf, jnp.asarray(pos, jnp.int32))
        assert {k: np.asarray(v).tolist() for k, v in reads} == own
        with att.collect_kv_reads() as reads:
            _xla_scan(kind, qg, leaf, jnp.asarray(pos, jnp.int32))
        assert {k: np.asarray(v).tolist() for k, v in reads} == {
            k: [max(v)] * 3 for k, v in own.items()
        }

    def test_each_path_is_counted_once_a_program_build(self):
        from distributed_llama_tpu import telemetry

        qg, leaf = _row_inputs(jnp.float32, 2, 2, 128, 8, 4)
        pos = jnp.asarray([100, 3], jnp.int32)
        telemetry.enable()
        try:
            telemetry.reset()
            ctr = telemetry.REGISTRY.counter("dllama_kernel_path_total", labelnames=("kernel", "path"))
            count = lambda path: ctr.labels(kernel="decode_attention", path=path).value  # noqa: E731
            fused = jax.jit(lambda q, a, p: att.batched_decode_attention(q, a, p, ROW_CHUNK))
            halves = jax.jit(lambda q, k, v, p: att.batched_decode_attention(q, (k, v), p, ROW_CHUNK))
            for _ in range(2):  # the second call builds nothing
                fused(qg, leaf, pos)
                halves(qg, leaf[0], leaf[1], pos)
            assert (count("pallas_rowbound"), count("xla_scan")) == (1, 1)
            jax.jit(lambda q, a, p: _attend_rows("eva", q, a, p))(qg, leaf[:, :, :96], pos)
            assert (count("pallas_rowbound"), count("xla_scan")) == (2, 1)
        finally:
            telemetry.reset()
            telemetry.disable()


class TestRingAllReduce:
    def _mesh(self):
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh

        return Mesh(mesh_utils.create_device_mesh((8,)), ("tp",))

    def test_ring_xla_matches_psum(self):
        from jax.sharding import PartitionSpec as P

        from distributed_llama_tpu.ops import collectives

        mesh = self._mesh()
        rng = np.random.RandomState(0)

        def weighted(impl):
            def f(y):
                w = 1.0 + jax.lax.axis_index("tp").astype(jnp.float32)
                return collectives.all_reduce(y * w, "tp", impl=impl)

            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P(None, None), out_specs=P(None, None),
                check_vma=False,
            ))

        for d in (4096, 4100, 256):
            x = jnp.asarray(rng.randn(2, d).astype(np.float32))
            ring = np.asarray(weighted("ring_xla")(x))
            psum = np.asarray(weighted("psum")(x))
            np.testing.assert_allclose(ring, psum, rtol=1e-5, atol=1e-5)

    def test_ring_replicated_bit_identity(self):
        """Replicated operands (the TP forward's case: every shard holds
        the same partial layout) must reduce to byte-identical results on
        every shard — the property replicated device sampling rests on."""
        from jax.sharding import PartitionSpec as P

        from distributed_llama_tpu.ops import collectives

        mesh = self._mesh()
        x = jnp.asarray(np.random.RandomState(1).randn(2, 512).astype(np.float32))

        def f(y):
            out = collectives.all_reduce(y, "tp", impl="ring_xla")
            # re-expose per-shard results so divergence would be visible
            return out[None]

        g = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(None, None), out_specs=P("tp", None, None),
            check_vma=False,
        ))
        per_shard = np.asarray(g(x))  # [8, 2, 512]
        for i in range(1, 8):
            np.testing.assert_array_equal(per_shard[0], per_shard[i])
        # and the ring equals psum bitwise on replicated inputs
        h = jax.jit(jax.shard_map(
            lambda y: jax.lax.psum(y, "tp"),
            mesh=mesh, in_specs=P(None, None), out_specs=P(None, None),
            check_vma=False,
        ))
        np.testing.assert_array_equal(per_shard[0], np.asarray(h(x)))

    def test_small_payload_falls_back_to_psum(self):
        """Payloads narrower than the axis take psum (the ring would ship
        empty chunks); the seam must stay correct, not just fast."""
        from jax.sharding import PartitionSpec as P

        from distributed_llama_tpu.ops import collectives

        mesh = self._mesh()
        x = jnp.ones((1, 4), jnp.float32)
        g = jax.jit(jax.shard_map(
            lambda y: collectives.all_reduce(y, "tp", impl="ring_xla"),
            mesh=mesh, in_specs=P(None, None), out_specs=P(None, None),
            check_vma=False,
        ))
        np.testing.assert_array_equal(np.asarray(g(x)), np.full((1, 4), 8.0))

    def test_seam_default_off_tpu_is_psum(self):
        from distributed_llama_tpu.ops import collectives

        assert collectives.default_impl() == "psum"  # CPU test substrate


class TestMatmulAllReduceSeam:
    """Tentpole (b)'s seam: the wo/down matmul+all-reduce entry point
    (``collectives.matmul_all_reduce``). The fused matmul+ring kernel is
    TPU-compiled only (remote DMA has no interpret mode), so the CPU-mesh
    contract is arm parity of the unfused arms:
    the psum arm is exactly the per-shard int8 matmul + psum composition,
    and ring-schedule arms agree within summation-order tolerance (the
    same allclose pin as the plain ring all-reduce)."""

    def _mesh(self):
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh

        return Mesh(mesh_utils.create_device_mesh((8,)), ("tp",))

    def _setup(self):
        rng = np.random.RandomState(0)
        n_shard, d, T = 512, 128, 2
        packs = [
            quantize_q40_tpu(rng.randn(n_shard, d).astype(np.float32) / 32)
            for _ in range(8)
        ]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *packs)
        xs = jnp.asarray(rng.randn(8, T, n_shard).astype(np.float32))
        return packs, stacked, xs

    def _run(self, mesh, stacked, xs, impl):
        from jax.sharding import PartitionSpec as P

        from distributed_llama_tpu.ops import collectives

        def f(x, qm):
            qm0 = jax.tree.map(lambda a: a[0], qm)
            return collectives.matmul_all_reduce(x[0], qm0, "tp", impl=impl)

        return np.asarray(jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("tp"), P("tp")), out_specs=P(None, None),
            check_vma=False,
        ))(xs, stacked))

    def test_seam_arms_agree(self):
        mesh = self._mesh()
        packs, stacked, xs = self._setup()
        # reference: the sum of per-shard standalone int8 matmuls
        ref = np.sum(
            [np.asarray(q40_matmul(xs[i], packs[i])) for i in range(8)],
            axis=0,
        )
        psum = self._run(mesh, stacked, xs, "psum")
        ring_xla = self._run(mesh, stacked, xs, "ring_xla")
        scale = np.abs(ref).max()
        np.testing.assert_allclose(psum / scale, ref / scale, atol=1e-5)
        np.testing.assert_allclose(ring_xla / scale, psum / scale, atol=1e-5)

    def test_ring_arm_asked_by_name_fails_loudly_off_tpu(self):
        """The remote-DMA kernel cannot run on the CPU mesh; asked for by
        name it must raise, never hand back a psum under the ring's label."""
        mesh = self._mesh()
        _, stacked, xs = self._setup()
        with pytest.raises(ValueError, match="interpret mode"):
            self._run(mesh, stacked, xs, "ring")

    def test_seam_no_axis_is_plain_matmul(self):
        packs, _, xs = self._setup()
        from distributed_llama_tpu.ops import collectives

        got = collectives.matmul_all_reduce(xs[0], packs[0], None)
        want = q40_matmul(xs[0], packs[0])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
