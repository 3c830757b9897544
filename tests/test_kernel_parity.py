"""Interpret-mode parity gates for the Pallas kernels (ISSUE 14 + the
ISSUE 17 decode-megakernel fusions).

All runnable on the CPU test substrate (conftest pins JAX_PLATFORMS=cpu +
an 8-device virtual mesh):

* int8 MXU Q40×Q80 matmul: tolerance vs the f32 kernel and the
  dequantize-then-matmul reference (the int8 path adds ONLY the Q80
  activation rounding, ~0.5% — far under Q40's own ~3% noise), plus
  path-dispatch/telemetry checks.
* fused rmsnorm→Q80 epilogue (``rmsnorm_q40_matmul``): BIT-parity vs the
  standalone rmsnorm + int8 matmul it replaces — the fused program inlines
  the identical op sequence, so any drift is a bug, not tolerance.
* fused paged decode-attention AND its verify form: BIT-parity vs the
  segmented-scan chain they replace, across bf16/f32/i8 and bucket shapes,
  double-buffered and serial DMA schedules, plus the spec-hit ==
  plain-decode transitivity on the fused path.
* ring all-reduce + the matmul_all_reduce seam: the ring schedule
  (ppermute realization — remote DMA has no interpret mode) vs psum
  under the CPU mesh mocks. The fused matmul+ring kernel is TPU-compiled
  only (tests/test_chip_compile.py compiles it for a described v5e).
"""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.ops import attention as att
from distributed_llama_tpu.ops import kv_cache as kvc
from distributed_llama_tpu.ops.q40 import (
    dequantize_tpu,
    q40_matmul,
    quantize_q40_tpu,
    quantize_q80,
    rmsnorm_q40_matmul,
    rmsnorm_ref,
)


class TestInt8Matmul:
    def _qm(self, n=1024, d=256, seed=2):
        rng = np.random.RandomState(seed)
        w = rng.randn(n, d).astype(np.float32) / np.sqrt(n)
        return quantize_q40_tpu(w), rng

    @pytest.mark.parametrize("T", [1, 8])
    def test_int8_matches_dequant_and_f32_kernel(self, T):
        qm, rng = self._qm()
        x = jnp.asarray(rng.randn(T, qm.n).astype(np.float32))
        want = np.asarray(x @ jnp.asarray(dequantize_tpu(qm)))
        f32 = np.asarray(q40_matmul(x, qm, interpret=True, path="f32"))
        i8 = np.asarray(q40_matmul(x, qm, interpret=True, path="int8"))
        scale = np.abs(want).max()
        # f32 kernel: bf16-free in interpret mode — near-exact
        np.testing.assert_allclose(f32 / scale, want / scale, atol=1e-5)
        # int8 adds only the Q80 activation rounding (~0.5% per element)
        np.testing.assert_allclose(i8 / scale, want / scale, atol=2e-2)
        np.testing.assert_allclose(i8 / scale, f32 / scale, atol=2e-2)

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
        """Matrices too small to tile take the XLA fallback on EVERY path
        (the dispatch owns eligibility, not the path argument)."""
        rng = np.random.RandomState(3)
        w = rng.randn(64, 96).astype(np.float32)
        qm = quantize_q40_tpu(w)
        x = jnp.asarray(rng.randn(2, 64).astype(np.float32))
        want = x @ jnp.asarray(dequantize_tpu(qm))
        for path in ("int8", "f32", None):
            got = q40_matmul(x, qm, path=path)
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
            q40_matmul(x, qm, interpret=True, path="int8")
            q40_matmul(x, qm, interpret=True, path="f32")
            small = quantize_q40_tpu(rng.randn(64, 96).astype(np.float32))
            q40_matmul(jnp.asarray(rng.randn(1, 64).astype(np.float32)), small)
            ctr = telemetry.REGISTRY.counter(
                "dllama_kernel_path_total", labelnames=("kernel", "path")
            )
            for path in ("mxu_int8", "vpu_f32", "xla_fallback"):
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
        fused = rmsnorm_q40_matmul(x, wgt, qm, interpret=True, path="int8")
        unfused = q40_matmul(
            rmsnorm_ref(x, wgt).astype(jnp.bfloat16), qm,
            interpret=True, path="int8",
        )
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))

    def test_flag_off_takes_standalone_arm(self, monkeypatch):
        """DLT_FUSED_Q80=0 must route through the exact standalone chain —
        the committed A/B baseline (bench.py --kernels)."""
        x, wgt, qm = self._case(1, 1024, 256, jnp.float32)
        want = q40_matmul(
            rmsnorm_ref(x, wgt).astype(jnp.bfloat16), qm,
            interpret=True, path="int8",
        )
        monkeypatch.setenv("DLT_FUSED_Q80", "0")
        off = rmsnorm_q40_matmul(x, wgt, qm, interpret=True, path="int8")
        np.testing.assert_array_equal(np.asarray(off), np.asarray(want))

    def test_untiled_and_f32_paths_fall_back(self):
        """Shapes the int8 kernel can't tile (or an explicit f32 path)
        take the standalone chain — dispatch owns eligibility, exactly
        like q40_matmul's fallback contract."""
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
            rmsnorm_q40_matmul(x, wgt, qm, interpret=True, path="int8")
            ctr = telemetry.REGISTRY.counter(
                "dllama_kernel_path_total", labelnames=("kernel", "path")
            )
            assert ctr.labels(kernel="q40_matmul", path="mxu_int8_fusedq").value >= 1
        finally:
            telemetry.reset()
            telemetry.disable()


def _mk_half(rng, shape, dtype):
    a = rng.randn(*shape).astype(np.float32)
    if dtype == "i8":
        q, s = kvc.quantize_rows(jnp.asarray(a).reshape(-1, *shape[-2:]))
        return kvc.QuantizedKV(
            q.reshape(shape), s.reshape(shape[:-1] + (1,))
        )
    return jnp.asarray(a).astype(dtype)


class TestFusedPagedAttention:
    """Bit-parity of the fused Pallas hit path vs the segmented scan —
    the EXACT-EMPTY-PARTIAL merge semantics must survive verbatim."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, "i8"])
    @pytest.mark.parametrize("B,S,chunk,page", [(3, 64, 16, 8), (2, 96, 24, 8)])
    def test_bit_parity_vs_segmented_scan(self, dtype, B, S, chunk, page):
        rng = np.random.RandomState(0)
        K, M, hd, P_ = 2, 2, 8, 16
        qg = jnp.asarray(rng.randn(B, K, M, hd).astype(np.float32))
        keys = _mk_half(rng, (B, S, K, hd), dtype)
        values = _mk_half(rng, (B, S, K, hd), dtype)
        pool_k = _mk_half(rng, (P_, page, K, hd), dtype)
        pool_v = _mk_half(rng, (P_, page, K, hd), dtype)
        tables = jnp.asarray(rng.randint(0, P_, (B, S // page)).astype(np.int32))
        matched = jnp.asarray(
            rng.randint(0, S // page + 1, B).astype(np.int32) * page
        )
        pos = jnp.asarray(rng.randint(0, S, B).astype(np.int32))
        paged = (pool_k, pool_v, tables, matched)
        # the dispatch default is the segmented scan (the path the chip
        # runs); the fused kernel is selected by its explicit entry point
        ref = att.batched_decode_attention(qg, (keys, values), pos, chunk, paged=paged)
        # tentpole (c): the double-buffered DMA schedule only reorders copy
        # issue/wait around unchanged compute — both arms bit-identical
        for db in (True, False):
            got = att.fused_paged_decode_attention(
                qg, keys, values, pos, chunk, paged, double_buffer=db
            )
            assert bool(jnp.all(got == ref)), (db, float(jnp.max(jnp.abs(got - ref))))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, "i8"])
    @pytest.mark.parametrize("B,S,chunk,page", [(3, 64, 16, 8), (2, 96, 24, 8)])
    def test_verify_bit_parity_and_decode_transitivity(self, dtype, B, S, chunk, page):
        """Tentpole (d): the fused verify kernel vs the segmented verify
        scan (bit), both DMA schedules, AND the spec-hit == plain-decode
        transitivity — query t of a verify window at position pos+t is
        the same MATH as a plain decode at that position, so the two agree
        within a few f32 roundings. Bit equality ACROSS query widths is
        not XLA's to promise and the claim is withdrawn (ops/attention.py):
        it emits one dot and one loop body per width T and rounds them
        differently — 1 ulp measured on the XLA of jax 0.9, and at
        chunk=24 even op-by-op dispatch differs."""
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
        for db in (True, False):
            got = att.fused_paged_verify_attention(
                qg, keys, values, pos, chunk, paged, double_buffer=db
            )
            assert bool(jnp.all(got == ref)), (db, float(jnp.max(jnp.abs(got - ref))))
        # transitivity: verify query t vs plain decode at pos+t
        t = 1
        dec = att.batched_decode_attention(
            qg[:, t], (keys, values), pos + t, chunk, paged=paged
        )
        # a handful of roundings per merge, at most S/chunk merges
        atol = 8 * np.finfo(np.float32).eps * float(jnp.max(jnp.abs(dec)))
        np.testing.assert_allclose(np.asarray(ref[:, t]), np.asarray(dec), rtol=0, atol=atol)

    def test_verify_dispatch_counts_fused_path(self, monkeypatch):
        from distributed_llama_tpu import telemetry

        monkeypatch.setenv("DLT_FUSED_PAGED", "1")
        telemetry.enable()
        try:
            telemetry.reset()
            rng = np.random.RandomState(6)
            B, S, K, M, hd, chunk, page, P_, T = 2, 32, 2, 1, 8, 8, 4, 8, 2
            qg = jnp.asarray(rng.randn(B, T, K, M, hd).astype(np.float32))
            keys = _mk_half(rng, (B, S, K, hd), jnp.float32)
            values = _mk_half(rng, (B, S, K, hd), jnp.float32)
            paged = (
                _mk_half(rng, (P_, page, K, hd), jnp.float32),
                _mk_half(rng, (P_, page, K, hd), jnp.float32),
                jnp.zeros((B, S // page), jnp.int32),
                jnp.asarray([8, 0], jnp.int32),
            )
            pos = jnp.asarray([20, 5], jnp.int32)
            att.batched_verify_attention(qg, (keys, values), pos, chunk, paged=paged)
            ctr = telemetry.REGISTRY.counter(
                "dllama_kernel_path_total", labelnames=("kernel", "path")
            )
            assert (
                ctr.labels(kernel="paged_attention", path="pallas_fused_verify").value
                >= 1
            )
        finally:
            telemetry.reset()
            telemetry.disable()

    def test_dispatch_takes_fused_path_and_counts_it(self, monkeypatch):
        from distributed_llama_tpu import telemetry

        monkeypatch.setenv("DLT_FUSED_PAGED", "1")
        telemetry.enable()
        try:
            telemetry.reset()
            rng = np.random.RandomState(1)
            B, S, K, M, hd, chunk, page, P_ = 2, 32, 2, 1, 8, 8, 4, 8
            qg = jnp.asarray(rng.randn(B, K, M, hd).astype(np.float32))
            keys = _mk_half(rng, (B, S, K, hd), jnp.float32)
            values = _mk_half(rng, (B, S, K, hd), jnp.float32)
            paged = (
                _mk_half(rng, (P_, page, K, hd), jnp.float32),
                _mk_half(rng, (P_, page, K, hd), jnp.float32),
                jnp.zeros((B, S // page), jnp.int32),
                jnp.asarray([8, 0], jnp.int32),
            )
            pos = jnp.asarray([20, 5], jnp.int32)
            att.batched_decode_attention(qg, (keys, values), pos, chunk, paged=paged)
            ctr = telemetry.REGISTRY.counter(
                "dllama_kernel_path_total", labelnames=("kernel", "path")
            )
            assert ctr.labels(kernel="paged_attention", path="pallas_fused").value >= 1
            # unset, every platform takes the segmented scan
            monkeypatch.delenv("DLT_FUSED_PAGED")
            att.batched_decode_attention(qg, (keys, values), pos, chunk, paged=paged)
            assert ctr.labels(kernel="paged_attention", path="xla_segmented").value >= 1
        finally:
            telemetry.reset()
            telemetry.disable()

    def test_non_paged_path_untouched(self):
        """paged=None must never route to the fused kernel (the plain slab
        scan is the cold path the parity suites pin separately)."""
        rng = np.random.RandomState(2)
        B, S, K, M, hd, chunk = 2, 32, 2, 1, 8, 8
        qg = jnp.asarray(rng.randn(B, K, M, hd).astype(np.float32))
        keys = _mk_half(rng, (B, S, K, hd), jnp.float32)
        values = _mk_half(rng, (B, S, K, hd), jnp.float32)
        pos = jnp.asarray([20, 5], jnp.int32)
        out = att.batched_decode_attention(qg, (keys, values), pos, chunk)
        assert out.shape == (B, K, M, hd)


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
            [np.asarray(q40_matmul(xs[i], packs[i], path="int8")) for i in range(8)],
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
