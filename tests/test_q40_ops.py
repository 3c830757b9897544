"""Q40 Pallas kernel tests (interpret mode on CPU).

The reference validates its quant matmuls by cross-dtype tolerance checks
(src/funcs-test.cpp:18-60); here the packed-layout matmul is checked exactly
against dequantize-then-matmul, and the repack is checked bit-exactly against
the file format."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.ops.q40 import (
    QuantizedMatrix,
    dequantize_tpu,
    pack_q40_tpu,
    q40_matmul,
    quantize_q40_tpu,
)
from distributed_llama_tpu.quants import dequantize_q40, quantize_q40


class TestPacking:
    def test_pack_matches_file_dequant(self):
        rng = np.random.RandomState(0)
        d_out, d_in = 64, 128
        w = rng.randn(d_out, d_in).astype(np.float32)
        qs, scales = quantize_q40(w)
        file_deq = dequantize_q40(qs, scales)  # [d_out, d_in]

        qm = pack_q40_tpu(qs.reshape(-1, 16), scales.reshape(-1), (d_out, d_in))
        tpu_deq = dequantize_tpu(qm)  # [d_in, d_out]
        np.testing.assert_array_equal(tpu_deq.T, file_deq)

    def test_quantize_q40_tpu_round_trip(self):
        rng = np.random.RandomState(1)
        w = rng.randn(96, 64).astype(np.float32)
        qm = quantize_q40_tpu(w)
        deq = dequantize_tpu(qm)
        assert deq.shape == w.shape
        # Q40 round-trip error bound (reference tolerates absmax/8 per value)
        assert np.abs(deq - w).max() < np.abs(w).max() / 7.0

    def test_pytree_registration(self):
        qm = quantize_q40_tpu(np.ones((32, 64), np.float32))
        leaves = jax.tree.leaves(qm)
        assert len(leaves) == 2
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), qm, qm)
        assert stacked.qs.shape == (2, 32, 64)  # n=32 padded to 64, half-split

    def test_a_leaf_carries_its_logical_dims_and_nothing_else(self):
        """One basis: what rides a Q40 leaf beside its two arrays is the
        logical (unpadded) dims, and there is no field to say otherwise."""
        qm = quantize_q40_tpu(np.ones((96, 64), np.float32))
        children, aux = qm.tree_flatten()
        assert len(children) == 2 and aux == (96, 64)
        assert [f.name for f in dataclasses.fields(QuantizedMatrix)] == [
            "qs", "scales", "n_logical", "d_logical",
        ]
        with pytest.raises(TypeError, match="interleaved"):
            QuantizedMatrix(qm.qs, qm.scales, interleaved=True)


class TestMatmul:
    @pytest.mark.parametrize("T", [1, 8])
    def test_kernel_matches_dequant_matmul(self, T):
        rng = np.random.RandomState(2)
        n, d = 512, 256
        w = rng.randn(n, d).astype(np.float32) / np.sqrt(n)
        qm = quantize_q40_tpu(w)
        x = jnp.asarray(rng.randn(T, n).astype(np.float32))

        want = np.asarray(x @ jnp.asarray(dequantize_tpu(qm)))
        got = np.asarray(q40_matmul(x, qm, block_n=512, block_d=128, interpret=True))
        # the kernel rounds x to Q80 (noise << Q40's own error)
        scale = np.abs(want).max()
        np.testing.assert_allclose(got / scale, want / scale, atol=2e-2)

    def test_fallback_for_untiled_shapes(self):
        rng = np.random.RandomState(3)
        n, d = 64, 96  # not multiples of the block sizes
        w = rng.randn(n, d).astype(np.float32)
        qm = quantize_q40_tpu(w)
        x = jnp.asarray(rng.randn(2, n).astype(np.float32))
        want = x @ jnp.asarray(dequantize_tpu(qm))
        got = q40_matmul(x, qm)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    def test_accuracy_vs_unquantized(self):
        rng = np.random.RandomState(4)
        n, d = 512, 256
        w = rng.randn(n, d).astype(np.float32) / np.sqrt(n)
        qm = quantize_q40_tpu(w)
        x = jnp.asarray(rng.randn(1, n).astype(np.float32))
        exact = np.asarray(x) @ w
        got = np.asarray(q40_matmul(x, qm, block_n=512, block_d=128, interpret=True))
        # quantization noise, not kernel error
        rel = np.abs(got - exact).max() / np.abs(exact).max()
        assert rel < 0.12, rel
