"""Q40 Pallas kernel tests (interpret mode on CPU).

The reference validates its quant matmuls by cross-dtype tolerance checks
(src/funcs-test.cpp:18-60); here the packed-layout matmul is checked exactly
against dequantize-then-matmul, and the repack is checked bit-exactly against
the file format."""

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


class TestInterleavedMigration:
    """The block-interleaved activation basis is RETIRED (ops.q40 legacy
    section): the runtime is standard-only, the legacy producers survive
    solely so basis-era snapshots can be synthesized, and the converter
    shims must invert them bit-exactly."""

    def _pair(self, n=512, d=256, seed=5):
        from distributed_llama_tpu.ops.q40 import interleave_input_rows

        rng = np.random.RandomState(seed)
        w = rng.randn(n, d).astype(np.float32) / np.sqrt(n)
        qm = quantize_q40_tpu(w)
        qi = interleave_input_rows(qm)
        assert qi.interleaved and qi.packed_bn > 0
        return qm, qi

    def test_retired_basis_rejected_at_every_entry_point(self):
        """An interleaved pack reaching the runtime is a migration bug, not
        a layout to dispatch on — dequantize and both matmul entry points
        must fail loudly instead of silently misreading the row order."""
        from distributed_llama_tpu.ops.q40 import rmsnorm_q40_matmul

        qm, qi = self._pair()
        x = jnp.ones((1, qm.n_padded), jnp.float32)
        with pytest.raises(ValueError, match="interleav"):
            dequantize_tpu(qi)
        with pytest.raises(ValueError, match="interleav"):
            q40_matmul(x, qi, interpret=True)
        with pytest.raises(ValueError, match="interleav"):
            rmsnorm_q40_matmul(
                x[:, : qm.n], jnp.ones((qm.n,), jnp.float32), qi, interpret=True
            )

    def test_input_row_round_trip_bit_exact(self):
        from distributed_llama_tpu.ops.q40 import deinterleave_input_rows

        qm, qi = self._pair()
        back = deinterleave_input_rows(qi)
        assert not back.interleaved
        np.testing.assert_array_equal(np.asarray(back.qs), np.asarray(qm.qs))
        np.testing.assert_array_equal(np.asarray(back.scales), np.asarray(qm.scales))
        np.testing.assert_array_equal(
            np.asarray(dequantize_tpu(back)), np.asarray(dequantize_tpu(qm))
        )

    def test_output_col_round_trip_bit_exact(self):
        """gate_up's consumer-basis column permutation (halves=2, padded
        consumer dims — the hardest case) must invert exactly, restoring
        the original zero d-padding."""
        from distributed_llama_tpu.ops.q40 import (
            deinterleave_output_cols,
            interleaved_output_cols,
        )

        rng = np.random.RandomState(9)
        F = 544  # pads to 1024 -> basis has interspersed pad positions
        qm = quantize_q40_tpu(rng.randn(512, 2 * F).astype(np.float32) / 16)
        qo = interleaved_output_cols(qm, F, halves=2)
        back = deinterleave_output_cols(qo, F, halves=2)
        assert back.d == qm.d and back.d_padded == qm.d_padded
        np.testing.assert_array_equal(np.asarray(back.qs), np.asarray(qm.qs))
        np.testing.assert_array_equal(np.asarray(back.scales), np.asarray(qm.scales))

    def test_vector_round_trip_bit_exact(self):
        from distributed_llama_tpu.ops.q40 import deinterleave_vector, interleave_vector

        rng = np.random.RandomState(11)
        v = jnp.asarray(rng.randn(512).astype(np.float32))
        vi = interleave_vector(v, 512)
        np.testing.assert_array_equal(
            np.asarray(deinterleave_vector(vi, 512)), np.asarray(v)
        )

    def test_output_cols_pad_positions_are_zero(self):
        """interleaved_output_cols on a padded consumer basis must emit
        exact zeros at the interspersed pad positions (they feed silu/mul
        and the next matmul's zero-scale rows)."""
        from distributed_llama_tpu.ops.q40 import (
            interleave_perm,
            interleave_window,
            interleaved_output_cols,
        )
        from distributed_llama_tpu.ops.q40 import _n_padded

        rng = np.random.RandomState(9)
        F = 544  # pads to 1024 -> basis has interspersed pad positions
        npc = _n_padded(F)
        w = rng.randn(512, 2 * F).astype(np.float32) / 16  # fused [a|b]
        qm = quantize_q40_tpu(w)
        qo = interleaved_output_cols(qm, F, halves=2)
        assert qo.d == 2 * npc
        deq = dequantize_tpu(qo)  # columns in the consumer basis
        perm = interleave_perm(npc, interleave_window(npc))
        pad_cols = np.concatenate([
            np.nonzero(perm >= F)[0], npc + np.nonzero(perm >= F)[0]
        ])
        assert np.all(deq[:, pad_cols] == 0.0)
