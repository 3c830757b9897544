"""Q40 Pallas kernel tests (interpret mode on CPU).

The reference validates its quant matmuls by cross-dtype tolerance checks
(src/funcs-test.cpp:18-60); here the packed-layout matmul is checked exactly
against dequantize-then-matmul, and the repack is checked bit-exactly against
the file format."""

import contextlib
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.ops import q40
from distributed_llama_tpu.ops.q40 import (
    GROUPED_ROW_TILE,
    QuantizedMatrix,
    _d_padded,
    _int8_tiles,
    _n_padded,
    dequantize_tpu,
    grouped_live_tiles,
    grouped_row_tile,
    pack_q40_tpu,
    q40_grouped_matmul,
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


# ---------------------------------------------------------------------------
# The grouped launch in row tiles (a held expert's bucket, PR 54)
# ---------------------------------------------------------------------------

TILE = GROUPED_ROW_TILE


def _seeded_bank(experts: int, n: int, d: int, seed: int) -> QuantizedMatrix:
    """A bank of ``experts`` packs n -> d as the loader pads them, random nibbles and scales."""
    n_pad, d_pad = _n_padded(n), _d_padded(d)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    scales = jax.random.uniform(k2, (experts, n_pad // 32, d_pad), jnp.float32, 0.5, 1.5) / 300.0
    return QuantizedMatrix(jax.random.bits(k1, (experts, n_pad // 2, d_pad), dtype=jnp.uint8), scales, n, d)


def _bucket_rows(counts, C: int, n: int, seed: int):
    """[E, C, n] buckets filled from slot 0 up with ``counts[e]`` random rows (at most C), zeros after."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (len(counts), C, n), jnp.float32).astype(jnp.bfloat16)
    return jnp.where((jnp.arange(C) < jnp.asarray(counts)[:, None])[..., None], x, 0)


@contextlib.contextmanager
def _row_tiles_of(rows: int):
    """The grouped launch with row tiles of ``rows`` rows (``GROUPED_ROW_TILE``:
    a bucket of that many is ONE row block, the launch until PR 54)."""
    was, q40.GROUPED_ROW_TILE = q40.GROUPED_ROW_TILE, rows
    q40_grouped_matmul.clear_cache()  # its trace read the row tile
    try:
        yield
    finally:
        q40.GROUPED_ROW_TILE = was
        q40_grouped_matmul.clear_cache()


# 1536 columns are ONE output tile at a row tile's 32 rows (and at 64) and two of 768 at a bucket's
# 128; 4096 columns one tile of 4096 against four of 1024 (two of 2048 at 64 rows): the two widths of
# Granite-4.0-H-Small's held experts (gate|up 4096 -> 1536, down 768 -> 4096), contraction cut to
# one input tile (gate|up) and padded to one (down)
@pytest.mark.parametrize("n,d", [(1024, 1536), (768, 4096)])
@pytest.mark.parametrize("C", [64, 128])
def test_a_tiled_bucket_gives_the_one_blocks_bits_on_live_rows_and_zeros_on_skipped_tiles(C, n, d):
    """The launch of ``[E, C, n]`` buckets in row tiles against the same
    launch with the bucket as one row block (the launch until PR 54), in
    interpret mode, at ragged counts: an expert off, 1 row, a row under, at
    and over a tile, a full bucket, and a count past the bucket (every tile
    live, as at ``C``). Every row of a live tile is the one block's to the
    bit; every row of a skipped tile is exactly 0.0."""
    counts = [0, 1, TILE - 1, TILE, TILE + 1, C, C + 9]
    bank = _seeded_bank(len(counts), n, d, seed=C + d)
    x = _bucket_rows(counts, C, n, seed=d)
    counts = jnp.asarray(counts, jnp.int32)
    assert _int8_tiles(bank, TILE, 1024, 4096)[1] == d  # a row tile takes the whole width in one output tile
    # ... where the whole bucket is cut by its rows' class
    assert _int8_tiles(bank, C, 1024, 4096)[1] == {(64, 1536): 1536, (128, 1536): 768, (64, 4096): 2048, (128, 4096): 1024}[C, d]
    with _row_tiles_of(C):
        whole = np.asarray(q40_grouped_matmul(x, bank, counts, interpret=True))
    tiled = np.asarray(q40_grouped_matmul(x, bank, counts, interpret=True))
    assert whole.shape == tiled.shape == (len(counts), C, d)
    tile, live = grouped_live_tiles(counts, C, shared=False)
    assert tile == TILE and live.sum(axis=1).tolist() == [0, 1, 1, 1, 2, C // TILE, C // TILE]
    live = np.repeat(np.asarray(live), TILE, axis=1)  # [E, C]: the rows of the live tiles
    assert np.abs(whole[live]).max() > 0.1
    np.testing.assert_array_equal(tiled[live], whole[live])
    assert (tiled[~live] == 0.0).all()


def test_a_bucket_of_one_row_tile_and_shared_rows_are_one_tile_an_expert():
    """A bucket of at most one row tile (every decode step's) and the
    every-row arm's shared rows have no tile to skip: the launch walks the
    experts, and an expert's count says no more than whether a row chose it
    (a bool does as well); a bucket of two tiles walks twice the pairs."""
    bank = _seeded_bank(3, 1024, 256, seed=1)
    on, counts = jnp.asarray([True, False, True]), jnp.asarray([5, 0, TILE], jnp.int32)
    assert [grouped_row_tile(rows) for rows in (8, 16, TILE, 2 * TILE, 4 * TILE)] == [8, 16, TILE, TILE, TILE]

    def text(x, counts):
        return str(jax.make_jaxpr(lambda x: q40_grouped_matmul.__wrapped__(x, bank, counts, interpret=True))(x))

    rng = np.random.default_rng(3)
    for shape in ((3, TILE, 1024), (2 * TILE, 1024)):
        tile, live = grouped_live_tiles(counts, shape[-2], shared=len(shape) == 2)
        assert tile == shape[-2] and live.tolist() == [[True], [False], [True]]
        x = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        assert "grid=(3, " in text(x, counts)
        got = np.asarray(q40_grouped_matmul(x, bank, counts, interpret=True))
        np.testing.assert_array_equal(got, np.asarray(q40_grouped_matmul(x, bank, on, interpret=True)))
        assert np.abs(got[0]).max() > 0.1 and not got[1].any()
    assert "grid=(6, " in text(jnp.zeros((3, 2 * TILE, 1024), jnp.bfloat16), counts)
