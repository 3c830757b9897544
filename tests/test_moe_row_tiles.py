"""The held experts' buckets through the grouped Q40 launch in row tiles
(``ops.q40.q40_grouped_matmul``, PR 54), end to end through
``models.moe._held_experts`` at banks wide enough for the int8 kernel
(interpret mode): at every level of a prompt piece's switch the result is the
every-row arm's to float32's rounding and, bit for bit, what the same step
gives with each bucket launched as one row block (the launch until PR 54);
and the rows the launches multiplied (``dllama_moe_expert_rows_total{rows=
"launched"}`` is fed from them) are the live row tiles' rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.formats.model_file import ArchType
from distributed_llama_tpu.models import moe
from distributed_llama_tpu.models.config import LlamaConfig
from distributed_llama_tpu.ops import q40
from tests.test_q40_ops import _row_tiles_of, _seeded_bank

DIM, WIDTH, HELD, T = 512, 512, 8, 256


def _held(cfg, lp, x, vals, idx):
    with moe.collect_piece_paths() as paths, moe.collect_launched() as launched:
        out = moe._held_experts(cfg, x, lp, vals, idx)
    return out, paths[0], launched[0]


# (experts a token, the router's width), the rows that choose expert 3, the arm they put the step on
# (0 the bucket, 1 the bucket of twice its rows, 2 every row) and the row tiles expert 3 then has
@pytest.mark.parametrize("k,routed,rows_of_3,arm,tiles_of_3", [
    (4, 64, 16, 0, 1),  # glm-4.7-flash's ratio: buckets of 64, then 128, then every row
    (4, 64, 65, 1, 3),
    (4, 64, 128, 1, 4),
    (4, 64, 129, 2, None),
    (10, 72, 36, 0, 2),  # granite-4.0-h-small's: the bucket of 128, or every row
    (10, 72, 128, 0, 4),
    (10, 72, 129, 2, None),
])
def test_a_pieces_arms_in_row_tiles_against_every_row_and_the_one_block(monkeypatch, k, routed, rows_of_3, arm, tiles_of_3):
    cfg = LlamaConfig(
        arch=ArchType.GRANITE_HYBRID, dim=DIM, hidden_dim=WIDTH, n_layers=1, n_heads=2, n_kv_heads=2,
        vocab_size=64, seq_len=64, head_size=DIM // 2, kv_dim=DIM, n_experts=HELD, n_active_experts=k,
        moe_hidden_dim=WIDTH, n_routed_experts=routed, first_expert=0)
    C = moe.held_bucket_rows(cfg, T)
    assert C == (64 if routed == 64 else 128) and q40.grouped_row_tile(C) == 32 < C
    lp = {"experts_gate_up": _seeded_bank(HELD, DIM, 2 * WIDTH, 1), "experts_down": _seeded_bank(HELD, WIDTH, DIM, 2)}
    rng = np.random.default_rng(rows_of_3)
    x = jnp.asarray(rng.standard_normal((T, DIM)), jnp.bfloat16)
    vals = jnp.asarray(rng.dirichlet(np.ones(k), T), jnp.float32)
    # row t chooses experts t, t + stride, ...: k distinct ones, each expert by T * k / routed rows
    # (rounded either way); then rows that did not choose expert 3 choose it first until it has its rows
    idx = (np.arange(T)[:, None] + (routed // k) * np.arange(k)[None, :]) % routed
    spare = np.flatnonzero(~(idx == 3).any(axis=1))
    idx[spare[: rows_of_3 - (idx == 3).sum()], 0] = 3
    counts = np.bincount(idx.ravel(), minlength=routed)[:HELD]
    assert counts[3] == rows_of_3 == counts.max()
    idx = jnp.asarray(idx, jnp.int32)

    got, every_row, launched = jax.jit(lambda x: _held(cfg, lp, x, vals, idx))(x)
    assert int(every_row) == (arm == 2)
    if arm == 2:
        assert int(launched) == T * (counts > 0).sum()
    else:
        bucket = C << arm
        assert -(-min(rows_of_3, bucket) // 32) == tiles_of_3
        assert int(launched) == 32 * (-(-np.minimum(counts, bucket) // 32)).sum() < HELD * bucket

    # every held expert over every row, alone in its program
    monkeypatch.setattr(moe, "held_bucket_rows", lambda cfg, rows: rows)
    want = np.asarray(jax.jit(lambda x: _held(cfg, lp, x, vals, idx))(x)[0])
    monkeypatch.undo()
    assert np.abs(np.asarray(got) - want).max() <= 2e-5 * np.abs(want).max()

    # the same step with a bucket launched as ONE row block: the same bits
    with _row_tiles_of(T):
        one_block = jax.jit(lambda x: _held(cfg, lp, x, vals, idx))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(one_block[0]))
    if arm != 2:
        assert int(one_block[2]) == (C << arm) * (counts > 0).sum()  # what the parent launched
