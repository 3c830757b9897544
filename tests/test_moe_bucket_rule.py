"""The held experts' bucket rule over the benchmark's share-holding
configurations (``models.moe.held_bucket_rows``): the three accepted ratios
keep their buckets, so their programs do not change; a token that chooses a
seventh of the experts gets a bucket UNDER its step's rows, reckoned from
those rows, so that a 32-row decode step, a last piece of 65-128 rows and a
256-row piece compute the rows that chose an expert and not every row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.formats.model_file import ArchType
from distributed_llama_tpu.models import moe
from distributed_llama_tpu.models.config import LlamaConfig


def config(k: int, routed: int, held: int, dim: int = 64, width: int = 32) -> LlamaConfig:
    return LlamaConfig(
        arch=ArchType.GRANITE_HYBRID, dim=dim, hidden_dim=width, n_layers=1, n_heads=2, n_kv_heads=2,
        vocab_size=64, seq_len=64, head_size=dim // 2, kv_dim=dim, n_experts=held, n_active_experts=k,
        moe_hidden_dim=width, n_routed_experts=routed, first_expert=0)


ROWS = (1, 8, 16, 32, 64, 128, 256)


@pytest.mark.parametrize("k,routed,buckets", [
    (8, 320, (8, 8, 8, 8, 8, 32, 32)),  # solar-open2-250b-q40-8l-ep16
    (8, 128, (16, 16, 16, 16, 16, 64, 64)),  # k-exaone-236b-q40-8l-ep8
    (4, 64, (16, 16, 16, 16, 16, 64, 64)),  # glm-4.7-flash-q40-stage0
    # tests/benchmark/granite_moe_tiny.py: four times the share EQUALS half the class, no cut
    (2, 16, (32, 32, 32, 32, 32, 128, 128)),
    # granite-4.0-h-small-q40-10l-ep4: four times the share is the whole class, so the bucket is
    # twice the even share of the step's OWN rows, and under 32 rows there is none (every row)
    (10, 72, (1, 8, 16, 16, 32, 64, 128)),
    # glm-5-q40-5l-ep16: a thirty-second of the router's width a token, the fifth ratio; four times
    # the even share of the class's largest step is 8 of 64 and 32 of 256, Solar's buckets: a
    # decode step gives a held expert a quarter of a row, a 256-row piece 8 (s.d. 2.8)
    (8, 256, (8, 8, 8, 8, 8, 32, 32)),
])
def test_the_bucket_by_rows_for_experts_a_token_over_the_routers_width(k, routed, buckets):
    cfg = config(k, routed, 4)
    assert tuple(moe.held_bucket_rows(cfg, rows) for rows in ROWS) == buckets
    for rows, bucket in zip(ROWS, buckets):
        # a bucket under its step's rows holds the even share with room: twice it at the least
        assert bucket >= rows or (bucket >= 2 * rows * k / routed and bucket >= 8)
    # a piece's bucket lies under its rows: the bucketed arm is there to be taken
    assert buckets[-1] < 256


@pytest.mark.parametrize("T,bucket", [
    (256, 128),  # a prefill chunk: 35 or 36 rows an expert
    (128, 64),  # a prompt's last piece of 65-128 rows: 17 or 18 rows an expert
    (32, 16),  # the decode step of 32 callers: 4 or 5 rows an expert
])
def test_a_step_at_10_of_72_with_even_routing_takes_the_bucketed_arm(T, bucket):
    """18 of 72 experts held, 10 chosen a token, ``T`` rows whose choices go
    round the experts evenly: every layer's counts say ``bucketed``
    (``dllama_moe_piece_layers_total{path}`` and, of a decode chunk,
    ``dllama_moe_expert_rows_total{phase="decode"}`` are fed from them) and
    the result is the every-row arm's to float32's rounding. An expert with
    exactly its bucket's rows still fits; one row more (17 of a 32-row step)
    and the step takes every row: exact too."""
    cfg = config(10, 72, 18)
    rng = np.random.default_rng(3)
    D, W, E = cfg.dim, cfg.moe_hidden_dim, cfg.n_experts
    xn = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    lp = {"experts_gate_up": jnp.asarray(rng.standard_normal((E, D, 2 * W)) / 8, jnp.float32),
          "experts_down": jnp.asarray(rng.standard_normal((E, W, D)) / 6, jnp.float32)}
    # token t chooses experts t, t + 7, ..., t + 63 (mod 72): ten distinct ones, each expert
    # T * 10 / 72 times, rounded either way
    choices = (np.arange(T)[:, None] + 7 * np.arange(10)[None, :]) % 72
    top_vals = jnp.asarray(rng.dirichlet(np.ones(10), T), jnp.float32)
    counts = np.bincount(choices.ravel(), minlength=72)
    assert counts.max() <= -(-T * 10 // 72) < moe.held_bucket_rows(cfg, T) == bucket
    # the path a layer took is a traced value: a program returns it with the result, as the
    # scheduler's prefill programs and its decode chunk do
    run = jax.jit(lambda x, vals, idx: run_with(cfg, lp, x, vals, idx))

    def every_row(weights, idx):
        """Every held expert over every row, mixed by the mostly-zero weights."""
        mix = np.zeros((T, 72), np.float32)
        np.put_along_axis(mix, idx, np.asarray(weights), axis=1)
        fused = np.einsum("td,edf->etf", np.asarray(xn), np.asarray(lp["experts_gate_up"]))
        h = fused[..., :W] / (1 + np.exp(-fused[..., :W])) * fused[..., W:]
        return np.einsum("te,etd->td", mix[:, :E], np.einsum("etf,efd->etd", h, np.asarray(lp["experts_down"])))

    # rows that did not choose expert 3 choose it first, one after the other: it has exactly its
    # bucket's rows (the bucketed arm still), then one more (every row)
    spare = np.flatnonzero(~(choices == 3).any(axis=1))
    full, over = choices.copy(), choices.copy()
    full[spare[:bucket - counts[3]], 0] = 3
    over[spare[:bucket - counts[3] + 1], 0] = 3
    for idx, rows_of_3, arm in ((choices, counts[3], 0), (full, bucket, 0), (over, bucket + 1, 1)):
        assert np.bincount(idx.ravel(), minlength=72)[3] == rows_of_3
        got, took_every_row = run(xn, top_vals, jnp.asarray(idx, jnp.int32))
        assert took_every_row.tolist() == [arm]  # `bucketed` moves, or `every_row` does
        want = every_row(top_vals, idx)
        assert np.abs(np.asarray(got) - want).max() <= 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("T", [1, 8, 16])
def test_a_step_of_fewer_than_32_rows_at_10_of_72_has_no_bucket(T):
    """The rule returns the step's rows: ``_held_experts`` runs every held
    expert over every row and holds no second arm (no ``cond`` in the
    program), as before the rule looked at the step's own rows."""
    cfg = config(10, 72, 18)
    assert moe.held_bucket_rows(cfg, T) == T
    rng = np.random.default_rng(T)
    E, D, W = cfg.n_experts, cfg.dim, cfg.moe_hidden_dim
    lp = {"experts_gate_up": jnp.zeros((E, D, 2 * W), jnp.float32), "experts_down": jnp.zeros((E, W, D), jnp.float32)}
    top_idx = jnp.asarray((np.arange(T)[:, None] + 7 * np.arange(10)[None, :]) % 72, jnp.int32)
    top_vals = jnp.asarray(rng.dirichlet(np.ones(10), T), jnp.float32)
    text = str(jax.make_jaxpr(lambda x: run_with(cfg, lp, x, top_vals, top_idx))(jnp.zeros((T, D), jnp.float32)))
    assert "cond" not in text
    assert run_with(cfg, lp, jnp.zeros((T, D), jnp.float32), top_vals, top_idx)[1].tolist() == [1]


def run_with(cfg, lp, x, top_vals, top_idx):
    with moe.collect_piece_paths() as paths:
        out = moe._held_experts(cfg, x, lp, top_vals, top_idx)
    return out, jnp.stack(paths)


@pytest.mark.parametrize("T,k,E,C", [(32, 10, 18, 16), (32, 8, 20, 8), (128, 10, 18, 64), (64, 4, 6, 4)])
def test_the_slots_matrices_gather_and_scatter_what_the_row_moves_did(T, k, E, C):
    """``_bucket_slots``' two matrices against ``bucket_rank`` /
    ``bucket_scatter`` / ``bucket_combine`` (the row-by-row algebra the held
    experts' buckets had until PR 52, still the capacity-bucketed prefill's):
    the same buckets to the bit, the same weighted sum to float32's rounding;
    a choice of no held expert (the sink) and one ranked past the bucket (the
    last case: 64 rows x 4 over 6 experts overflow buckets of 4) take no slot."""
    rng = np.random.default_rng(T + k)
    routed = 4 * E
    top_idx = np.stack([rng.permutation(routed)[:k] for _ in range(T)])  # k distinct experts a token
    local = jnp.asarray(np.where(top_idx < E, top_idx, E), jnp.int32)  # E: the sink
    weights = jnp.asarray(np.where(top_idx < E, rng.random((T, k)), 0.0), jnp.float32)
    x = jnp.asarray(rng.standard_normal((T, 24)), jnp.float32)
    place, mix = moe._bucket_slots(local, weights, E, C)
    flat_e, rank, t_ids = moe.bucket_rank(local, E + 1)
    want = moe.bucket_scatter(x, flat_e, rank, t_ids, E, C)
    hi = jax.lax.Precision.HIGHEST
    got = jnp.einsum("ts,td->sd", place, x, precision=hi).reshape(E, C, -1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert set(np.unique(np.asarray(place))) <= {0.0, 1.0} and np.asarray(place).sum(0).max() <= 1  # a slot, one row
    outs = jnp.asarray(rng.standard_normal((E, C, 24)), jnp.float32)
    want = moe.bucket_combine(outs, jnp.minimum(local, E - 1), rank, weights, C)
    got = jnp.einsum("ts,sd->td", mix, outs.reshape(E * C, -1), precision=hi)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-5)
    overflows = int((np.asarray(rank) >= C)[np.asarray(flat_e) < E].sum())
    assert (overflows > 0) == ((T, k, E, C) == (64, 4, 6, 4))
    assert int(np.asarray(place).sum()) == int((np.asarray(local) < E).sum()) - overflows
