"""The held experts' bucket rule over the benchmark's share-holding
configurations (``models.moe.held_bucket_rows``): the three accepted ratios
keep their buckets, so their programs do not change; a token that chooses a
seventh of the experts gets a bucket UNDER its step's rows, so that a 256-row
piece computes the rows that chose an expert and not every row."""

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


@pytest.mark.parametrize("k,routed,decode,piece", [
    (8, 320, 8, 32),  # solar-open2-250b-q40-8l-ep16
    (8, 128, 16, 64),  # k-exaone-236b-q40-8l-ep8
    (4, 64, 16, 64),  # glm-4.7-flash-q40-stage0
    (10, 72, 32, 128),  # granite-4.0-h-small-q40-10l-ep4: four times the share is the whole step
])
def test_the_bucket_by_experts_a_token_over_the_routers_width(k, routed, decode, piece):
    cfg = config(k, routed, 4)
    assert [moe.held_bucket_rows(cfg, rows) for rows in (1, 8, 32, 64)] == [decode] * 4
    assert [moe.held_bucket_rows(cfg, rows) for rows in (128, 256)] == [piece] * 2
    # a piece's bucket lies under its rows: the bucketed arm is there to be taken
    assert moe.held_bucket_rows(cfg, 256) < 256
    # ... and holds the even share with room: twice it at the least
    assert piece >= 2 * 256 * k / routed and decode >= 8


def test_a_256_row_piece_at_10_of_72_with_even_routing_takes_the_bucketed_arm():
    """18 of 72 experts held, 10 chosen a token, 256 rows whose choices go
    round the experts evenly (35 or 36 rows an expert): every layer's counts
    say ``bucketed`` (``dllama_moe_piece_layers_total{path}`` is fed from
    them) and the result is the every-row arm's to float32's rounding."""
    cfg = config(10, 72, 18)
    rng = np.random.default_rng(3)
    T, D, W, E = 256, cfg.dim, cfg.moe_hidden_dim, cfg.n_experts
    xn = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    lp = {"experts_gate_up": jnp.asarray(rng.standard_normal((E, D, 2 * W)) / 8, jnp.float32),
          "experts_down": jnp.asarray(rng.standard_normal((E, W, D)) / 6, jnp.float32)}
    # token t chooses experts t, t + 7, ..., t + 63 (mod 72): ten distinct ones, each expert 35 or 36 times
    top_idx = jnp.asarray((np.arange(T)[:, None] + 7 * np.arange(10)[None, :]) % 72, jnp.int32)
    top_vals = jnp.asarray(rng.dirichlet(np.ones(10), T), jnp.float32)
    counts = np.bincount(np.asarray(top_idx).ravel(), minlength=72)
    assert counts.max() <= 36 < moe.held_bucket_rows(cfg, T) == 128
    # the path a layer took is a traced value: a program returns it with the result, as the
    # scheduler's prefill programs do
    run = jax.jit(lambda x, idx: run_with(cfg, lp, x, top_vals, idx))
    got, took_every_row = run(xn, top_idx)
    assert took_every_row.tolist() == [0]  # the bucketed arm: `bucketed` moves, `every_row` does not
    # the same sum as every held expert over every row, mixed by the mostly-zero weights
    local = np.asarray(top_idx)
    weights = np.zeros((T, 72), np.float32)
    np.put_along_axis(weights, local, np.asarray(top_vals), axis=1)
    fused = np.einsum("td,edf->etf", np.asarray(xn), np.asarray(lp["experts_gate_up"]))
    h = fused[..., :W] / (1 + np.exp(-fused[..., :W])) * fused[..., W:]
    want = np.einsum("te,etd->td", weights[:, :E], np.einsum("etf,efd->etd", h, np.asarray(lp["experts_down"])))
    assert np.abs(np.asarray(got) - want).max() <= 2e-5 * np.abs(want).max()
    # a piece in which one expert has more rows than the bucket takes the every-row arm: exact too
    _, took_every_row = run(xn, top_idx.at[:140, 0].set(3))
    assert took_every_row.tolist() == [1]


def run_with(cfg, lp, x, top_vals, top_idx):
    with moe.collect_piece_paths() as paths:
        out = moe._held_experts(cfg, x, lp, top_vals, top_idx)
    return out, jnp.stack(paths)
