"""``ops.ssd``: the decode step and the chunked prefill of the state-space
(SSD) recurrence against a token-by-token float32 recurrence in the
recurrence's own layout (``h`` [H, P, N]), at the published head sizes (P 64,
N 128: the Pallas kernels, in interpret mode here) and at toy sizes (XLA)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.ops import ssd

HI = jax.lax.Precision.HIGHEST
# (heads, P, N): the published head (64 heads of it step as 32 groups; 32 heads are one grid
# step of the decode kernel) and two toy sizes, one whose heads all share a row of lanes and
# one whose head count the lanes' share does not divide
SIZES = [pytest.param(32, 64, 128, id="published-head-pallas"),
         pytest.param(4, 8, 16, id="toy-xla"),
         pytest.param(3, 16, 8, id="toy-odd-heads-xla")]
# float32 sums in another order: the chunked form adds a sub-chunk's tokens in one product
TOL = dict(rtol=2e-4, atol=2e-5)


def _inputs(seed, T, H, P, N, rows=None):
    """Seeded inputs of ``T`` tokens (of ``rows`` rows where given): rates that
    forget within a few tokens in some heads and carry a whole piece in others."""
    r = np.random.default_rng(seed)
    lead = (T,) if rows is None else (rows, T)
    x = r.standard_normal(lead + (H, P)).astype(np.float32)
    Bm = r.standard_normal(lead + (N,)).astype(np.float32) / np.sqrt(N)
    Cm = r.standard_normal(lead + (N,)).astype(np.float32)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(0.5), lead + (H,))).astype(np.float32)
    a = -np.exp(r.uniform(np.log(0.5), np.log(16.0), (H,))).astype(np.float32)
    return tuple(jnp.asarray(v) for v in (x, Bm, Cm, dt, a))


def recurrence(h0, x, Bm, Cm, dt, a):
    """Token by token: ``h0`` [H, P, N] -> (y [T, H, P], h_T)."""
    def step(h, xs):
        x_t, b_t, c_t, dt_t = xs
        h = jnp.exp(dt_t * a)[:, None, None] * h + (dt_t[:, None] * x_t)[..., None] * b_t
        return h, jnp.einsum("hpn,n->hp", h, c_t, precision=HI)

    h, y = jax.lax.scan(step, h0, (x, Bm, Cm, dt))
    return y, h


def test_the_state_layout_round_trips_and_fills_the_lanes():
    assert ssd.state_shape(64, 64, 128) == (32, 128, 128)
    assert ssd.state_shape(4, 8, 16) == (1, 16, 32)
    h = jnp.arange(3 * 6 * 16 * 8, dtype=jnp.float32).reshape(3, 6, 16, 8)
    S = ssd.pack_state(h)
    assert S.shape == (3,) + ssd.state_shape(6, 16, 8)
    np.testing.assert_array_equal(ssd.unpack_state(S, 16), h)
    # head 1's value p with state n sits at [group 0, n, P + p]: two heads share a row
    assert S[0, 0, 5, 16 + 3] == h[0, 1, 3, 5]


@pytest.mark.parametrize("H,P,N", SIZES)
def test_one_pass_is_pieces_of_unequal_length_is_prefill_then_steps(H, P, N):
    T = 40
    x, Bm, Cm, dt, a = _inputs(1, T, H, P, N)
    h0 = jnp.asarray(np.random.default_rng(2).standard_normal((H, P, N)).astype(np.float32))
    y_ref, h_ref = recurrence(h0, x, Bm, Cm, dt, a)

    # one pass
    y, S = ssd.ssd_chunk(ssd.pack_state(h0), x, Bm, Cm, dt, a)
    np.testing.assert_allclose(y, y_ref, **TOL)
    np.testing.assert_allclose(ssd.unpack_state(S, P), h_ref, **TOL)

    # pieces of 24 and 16 tokens, each padded to a bucket of 32 with rows that must not count
    S, ys = ssd.pack_state(h0), []
    for lo, hi in ((0, 24), (24, 40)):
        pad = lambda v: jnp.pad(v[lo:hi], ((0, 32 - (hi - lo)),) + ((0, 0),) * (v.ndim - 1),
                                constant_values=0.7)
        y, S = ssd.ssd_chunk(S, pad(x), pad(Bm), pad(Cm), pad(dt), a, n_real=jnp.int32(hi - lo))
        ys.append(y[: hi - lo])
    np.testing.assert_allclose(jnp.concatenate(ys), y_ref, **TOL)
    np.testing.assert_allclose(ssd.unpack_state(S, P), h_ref, **TOL)

    # prefill of 32 tokens, then 8 decode steps of a slab whose row 1 is this row
    _, S = ssd.ssd_chunk(ssd.pack_state(h0), x[:32], Bm[:32], Cm[:32], dt[:32], a)
    slab = jnp.stack([jnp.full_like(S, 3.0), S, jnp.full_like(S, 5.0)])
    other = _inputs(7, 8, H, P, N)
    for t in range(32, 40):
        row = lambda v, o: jnp.stack([o[t - 32], v[t]])
        y, slab = ssd.ssd_step(slab, row(x, other[0]), row(Bm, other[1]), row(Cm, other[2]),
                               row(dt, other[3]), a, jnp.asarray([False, True]))
        np.testing.assert_allclose(y[1], y_ref[t], **TOL)
    np.testing.assert_allclose(ssd.unpack_state(slab[1], P), h_ref, **TOL)
    # the inactive row and the row past the bucket kept their state to the bit
    np.testing.assert_array_equal(slab[0], jnp.full_like(S, 3.0))
    np.testing.assert_array_equal(slab[2], jnp.full_like(S, 5.0))


@pytest.mark.parametrize("H,P,N", SIZES)
def test_a_token_with_dt_zero_is_the_identity_on_the_state(H, P, N):
    x, Bm, Cm, dt, a = _inputs(3, 8, H, P, N)
    S0 = ssd.pack_state(
        jnp.asarray(np.random.default_rng(4).standard_normal((H, P, N)).astype(np.float32)))
    y, S = ssd.ssd_chunk(S0, x, Bm, Cm, jnp.zeros_like(dt), a)
    np.testing.assert_array_equal(S, S0)
    # ... and reads the state it found: y_t = h_0 C_t
    np.testing.assert_allclose(
        y, jnp.einsum("hpn,tn->thp", ssd.unpack_state(S0, P), Cm, precision=HI), **TOL)
    y1, S1 = ssd.ssd_step(S0[None], x[:1], Bm[:1], Cm[:1], jnp.zeros_like(dt[:1]), a)
    np.testing.assert_array_equal(S1[0], S0)
    np.testing.assert_allclose(y1, y[:1], **TOL)


def test_a_piece_longer_than_a_sub_chunk_hands_the_state_on(monkeypatch):
    """Sub-chunks inside one call (the kernel's own choice) give the recurrence's result."""
    monkeypatch.setattr(ssd, "SUB_CHUNK", 16)
    for H, P, N in ((2, 64, 128), (4, 8, 16)):
        x, Bm, Cm, dt, a = _inputs(5, 40, H, P, N)
        h0 = jnp.zeros((H, P, N), jnp.float32)
        y_ref, h_ref = recurrence(h0, x, Bm, Cm, dt, a)
        y, S = ssd.ssd_chunk(ssd.pack_state(h0), x, Bm, Cm, dt, a, n_real=jnp.int32(40))
        np.testing.assert_allclose(y, y_ref, **TOL)
        np.testing.assert_allclose(ssd.unpack_state(S, P), h_ref, **TOL)


def test_a_fast_head_over_a_long_piece_neither_overflows_nor_underflows_to_nan():
    """exp is taken of differences g_t - g_s <= 0 only: a head that forgets in
    one token (dt a = -40 a step, exp(+40 * 256) if it were factored) stays finite."""
    H, P, N, T = 2, 64, 128, 256
    x, Bm, Cm, dt, a = _inputs(6, T, H, P, N)
    dt = jnp.full_like(dt, 2.5)
    a = jnp.asarray([-16.0, -1e-3])
    y_ref, h_ref = recurrence(jnp.zeros((H, P, N)), x, Bm, Cm, dt, a)
    y, S = ssd.ssd_chunk(ssd.pack_state(jnp.zeros((H, P, N))), x, Bm, Cm, dt, a)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(S).all())
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(ssd.unpack_state(S, P), h_ref, rtol=2e-4, atol=2e-4)
