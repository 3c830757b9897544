"""The gated delta-rule recurrence of a linear-attention layer
(``ArchType.SOLAR_OPEN2``), per head with a state ``S`` of ``[dk, dv]``:

    S' = Diag(alpha_t) S_{t-1}                alpha_t = exp(a_t) in (0, 1)^dk
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

:func:`kda_step` is one decode step over a slab of rows, :func:`kda_chunk`
the prefill of ``T`` tokens of one row, which walks sub-chunks of
``SUB_CHUNK`` tokens and hands the state from one to the next (and from one
prefill chunk of the scheduler to the next, through the cache). At the
published head size (128) each is one Pallas kernel, at other sizes plain
XLA; float32 at ``highest`` precision either way, the state never leaves f32.

Inside a sub-chunk (positions 1..C, ``g_t`` the running sum of ``a``):

    U = (I + A)^-1 (V - K+ S_0)      A_ts = beta_s <k+_t, k-_s>, s < t
    O = Q+ S_0 + (B . tril(Q+ K-^T)) U
    S_C = Diag(exp(g_C)) (S_0 + (beta . K-)^T U)

with ``k+ = exp(g) k``, ``k- = exp(-g) k``, ``q+ = exp(g) q``. ``exp(-g)``
grows with the sub-chunk: 32 tokens at a decay of 0.5 a step reach 2**32,
far inside float32; a decay under 0.07 a step for a whole sub-chunk would
overflow, which no softplus-parametrised gate reaches with sane weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB_CHUNK = 32
HI = jax.lax.Precision.HIGHEST
# heads a grid step of the decode kernel holds: 16 states of [128, 128] f32
# are 1 MiB, in and out double-buffered 4 MiB of VMEM
STEP_HEADS = 16
LANES = 128


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def kda_step(S, q, k, v, a, beta, active=None):
    """One step of every row. ``S`` [B_max, H, dk, dv] f32, of which the
    first B rows step; ``q``, ``k``, ``a`` [B, H, dk]; ``v`` [B, H, dv];
    ``beta`` [B, H]; ``active`` [B] bool, rows where it is False keep their
    state (their output is garbage). Returns (o [B, H, dv], new S [B_max,
    ...]). At the published head size the step is one Pallas launch named
    ``kda_step`` that reads and writes each state once, in place; other
    sizes (the tests' toy heads) take :func:`kda_step_xla`."""
    B, H, dk = q.shape
    if dk == LANES and v.shape[-1] == LANES and H % STEP_HEADS == 0:
        if active is None:
            active = jnp.ones((B,), bool)
        return _kda_step_pallas(S, q, k, v, a, beta, active, interpret=_interpret_default())
    o, S_new = kda_step_xla(S[:B], q, k, v, a, beta, active)
    if S.shape[0] != B:
        S_new = jax.lax.dynamic_update_slice_in_dim(S, S_new, 0, axis=0)
    return o, S_new


def _step_kernel(active_ref, cols_ref, v_ref, s_ref, o_ref, s_out_ref):
    """One row's ``STEP_HEADS`` heads. ``cols_ref`` [128, 128]: rows 0..15
    the heads' decays, 16..31 beta * k, 32..47 k, 48..63 q (the rest zero),
    each a vector over dk on the lanes; transposed here so that a head's
    vector lies along the state's dk (sublane) axis and broadcasts over dv."""
    live = active_ref[pl.program_id(0)] != 0
    cols = jnp.transpose(cols_ref[0, 0])  # [dk, 4 * STEP_HEADS (+ padding)]
    for h in range(STEP_HEADS):
        S = s_ref[0, h]
        alpha, kb, k, q = (cols[:, i * STEP_HEADS + h][:, None] for i in range(4))
        S1 = alpha * S
        u = v_ref[0, h][None, :] - jnp.sum(S1 * k, axis=0, keepdims=True)
        S2 = S1 + kb * u
        o_ref[0, h] = jnp.sum(S2 * q, axis=0)
        s_out_ref[0, h] = jnp.where(live, S2, S)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_step_pallas(S, q, k, v, a, beta, active, interpret: bool):
    B, H, dk = q.shape
    groups = H // STEP_HEADS
    packed = jnp.stack([jnp.exp(a), beta[..., None] * k, k, q], axis=1)  # [B, 4, H, dk]
    packed = packed.reshape(B, 4, groups, STEP_HEADS, dk).transpose(0, 2, 1, 3, 4)
    packed = packed.reshape(B, groups, 4 * STEP_HEADS, dk)
    packed = jnp.pad(packed, ((0, 0), (0, 0), (0, LANES - 4 * STEP_HEADS), (0, 0)))
    state_block = (1, STEP_HEADS, dk, v.shape[-1])
    o, S_new = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, groups),
            in_specs=[
                pl.BlockSpec((1, 1, LANES, dk), lambda b, g, act: (b, g, 0, 0)),
                pl.BlockSpec((1, STEP_HEADS, v.shape[-1]), lambda b, g, act: (b, g, 0)),
                pl.BlockSpec(state_block, lambda b, g, act: (b, g, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, STEP_HEADS, v.shape[-1]), lambda b, g, act: (b, g, 0)),
                pl.BlockSpec(state_block, lambda b, g, act: (b, g, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
            jax.ShapeDtypeStruct(S.shape, jnp.float32),
        ],
        # the state is updated in place: rows past B are never visited
        input_output_aliases={3: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        name="kda_step",
    )(active.astype(jnp.int32), packed, v, S)
    return o, S_new


def kda_step_xla(S, q, k, v, a, beta, active=None):
    """:func:`kda_step` in plain XLA over exactly the rows given."""
    with jax.named_scope("kda_step"):
        S1 = jnp.exp(a)[..., None] * S
        u = v - jnp.einsum("bhkv,bhk->bhv", S1, k, precision=HI)
        S2 = S1 + (beta[..., None] * k)[..., None] * u[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", S2, q, precision=HI)
        if active is not None:
            S2 = jnp.where(active[:, None, None, None], S2, S)
        return o, S2


def _sub_chunk(S0, q, k, v, a, beta):
    """One sub-chunk of one row: ``q``, ``k``, ``a`` [C, H, dk], ``v``
    [C, H, dv], ``beta`` [C, H], ``S0`` [H, dk, dv]."""
    C = q.shape[0]
    g = jnp.cumsum(a, axis=0)
    up, down = jnp.exp(g), jnp.exp(-g)
    k_up, k_down, q_up = k * up, k * down, q * up
    lower = jnp.tril(jnp.ones((C, C), bool), -1)[None]
    A = jnp.einsum("thk,shk->hts", k_up, k_down, precision=HI) * beta.T[:, None, :]
    A = jnp.where(lower, A, 0.0)
    rhs = v - jnp.einsum("thk,hkv->thv", k_up, S0, precision=HI)
    U = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=A.dtype), jnp.swapaxes(rhs, 0, 1), lower=True, unit_diagonal=True
    )  # [H, C, dv]
    P = jnp.einsum("thk,shk->hts", q_up, k_down, precision=HI) * beta.T[:, None, :]
    P = jnp.where(lower | jnp.eye(C, dtype=bool)[None], P, 0.0)
    o = jnp.einsum("thk,hkv->thv", q_up, S0, precision=HI) + jnp.swapaxes(
        jnp.einsum("hts,hsv->htv", P, U, precision=HI), 0, 1
    )
    kb = k_down * beta[..., None]
    S = up[-1][..., None] * (S0 + jnp.einsum("shk,hsv->hkv", kb, U, precision=HI))
    return o, S


def kda_chunk(S0, q, k, v, a, beta, n_real=None):
    """``T`` tokens of one row from state ``S0`` [H, dk, dv]: ``q``, ``k``,
    ``a`` [T, H, dk], ``v`` [T, H, dv], ``beta`` [T, H]. Tokens at and past
    ``n_real`` (the padding of a prefill bucket) leave the state untouched.
    Returns (o [T, H, dv], the state after the last real token). At the
    published head size the sub-chunks of a head are walked by one Pallas
    launch named ``kda_chunk``; other sizes take the XLA scan."""
    with jax.named_scope("kda_chunk"):
        T = q.shape[0]
        if n_real is not None:
            real = jnp.arange(T) < n_real
            a = jnp.where(real[:, None, None], a, 0.0)
            beta = jnp.where(real[:, None], beta, 0.0)
        C = min(SUB_CHUNK, T)
        pad = -T % C
        if pad:
            # decay 1 and beta 0: padded tokens are the identity on the state
            q, k, v, a, beta = (
                jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) for x in (q, k, v, a, beta)
            )
        n = (T + pad) // C
        if q.shape[-1] == LANES and v.shape[-1] == LANES and C % 8 == 0 and C & (C - 1) == 0:
            o, S = _kda_chunk_pallas(S0, q, k, v, a, beta, C, interpret=_interpret_default())
            return o[:T], S

        def body(S, xs):
            o, S = _sub_chunk(S, *xs)
            return S, o

        split = lambda x: x.reshape((n, C) + x.shape[1:])
        S, o = jax.lax.scan(body, S0, tuple(split(x) for x in (q, k, v, a, beta)))
        return o.reshape((n * C,) + o.shape[2:])[:T], S


def _chunk_kernel(C: int, n: int):
    """One head: its ``n`` sub-chunks of ``C`` tokens in turn, the state in
    registers and VMEM between them. The inputs come with the running decay
    already applied (``q+``, ``k+``, ``beta k-`` of the module docstring) and
    the decay over each whole sub-chunk as a row of ``d_ref``. The unit
    lower-triangular system is solved by the nilpotent product ``(I + A)^-1 =
    (I + N)(I + N^2)(I + N^4) ..``, ``N = -A``: ``log2 C`` small matmuls
    instead of ``C`` dependent steps; with unit keys and ``beta < 2`` the
    powers shrink (|A| stays well under 1 unless consecutive keys are nearly
    parallel, where the recurrence itself is as ill-conditioned)."""

    def dot(x, y, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(x, y, dims, precision=HI, preferred_element_type=jnp.float32)

    nt = (((1,), (1,)), ((), ()))  # x @ y^T
    tn = (((0,), (0,)), ((), ()))  # x^T @ y

    def kernel(qu_ref, ku_ref, kb_ref, v_ref, d_ref, s0_ref, o_ref, s_ref):
        row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        eye_c = (row == col).astype(jnp.float32)
        dk = s0_ref.shape[1]
        eye_k = (
            jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
        ).astype(jnp.float32)
        S = s0_ref[0]
        for c in range(n):
            rows = pl.ds(c * C, C)
            qu, ku, kb, v = qu_ref[0, rows, :], ku_ref[0, rows, :], kb_ref[0, rows, :], v_ref[0, rows, :]
            N = jnp.where(row > col, -dot(ku, kb, nt), 0.0)
            inv, power = eye_c + N, N
            for _ in range(C.bit_length() - 2):
                power = dot(power, power)
                inv = inv + dot(inv, power)
            U = dot(inv, v - dot(ku, S))
            P = jnp.where(row >= col, dot(qu, kb, nt), 0.0)
            o_ref[0, rows, :] = dot(qu, S) + dot(P, U)
            # Diag(decay over the sub-chunk) from the left, as a matmul: the decay is a
            # vector along the lanes, the state's dk runs along the sublanes
            S = dot(eye_k * d_ref[0, c : c + 1, :], S + dot(kb, U, tn))
        s_ref[0] = S

    return kernel


@functools.partial(jax.jit, static_argnames=("C", "interpret"))
def _kda_chunk_pallas(S0, q, k, v, a, beta, C: int, interpret: bool):
    T, H, dk = q.shape
    n = T // C
    g = jnp.cumsum(a.reshape(n, C, H, dk), axis=1)
    up, down = jnp.exp(g).reshape(T, H, dk), jnp.exp(-g).reshape(T, H, dk)
    heads_first = lambda x: jnp.swapaxes(x, 0, 1)  # [H, T, .]
    operands = [heads_first(x) for x in (q * up, k * up, beta[..., None] * k * down, v)]
    d_end = heads_first(jnp.exp(g[:, -1]))  # [H, n, dk]
    tokens = pl.BlockSpec((1, T, dk), lambda h: (h, 0, 0))
    state = pl.BlockSpec((1, dk, v.shape[-1]), lambda h: (h, 0, 0))
    o, S = pl.pallas_call(
        _chunk_kernel(C, n),
        grid=(H,),
        in_specs=[tokens, tokens, tokens, tokens, pl.BlockSpec((1, n, dk), lambda h: (h, 0, 0)), state],
        out_specs=[tokens, state],
        out_shape=[
            jax.ShapeDtypeStruct((H, T, v.shape[-1]), jnp.float32),
            jax.ShapeDtypeStruct(S0.shape, jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="kda_chunk",
    )(*operands, d_end, S0)
    return jnp.swapaxes(o, 0, 1), S


def causal_conv(x, tail, taps, n_real=None):
    """Causal depthwise convolution over time. ``x`` [T, C] the new inputs,
    ``tail`` [K-1, C] the inputs before them (zeros at a sequence's start),
    ``taps`` [C, K], the last tap on the newest input. Returns (y [T, C],
    the tail after the last real input)."""
    K = taps.shape[1]
    T = x.shape[0]
    window = jnp.concatenate([tail, x], axis=0)  # [K-1+T, C]
    y = sum(window[j : j + T] * taps[:, j] for j in range(K))
    end = T if n_real is None else n_real
    return y, jax.lax.dynamic_slice_in_dim(window, end, K - 1, axis=0)


def causal_conv_step(x, tail, taps, active=None):
    """:func:`causal_conv` for one new input of every row: ``x`` [B, C],
    ``tail`` [B, K-1, C]."""
    window = jnp.concatenate([tail, x[:, None]], axis=1)  # [B, K, C]
    y = jnp.einsum("bkc,ck->bc", window, taps, precision=HI)
    new_tail = window[:, 1:]
    if active is not None:
        new_tail = jnp.where(active[:, None, None], new_tail, tail)
    return y, new_tail
