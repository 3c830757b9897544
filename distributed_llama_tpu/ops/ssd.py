"""The state-space (Mamba-2 SSD) recurrence of an ``ssm`` layer
(``ArchType.GRANITE_HYBRID``), per head ``h`` with a state of ``[P, N]``
(``P`` values a head, ``N`` state values each), ONE ``B`` and ``C`` for all
heads:

    h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t        a < 0, dt_t > 0
    y_t = h_t C_t

(the skip ``D x`` and the gated norm are the layer's, ``models.llama``).
:func:`ssd_step` is one decode step over a slab of rows, :func:`ssd_chunk` the
prefill of ``T`` tokens of one row, which walks sub-chunks of at most
``SUB_CHUNK`` tokens and hands the state from one to the next (and from one
prefill piece of the scheduler to the next, through the cache). Float32 at
``highest`` precision, the state never leaves f32.

Inside a sub-chunk (``g_t`` the running sum of ``dt a``, so ``g_t - g_s <= 0``
for ``s <= t`` and nothing is exponentiated that could overflow):

    y_t = exp(g_t) h_0 C_t + sum_{s<=t} exp(g_t - g_s) (C_t . B_s) dt_s x_s
    h_C = exp(g_C) h_0 + sum_s exp(g_C - g_s) dt_s x_s (x) B_s

``C B^T`` is one ``[C, C]`` product for all heads. A token with ``dt = 0`` is
the identity on the state (how a piece's pad rows are masked).

**The state's layout.** A head's ``[P, N]`` is stored transposed and
``pack`` heads side by side: ``[G, N, L]`` with ``L = pack * P`` and ``G =
heads / pack`` (:func:`state_shape`; at the published sizes two heads of 64
fill the 128 lanes, ``[32, 128, 128]``). Everything a head multiplies its
state by is then a row over ``L`` (its decay, ``dt x``) or a column over ``N``
(``B``, ``C``), the output is a sum over the sublane axis, and a step is five
vector operations a state element with no transpose and no lane reduction.
The XLA forms below use the same layout, so the tests' toy sizes index it as
the kernels do.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HI = jax.lax.Precision.HIGHEST
LANES = 128
# tokens of one sub-chunk of the prefill: the decay matrix of a head is
# [SUB_CHUNK, SUB_CHUNK] f32 (256 KiB), a piece of the scheduler (256) is one
SUB_CHUNK = 256
# head groups a grid step of the decode kernel holds: 16 states of [128, 128]
# f32 are 1 MiB, in and out double-buffered 4 MiB of VMEM
STEP_GROUPS = 16


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def head_pack(heads: int, head_dim: int) -> int:
    """Heads that share one row of the state's minor axis."""
    return math.gcd(heads, max(1, LANES // head_dim))


def state_shape(heads: int, head_dim: int, n_state: int) -> tuple[int, int, int]:
    """``[G, N, L]``: the shape one row's state has in a layer's cache."""
    pack = head_pack(heads, head_dim)
    return heads // pack, n_state, pack * head_dim


def pack_state(h: jax.Array) -> jax.Array:
    """The recurrence's own ``[..., H, P, N]`` -> the cache's ``[..., G, N, L]``."""
    *lead, H, P, N = h.shape
    pack = head_pack(H, P)
    h = h.reshape(*lead, H // pack, pack, P, N)
    return jnp.moveaxis(h, -1, -3).reshape(*lead, H // pack, N, pack * P)


def unpack_state(S: jax.Array, head_dim: int) -> jax.Array:
    """:func:`pack_state` in reverse: ``[..., G, N, L]`` -> ``[..., H, P, N]``."""
    *lead, G, N, L = S.shape
    pack = L // head_dim
    S = jnp.moveaxis(S.reshape(*lead, G, N, pack, head_dim), -3, -1)
    return S.reshape(*lead, G * pack, head_dim, N)


def _rows(v: jax.Array, head_dim: int, groups: int) -> jax.Array:
    """A value a head ``[..., H]`` as rows over the state's minor axis
    ``[..., G, L]``: each head's value under its own ``head_dim`` lanes."""
    return jnp.repeat(v, head_dim, axis=-1).reshape(*v.shape[:-1], groups, -1)


def _kernel_sizes(S: jax.Array, P: int) -> bool:
    """Whether the Pallas kernels take this state: full lanes, a square tile a
    group (the step kernel transposes B and C inside one)."""
    G, N, L = S.shape[-3:]
    return N == LANES and L == LANES and L % P == 0


def ssd_step(S, x, Bm, Cm, dt, a, active=None):
    """One step of every row. ``S`` [B_max, G, N, L] f32 (:func:`state_shape`),
    of which the first B rows step; ``x`` [B, H, P]; ``Bm``, ``Cm`` [B, N];
    ``dt`` [B, H]; ``a`` [H]; ``active`` [B] bool, rows where it is False keep
    their state (their output is garbage). Returns (y [B, H, P], new S
    [B_max, ...]). At the published sizes the step is one Pallas launch named
    ``ssd_step`` that reads and writes each stepping row's state once, in
    place; other sizes (the tests' toy heads) take :func:`ssd_step_xla`."""
    B, H, P = x.shape
    if _kernel_sizes(S, P) and S.shape[1] % STEP_GROUPS == 0:
        if active is None:
            active = jnp.ones((B,), bool)
        return _ssd_step_pallas(S, x, Bm, Cm, dt, a, active, interpret=_interpret_default())
    y, S_new = ssd_step_xla(S[:B], x, Bm, Cm, dt, a, active)
    if S.shape[0] != B:
        S_new = jax.lax.dynamic_update_slice_in_dim(S, S_new, 0, axis=0)
    return y, S_new


def ssd_step_xla(S, x, Bm, Cm, dt, a, active=None):
    """:func:`ssd_step` in plain XLA over exactly the rows given."""
    with jax.named_scope("ssd_step"):
        B, H, P = x.shape
        G = S.shape[1]
        decay = _rows(jnp.exp(dt * a), P, G)[:, :, None, :]  # [B, G, 1, L]
        dtx = (dt[..., None] * x).reshape(B, G, 1, -1)
        S2 = decay * S + Bm[:, None, :, None] * dtx
        y = jnp.einsum("bgnl,bn->bgl", S2, Cm, precision=HI).reshape(B, H, P)
        if active is not None:
            S2 = jnp.where(active[:, None, None, None], S2, S)
        return y, S2


def _step_kernel(active_ref, bc_ref, rows_ref, s_ref, y_ref, s_out_ref):
    """One row's ``STEP_GROUPS`` head groups. ``bc_ref`` [128, N]: row 0 the
    row's ``B``, row 1 its ``C`` (the rest zero), transposed here so that each
    lies along the state's N (sublane) axis and broadcasts over the lanes.
    ``rows_ref`` [2, STEP_GROUPS, L]: the groups' decays, then their ``dt x``."""
    live = active_ref[pl.program_id(0)] != 0
    cols = jnp.transpose(bc_ref[0])  # [N, 128]
    b_col, c_col = cols[:, 0:1], cols[:, 1:2]
    for g in range(STEP_GROUPS):
        S = s_ref[0, g]
        S2 = rows_ref[0, 0, g : g + 1, :] * S + b_col * rows_ref[0, 1, g : g + 1, :]
        y_ref[0, g : g + 1, :] = jnp.sum(S2 * c_col, axis=0, keepdims=True)
        s_out_ref[0, g] = jnp.where(live, S2, S)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_step_pallas(S, x, Bm, Cm, dt, a, active, interpret: bool):
    B, H, P = x.shape
    _, G, N, L = S.shape
    rows = jnp.stack([_rows(jnp.exp(dt * a), P, G), (dt[..., None] * x).reshape(B, G, L)], axis=1)
    bc = jnp.pad(jnp.stack([Bm, Cm], axis=1), ((0, 0), (0, LANES - 2), (0, 0)))  # [B, 128, N]
    state_block = (1, STEP_GROUPS, N, L)
    y, S_new = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, G // STEP_GROUPS),
            in_specs=[
                pl.BlockSpec((1, LANES, N), lambda b, g, act: (b, 0, 0)),
                pl.BlockSpec((1, 2, STEP_GROUPS, L), lambda b, g, act: (b, 0, g, 0)),
                pl.BlockSpec(state_block, lambda b, g, act: (b, g, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, STEP_GROUPS, L), lambda b, g, act: (b, g, 0)),
                pl.BlockSpec(state_block, lambda b, g, act: (b, g, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, G, L), jnp.float32),
            jax.ShapeDtypeStruct(S.shape, jnp.float32),
        ],
        # the state is updated in place: rows past B are never visited
        input_output_aliases={3: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        name="ssd_step",
    )(active.astype(jnp.int32), bc, rows, S)
    return y.reshape(B, H, P), S_new


def _sub_chunk(S0, dtx, Bm, Cm, g, P):
    """One sub-chunk of one row in XLA: ``dtx`` [C, G, L] (``dt x``), ``Bm``,
    ``Cm`` [C, N], ``g`` [C, H] the running sum of ``dt a`` inside the
    sub-chunk, ``S0`` [G, N, L]. Returns (y [C, G, L], the state after it)."""
    C, G = dtx.shape[0], dtx.shape[1]
    pack = g.shape[1] // G
    gl = _rows(g, P, G)  # [C, G, L]
    y = jnp.einsum("tn,gnl->tgl", Cm, S0, precision=HI) * jnp.exp(gl)
    M = jnp.einsum("tn,sn->ts", Cm, Bm, precision=HI)
    # a head's decay from s to t, only where s <= t (elsewhere the difference is positive)
    causal = jnp.tril(jnp.ones((C, C), bool))[:, :, None]
    W = jnp.where(causal, jnp.exp(jnp.where(causal, g[:, None, :] - g[None, :, :], 0.0)), 0.0)
    W = (M[:, :, None] * W).reshape(C, C, G, pack)
    y = y + jnp.einsum(
        "tsgj,sgjp->tgjp", W, dtx.reshape(C, G, pack, P), precision=HI
    ).reshape(C, G, -1)
    S = jnp.exp(gl[-1])[:, None, :] * S0 + jnp.einsum(
        "sn,sgl->gnl", Bm, dtx * jnp.exp(gl[-1][None] - gl), precision=HI
    )
    return y, S


def ssd_chunk(S0, x, Bm, Cm, dt, a, n_real=None):
    """``T`` tokens of one row from state ``S0`` [G, N, L]: ``x`` [T, H, P],
    ``Bm``, ``Cm`` [T, N], ``dt`` [T, H], ``a`` [H]. Tokens at and past
    ``n_real`` (the padding of a prefill bucket) leave the state untouched.
    Returns (y [T, H, P], the state after the last real token). At the
    published sizes the sub-chunks of a head group are walked by one Pallas
    launch named ``ssd_chunk``; other sizes take the XLA scan."""
    with jax.named_scope("ssd_chunk"):
        T, H, P = x.shape
        G = S0.shape[0]
        if n_real is not None:
            dt = jnp.where((jnp.arange(T) < n_real)[:, None], dt, 0.0)
        C = min(SUB_CHUNK, T)
        pad = -T % C
        if pad:
            # dt 0: padded tokens are the identity on the state
            x, Bm, Cm, dt = (
                jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)) for v in (x, Bm, Cm, dt)
            )
        n = (T + pad) // C
        dtx = (dt[..., None] * x).reshape(n * C, G, -1)
        g = jnp.cumsum((dt * a).reshape(n, C, H), axis=1)  # from each sub-chunk's start
        if _kernel_sizes(S0, P) and C % 8 == 0:
            y, S = _ssd_chunk_pallas(
                S0, dtx, Bm, Cm, g.reshape(n * C, H), C, P, interpret=_interpret_default()
            )
            return y[:T].reshape(T, H, P), S

        def body(S, xs):
            y, S = _sub_chunk(S, *xs, P)
            return S, y

        split = lambda v: v.reshape((n, C) + v.shape[1:])
        S, y = jax.lax.scan(body, S0, (split(dtx), split(Bm), split(Cm), g))
        return y.reshape(n * C, H, P)[:T], S


def _chunk_kernel(C: int, n: int, P: int):
    """One head group: its ``n`` sub-chunks of ``C`` tokens in turn, the state
    in VMEM between them. ``m_ref`` [n, C, C] holds ``C B^T`` of each
    sub-chunk (one product for all heads, made outside), ``gl_ref`` the
    running log-decay as rows over the lanes and ``gt_ref`` [pack, T] the same
    with the tokens along the lanes: a head's decay matrix ``exp(g_t - g_s)``
    is the difference of a column of the one and a row of the other."""

    def dot(x, y, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(x, y, dims, precision=HI, preferred_element_type=jnp.float32)

    tn = (((0,), (0,)), ((), ()))  # x^T @ y

    def kernel(dtx_ref, gl_ref, gt_ref, b_ref, c_ref, m_ref, s0_ref, y_ref, s_ref):
        L = s0_ref.shape[2]
        causal = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
                  >= jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
        lane = jax.lax.broadcasted_iota(jnp.int32, (C, L), 1)
        S = s0_ref[0]
        for c in range(n):
            rows = pl.ds(c * C, C)
            dtx, gl = dtx_ref[rows, :], gl_ref[rows, :]
            y = dot(c_ref[rows, :], S) * jnp.exp(gl)
            for j in range(L // P):
                # head j of the group: its lanes of dt x, the others zero, so that the
                # product lands in its own lanes of the sum
                decay = jnp.exp(jnp.where(causal, gl[:, j * P : j * P + 1] - gt_ref[0, j : j + 1, rows], 0.0))
                mine = (lane >= j * P) & (lane < (j + 1) * P)
                y = y + dot(jnp.where(causal, m_ref[c] * decay, 0.0), jnp.where(mine, dtx, 0.0))
            y_ref[rows, :] = y
            last = gl[C - 1 : C, :]
            S = jnp.exp(last) * S + dot(b_ref[rows, :], dtx * jnp.exp(last - gl), tn)
        s_ref[0] = S

    return kernel


@functools.partial(jax.jit, static_argnames=("C", "P", "interpret"))
def _ssd_chunk_pallas(S0, dtx, Bm, Cm, g, C: int, P: int, interpret: bool):
    T, G, L = dtx.shape
    N, n, pack = Bm.shape[1], T // C, L // P
    M = jnp.einsum("ctn,csn->cts", Cm.reshape(n, C, N), Bm.reshape(n, C, N), precision=HI)
    gl = _rows(g, P, G).reshape(T, G * L)
    gt = jnp.swapaxes(g, 0, 1).reshape(G, pack, T)
    tokens = pl.BlockSpec((T, L), lambda h: (0, h))
    shared = pl.BlockSpec((T, N), lambda h: (0, 0))
    state = pl.BlockSpec((1, N, L), lambda h: (h, 0, 0))
    y, S = pl.pallas_call(
        _chunk_kernel(C, n, P),
        grid=(G,),
        in_specs=[tokens, tokens, pl.BlockSpec((1, pack, T), lambda h: (h, 0, 0)), shared, shared,
                  pl.BlockSpec((n, C, C), lambda h: (0, 0, 0)), state],
        out_specs=[tokens, state],
        out_shape=[
            jax.ShapeDtypeStruct((T, G * L), jnp.float32),
            jax.ShapeDtypeStruct(S0.shape, jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="ssd_chunk",
    )(dtx.reshape(T, G * L), gl, gt, Bm, Cm, M, S0)
    return y, S
