"""Granite-4.0-H's plain reference (the dense members: no experts):
state-space layers as the token-by-token recurrence (a ``lax.scan`` over time,
no chunked form, no cache) behind a causal depthwise convolution, softmax
layers without rotation, a dense SwiGLU in every layer and the lineage's four
multipliers exactly as published; in straightforward float32 ``jax.numpy`` at
``highest`` precision, over weights dequantized from the file's raw Q40 bytes
one layer at a time.

``x_0 = embedding_multiplier * E[token]``. Per layer ``l``:
``h = x + residual_multiplier * Mixer_l(rmsnorm(x))``,
``y = h + residual_multiplier * SwiGLU(rmsnorm(h))``.
``logits = rmsnorm(x_L) W_cls^T / logits_scaling`` (``W_cls`` is the
embedding's matrix, which the file holds a second time in Q40).

* state-space (``u`` the normed input): ``[z | xBC | dt] = u W_in`` (widths
  inner | inner + 2N | heads, in that order); ``xBC = silu(conv(xBC) +
  b_conv)``; ``[x | B | C] = xBC`` (x as [heads, P]; ONE B and C of N values
  for all heads); ``dt = softplus(dt + dt_bias)`` and ``a = -exp(A_log)`` per
  head; ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t`` (h [heads, P, N]);
  ``y_t = h_t C_t + D x_t``; ``out = rmsnorm_w(y * silu(z)) W_out``, the norm
  over the whole inner width, the gate applied BEFORE it.
* softmax (layer ``l`` where ``l % attn_period == attn_offset``): ``q, k, v =
  u W``; causal ``softmax(attention_multiplier * q k^T) v`` (GQA, no
  rotation, no bias); ``out = attn W_o``.

Departures forced by the file format: Q40 weights (dequantized exactly), the
multipliers in millionths. What the published config leaves open is listed
under ``assumed`` in the configuration's file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.ops import HI, matmul, rmsnorm
from benchmark.reference.qfile import F32, Q40, named

ARCH = 0xABCD07
KEYS = {24: "attn_period", 27: "conv_taps", 41: "attn_offset", 42: "ssm_heads", 43: "ssm_head_dim",
        44: "ssm_state", 45: "embed_scale_micro", 46: "residual_scale_micro", 47: "attn_scale_micro",
        48: "logits_divisor_micro"}


def header(raw: dict[int, int]) -> dict:
    h = named(raw, KEYS)
    if h["weights_float_type"] != Q40 or h["hidden_act"] != 1:
        raise ValueError("the reference reads Q40 weights with SiLU only")
    if h["arch"] != ARCH:
        raise ValueError(f"unknown architecture {h['arch']:#x}")
    h["head_dim"] = h["dim"] // h["n_heads"]
    h["kv_dim"] = h["head_dim"] * h["n_kv_heads"]
    h["inner"] = h["ssm_heads"] * h["ssm_head_dim"]
    for key in ("embed", "residual", "attn"):
        h[key + "_scale"] = h[key + "_scale_micro"] / 1e6
    h["logits_divisor"] = h["logits_divisor_micro"] / 1e6
    return h


def is_softmax(h: dict, l: int) -> bool:
    return l % h["attn_period"] == h["attn_offset"]


def layout(h: dict):
    """(name, shape, kind) of every tensor, in file order."""
    dim, vocab, hidden = h["dim"], h["vocab_size"], h["hidden_dim"]
    inner, conv = h["inner"], h["inner"] + 2 * h["ssm_state"]
    yield "embedding", (vocab, dim), F32
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        if is_softmax(h, l):
            yield p + "q", (dim, dim), Q40
            yield p + "k", (h["kv_dim"], dim), Q40
            yield p + "v", (h["kv_dim"], dim), Q40
            yield p + "wo", (dim, dim), Q40
        else:
            yield p + "ssm_in", (inner + conv + h["ssm_heads"], dim), Q40
            yield p + "conv", (conv, h["conv_taps"]), F32
            yield p + "conv_bias", (conv,), F32
            yield p + "dt_bias", (h["ssm_heads"],), F32
            yield p + "a_log", (h["ssm_heads"],), F32
            yield p + "ssm_d", (h["ssm_heads"],), F32
            yield p + "ssm_norm", (inner,), F32
            yield p + "wo", (dim, inner), Q40
        yield p + "gate", (hidden, dim), Q40
        yield p + "down", (dim, hidden), Q40
        yield p + "up", (hidden, dim), Q40
        yield p + "rms_att", (dim,), F32
        yield p + "rms_ffn", (dim,), F32
    yield "rms_final", (dim,), F32
    yield "wcls", (vocab, dim), Q40


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "scale"))
def softmax_mixer(xn, wq, wk, wv, wo, *, n_heads, n_kv, scale):
    B, T, _ = xn.shape
    hd = wq.shape[0] // n_heads
    q = matmul(xn, wq).reshape(B, T, n_heads, hd)
    k = jnp.repeat(matmul(xn, wk).reshape(B, T, n_kv, hd), n_heads // n_kv, axis=2)
    v = jnp.repeat(matmul(xn, wv).reshape(B, T, n_kv, hd), n_heads // n_kv, axis=2)
    s = scale * jnp.einsum("bthd,bshd->bhts", q, k, precision=HI)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v, precision=HI)
    return matmul(o.reshape(B, T, n_heads * hd), wo)


def conv_silu(x, taps, bias):
    """Causal depthwise convolution over time, its bias, then SiLU. x [B, T,
    C], taps [C, K]: y_t = sum_j taps[:, j] x_{t-K+1+j}, zeros before the start."""
    K, T = taps.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + T] * taps[:, j] for j in range(K)) + bias)


def carry(h):
    """What a step hands the next: the state as it is, float32 (``assumed``:
    ``state``). The precision control computes with a rounding here."""
    return h


def skip(dx):
    """The skip connection around the recurrence, ``D x`` per head, as it
    joins the output. A control of the check plants a fault here."""
    return dx


@functools.partial(jax.jit, static_argnames=("n_heads", "n_state"))
def ssm_mixer(xn, w_in, taps, conv_bias, dt_bias, a_log, d, norm, wo, *, n_heads, n_state):
    B, T, _ = xn.shape
    inner = norm.shape[0]
    P = inner // n_heads
    zxbcdt = matmul(xn, w_in)
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * n_state], axis=-1)
    xbc = conv_silu(xbc, taps, conv_bias)
    x, Bm, Cm = jnp.split(xbc, [inner, inner + n_state], axis=-1)
    x = x.reshape(B, T, n_heads, P)
    dt = jax.nn.softplus(dt + dt_bias)  # [B, T, heads]
    a = -jnp.exp(a_log)

    def step(h, xs):
        x_t, b_t, c_t, dt_t = xs  # [B, heads, P], [B, N], [B, N], [B, heads]
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return carry(h), jnp.einsum("bhpn,bn->bhp", h, c_t, precision=HI) + skip(d[:, None] * x_t)

    h0 = jnp.zeros((B, n_heads, P, n_state), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(t, 1, 0) for t in (x, Bm, Cm, dt)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, T, inner)
    return matmul(rmsnorm(y * jax.nn.silu(z), norm), wo)


@jax.jit
def ffn(xn, gate, up, down):
    return matmul(jax.nn.silu(matmul(xn, gate)) * matmul(xn, up), down)


@jax.jit
def head(x, rms, wcls, divisor):
    return matmul(rmsnorm(x, rms), wcls) / divisor


def mixer(qf, l: int, xn):
    h, p = qf.h, f"layers.{l}."
    if is_softmax(h, l):
        return softmax_mixer(xn, qf.raw(p + "q"), qf.raw(p + "k"), qf.raw(p + "v"), qf.raw(p + "wo"),
                             n_heads=h["n_heads"], n_kv=h["n_kv_heads"], scale=h["attn_scale"])
    return ssm_mixer(xn, qf.raw(p + "ssm_in"), qf.f32(p + "conv"), qf.f32(p + "conv_bias"),
                     qf.f32(p + "dt_bias"), qf.f32(p + "a_log"), qf.f32(p + "ssm_d"),
                     qf.f32(p + "ssm_norm"), qf.raw(p + "wo"), n_heads=h["ssm_heads"],
                     n_state=h["ssm_state"])


def forward(qf, tokens: np.ndarray, positions: np.ndarray,
            router_gaps: list | None = None) -> np.ndarray:
    """Logits [B, len(positions), vocab] after a full causal pass over
    ``tokens`` [B, T]; layers are streamed from the file one at a time. A
    dense model: ``router_gaps`` stays empty."""
    h = qf.h
    x = h["embed_scale"] * jnp.asarray(qf.f32("embedding", rows=np.asarray(tokens)))
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        x = x + h["residual_scale"] * mixer(qf, l, rmsnorm(x, qf.f32(p + "rms_att")))
        x = x + h["residual_scale"] * ffn(rmsnorm(x, qf.f32(p + "rms_ffn")), qf.raw(p + "gate"),
                                          qf.raw(p + "up"), qf.raw(p + "down"))
    return np.asarray(head(x[:, np.asarray(positions)], qf.f32("rms_final"), qf.raw("wcls"),
                           h["logits_divisor"]))
