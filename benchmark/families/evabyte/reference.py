"""EvaByte's plain reference: every layer EVA attention, in straightforward
float32 ``jax.numpy`` at ``highest`` precision, over weights dequantized from
the file's raw Q40 bytes one layer at a time. No cache and no kernel: every
summary is recomputed from the whole sequence in every pass.

Per layer (``x`` the residual stream, eps 1e-5, ``s = head_dim ** -0.5``, ``W``
the window, ``c`` the chunk):

* ``h = rmsnorm(x) * (1 + g_att)``; ``q = h W_q``, ``k = h W_k``, ``v = h W_v``
  (``n_heads`` heads of ``head_dim`` each, no grouping); q and k rotated at
  their absolute positions (pairs ``(j, j + head_dim / 2)``, ``theta ** (-2j /
  head_dim)``).
* summaries: for chunk ``m`` (positions ``c m .. c m + c - 1``) and head ``h``:
  ``w = softmax_j(s <k_j, phi[h]>)`` over the chunk's ROTATED keys; ``k~_m =
  sum_j w_j k_j + mu[h]``; ``v~_m = sum_j w_j v_j``.
* attention: a query at ``t`` (window ``b = t // W``) takes ONE softmax of ``s
  <q_t, .>`` over the keys ``{k_j : b W <= j <= t}`` and the summaries ``{k~_m :
  m < (W / c) b}`` (every chunk of every earlier window, none of its own),
  and the weights mix the matching ``v_j`` and ``v~_m``. ``x += concat(heads)
  W_o``.
* ``h2 = rmsnorm(x) * (1 + g_ffn)``; ``x += W_down(silu(W_gate h2) * W_up h2)``.
* ``logits = (rmsnorm(x) * (1 + g_final)) W_head[:vocab]``: the output matrix
  holds ``n_pred_heads`` heads of ``vocab`` rows and rows ``0 .. vocab - 1``
  are the next byte's; the others do not enter the next byte's logits.

What the published config leaves open (that keys are rotated before they are
pooled, the pooling's form, the output matrix's row order) is listed under
``assumed`` in the configuration's file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.ops import HI, matmul, rmsnorm
from benchmark.reference.qfile import F32, Q40, named

ARCH = 0xABCD05
KEYS = {29: "flags", 30: "window", 34: "eva_chunk", 35: "n_pred_heads"}
USE_ROPE, NORM_UNIT_OFFSET = 1, 128
ROPE_HALVES = 1  # the rotation pairs value j with value j + head_dim / 2
QUERY_BLOCK = 512


def header(raw: dict[int, int]) -> dict:
    h = named(raw, KEYS)
    if h["weights_float_type"] != Q40 or h["hidden_act"] != 1:
        raise ValueError("the reference reads Q40 weights with SiLU only")
    if h["arch"] != ARCH:
        raise ValueError(f"unknown architecture {h['arch']:#x}")
    if h["flags"] != USE_ROPE | NORM_UNIT_OFFSET or h["rope_type"] != ROPE_HALVES:
        raise ValueError(f"this reference computes one set of flags and one pairing of the "
                         f"rotation, not {h['flags']:#x} / {h['rope_type']}")
    if h["n_kv_heads"] != h["n_heads"] or h["window"] % h["eva_chunk"]:
        raise ValueError("EVA attention as read here has a key head for every query head and a "
                         "window of whole chunks")
    h["head_dim"] = h["dim"] // h["n_heads"]
    return h


def layout(h: dict):
    """(name, shape, kind) of every tensor, in file order."""
    dim, vocab, hidden, heads = h["dim"], h["vocab_size"], h["hidden_dim"], h["n_heads"]
    yield "embedding", (vocab, dim), F32
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        yield p + "rms_att", (dim,), F32
        yield p + "rms_ffn", (dim,), F32
        yield p + "q", (dim, dim), Q40
        yield p + "k", (dim, dim), Q40
        yield p + "v", (dim, dim), Q40
        yield p + "eva_phi", (heads, h["head_dim"]), F32
        yield p + "eva_mu", (heads, h["head_dim"]), F32
        yield p + "wo", (dim, dim), Q40
        yield p + "gate", (hidden, dim), Q40
        yield p + "down", (dim, hidden), Q40
        yield p + "up", (hidden, dim), Q40
    yield "rms_final", (dim,), F32
    yield "wcls", (h["n_pred_heads"] * vocab, dim), Q40


def rope(x, theta: float):
    """x [B, T, heads, hd] at positions 0..T-1; pairs (j, j + hd/2)."""
    hd = x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "window", "chunk", "theta"))
def mixer(xn, wq, wk, wv, phi, mu, wo, *, heads, window, chunk, theta):
    """EVA attention of one layer on normed ``xn`` [B, T, dim], a block of
    queries at a time against every key and every summary, masked."""
    B, T, dim = xn.shape
    hd = dim // heads
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    q = rope(matmul(xn, wq).reshape(B, T, heads, hd), theta)
    k = rope(matmul(xn, wk).reshape(B, T, heads, hd), theta)
    v = matmul(xn, wv).reshape(B, T, heads, hd)
    # one summary for every WHOLE chunk of the sequence
    n = T // chunk
    kc = k[:, : n * chunk].reshape(B, n, chunk, heads, hd)
    vc = v[:, : n * chunk].reshape(B, n, chunk, heads, hd)
    w = jax.nn.softmax(scale * jnp.einsum("bnchd,hd->bnch", kc, phi, precision=HI), axis=2)
    ks = jnp.einsum("bnch,bnchd->bnhd", w, kc, precision=HI) + mu
    vs = jnp.einsum("bnch,bnchd->bnhd", w, vc, precision=HI)
    keys, values = jnp.concatenate([k, ks], axis=1), jnp.concatenate([v, vs], axis=1)
    outs = []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(T, start + QUERY_BLOCK)
        t = jnp.arange(start, stop)[:, None]
        j = jnp.arange(T)[None, :]
        exact = (j <= t) & (j // window == t // window)
        earlier = jnp.arange(n)[None, :] < (window // chunk) * (t // window)
        seen = jnp.concatenate([exact, earlier], axis=1)
        s = scale * jnp.einsum("bthd,bshd->bhts", q[:, start:stop], keys, precision=HI)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), values,
                               precision=HI))
    return matmul(jnp.concatenate(outs, axis=1).reshape(B, T, dim), wo)


@jax.jit
def ffn(xn, gate, up, down):
    return matmul(jax.nn.silu(matmul(xn, gate)) * matmul(xn, up), down)


@jax.jit
def head(x, g, wcls):
    return matmul(rmsnorm(x, 1.0 + g), wcls)


def forward(qf, tokens: np.ndarray, positions: np.ndarray,
            router_gaps: list | None = None) -> np.ndarray:
    """Logits [B, len(positions), vocab] of the next byte after a full pass
    over ``tokens`` [B, T]; layers are streamed from the file one at a time.
    A dense model has no routing gap to report: ``router_gaps`` stays empty."""
    h = qf.h
    x = jnp.asarray(qf.f32("embedding", rows=np.asarray(tokens)))
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        x = x + mixer(rmsnorm(x, 1.0 + qf.f32(p + "rms_att")), qf.raw(p + "q"), qf.raw(p + "k"),
                      qf.raw(p + "v"), qf.f32(p + "eva_phi"), qf.f32(p + "eva_mu"),
                      qf.raw(p + "wo"), heads=h["n_heads"], window=h["window"],
                      chunk=h["eva_chunk"], theta=float(h["rope_theta"]))
        x = x + ffn(rmsnorm(x, 1.0 + qf.f32(p + "rms_ffn")), qf.raw(p + "gate"),
                    qf.raw(p + "up"), qf.raw(p + "down"))
    next_byte = qf.raw("wcls")[: h["vocab_size"]]
    return np.asarray(head(x[:, np.asarray(positions)], qf.f32("rms_final"), next_byte))
