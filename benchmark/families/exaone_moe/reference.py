"""K-EXAONE's plain reference: window attention (a query sees the last
``window`` positions, itself included) beside full causal attention, three
window layers to one full one; every q and k head RMS-normalised with a
learned weight; rotation in the window layers only; a leading dense layer and
after it sparse-expert layers of which this file holds a SHARE of the routed
experts beside the shared one; in straightforward float32 ``jax.numpy`` at
``highest`` precision, over weights dequantized from the file's raw Q40 bytes
one layer at a time. No cache and no kernel; attention in blocks of queries,
so that a prompt of some thousand tokens fits the host.

Per layer ``l`` (``x`` the residual stream, eps 1e-5):

* ``h = rmsnorm(x, w_att)``; ``q = h W_q`` (``n_heads`` of ``head_dim``), ``k = h
  W_k``, ``v = h W_v`` (``n_kv_heads``); each q head and each k head is
  RMS-normalised over its ``head_dim`` values, times ``w_qn`` / ``w_kn``.
* window layer (``l % window_period != window_period - 1``): q and k rotated
  (pairs ``(j, j + head_dim / 2)``, ``theta ** (-2j / head_dim)``); query ``t``
  sees key ``s`` iff ``t - window < s <= t``. Full layer: no rotation, causal.
  Both: ``softmax(q k^T / sqrt(head_dim))``, ``x += concat(heads) W_o``.
* ``h2 = rmsnorm(x, w_ffn)``. A leading dense layer: ``x += W_down(silu(W_gate
  h2) * W_up h2)``. An expert layer: ``s = sigmoid(h2 W_r)``; the ``k`` largest
  of ``s + b`` are chosen (``b`` for choosing only); ``w_e = factor * s_e / sum
  of the chosen s``; ``x += SwiGLU_shared(h2) + sum over the chosen experts
  HELD HERE of w_e SwiGLU_e(h2)``. What an absent expert would add is left
  out, as the program leaves it out.
* ``logits = rmsnorm(x, w_final) W_head``.

Departures forced by the file format: Q40 weights (dequantized exactly), the
router Q40 like every matrix. What the published config leaves open is listed
under ``assumed`` in the configuration's file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.ops import EPS, HI, matmul, rmsnorm
from benchmark.reference.qfile import F32, Q40, named

ARCH = 0xABCD04
KEYS = {19: "head_dim", 20: "moe_hidden_dim", 21: "n_shared_experts", 22: "n_routed_experts",
        23: "first_expert", 29: "flags", 30: "window", 31: "window_period", 32: "first_dense",
        33: "routed_scale_milli"}
USE_ROPE, NORM_TOPK, SIGMOID_ROUTER, QK_NORM, ROPE_WINDOW_ONLY = 1, 8, 16, 32, 64
ROPE_HALVES = 1  # the rotation pairs value j with value j + head_dim / 2
QUERY_BLOCK = 512


def header(raw: dict[int, int]) -> dict:
    h = named(raw, KEYS)
    if h["weights_float_type"] != Q40 or h["hidden_act"] != 1:
        raise ValueError("the reference reads Q40 weights with SiLU only")
    if h["arch"] != ARCH:
        raise ValueError(f"unknown architecture {h['arch']:#x}")
    if h["flags"] != USE_ROPE | NORM_TOPK | SIGMOID_ROUTER | QK_NORM | ROPE_WINDOW_ONLY \
            or h["rope_type"] != ROPE_HALVES:
        raise ValueError(f"this reference computes one set of flags and one pairing of the "
                         f"rotation, not {h['flags']:#x} / {h['rope_type']}")
    h["kv_dim"] = h["head_dim"] * h["n_kv_heads"]
    return h


def is_window(h: dict, l: int) -> bool:
    return l % h["window_period"] != h["window_period"] - 1


def is_dense(h: dict, l: int) -> bool:
    return l < h["first_dense"]


def layout(h: dict):
    """(name, shape, kind) of every tensor, in file order."""
    dim, vocab, width, hidden = h["dim"], h["vocab_size"], h["moe_hidden_dim"], h["hidden_dim"]
    q_dim = h["n_heads"] * h["head_dim"]
    yield "embedding", (vocab, dim), F32
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        yield p + "rms_att", (dim,), F32
        yield p + "rms_ffn", (dim,), F32
        yield p + "q", (q_dim, dim), Q40
        yield p + "k", (h["kv_dim"], dim), Q40
        yield p + "v", (h["kv_dim"], dim), Q40
        yield p + "q_norm", (h["head_dim"],), F32
        yield p + "k_norm", (h["head_dim"],), F32
        yield p + "wo", (dim, q_dim), Q40
        if is_dense(h, l):
            yield p + "gate", (hidden, dim), Q40
            yield p + "down", (dim, hidden), Q40
            yield p + "up", (hidden, dim), Q40
            continue
        yield p + "moe_router", (h["n_routed_experts"], dim), Q40
        yield p + "router_bias", (h["n_routed_experts"],), F32
        for e in range(h["n_experts"]):
            yield f"{p}experts.{e}.up", (width, dim), Q40
            yield f"{p}experts.{e}.gate", (width, dim), Q40
            yield f"{p}experts.{e}.down", (dim, width), Q40
        if h["n_shared_experts"]:
            shared = h["n_shared_experts"] * width
            yield p + "shared.up", (shared, dim), Q40
            yield p + "shared.gate", (shared, dim), Q40
            yield p + "shared.down", (dim, shared), Q40
    yield "rms_final", (dim,), F32
    yield "wcls", (vocab, dim), Q40


def head_norm(x, w):
    """RMS norm of every head [.., heads, hd] over its hd values."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def rope(x, theta: float):
    """x [B, T, heads, hd] at positions 0..T-1; pairs (j, j + hd/2)."""
    hd = x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "hd", "window", "theta"))
def mixer(xn, wq, wk, wv, w_qn, w_kn, wo, *, n_heads, n_kv, hd, window, theta):
    """Attention of one layer on normed ``xn`` [B, T, dim]; ``window`` 0 is a
    full layer (causal, not rotated). Computed a block of queries at a time
    against the keys that block can see."""
    B, T, _ = xn.shape
    q = head_norm(matmul(xn, wq).reshape(B, T, n_heads, hd), w_qn)
    k = head_norm(matmul(xn, wk).reshape(B, T, n_kv, hd), w_kn)
    v = matmul(xn, wv).reshape(B, T, n_kv, hd)
    if window:
        q, k = rope(q, theta), rope(k, theta)
    k = jnp.repeat(k, n_heads // n_kv, axis=2)
    v = jnp.repeat(v, n_heads // n_kv, axis=2)
    outs = []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(T, start + QUERY_BLOCK)
        lo = max(0, start - window + 1) if window else 0
        t = jnp.arange(start, stop)[:, None]
        s_at = jnp.arange(lo, stop)[None, :]
        seen = (s_at <= t) & ((s_at > t - window) if window else True)
        s = jnp.einsum("bthd,bshd->bhts", q[:, start:stop], k[:, lo:stop], precision=HI)
        s = jnp.where(seen[None, None], s / jnp.sqrt(jnp.float32(hd)), -jnp.inf)
        outs.append(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v[:, lo:stop],
                               precision=HI))
    return matmul(jnp.concatenate(outs, axis=1).reshape(B, T, n_heads * hd), wo)


@jax.jit
def ffn(xn, gate, up, down):
    return matmul(jax.nn.silu(matmul(xn, gate)) * matmul(xn, up), down)


@functools.partial(jax.jit, static_argnames=("top_k", "first", "held", "factor"))
def routing(xn, router, bias, *, top_k, first, held, factor):
    """[B, T, E] mixing weights over ALL experts: sigmoid scores, the top k of
    score + bias kept, their scores renormalised to sum to one and multiplied
    by ``factor``, zero elsewhere. And [B, T] how decided the choice was FOR
    THE EXPERTS HELD HERE (``first`` .. ``first + held - 1``): the least
    distance, in score + bias, of a held expert from the other side of the
    boundary between the last expert kept and the first one dropped, as a
    share of max|score + bias|. A swap between two absent experts moves no
    held expert in or out."""
    scores = jax.nn.sigmoid(matmul(xn, router))
    select = scores + bias
    _, idx = jax.lax.top_k(select, top_k)
    chosen = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=jnp.float32), axis=-2)
    mix = chosen * scores
    mix = factor * mix / jnp.sum(mix, axis=-1, keepdims=True)
    ranked = jnp.sort(select, axis=-1)
    last_kept, first_dropped = ranked[..., -top_k, None], ranked[..., -top_k - 1, None]
    sel_h, chosen_h = select[..., first:first + held], chosen[..., first:first + held]
    to_other_side = jnp.where(chosen_h > 0, sel_h - first_dropped, last_kept - sel_h)
    gap = jnp.min(to_other_side, axis=-1) / jnp.max(jnp.abs(select), axis=-1)
    return mix, gap


@jax.jit
def head(x, rms, wcls):
    return matmul(rmsnorm(x, rms), wcls)


def moe(qf, l: int, xn, positions, router_gaps):
    """The feed-forward of expert layer ``l`` on normed ``xn``: the shared
    expert plus the held experts' part of the routed sum."""
    h, p = qf.h, f"layers.{l}."
    first, held = h["first_expert"], h["n_experts"]
    mix, gap = routing(xn, qf.raw(p + "moe_router"), qf.f32(p + "router_bias"),
                       top_k=h["n_active_experts"], first=first, held=held,
                       factor=h["routed_scale_milli"] / 1000.0)
    if router_gaps is not None:
        router_gaps.append(np.asarray(gap[:, np.asarray(positions)]))
    out = jnp.zeros_like(xn)
    if h["n_shared_experts"]:
        out = ffn(xn, qf.raw(p + "shared.gate"), qf.raw(p + "shared.up"), qf.raw(p + "shared.down"))
    for e in range(held):
        ep = f"{p}experts.{e}."
        out = out + mix[..., first + e, None] * ffn(xn, qf.raw(ep + "gate"), qf.raw(ep + "up"),
                                                    qf.raw(ep + "down"))
    return out


def forward(qf, tokens: np.ndarray, positions: np.ndarray,
            router_gaps: list | None = None) -> np.ndarray:
    """Logits [B, len(positions), vocab] after a full causal pass over
    ``tokens`` [B, T]; layers are streamed from the file one at a time. Each
    expert layer's [B, len(positions)] routing gap (see ``routing``) is
    appended to ``router_gaps`` where a list is given."""
    h = qf.h
    x = jnp.asarray(qf.f32("embedding", rows=np.asarray(tokens)))
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        x = x + mixer(rmsnorm(x, qf.f32(p + "rms_att")), qf.raw(p + "q"), qf.raw(p + "k"),
                      qf.raw(p + "v"), qf.f32(p + "q_norm"), qf.f32(p + "k_norm"), qf.raw(p + "wo"),
                      n_heads=h["n_heads"], n_kv=h["n_kv_heads"], hd=h["head_dim"],
                      window=h["window"] if is_window(h, l) else 0, theta=float(h["rope_theta"]))
        xn = rmsnorm(x, qf.f32(p + "rms_ffn"))
        if is_dense(h, l):
            x = x + ffn(xn, qf.raw(p + "gate"), qf.raw(p + "up"), qf.raw(p + "down"))
        else:
            x = x + moe(qf, l, xn, positions, router_gaps)
    return np.asarray(head(x[:, np.asarray(positions)], qf.f32("rms_final"), qf.raw("wcls")))
