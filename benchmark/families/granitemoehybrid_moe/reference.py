"""Granite-4.0-H's plain reference, the members WITH experts (``model_type``
``granitemoehybrid``, ``num_local_experts`` > 0: H-Small, H-Tiny): the dense
members' mixers word for word (state-space layers as the token-by-token
recurrence, a ``lax.scan`` over time with no chunked form and no cache, behind
a causal depthwise convolution; softmax layers without rotation) and, in
EVERY layer, a router over the published number of experts of which this file
HOLDS a share, beside a shared expert; the lineage's four multipliers exactly
as published; in straightforward float32 ``jax.numpy`` at ``highest``
precision, over weights dequantized from the file's raw Q40 bytes one layer at
a time. It carries its own copy of the mixers (the sibling family
``granitemoehybrid`` has the same lines): the precision control rounds what
THIS module's ``matmul`` and ``carry`` see.

``x_0 = embedding_multiplier * E[token]``. Per layer ``l``:
``h = x + residual_multiplier * Mixer_l(rmsnorm(x))``, ``u = rmsnorm(h)``,
``y = h + residual_multiplier * (MoE(u) + Shared(u))``.
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
* ``MoE(u)``: ``l = u W_r^T`` (one logit an expert of the router's published
  width, no bias); the ``k`` largest are chosen; their weights are the softmax
  over those ``k`` logits ALONE (the same numbers as a softmax over all of
  them followed by renormalising the chosen); ``MoE(u) = sum over the chosen
  experts HELD HERE of w_e W_down,e (silu(W_gate,e u) * W_up,e u)``. What an
  absent expert would add is left out, as the program leaves it out.
  ``Shared(u)`` is one SwiGLU every token takes, added unweighted. No scaling
  factor, no selection bias, no groups.

Departures forced by the file format: Q40 weights (dequantized exactly), the
router Q40 like every matrix, the multipliers in millionths (the attention
scores' in billionths: 1/128 is no whole millionth). What the published config
leaves open is listed under ``assumed`` in the configuration's file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.ops import HI, matmul, rmsnorm
from benchmark.reference.qfile import F32, Q40, named

ARCH = 0xABCD07
KEYS = {20: "moe_hidden_dim", 21: "n_shared_experts", 22: "n_routed_experts", 23: "first_expert",
        24: "attn_period", 27: "conv_taps", 41: "attn_offset", 42: "ssm_heads", 43: "ssm_head_dim",
        44: "ssm_state", 45: "embed_scale_micro", 46: "residual_scale_micro", 47: "attn_scale_micro",
        48: "logits_divisor_micro", 49: "attn_scale_nano"}


def header(raw: dict[int, int]) -> dict:
    h = named(raw, KEYS)
    if h["weights_float_type"] != Q40 or h["hidden_act"] != 1:
        raise ValueError("the reference reads Q40 weights with SiLU only")
    if h["arch"] != ARCH:
        raise ValueError(f"unknown architecture {h['arch']:#x}")
    if not h.get("n_routed_experts") or not h["n_experts"]:
        raise ValueError("this reference reads the members with experts: the header states no router "
                         "(family granitemoehybrid reads the dense members)")
    h.setdefault("first_expert", 0)  # a key the file carries only where it is not zero
    h.setdefault("n_shared_experts", 0)
    if not 0 <= h["first_expert"] <= h["n_routed_experts"] - h["n_experts"]:
        raise ValueError("the held experts do not lie inside the router's width")
    h["head_dim"] = h["dim"] // h["n_heads"]
    h["kv_dim"] = h["head_dim"] * h["n_kv_heads"]
    h["inner"] = h["ssm_heads"] * h["ssm_head_dim"]
    for key in ("embed", "residual"):
        h[key + "_scale"] = h[key + "_scale_micro"] / 1e6
    h["attn_scale"] = h["attn_scale_nano"] / 1e9 if h.get("attn_scale_nano") else h["attn_scale_micro"] / 1e6
    h["logits_divisor"] = h["logits_divisor_micro"] / 1e6
    return h


def is_softmax(h: dict, l: int) -> bool:
    return l % h["attn_period"] == h["attn_offset"]


def layout(h: dict):
    """(name, shape, kind) of every tensor, in file order."""
    dim, vocab, width = h["dim"], h["vocab_size"], h["moe_hidden_dim"]
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
        yield p + "moe_router", (h["n_routed_experts"], dim), Q40
        for e in range(h["n_experts"]):
            yield f"{p}experts.{e}.up", (width, dim), Q40
            yield f"{p}experts.{e}.gate", (width, dim), Q40
            yield f"{p}experts.{e}.down", (dim, width), Q40
        if h["n_shared_experts"]:
            shared = h["n_shared_experts"] * width
            yield p + "shared.up", (shared, dim), Q40
            yield p + "shared.gate", (shared, dim), Q40
            yield p + "shared.down", (dim, shared), Q40
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


@functools.partial(jax.jit, static_argnames=("top_k", "first", "held"))
def routing(xn, router, *, top_k, first, held):
    """[B, T, E] mixing weights over ALL experts of the router's width: the
    ``top_k`` largest logits kept, a softmax over those logits alone, zero
    elsewhere. And [B, T] how decided the choice was FOR THE EXPERTS HELD
    HERE (``first`` .. ``first + held - 1``): the least distance, in logits,
    of a held expert from the other side of the boundary between the last
    expert kept (the ``top_k``-th logit) and the first one dropped (the next),
    as a share of max|logit|. A swap between two absent experts moves no held
    expert in or out, and the chosen weights hardly: their logits are equal."""
    logits = matmul(xn, router)
    _, idx = jax.lax.top_k(logits, top_k)
    chosen = jnp.sum(jax.nn.one_hot(idx, logits.shape[-1], dtype=jnp.float32), axis=-2)
    mix = chosen * jax.nn.softmax(jnp.where(chosen > 0, logits, -jnp.inf), axis=-1)
    ranked = jnp.sort(logits, axis=-1)
    last_kept, first_dropped = ranked[..., -top_k, None], ranked[..., -top_k - 1, None]
    log_h, chosen_h = logits[..., first:first + held], chosen[..., first:first + held]
    to_other_side = jnp.where(chosen_h > 0, log_h - first_dropped, last_kept - log_h)
    gap = jnp.min(to_other_side, axis=-1) / jnp.max(jnp.abs(logits), axis=-1)
    return mix, gap


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


def shared_expert(qf, l: int, xn):
    """The SwiGLU every token takes, unweighted (zeros where the file has none)."""
    p = f"layers.{l}."
    if not qf.h["n_shared_experts"]:
        return jnp.zeros_like(xn)
    return ffn(xn, qf.raw(p + "shared.gate"), qf.raw(p + "shared.up"), qf.raw(p + "shared.down"))


def held_experts(qf, l: int, xn, positions=None, router_gaps=None):
    """The held experts' part of the routed sum of layer ``l`` on normed
    ``xn``: a loop over the experts held here, each over every token, times
    the token's mixing weight for it (zero where the token did not choose it)."""
    h, p = qf.h, f"layers.{l}."
    first, held = h["first_expert"], h["n_experts"]
    mix, gap = routing(xn, qf.raw(p + "moe_router"), top_k=h["n_active_experts"], first=first, held=held)
    if router_gaps is not None:
        router_gaps.append(np.asarray(gap[:, np.asarray(positions)]))
    out = jnp.zeros_like(xn)
    for e in range(held):
        ep = f"{p}experts.{e}."
        out = out + mix[..., first + e, None] * ffn(xn, qf.raw(ep + "gate"), qf.raw(ep + "up"),
                                                    qf.raw(ep + "down"))
    return out


def forward(qf, tokens: np.ndarray, positions: np.ndarray,
            router_gaps: list | None = None) -> np.ndarray:
    """Logits [B, len(positions), vocab] after a full causal pass over
    ``tokens`` [B, T]; layers are streamed from the file one at a time. Each
    layer's [B, len(positions)] routing gap (see ``routing``) is appended to
    ``router_gaps`` where a list is given."""
    h = qf.h
    x = h["embed_scale"] * jnp.asarray(qf.f32("embedding", rows=np.asarray(tokens)))
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        x = x + h["residual_scale"] * mixer(qf, l, rmsnorm(x, qf.f32(p + "rms_att")))
        u = rmsnorm(x, qf.f32(p + "rms_ffn"))
        x = x + h["residual_scale"] * (held_experts(qf, l, u, positions, router_gaps)
                                       + shared_expert(qf, l, u))
    return np.asarray(head(x[:, np.asarray(positions)], qf.f32("rms_final"), qf.raw("wcls"),
                           h["logits_divisor"]))
