"""Solar-Open2's plain reference: softmax layers without positional rotation
and with a per-channel output gate, gated delta-rule linear-attention layers
(a causal depthwise convolution in front, a ``lax.scan`` over time, no
chunking, no cache), and in every layer a sparse-expert feed-forward of which
this file holds a SHARE of the routed experts beside the shared one; in
straightforward float32 ``jax.numpy`` at ``highest`` precision, over weights
dequantized from the file's raw Q40 bytes one layer at a time.

Per layer ``l`` (``xn = rmsnorm(x)``): ``h = x + Mixer_l(xn)``,
``y = h + MoE(rmsnorm(h))``; the mixer is softmax attention where
``l % attn_period == 0``, else linear:

* softmax: ``q, k, v = xn W``; causal ``softmax(q k^T / sqrt(hd)) v`` (GQA);
  ``out = (attn * sigmoid(xn W_g)) W_o``.
* linear: ``q~, k~, v = silu(conv(xn W_{q,k,v}))``; ``q = l2norm(q~)/sqrt(dl)``,
  ``k = l2norm(k~)`` per head; ``a_t = -exp(A_log) * softplus((xn W_f-) W_f+ +
  dt_bias)`` per channel, ``alpha_t = exp(a_t)``; ``beta_t = 2 sigmoid(xn
  W_beta)`` per head. ``S' = Diag(alpha_t) S``; ``S = S' + beta_t k_t (v_t -
  S'^T k_t)^T``; ``o_t = S^T q_t``. ``out = (rmsnorm_head(o_t) * sigmoid((xn
  W_g-) W_g+)) W_o``.
* feed-forward: ``s = sigmoid(xn W_r)``; the 8 largest of ``s + bias``;
  weights ``s_i / sum of the chosen s``; ``MoE = SwiGLU_shared(xn) + sum over
  the chosen experts HELD HERE of w_i SwiGLU_i(xn)``. What an absent expert
  would add is left out, as the program leaves it out.

Departures forced by the file format: Q40 weights (dequantized exactly), the
router and the low-rank pairs Q40 like every matrix. What the published
config leaves open is listed under ``assumed`` in the configuration's file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.ops import HI, matmul, rmsnorm
from benchmark.reference.qfile import F32, Q40, named

ARCH = 0xABCD03
KEYS = {19: "head_dim", 20: "moe_hidden_dim", 21: "n_shared_experts", 22: "n_routed_experts",
        23: "first_expert", 24: "attn_period", 25: "lin_heads", 26: "lin_head_dim", 27: "lin_conv",
        28: "lin_rank", 29: "flags"}
USE_ROPE, GQA_GATE, NEG_EIGVAL, NORM_TOPK, SIGMOID_ROUTER = 1, 2, 4, 8, 16


def header(raw: dict[int, int]) -> dict:
    h = named(raw, KEYS)
    if h["weights_float_type"] != Q40 or h["hidden_act"] != 1:
        raise ValueError("the reference reads Q40 weights with SiLU only")
    if h["arch"] != ARCH:
        raise ValueError(f"unknown architecture {h['arch']:#x}")
    if h["flags"] != GQA_GATE | NEG_EIGVAL | NORM_TOPK | SIGMOID_ROUTER:
        raise ValueError(f"this reference computes one set of flags, not {h['flags']:#x}")
    h["kv_dim"] = h["head_dim"] * h["n_kv_heads"]
    return h


def is_softmax(h: dict, l: int) -> bool:
    return l % h["attn_period"] == 0


def layout(h: dict):
    """(name, shape, kind) of every tensor, in file order."""
    dim, vocab, width = h["dim"], h["vocab_size"], h["moe_hidden_dim"]
    yield "embedding", (vocab, dim), F32
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        if is_softmax(h, l):
            q_dim = h["n_heads"] * h["head_dim"]
            yield p + "q", (q_dim, dim), Q40
            yield p + "k", (h["kv_dim"], dim), Q40
            yield p + "v", (h["kv_dim"], dim), Q40
            yield p + "gate", (q_dim, dim), Q40
            yield p + "wo", (dim, q_dim), Q40
        else:
            lin, rank = h["lin_heads"] * h["lin_head_dim"], h["lin_rank"]
            for name in ("q", "k", "v"):
                yield p + name, (lin, dim), Q40
            yield p + "conv", (3 * lin, h["lin_conv"]), F32
            yield p + "f_down", (rank, dim), Q40
            yield p + "f_up", (lin, rank), Q40
            yield p + "dt_bias", (lin,), F32
            yield p + "a_log", (h["lin_heads"],), F32
            yield p + "beta", (h["lin_heads"], dim), Q40
            yield p + "g_down", (rank, dim), Q40
            yield p + "g_up", (lin, rank), Q40
            yield p + "o_norm", (h["lin_head_dim"],), F32
            yield p + "wo", (dim, lin), Q40
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
        yield p + "rms_att", (dim,), F32
        yield p + "rms_ffn", (dim,), F32
    yield "rms_final", (dim,), F32
    yield "wcls", (vocab, dim), Q40


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "hd"))
def softmax_mixer(xn, wq, wk, wv, wg, wo, *, n_heads, n_kv, hd):
    B, T, _ = xn.shape
    q = matmul(xn, wq).reshape(B, T, n_heads, hd)
    k = jnp.repeat(matmul(xn, wk).reshape(B, T, n_kv, hd), n_heads // n_kv, axis=2)
    v = jnp.repeat(matmul(xn, wv).reshape(B, T, n_kv, hd), n_heads // n_kv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v, precision=HI)
    return matmul(o.reshape(B, T, n_heads * hd) * jax.nn.sigmoid(matmul(xn, wg)), wo)


def conv_silu(x, taps):
    """Causal depthwise convolution over time, then SiLU. x [B, T, C], taps
    [C, K]: y_t = sum_j taps[:, j] x_{t-K+1+j}, zeros before the start."""
    K, T = taps.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + T] * taps[:, j] for j in range(K)))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def carry(S):
    """What a step hands the next: the state as it is, float32 (``assumed``:
    ``state``). The precision control computes with a rounding here."""
    return S


@functools.partial(jax.jit, static_argnames=("n_heads", "hd"))
def linear_mixer(xn, wq, wk, wv, taps, f_down, f_up, dt_bias, a_log, w_beta, g_down, g_up,
                 o_norm, wo, *, n_heads, hd):
    B, T, _ = xn.shape
    qkv = conv_silu(jnp.concatenate([matmul(xn, wq), matmul(xn, wk), matmul(xn, wv)], axis=-1), taps)
    q, k, v = (t.reshape(B, T, n_heads, hd) for t in jnp.split(qkv, 3, axis=-1))
    q, k = l2norm(q) / jnp.sqrt(jnp.float32(hd)), l2norm(k)
    decay = matmul(matmul(xn, f_down), f_up) + dt_bias
    alpha = jnp.exp(-jnp.exp(a_log)[:, None] * jax.nn.softplus(decay.reshape(B, T, n_heads, hd)))
    beta = 2.0 * jax.nn.sigmoid(matmul(xn, w_beta))  # [B, T, heads]

    def step(S, xs):
        q_t, k_t, v_t, alpha_t, beta_t = xs  # [B, heads, hd], beta [B, heads]
        S = alpha_t[..., None] * S
        u = v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=HI)
        S = S + beta_t[..., None, None] * k_t[..., None] * u[..., None, :]
        return carry(S), jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=HI)

    S0 = jnp.zeros((B, n_heads, hd, hd), jnp.float32)
    _, o = jax.lax.scan(step, S0, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, alpha, beta)))
    o = jnp.moveaxis(o, 0, 1)  # [B, T, heads, hd]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-5) * o_norm
    gate = jax.nn.sigmoid(matmul(matmul(xn, g_down), g_up))
    return matmul(o.reshape(B, T, n_heads * hd) * gate, wo)


@jax.jit
def ffn(xn, gate, up, down):
    return matmul(jax.nn.silu(matmul(xn, gate)) * matmul(xn, up), down)


@functools.partial(jax.jit, static_argnames=("top_k", "first", "held"))
def routing(xn, router, bias, *, top_k, first, held):
    """[B, T, E] mixing weights over ALL experts: sigmoid scores, the top k of
    score + bias kept, their scores renormalised to sum to one, zero
    elsewhere. And [B, T] how decided the choice was FOR THE EXPERTS HELD
    HERE (``first`` .. ``first + held - 1``): the least distance, in score +
    bias, of a held expert from the other side of the boundary between the
    last expert kept and the first one dropped, as a share of max|score +
    bias|. A swap between two absent experts moves no held expert in or out."""
    scores = jax.nn.sigmoid(matmul(xn, router))
    select = scores + bias
    _, idx = jax.lax.top_k(select, top_k)
    chosen = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=jnp.float32), axis=-2)
    mix = chosen * scores
    mix = mix / jnp.sum(mix, axis=-1, keepdims=True)
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
    """The feed-forward of layer ``l`` on normed ``xn``: the shared expert
    plus the held experts' part of the routed sum."""
    h, p = qf.h, f"layers.{l}."
    first, held = h["first_expert"], h["n_experts"]
    mix, gap = routing(xn, qf.raw(p + "moe_router"), qf.f32(p + "router_bias"),
                       top_k=h["n_active_experts"], first=first, held=held)
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


def mixer(qf, l: int, xn):
    h, p = qf.h, f"layers.{l}."
    if is_softmax(h, l):
        return softmax_mixer(xn, qf.raw(p + "q"), qf.raw(p + "k"), qf.raw(p + "v"),
                             qf.raw(p + "gate"), qf.raw(p + "wo"), n_heads=h["n_heads"],
                             n_kv=h["n_kv_heads"], hd=h["head_dim"])
    return linear_mixer(xn, qf.raw(p + "q"), qf.raw(p + "k"), qf.raw(p + "v"), qf.f32(p + "conv"),
                        qf.raw(p + "f_down"), qf.raw(p + "f_up"), qf.f32(p + "dt_bias"),
                        qf.f32(p + "a_log"), qf.raw(p + "beta"), qf.raw(p + "g_down"),
                        qf.raw(p + "g_up"), qf.f32(p + "o_norm"), qf.raw(p + "wo"),
                        n_heads=h["lin_heads"], hd=h["lin_head_dim"])


def forward(qf, tokens: np.ndarray, positions: np.ndarray,
            router_gaps: list | None = None) -> np.ndarray:
    """Logits [B, len(positions), vocab] after a full causal pass over
    ``tokens`` [B, T]; layers are streamed from the file one at a time. Each
    layer's [B, len(positions)] routing gap (see ``routing``) is appended to
    ``router_gaps`` where a list is given."""
    x = jnp.asarray(qf.f32("embedding", rows=np.asarray(tokens)))
    for l in range(qf.h["n_layers"]):
        p = f"layers.{l}."
        x = x + mixer(qf, l, rmsnorm(x, qf.f32(p + "rms_att")))
        x = x + moe(qf, l, rmsnorm(x, qf.f32(p + "rms_ffn")), positions, router_gaps)
    return np.asarray(head(x[:, np.asarray(positions)], qf.f32("rms_final"), qf.raw("wcls")))
