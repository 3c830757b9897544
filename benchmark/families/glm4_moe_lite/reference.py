"""GLM-4.7-Flash's plain reference: latent attention in its EXPANDED form
(every position's keys and values are computed from its latent, for every
head, and attended causally), a leading dense layer and after it sparse-expert
layers beside a shared expert; in straightforward float32 ``jax.numpy`` at
``highest`` precision, over weights dequantized from the file's raw Q40 bytes
one layer at a time. No cache, no kernel and NO ABSORPTION: the served path,
which keeps latents and folds the up-projections into query and output, is
held to this order of operations. Attention in blocks of queries, so that a
prompt of some thousand tokens fits the host.

Per layer (``x`` the residual stream, eps 1e-5, ``H`` heads):

* ``h = rmsnorm(x, w_att)``; ``c_q = rmsnorm(h W_qa, w_qn)`` (``q_lora_rank``);
  ``q = c_q W_qb``, a head ``[q_nope | q_rope]`` (``qk_nope_head_dim`` |
  ``qk_rope_head_dim``). ``[c | k_r] = h W_kva`` (``kv_lora_rank`` |
  ``qk_rope_head_dim``); ``c_kv = rmsnorm(c, w_kvn)``; ``[k_nope | v] = c_kv
  W_kvb``, a head ``qk_nope_head_dim`` | ``v_head_dim``.
* ``q_rope`` and ``k_r`` are rotated at their position (pairs ``(j, j +
  rope / 2)``, ``theta ** (-2j / rope)``); the ONE rotated ``k_r`` is every
  head's. ``score = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``,
  causal softmax, ``o = sum p v``; ``x += concat(o) W_o``.
* ``h2 = rmsnorm(x, w_ffn)``. A leading dense layer: ``x += W_down(silu(W_gate
  h2) * W_up h2)``. An expert layer: ``s = sigmoid(h2 W_r)``; the ``k`` largest
  of ``s + b`` are chosen (``b`` for choosing only); ``w_e = factor * s_e / sum
  of the chosen s``; ``x += SwiGLU_shared(h2) + sum over the chosen experts
  held in the file of w_e SwiGLU_e(h2)``.
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

from benchmark.reference.ops import HI, matmul, rmsnorm
from benchmark.reference.qfile import F32, Q40, named

ARCH = 0xABCD06
KEYS = {19: "head_dim", 20: "moe_hidden_dim", 21: "n_shared_experts", 22: "n_routed_experts",
        23: "first_expert", 29: "flags", 32: "first_dense", 33: "routed_scale_milli",
        36: "q_lora_rank", 37: "kv_lora_rank", 38: "qk_nope_head_dim", 39: "qk_rope_head_dim",
        40: "v_head_dim"}
USE_ROPE, NORM_TOPK, SIGMOID_ROUTER = 1, 8, 16
ROPE_HALVES = 1  # the rotation pairs value j with value j + rope / 2
QUERY_BLOCK = 512


def header(raw: dict[int, int]) -> dict:
    h = named(raw, KEYS)
    if h["weights_float_type"] != Q40 or h["hidden_act"] != 1:
        raise ValueError("the reference reads Q40 weights with SiLU only")
    if h["arch"] != ARCH:
        raise ValueError(f"unknown architecture {h['arch']:#x}")
    if h["flags"] != USE_ROPE | NORM_TOPK | SIGMOID_ROUTER or h["rope_type"] != ROPE_HALVES:
        raise ValueError(f"this reference computes one set of flags and one pairing of the "
                         f"rotation, not {h['flags']:#x} / {h['rope_type']}")
    if h["head_dim"] != h["qk_nope_head_dim"] + h["qk_rope_head_dim"]:
        raise ValueError("a q/k head is its unrotated and its rotated values")
    return h


def is_dense(h: dict, l: int) -> bool:
    return l < h["first_dense"]


def layout(h: dict):
    """(name, shape, kind) of every tensor, in file order."""
    dim, vocab, width, hidden = h["dim"], h["vocab_size"], h["moe_hidden_dim"], h["hidden_dim"]
    H, nope, rope, v = h["n_heads"], h["qk_nope_head_dim"], h["qk_rope_head_dim"], h["v_head_dim"]
    yield "embedding", (vocab, dim), F32
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        yield p + "rms_att", (dim,), F32
        yield p + "rms_ffn", (dim,), F32
        yield p + "q_a", (h["q_lora_rank"], dim), Q40
        yield p + "q_a_norm", (h["q_lora_rank"],), F32
        yield p + "q_b", (H * (nope + rope), h["q_lora_rank"]), Q40
        yield p + "kv_a", (h["kv_lora_rank"] + rope, dim), Q40
        yield p + "kv_a_norm", (h["kv_lora_rank"],), F32
        yield p + "kv_b", (H * (nope + v), h["kv_lora_rank"]), Q40
        yield p + "wo", (dim, H * v), Q40
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


def rope(x, theta: float):
    """x [B, T, heads, r] at positions 0..T-1; pairs (j, j + r/2)."""
    r = x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "nope", "rope_dim", "v_dim", "theta"))
def mixer(xn, q_a, w_qn, q_b, kv_a, w_kvn, kv_b, wo, *, heads, nope, rope_dim, v_dim, theta):
    """Latent attention of one layer on normed ``xn`` [B, T, dim], expanded:
    every position's keys and values for every head, then causal attention a
    block of queries at a time."""
    B, T, _ = xn.shape
    q = matmul(rmsnorm(matmul(xn, q_a), w_qn), q_b).reshape(B, T, heads, nope + rope_dim)
    low = matmul(xn, kv_a)
    rank = low.shape[-1] - rope_dim
    kv = matmul(rmsnorm(low[..., :rank], w_kvn), kv_b).reshape(B, T, heads, nope + v_dim)
    k_rope = jnp.broadcast_to(rope(low[..., None, rank:], theta), (B, T, heads, rope_dim))
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
    v = kv[..., nope:]
    outs = []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(T, start + QUERY_BLOCK)
        seen = jnp.arange(stop)[None, :] <= jnp.arange(start, stop)[:, None]
        s = jnp.einsum("bthd,bshd->bhts", q[:, start:stop], k[:, :stop], precision=HI)
        s = jnp.where(seen[None, None], s / jnp.sqrt(jnp.float32(nope + rope_dim)), -jnp.inf)
        outs.append(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v[:, :stop],
                               precision=HI))
    return matmul(jnp.concatenate(outs, axis=1).reshape(B, T, heads * v_dim), wo)


@jax.jit
def ffn(xn, gate, up, down):
    return matmul(jax.nn.silu(matmul(xn, gate)) * matmul(xn, up), down)


@functools.partial(jax.jit, static_argnames=("top_k", "first", "held", "factor"))
def routing(xn, router, bias, *, top_k, first, held, factor):
    """[B, T, E] mixing weights over ALL experts: sigmoid scores, the top k of
    score + bias kept, their scores renormalised to sum to one and multiplied
    by ``factor``, zero elsewhere. And [B, T] how decided the choice was for
    the experts held in the file (``first`` .. ``first + held - 1``; all of
    them where the file holds every routed expert): the least distance, in
    score + bias, of a held expert from the other side of the boundary between
    the last expert kept and the first one dropped, as a share of max|score +
    bias|."""
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
    expert plus the routed sum over the experts the file holds, an expert
    computed over the positions that chose it (every other position's weight
    for it is zero; the rows are padded to a multiple of 64 so that ``ffn`` is
    built for a handful of shapes)."""
    h, p = qf.h, f"layers.{l}."
    first, held = h["first_expert"], h["n_experts"]
    mix, gap = routing(xn, qf.raw(p + "moe_router"), qf.f32(p + "router_bias"),
                       top_k=h["n_active_experts"], first=first, held=held,
                       factor=h["routed_scale_milli"] / 1000.0)
    if router_gaps is not None:
        router_gaps.append(np.asarray(gap[:, np.asarray(positions)]))
    flat = xn.reshape(-1, xn.shape[-1])
    mix = np.asarray(mix).reshape(len(flat), -1)
    out = np.zeros(flat.shape, np.float32)
    if h["n_shared_experts"]:
        out += np.asarray(ffn(flat, qf.raw(p + "shared.gate"), qf.raw(p + "shared.up"),
                              qf.raw(p + "shared.down")))
    for e in range(held):
        rows = np.flatnonzero(mix[:, first + e])
        if not len(rows):
            continue
        padded = np.zeros(-(-len(rows) // 64) * 64, rows.dtype)
        padded[:len(rows)] = rows
        ep = f"{p}experts.{e}."
        y = ffn(flat[padded], qf.raw(ep + "gate"), qf.raw(ep + "up"), qf.raw(ep + "down"))
        out[rows] += mix[rows, first + e, None] * np.asarray(y)[:len(rows)]
    return jnp.asarray(out).reshape(xn.shape)


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
        x = x + mixer(rmsnorm(x, qf.f32(p + "rms_att")), qf.raw(p + "q_a"), qf.f32(p + "q_a_norm"),
                      qf.raw(p + "q_b"), qf.raw(p + "kv_a"), qf.f32(p + "kv_a_norm"),
                      qf.raw(p + "kv_b"), qf.raw(p + "wo"), heads=h["n_heads"],
                      nope=h["qk_nope_head_dim"], rope_dim=h["qk_rope_head_dim"],
                      v_dim=h["v_head_dim"], theta=float(h["rope_theta"]))
        xn = rmsnorm(x, qf.f32(p + "rms_ffn"))
        if is_dense(h, l):
            x = x + ffn(xn, qf.raw(p + "gate"), qf.raw(p + "up"), qf.raw(p + "down"))
        else:
            x = x + moe(qf, l, xn, positions, router_gaps)
    return np.asarray(head(x[:, np.asarray(positions)], qf.f32("rms_final"), qf.raw("wcls")))
