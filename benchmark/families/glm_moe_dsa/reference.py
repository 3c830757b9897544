"""GLM-5's plain reference (``glm_moe_dsa``): GLM-4.7-Flash's layers (latent
attention in its EXPANDED form, leading dense layers, then sparse-expert
layers that hold a share of the routed experts beside a shared one) with, in
every layer, DeepSeek Sparse Attention in front of the attention: an indexer
scores every earlier position for a query and the softmax runs over the
``index_topk`` positions of largest score. In straightforward float32
``jax.numpy`` at ``highest`` precision, over weights dequantized from the
file's raw Q40 bytes one layer at a time. No cache, no kernel, NO ABSORPTION,
and the selection by a plain ``top_k`` over the scores: the served path,
which keeps latents and index keys in bfloat16, folds the up-projections into
query and output and finds the k-th largest score bit by bit, is held to this
order of operations. Attention in blocks of queries, so that a prompt of some
thousand tokens fits the host. It carries its own copy of the sibling family's
lines (``glm4_moe_lite``): the precision control rounds what THIS module's
``matmul`` sees.

Per layer (``x`` the residual stream, eps 1e-5, ``H`` heads, ``u =
rmsnorm(x, w_att)``):

* latent attention, GLM-4.7-Flash's word for word: ``c_q = rmsnorm(u W_qa,
  w_qn)`` (``q_lora_rank``); ``q = c_q W_qb``, a head ``[q_nope | q_rope]``;
  ``[c | k_r] = u W_kva``; ``c_kv = rmsnorm(c, w_kvn)``; ``[k_nope | v] =
  c_kv W_kvb``; ``q_rope`` and ``k_r`` rotated at their position (pairs ``(j,
  j + rope / 2)``, ``theta ** (-2j / rope)``), the ONE rotated ``k_r`` every
  head's; ``score = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``.
* the indexer: ``q_I[t] = c_q[t] W_Iq``, ``J`` heads of ``I`` values, the first
  ``rope`` of each rotated; ``k_I[s] = layernorm(u[s] W_Ik)`` (weight and bias,
  eps 1e-6), ``I`` values, its first ``rope`` rotated (the same positions and
  theta); ``w[t] = u[t] W_Iw`` (``J``); ``I[t, s] = sum_j w[t, j] relu(q_I[t,
  j] . k_I[s])`` for ``s <= t``. ``S_t`` is the ``min(index_topk, t + 1)``
  positions of largest ``I[t, .]``: every position while ``t < index_topk``.
* ``o = sum over s in S_t of softmax over S_t (score)[s] v[s]``; ``x +=
  concat(o) W_o``.
* the feed-forward as GLM-4.7-Flash's: a leading dense layer ``x +=
  W_down(silu(W_gate h2) * W_up h2)``; an expert layer ``s = sigmoid(h2 W_r)``,
  the ``k`` largest of ``s + b`` chosen, ``w_e = factor * s_e / sum of the
  chosen s``, ``x += SwiGLU_shared(h2) + sum over the chosen experts HELD in
  the file of w_e SwiGLU_e(h2)``; what an absent expert would add is left out,
  as the program leaves it out.
* ``logits = rmsnorm(x, w_final) W_head``.

Departures from the published mechanism, each a choice that cannot move the
selection or that seeded weights make a relabelling of (the configuration's
file lists them under ``assumed``): the indexer's constant factors (``J **
-0.5``, ``I ** -0.5``, an FP8 scale) are positive and are left out; its
Hadamard rotation of ``q_I`` and ``k_I`` is orthogonal and leaves every dot
product what it was, and is left out; its FP8 rounding is a precision nothing
here serves: the PROGRAM caches index keys in bfloat16 and scores in float32,
this reference keeps both in float32. A tie at the ``index_topk``-th score may
fall either way: ``forward`` reports how wide the gap there is
(``selection_gaps``), as it reports router gaps. Departures forced by the file
format: Q40 weights (dequantized exactly), the router Q40 like every matrix.
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
        40: "v_head_dim", 50: "index_n_heads", 51: "index_head_dim", 52: "index_topk"}
USE_ROPE, NORM_TOPK, SIGMOID_ROUTER = 1, 8, 16
ROPE_HALVES = 1  # the rotation pairs value j with value j + rope / 2
QUERY_BLOCK = 512
LAYERNORM_EPS = 1e-6


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
    if not h.get("index_topk") or not h.get("index_n_heads") or h.get("index_head_dim", 0) < h["qk_rope_head_dim"]:
        raise ValueError("this reference reads the files with an indexer: the header states none "
                         "(family glm4_moe_lite reads those)")
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
        yield p + "index_q", (h["index_n_heads"] * h["index_head_dim"], h["q_lora_rank"]), Q40
        yield p + "index_k", (h["index_head_dim"], dim), Q40
        yield p + "index_k_norm", (2, h["index_head_dim"]), F32
        yield p + "index_w", (h["index_n_heads"], dim), Q40
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


def rope_leading(x, r: int, theta: float):
    """x [B, T, heads, n]: the first ``r`` values of every head rotated."""
    return jnp.concatenate([rope(x[..., :r], theta), x[..., r:]], axis=-1)


def layernorm(x, weight_bias):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    normed = centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + LAYERNORM_EPS)
    return normed * weight_bias[0] + weight_bias[1]


def select(scores, seen, top_k: int):
    """bool [B, t, s], the ``top_k`` seen positions of largest ``scores`` [B,
    t, s] a query (every seen one where there are no more), by a plain top-k;
    and [B, t] how decided the cut was: the ``top_k``-th score less the next
    one, as a share of the largest |score| the query sees (``inf`` where
    nothing was cut)."""
    B, t, s = scores.shape
    scores = jnp.where(seen, scores, -jnp.inf)
    if s <= top_k:
        return jnp.broadcast_to(seen, scores.shape), jnp.full((B, t), jnp.inf)
    vals, idx = jax.lax.top_k(scores, top_k + 1)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(t)[None, :, None], idx[..., :top_k]].set(True)
    largest = jnp.max(jnp.where(seen, jnp.abs(scores), 0.0), axis=-1)
    cut = jnp.isfinite(vals[..., top_k])
    gap = jnp.where(cut, (vals[..., top_k - 1] - vals[..., top_k]) / largest, jnp.inf)
    return chosen & seen, gap


@functools.partial(jax.jit, static_argnames=("heads", "nope", "rope_dim", "v_dim", "theta",
                                             "index_heads", "top_k"))
def mixer(xn, q_a, w_qn, q_b, kv_a, w_kvn, kv_b, wo, index_q, index_k, index_k_norm, index_w, *,
          heads, nope, rope_dim, v_dim, theta, index_heads, top_k):
    """Sparse latent attention of one layer on normed ``xn`` [B, T, dim],
    expanded: every position's keys and values for every head and its index
    key, then, a block of queries at a time, the indexer's scores, the
    selection and causal attention over the selected positions. Returns the
    layer's output and the selection's gaps [B, T] (:func:`select`)."""
    B, T, _ = xn.shape
    c_q = rmsnorm(matmul(xn, q_a), w_qn)
    q = matmul(c_q, q_b).reshape(B, T, heads, nope + rope_dim)
    low = matmul(xn, kv_a)
    rank = low.shape[-1] - rope_dim
    kv = matmul(rmsnorm(low[..., :rank], w_kvn), kv_b).reshape(B, T, heads, nope + v_dim)
    k_rope = jnp.broadcast_to(rope(low[..., None, rank:], theta), (B, T, heads, rope_dim))
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
    v = kv[..., nope:]
    q_i = rope_leading(matmul(c_q, index_q).reshape(B, T, index_heads, -1), rope_dim, theta)
    k_i = rope_leading(layernorm(matmul(xn, index_k), index_k_norm)[:, :, None], rope_dim, theta)[:, :, 0]
    w_i = matmul(xn, index_w)
    outs, gaps = [], []
    for start in range(0, T, QUERY_BLOCK):
        stop = min(T, start + QUERY_BLOCK)
        seen = (jnp.arange(stop)[None, :] <= jnp.arange(start, stop)[:, None])[None]
        dots = jnp.einsum("btji,bsi->btjs", q_i[:, start:stop], k_i[:, :stop], precision=HI)
        index = jnp.sum(w_i[:, start:stop, :, None] * jax.nn.relu(dots), axis=2)
        chosen, gap = select(index, seen, top_k)
        gaps.append(gap)
        s = jnp.einsum("bthd,bshd->bhts", q[:, start:stop], k[:, :stop], precision=HI)
        s = jnp.where(chosen[:, None], s / jnp.sqrt(jnp.float32(nope + rope_dim)), -jnp.inf)
        outs.append(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v[:, :stop],
                               precision=HI))
    out = matmul(jnp.concatenate(outs, axis=1).reshape(B, T, heads * v_dim), wo)
    return out, jnp.concatenate(gaps, axis=1)


@jax.jit
def ffn(xn, gate, up, down):
    return matmul(jax.nn.silu(matmul(xn, gate)) * matmul(xn, up), down)


@functools.partial(jax.jit, static_argnames=("top_k", "first", "held", "factor"))
def routing(xn, router, bias, *, top_k, first, held, factor):
    """[B, T, E] mixing weights over ALL experts: sigmoid scores, the top k of
    score + bias kept, their scores renormalised to sum to one and multiplied
    by ``factor``, zero elsewhere. And [B, T] how decided the choice was for
    the experts held in the file (``first`` .. ``first + held - 1``): the
    least distance, in score + bias, of a held expert from the other side of
    the boundary between the last expert kept and the first one dropped, as a
    share of max|score + bias|."""
    scores = jax.nn.sigmoid(matmul(xn, router))
    select_by = scores + bias
    _, idx = jax.lax.top_k(select_by, top_k)
    chosen = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=jnp.float32), axis=-2)
    mix = chosen * scores
    mix = factor * mix / jnp.sum(mix, axis=-1, keepdims=True)
    ranked = jnp.sort(select_by, axis=-1)
    last_kept, first_dropped = ranked[..., -top_k, None], ranked[..., -top_k - 1, None]
    sel_h, chosen_h = select_by[..., first:first + held], chosen[..., first:first + held]
    to_other_side = jnp.where(chosen_h > 0, sel_h - first_dropped, last_kept - sel_h)
    gap = jnp.min(to_other_side, axis=-1) / jnp.max(jnp.abs(select_by), axis=-1)
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


def forward(qf, tokens: np.ndarray, positions: np.ndarray, router_gaps: list | None = None,
            selection_gaps: list | None = None) -> np.ndarray:
    """Logits [B, len(positions), vocab] after a full causal pass over
    ``tokens`` [B, T]; layers are streamed from the file one at a time. Each
    expert layer's [B, len(positions)] routing gap (see ``routing``) is
    appended to ``router_gaps`` where a list is given, each layer's [B,
    len(positions)] selection gap (see ``select``) to ``selection_gaps``."""
    h = qf.h
    x = jnp.asarray(qf.f32("embedding", rows=np.asarray(tokens)))
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        att, gap = mixer(
            rmsnorm(x, qf.f32(p + "rms_att")), qf.raw(p + "q_a"), qf.f32(p + "q_a_norm"),
            qf.raw(p + "q_b"), qf.raw(p + "kv_a"), qf.f32(p + "kv_a_norm"), qf.raw(p + "kv_b"),
            qf.raw(p + "wo"), qf.raw(p + "index_q"), qf.raw(p + "index_k"),
            qf.f32(p + "index_k_norm"), qf.raw(p + "index_w"), heads=h["n_heads"],
            nope=h["qk_nope_head_dim"], rope_dim=h["qk_rope_head_dim"], v_dim=h["v_head_dim"],
            theta=float(h["rope_theta"]), index_heads=h["index_n_heads"], top_k=h["index_topk"])
        if selection_gaps is not None:
            selection_gaps.append(np.asarray(gap[:, np.asarray(positions)]))
        x = x + att
        xn = rmsnorm(x, qf.f32(p + "rms_ffn"))
        if is_dense(h, l):
            x = x + ffn(xn, qf.raw(p + "gate"), qf.raw(p + "up"), qf.raw(p + "down"))
        else:
            x = x + moe(qf, l, xn, positions, router_gaps)
    return np.asarray(head(x[:, np.asarray(positions)], qf.f32("rms_final"), qf.raw("wcls")))
