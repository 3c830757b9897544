"""The Llama lineage's plain reference: the forward pass of a dense GQA
transformer (Mistral) and of its sparse-expert sibling (Mixtral) in
straightforward float32 ``jax.numpy``, with no kernel, no cache and no
batching tricks, over weights dequantized from the file's raw Q40 bytes one
layer at a time; and what the ``.m`` file of either looks like.

Departures from the published models, all forced by the file format under
test: weights are Q40 blocks (dequantized exactly: value = scale * (nibble - 8)),
the router is stored Q40 like every matrix, and the rope pairing is the
header's (interleaved pairs for the llama layout, half-split for mixtral).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.ops import HI, matmul, rmsnorm
from benchmark.reference.qfile import F32, Q40, named

ARCH_LLAMA, ARCH_MIXTRAL = 0xABCD00, 0xABCD02
ROPE_INTERLEAVED, ROPE_HALF_SPLIT = 0, 1


def header(raw: dict[int, int]) -> dict:
    """The header's numbered values under their names, with what follows
    from them; refuses what this reference cannot read."""
    h = {"n_experts": 0, "n_active_experts": 0, "rope_type": -1, **named(raw)}
    if h["weights_float_type"] != Q40 or h["hidden_act"] != 1:
        raise ValueError("the reference reads Q40 weights with SiLU only")
    if h["arch"] not in (ARCH_LLAMA, ARCH_MIXTRAL):
        raise ValueError(f"unknown architecture {h['arch']:#x}")
    if h["rope_type"] < 0:
        h["rope_type"] = ROPE_INTERLEAVED if h["arch"] == ARCH_LLAMA else ROPE_HALF_SPLIT
    h["head_dim"] = h["dim"] // h["n_heads"]
    h["kv_dim"] = h["head_dim"] * h["n_kv_heads"]
    return h


def layout(h: dict):
    """(name, shape, kind) of every tensor, in file order."""
    dim, hid, kv, vocab = h["dim"], h["hidden_dim"], h["kv_dim"], h["vocab_size"]
    yield "embedding", (vocab, dim), F32
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        yield p + "q", (dim, dim), Q40
        yield p + "k", (kv, dim), Q40
        yield p + "v", (kv, dim), Q40
        yield p + "wo", (dim, dim), Q40
        if h["n_experts"]:
            yield p + "moe_router", (h["n_experts"], dim), Q40
            for e in range(h["n_experts"]):
                yield f"{p}experts.{e}.up", (hid, dim), Q40
                yield f"{p}experts.{e}.gate", (hid, dim), Q40
                yield f"{p}experts.{e}.down", (dim, hid), Q40
        else:
            yield p + "gate", (hid, dim), Q40
            yield p + "down", (dim, hid), Q40
            yield p + "up", (hid, dim), Q40
        yield p + "rms_att", (dim,), F32
        yield p + "rms_ffn", (dim,), F32
    yield "rms_final", (dim,), F32
    yield "wcls", (vocab, dim), Q40


def rope(x, theta: float, interleaved: bool):
    """x [B, T, heads, hd], positions 0..T-1."""
    hd = x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)  # [hd/2]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]  # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta", "interleaved"))
def attention_block(x, rms, wq, wk, wv, wo, *, n_heads, n_kv, theta, interleaved):
    B, T, D = x.shape
    hd = D // n_heads
    xn = rmsnorm(x, rms)
    q = rope(matmul(xn, wq).reshape(B, T, n_heads, hd), theta, interleaved)
    k = rope(matmul(xn, wk).reshape(B, T, n_kv, hd), theta, interleaved)
    v = matmul(xn, wv).reshape(B, T, n_kv, hd)
    k = jnp.repeat(k, n_heads // n_kv, axis=2)
    v = jnp.repeat(v, n_heads // n_kv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v, precision=HI)
    return x + matmul(o.reshape(B, T, D), wo)


@jax.jit
def ffn(xn, gate, up, down):
    return matmul(jax.nn.silu(matmul(xn, gate)) * matmul(xn, up), down)


@functools.partial(jax.jit, static_argnames=("top_k",))
def routing(xn, router, *, top_k):
    """[B, T, E] mixing weights: softmax over all experts, the top k kept and
    renormalised to sum to one, zero elsewhere. And [B, T] how decided the
    choice was: the router logit of the last expert kept minus that of the
    first one dropped, as a share of max|router logit|."""
    scores = matmul(xn, router)
    probs = jax.nn.softmax(scores, axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    mix = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32) * top[..., None], axis=-2)
    ranked = jnp.sort(scores, axis=-1)
    gap = (ranked[..., -top_k] - ranked[..., -top_k - 1]) / jnp.max(jnp.abs(scores), axis=-1)
    return mix, gap


@jax.jit
def head(x, rms, wcls):
    return matmul(rmsnorm(x, rms), wcls)


def forward(qf, tokens: np.ndarray, positions: np.ndarray,
            router_gaps: list | None = None) -> np.ndarray:
    """Logits [B, len(positions), vocab] after a full causal pass over
    ``tokens`` [B, T]; layers are streamed from the file one at a time. A
    sparse-expert model appends each layer's [B, len(positions)] routing gap
    (see ``routing``) to ``router_gaps`` where a list is given."""
    h = qf.h
    kw = dict(n_heads=h["n_heads"], n_kv=h["n_kv_heads"], theta=float(h["rope_theta"]),
              interleaved=h["rope_type"] == ROPE_INTERLEAVED)
    x = jnp.asarray(qf.f32("embedding", rows=np.asarray(tokens)))
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        x = attention_block(x, qf.f32(p + "rms_att"), qf.raw(p + "q"), qf.raw(p + "k"),
                            qf.raw(p + "v"), qf.raw(p + "wo"), **kw)
        xn = rmsnorm(x, qf.f32(p + "rms_ffn"))
        if h["n_experts"]:
            mix, gap = routing(xn, qf.raw(p + "moe_router"), top_k=h["n_active_experts"])
            if router_gaps is not None:
                router_gaps.append(np.asarray(gap[:, np.asarray(positions)]))
            for e in range(h["n_experts"]):
                ep = f"{p}experts.{e}."
                x = x + mix[..., e:e + 1] * ffn(xn, qf.raw(ep + "gate"), qf.raw(ep + "up"),
                                                 qf.raw(ep + "down"))
        else:
            x = x + ffn(xn, qf.raw(p + "gate"), qf.raw(p + "up"), qf.raw(p + "down"))
    return np.asarray(head(x[:, np.asarray(positions)], qf.f32("rms_final"), qf.raw("wcls")))
