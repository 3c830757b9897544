"""The TOY family's plain reference: the dense block's attention, a
feed-forward with tanh-GELU in place of SiLU. The attention is the one the
checkout's ``llama`` family wrote down; the rest is the toy's own."""

from __future__ import annotations

import os

import jax
import numpy as np

from benchmark import families
from benchmark.reference.ops import matmul, rmsnorm
from benchmark.reference.qfile import Q40, named

_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_dense = families.load({"name": "toy", "family": "llama"}, "reference", _BENCH_DIR)


def header(raw: dict[int, int]) -> dict:
    h = named(raw)
    if h["weights_float_type"] != Q40 or h["hidden_act"] != 0 or h.get("n_experts", 0):
        raise ValueError("the toy reference reads dense Q40 files with GELU only")
    h["head_dim"] = h["dim"] // h["n_heads"]
    h["kv_dim"] = h["head_dim"] * h["n_kv_heads"]
    return h


def layout(h: dict):
    return _dense.layout({**h, "n_experts": 0})  # the dense block's tensors, in its order


@jax.jit
def ffn(xn, gate, up, down):
    return matmul(jax.nn.gelu(matmul(xn, gate), approximate=True) * matmul(xn, up), down)


def forward(qf, tokens: np.ndarray, positions: np.ndarray, router_gaps: list | None = None) -> np.ndarray:
    h = qf.h
    x = jax.numpy.asarray(qf.f32("embedding", rows=np.asarray(tokens)))
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        x = _dense.attention_block(x, qf.f32(p + "rms_att"), qf.raw(p + "q"), qf.raw(p + "k"),
                                   qf.raw(p + "v"), qf.raw(p + "wo"), n_heads=h["n_heads"],
                                   n_kv=h["n_kv_heads"], theta=float(h["rope_theta"]), interleaved=True)
        x = x + ffn(rmsnorm(x, qf.f32(p + "rms_ffn")), qf.raw(p + "gate"), qf.raw(p + "up"),
                    qf.raw(p + "down"))
    return np.asarray(_dense.head(x[:, np.asarray(positions)], qf.f32("rms_final"), qf.raw("wcls")))
