"""The Q40 leaves of a benchmark configuration as the loader would build them,
at the published widths, as SHAPES: ``engine.weights.load_params`` run over a
reader that knows the file's tensor list (the family's ``model_spec`` and the
format's ``tensor_layout``) and holds no bytes, with the three packers of
``ops/q40.py`` standing in as shape arithmetic (``_n_padded`` / ``_d_padded``,
the one place the padding rule lives). Which tensors are fused into one pack
(``q|k|v``, ``gate|up``, ``q_a|kv_a``), which are stacked into a bank and what
the head is cut to stay the loader's decisions, not a table's.
"""

from __future__ import annotations

import glob
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(os.path.basename(p)[: -len(".json")]
                 for p in glob.glob(os.path.join(REPO, "benchmark", "configs", "*.json")))
SEQ_LEN = 2048  # no Q40 leaf's shape reads it


class _Unread(np.ndarray):
    """A zero-stride view of one zero standing for a float32 tensor nobody
    reads: the embedding's ``astype`` would be its only copy of file size."""

    def astype(self, dtype, *args, **kwargs):
        return self


class _ShapeReader:
    """``ModelFileReader``'s face for the loader, with no file behind it."""

    def __init__(self, spec):
        from distributed_llama_tpu.formats.model_file import tensor_layout

        self.spec = spec
        self.entries = {e.name: e for e in tensor_layout(spec)}

    def raw(self, name: str) -> np.ndarray:
        return np.zeros((0,), np.uint8)

    def raw_rows(self, name: str, row_start: int, row_end: int) -> np.ndarray:
        return np.zeros((0,), np.uint8)

    def _zeros(self, shape) -> np.ndarray:
        return np.broadcast_to(np.zeros((), np.float32), shape).view(_Unread)

    def tensor(self, name: str) -> np.ndarray:
        return self._zeros(self.entries[name].shape)

    def tensor_rows(self, name: str, row_start: int, row_end: int) -> np.ndarray:
        return self._zeros((row_end - row_start,) + tuple(self.entries[name].shape[1:]))


def _pack_shape(d_out: int, d_in: int):
    from distributed_llama_tpu.ops import q40

    np_, dp = q40._n_padded(d_in), q40._d_padded(d_out)
    return q40.QuantizedMatrix(
        jax.ShapeDtypeStruct((np_ // 2, dp), jnp.uint8),
        jax.ShapeDtypeStruct((np_ // 32, dp), jnp.float32), d_in, d_out,
    )


def _stack_shapes(mats):
    from distributed_llama_tpu.ops import q40

    first, E = mats[0], len(mats)
    assert all((m.qs.shape, m.n, m.d) == (first.qs.shape, first.n, first.d) for m in mats)
    return q40.QuantizedMatrix(
        jax.ShapeDtypeStruct((E,) + first.qs.shape, jnp.uint8),
        jax.ShapeDtypeStruct((E,) + first.scales.shape, jnp.float32), first.n, first.d,
    )


def config_of(name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def param_shapes(name: str):
    """``load_params``' tree for configuration ``name`` with every Q40 leaf a
    ``QuantizedMatrix`` of ``ShapeDtypeStruct``s."""
    from benchmark import families
    from distributed_llama_tpu.engine.weights import load_params
    from distributed_llama_tpu.ops import q40

    config = config_of(name)
    spec = families.load(config, "modelfile").model_spec(config, SEQ_LEN)
    with mock.patch.multiple(
        q40, pack_q40_raw=lambda raw, shape: _pack_shape(*shape),
        quantize_q40_tpu=lambda w: _pack_shape(w.shape[1], w.shape[0]), stack_bank=_stack_shapes,
    ):
        return load_params(_ShapeReader(spec), dtype="q40")


def q40_leaves(params) -> dict[str, list]:
    """{leaf name: its packs, one a layer that has it} in the naming of
    ``engine.weights.q40_padded_bytes``."""
    from distributed_llama_tpu.ops.q40 import QuantizedMatrix

    out: dict[str, list] = {}

    def walk(name, node):
        if isinstance(node, QuantizedMatrix):
            out.setdefault(name, []).append(node)
        elif isinstance(node, dict):
            for key, value in node.items():
                walk(key, value)
        elif isinstance(node, (list, tuple)):
            for value in node:
                walk(name, value)

    walk("", params)
    return out
