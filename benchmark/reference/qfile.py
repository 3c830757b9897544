"""The benchmark's own reader of the ``.m`` format: header, tensor layout and
raw Q40 bytes, written from the format's description (reference
distributed-llama ``transformer.cpp`` / ``converter/writer.py``) and sharing
no code with the package under test. The format is one for every family;
which header values a family's files carry, what follows from them and which
tensors follow the header are the family's (``reference.header``,
``reference.layout``)."""

from __future__ import annotations

import struct

import numpy as np

MAGIC = 0xA00ABCD
# the format's numbered header keys; a key without a name here stays under its number
KEYS = {1: "arch", 2: "dim", 3: "hidden_dim", 4: "n_layers", 5: "n_heads", 6: "n_kv_heads",
        7: "n_experts", 8: "n_active_experts", 9: "vocab_size", 10: "seq_len", 11: "hidden_act",
        12: "rope_theta", 13: "weights_float_type", 18: "rope_type"}
Q40, F32 = 2, 0
BLOCK, BLOCK_BYTES = 32, 18


def named(raw: dict[int, int], more: dict[int, str] | None = None) -> dict:
    """Every value the header holds, under its key's name where ``KEYS`` or
    the family's ``more`` gives one."""
    names = {**KEYS, **(more or {})}
    return {names.get(key, key): value for key, value in raw.items()}


class QFile:
    """mmap of a Q40 ``.m`` file read as ``family`` (a family's ``reference``
    part) lays it out: ``h`` the header, ``raw(name)`` a Q40 tensor's blocks
    as uint8 ``[d_out, d_in/32, 18]``, ``f32(name)`` an f32 tensor."""

    def __init__(self, path: str, family):
        with open(path, "rb") as f:
            magic, size = struct.unpack("<ii", f.read(8))
            if magic != MAGIC:
                raise ValueError(f"not a key-value .m header: magic {magic:#x}")
            ints = struct.unpack(f"<{(size - 8) // 4}i", f.read(size - 8))
        self.h = family.header(dict(zip(ints[::2], ints[1::2])))
        self._map = np.memmap(path, dtype=np.uint8, mode="r")
        self._at: dict[str, tuple[int, tuple[int, ...], int]] = {}
        offset = size
        for name, shape, kind in family.layout(self.h):
            n = int(np.prod(shape))
            if kind not in (Q40, F32) or name in self._at:
                raise ValueError(f"layout entry {name!r}: kind {kind}, or named twice")
            self._at[name] = (offset, shape, kind)
            offset += n * 4 if kind == F32 else n // BLOCK * BLOCK_BYTES
        if offset != self._map.shape[0]:
            raise ValueError(f"layout expects {offset} bytes, the file has {self._map.shape[0]}")

    def raw(self, name: str) -> np.ndarray:
        offset, (d_out, d_in), kind = self._at[name]
        if kind != Q40:
            raise ValueError(f"{name} is not Q40")
        n = d_out * (d_in // BLOCK) * BLOCK_BYTES
        return np.asarray(self._map[offset:offset + n]).reshape(d_out, d_in // BLOCK, BLOCK_BYTES)

    def f32(self, name: str, rows: np.ndarray | None = None) -> np.ndarray:
        offset, shape, kind = self._at[name]
        if kind != F32:
            raise ValueError(f"{name} is not f32")
        n = int(np.prod(shape))
        full = self._map[offset:offset + 4 * n].view(np.float32).reshape(shape)
        return np.asarray(full if rows is None else full[rows])
