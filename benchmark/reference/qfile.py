"""The benchmark's own reader of the ``.m`` format: header, tensor layout and
raw Q40 bytes, written from the format's description (reference
distributed-llama ``transformer.cpp`` / ``converter/writer.py``) and sharing
no code with the package under test."""

from __future__ import annotations

import struct

import numpy as np

MAGIC = 0xA00ABCD
_KEYS = {1: "arch", 2: "dim", 3: "hidden_dim", 4: "n_layers", 5: "n_heads", 6: "n_kv_heads",
         7: "n_experts", 8: "n_active_experts", 9: "vocab_size", 10: "seq_len", 11: "hidden_act",
         12: "rope_theta", 13: "weights_float_type", 18: "rope_type"}
ARCH_LLAMA, ARCH_MIXTRAL = 0xABCD00, 0xABCD02
ROPE_INTERLEAVED, ROPE_HALF_SPLIT = 0, 1
Q40, F32 = 2, 0
BLOCK, BLOCK_BYTES = 32, 18


class QFile:
    """mmap of a Q40 ``.m`` file: ``raw(name)`` gives a Q40 tensor's blocks as
    uint8 ``[d_out, d_in/32, 18]``, ``f32(name)`` an f32 tensor."""

    def __init__(self, path: str):
        with open(path, "rb") as f:
            magic, size = struct.unpack("<ii", f.read(8))
            if magic != MAGIC:
                raise ValueError(f"not a key-value .m header: magic {magic:#x}")
            ints = struct.unpack(f"<{(size - 8) // 4}i", f.read(size - 8))
        h = {"n_experts": 0, "n_active_experts": 0, "rope_type": -1}
        for key, value in zip(ints[::2], ints[1::2]):
            if key in _KEYS:
                h[_KEYS[key]] = value
        if h["weights_float_type"] != Q40 or h["hidden_act"] != 1:
            raise ValueError("the reference reads Q40 weights with SiLU only")
        if h["arch"] not in (ARCH_LLAMA, ARCH_MIXTRAL):
            raise ValueError(f"unknown architecture {h['arch']:#x}")
        if h["rope_type"] < 0:
            h["rope_type"] = ROPE_INTERLEAVED if h["arch"] == ARCH_LLAMA else ROPE_HALF_SPLIT
        h["head_dim"] = h["dim"] // h["n_heads"]
        h["kv_dim"] = h["head_dim"] * h["n_kv_heads"]
        self.h = h
        self._map = np.memmap(path, dtype=np.uint8, mode="r")
        self._at: dict[str, tuple[int, tuple[int, int], int]] = {}
        offset = size

        def add(name: str, shape: tuple[int, ...], kind: int) -> None:
            nonlocal offset
            n = int(np.prod(shape))
            nbytes = n * 4 if kind == F32 else n // BLOCK * BLOCK_BYTES
            self._at[name] = (offset, shape, kind)
            offset += nbytes

        dim, hid, kv, vocab = h["dim"], h["hidden_dim"], h["kv_dim"], h["vocab_size"]
        add("embedding", (vocab, dim), F32)
        for l in range(h["n_layers"]):
            p = f"layers.{l}."
            add(p + "q", (dim, dim), Q40)
            add(p + "k", (kv, dim), Q40)
            add(p + "v", (kv, dim), Q40)
            add(p + "wo", (dim, dim), Q40)
            if h["n_experts"]:
                add(p + "moe_router", (h["n_experts"], dim), Q40)
                for e in range(h["n_experts"]):
                    add(f"{p}experts.{e}.up", (hid, dim), Q40)
                    add(f"{p}experts.{e}.gate", (hid, dim), Q40)
                    add(f"{p}experts.{e}.down", (dim, hid), Q40)
            else:
                add(p + "gate", (hid, dim), Q40)
                add(p + "down", (dim, hid), Q40)
                add(p + "up", (hid, dim), Q40)
            add(p + "rms_att", (dim,), F32)
            add(p + "rms_ffn", (dim,), F32)
        add("rms_final", (dim,), F32)
        add("wcls", (vocab, dim), Q40)
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
