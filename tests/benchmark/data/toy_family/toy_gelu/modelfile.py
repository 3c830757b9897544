"""A TOY family, for tests/benchmark/test_bench_family.py only: the dense GQA
block with a tanh-GELU feed-forward, spelled with keys of its own. It is no
model anybody serves; it is here to arrive as files."""

from __future__ import annotations

import numpy as np


def model_spec(config: dict, seq_len: int):
    from distributed_llama_tpu.formats.model_file import ArchType, HiddenAct, ModelSpec, RopeType
    from distributed_llama_tpu.quants import FloatType

    return ModelSpec(
        arch_type=ArchType.LLAMA, dim=config["d_model"], hidden_dim=config["d_ff"],
        n_layers=config["n_layer"], n_heads=config["n_head"], n_kv_heads=config["n_kv_head"],
        vocab_size=config["vocab"], seq_len=seq_len, n_experts=0, n_active_experts=0,
        hidden_act=HiddenAct.GELU, rope_theta=float(config["theta"]), rope_type=RopeType.LLAMA,
        weights_float_type=FloatType.Q40)


def role(name: str) -> str | None:
    if name == "rms_final":
        return None  # no shared rule: this family draws it itself
    if name in ("embedding", "wcls"):
        return {"embedding": "embedding", "wcls": "head"}[name]
    if "rms" in name:
        return "norm"
    return "residual" if name.endswith((".wo", ".down")) else "matrix"


def draw(entry, rng: np.random.Generator) -> np.ndarray:
    if entry.name == "rms_final":
        return np.full(entry.shape, 1.25, np.float32)
    raise ValueError(f"the toy family draws no {entry.name}")
