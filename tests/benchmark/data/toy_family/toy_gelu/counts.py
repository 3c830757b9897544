"""The TOY family's counts: a dense step reads every matrix once."""

from __future__ import annotations

CONFIG_KEYS = frozenset({"d_model", "d_ff", "n_layer", "n_head", "n_kv_head", "d_head", "vocab", "theta"})
_Q40 = 18 / 32


def decode_step_bytes(c: dict, rows: float, live_positions: float) -> float:
    d, kv = c["d_model"], c["n_kv_head"] * c["d_head"]
    layer = 2 * d * d + 2 * d * kv + 3 * d * c["d_ff"]
    weights = (c["n_layer"] * layer + d * c["vocab"]) * _Q40 + 4 * ((2 * c["n_layer"] + 1) * d + rows * d)
    return weights + live_positions * 2 * c["n_layer"] * kv * 2


def kernel_launch(c: dict, role: str, shape: list[int]) -> tuple[float, float]:
    d_in = {"gate_up": c["d_model"], "down": c["d_ff"]}[role]
    rows, d_out = shape
    return d_in * d_out * _Q40 + rows * d_in + 4 * rows * d_out, 2.0 * rows * d_in * d_out
