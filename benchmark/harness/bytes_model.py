"""Bytes a decode step must read from device memory, from shapes alone: every
weight the step touches once, plus the live keys and values. It is a floor
(what the algorithm needs), so dividing it by measured time and the chip's
peak gives a share that a correct count cannot push past 100 %."""

from __future__ import annotations

Q40_BYTES_PER_WEIGHT = 18 / 32  # a 32-value block: f16 scale + 16 nibble bytes


def _attention_weights(c: dict) -> int:
    h, hd = c["hidden_size"], c["head_dim"]
    q = h * c["num_attention_heads"] * hd
    kv = 2 * h * c["num_key_value_heads"] * hd
    o = c["num_attention_heads"] * hd * h
    return q + kv + o


def _ffn_weights(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def experts_touched(n_experts: int, top_k: int, rows: float) -> float:
    """Expected number of distinct experts that ``rows`` tokens choosing
    ``top_k`` of ``n_experts`` at random touch in one layer."""
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** rows)


def weight_bytes_per_step(c: dict, rows: float) -> float:
    """Q40 bytes of the layers' matrices and the output head read in one
    decode step of ``rows`` sequences, plus the f32 norms and one f32
    embedding row per sequence."""
    h = c["hidden_size"]
    n_exp = c.get("num_local_experts", 0)
    per_layer = _attention_weights(c)
    if n_exp:
        per_layer += h * n_exp  # router
        per_layer += _ffn_weights(c) * experts_touched(n_exp, c["num_experts_per_tok"], rows)
    else:
        per_layer += _ffn_weights(c)
    q40 = c["num_hidden_layers"] * per_layer + h * c["vocab_size"]
    f32 = (2 * c["num_hidden_layers"] + 1) * h + rows * h
    return q40 * Q40_BYTES_PER_WEIGHT + 4 * f32


def kv_bytes_per_position(c: dict, kv_bytes: int = 2) -> int:
    """Keys and values of one position across all layers (bf16 by default)."""
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * c["head_dim"] * kv_bytes


def decode_step_bytes(c: dict, rows: float, live_positions: float) -> float:
    """``live_positions`` is the sum over the step's sequences of their
    context lengths."""
    return weight_bytes_per_step(c, rows) + live_positions * kv_bytes_per_position(c)
