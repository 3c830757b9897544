"""What the Llama lineage's decode step and its Q40 kernels must move and
compute, from shapes alone. Every count is a floor (what the algorithm
needs: a weight is read once, at the 18/32 bytes the file holds it in), so
dividing it by measured time and the chip's peak gives a share that a
correct count cannot push past 100 %."""

from __future__ import annotations

Q40_BYTES_PER_WEIGHT = 18 / 32  # a 32-value block: f16 scale + 16 nibble bytes

# every key of a configuration's file these functions and the family's builder read, and the
# published keys they knowingly leave alone (the norm's epsilon is the program's own constant)
CONFIG_KEYS = frozenset({
    "arch", "model_type", "hidden_act", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "vocab_size", "rope_theta",
    "rms_norm_eps", "sliding_window", "tie_word_embeddings", "num_local_experts",
    "num_experts_per_tok"})


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


def _matrix(c: dict, role: str, d_out: int) -> tuple[int, int]:
    """(d_in, rows of the matrix that hold weights) of the Q40 matrix a
    launch of ``role`` with ``d_out`` output columns multiplies by."""
    h, inter = c["hidden_size"], c["intermediate_size"]
    heads, hd = c["num_attention_heads"], c["head_dim"]
    if role == "wqkv" and d_out == (heads + 2 * c["num_key_value_heads"]) * hd:
        return h, d_out
    if role == "wo" and d_out == h:
        return heads * hd, d_out
    # gate and up as one matrix (2 x intermediate columns) or as two launches
    if role in ("gate_up", "experts") and d_out in (inter, 2 * inter):
        return h, d_out
    if role in ("down", "experts") and d_out == h:
        return inter, d_out
    # the head's columns are padded to the kernel's tile: the padding holds no weight
    if role == "logits" and c["vocab_size"] <= d_out < c["vocab_size"] + 4096:
        return h, c["vocab_size"]
    raise ValueError(f"no Q40 matrix of role {role!r} has {d_out} output columns in configuration "
                     f"{c.get('name')!r}")


def kernel_launch(c: dict, role: str, shape: list[int]) -> tuple[float, float]:
    """(bytes, operations) of ONE launch of the Q40 matmul kernel that carries
    ``role`` in its name and whose result is ``shape`` = [rows, d_out]. Bytes:
    the matrix once at its file size, the activations in at one byte a value
    (the int8 kernel's Q80 input; the norm-fused kernel reads more), the
    result out as the f32 the shape says. Operations: a multiply and an add
    for every weight and row. ``experts`` is one expert's launch: its output
    width says which of its matrices."""
    if role == "experts" and not c.get("num_local_experts"):
        raise ValueError(f"configuration {c.get('name')!r} has no experts")
    rows, d_out = shape
    d_in, d_held = _matrix(c, role, d_out)
    nbytes = d_in * d_held * Q40_BYTES_PER_WEIGHT + rows * d_in + 4 * rows * d_out
    return nbytes, 2.0 * rows * d_in * d_held
