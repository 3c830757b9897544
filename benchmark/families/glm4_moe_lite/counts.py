"""What GLM-4.7-Flash's decode step and its kernels must move and compute,
from shapes alone. Every count is a floor (a weight is read once, at the 18/32
bytes the file holds it in; a cached position is ONE latent row a layer,
``kv_lora_rank + qk_rope_head_dim`` values, whatever the number of heads), so
dividing it by measured time and the chip's peak gives a share that a correct
count cannot push past 100 %."""

from __future__ import annotations

import re

Q40_BYTES_PER_WEIGHT = 18 / 32  # a 32-value block: f16 scale + 16 nibble bytes

# every key of a configuration's file these functions and the family's builder read, and the
# published keys they knowingly leave alone (the norm's epsilon is the program's own constant;
# the multi-token-prediction layer is not written)
CONFIG_KEYS = frozenset({
    "model_type", "attention_bias", "first_k_dense_replace", "hidden_act", "hidden_size",
    "intermediate_size", "kv_lora_rank", "moe_intermediate_size", "n_group", "n_routed_experts",
    "n_shared_experts", "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "num_nextn_predict_layers", "partial_rotary_factor",
    "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_scaling",
    "rope_theta", "routed_scaling_factor", "tie_word_embeddings", "topk_group", "topk_method",
    "v_head_dim", "vocab_size", "first_routed_expert"})


def _sizes(c: dict) -> dict:
    depth, heads = c["num_hidden_layers"], c["num_attention_heads"]
    n_dense = min(c["first_k_dense_replace"], depth)
    return {
        "h": c["hidden_size"], "q_rank": c["q_lora_rank"], "kv_rank": c["kv_lora_rank"],
        "rope": c["qk_rope_head_dim"], "latent": c["kv_lora_rank"] + c["qk_rope_head_dim"],
        "q": heads * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]),
        "kv_up": heads * (c["qk_nope_head_dim"] + c["v_head_dim"]), "o": heads * c["v_head_dim"],
        "dense": c["intermediate_size"], "width": c["moe_intermediate_size"],
        "shared": c["n_shared_experts"] * c["moe_intermediate_size"],
        "routed": c.get("reduced_from", {}).get("n_routed_experts", c["n_routed_experts"]),
        "held": c["n_routed_experts"], "top_k": c["num_experts_per_tok"],
        "n_dense": n_dense, "n_sparse": depth - n_dense,
    }


def experts_touched(held: int, routed: int, top_k: int, rows: float) -> float:
    """Expected number of the ``held`` experts that ``rows`` tokens choosing
    ``top_k`` of ``routed`` at random touch in one layer (25.8 of 64 at 8 rows
    of top 4)."""
    return held * (1.0 - (1.0 - top_k / routed) ** rows)


def weight_bytes_per_step(c: dict, rows: float) -> float:
    """Q40 bytes of the layers' matrices and the output head read in one
    decode step of ``rows`` sequences (of the experts those that ``rows``
    tokens touch in expectation; the keys' and values' up-projection at its
    file size, though the program keeps it dequantised: a floor), plus the f32
    tensors (norms, the selection bias) and one f32 embedding row per
    sequence."""
    s = _sizes(c)
    h, depth = s["h"], c["num_hidden_layers"]
    attention = (h * (s["q_rank"] + s["latent"]) + s["q_rank"] * s["q"] + s["kv_rank"] * s["kv_up"]
                 + s["o"] * h)
    expert = 3 * h * s["width"]
    sparse = h * s["routed"] + 3 * h * s["shared"] + expert * experts_touched(
        s["held"], s["routed"], s["top_k"], rows)
    q40 = (depth * attention + s["n_dense"] * 3 * h * s["dense"] + s["n_sparse"] * sparse
           + h * c["vocab_size"])
    f32 = ((2 * depth + 1) * h + depth * (s["q_rank"] + s["kv_rank"]) + s["n_sparse"] * s["routed"]
           + rows * h)
    return q40 * Q40_BYTES_PER_WEIGHT + 4 * f32


def latent_bytes_per_position(c: dict, kv_bytes: int = 2) -> int:
    """The latent rows of one position across the layers, bf16: 9 x 576 x 2 at
    the depth served. The model's own expanded keys and values would be
    ``heads * (nope + rope + v)`` values a layer, 15.6 times as many."""
    return c["num_hidden_layers"] * _sizes(c)["latent"] * kv_bytes


def decode_step_bytes(c: dict, rows: float, live_positions: float) -> float:
    """``live_positions`` is the sum over the step's sequences of their
    context lengths: every layer reads every one of them, one latent row
    each."""
    return weight_bytes_per_step(c, rows) + live_positions * latent_bytes_per_position(c)


def _matrices(c: dict, role: str, d_out: int) -> list[tuple[int, int, int]]:
    """The Q40 matrices a launch of ``role`` with ``d_out`` output columns may
    be multiplying by, as (d_in, columns that hold weights, how many layers
    launch it in one step); a kernel pads its columns to its tile (the two
    down-projections side by side are 1344 columns in 2048), the padding holds
    no weight. The dense layer's down and the shared expert's both give
    ``hidden_size`` columns under one name: both are returned, and the caller
    takes their mean by launches."""
    s = _sizes(c)
    h, depth = s["h"], c["num_hidden_layers"]
    known = {
        "wqkv": [(h, s["q_rank"] + s["latent"], depth)],
        "mla_project": [(s["q_rank"], s["q"], depth)],
        "wo": [(s["o"], h, depth)],
        "gate_up": [(h, 2 * s["dense"], s["n_dense"]), (h, 2 * s["shared"], s["n_sparse"])],
        "down": [(s["dense"], h, s["n_dense"]), (s["shared"], h, s["n_sparse"])],
        "held_experts": [(h, 2 * s["width"], s["n_sparse"]), (s["width"], h, s["n_sparse"])],
        "logits": [(h, c["vocab_size"], 1)],
    }
    fits = [m for m in known.get(role, []) if m[1] <= d_out < m[1] + 1024 and m[2]]
    if not fits:
        raise ValueError(f"no Q40 matrix of role {role!r} has {d_out} output columns in "
                         f"configuration {c.get('name')!r}")
    return fits


def kernel_launch(c: dict, role: str, shape: list[int]) -> tuple[float, float]:
    """(bytes, operations) of ONE launch of the kernel that carries ``role``
    in its name and whose first result is ``shape``.

    A Q40 matmul (``shape`` = [rows, d_out]): the matrix once at its file
    size, the activations in at one byte a value, the result out as f32; a
    multiply and an add for every weight and row. Where two matrices of a
    step share role and columns (``down``: one dense layer's 10240 rows in,
    the shared experts' 1536), a launch counts as their mean by launches:
    every step launches each once a layer, so over a trace the sum is exact.

    ``held_experts_t<tokens>`` is ONE grouped launch over the bank of routed
    experts (``shape`` = [experts, rows, d_out], the output width says which
    of an expert's matrices). ``tokens`` is the number of rows of the step
    that routed; ``rows`` is what each expert multiplies: its bucket, or
    every token where the step took the every-row path. An expert no token
    chose is neither read nor computed, and which were chosen is not in the
    launch's name, so bytes and operations are those of the experts that
    ``tokens`` tokens choosing at random touch IN EXPECTATION, each over its
    ``rows``."""
    if role.startswith("held_experts"):
        found = re.fullmatch(r"held_experts_t(\d+)", role)
        if found is None or len(shape) != 3:
            raise ValueError(f"a grouped launch is named held_experts_t<tokens> and gives "
                             f"[experts, rows, columns], not {role!r} {shape}")
        s = _sizes(c)
        tokens, (experts, rows, d_out) = int(found.group(1)), shape
        (d_in, d_held, _), = _matrices(c, "held_experts", d_out)
        touched = experts_touched(experts, s["routed"], s["top_k"], tokens)
        # gate|up of the every-row path reads the same rows for every expert
        rows_in = rows if d_in == s["h"] and rows == tokens else touched * rows
        nbytes = touched * d_in * d_held * Q40_BYTES_PER_WEIGHT + rows_in * d_in + 4 * touched * rows * d_out
        return nbytes, 2.0 * touched * rows * d_in * d_held
    rows, d_out = shape
    found = _matrices(c, role, d_out)
    launches = sum(n for _, _, n in found)
    nbytes = sum(n * (d_in * d_held * Q40_BYTES_PER_WEIGHT + rows * d_in + 4 * rows * d_out)
                 for d_in, d_held, n in found) / launches
    return nbytes, sum(n * 2.0 * rows * d_in * d_held for d_in, d_held, n in found) / launches
