"""What K-EXAONE's decode step and its kernels must move and compute, from
shapes alone. Every count is a floor (a weight is read once, at the 18/32
bytes the file holds it in; a window layer's live keys and values are the
window's, whatever the context), so dividing it by measured time and the
chip's peak gives a share that a correct count cannot push past 100 %."""

from __future__ import annotations

import re

Q40_BYTES_PER_WEIGHT = 18 / 32  # a 32-value block: f16 scale + 16 nibble bytes

CONFIG_KEYS = frozenset({
    "model_type", "first_k_dense_replace", "head_dim", "hidden_act", "hidden_size",
    "intermediate_size", "layer_types", "mlp_layer_types", "moe_intermediate_size",
    "mtp_layer_types", "mtp_sliding_windows", "n_group", "norm_topk_prob", "num_attention_heads",
    "num_experts", "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "num_nextn_predict_layers", "num_shared_experts", "rms_norm_eps", "rope_parameters",
    "routed_scaling_factor", "scoring_func", "sliding_window", "sliding_window_pattern",
    "sliding_windows", "tie_word_embeddings", "topk_group", "vocab_size", "first_routed_expert"})


def _sizes(c: dict) -> dict:
    depth, period = c["num_hidden_layers"], len(c["sliding_window_pattern"])
    n_full = len(range(period - 1, depth, period))
    n_dense = min(c["first_k_dense_replace"], depth)
    return {
        "h": c["hidden_size"], "q": c["num_attention_heads"] * c["head_dim"],
        "kv": c["num_key_value_heads"] * c["head_dim"], "hd": c["head_dim"],
        "dense": c["intermediate_size"], "width": c["moe_intermediate_size"],
        "shared": c["num_shared_experts"] * c["moe_intermediate_size"],
        "routed": c.get("reduced_from", {}).get("num_experts", c["num_experts"]),
        "held": c["num_experts"], "top_k": c["num_experts_per_tok"], "window": c["sliding_window"],
        "n_full": n_full, "n_window": depth - n_full, "n_dense": n_dense, "n_sparse": depth - n_dense,
    }


def experts_touched(held: int, routed: int, top_k: int, rows: float) -> float:
    """Expected number of the ``held`` experts that ``rows`` tokens choosing
    ``top_k`` of ``routed`` at random touch in one layer."""
    return held * (1.0 - (1.0 - top_k / routed) ** rows)


def weight_bytes_per_step(c: dict, rows: float) -> float:
    """Q40 bytes of the layers' matrices and the output head read in one
    decode step of ``rows`` sequences (of the held experts those that
    ``rows`` tokens touch in expectation), plus the f32 tensors (norms, the
    selection bias) and one f32 embedding row per sequence."""
    s = _sizes(c)
    h, depth = s["h"], c["num_hidden_layers"]
    attention = h * (s["q"] + 2 * s["kv"]) + s["q"] * h
    expert = 3 * h * s["width"]
    sparse = h * s["routed"] + 3 * h * s["shared"] + expert * experts_touched(
        s["held"], s["routed"], s["top_k"], rows)
    q40 = (depth * attention + s["n_dense"] * 3 * h * s["dense"] + s["n_sparse"] * sparse
           + h * c["vocab_size"])
    f32 = (2 * depth + 1) * h + depth * 2 * s["hd"] + s["n_sparse"] * s["routed"] + rows * h
    return q40 * Q40_BYTES_PER_WEIGHT + 4 * f32


def kv_bytes_per_position(c: dict, kind: str, kv_bytes: int = 2) -> int:
    """Keys and values of one position across the layers of ``kind``
    (``full`` or ``window``), bf16."""
    s = _sizes(c)
    return 2 * s[f"n_{kind}"] * s["kv"] * kv_bytes


def decode_step_bytes(c: dict, rows: float, live_positions: float) -> float:
    """``live_positions`` is the sum over the step's sequences of their
    context lengths: a full layer reads them all, a window layer at most the
    window's of each row (``min(position, window)``, which is the window once
    a row is past it: the sum's share beyond ``rows * window`` is not read)."""
    s = _sizes(c)
    return (weight_bytes_per_step(c, rows) + live_positions * kv_bytes_per_position(c, "full")
            + min(live_positions, rows * s["window"]) * kv_bytes_per_position(c, "window"))


def _matrices(c: dict, role: str, d_out: int) -> list[tuple[int, int, int]]:
    """The Q40 matrices a launch of ``role`` with ``d_out`` output columns may
    be multiplying by, as (d_in, columns that hold weights, how many layers
    launch it in one step); a kernel pads its columns to its tile, the padding
    holds no weight. The dense layer's down and the shared expert's both give
    ``hidden_size`` columns under one name: both are returned, and the caller
    takes their mean by launches."""
    s = _sizes(c)
    h, depth = s["h"], c["num_hidden_layers"]
    known = {
        "wqkv": [(h, s["q"] + 2 * s["kv"], depth)],
        "wo": [(s["q"], h, depth)],
        "gate_up": [(h, 2 * s["dense"], s["n_dense"]), (h, 2 * s["shared"], s["n_sparse"])],
        "down": [(s["dense"], h, s["n_dense"]), (s["shared"], h, s["n_sparse"])],
        "held_experts": [(h, 2 * s["width"], s["n_sparse"]), (s["width"], h, s["n_sparse"])],
        "logits": [(h, c["vocab_size"], 1)],
    }
    fits = [m for m in known.get(role, []) if m[1] <= d_out < m[1] + 4096 and m[2]]
    if not fits:
        raise ValueError(f"no Q40 matrix of role {role!r} has {d_out} output columns in "
                         f"configuration {c.get('name')!r}")
    widest = max(m[1] for m in fits)  # an expert's gate|up fits its down's columns too: the down's
    return [m for m in fits if m[1] == widest]


def kernel_launch(c: dict, role: str, shape: list[int]) -> tuple[float, float]:
    """(bytes, operations) of ONE launch of the kernel that carries ``role``
    in its name and whose first result is ``shape``.

    A Q40 matmul (``shape`` = [rows, d_out]): the matrix once at its file
    size, the activations in at one byte a value, the result out as f32; a
    multiply and an add for every weight and row. Where two matrices of a
    step share role and columns (``down``: one dense layer's 18432 rows in,
    seven shared experts' 2048), a launch counts as their mean by launches:
    every step launches each once a layer, so over a trace the sum is exact.

    ``held_experts_t<tokens>`` is ONE grouped launch over the bank of held
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
