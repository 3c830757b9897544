"""What Solar-Open2's decode step and its kernels must move and compute, from
shapes alone. Every count is a floor (a weight is read once, at the 18/32
bytes the file holds it in; a row's recurrent state is read once and written
once), so dividing it by measured time and the chip's peak gives a share
that a correct count cannot push past 100 %."""

from __future__ import annotations

import re

Q40_BYTES_PER_WEIGHT = 18 / 32  # a 32-value block: f16 scale + 16 nibble bytes

CONFIG_KEYS = frozenset({
    "model_type", "partial_rotary_factor", "linear_attn_config", "hidden_size",
    "num_hidden_layers", "num_attention_heads", "head_dim", "num_key_value_heads", "vocab_size",
    "intermediate_size", "moe_intermediate_size", "rms_norm_eps", "rope_theta",
    "tie_word_embeddings", "first_k_dense_replace", "use_rope", "gqa_interval", "gqa_layers",
    "use_gqa_gate", "kda_use_full_proj", "kda_allow_neg_eigval", "n_routed_experts",
    "n_shared_experts", "norm_topk_prob", "routed_scaling_factor", "num_experts_per_tok",
    "first_routed_expert", "kda_low_rank_dim"})


def _sizes(c: dict) -> dict:
    lin = c["linear_attn_config"]
    depth, period = c["num_hidden_layers"], c["gqa_interval"] + 1
    n_softmax = len(range(0, depth, period))
    return {
        "h": c["hidden_size"], "q": c["num_attention_heads"] * c["head_dim"],
        "kv": c["num_key_value_heads"] * c["head_dim"], "lin": lin["num_heads"] * lin["head_dim"],
        "lin_heads": lin["num_heads"], "dl": lin["head_dim"], "taps": lin["short_conv_kernel_size"],
        "rank": c["kda_low_rank_dim"], "width": c["moe_intermediate_size"],
        "routed": c.get("reduced_from", {}).get("n_routed_experts", c["n_routed_experts"]),
        "held": c["n_routed_experts"], "top_k": c["num_experts_per_tok"],
        "n_softmax": n_softmax, "n_linear": depth - n_softmax,
    }


def experts_touched(held: int, routed: int, top_k: int, rows: float) -> float:
    """Expected number of the ``held`` experts that ``rows`` tokens choosing
    ``top_k`` of ``routed`` at random touch in one layer."""
    return held * (1.0 - (1.0 - top_k / routed) ** rows)


def weight_bytes_per_step(c: dict, rows: float) -> float:
    """Q40 bytes of the layers' matrices and the output head read in one
    decode step of ``rows`` sequences, plus the f32 tensors (norms, conv
    taps, biases) and one f32 embedding row per sequence."""
    s = _sizes(c)
    h = s["h"]
    softmax = h * (2 * s["q"] + 2 * s["kv"]) + s["q"] * h
    linear = h * (3 * s["lin"] + 2 * s["rank"] + s["lin_heads"]) + 2 * s["rank"] * s["lin"] + s["lin"] * h
    expert = 3 * h * s["width"]
    moe = h * s["routed"] + expert * (c["n_shared_experts"]
                                     + experts_touched(s["held"], s["routed"], s["top_k"], rows))
    q40 = (s["n_softmax"] * softmax + s["n_linear"] * linear
           + c["num_hidden_layers"] * moe + h * c["vocab_size"])
    f32 = ((2 * c["num_hidden_layers"] + 1) * h + c["num_hidden_layers"] * s["routed"]
           + s["n_linear"] * (3 * s["lin"] * s["taps"] + s["lin"] + s["lin_heads"] + s["dl"]) + rows * h)
    return q40 * Q40_BYTES_PER_WEIGHT + 4 * f32


def state_bytes_per_row(c: dict) -> int:
    """Recurrent state and convolution tail of one row across the linear
    layers, float32."""
    s = _sizes(c)
    return 4 * s["n_linear"] * (s["lin_heads"] * s["dl"] * s["dl"] + (s["taps"] - 1) * 3 * s["lin"])


def kv_bytes_per_position(c: dict, kv_bytes: int = 2) -> int:
    """Keys and values of one position across the SOFTMAX layers (bf16)."""
    s = _sizes(c)
    return 2 * s["n_softmax"] * s["kv"] * kv_bytes


def decode_step_bytes(c: dict, rows: float, live_positions: float) -> float:
    """``live_positions`` is the sum over the step's sequences of their
    context lengths. A row's state is read once and written once a step,
    whatever its length."""
    return (weight_bytes_per_step(c, rows) + 2 * rows * state_bytes_per_row(c)
            + live_positions * kv_bytes_per_position(c))


def _matrix(c: dict, role: str, d_out: int) -> tuple[int, int]:
    """(d_in, columns that hold weights) of the Q40 matrix a launch of
    ``role`` with ``d_out`` output columns multiplies by; a kernel pads its
    columns to its tile, the padding holds no weight."""
    s = _sizes(c)
    h = s["h"]
    known = {
        "wqkv": [(h, 2 * s["q"] + 2 * s["kv"])],
        "lin_in": [(h, 3 * s["lin"] + 2 * s["rank"] + s["lin_heads"])],
        "wo": [(s["q"], h), (s["lin"], h)],
        "gate_up": [(h, 2 * c["n_shared_experts"] * s["width"])],
        "down": [(c["n_shared_experts"] * s["width"], h)],
        "held_experts": [(h, 2 * s["width"]), (s["width"], h)],
        "logits": [(h, c["vocab_size"])],
    }
    # the widest matrix the launch's columns can hold: an expert's gate|up (2560 columns, padded
    # to 3072) and its down (4096) both fit a result of 4096 columns, and it is the down's
    fits = [(d_held, d_in) for d_in, d_held in known.get(role, []) if d_held <= d_out < d_held + 4096]
    if fits:
        d_held, d_in = max(fits)
        return d_in, d_held
    raise ValueError(f"no Q40 matrix of role {role!r} has {d_out} output columns in configuration "
                     f"{c.get('name')!r}")


def kernel_launch(c: dict, role: str, shape: list[int]) -> tuple[float, float]:
    """(bytes, operations) of ONE launch of the kernel that carries ``role``
    in its name and whose first result is ``shape``.

    A Q40 matmul (``shape`` = [rows, d_out]): the matrix once at its file
    size, the activations in at one byte a value, the result out as f32; a
    multiply and an add for every weight and row.

    ``held_experts_t<tokens>`` is ONE grouped launch over the bank of held
    experts (``shape`` = [experts, rows, d_out], the output width says which
    of an expert's matrices). ``tokens`` is the number of rows of the step
    that routed; ``rows`` is what each expert multiplies: its bucket (8 rows
    in a 32-row decode step), or every token where the step took the
    every-row path. An expert no token chose is neither read nor computed,
    and which were chosen is not in the launch's name, so bytes and
    operations are those of the experts that ``tokens`` tokens choosing at
    random touch IN EXPECTATION, each over its ``rows``.

    ``kda_step`` (``shape`` = [rows, heads, dl], the step's output): every
    row's state read once and written once, q, k, v, decay and beta in, the
    output out, float32; per head and row the decay (dl^2 multiplies), S'^T k
    and S^T q (2 dl^2 each) and the rank-one update (2 dl^2).

    ``kda_chunk`` (``shape`` = [heads, tokens, dl], one row's prefill chunk):
    the state in and out once, the tokens' q, k, v, decay, beta in and the
    output out; operations of the recurrence itself, 7 dl^2 a token and head
    (what a chunked form adds in small matmuls is not counted: a floor)."""
    if role.startswith("held_experts"):
        # a grouped launch over the bank: [experts, rows, d_out], its step's tokens in the role
        found = re.fullmatch(r"held_experts_t(\d+)", role)
        if found is None or len(shape) != 3:
            raise ValueError(f"a grouped launch is named held_experts_t<tokens> and gives "
                             f"[experts, rows, columns], not {role!r} {shape}")
        s = _sizes(c)
        tokens, (experts, rows, d_out) = int(found.group(1)), shape
        d_in, d_held = _matrix(c, "held_experts", d_out)
        touched = experts_touched(experts, s["routed"], s["top_k"], tokens)
        # gate|up of the every-row path reads the same rows for every expert
        rows_in = rows if d_in == s["h"] and rows == tokens else touched * rows
        nbytes = touched * d_in * d_held * Q40_BYTES_PER_WEIGHT + rows_in * d_in + 4 * touched * rows * d_out
        return nbytes, 2.0 * touched * rows * d_in * d_held
    if role in ("kda_step", "kda_chunk"):
        # the step's result is [rows, heads, dl], the chunk's [heads, tokens, dl]
        rows, heads, dl = shape if role == "kda_step" else (shape[1], shape[0], shape[2])
        per_token = 4 * heads * (4 * dl + 1 + dl)
        state = 4 * heads * dl * dl
        states = rows if role == "kda_step" else 1
        return 2.0 * states * state + rows * per_token, 7.0 * rows * heads * dl * dl
    rows, d_out = shape
    d_in, d_held = _matrix(c, role, d_out)
    nbytes = d_in * d_held * Q40_BYTES_PER_WEIGHT + rows * d_in + 4 * rows * d_out
    return nbytes, 2.0 * rows * d_in * d_held
