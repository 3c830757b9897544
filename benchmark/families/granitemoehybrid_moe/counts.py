"""What a decode step of Granite-4.0-H with experts and its kernels must move
and compute, from shapes alone. Every count is a floor (a weight is read once,
at the 18/32 bytes the file holds it in, an expert at its PUBLISHED width: what
a kernel pads a 768-wide contraction to is not work; of the held experts those
that the step's tokens touch in expectation; a stepping row's recurrent state
is read once and written once, float32; a cached position is its keys and
values in the softmax layers only), so dividing it by measured time and the
chip's peak gives a share that a correct count cannot push past 100 %."""

from __future__ import annotations

import math
import re

Q40_BYTES_PER_WEIGHT = 18 / 32  # a 32-value block: f16 scale + 16 nibble bytes

# every key of a configuration's file these functions and the family's builder read, and the
# published keys they knowingly leave alone (the norm's epsilon is the program's own constant;
# mamba_chunk_size is the publisher's kernel's sub-chunk, ours is ops/ssd.py's own; rope_theta
# and rope_scaling rotate nothing under position_embedding_type nope)
CONFIG_KEYS = frozenset({
    "model_type", "attention_bias", "attention_multiplier", "embedding_multiplier", "hidden_act",
    "hidden_size", "intermediate_size", "layer_types", "logits_scaling", "mamba_chunk_size",
    "mamba_conv_bias", "mamba_d_conv", "mamba_d_head", "mamba_d_state", "mamba_expand",
    "mamba_n_groups", "mamba_n_heads", "mamba_proj_bias", "normalization_function",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "num_local_experts", "position_embedding_type", "residual_multiplier", "rms_norm_eps",
    "rope_scaling", "rope_theta", "shared_intermediate_size", "tie_word_embeddings", "vocab_size",
    "first_routed_expert"})


def _sizes(c: dict) -> dict:
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    head = c["hidden_size"] // c["num_attention_heads"]
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    return {
        "h": c["hidden_size"], "q": c["num_attention_heads"] * head,
        "kv": c["num_key_value_heads"] * head, "inner": inner, "state": c["mamba_d_state"],
        "heads": c["mamba_n_heads"], "conv": inner + 2 * c["mamba_d_state"],
        "taps": c["mamba_d_conv"], "width": c["intermediate_size"],
        "shared": c["shared_intermediate_size"], "held": c["num_local_experts"],
        "routed": c.get("reduced_from", {}).get("num_local_experts", c["num_local_experts"]),
        "top_k": c["num_experts_per_tok"],
        "n_softmax": kinds.count("attention"), "n_ssm": kinds.count("mamba"),
    }


def experts_touched(held: int, routed: int, top_k: int, rows: float) -> float:
    """Expected number of the ``held`` experts that ``rows`` tokens choosing
    ``top_k`` of ``routed`` at random touch in one layer."""
    return held * (1.0 - (1.0 - top_k / routed) ** rows)


def _mixer_weights(s: dict, kind: str) -> int:
    """The Q40 matrices of one layer's mixer."""
    if kind == "attention":
        return s["h"] * (s["q"] + 2 * s["kv"]) + s["q"] * s["h"]
    return s["h"] * (s["inner"] + s["conv"] + s["heads"]) + s["inner"] * s["h"]


def layer_weights(c: dict, kind: str, experts: int | None = None) -> int:
    """Weights of one layer of ``kind`` (``mamba`` | ``attention``) that holds
    ``experts`` routed experts (None: those the file holds): its mixer's
    matrices and vectors, the router at its published width, the experts, the
    shared expert, the two norms."""
    s = _sizes(c)
    h = s["h"]
    experts = s["held"] if experts is None else experts
    tail = h * s["routed"] + experts * 3 * h * s["width"] + 3 * h * s["shared"] + 2 * h
    vectors = 0 if kind == "attention" else s["conv"] * (s["taps"] + 1) + 3 * s["heads"] + s["inner"]
    return _mixer_weights(s, kind) + vectors + tail


def model_weights(c: dict) -> int:
    """Weights of the WHOLE model as published, whatever the file serves
    (``reduced_from``: the depth, every routed expert, the whole vocabulary):
    the layers, the final norm, and the embedding ONCE (the head is the same
    matrix)."""
    whole = c.get("reduced_from", {})
    depth = whole.get("num_hidden_layers", c["num_hidden_layers"])
    vocab = whole.get("vocab_size", c["vocab_size"])
    routed = _sizes(c)["routed"]
    return (sum(layer_weights(c, kind, routed) for kind in c["layer_types"][:depth])
            + c["hidden_size"] + c["hidden_size"] * vocab)


def file_weights(c: dict) -> tuple[int, int]:
    """(Q40 weights, f32 values) of the file as served: the layers' matrices
    with the held experts and the head's Q40 copy of the embedding; the f32
    embedding, the norms and the recurrence's vectors."""
    s = _sizes(c)
    h = s["h"]
    tail = h * s["routed"] + s["held"] * 3 * h * s["width"] + 3 * h * s["shared"]
    q40 = (s["n_ssm"] * (_mixer_weights(s, "mamba") + tail)
           + s["n_softmax"] * (_mixer_weights(s, "attention") + tail) + h * c["vocab_size"])
    f32 = (h * c["vocab_size"] + (2 * c["num_hidden_layers"] + 1) * h
           + s["n_ssm"] * (s["conv"] * (s["taps"] + 1) + 3 * s["heads"] + s["inner"]))
    return q40, f32


def weight_bytes_per_step(c: dict, rows: float) -> float:
    """Q40 bytes of the layers' matrices (of the held experts those that
    ``rows`` tokens touch in expectation) and of the head's Q40 copy of the
    embedding read in one decode step of ``rows`` sequences, plus the f32
    tensors (norms, conv taps and bias, the recurrence's vectors) and one f32
    embedding row per sequence."""
    s = _sizes(c)
    h = s["h"]
    tail = (h * s["routed"] + 3 * h * s["shared"]
            + 3 * h * s["width"] * experts_touched(s["held"], s["routed"], s["top_k"], rows))
    q40 = (s["n_ssm"] * (_mixer_weights(s, "mamba") + tail)
           + s["n_softmax"] * (_mixer_weights(s, "attention") + tail) + h * c["vocab_size"])
    f32 = ((2 * c["num_hidden_layers"] + 1) * h + rows * h
           + s["n_ssm"] * (s["conv"] * (s["taps"] + 1) + 3 * s["heads"] + s["inner"]))
    return q40 * Q40_BYTES_PER_WEIGHT + 4 * f32


def state_bytes_per_row(c: dict) -> int:
    """Recurrent state and convolution tail of one row across the
    state-space layers, float32."""
    s = _sizes(c)
    return 4 * s["n_ssm"] * (s["inner"] * s["state"] + (s["taps"] - 1) * s["conv"])


def kv_bytes_per_position(c: dict, kv_bytes: int = 2) -> int:
    """Keys and values of one position across the SOFTMAX layers (bf16)."""
    s = _sizes(c)
    return 2 * s["n_softmax"] * s["kv"] * kv_bytes


def decode_step_bytes(c: dict, rows: float, live_positions: float) -> float:
    """``live_positions`` is the sum over the step's sequences of their
    context lengths. A row's state is read once and written once a step,
    whatever its length: at 32 rows that is over half of the step."""
    return (weight_bytes_per_step(c, rows) + 2 * rows * state_bytes_per_row(c)
            + live_positions * kv_bytes_per_position(c))


def _matrices(c: dict, role: str, d_out: int) -> list[tuple[int, int, int]]:
    """The Q40 matrices a launch of ``role`` with ``d_out`` output columns may
    be multiplying by, as (d_in, columns that hold weights, how many layers
    launch it in one step); a kernel pads its columns to its tile (the
    state-space layer's input projection is 16768 columns in 17408), the
    padding holds no weight. ``lin_in`` is that projection: the role the
    linear layers' fused input projection has in every trace. The softmax
    layers' output projection and the state-space layers' both give
    ``hidden_size`` columns under ``wo``: both are returned, and the caller
    takes their mean by launches. ``gate_up`` and ``down`` are the shared
    expert's; ``held_experts`` an expert's gate|up and its down, the down's
    contraction at the published width."""
    s = _sizes(c)
    h, depth = s["h"], c["num_hidden_layers"]
    known = {
        "wqkv": [(h, s["q"] + 2 * s["kv"], s["n_softmax"])],
        "lin_in": [(h, s["inner"] + s["conv"] + s["heads"], s["n_ssm"])],
        "wo": [(s["q"], h, s["n_softmax"]), (s["inner"], h, s["n_ssm"])],
        "gate_up": [(h, 2 * s["shared"], depth)],
        "down": [(s["shared"], h, depth)],
        "held_experts": [(h, 2 * s["width"], depth), (s["width"], h, depth)],
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
    step share role and columns (``wo``: the softmax layers' 4096 rows in, the
    state-space layers' 8192), a launch counts as their mean by launches:
    every step launches each once a layer, so over a trace the sum is exact.

    ``held_experts_t<tokens>`` is ONE grouped launch over the bank of held
    experts (``shape`` = [experts, rows, d_out], the output width says which
    of an expert's matrices). ``tokens`` is the number of rows of the step
    that routed; ``rows`` is what each expert multiplies: its bucket, or
    every token where the step took the every-row path. An expert no token
    chose is neither read nor computed, and which were chosen is not in the
    launch's name, so bytes and operations are those of the experts that
    ``tokens`` tokens choosing 10 of 72 at random touch IN EXPECTATION among
    the 18 held (17.8 at 32 tokens), each over its ``rows`` and at its
    published width, 768, UNPADDED (the kernel's input tile pads the down
    bank's contraction to 1024: a third more of its bytes are read, and are
    no work).

    ``ssd_step`` (``shape`` = [rows, groups, lanes], the step's output in the
    state's layout: groups x lanes = heads x P values a row): every stepping
    row's state (heads x P x N float32) read once and written once, its x, B,
    C and dt in and its output out; 5 operations a state element (the decay,
    the outer product's multiply and add, the product with C and its sum).

    ``ssd_chunk`` (``shape`` = [tokens, heads x P], one row's prefill piece):
    the state in and out ONCE, the tokens' x, B, C, dt in and the output out;
    the operations of the recurrence itself, 5 a token and state element
    (what the chunked form adds in matrix products is not counted: a floor)."""
    s = _sizes(c)
    if role in ("ssd_step", "ssd_chunk"):
        tokens = shape[0]
        if len(shape) != (3 if role == "ssd_step" else 2) or math.prod(shape[1:]) != s["inner"]:
            raise ValueError(f"{role} gives [rows, groups, lanes] / [tokens, heads x P] of "
                             f"{s['inner']} values a row, not {shape}")
        state = s["inner"] * s["state"]
        per_token = 4 * (2 * s["inner"] + 2 * s["state"] + s["heads"])
        states = tokens if role == "ssd_step" else 1
        return 2.0 * 4 * states * state + tokens * per_token, 5.0 * tokens * state
    if role.startswith("held_experts"):
        found = re.fullmatch(r"held_experts_t(\d+)", role)
        if found is None or len(shape) != 3:
            raise ValueError(f"a grouped launch is named held_experts_t<tokens> and gives "
                             f"[experts, rows, columns], not {role!r} {shape}")
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
