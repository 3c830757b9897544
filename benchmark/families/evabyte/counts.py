"""What EvaByte's decode step and its Q40 kernels must move and compute, from
shapes alone. Every count is a floor (a weight is read once, at the 18/32
bytes the file holds it in; of the output matrix only the next byte's head is
read), so dividing it by measured time and the chip's peak gives a share that
a correct count cannot push past 100 %."""

from __future__ import annotations

Q40_BYTES_PER_WEIGHT = 18 / 32  # a 32-value block: f16 scale + 16 nibble bytes

# every key of a configuration's file these functions and the family's builder read, and the
# published keys they knowingly leave alone (the norm's epsilon is the program's own constant;
# the initialisation and the training's precision say nothing of a served forward pass)
CONFIG_KEYS = frozenset({
    "attention_bias", "attention_class", "chunk_size", "fp32_ln", "fp32_logits", "fp32_skip_add",
    "hidden_act", "hidden_size", "init_cutoff_factor", "init_fn", "init_std", "intermediate_size",
    "lazy_init", "max_seq_length", "mixedp_attn", "model_type", "norm_add_unit_offset",
    "num_attention_heads", "num_chunks", "num_hidden_layers", "num_key_value_heads",
    "num_pred_heads", "rms_norm_eps", "rope_scaling", "rope_theta", "tie_word_embeddings",
    "vocab_size", "window_size"})


def _sizes(c: dict) -> dict:
    h, heads = c["hidden_size"], c["num_attention_heads"]
    hd = h // heads
    return {"h": h, "hd": hd, "q": heads * hd, "kv": c["num_key_value_heads"] * hd,
            "ffn": c["intermediate_size"], "depth": c["num_hidden_layers"]}


def weight_bytes_per_step(c: dict, rows: float) -> float:
    """Q40 bytes of the layers' matrices and of the next byte's head read in
    one decode step, plus the f32 tensors (norms, the summariser's two
    vectors a head) and one f32 embedding row per sequence."""
    s = _sizes(c)
    h = s["h"]
    layer = h * (s["q"] + 2 * s["kv"]) + s["q"] * h + 3 * h * s["ffn"]
    f32 = s["depth"] * (2 * h + 2 * s["kv"]) + h + rows * h
    return (s["depth"] * layer + h * c["vocab_size"]) * Q40_BYTES_PER_WEIGHT + 4 * f32


def kv_bytes_per_entry(c: dict, kv_bytes: int = 2) -> int:
    """A key and a value (exact, or a summary's) across all layers, bf16."""
    return 2 * c["num_hidden_layers"] * _sizes(c)["kv"] * kv_bytes


def entries_read(c: dict, position: float) -> float:
    """What a query at ``position`` reads in one layer: the keys of its
    aligned window up to itself and one summary per chunk of the windows
    before it."""
    window, chunk = c["window_size"], c["chunk_size"]
    before = int(position // window)
    return position - before * window + 1 + before * (window // chunk)


def decode_step_bytes(c: dict, rows: float, live_positions: float) -> float:
    """``live_positions`` is the sum over the step's sequences of their
    context lengths, and it does not say where in its window a sequence
    stands. What holds wherever it does: a sequence at position t reads at
    least ``t / chunk_size`` entries a layer (:func:`entries_read` is never
    less: a summary stands for ``chunk_size`` positions, an exact key for
    one), so the sum over sequences is at least ``live_positions /
    chunk_size``. The exact keys of the current windows beyond that are left
    out: this floor reads LOW by up to a window a sequence, and the share it
    gives (``decode_hbm_share``) with it."""
    return weight_bytes_per_step(c, rows) + live_positions / c["chunk_size"] * kv_bytes_per_entry(c)


def _matrix(c: dict, role: str, d_out: int) -> tuple[int, int]:
    """(d_in, output columns that hold weights) of the Q40 matrix a launch of
    ``role`` with ``d_out`` output columns multiplies by. A kernel pads its
    columns to its tile (11008 is no multiple of 1024): the padding holds no
    weight."""
    s = _sizes(c)
    known = {"wqkv": (s["h"], s["q"] + 2 * s["kv"]), "wo": (s["q"], s["h"]),
             "gate_up": (s["h"], 2 * s["ffn"]), "down": (s["ffn"], s["h"]),
             "logits": (s["h"], c["vocab_size"])}
    if role in known and known[role][1] <= d_out < known[role][1] + 1024:
        return known[role]
    raise ValueError(f"no Q40 matrix of role {role!r} has {d_out} output columns in configuration "
                     f"{c.get('name')!r}")


def kernel_launch(c: dict, role: str, shape: list[int]) -> tuple[float, float]:
    """(bytes, operations) of ONE launch of the Q40 matmul kernel that carries
    ``role`` in its name and whose result is ``shape`` = [rows, d_out]: the
    matrix once at its file size, the activations in at one byte a value, the
    result out as f32; a multiply and an add for every weight and row."""
    rows, d_out = shape
    d_in, d_held = _matrix(c, role, d_out)
    nbytes = d_in * d_held * Q40_BYTES_PER_WEIGHT + rows * d_in + 4 * rows * d_out
    return nbytes, 2.0 * rows * d_in * d_held
