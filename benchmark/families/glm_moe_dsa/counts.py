"""What GLM-5's decode step and its kernels must move and compute, from shapes
alone. Every count is a floor (a weight is read once, at the 18/32 bytes the
file holds it in; of a cached position a layer reads the index key, ``index_head_dim``
values, and, of the ``index_topk`` positions its indexer selects, ONE latent row,
``kv_lora_rank + qk_rope_head_dim`` values, whatever the number of heads), so
dividing it by measured time and the chip's peak gives a share that a correct
count cannot push past 100 %. The served selected attention is a MASKED pass
that reads every visible latent row (PERF.md section 6, PR 53): it reads more
than this floor, which is what the share is there to show."""

from __future__ import annotations

import re

Q40_BYTES_PER_WEIGHT = 18 / 32  # a 32-value block: f16 scale + 16 nibble bytes

# every key of a configuration's file these functions and the family's builder read, and the
# published keys they knowingly leave alone (the norm's epsilon is the program's own constant;
# the multi-token-prediction layer is not written; ep_size is a launcher's setting; the two
# interleave flags say how the published checkpoint pairs rotated values, which a file of
# seeded rows relabels: configuration file, ``assumed``)
CONFIG_KEYS = frozenset({
    "model_type", "attention_bias", "ep_size", "first_k_dense_replace", "head_dim", "hidden_act",
    "hidden_size", "index_head_dim", "index_n_heads", "index_topk", "indexer_rope_interleave",
    "intermediate_size", "kv_lora_rank", "moe_intermediate_size", "moe_layer_freq", "n_group",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads", "num_nextn_predict_layers",
    "q_lora_rank", "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps",
    "rope_interleave", "rope_parameters", "routed_scaling_factor", "scoring_func",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim", "vocab_size",
    "first_routed_expert"})


def _sizes(c: dict) -> dict:
    depth, heads = c["num_hidden_layers"], c["num_attention_heads"]
    n_dense = min(c["first_k_dense_replace"], depth)
    return {
        "h": c["hidden_size"], "q_rank": c["q_lora_rank"], "kv_rank": c["kv_lora_rank"],
        "rope": c["qk_rope_head_dim"], "latent": c["kv_lora_rank"] + c["qk_rope_head_dim"],
        "q": heads * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]),
        "kv_up": heads * (c["qk_nope_head_dim"] + c["v_head_dim"]), "o": heads * c["v_head_dim"],
        "index_q": c["index_n_heads"] * c["index_head_dim"], "index_k": c["index_head_dim"],
        "index_w": c["index_n_heads"], "top": c["index_topk"],
        "dense": c["intermediate_size"], "width": c["moe_intermediate_size"],
        "shared": c["n_shared_experts"] * c["moe_intermediate_size"],
        "routed": c.get("reduced_from", {}).get("n_routed_experts", c["n_routed_experts"]),
        "held": c["n_routed_experts"], "top_k": c["num_experts_per_tok"],
        "n_dense": n_dense, "n_sparse": depth - n_dense,
    }


def layer_weights(c: dict) -> dict:
    """Weights of one layer's parts (the matrices; norms and biases are
    thousands): ``attention`` (the five matrices of the latent attention),
    ``indexer`` (its three), ``shared`` and ``router`` of an expert layer,
    ``expert`` (ONE routed expert), ``dense`` (a leading layer's SwiGLU)."""
    s = _sizes(c)
    h = s["h"]
    return {
        "attention": (h * s["q_rank"] + s["q_rank"] * s["q"] + h * s["latent"]
                      + s["kv_rank"] * s["kv_up"] + s["o"] * h),
        "indexer": s["q_rank"] * s["index_q"] + h * s["index_k"] + h * s["index_w"],
        "shared": 3 * h * s["shared"], "router": h * s["routed"],
        "expert": 3 * h * s["width"], "dense": 3 * h * s["dense"],
    }


def file_bytes(c: dict) -> float:
    """Bytes of the configuration's Q40 ``.m``: the matrices at 18/32 B a
    weight and the float32 embedding (norms, biases and the header are
    kilobytes and are left out)."""
    s, w = _sizes(c), layer_weights(c)
    depth = c["num_hidden_layers"]
    q40 = (depth * (w["attention"] + w["indexer"]) + s["n_dense"] * w["dense"]
           + s["n_sparse"] * (w["shared"] + w["router"] + s["held"] * w["expert"])
           + s["h"] * c["vocab_size"])
    return q40 * Q40_BYTES_PER_WEIGHT + 4 * s["h"] * c["vocab_size"]


def experts_touched(held: int, routed: int, top_k: int, rows: float) -> float:
    """Expected number of the ``held`` experts that ``rows`` tokens choosing
    ``top_k`` of ``routed`` at random touch in one layer (3.6 of 16 at 8 rows
    of top 8 of 256)."""
    return held * (1.0 - (1.0 - top_k / routed) ** rows)


def weight_bytes_per_step(c: dict, rows: float) -> float:
    """Q40 bytes of the layers' matrices and the output head read in one
    decode step of ``rows`` sequences (of the experts those that ``rows``
    tokens touch in expectation; the keys' and values' up-projection at its
    file size, though the program keeps it dequantised: a floor), plus the f32
    tensors (norms, the selection bias, the index key's LayerNorm) and one f32
    embedding row per sequence."""
    s, w = _sizes(c), layer_weights(c)
    h, depth = s["h"], c["num_hidden_layers"]
    sparse = w["router"] + w["shared"] + w["expert"] * experts_touched(
        s["held"], s["routed"], s["top_k"], rows)
    q40 = (depth * (w["attention"] + w["indexer"]) + s["n_dense"] * w["dense"]
           + s["n_sparse"] * sparse + h * c["vocab_size"])
    f32 = ((2 * depth + 1) * h + depth * (s["q_rank"] + s["kv_rank"] + 2 * s["index_k"])
           + s["n_sparse"] * s["routed"] + rows * h)
    return q40 * Q40_BYTES_PER_WEIGHT + 4 * f32


def index_bytes_per_position(c: dict, kv_bytes: int = 2) -> int:
    """The index keys of one position across the layers, bf16: 5 x 128 x 2 at
    the depth served."""
    return c["num_hidden_layers"] * c["index_head_dim"] * kv_bytes


def latent_bytes_per_position(c: dict, kv_bytes: int = 2) -> int:
    """The latent rows of one position across the layers, bf16: 5 x 576 x 2 at
    the depth served."""
    return c["num_hidden_layers"] * _sizes(c)["latent"] * kv_bytes


def decode_step_bytes(c: dict, rows: float, live_positions: float) -> float:
    """``live_positions`` is the sum over the step's sequences of their
    context lengths. Every layer's indexer reads the index key of every one
    of them; its attention must read the latent rows of the positions it
    selects, at most ``index_topk`` a sequence (every position of a sequence
    no longer than that)."""
    rows = max(rows, 1.0)
    selected = min(live_positions, rows * c["index_topk"])
    return (weight_bytes_per_step(c, rows) + live_positions * index_bytes_per_position(c)
            + selected * latent_bytes_per_position(c))


def _matrices(c: dict, role: str, d_out: int) -> list[tuple[int, int, int]]:
    """The Q40 matrices a launch of ``role`` with ``d_out`` output columns may
    be multiplying by, as (d_in, columns that hold weights, how many layers
    launch it in one step); a kernel pads its columns to its tile (the two
    down-projections with the index key's and the index weights' matrices
    behind them are 2784 columns in 3072), the padding holds no weight. The
    dense layer's down and the shared expert's both give ``hidden_size``
    columns under one name: both are returned, and the caller takes their mean
    by launches."""
    s = _sizes(c)
    h, depth = s["h"], c["num_hidden_layers"]
    known = {
        "wqkv": [(h, s["q_rank"] + s["latent"] + s["index_k"] + s["index_w"], depth)],
        "mla_project": [(s["q_rank"], s["q"] + s["index_q"], depth)],
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
    in its name and whose first result is ``shape``: the sibling family's
    rule (``glm4_moe_lite/counts.py`` says it at length). A Q40 matmul
    (``shape`` = [rows, d_out]): the matrix once at its file size, the
    activations in at one byte a value, the result out as f32; two matrices
    that share role and columns count as their mean by launches.
    ``held_experts_t<tokens>`` is ONE grouped launch over the bank of held
    experts (``shape`` = [experts, rows, d_out]): bytes and operations of the
    experts that ``tokens`` tokens choosing at random touch IN EXPECTATION,
    each over its ``rows``."""
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
