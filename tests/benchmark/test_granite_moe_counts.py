"""Granite-4.0-H-Small's byte and operation counts against counts made by
hand: ISSUE 50's arithmetic (an expert 9.437 M weights, a state-space layer
121.4 M outside its routed experts, an attention layer 61.1 M, 72 experts
679.5 M a layer, the whole model 32.2 B, this chip's file 2.07 GB), the decode
step's floor at 32 rows (4.2 GB: the state in and out 2.4, the held experts
that 32 tokens touch 0.95), and every role the configuration launches, an
expert's 768-wide contraction UNPADDED."""

import dataclasses
import json
import os

import pytest

from benchmark import families

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmark", "configs", "granite-4.0-h-small-q40-10l-ep4.json")) as f:
    CONFIG = json.load(f)
counts = families.counts(CONFIG)
Q40 = 18 / 32
H, INNER, N, HEADS, CONV, WIDTH, SHARED, VOCAB = 4096, 8192, 128, 128, 8448, 768, 1536, 25088
STATE = INNER * N  # 128 heads x 64 values x 128 state values
EXPERT = 3 * H * WIDTH


def test_the_cut_is_the_stated_one():
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert CONFIG["reduced_from"] == {"num_hidden_layers": 40, "num_local_experts": 72, "vocab_size": 100352}
    assert (CONFIG["num_hidden_layers"], CONFIG["num_local_experts"], CONFIG["vocab_size"]) == (10, 18, VOCAB)
    # a whole period, 10 >= 4 layers, 18 >= 8 experts, a quarter >= an eighth of the vocabulary
    assert CONFIG["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert [l for l, kind in enumerate(CONFIG["layer_types"]) if kind == "attention"] == [5, 15, 25, 35]
    # no width is cut: the router keeps its 72 outputs and 10 a token
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"], CONFIG["shared_intermediate_size"],
            CONFIG["num_experts_per_tok"], CONFIG["mamba_n_heads"], CONFIG["mamba_d_head"],
            CONFIG["mamba_d_state"]) == (H, WIDTH, SHARED, 10, HEADS, 64, N)
    assert CONFIG["attention_multiplier"] == 1 / 128 and CONFIG["first_routed_expert"] == 0
    for key in ("first_routed_expert", "weights", "tied_head", "state", "intermediate_size", "input_linear_order",
                "router", "shared_expert", "in_proj_order", "gated_norm", "dt", "attention", "multipliers",
                "context_served"):
        assert key in CONFIG["assumed"], key
    assert "FOUR-CHIP".lower() in CONFIG["deployment"].lower() and "4.4 rows" in CONFIG["deployment"]


def test_the_models_weights_by_hand():
    assert EXPERT == 9437184
    outside = counts.layer_weights(CONFIG, "mamba", 0)
    assert outside == (H * 16768 + INNER * H + 3 * H * SHARED + H * 72
                       + CONV * 5 + 3 * HEADS + INNER + 2 * H)
    assert (H * 16768, INNER * H, 3 * H * SHARED, H * 72) == (68681728, 33554432, 18874368, 294912)
    assert outside == pytest.approx(121.4e6, rel=1e-3)
    attention = counts.layer_weights(CONFIG, "attention", 0)
    assert attention == H * 6144 + H * H + 3 * H * SHARED + H * 72 + 2 * H
    assert attention == pytest.approx(61.1e6, rel=1e-3)
    assert 72 * EXPERT == pytest.approx(679.5e6, rel=1e-3)
    # the WHOLE model: 40 layers, all 72 experts, the whole vocabulary, the embedding once
    whole = 36 * (outside + 72 * EXPERT) + 4 * (attention + 72 * EXPERT) + H + H * 100352
    assert counts.model_weights(CONFIG) == whole and whole == pytest.approx(32.2e9, rel=2e-3)
    # 18.1 GB of Q40 + 1.64 GB of f32 embedding: over one chip, over two beside 32 rows of state
    assert (whole - H * 100352) * Q40 == pytest.approx(17.9e9, rel=1e-2) and 4 * H * 100352 == pytest.approx(1.64e9, rel=1e-2)


def test_this_chips_file_is_two_gigabytes(tmp_path):
    """2853 M layer weights + 103 M head at Q40, 0.41 GB of f32 embedding:
    2.07 GB, by the counts and by the program's own layout of the header the
    family's builder writes."""
    from distributed_llama_tpu.formats.model_file import _header_pairs, tensor_layout

    q40, f32 = counts.file_weights(CONFIG)
    assert q40 - H * VOCAB == pytest.approx(2853e6, rel=1e-3) and H * VOCAB == pytest.approx(103e6, rel=3e-3)
    assert q40 * Q40 + 4 * f32 == pytest.approx(2.07e9, rel=3e-3)
    spec = families.load(CONFIG, "modelfile").model_spec(CONFIG, 2048)
    header = 8 + 8 * len(_header_pairs(spec))
    last = tensor_layout(dataclasses.replace(spec, header_size=header))[-1]
    assert last.offset + last.nbytes == header + q40 * Q40 + 4 * f32
    assert (spec.n_experts, spec.n_routed_experts, spec.n_shared_experts, spec.moe_hidden_dim) == (18, 72, 2, WIDTH)
    assert (spec.attn_scale_micro, spec.attn_scale_nano, spec.logits_divisor_micro) == (0, 7812500, 16000000)


def test_a_decode_step_by_hand():
    touched = counts.experts_touched(18, 72, 10, 32)
    assert touched == pytest.approx(18 * (1 - (62 / 72) ** 32)) and 17.8 < touched < 17.9  # 99 % of them
    tail = H * 72 + 3 * H * SHARED + touched * EXPERT
    ssm = H * 16768 + INNER * H + tail
    softmax = H * 6144 + H * H + tail
    q40 = (9 * ssm + softmax + H * VOCAB) * Q40
    got = counts.weight_bytes_per_step(CONFIG, rows=32)
    assert q40 < got < q40 * 1.005  # + the f32 vectors, norms and 32 embedding rows
    assert 10 * touched * EXPERT * Q40 == pytest.approx(0.95e9, rel=1e-2)  # the held experts
    # a row's state and tail: 9 layers x (128 x 64 x 128 + 3 x 8448) float32 = 38.7 MB
    assert counts.state_bytes_per_row(CONFIG) == 4 * 9 * (STATE + 3 * CONV) == 38661120
    # keys and values of a position: the ONE softmax layer, 8 heads of 128, bf16
    assert counts.kv_bytes_per_position(CONFIG) == 2 * 1024 * 2
    step = counts.decode_step_bytes(CONFIG, 32, 32 * 800)
    assert step == pytest.approx(got + 2 * 32 * 38661120 + 32 * 800 * 4096)
    # the state in and out 2.47 GB (58 %), the experts 22 %: four fifths of the step together
    assert 2 * 32 * 38661120 == pytest.approx(2.47e9, rel=1e-2)
    assert 0.78 < (2 * 32 * 38661120 + 10 * touched * EXPERT * Q40) / step < 0.83
    assert 4.2e9 < step < 4.3e9  # 5.2 ms at 819 GB/s


@pytest.mark.parametrize("role,shape,d_in,d_held", [
    ("lin_in", [32, 17408], H, 16768),  # in_proj: 16768 columns in 17 tiles of 1024
    ("wqkv", [32, 6144], H, 6144),
    ("gate_up", [32, 3072], H, 2 * SHARED),  # the shared expert's
    ("down", [32, 4096], SHARED, H),
    ("logits", [32, 25600], H, VOCAB),
    ("gate_up", [256, 3072], H, 2 * SHARED),
])
def test_a_q40_launch_by_hand(role, shape, d_in, d_held):
    rows = shape[0]
    nbytes, ops = counts.kernel_launch(CONFIG, role, shape)
    assert nbytes == d_in * d_held * Q40 + rows * d_in + 4 * rows * shape[1]
    assert ops == 2.0 * rows * d_in * d_held


def test_the_two_output_projections_share_a_role_and_count_as_their_mean_by_launches():
    nbytes, ops = counts.kernel_launch(CONFIG, "wo", [32, 4096])
    softmax = H * H * Q40 + 32 * H + 4 * 32 * H
    ssm = INNER * H * Q40 + 32 * INNER + 4 * 32 * H
    assert nbytes == pytest.approx((softmax + 9 * ssm) / 10)
    assert ops == pytest.approx(2.0 * 32 * H * (H + 9 * INNER) / 10)


@pytest.mark.parametrize("tokens,rows,touched", [(32, 32, 17.85), (256, 128, 18.0), (256, 256, 18.0)],
                         ids=["a decode step, every row", "a piece, the bucket", "a piece, every row"])
def test_the_held_experts_launches_by_hand(tokens, rows, touched):
    """The experts that ``tokens`` tokens choosing 10 of 72 touch in
    expectation among the 18 held, each over its ``rows``, at 768 wide
    UNPADDED: the down bank's launch moves a third more (its contraction is
    padded to the 1024 of an input tile), which is no work."""
    role = f"held_experts_t{tokens}"
    want = counts.experts_touched(18, 72, 10, tokens)
    assert want == pytest.approx(touched, abs=0.01)
    nbytes, ops = counts.kernel_launch(CONFIG, role, [18, rows, 2048])  # gate|up: 1536 columns in 2048
    rows_in = rows if rows == tokens else want * rows  # the every-row arm reads the same rows for all
    assert nbytes == pytest.approx(want * H * 2 * WIDTH * Q40 + rows_in * H + 4 * want * rows * 2048)
    assert ops == pytest.approx(2.0 * want * rows * H * 2 * WIDTH)
    nbytes, ops = counts.kernel_launch(CONFIG, role, [18, rows, 4096])  # down: 768 values in
    assert nbytes == pytest.approx(want * WIDTH * H * Q40 + want * rows * WIDTH + 4 * want * rows * H)
    assert ops == pytest.approx(2.0 * want * rows * WIDTH * H)
    # what the launch really reads of the bank is 1024 / 768 of the floor's weights
    assert want * 1024 * H * Q40 > 1.3 * want * WIDTH * H * Q40
    with pytest.raises(ValueError, match="held_experts_t<tokens>"):
        counts.kernel_launch(CONFIG, "held_experts", [18, rows, 4096])


def test_the_state_space_kernels_by_hand():
    nbytes, ops = counts.kernel_launch(CONFIG, "ssd_step", [32, 64, 128])
    assert nbytes == 2 * 4 * 32 * STATE + 32 * 4 * (2 * INNER + 2 * N + HEADS) and ops == 5.0 * 32 * STATE
    assert nbytes == pytest.approx(270.6e6, rel=1e-3)  # 330 us at 819 GB/s; 9 launches a step: 3.0 ms
    assert ops / 197e12 < nbytes / 819e9 / 30  # the operations never bind
    nbytes, ops = counts.kernel_launch(CONFIG, "ssd_chunk", [256, 8192])
    assert nbytes == 2 * 4 * STATE + 256 * 4 * (2 * INNER + 2 * N + HEADS) and ops == 5.0 * 256 * STATE
    for role, shape in (("ssd_step", [32, 8192]), ("ssd_chunk", [256, 128, 64]), ("ssd_step", [32, 32, 128])):
        with pytest.raises(ValueError, match="values a row"):
            counts.kernel_launch(CONFIG, role, shape)


def test_the_cell_is_over_the_drivers_floor_by_arithmetic():
    """What the cell holds on the chip, from the counts alone (the measured
    peak is PERF.md's): the file's weights as they are resident (0.625 B a Q40
    weight with float32 scales), 32 rows of state and keys and values, 24
    snapshot slots; over the 4 GB floor before the temporaries."""
    q40, f32 = counts.file_weights(CONFIG)
    weights = q40 * 20 / 32 + 4 * f32
    slab = 32 * counts.state_bytes_per_row(CONFIG) + 32 * 2048 * counts.kv_bytes_per_position(CONFIG)
    snapshots = (384 // 16) * counts.state_bytes_per_row(CONFIG)
    assert 2.2e9 < weights < 2.3e9 and 1.5e9 < slab < 1.52e9 and 0.92e9 < snapshots < 0.94e9
    assert weights + slab + snapshots > 4.6e9
