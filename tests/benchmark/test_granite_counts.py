"""Granite-4.0-H-Micro's byte and operation counts against counts made by
hand: the model's weights by layer kind (ISSUE 45's 76.2 M, 60.8 M, 205.5 M,
3.19 B for the whole model), the decode step's floor at the depth served (20
of 40 layers; a stepping row's state in AND out: over half of it at 32 rows,
two thirds at the whole depth), and every role the configuration launches."""

import json
import os

import pytest

from benchmark import families

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmark", "configs", "granite-4.0-h-micro-q40.json")) as f:
    CONFIG = json.load(f)
counts = families.counts(CONFIG)
Q40 = 18 / 32
H, INNER, N, HEADS, CONV, FFN, VOCAB = 2048, 4096, 128, 64, 4352, 8192, 100352
STATE = INNER * N  # 64 heads x 64 values x 128 state values


def test_the_models_weights_by_hand():
    # the leading two periods of the published pattern are served, for the run's clock
    assert CONFIG["num_hidden_layers"] == 20 and CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["reduced_from"] == {"num_hidden_layers": 40} and len(CONFIG["layer_types"]) == 40
    assert CONFIG["layer_types"].count("mamba") == 36
    assert [l for l, kind in enumerate(CONFIG["layer_types"]) if kind == "attention"] == [5, 15, 25, 35]
    mlp = 3 * H * FFN
    assert (H * 8512, INNER * H, mlp) == (17432576, 8388608, 50331648)  # in_proj, out_proj, the SwiGLU
    mamba = counts.layer_weights(CONFIG, "mamba")
    assert mamba == H * 8512 + INNER * H + mlp + CONV * 5 + 3 * HEADS + INNER + 2 * H
    assert mamba == pytest.approx(76.2e6, rel=1e-3)
    attention = counts.layer_weights(CONFIG, "attention")
    assert attention == 2 * H * H + 2 * 512 * H + mlp + 2 * H and attention == pytest.approx(60.8e6, rel=1e-3)
    assert H * VOCAB == pytest.approx(205.5e6, rel=1e-3)
    # the WHOLE model (the published depth), the embedding once: the head is the same matrix
    assert counts.model_weights(CONFIG) == 36 * mamba + 4 * attention + H + H * VOCAB
    assert counts.model_weights(CONFIG) == pytest.approx(3.19e9, rel=1e-3)
    assert counts.model_weights({**CONFIG, "reduced_from": {}}) == 18 * mamba + 2 * attention + H + H * VOCAB


def test_a_decode_step_by_hand():
    ssm = H * 8512 + INNER * H + 3 * H * FFN
    softmax = H * 3072 + H * H + 3 * H * FFN
    q40 = (18 * ssm + 2 * softmax + H * VOCAB) * Q40
    got = counts.weight_bytes_per_step(CONFIG, rows=32)
    assert q40 < got < q40 * 1.005  # + the f32 vectors, norms and 32 embedding rows
    assert 0.95e9 < got < 0.98e9
    # a row's state and tail: 18 layers x (64 x 64 x 128 + 3 x 4352) float32 = 38.7 MB
    assert counts.state_bytes_per_row(CONFIG) == 4 * 18 * (STATE + 3 * CONV) == 38688768
    # keys and values of a position: the TWO softmax layers only, 8 heads of 64, bf16
    assert counts.kv_bytes_per_position(CONFIG) == 2 * 2 * 512 * 2
    step = counts.decode_step_bytes(CONFIG, 32, 32 * 800)
    assert step == pytest.approx(got + 2 * 32 * 38688768 + 32 * 800 * 4096)
    # the state in and out is 70 % of the step at 32 rows; keys and values 3 %
    assert 0.68 < 2 * 32 * 38688768 / step < 0.72 and 32 * 800 * 4096 / step < 0.035
    assert 3.5e9 < step < 3.6e9  # 4.3 ms at 819 GB/s
    # the whole depth: twice the layers' part, the head's once: 7.0 GB, 8.6 ms
    whole = {**CONFIG, "num_hidden_layers": 40}
    assert 6.9e9 < counts.decode_step_bytes(whole, 32, 32 * 800) < 7.1e9
    assert counts.state_bytes_per_row(whole) == 77377536
    # the same model at 16 rows of short prompts: the state is under three fifths
    assert 2 * 16 * 38688768 / counts.decode_step_bytes(CONFIG, 16, 16 * 200) < 0.6


@pytest.mark.parametrize("role,shape,d_in,d_held", [
    ("lin_in", [32, 9216], H, 8512),  # in_proj: 8512 columns in 9 tiles of 1024
    ("wqkv", [32, 3072], H, 3072),
    ("gate_up", [32, 16384], H, 2 * FFN),
    ("down", [32, 2048], FFN, H),
    ("logits", [32, 100352], H, VOCAB),
    ("gate_up", [256, 16384], H, 2 * FFN),
])
def test_a_q40_launch_by_hand(role, shape, d_in, d_held):
    rows = shape[0]
    nbytes, ops = counts.kernel_launch(CONFIG, role, shape)
    assert nbytes == d_in * d_held * Q40 + rows * d_in + 4 * rows * shape[1]
    assert ops == 2.0 * rows * d_in * d_held


def test_the_two_output_projections_share_a_role_and_count_as_their_mean_by_launches():
    nbytes, ops = counts.kernel_launch(CONFIG, "wo", [32, 2048])
    softmax = H * H * Q40 + 32 * H + 4 * 32 * H
    ssm = INNER * H * Q40 + 32 * INNER + 4 * 32 * H
    assert nbytes == pytest.approx((2 * softmax + 18 * ssm) / 20)
    assert ops == pytest.approx(2.0 * 32 * H * (2 * H + 18 * INNER) / 20)


def test_the_state_space_kernels_by_hand():
    # the step: result [rows, head groups, lanes]; every row's state in and out, x, B, C, dt in, y out
    nbytes, ops = counts.kernel_launch(CONFIG, "ssd_step", [32, 32, 128])
    assert nbytes == 2 * 4 * 32 * STATE + 32 * 4 * (2 * INNER + 2 * N + HEADS) and ops == 5.0 * 32 * STATE
    assert nbytes == pytest.approx(135.3e6, rel=1e-3)  # 165 us at 819 GB/s; 18 launches a step: 3.0 ms
    assert ops / 197e12 < nbytes / 819e9 / 30  # the operations never bind
    # a piece: result [tokens, heads x P]; ONE state in and out, the tokens' inputs and output
    nbytes, ops = counts.kernel_launch(CONFIG, "ssd_chunk", [256, 4096])
    assert nbytes == 2 * 4 * STATE + 256 * 4 * (2 * INNER + 2 * N + HEADS) and ops == 5.0 * 256 * STATE
    assert nbytes / 819e9 > ops / 197e12  # 15.8 us of bytes against 3.4 us of operations
    for role, shape in (("ssd_step", [32, 4096]), ("ssd_chunk", [256, 64, 64]), ("ssd_step", [32, 16, 128])):
        with pytest.raises(ValueError, match="values a row"):
            counts.kernel_launch(CONFIG, role, shape)
    with pytest.raises(ValueError, match="no Q40 matrix"):
        counts.kernel_launch(CONFIG, "held_experts", [32, 4096])


def test_the_cell_is_over_the_drivers_floor_by_arithmetic():
    """What the cell holds on the chip, from the counts alone (the measured
    peak is PERF.md's): the served layers' weights, embedding and head, 32 rows
    of state and keys and values, 24 snapshot slots; over the 4 GB floor before
    the temporaries."""
    served = counts.model_weights({**CONFIG, "reduced_from": {}})
    weights = (served - H * VOCAB) * 20 / 32 + H * VOCAB * (4 + 20 / 32)
    slab = 32 * counts.state_bytes_per_row(CONFIG) + 32 * 2048 * counts.kv_bytes_per_position(CONFIG)
    snapshots = (384 // 16) * counts.state_bytes_per_row(CONFIG)
    assert 1.8e9 < weights < 2.0e9 and 1.5e9 < slab < 1.55e9 and 0.9e9 < snapshots < 0.95e9
    assert weights + slab + snapshots > 4.2e9
