"""K-EXAONE's byte and operation counts against counts made by hand: the
decode step's floor (a window layer's live keys and values are the window's),
and every role the configuration launches."""

import json
import os

import pytest

from benchmark import families

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmark", "configs", "k-exaone-236b-q40-8l-ep8.json")) as f:
    CONFIG = json.load(f)
counts = families.counts(CONFIG)
Q40 = 18 / 32
H, Q, KV, DENSE, WIDTH = 6144, 8192, 1024, 18432, 2048


def test_a_decode_step_by_hand():
    attention = H * (Q + 2 * KV) + Q * H
    touched = counts.experts_touched(16, 128, 8, 16)
    assert touched == pytest.approx(16 * (1 - (1 - 1 / 16) ** 16)) and 10.2 < touched < 10.4
    sparse = H * 128 + 3 * H * WIDTH * (1 + touched)
    q40 = (8 * attention + 3 * H * DENSE + 7 * sparse + H * 19200) * Q40
    got = counts.weight_bytes_per_step(CONFIG, rows=16)
    assert q40 < got < q40 * 1.002  # + the f32 tensors and 16 embedding rows
    # ISSUE 37: 0.92 GB of weights outside the experts and 1.53 GB of touched experts
    assert 2.4e9 < got < 2.5e9
    assert counts.kv_bytes_per_position(CONFIG, "full") == 2 * 2 * KV * 2
    assert counts.kv_bytes_per_position(CONFIG, "window") == 2 * 6 * KV * 2
    step = counts.decode_step_bytes(CONFIG, 16, 16 * 11000)
    assert step == pytest.approx(got + 16 * 11000 * 8192 + 16 * 128 * 24576)
    assert 3.8e9 < step < 4.0e9  # ISSUE 37: "3.9 GB: 4.8 ms at 819 GB/s"
    # the window layers' part does not grow with the context, and is the rows' positions below it
    assert counts.decode_step_bytes(CONFIG, 16, 16 * 12000) - step == 16 * 1000 * 8192
    assert counts.decode_step_bytes(CONFIG, 16, 16 * 100) == pytest.approx(got + 16 * 100 * (8192 + 24576))
    # uniform layers would read four times the K/V: what the rings spare a step
    uniform = 16 * 11000 * 4 * 8192
    assert uniform / (16 * 11000 * 8192 + 16 * 128 * 24576) > 3.8


@pytest.mark.parametrize("role,shape,d_in,d_held", [
    ("wqkv", [16, 10240], H, Q + 2 * KV), ("wo", [16, 6144], Q, H),
    ("gate_up", [16, 36864], H, 2 * DENSE), ("gate_up", [16, 4096], H, 2 * WIDTH),
    ("logits", [16, 19200], H, 19200), ("wqkv", [256, 10240], H, Q + 2 * KV)])
def test_a_dense_launch_reads_its_matrix_once(role, shape, d_in, d_held):
    nbytes, ops = counts.kernel_launch(CONFIG, role, shape)
    rows = shape[0]
    assert nbytes == pytest.approx(d_in * d_held * Q40 + rows * d_in + 4 * rows * shape[1])
    assert ops == 2.0 * rows * d_in * d_held


def test_the_two_down_matrices_of_one_name_count_as_their_mean_by_launches():
    """One dense layer's down (18432 rows in) and seven shared experts' (2048)
    give the same columns under the same role: over a trace, launches x the
    mean is the sum."""
    nbytes, ops = counts.kernel_launch(CONFIG, "down", [16, 6144])
    one = lambda d_in: d_in * H * Q40 + 16 * d_in + 4 * 16 * H
    assert 8 * nbytes == pytest.approx(one(DENSE) + 7 * one(WIDTH))
    assert 8 * ops == pytest.approx(2.0 * 16 * H * (DENSE + 7 * WIDTH))


@pytest.mark.parametrize("d_out,d_in,d_held", [(4096, H, 2 * WIDTH), (6144, WIDTH, H)])
def test_a_grouped_launch_reads_the_experts_its_steps_tokens_touch(d_out, d_in, d_held):
    touched = counts.experts_touched(16, 128, 8, 16)
    nbytes, ops = counts.kernel_launch(CONFIG, "held_experts_t16", [16, 16, d_out])
    weights = touched * d_in * d_held * Q40
    assert weights / nbytes > 0.9 and ops == pytest.approx(2 * touched * 16 * d_in * d_held)
    # a prompt piece's bucket of 64 rows: every one of the 16, four times the rows
    chunk, _ = counts.kernel_launch(CONFIG, "held_experts_t256", [16, 64, d_out])
    assert 16 / touched < chunk / nbytes < 2.2 * 16 / touched


@pytest.mark.parametrize("role,shape", [("held_experts", [16, 16, 4096]), ("lin_in", [16, 4096]),
                                        ("down", [16, 4096])])
def test_a_launch_the_configuration_does_not_make_is_an_error(role, shape):
    with pytest.raises(ValueError):
        counts.kernel_launch(CONFIG, role, shape)
