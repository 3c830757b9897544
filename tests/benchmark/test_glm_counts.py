"""GLM-4.7-Flash's byte and operation counts against counts made by hand: the
decode step's floor (a cached position is one latent row a layer, and 8 rows
touch 25.8 of 64 experts), and every role the configuration launches."""

import json
import os

import pytest

from benchmark import families

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmark", "configs", "glm-4.7-flash-q40-stage0.json")) as f:
    CONFIG = json.load(f)
counts = families.counts(CONFIG)
Q40 = 18 / 32
H, QR, LATENT, Q, KV_UP, O, DENSE, WIDTH, VOCAB = 2048, 768, 576, 5120, 8960, 5120, 10240, 1536, 154880


def test_a_decode_step_by_hand():
    # ISSUE 43: "attention 21.76 M a layer (1.57 + 3.93 + 1.18 + 4.59 + 10.49)"
    attention = H * (QR + LATENT) + QR * Q + 512 * KV_UP + O * H
    assert attention == pytest.approx(21.76e6, rel=1e-3)
    touched = counts.experts_touched(64, 64, 4, 8)
    assert touched == pytest.approx(64 * (1 - (1 - 1 / 16) ** 8)) and 25.7 < touched < 25.9
    sparse = H * 64 + 3 * H * WIDTH * (1 + touched)
    q40 = (9 * attention + 3 * H * DENSE + 8 * sparse + H * VOCAB) * Q40
    got = counts.weight_bytes_per_step(CONFIG, rows=8)
    assert q40 < got < q40 * 1.002  # + the f32 tensors and 8 embedding rows
    # ISSUE 43: "152 MB of the 26 experts that 8 rows touch and 20 MB of other matrices" a layer
    assert 3 * H * WIDTH * touched * 20 / 32 == pytest.approx(152e6, rel=0.01)
    assert 1.4e9 < got < 1.6e9
    assert counts.latent_bytes_per_position(CONFIG) == 9 * 1152
    step = counts.decode_step_bytes(CONFIG, 8, 8 * 7500)
    assert step == pytest.approx(got + 8 * 7500 * 9 * 1152)
    # ISSUE 43: "a decode step reads 8 x 7.5k x 1152 B = 69 MB of latents a layer"
    assert (step - got) / 9 == pytest.approx(69e6, rel=0.01)
    # the model's own expanded keys and values a position and layer: 17920 B, 15.6 times the latent
    assert 20 * (192 + 256) * 2 == 17920 and 17920 / 1152 > 15.5  # the nope keys and the values; the rope slice is shared
    # ... and the whole model: 29.9 B weights, over one chip at Q40
    full = dict(CONFIG, num_hidden_layers=47)
    weights = (47 * attention + 3 * H * DENSE + 46 * (H * 64 + 3 * H * WIDTH * 65) + 2 * H * VOCAB)
    assert weights == pytest.approx(29.9e9, rel=5e-3)
    assert counts.latent_bytes_per_position(full) == 47 * 1152


@pytest.mark.parametrize("role,shape,d_in,d_held", [
    ("wqkv", [8, 2048], H, QR + LATENT), ("mla_project", [8, 5120], QR, Q), ("wo", [8, 2048], O, H),
    ("gate_up", [8, 20480], H, 2 * DENSE), ("gate_up", [8, 3072], H, 2 * WIDTH),
    ("logits", [8, 155648], H, VOCAB), ("wqkv", [256, 2048], H, QR + LATENT)])
def test_a_dense_launch_reads_its_matrix_once(role, shape, d_in, d_held):
    nbytes, ops = counts.kernel_launch(CONFIG, role, shape)
    rows = shape[0]
    assert nbytes == pytest.approx(d_in * d_held * Q40 + rows * d_in + 4 * rows * shape[1])
    assert ops == 2.0 * rows * d_in * d_held


def test_the_two_down_matrices_of_one_name_count_as_their_mean_by_launches():
    """One dense layer's down (10240 rows in) and eight shared experts' (1536)
    give the same columns under the same role: over a trace, launches x the
    mean is the sum."""
    nbytes, ops = counts.kernel_launch(CONFIG, "down", [8, 2048])
    one = lambda d_in: d_in * H * Q40 + 8 * d_in + 4 * 8 * H
    assert 9 * nbytes == pytest.approx(one(DENSE) + 8 * one(WIDTH))
    assert 9 * ops == pytest.approx(2.0 * 8 * H * (DENSE + 8 * WIDTH))


@pytest.mark.parametrize("d_out,d_in,d_held", [(3072, H, 2 * WIDTH), (2048, WIDTH, H)])
def test_a_grouped_launch_reads_the_experts_its_steps_tokens_touch(d_out, d_in, d_held):
    touched = counts.experts_touched(64, 64, 4, 8)
    nbytes, ops = counts.kernel_launch(CONFIG, "held_experts_t8", [64, 8, d_out])
    weights = touched * d_in * d_held * Q40
    assert weights / nbytes > 0.9 and ops == pytest.approx(2 * touched * 8 * d_in * d_held)
    # a prompt piece's bucket of 64 rows: every one of the 64, eight times the rows
    chunk, _ = counts.kernel_launch(CONFIG, "held_experts_t256", [64, 64, d_out])
    assert 64 / touched < chunk / nbytes < 2.6 * 64 / touched


@pytest.mark.parametrize("role,shape", [("held_experts", [64, 8, 3072]), ("lin_in", [8, 4096]),
                                        ("down", [8, 4096]), ("wqkv", [8, 6144])])
def test_a_launch_the_configuration_does_not_make_is_an_error(role, shape):
    with pytest.raises(ValueError):
        counts.kernel_launch(CONFIG, role, shape)
