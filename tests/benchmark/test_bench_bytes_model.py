"""The counts of both accepted configurations' family (``decode_step_bytes``,
once ``harness/bytes_model.py``) against counts made by hand."""

import json
import os

import pytest

from benchmark import families

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(REPO, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


bytes_model = families.counts(config("mistral-7b-q40-16l"))


def full_depth(name):
    """The published model: the cut configuration with its depth put back."""
    cut = config(name)
    return dict(cut, **cut["reduced_from"])


def test_mistral_7b_decode_step_weights_by_hand():
    # per layer: q 4096x4096, k and v 1024x4096 each, wo 4096x4096, three 4096x14336
    layer = 4096 * 4096 * 2 + 2 * 1024 * 4096 + 3 * 4096 * 14336
    q40 = (32 * layer + 32000 * 4096) * 18 / 32
    f32 = 4 * ((2 * 32 + 1) * 4096 + 4096)
    got = bytes_model.weight_bytes_per_step(full_depth("mistral-7b-q40-16l"), rows=1)
    assert got == pytest.approx(q40 + f32)
    assert 3.9e9 < got < 4.1e9  # the "4.0 GB of weights" of ISSUE 22


def test_mistral_kv_bytes_per_position_by_hand():
    # keys and values, 32 layers x 8 heads x 128 x bf16
    assert bytes_model.kv_bytes_per_position(full_depth("mistral-7b-q40-16l")) == 2 * 32 * 8 * 128 * 2 == 131072


def test_mixtral_reads_every_expert_at_sixteen_rows_and_two_at_one():
    c = config("mixtral-8x7b-q40-4l")
    attn = 4096 * 4096 * 2 + 2 * 1024 * 4096
    expert = 3 * 4096 * 14336
    assert bytes_model.experts_touched(8, 2, 1) == pytest.approx(2.0)
    assert 7.9 < bytes_model.experts_touched(8, 2, 16) < 8.0
    one = bytes_model.weight_bytes_per_step(c, rows=1)
    want_one = (4 * (attn + 8 * 4096 + 2 * expert) + 32000 * 4096) * 18 / 32 + 4 * (9 * 4096 + 4096)
    assert one == pytest.approx(want_one)
    sixteen = bytes_model.weight_bytes_per_step(c, rows=16)
    assert 0.80e9 < (sixteen - 32000 * 4096 * 18 / 32) / 4 < 0.83e9  # 0.82 GB a layer: why depth is cut
    assert bytes_model.kv_bytes_per_position(c) == 2 * 4 * 8 * 128 * 2


def test_live_context_adds_its_keys_and_values():
    c = full_depth("mistral-7b-q40-16l")
    base = bytes_model.decode_step_bytes(c, 16, 0)
    assert bytes_model.decode_step_bytes(c, 16, 16 * 1000) - base == pytest.approx(16 * 1000 * 131072)


def test_the_half_depth_mistral_is_half_the_layers_and_nothing_else():
    full, half = full_depth("mistral-7b-q40-16l"), config("mistral-7b-q40-16l")
    assert set(half["reduced_from"]) == {"num_hidden_layers"} == set(half["reduced"])
    assert full["num_hidden_layers"] == 32 and half["num_hidden_layers"] == 16
    assert bytes_model.kv_bytes_per_position(half) * 2 == bytes_model.kv_bytes_per_position(full)
