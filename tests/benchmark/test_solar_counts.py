"""Solar-Open2's byte and operation counts against counts made by hand, and
the kernel readers of its cell over the op table of a capture of that cell
on the chip (launches and device seconds as `trace_reduce` gave them)."""

import json
import os

import pytest

from benchmark import families
from benchmark.harness import readers

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmark", "configs", "solar-open2-250b-q40-8l-ep16.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
    PEAKS = json.load(f)["TPU v5 lite"]
counts = families.counts(CONFIG)
Q40 = 18 / 32

# a 2 s decode-only capture of `solar-open2.batch_prompted` (my chip run, PR 26): launches, seconds;
# the grouped launches under the name they carry since the step's tokens are in it
CAPTURE = {
    "kda_step f32[32,64,128]": (719, 0.3051),
    "q40_int8_grouped_held_experts_t32 f32[20,8,3072]": (960, 0.1439),
    "q40_int8_grouped_held_experts_t32 f32[20,8,4096]": (960, 0.0695),
    "q40_int8_lin_in f32[32,25600]": (720, 0.1164),
    "q40_int8_wo f32[32,4096]": (960, 0.0514),
    "q40_int8_wqkv f32[32,18432]": (240, 0.0278),
    "q40_int8_gate_up f32[32,3072]": (960, 0.0194),
    "q40_int8_logits f32[32,24576]": (120, 0.0185),
    "fusion f32[20,9,4096]": (960, 0.0281),
}


def test_thirty_two_tokens_touch_eleven_of_twenty_held_experts():
    assert counts.experts_touched(20, 320, 8, 32) == pytest.approx(20 * (1 - (1 - 8 / 320) ** 32))
    assert 11.0 < counts.experts_touched(20, 320, 8, 32) < 11.2
    assert 19.9 < counts.experts_touched(20, 320, 8, 256) < 20.0


@pytest.mark.parametrize("d_out,d_in,d_held", [(3072, 4096, 2560), (4096, 1280, 4096)])
def test_a_bucketed_launch_reads_the_experts_its_steps_tokens_touch(d_out, d_in, d_held):
    """The bucket's 8 rows are what an expert multiplies, not how many tokens
    routed: the weights read are those of a 32-token step."""
    touched = counts.experts_touched(20, 320, 8, 32)
    nbytes, ops = counts.kernel_launch(CONFIG, "held_experts_t32", [20, 8, d_out])
    weights = touched * d_in * d_held * Q40
    assert nbytes == pytest.approx(weights + touched * 8 * d_in + 4 * touched * 8 * d_out)
    assert weights / nbytes > 0.95 and ops == pytest.approx(2 * touched * 8 * d_in * d_held)
    # the every-row launch of the same step reads the same experts
    every_row, _ = counts.kernel_launch(CONFIG, "held_experts_t32", [20, 32, d_out])
    assert nbytes < every_row < 1.15 * nbytes
    # and a prefill chunk's bucket of 32 rows every one of the 20 (and four times the rows)
    chunk, _ = counts.kernel_launch(CONFIG, "held_experts_t256", [20, 32, d_out])
    more = counts.experts_touched(20, 320, 8, 256) / touched
    assert more < chunk / nbytes < 1.2 * more


@pytest.mark.parametrize("role,shape", [("held_experts", [20, 8, 3072]), ("held_experts_t32", [32, 3072]),
                                        ("held_experts_t", [20, 8, 3072])])
def test_a_grouped_launch_that_does_not_say_its_tokens_is_an_error(role, shape):
    with pytest.raises(ValueError, match="held_experts_t<tokens>"):
        counts.kernel_launch(CONFIG, role, shape)


def test_a_decode_step_by_hand():
    h, lin, q, kv, width = 4096, 8192, 8192, 1024, 1280
    softmax = h * (2 * q + 2 * kv) + q * h
    linear = h * (3 * lin + 2 * 128 + 64) + 2 * 128 * lin + lin * h
    moe = h * 320 + 3 * h * width * (1 + counts.experts_touched(20, 320, 8, 32))
    q40 = (2 * softmax + 6 * linear + 8 * moe + h * 24576) * Q40
    got = counts.weight_bytes_per_step(CONFIG, rows=32)
    assert q40 < got < q40 * 1.003  # + the f32 tensors and 32 embedding rows
    assert counts.state_bytes_per_row(CONFIG) == 4 * 6 * (64 * 128 * 128 + 3 * 3 * lin)
    assert counts.kv_bytes_per_position(CONFIG) == 2 * 2 * kv * 2
    step = counts.decode_step_bytes(CONFIG, 32, 32 * 800)
    assert step == pytest.approx(got + 2 * 32 * counts.state_bytes_per_row(CONFIG) + 32 * 800 * 8192)
    assert 3.3e9 < step < 3.6e9  # ISSUE 26: "a decode step of 32 rows moves about 3.5 GB"


def _reader(name):
    with open(os.path.join(REPO, "benchmark", "layer_metrics", f"{name}.json")) as f:
        return json.load(f)["reader"]


@pytest.mark.parametrize("name,low,high", [
    ("kda_step_roofline", 75.0, 82.0),
    ("q40_held_experts_roofline", 50.0, 60.0),  # 22 to 24 % while the bucket's rows stood for the tokens
    ("q40_dense32_roofline", 40.0, 52.0),
])
def test_the_cells_kernel_readers_over_a_capture_of_the_chip(name, low, high):
    facts = {"model.kernel_launch": lambda role, shape: counts.kernel_launch(CONFIG, role, shape),
             "peaks": PEAKS,
             "trace.ops": {op: {"count": n, "seconds": s} for op, (n, s) in CAPTURE.items()}}
    assert low < readers._kernel_roofline(_reader(name), facts) < high


def test_the_readers_of_the_accepted_cells_find_nothing_in_this_cells_capture():
    facts = {"model.kernel_launch": None, "peaks": PEAKS,
             "trace.ops": {op: {"count": n, "seconds": s} for op, (n, s) in CAPTURE.items()}}
    for name in ("q40_dense_roofline", "q40_experts_roofline"):
        assert readers._kernel_roofline(_reader(name), facts) is None
