"""``trace_reduce`` on hand-made intervals and on a trimmed recording from the
chip (``data/chip_trace_trimmed.json``: the first events of each line of a
``mistral7b.chat_shared`` trace taken on a TPU v5e)."""

import json
import os

import pytest

from benchmark.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "chip_trace_trimmed.json")


def plane(ops, modules):
    return {tr.OPS_LINE: [list(e) for e in ops], tr.MODULES_LINE: [list(e) for e in modules]}


def test_busy_is_the_union_of_op_intervals():
    # two ops overlap (a while spans its body), one stands alone
    p = plane([("while", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 50, 40), ("copy", 200, 50)],
              [("jit_step(7)", 0, 100), ("jit_step(7)", 200, 50)])
    red = tr.reduce_plane(p)
    assert red["window_ns"] == 250 and red["busy_ns"] == 150
    assert red["modules"] == {"jit_step": {"count": 2, "ns": 150}}


def test_self_time_takes_nested_ops_out_of_their_parent():
    ops = [["while", 0, 100], ["fusion.1", 10, 30], ["fusion.2", 50, 40], ["fusion.1", 120, 5]]
    assert tr._self_times(ops) == {"while": 30, "fusion.1": 35, "fusion.2": 40}


def test_gaps_are_labelled_by_the_programs_around_them():
    p = plane([("a", 0, 10), ("b", 40, 10), ("c", 50, 10), ("a", 100, 10)],
              [("jit_prefill(1)", 0, 10), ("jit_decode(2)", 40, 20), ("jit_prefill(1)", 100, 10)])
    gaps = tr.reduce_plane(p)["gaps"]
    assert gaps == {"jit_prefill -> jit_decode": 30, "jit_decode -> jit_prefill": 40}


def test_idle_share_and_the_average_over_chips():
    busy_half = plane([("op", 0, 50)], [("jit_f(1)", 0, 100)])
    busy_full = plane([("op", 0, 100)], [("jit_f(1)", 0, 100)])
    red = tr.reduce({"/device:TPU:0": busy_half, "/device:TPU:1": busy_full,
                     "/device:TPU:2": plane([], []), "_inventory": {}}, chips=2)
    assert red["busy_s"] == pytest.approx(75e-9) and red["window_s"] == pytest.approx(100e-9)
    assert red["idle_share"] == pytest.approx(25.0)
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce({"/device:TPU:0": plane([], []), "_inventory": {}}, chips=1)


def test_module_names_lose_their_run_specific_id():
    assert tr._module_name("jit_decode_chunk_batched_paged(1234567890)") == "jit_decode_chunk_batched_paged"
    assert tr._module_name("jit_f") == "jit_f"


@pytest.mark.parametrize("printed,kind", [
    ("%_rmsnorm_q40_matmul_int8.5 = f32[16,28672]{1,0:T(8,128)} custom-call(bf16[16,4096]{1,0} %p)",
     "_rmsnorm_q40_matmul_int8 f32[16,28672]"),
    ("%slice_bitcast_fusion.102 = (bf16[16,2048,8,128]{3,2,1,0}, bf16[16,2048,8,128]{3,2,1,0}) fusion(%x)",
     "slice_bitcast_fusion bf16[16,2048,8,128]"),
    ("%fusion.12.1 = s32[]{:T(128)} fusion()", "fusion s32[]"),
    ("%while.3", "while"),
])
def test_op_kinds_group_the_per_layer_copies_of_one_op(printed, kind):
    assert tr._op_name(printed) == kind


def test_union_merges_touching_and_nested_intervals():
    assert tr._union([(0, 10), (10, 20), (5, 8), (30, 40)]) == [(0, 20), (30, 40)]


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def test_recorded_chip_trace_reduces(recorded):
    planes = {**recorded, "_inventory": {}}
    red = tr.reduce(planes, chips=1)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0.0 <= red["idle_share"] < 100.0
    assert red["device_ops"] and all(s > 0 for _, s in red["device_ops"])
    assert any("decode_chunk" in name for name in red["modules"])
    # the sums stand: ops' self times add up to the busy time of their line
    lines = recorded[red["planes_used"][0]]
    assert sum(tr._self_times(lines[tr.OPS_LINE]).values()) == pytest.approx(red["busy_s"] * 1e9, rel=1e-6)
