"""What ``correct`` lets through and what it stops for the
Granite-4.0-H-Small cell: its ``check`` block (``probe_prompt`` 320, two long
probes of 1100 tokens, ``miss_tol`` and ``router_tie`` fitted: the block says
why) through ``cell.judge_probes``, over the family's reference computed in
lower precisions and with a planted fault
(``benchmark/tools/precision_control.py``), at a width of 512 with the
published head sizes (64 values and 128 state values a state-space head, 128 a
softmax head), the published 10 of 72 experts a token with 18 held, and the ten
layers the cell serves. The served path returns no logits, so the rule sees
greedy tokens only."""

import json
import os

import jax
import pytest

import granite_moe_tiny
import tiny_root
from benchmark import families
from benchmark.harness import cell, modelfile
from benchmark.tools import precision_control

with open(os.path.join(tiny_root.REPO, "benchmark", "workloads", f"{granite_moe_tiny.REAL_CELL}.json")) as f:
    LAUNCH = json.load(f)  # the real cell's rule: its check block is in the cell's own file
CONFIG = granite_moe_tiny.MID
CHECK = cell.load_check(config=CONFIG, launch=LAUNCH)


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    return modelfile.write_model(str(tmp_path_factory.mktemp("mid") / "mid.m"), CONFIG, 2048, 2**31 + 5)


@pytest.fixture(scope="module")
def verdicts(model):
    return precision_control.control(CONFIG, model, CHECK, 7, list(precision_control.VARIANTS))


def test_the_rule_is_the_real_cells():
    assert (CHECK["probe_prompt"], CHECK["long_probes"], CHECK["long_probe_prompt"]) == (320, 2, 1100)
    assert (CHECK["probes"], CHECK["probe_tokens"], CHECK["miss_tol"], CHECK["router_tie"]) == (8, 32, 7e-2, 1e-3)
    assert CHECK["max_miss_share"] == 0.03 and CHECK["min_compared_share"] == 0.25
    # a probe's prompt crosses a prefill piece (256), a long one four of them: state and tail are
    # handed on, and the held experts run at a piece's rows, before the answer is decoded
    assert CHECK["probe_prompt"] > 256 and CHECK["long_probe_prompt"] > 4 * 256
    assert (CONFIG["num_experts_per_tok"], CONFIG["num_local_experts"],
            CONFIG["reduced_from"]["num_local_experts"]) == (10, 18, 72)


@pytest.mark.parametrize("variant,want", [
    ("q80", True),  # the engine's own rounding of every matmul's input
    ("bfloat16", True),
    ("three_mantissa_bits", False),  # float8's mantissa: the nearest format below Q80
    ("state_three_mantissa_bits", False),
    # what the token rule does NOT see, as in the sibling's cell: a recurrent state kept in
    # bfloat16; the engine's float32 tests on the CPU and tools/ssd_state_witness.py on the chip
    # hold the state in the LOGITS
    ("state_bfloat16", True),
])
def test_a_lower_precision_is_stopped_where_the_rule_can_see_it(verdicts, variant, want):
    ok, note = verdicts[variant]
    assert ok is want, note
    assert "after a prompt of 1100 tokens" in note and "routing near-ties left out" in note


def test_the_routing_gap_excuses_a_seventh_of_the_positions_and_no_more(verdicts):
    """With 18 held experts in each of ten layers a position whose nearest
    held expert lies within the Q80 rounding's reach of the boundary (2e-2 of
    max|logit|, ``check.json``'s default) is nearly every position (250 of
    256): the cell's ``router_tie`` 1e-3 leaves out 30 to 45 of 256 and
    compares the rest."""
    import re

    _, note = verdicts["q80"]
    compared, ties = (int(n) for n in re.search(r"(\d+) positions compared \((\d+) routing near-ties", note).groups())
    assert compared + ties == 256 and 20 <= ties <= 60, note


def test_a_dropped_shared_expert_is_stopped(model, monkeypatch):
    """The shared expert left out of the variant: the rule has to read NOT
    correct (the float32 reference judges)."""
    ref = families.load(CONFIG, "reference")
    monkeypatch.setitem(precision_control.VARIANTS, "no_shared", ("shared_expert", None))
    plain = ref.shared_expert
    monkeypatch.setattr(precision_control, "rounded", _without_shared(ref, plain))
    out = precision_control.control(CONFIG, model, CHECK, 7, ["no_shared"])
    jax.clear_caches()
    ok, note = out["no_shared"]
    assert ok is False, note
    assert ref.shared_expert is plain  # the control put the family's own back


def _without_shared(ref, plain):
    import contextlib

    @contextlib.contextmanager
    def rounded(_ref, _name):
        ref.shared_expert = lambda qf, l, xn: 0.0 * plain(qf, l, xn)
        jax.clear_caches()
        try:
            yield
        finally:
            ref.shared_expert = plain
            jax.clear_caches()
    return rounded
