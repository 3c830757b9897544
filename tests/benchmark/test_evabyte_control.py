"""What ``correct`` lets through and what it stops for the EvaByte cell: its
own ``check`` block (one long probe of 4128 positions among the 8, the
defaults else) through ``cell.judge_probes``, over the family's reference
computed in lower precisions (``benchmark/tools/precision_control.py``, which
gives the same readings at the published width in minutes), at a width of 256
and the published window and chunk. The served path returns no logits, so
the rule sees greedy tokens only."""

import json
import os

import pytest

import evabyte_tiny
import tiny_root
from benchmark.harness import cell, modelfile
from benchmark.tools import precision_control

with open(os.path.join(tiny_root.REPO, "benchmark", "workloads", f"{evabyte_tiny.REAL_CELL}.json")) as f:
    LAUNCH = json.load(f)
# the published window and chunk: the long probe's answers read 256 summaries, as the real cell's do
CONFIG = {**evabyte_tiny.MID, "window_size": 2048, "chunk_size": 16}
CHECK = cell.load_check(config=CONFIG, launch=LAUNCH)  # the real cell's rule


@pytest.fixture(scope="module")
def verdicts(tmp_path_factory):
    path = modelfile.write_model(str(tmp_path_factory.mktemp("mid") / "mid.m"), CONFIG, 8192, 2**31 + 5)
    return precision_control.control(CONFIG, path, CHECK, 7, ["q80", "bfloat16", "three_mantissa_bits",
                                                              "state_bfloat16"])


def test_the_rule_is_the_real_cells():
    assert (CHECK["long_probes"], CHECK["long_probe_prompt"], CHECK["probes"], CHECK["probe_tokens"]) == \
        (1, 4128, 8, 32)
    # the long probe's answers lie in the third window: two finished ones are read as summaries
    assert 2 * 2048 < CHECK["long_probe_prompt"] < CHECK["long_probe_prompt"] + 32 < 3 * 2048
    assert CHECK["router_tie"] == 2e-2 and CHECK["dense_hard_tol"] == 3e-2  # the defaults


@pytest.mark.parametrize("variant,want", [
    ("q80", True),  # the engine's own rounding of every matmul's input
    ("bfloat16", True),
    ("three_mantissa_bits", False),  # float8's mantissa: the nearest format below Q80
    ("state_bfloat16", None),  # no state is handed from step to step: nothing to round
])
def test_a_lower_precision_is_stopped_where_the_rule_can_see_it(verdicts, variant, want):
    ok, note = verdicts[variant]
    assert ok is want, note
    if want is not None:
        assert "positions after a prompt of 4128 tokens" in note
