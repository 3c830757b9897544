"""What ``correct`` lets through and what it stops for the GLM-5 cell: its own
``check`` block (a long probe of 4128 tokens, past ``index_topk`` 2048, among
the probes; a routing tie of its own, the defaults else) through
``cell.judge_probes``, over the family's reference computed in lower
precisions (``benchmark/tools/precision_control.py``, which gives the same
readings at the published width in minutes), at a width where the router (a
top 4 of 64, 8 held) is decided as the published one is and with the PUBLISHED
``index_topk``, so that the long probe's positions attend a selection. The
served path returns no logits, so the rule sees greedy tokens only."""

import json
import os

import pytest

import glm5_tiny
import tiny_root
from benchmark.harness import cell, modelfile
from benchmark.tools import precision_control

with open(os.path.join(tiny_root.REPO, "benchmark", "workloads", f"{glm5_tiny.REAL_CELL}.json")) as f:
    LAUNCH = json.load(f)
CONFIG = {k: v for k, v in glm5_tiny.MID.items() if k != "check"}
CHECK = cell.load_check(config=CONFIG, launch=LAUNCH)  # the real cell's rule


@pytest.fixture(scope="module")
def verdicts(tmp_path_factory):
    path = modelfile.write_model(str(tmp_path_factory.mktemp("mid") / "mid.m"), CONFIG, 512, 2**31 + 5)
    return precision_control.control(CONFIG, path, CHECK, 7, ["q80", "three_mantissa_bits", "state_bfloat16"])


def test_the_rule_is_the_real_cells():
    assert (CHECK["long_probes"], CHECK["long_probe_prompt"], CHECK["probe_tokens"]) == (1, 4128, 32)
    assert CHECK["probes"] == LAUNCH["check"]["probes"] and CHECK["router_tie"] == LAUNCH["check"]["router_tie"]
    # a long probe lies 16 prefill pieces deep and its every answered position attends a selection
    assert CHECK["long_probe_prompt"] > 16 * 256 and CHECK["long_probe_prompt"] > 2 * CONFIG["index_topk"]


@pytest.mark.parametrize("variant,want", [
    ("q80", True),  # the engine's own rounding of every matmul's input
    ("three_mantissa_bits", False),  # float8's mantissa: the nearest format below Q80
    ("state_bfloat16", None),  # no state is handed from step to step: nothing to round
])
def test_a_lower_precision_is_stopped_where_the_rule_can_see_it(verdicts, variant, want):
    ok, note = verdicts[variant]
    assert ok is want, note
    if want is not None:
        assert "positions after a prompt of 4128 tokens" in note
