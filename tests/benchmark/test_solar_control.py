"""What ``correct`` lets through and what it stops for the Solar-Open2
configuration: its ``check`` block (``probe_prompt`` 320, ``router_tie`` 2e-3,
the defaults else) through ``cell.judge_probes``, over the family's reference
computed in lower precisions (``benchmark/tools/precision_control.py``, which
gives the same readings at the published width in minutes), at a width where
the router is decided as the published one is. The served path returns no
logits, so the rule sees greedy tokens only."""

import json
import os

import pytest

import solar_tiny
import tiny_root
from benchmark.harness import cell, modelfile
from benchmark.tools import precision_control

with open(os.path.join(tiny_root.REPO, "benchmark", "configs", "solar-open2-250b-q40-8l-ep16.json")) as f:
    CONFIG = {**solar_tiny.MID, "check": json.load(f)["check"]}  # the real cell's rule
CHECK = cell.load_check(config=CONFIG)


@pytest.fixture(scope="module")
def verdicts(tmp_path_factory):
    path = modelfile.write_model(str(tmp_path_factory.mktemp("mid") / "mid.m"), CONFIG, 512, 2**31 + 5)
    return precision_control.control(CONFIG, path, CHECK, 7, list(precision_control.VARIANTS))


def test_the_rule_is_the_real_cells():
    assert (CHECK["probe_prompt"], CHECK["router_tie"], CHECK["probes"], CHECK["probe_tokens"]) == (320, 2e-3, 8, 32)
    assert CHECK["probe_prompt"] > 256  # a probe's prompt crosses a prefill chunk: the state is handed on


@pytest.mark.parametrize("variant,want", [
    ("q80", True),  # the engine's own rounding of every matmul's input
    ("bfloat16", True),
    ("three_mantissa_bits", False),  # float8's mantissa: the nearest format below Q80
    ("state_three_mantissa_bits", False),
    # what the token rule does NOT see (PERF.md §7): a recurrent state held in bfloat16, where the
    # configuration assumes float32; the engine's CPU tests hold the state to 2e-5 of max|logit|
    ("state_bfloat16", True),
])
def test_a_lower_precision_is_stopped_where_the_rule_can_see_it(verdicts, variant, want):
    ok, note = verdicts[variant]
    assert ok is want, note
    assert "routing near-ties left out" in note


def test_the_llama_family_has_no_state_to_round(tmp_path):
    dense = {**tiny_root.TINY, "name": "ctl-dense", "family": "llama", "arch": "llama", "num_hidden_layers": 2}
    check = dict(cell.load_check(), probes=2, min_compared=16)
    path = modelfile.write_model(str(tmp_path / "d.m"), dense, 512, 2**31 + 5)
    out = precision_control.control(dense, path, check, 7, ["q80", "state_bfloat16"])
    assert out["q80"][0] is True and out["state_bfloat16"][0] is None
