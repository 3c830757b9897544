"""What ``correct`` lets through and what it stops for the Granite-4.0-H-Micro
cell: its ``check`` block (``probe_prompt`` 320, two long probes of 1100
tokens, ``miss_tol`` 1e-1 and ``dense_hard_tol`` 2e-1: the block says why)
through ``cell.judge_probes``, over the family's reference computed in lower
precisions and with planted faults (``benchmark/tools/precision_control.py``),
at a width of 256 with heads of the published size (64 values, 128 state
values) and the 20 layers the cell serves: the depth is what carries a
rounding on (at 40 layers the reference's Q80 reading at this width, 47 of 256
positions over 1e-2 and none over 1e-1, was the served engine's on the chip at
40 layers: 44 and 49, none). The served path returns no logits, so the rule
sees greedy tokens only."""

import json
import os

import jax
import pytest

import granite_tiny
import tiny_root
from benchmark import families
from benchmark.harness import cell, modelfile
from benchmark.tools import precision_control

with open(os.path.join(tiny_root.REPO, "benchmark", "workloads", "granite-4.0-h-micro.batch_prompted.json")) as f:
    LAUNCH = json.load(f)  # the real cell's rule: its check block is in the cell's own file
CONFIG = {**{k: v for k, v in granite_tiny.MID.items() if k != "check"}, "name": "mid-granite-20",
          "num_hidden_layers": 20, "layer_types": granite_tiny.CONFIG["layer_types"] * 4}
CHECK = cell.load_check(config=CONFIG, launch=LAUNCH)


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    return modelfile.write_model(str(tmp_path_factory.mktemp("mid") / "mid.m"), CONFIG, 2048, 2**31 + 5)


@pytest.fixture(scope="module")
def verdicts(model):
    return precision_control.control(CONFIG, model, CHECK, 7, list(precision_control.VARIANTS))


def test_the_rule_is_the_real_cells():
    assert (CHECK["probe_prompt"], CHECK["long_probes"], CHECK["long_probe_prompt"]) == (320, 2, 1100)
    assert (CHECK["probes"], CHECK["probe_tokens"], CHECK["dense_hard_tol"], CHECK["miss_tol"]) == (8, 32, 2e-1, 1e-1)
    assert CHECK["max_miss_share"] == 0.03  # 7 of 256
    # a probe's prompt crosses a prefill piece (256), a long one four of them: state and tail are
    # handed on before the answer is decoded; the longest prompt of the traffic is 1024 + the template
    assert CHECK["probe_prompt"] > 256 and CHECK["long_probe_prompt"] > 4 * 256


@pytest.mark.parametrize("variant,want", [
    ("q80", True),  # the engine's own rounding of every matmul's input
    ("bfloat16", True),
    ("three_mantissa_bits", False),  # float8's mantissa: the nearest format below Q80
    ("state_three_mantissa_bits", False),
    # what the token rule does NOT see (PERF.md section 7): the fault ISSUE 45 plants on the chip,
    # the recurrent state kept in bfloat16 where the configuration assumes float32. It moves the
    # logits half as far as the Q80 rounding does and no greedy token; the engine's float32 tests on the CPU and
    # tools/ssd_state_witness.py on the chip hold the state in the LOGITS
    ("state_bfloat16", True),
])
def test_a_lower_precision_is_stopped_where_the_rule_can_see_it(verdicts, variant, want):
    ok, note = verdicts[variant]
    assert ok is want, note
    assert "after a prompt of 1100 tokens" in note


def test_a_skipped_d_is_stopped(model, monkeypatch):
    """The skip connection around the recurrence left out of the variant:
    the rule has to read NOT correct (the float32 reference judges)."""
    ref = families.load(CONFIG, "reference")
    monkeypatch.setitem(precision_control.VARIANTS, "no_skip", ("skip", lambda dx: 0.0 * dx))
    out = precision_control.control(CONFIG, model, CHECK, 7, ["no_skip"])
    jax.clear_caches()
    ok, note = out["no_skip"]
    assert ok is False, note
    assert ref.skip.__name__ == "skip"  # the control put the family's own back
