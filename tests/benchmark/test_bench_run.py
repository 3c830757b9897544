"""``run_cell`` end to end at a tiny size on the CPU: one device, four virtual
devices with ``--tp 4``, the MoE path; and the ways a run must refuse."""

import json
import os
import subprocess
import sys
import time

import pytest

import tiny_root
from benchmark.harness.cell import BenchFailure, Cell, run_cell

REPO = tiny_root.REPO
OPEN = {"out_tok_s", "setup_s"}  # the open-loop cell's latencies are recorded, not judged
NAMES = {  # each miniature cell reports what the real cell it stands for reports (tiny_root.build)
    "tiny.open": OPEN,
    "tiny-moe.closed": OPEN | {"ttft_p50_ms", "tpot_p50_ms.batch", "stall_p50_ms.batch"},
    "tiny-tp4.closed": OPEN | {"ttft_p50_ms", "tpot_p50_ms", "stall_p50_ms"},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root.build(str(tmp_path_factory.mktemp("checkout")))


def check_line(result, chips, names):
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == names
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] >= chips
    assert "memory_peak_bytes" in result["device"]
    json.dumps(result)


@pytest.mark.parametrize("cell,chips", [("tiny.open", 1), ("tiny-moe.closed", 1), ("tiny-tp4.closed", 4)])
def test_a_cell_runs_and_agrees_with_the_reference(root, cell, chips):
    result = run_cell(root, cell, 2**31 + 11, 3.0, False, "cpu", time.monotonic())
    check_line(result, chips, NAMES[cell])
    assert not os.path.exists(os.path.join(root, "benchmark", ".cache", "model"))  # gigabytes at full size


def test_a_traced_run_with_no_device_operation_is_refused(root):
    with pytest.raises(BenchFailure, match="no device operation"):
        run_cell(root, "tiny.open", 5, 2.0, True, "cpu", time.monotonic())


def test_the_wrong_platform_is_a_failed_run(root):
    with pytest.raises(BenchFailure, match="exited with code 3"):
        run_cell(root, "tiny.open", 5, 2.0, False, "tpu", time.monotonic())


def test_more_chips_than_devices_is_a_failed_run(root, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    with pytest.raises(BenchFailure, match="exited with code 3"):
        run_cell(root, "tiny-tp4.closed", 5, 2.0, False, "cpu", time.monotonic())


def test_an_unknown_cell_is_refused(root):
    with pytest.raises(BenchFailure, match="no workload"):
        Cell(root, "tiny.absent")


def test_the_command_line_cannot_waive_the_tpu_and_prints_no_line_without_one():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload",
         "mistral7b.single_stream", "--seed", "1", "--seconds", "1", "--trace", "0",
         "--require-platform", "cpu"], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2 and "unrecognized arguments" in proc.stderr


def test_a_directory_without_the_program_prints_no_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "mistral7b.single_stream", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0 and "not in this directory" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
