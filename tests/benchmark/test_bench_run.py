"""``run_cell`` end to end at a tiny size on the CPU: one device, four virtual
devices with ``--tp 4``, the MoE path; and the ways a run must refuse."""

import json
import os
import re
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
    # the 16-row closed loop: pace as a median; ttft and the freeze recorded (.rows16)
    "tiny-moe.closed": OPEN | {"tpot_p50_ms.batch"},
    "tiny-tp4.closed": OPEN | {"ttft_p50_ms", "tpot_p50_ms", "stall_p50_ms"},
    "tiny-docs.closed": OPEN,  # the 8-row document cell: its three latencies recorded (.rows8)
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


@pytest.mark.parametrize("cell,chips", [("tiny.open", 1), ("tiny-moe.closed", 1), ("tiny-tp4.closed", 4),
                                        ("tiny-docs.closed", 1)])
def test_a_cell_runs_and_agrees_with_the_reference(root, cell, chips, capsys):
    result = run_cell(root, cell, 2**31 + 11, 3.0, False, "cpu", time.monotonic())
    check_line(result, chips, NAMES[cell])
    assert not os.path.exists(os.path.join(root, "benchmark", ".cache", "model"))  # gigabytes at full size
    said = capsys.readouterr().err
    if cell == "tiny-docs.closed":
        # the check block of the cell's own file: a probe of 200 prompt tokens among the 8, answered,
        # compared at every position, and repeated after the drain beside the first
        assert Cell(root, cell).check["long_probe_prompt"] == 200
        assert re.search(r"32 of the positions answered follow a prompt of 200 tokens, worst \S+, "
                         r"0 over the miss line \(1 of 1 long probes read back", said)
        assert "[check] greedy probe (the long one, 200 prompt tokens) repeated after the drain: identical" in said
    else:
        assert "[check] greedy probe repeated after the drain: identical" in said
        assert "follow a prompt of" not in said


def _cpu_trace_as_device(cell, trace_dir):
    """Stands in for ``cell._reduce_trace`` on the CPU, where a capture has no
    ``/device:TPU`` plane: the XLA CPU client's thread lines of the capture
    the PROGRAM took are read as one device's ops, by the real reduction."""
    import re

    from benchmark.harness import trace_reduce

    planes = trace_reduce.load(trace_dir, re.compile(r"^/host:CPU$"))
    host = planes["/host:CPU"]
    ops = [e for line, events in host.items() if line.startswith("tf_XLA") for e in events]
    assert any(e[0].startswith("dllama/") for events in host.values() for e in events), \
        "the program's spans are on the capture's host lines"
    return {"inventory": planes["_inventory"],
            **trace_reduce.reduce({"/device:TPU:0": {trace_reduce.OPS_LINE: ops}}, cell.chips)}


NEW_COUNTERS = {"decode_consumed_share", "decode_orphaned_share", "decode_active_rows_mean",
                "decode_row_fill_share", "chunk_host_ms_mean", "chunk_fetch_wait_ms_mean",
                "program_builds_in_window"}


RECORDED = {"tiny.open": {"ttft_p50_ms.open", "tpot_p50_ms.open", "stall_p50_ms.open"},
            "tiny-moe.closed": {"ttft_p50_ms.rows16", "stall_p50_ms.rows16"},
            "tiny-docs.closed": {"ttft_p50_ms.rows8", "tpot_p50_ms.rows8", "stall_p50_ms.rows8"}}


@pytest.mark.parametrize("cell", ["tiny.open", "tiny-moe.closed", "tiny-docs.closed"])
def test_trace_2_measures_as_trace_0_does_and_then_traces_in_the_same_process(root, cell, monkeypatch):
    from benchmark.harness import cell as cell_mod

    phases = []
    real_phase, real_e2e = cell_mod._traced_phase, cell_mod.stats.end_to_end

    def e2e(*a, **kw):
        phases.append("window")  # the window's numbers are taken ...
        return real_e2e(*a, **kw)

    def phase(server, mix, seed, seconds, requests, trace_dir):
        phases.append("traced")  # ... before anything of the trace starts
        assert (requests is None) == (mix["loop"] == "open")
        before = server.scrape()
        out = real_phase(server, mix, seed, seconds, requests, trace_dir)
        # the traced phase sends NEW requests: none of the window's is replayed
        assert out["records"] and all(r.ok for r in out["records"])
        assert out["trace_stop"] - out["trace_start"] >= cell_mod.TRACE_SECONDS
        assert os.path.exists(os.path.join(trace_dir, "host_spans.json"))
        assert not os.path.exists(trace_dir + ".first")
        grew = cell_mod.prom.delta(before, server.scrape(), "dllama_tokens_streamed_total")
        assert grew == sum(len(r.deltas) for r in out["records"])
        return out

    monkeypatch.setattr(cell_mod.stats, "end_to_end", e2e)
    monkeypatch.setattr(cell_mod, "_traced_phase", phase)
    monkeypatch.setattr(cell_mod, "_reduce_trace", _cpu_trace_as_device)
    monkeypatch.setattr(cell_mod, "TRACE_SECONDS", 0.5)
    result = run_cell(root, cell, 2**31 + 13, 3.0, 2, "cpu", time.monotonic())
    assert phases == ["window", "traced"]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    # both kinds of metric, side by side: the end-to-end ones of a --trace 0 line ...
    assert NAMES[cell] <= set(result["metrics"])
    # ... and the per-layer ones, the counters read over the MEASURED window
    per_layer = set(result["metrics"]) - NAMES[cell]
    # (neither cell judges ttft, so the readers that move it elsewhere move out_tok_s here: .open)
    assert NEW_COUNTERS | RECORDED[cell] | {"server_ttft_ms_mean.open", "prefill_chunks_ahead_mean.open",
                                            "device_idle_share", "decode_ms_per_tok"} <= per_layer
    assert not {m for m in per_layer if m.endswith(".closed")}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < m["decode_consumed_share"] <= 100 and 0 <= m["decode_orphaned_share"] < 100
    assert 0 < m["decode_row_fill_share"] <= 100 and m["program_builds_in_window"] == 0
    if cell == "tiny-docs.closed":
        # a document's second ask finds its whole pages in the prefix cache: 1 or 2 of a prompt's 3
        assert 20 < m["prefix_hit_share.open"] < 50
    assert set(result["device"]) >= {"memory_peak_bytes", "busy_s", "window_s"}
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert not os.path.exists(os.path.join(root, "benchmark", ".cache", "trace"))  # reduced, deleted
    json.dumps(result)


BROKEN_UNDERNEATH = '''
"""Laid into a miniature checkout by test_bench_run.py: Python imports it at the start of every
child of the run, and in the server child it alters each token where it is produced (the
logits are rolled by one id, so the greedy token is the neighbour of the right one)."""
import sys

if "benchmark.harness.server_child" in getattr(sys, "orig_argv", []):
    import jax.numpy as jnp

    from distributed_llama_tpu.models import llama

    _final_logits = llama.final_logits
    llama.final_logits = lambda cfg, params, x: jnp.roll(_final_logits(cfg, params, x), 1, axis=-1)
'''


def test_a_run_whose_timed_path_is_broken_underneath_comes_out_not_correct(tmp_path, capsys):
    """The whole of a run but the look for a chip, over a server that answers
    every request in full and on time with the wrong tokens: the window's
    numbers are all there, and ``correct`` is false by the reference rule."""
    broken = tiny_root.build(str(tmp_path / "checkout"))
    with open(os.path.join(broken, "sitecustomize.py"), "w") as f:
        f.write(BROKEN_UNDERNEATH)
    result = run_cell(broken, "tiny-moe.closed", 2**31 + 19, 3.0, 0, "cpu", time.monotonic())
    assert result["correct"] is False and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == NAMES["tiny-moe.closed"]
    # each number compared beside its limit, as the last lines of standard error too
    last = capsys.readouterr().err.rstrip().splitlines()[-1]
    assert last.startswith("[check] reference:") and "allowed" in last
    found = re.search(r"(\d+) over 1e-02 of max\|logit\| below its best \((\d+) allowed\)", last)
    assert found and int(found.group(1)) > int(found.group(2))


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
