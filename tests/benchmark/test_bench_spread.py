"""``benchmark/tools/spread.py`` on hand-made logs: the spread of each end-to-end metric over
the runs of one cell against half its bound, and the same for the median and the mean over
each run's requests."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
spec = importlib.util.spec_from_file_location(
    "bench_spread", os.path.join(REPO, "benchmark", "tools", "spread.py"))
spread = importlib.util.module_from_spec(spec)
spec.loader.exec_module(spread)

MANIFEST = {
    "workloads": [{"name": "a.closed"}, {"name": "b.open"}],
    "end_to_end": [
        {"name": "ttft_p50_ms", "bound": 0.04, "workloads": ["a.closed"]},
        {"name": "stall_mean_ms.batch", "bound": 0.05, "workloads": ["a.closed"]},
        {"name": "out_tok_s", "bound": 0.1},
        {"name": "setup_s", "bound": 0.1},
    ],
}


def lay(tmp_path, runs):
    """One log a run: the per-request line, then the result line as the last."""
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(MANIFEST))
    paths = []
    for i, (metrics, requests) in enumerate(runs):
        lines = ["[load] closed loop", spread.PER_REQUEST + json.dumps(requests),
                 json.dumps({"correct": True, "metrics": {k: {"value": v, "unit": "x"} for k, v in metrics.items()}})]
        (tmp_path / f"run{i}.out").write_text("\n".join(lines) + "\n")
        paths.append(str(tmp_path / f"run{i}.out"))
    return ["--workload", "a.closed", "--manifest", str(tmp_path / "BENCHMARK.json")], paths


def test_the_spread_is_the_quartiles_distance_over_the_median():
    assert spread.spread([100.0, 101.0, 102.0, 103.0, 104.0, 105.0]) == pytest.approx(3.5 / 102.5)
    assert spread.without_farthest([100.0, 101.0, 102.0, 140.0]) == [100.0, 101.0, 102.0]
    # the line it reads is the one a run logs
    with open(os.path.join(REPO, "benchmark", "harness", "cell.py")) as f:
        assert f'log("{spread.PER_REQUEST}"' in f.read()


@pytest.mark.parametrize("case,ttfts,setups,rc", [
    ("every judged pairing inside half its bound", [400, 402, 404, 406, 408, 410], [400, 200, 200, 200, 200, 200], 0),
    ("setup_s is printed and not held to the rule", [400, 402, 404, 406, 408, 410], [100, 200, 300, 400, 500, 600], 0),
    ("a judged pairing over half its bound", [400, 410, 420, 430, 440, 450], [200] * 6, 1),
])
def test_a_set_of_runs_against_the_bounds(tmp_path, capsys, case, ttfts, setups, rc):
    # per request [ttft, tpot, stall]: a run's median stall is 500 or 540 by turns, its mean 513.3 + i / 3
    runs = [({"ttft_p50_ms": t, "stall_mean_ms.batch": 500.0 + i, "out_tok_s": 850.0 + i, "setup_s": s,
              "device_idle_share": 4.0 + 3 * i},
             [[t - 300, 16.0, 480 - 20 * (i % 2)], [t, 16.5, 500 + 40 * (i % 2)], [t + 1, None, None],
              [t + 300, 17.0, 560 + i - 20 * (i % 2)]])
            for i, (t, s) in enumerate(zip(ttfts, setups))]
    args, paths = lay(tmp_path, runs)
    assert spread.main(args + paths) == rc, case
    out = capsys.readouterr().out
    assert "device_idle_share" not in out and "out_tok_s" in out  # the end-to-end entries alone
    assert ("OVER half the bound" in out) == (rc == 1)
    assert "judged by its median alone" in out
    # the forms over the requests: this cell judges stall as a mean and ttft as a median
    assert spread.main(args + ["--requests"] + paths) == rc
    rows = {line.split()[0]: line for line in capsys.readouterr().out.split("completed requests\n")[1].splitlines()}
    assert set(rows) == {f"{q}_{form}_ms" for q in spread.QUANTITIES for form in ("p50", "mean")}
    assert "(stall_mean_ms.batch): holds" in rows["stall_mean_ms"]
    assert "no judged entry of this form" in rows["stall_p50_ms"] and "no judged entry" in rows["tpot_mean_ms"]
    # a run's median over its four requests is t + 0.5; over the six runs, the middle two's
    assert f"median {(ttfts[2] + ttfts[3]) / 2 + 0.5:10.4f}" in rows["ttft_p50_ms"]
    wide = float(rows["stall_p50_ms"].split("spread")[1].split("%")[0])
    assert wide > 5 > float(rows["stall_mean_ms"].split("spread")[1].split("%")[0])


def test_a_file_without_a_result_or_an_unknown_cell_is_refused(tmp_path, capsys):
    args, paths = lay(tmp_path, [({"out_tok_s": 1.0}, [])] * 3)
    (tmp_path / "empty.out").write_text("[load] nothing came\n")
    assert spread.main(args + paths + [str(tmp_path / "empty.out")]) == 2
    assert spread.main(["--workload", "c.absent"] + args[2:] + paths) == 2
    assert "no result line" in capsys.readouterr().err
