"""``BENCHMARK.json`` against the contract it is written to, the data files it
names, and the shape of the result line."""

import json
import os
import re

import pytest

from benchmark import families

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PUBLISHED = os.path.join(REPO, "tests", "benchmark", "data", "published")
WIDTHS = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|head|experts_per_tok")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def against_published(data: dict, directory: str = PUBLISHED) -> list[str]:
    """What is wrong with a configuration's file when it is held to its
    source's published values, ``<directory>/<name>.json``: [] if nothing.
    Every published key is in the file, equal unless ``reduced`` lists it
    (then ``reduced_from`` gives the published value); no width is reduced;
    and every number of the file is a published one, an ``assumed`` one, or
    the harness's own."""
    path = os.path.join(directory, f"{data['name']}.json")
    if not os.path.isfile(path):
        return [f"no published values on file for {data['name']!r}: {path}"]
    with open(path) as f:
        on_file = json.load(f)
    wrong = [] if on_file["source"] == data["source"] else ["the published file is of another source"]
    published, reduced = on_file["config"], data.get("reduced", [])
    wrong += [f"{k}: a width may not be reduced" for k in reduced if WIDTHS.search(k)]
    for key, value in published.items():
        if key in reduced:
            if data.get("reduced_from", {}).get(key) != value:
                wrong.append(f"{key}: reduced_from does not give the published {value!r}")
        elif key not in data or data[key] != value or type(data[key]) is not type(value):
            wrong.append(f"{key}: {data.get(key)!r} in the file, {value!r} published, not in reduced")
    wrong += [f"{k}: reduced, but not a published key" for k in reduced if k not in published]
    for key, value in data.items():
        if (isinstance(value, (int, float)) and not isinstance(value, bool) and key not in published
                and key not in data.get("assumed", {}) and key not in families.HARNESS_KEYS):
            wrong.append(f"{key}: a number that is neither published nor listed under assumed")
    return wrong


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer", "trace_in_run"}
    assert bench["trace_in_run"] is True  # the runs that measure also trace: run.py --trace 2
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16 and all(os.path.isdir(os.path.join(REPO, p)) for p in bench["paths"])
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    # a full check must fit: 2 + 14 runs a cell, run_seconds + 60 each, 180 s a cell to compile
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and len({c["file"] for c in bench["configs"]}) == len(names)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["source"].startswith("https://") and len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        data = load(c["file"])
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert data["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert set(data.get("reduced_from", {})) == set(c["reduced"])
        # the source's published values, which no cut but those of ``reduced`` may touch
        assert against_published(data) == []
        # its family is there, whole, and knows every key of the file
        assert families.counts(data) and all(families.load(data, part) for part in families.PARTS)


OTHER_WIDTHS = {"name": "other", "source": "https://example.org/other/config.json", "family": "llama",
                "d_model": 6144, "n_layer": 8, "n_head": 48, "head_dim": 128, "tokenizer_vocab": 1000,
                "reduced": ["n_layer"], "reduced_from": {"n_layer": 64},
                "assumed": {"head_dim": "d_model / n_head"}}
OTHER_PUBLISHED = {"source": OTHER_WIDTHS["source"], "config": {"d_model": 6144, "n_layer": 64, "n_head": 48}}


@pytest.mark.parametrize("case,change,published,complaint", [
    ("other widths, published values on file", {}, OTHER_PUBLISHED, None),
    ("other widths, nothing on file", {}, None, "no published values on file"),
    ("a width that differs from the published one", {"d_model": 4096}, OTHER_PUBLISHED, "d_model: 4096 in the file"),
    ("a published key left out", {"n_head": None}, OTHER_PUBLISHED, "n_head: None in the file"),
    ("a reduced key whose published value is not stated", {"reduced_from": {"n_layer": 32}}, OTHER_PUBLISHED,
     "n_layer: reduced_from does not give"),
    ("a width in reduced", {"reduced": ["n_layer", "head_dim"]}, OTHER_PUBLISHED, "head_dim: a width may not"),
    ("a number from nowhere", {"n_shared_experts": 1}, OTHER_PUBLISHED, "n_shared_experts: a number that is neither"),
    ("the file of another source", {"source": "https://example.org/sibling"}, OTHER_PUBLISHED, "another source"),
])
def test_a_configuration_is_held_to_its_published_values_whatever_its_widths(
        tmp_path, case, change, published, complaint):
    data = {k: v for k, v in {**OTHER_WIDTHS, **change}.items() if v is not None}
    if published is not None:
        (tmp_path / "other.json").write_text(json.dumps(published))
    wrong = against_published(data, str(tmp_path))
    assert (wrong == []) if complaint is None else any(complaint in w for w in wrong), f"{case}: {wrong}"


def test_workloads_and_their_files(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == len(names)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(names) // 4)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        cell = load("benchmark", "workloads", f"{w['name']}.json")
        assert {k: cell[k] for k in ("name", "config", "traffic", "chips")} == \
            {k: w[k] for k in ("name", "config", "traffic", "chips")}
        # the launch describes the deployment; the program's tunables keep their defaults
        assert not {"--decode-chunk", "--prefill-chunk", "--kv-page-size", "--admission-queue"} & set(cell["flags"])
        mix = load("benchmark", "traffic", f"{w['traffic']}.json")
        assert mix["loop"] in ("open", "closed") and len(mix["why"]) > 40
        assert ("rate_rps" in mix) == (mix["loop"] == "open")
        assert ("callers" in mix) == (mix["loop"] == "closed")


def test_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    every = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        # one reader file per quantity, found by the name up to its first dot
        data = load("benchmark", "layer_metrics", f"{m['name'].split('.')[0]}.json")
        assert set(data) == {"unit", "source", "what", "reader"}
        assert (data["unit"], data["source"]) == (m["unit"], m["source"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.add(m["layer"])
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    perf = open(os.path.join(REPO, "PERF.md")).read()
    assert all(layer in perf for layer in layers)
    readers = {f[:-5] for f in os.listdir(os.path.join(REPO, "benchmark", "layer_metrics"))}
    assert readers == {m["name"].split(".")[0] for m in bench["per_layer"]}  # none unused


def test_a_per_layer_entry_moves_a_metric_that_every_cell_of_its_list_reports(bench):
    """``moves`` names an end-to-end entry; a cell in which the per-layer entry is reported (the
    cells of its list, every cell where it has none) has to report that entry too, or the
    driver finds a line whose per-layer metric moves nothing the cell is judged on."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    stray = [(m["name"], cell, m["moves"]) for m in bench["per_layer"] for cell in m.get("workloads", cells)
             if cell not in e2e[m["moves"]].get("workloads", cells)]
    assert stray == []
    # and one form a quantity and cell: no cell is judged on a quantity as a median and as a mean
    for cell in cells:
        judged = [m["name"].split("_")[0] for m in bench["end_to_end"]
                  if cell in m.get("workloads", cells) and re.match(r"(ttft|tpot|stall)_", m["name"])]
        assert len(judged) == len(set(judged)), (cell, judged)


def test_peaks_table_has_a_source_and_the_v5e(bench):
    peaks = load("benchmark", "peaks.json")
    assert "TPU v5e" in peaks["source"]
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["bf16_flop_per_s"] == 197e12


def test_files_under_paths_are_named_from_name_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__")]
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(base, f), REPO))
