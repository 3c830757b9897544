"""A family of architectures arrives as files. The proof: a THIRD family (a toy:
the dense block with a GELU feed-forward, ``data/toy_family/``) laid into the
miniature checkout as new files only (builder, reference, counts, a
configuration with a ``check`` block, a cell) runs through ``run_cell`` to
``correct: true``. And what a family's parts are held to: an unknown family,
a tensor no rule covers and a configuration key the counts do not know fail
by name; the kernel counts agree with arithmetic written out by hand."""

import json
import os
import shutil
import sys
import time

import pytest

import test_bench_run
import tiny_root
from benchmark import families
from benchmark.harness import cell as cell_mod
from benchmark.harness import modelfile, readers, traffic

REPO = tiny_root.REPO
TOY = os.path.join(REPO, "tests", "benchmark", "data", "toy_family")
BENCH = os.path.join(REPO, "benchmark")


def real_benchmark_files():
    out = {}
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__")]
        for f in files:
            path = os.path.join(base, f)
            out[path] = (os.path.getsize(path), os.stat(path).st_mtime_ns)
    return out


def lay_toy_family(root: str) -> None:
    """The toy family into the miniature checkout ``root``: new files and
    entries only, as a later PR would bring them."""
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(TOY, "toy_gelu"), os.path.join(bench, "families", "toy_gelu"))
    shutil.copy(os.path.join(TOY, "toy-gelu.json"), os.path.join(bench, "configs", "toy-gelu.json"))
    entry = {"name": "toy.closed", "config": "toy-gelu", "traffic": "closed", "chips": 1, "why": "rehearsal"}
    with open(os.path.join(bench, "workloads", "toy.closed.json"), "w") as f:
        json.dump({**entry, "flags": tiny_root.FLAGS}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append(entry)
    manifest["configs"].append({"name": "toy-gelu", "file": "benchmark/configs/toy-gelu.json",
                                "source": "none", "reduced": [], "why": "rehearsal"})
    for group in ("end_to_end", "per_layer"):  # it reports what the other one-chip closed loop does
        for m in manifest[group]:
            if "tiny-moe.closed" in m.get("workloads", []):
                m["workloads"].append("toy.closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


def test_a_third_family_supplied_only_as_new_files_runs_to_correct(tmp_path):
    before = real_benchmark_files()
    root = tiny_root.build(str(tmp_path / "checkout"))
    lay_toy_family(root)
    result = cell_mod.run_cell(root, "toy.closed", 2**31 + 17, 3.0, 0, "cpu", time.monotonic())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == test_bench_run.NAMES["tiny-moe.closed"]
    # its own check block was what it was judged by: 4 probes of 12 tokens
    log = open(os.path.join(root, "benchmark", ".cache", "reference.log")).read()
    assert log == "", log
    cell = cell_mod.Cell(root, "toy.closed")
    assert (cell.check["probes"], cell.check["probe_tokens"], cell.check["min_compared"]) == (4, 12, 12)
    assert cell.check["miss_tol"] == cell_mod.load_check()["miss_tol"]  # what it did not override
    # nothing of the real benchmark/ was written, and nothing of it is shadowed: the toy's parts
    # are modules of the miniature's files, the harness is still this repository's
    assert real_benchmark_files() == before
    assert not os.path.exists(os.path.join(BENCH, "families", "toy_gelu"))
    toy = families.load(cell.config, "counts", cell.dir)
    assert toy.__file__.startswith(root) and toy is cell.counts
    assert sys.modules["benchmark.families"].__file__.startswith(BENCH)
    assert sys.modules["benchmark.harness.cell"].__file__.startswith(BENCH)
    with pytest.raises(families.FamilyError, match="unknown family 'toy_gelu'"):
        families.load(cell.config, "counts")  # the real benchmark/ has no such family


LONG_MIX = {"loop": "closed", "callers": 2, "lead_in_s": 1, "block": 8,
            "user_tokens": {"dist": "uniform", "min": 4096, "max": 12288},
            "output_tokens": {"dist": "uniform", "min": 4, "max": 8},
            "context_cap": 14336, "prompt_cap": 12544, "drain_limit_s": 120, "trace_lead_in_s": 2,
            "why": "rehearsal: what a model_config PR brings for a context of 16384 positions"}


def lay_long_context_cell(root: str) -> None:
    """A cell of 12288-token prompts at ``--max-seq-len 16384`` into the
    miniature checkout ``root``: a configuration's file, a mix, a cell's file
    and their entries, new files only, as the next ``model_config`` PR brings
    them (no ``documents`` key: single requests)."""
    bench = os.path.join(root, "benchmark")
    config = {**tiny_root.CONFIGS["tiny-dense"], "name": "tiny-long", "max_position_embeddings": 16384}
    with open(os.path.join(bench, "configs", "tiny-long.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "long.json"), "w") as f:
        json.dump(LONG_MIX, f)
    entry = {"name": "tiny-long.closed", "config": "tiny-long", "traffic": "long", "chips": 1, "why": "rehearsal"}
    flags = [{"512": "16384", "24": "512"}.get(f, f) for f in tiny_root.FLAGS]
    with open(os.path.join(bench, "workloads", "tiny-long.closed.json"), "w") as f:
        json.dump({**entry, "flags": flags,
                   "check": {"why": "one probe past the 16th prefill chunk", "long_probes": 1,
                             "long_probe_prompt": 4128, "probe_tokens": 8}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append(entry)
    manifest["configs"].append({"name": "tiny-long", "file": "benchmark/configs/tiny-long.json",
                                "source": "none", "reduced": [], "why": "rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "tiny-docs.closed" in m.get("workloads", []):
                m["workloads"].append("tiny-long.closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


def test_a_cell_of_16384_positions_supplied_only_as_new_files_passes_and_runs(tmp_path, capsys):
    import test_bench_traffic

    before = real_benchmark_files()
    root = tiny_root.build(str(tmp_path / "checkout"))
    lay_long_context_cell(root)
    # what tests/benchmark holds every mix to, with the bounds of the cell that sends it
    assert test_bench_traffic.served_positions("long", root) == 16384
    gen = traffic.closed_loop_requests(LONG_MIX, 3)
    sent = [next(gen) for _ in range(16)]
    assert max(r.prompt_tokens for r in sent) > 11500  # the top stratum of 8 between 4096 and 12288
    assert test_bench_traffic.out_of_bounds(LONG_MIX, 16384, sent) == []
    assert test_bench_traffic.out_of_bounds(LONG_MIX, 2048, sent) != []
    singles = [w[0].prompt_tokens for w in traffic.warmup_waves(LONG_MIX, 1, 2, 512 * 64) if len(w) == 1]
    assert singles[5:9] == [2068, 4116, 8212, 12544]  # 196 pages: the bucket of 256 is built before the window
    cell = cell_mod.Cell(root, "tiny-long.closed")
    assert (cell.flag("--max-seq-len", 0), cell.check["long_probe_prompt"], cell.check["probe_tokens"]) == \
        (16384, 4128, 8)
    result = cell_mod.run_cell(root, "tiny-long.closed", 2**31 + 23, 15.0, 0, "cpu", time.monotonic())
    # (on a loaded machine no request may be DUE inside so short a window: the two the callers
    # start with take seconds each; `correct` says their tokens arrived and were counted)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == test_bench_run.NAMES["tiny-docs.closed"]
    said = capsys.readouterr().err
    assert "8 of the positions answered follow a prompt of 4128 tokens" in said
    assert "programs built inside the window: 0" in said
    assert real_benchmark_files() == before


TINY_DENSE = tiny_root.CONFIGS["tiny-dense"]


@pytest.mark.parametrize("case,config,match", [
    ("no family named", {k: v for k, v in TINY_DENSE.items() if k != "family"}, "names no family"),
    ("a family that is not there", {**TINY_DENSE, "family": "mamba"}, "unknown family 'mamba'"),
    ("experts spelled another way", {**TINY_DENSE, "n_routed_experts": 320},
     r"keys \['n_routed_experts'\] that the counts of family 'llama' do not know"),
])
def test_a_configuration_its_family_does_not_know_fails_by_name(case, config, match):
    with pytest.raises(families.FamilyError, match=match):
        families.counts(config)


def test_a_cell_of_such_a_configuration_is_refused_before_anything_runs(tmp_path):
    root = tiny_root.build(str(tmp_path / "checkout"))
    path = os.path.join(root, "benchmark", "configs", "tiny-dense.json")
    with open(path, "w") as f:
        json.dump({**TINY_DENSE, "n_routed_experts": 320}, f)
    with pytest.raises(cell_mod.BenchFailure, match="n_routed_experts"):
        cell_mod.Cell(root, "tiny.open")


def test_a_tensor_no_rule_covers_is_an_error_not_a_default(tmp_path):
    """The toy family without its ``draw``: ``rms_final`` has no role and
    nobody draws it."""
    root = tiny_root.build(str(tmp_path / "checkout"))
    lay_toy_family(root)
    part = os.path.join(root, "benchmark", "families", "toy_gelu", "modelfile.py")
    with open(part) as f:
        source = f.read()
    with open(part, "w") as f:
        f.write(source[:source.index("def draw(")])
    with open(os.path.join(root, "benchmark", "configs", "toy-gelu.json")) as f:
        config = json.load(f)
    with pytest.raises(families.FamilyError, match="tensor 'rms_final'.*'toy_gelu' draws none"):
        modelfile.write_model(str(tmp_path / "toy.m"), config, 512, 1, os.path.join(root, "benchmark"))


def test_a_check_block_overrides_by_name_and_carries_its_reason():
    defaults = cell_mod.load_check()
    assert (defaults["probes"], defaults["probe_prompt"], defaults["probe_tokens"],
            defaults["min_compared"]) == (8, 64, 32, 64)
    long = cell_mod.load_check(config={"name": "c", "check": {"why": "a chunked recurrence hands its state "
                                                              "on at 256 positions", "probe_prompt": 600}})
    assert long["probe_prompt"] == 600 and {k: v for k, v in long.items() if k != "probe_prompt"} == \
        {k: v for k, v in defaults.items() if k != "probe_prompt"}
    for block in ({"probe_prompt": 600}, {"why": " ", "probe_prompt": 600}, {"why": "x", "probe_len": 600}):
        with pytest.raises(cell_mod.BenchFailure, match="check block"):
            cell_mod.load_check(config={"name": "c", "check": block})
        with pytest.raises(cell_mod.BenchFailure, match="cell 'w': its check block"):
            cell_mod.load_check(launch={"name": "w", "check": block})


@pytest.mark.parametrize("config_block,cell_block,want", [
    (None, None, (64, 32, 0, 0)),
    ({"probe_prompt": 600}, None, (600, 32, 0, 0)),
    # the cell's own block over its configuration's, name by name, for that cell alone
    ({"probe_prompt": 600, "probe_tokens": 16}, {"probe_prompt": 320, "long_probes": 1, "long_probe_prompt": 4128},
     (320, 16, 1, 4128)),
    (None, {"long_probes": 2, "long_probe_prompt": 4128}, (64, 32, 2, 4128)),
])
def test_a_cells_check_block_overrides_its_configurations(config_block, cell_block, want):
    config = {"name": "c", **({"check": {"why": "the configuration's reason", **config_block}} if config_block else {})}
    launch = {"name": "w", **({"check": {"why": "the cell's reason", **cell_block}} if cell_block else {})}
    check = cell_mod.load_check(config=config, launch=launch)
    assert (check["probe_prompt"], check["probe_tokens"], check["long_probes"], check["long_probe_prompt"]) == want
    assert check["min_compared"] == int(8 * check["probe_tokens"] * 0.25)
    probes = traffic.probe_requests(5, check["probes"], check["probe_prompt"], check["probe_tokens"],
                                    check["long_probes"], check["long_probe_prompt"])
    short = traffic.probe_requests(5, check["probes"], check["probe_prompt"], check["probe_tokens"])
    n_long = check["long_probes"]
    assert [p.prompt_tokens for p in probes] == [want[0]] * (8 - n_long) + [want[3]] * n_long
    assert [p.body for p in probes[:8 - n_long]] == [p.body for p in short[:8 - n_long]]  # the short ones stay


@pytest.mark.parametrize("block,complaint", [
    ({"long_probes": 9, "long_probe_prompt": 4128}, "9 long probes"),
    ({"long_probes": 1}, "1 long probes of 0 tokens"),
    ({"long_probes": 1, "long_probe_prompt": 64}, "1 long probes of 64 tokens among 8 of 64"),
])
def test_a_long_probe_has_to_be_longer_than_the_rest_and_one_of_them(block, complaint):
    with pytest.raises(cell_mod.BenchFailure, match=complaint):
        cell_mod.load_check(launch={"name": "w", "check": {"why": "x", **block}})


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


MISTRAL, MIXTRAL = (load("benchmark", "configs", f"{n}.json")
                    for n in ("mistral-7b-q40-16l", "mixtral-8x7b-q40-4l"))


@pytest.mark.parametrize("config,role,shape,weights,rows_in,out_f32", [
    # weights x 18/32 B + activations in at 1 B + result out at 4 B, written out by hand
    (MISTRAL, "gate_up", [1, 28672], 4096 * 28672, 4096, 28672),
    (MISTRAL, "gate_up", [256, 28672], 4096 * 28672, 256 * 4096, 256 * 28672),
    (MISTRAL, "down", [1, 4096], 14336 * 4096, 14336, 4096),
    (MISTRAL, "wqkv", [1, 6144], 4096 * (32 + 8 + 8) * 128, 4096, 6144),
    (MISTRAL, "wo", [8, 4096], 4096 * 4096, 8 * 4096, 8 * 4096),
    (MISTRAL, "logits", [1, 32768], 4096 * 32000, 4096, 32768),  # the padding holds no weight
    (MIXTRAL, "experts", [16, 28672], 4096 * 28672, 16 * 4096, 16 * 28672),  # one expert's gate|up
    (MIXTRAL, "experts", [16, 4096], 14336 * 4096, 16 * 14336, 16 * 4096),  # one expert's down
])
def test_one_launch_of_each_kernel_role_by_hand(config, role, shape, weights, rows_in, out_f32):
    nbytes, operations = families.counts(config).kernel_launch(config, role, shape)
    assert nbytes == weights * 18 / 32 + rows_in + 4 * out_f32
    assert operations == 2 * shape[0] * weights


@pytest.mark.parametrize("config,role,shape", [
    (MISTRAL, "gate_up", [1, 4096]), (MISTRAL, "wo", [1, 1024]), (MISTRAL, "experts", [16, 28672]),
    (MISTRAL, "conv", [1, 4096]), (MIXTRAL, "logits", [1, 65536]),
])
def test_a_launch_the_configuration_has_no_matrix_for_is_an_error(config, role, shape):
    with pytest.raises(ValueError, match=config["name"]):
        families.counts(config).kernel_launch(config, role, shape)


def roofline(ops: dict, pattern: str, config=MISTRAL):
    import functools

    facts = {"trace.ops": ops, "peaks": load("benchmark", "peaks.json")["TPU v5 lite"],
             "model.kernel_launch": functools.partial(families.counts(config).kernel_launch, config)}
    return readers._kernel_roofline({"ops": pattern, "rate": "int8_op_per_s"}, facts)


def test_kernel_roofline_is_the_least_time_of_the_launches_over_their_device_seconds():
    dense = load("benchmark", "layer_metrics", "q40_dense_roofline.json")["reader"]
    assert dense["kind"] == "kernel_roofline"
    gate_up = (4096 * 28672 * 18 / 32 + 4096 + 4 * 28672) / 819e9  # memory-bound at one row
    down = (14336 * 4096 * 18 / 32 + 14336 + 4 * 4096) / 819e9
    ops = {"q40_int8_gate_up f32[1,28672]": {"count": 100, "seconds": 100 * gate_up / 0.8},
           "q40_int8_down f32[1,4096]": {"count": 100, "seconds": 100 * down / 0.5},
           # not decode shapes, not this reader's kernels: left out of both sums
           "q40_int8_gate_up f32[256,28672]": {"count": 7, "seconds": 1.0},
           "q40_int8_logits f32[1,32768]": {"count": 100, "seconds": 1.0},
           "fusion f32[1,28672]": {"count": 100, "seconds": 1.0}}
    want = 100.0 * (gate_up + down) / (gate_up / 0.8 + down / 0.5)
    assert roofline(ops, dense["ops"]) == pytest.approx(want)
    # compute-bound where the rows are many: 2 x rows x weights over the int8 peak
    wide = {"q40_int8_gate_up f32[256,28672]": {"count": 1, "seconds": 1e-3}}
    assert roofline(wide, r"^q40_int8_(?P<role>gate_up) ") == \
        pytest.approx(100.0 * 2 * 256 * 4096 * 28672 / 393e12 / 1e-3)
    experts = load("benchmark", "layer_metrics", "q40_experts_roofline.json")["reader"]
    one = (4096 * 28672 * 18 / 32 + 16 * 4096 + 4 * 16 * 28672) / 819e9
    assert roofline({"q40_int8_experts f32[16,28672]": {"count": 32, "seconds": 32 * one / 0.5}},
                    experts["ops"], MIXTRAL) == pytest.approx(50.0)
    # nothing to read is nothing, never 0
    assert roofline({"fusion f32[1,28672]": {"count": 1, "seconds": 1.0}}, dense["ops"]) is None
    assert roofline({}, dense["ops"]) is None


def test_the_reduction_hands_on_launches_and_seconds_of_every_op():
    from benchmark.harness import trace_reduce as tr

    ops = [[f"%q40_int8_wo.{i} = f32[1,4096]{{1,0}} custom-call(%x)", 100 * i, 40] for i in range(12)]
    ops += [[f"%fusion.{i} = f32[{i + 1},8]{{1,0}} fusion(%x)", 100 * i + 50, 10] for i in range(12)]
    red = tr.reduce({"/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: [["jit_f(1)", 0, 1200]]},
                     "_inventory": {}}, chips=1)
    assert len(red["device_ops"]) == 10 and len(red["ops"]) == 13  # the breakdown keeps its ten
    assert red["ops"]["q40_int8_wo f32[1,4096]"] == {"count": 12, "seconds": pytest.approx(480e-9)}
    assert sum(op["seconds"] for op in red["ops"].values()) == pytest.approx(red["busy_s"])


@pytest.mark.parametrize("cap,more", [(1792, []), (2068, [2068]), (7424, [2068, 4116, 7424]),
                                      (8212, [2068, 4116, 8212]), (9000, [2068, 4116, 8212, 9000])])
def test_past_16_pages_the_warm_up_goes_on_doubling_and_ends_with_the_longest_prompt(cap, more):
    mix = {**load("benchmark", "traffic", "single_stream.json"), "prompt_cap": cap, "context_cap": cap + 200}
    singles = [w[0].prompt_tokens for w in traffic.warmup_waves(mix, 1, 2, 384 * 64) if len(w) == 1]
    assert singles[:5] == [84, 148, 276, 532, 1044] and singles[5:5 + len(more)] == more
    assert singles[5 + len(more):] == [261, 269, 285, 317, 381, 509]
