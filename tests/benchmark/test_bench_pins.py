"""Nothing of the yardstick moved when the families became files (PR 25):
what the parent commit produced (``data/pins_parent.json``, taken on 1712d2c
before any edit) is what this tree produces. The same seed gives the same
``.m`` and ``.t`` bytes (tiny here; both accepted configurations at full
size once by hand, ``--full`` below), the decode step's floor of bytes at
the three cells' usual rows and positions, the warm-up's requests for the
three mixes, the probes, and ``judge_probes``' note on fixed rows.

    JAX_PLATFORMS=cpu python3 tests/benchmark/test_bench_pins.py --full   # 6.4 GB of files, a minute
"""

import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if __name__ == "__main__":
    sys.path[:0] = [REPO, HERE]

import tiny_root  # noqa: E402
from benchmark import families  # noqa: E402
from benchmark.harness import cell, modelfile, traffic  # noqa: E402

with open(os.path.join(HERE, "data", "pins_parent.json")) as f:
    PINS = json.load(f)
SEED = PINS["seed"]


def load(*parts):
    with open(os.path.join(REPO, "benchmark", *parts)) as f:
        return json.load(f)


def sha256(path):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 24), b""):
            h.update(block)
    return h.hexdigest()


def files_of(config, directory, seq_len):
    model, tokenizer = modelfile.write_artifacts(config, SEED, directory, seq_len)
    out = {"m": sha256(model), "t": sha256(tokenizer), "bytes": os.path.getsize(model)}
    os.remove(model)
    os.remove(tokenizer)
    return out


@pytest.mark.parametrize("name", sorted(PINS["files_tiny"]))
def test_the_same_seed_gives_the_parents_bytes(name, tmp_path):
    assert files_of(tiny_root.CONFIGS[name], str(tmp_path), 512) == PINS["files_tiny"][name]


@pytest.mark.parametrize("workload", sorted(PINS["decode_step_bytes"]))
def test_the_decode_steps_floor_of_bytes_is_the_parents(workload):
    pin = PINS["decode_step_bytes"][workload]
    config = load("configs", f"{load('workloads', workload + '.json')['config']}.json")
    assert families.counts(config).decode_step_bytes(config, pin["rows"], pin["positions"]) == pin["bytes"]


@pytest.mark.parametrize("name", sorted(PINS["warmup"]))
def test_the_warm_up_sends_the_parents_requests(name):
    tiny = name.startswith("tiny.")
    mix = tiny_root.TRAFFIC[name[5:]] if tiny else load("traffic", f"{name}.json")
    rows = 2 if tiny else 16 if mix["loop"] == "open" else min(16, int(mix["callers"]))
    waves = traffic.warmup_waves(mix, SEED, rows, (24 if tiny else 384) * 64)
    assert [[[r.prompt_tokens, r.max_tokens, r.due_s] for r in w] for w in waves] == PINS["warmup"][name]


def rows(n, misses=(), router_gap=None):
    out = [{"server": 5, "reference": 5, "deficit": 0.0, "router_gap": router_gap} for _ in range(n)]
    for i, d in enumerate(misses):
        out[i] = {"server": 6, "reference": 5, "deficit": d, "router_gap": router_gap}
    return out


ROWS = {"dense": rows(256, [0.011] * 5 + [0.02]), "dense_far": rows(256, [0.031]),
        "moe": rows(200, [0.2], router_gap=0.3) + rows(56, [0.2] * 56, router_gap=0.019),
        "few": rows(63)}


@pytest.mark.parametrize("case", sorted(ROWS))
@pytest.mark.parametrize("config", ["mistral-7b-q40-16l", "mixtral-8x7b-q40-4l"])
def test_both_accepted_configurations_get_the_parents_verdict_and_note(config, case):
    config = load("configs", f"{config}.json")
    assert "check" not in config  # neither overrides a default
    assert list(cell.judge_probes(ROWS[case], cell.load_check(config=config))) == PINS["judge"][case]


def test_the_probes_are_the_parents():
    check = cell.load_check()
    probes = traffic.probe_requests(SEED, check["probes"], check["probe_prompt"], check["probe_tokens"])
    assert [[p.prompt_tokens, p.max_tokens,
             hashlib.sha256(json.dumps(p.body, sort_keys=True).encode()).hexdigest()] for p in probes] \
        == PINS["probes"]


def main() -> int:
    """``--full``: both accepted configurations at full size against the parent's hashes."""
    directory = os.path.join(REPO, "benchmark", ".cache", "pins")
    ok = True
    for name, pin in PINS["files_full_size"].items():
        config = load("configs", f"{name}.json")
        got = files_of(config, directory, config["max_position_embeddings"])
        ok &= got == pin
        print(f"{name}: {'the same bytes as on the parent' if got == pin else 'DIFFERENT'} {json.dumps(got)}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main() if "--full" in sys.argv else 2)
