"""The plain reference against the program's engine at a tiny dense-GQA and a
tiny 8-expert size: logits, not tokens (with random weights the largest logit
changes on rounding)."""

import os

import numpy as np
import pytest

import tiny_root
from benchmark import families
from benchmark.harness import modelfile
from benchmark.harness.cell import load_check
from benchmark.reference.qfile import QFile

LOGIT_TOL = load_check()["logit_tol"]

TOKENS = [1, 259, 300, 17, 1999, 42, 1033, 5, 77, 612, 800, 9, 1500]


@pytest.fixture(scope="module", params=sorted(tiny_root.CONFIGS))
def artifacts(request, tmp_path_factory):
    config = tiny_root.CONFIGS[request.param]
    directory = str(tmp_path_factory.mktemp(request.param))
    model, _ = modelfile.write_artifacts(config, 2**31 + 3, directory, 512)
    return config, model


def reference_logits(config, model):
    ref = families.load(config, "reference")
    return ref.forward(QFile(model, ref), np.asarray([TOKENS], np.int32), np.arange(len(TOKENS)))[0]


def test_header_and_layout_read_back(artifacts):
    config, model = artifacts
    qf = QFile(model, families.load(config, "reference"))
    assert qf.h["dim"] == config["hidden_size"] and qf.h["n_layers"] == config["num_hidden_layers"]
    assert qf.h["n_experts"] == config.get("num_local_experts", 0)
    assert qf.h["rope_theta"] == 1000000 and qf.h["seq_len"] == 512
    assert qf.raw("wcls").shape == (config["vocab_size"], config["hidden_size"] // 32, 18)


def test_dequant_matches_the_block_format():
    from benchmark.reference.ops import dequant

    raw = np.zeros((1, 1, 18), np.uint8)
    raw[0, 0, :2] = np.frombuffer(np.float16(0.5).tobytes(), np.uint8)
    raw[0, 0, 2:] = np.arange(16, dtype=np.uint8) | (np.arange(15, -1, -1, dtype=np.uint8) << 4)
    want = 0.5 * (np.concatenate([np.arange(16), np.arange(15, -1, -1)]) - 8)
    assert np.array_equal(np.asarray(dequant(raw))[0], want.astype(np.float32))


def test_last_position_logits_match_the_engine_in_float32(artifacts):
    """The engine at --dtype f32 dequantizes the same blocks: what is left is
    float32 rounding, so the tolerance is float32's (1e-4 of max|logit|), tight
    enough that a wrong rope pairing, head grouping or expert mix fails."""
    import jax.numpy as jnp

    from distributed_llama_tpu.engine import InferenceEngine

    config, model = artifacts
    engine = InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32)
    got = np.asarray(engine.prefill(TOKENS), np.float32)
    want = reference_logits(config, model)[-1]
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_q40_engine_stays_inside_the_benchmarks_logit_tolerance(artifacts):
    from distributed_llama_tpu.engine import InferenceEngine

    config, model = artifacts
    got = np.asarray(InferenceEngine(model, dtype="q40").prefill(TOKENS), np.float32)
    want = reference_logits(config, model)[-1]
    assert np.abs(got - want).max() <= LOGIT_TOL * np.abs(want).max()


def test_reference_is_causal(artifacts):
    config, model = artifacts
    forward = families.load(config, "reference").forward
    qf = QFile(model, families.load(config, "reference"))
    full = forward(qf, np.asarray([TOKENS], np.int32), np.asarray([4]))
    cut = forward(qf, np.asarray([TOKENS[:5] + [0] * 8], np.int32), np.asarray([4]))
    assert np.allclose(full, cut, atol=1e-5)


def test_a_truncated_file_is_refused(artifacts, tmp_path):
    config, model = artifacts
    short = tmp_path / "short.m"
    short.write_bytes(open(model, "rb").read()[:-18])
    with pytest.raises(ValueError):
        QFile(str(short), families.load(config, "reference"))
    assert os.path.getsize(model) > 0
