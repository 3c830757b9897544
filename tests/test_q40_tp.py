"""Q40 under tensor parallelism: sharded packs, parity, decode loop, and
per-shard read accounting.

The reference's production configuration is exactly this — Q40 weights
sharded block-aware across nodes (reference: src/commands.cpp:22-73; every
published benchmark in README.md:100-133 is Q40 multi-node). Runs on the
virtual 8-device CPU mesh (tests/conftest.py).
"""

import numpy as np
import pytest

from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.formats.model_file import ModelFileReader
from distributed_llama_tpu.quants import FloatType

from tests.model_utils import random_tensors, tiny_spec, write_model_file

# dims satisfy the q40 TP constraint dim % (tp*32) == 0 up to tp=8
SPEC_KW = dict(
    dim=256,
    hidden_dim=512,
    n_layers=2,
    n_heads=8,
    n_kv_heads=8,
    vocab_size=512,
    seq_len=32,
    weights_float_type=FloatType.Q40,
)


@pytest.fixture(scope="module")
def q40_model(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("q40tp")
    spec = tiny_spec(**SPEC_KW)
    path = str(tmp / "m.m")
    write_model_file(path, spec, random_tensors(spec, seed=5))
    return path


@pytest.fixture(scope="module")
def dense_logits(q40_model):
    """Single-device reference: prefill logits + one decode step."""
    e = InferenceEngine(q40_model, dtype="q40")
    prefill = e.prefill([1, 2, 3, 4])
    step = e.decode_step(7)
    return prefill, step


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_q40_tp_logit_parity(q40_model, dense_logits, tp):
    """tp-sharded q40 forward matches the single-device q40 forward: the
    shards are exact byte repacks of the same quantized values, so only
    float summation order differs (psum vs in-kernel accumulation).

    Tolerance note: this tiny random-Q40 model is CHAOTIC — a measured 1e-6
    input perturbation amplifies ~18,000x through its sharp random softmaxes
    to ~2e-2 at the logits. Summation-order noise is O(1e-6), so the
    achievable bound here is ~3e-2; real sharding bugs (wrong slice, wrong
    psum) produce O(1) errors and the greedy-stream test below catches
    behavioral drift."""
    want_prefill, want_step = dense_logits
    etp = InferenceEngine(q40_model, dtype="q40", tp=tp)
    logits_tp = etp.prefill([1, 2, 3, 4])
    scale = np.abs(want_prefill).max()
    np.testing.assert_allclose(logits_tp / scale, want_prefill / scale, atol=3e-2)
    got = etp.decode_step(7)
    step_scale = np.abs(want_step).max()
    np.testing.assert_allclose(got / step_scale, want_step / step_scale, atol=3e-2)


def test_q40_tp_on_device_decode(q40_model):
    """The sharded decode loop (one dispatch, psums every step) produces the
    same greedy tokens as the single-device loop."""
    e1 = InferenceEngine(q40_model, dtype="q40")
    e1.prefill([1, 2, 3])
    want = e1.generate_on_device(4, 6, temperature=0.0)

    e4 = InferenceEngine(q40_model, dtype="q40", tp=4)
    e4.prefill([1, 2, 3])
    got = e4.generate_on_device(4, 6, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert e4.pos == e1.pos == 9


def test_sharded_load_reads_disjoint_slices(q40_model):
    """Each shard's pack is read as its own row/block slice: building shard s
    touches ~1/tp of the matrix bytes (the read-time replacement for the
    reference's root-scatter, src/transformer.cpp:432-451), and a full tp=4
    load reads the matrix region of the file only once, not 4 times."""
    r1 = ModelFileReader(q40_model)
    e = r1.entries["layers.0.q"]
    total = e.nbytes
    before = r1.bytes_read
    r1.raw_rows("layers.0.q", e.shape[0] // 4, e.shape[0] // 2)  # shard 1 of 4
    assert r1.bytes_read - before == total // 4 < total // 2
    before = r1.bytes_read
    r1.raw_row_blocks("layers.0.wo", 64, 128)  # one 1/4 column slice
    wo = r1.entries["layers.0.wo"]
    assert r1.bytes_read - before == wo.nbytes // 4 < wo.nbytes // 2
    r1.close()

    from distributed_llama_tpu.engine.weights import load_params
    from distributed_llama_tpu.models.config import config_from_spec

    ra = ModelFileReader(q40_model)
    load_params(ra, config_from_spec(ra.spec), dtype="q40", tp=1)
    dense_bytes = ra.bytes_read
    ra.close()

    rb = ModelFileReader(q40_model)
    load_params(rb, config_from_spec(rb.spec), dtype="q40", tp=4)
    sharded_bytes = rb.bytes_read
    rb.close()
    # all 4 shards together read each matrix exactly once
    assert sharded_bytes <= dense_bytes * 1.05


def test_q40_tp_divisibility_enforced(tmp_path):
    spec = tiny_spec(**{**SPEC_KW, "dim": 96, "hidden_dim": 192, "n_heads": 4,
                        "n_kv_heads": 4, "vocab_size": 128})
    path = str(tmp_path / "bad.m")
    write_model_file(path, spec, random_tensors(spec, seed=0))
    with pytest.raises(ValueError, match="divisible"):
        InferenceEngine(path, dtype="q40", tp=4)


def test_tp_loads_standard_basis_on_eligible_dims(tmp_path):
    """A TP engine at kernel-eligible widths matches the single-device
    engine. The dims keep every per-shard matrix kernel-eligible (input
    dims >= the 512 tile granule after the tp=2 split), so both run the SAME
    arithmetic: a shard that dropped to the XLA fallback would skip the Q80
    activation rounding its single-device twin applies (0.04 of drift at
    dim=512, where wo's shard is 256 wide)."""
    import numpy as np

    from tests.model_utils import random_tensors, tiny_spec, write_model_file
    from distributed_llama_tpu.engine import InferenceEngine
    from distributed_llama_tpu.quants import FloatType

    spec = tiny_spec(
        dim=1024, hidden_dim=2048, n_heads=4, n_kv_heads=4, vocab_size=96,
        seq_len=24, weights_float_type=FloatType.Q40,
    )
    path = str(tmp_path / "tp_std.m")
    write_model_file(path, spec, random_tensors(spec, seed=7))

    got = InferenceEngine(path, dtype="q40", tp=2).forward([1, 5, 9, 13])
    want = InferenceEngine(path, dtype="q40").forward([1, 5, 9, 13])
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
