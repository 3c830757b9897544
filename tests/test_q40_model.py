"""End-to-end Q40 model path: a Q40 `.m` file decoded with 4-bit weights on
device must match the dequantize-to-f32 path exactly (the repack is exact and
both paths see identical dequantized values)."""

import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.quants import FloatType

from tests.model_utils import random_tensors, tiny_spec, write_model_file


def test_q40_engine_matches_f32_dequant_path(tmp_path):
    spec = tiny_spec(weights_float_type=FloatType.Q40)
    tensors = random_tensors(spec, seed=0)
    path = str(tmp_path / "model.m")
    write_model_file(path, spec, tensors)

    engine_q = InferenceEngine(path, dtype="q40")
    engine_f = InferenceEngine(path, dtype=jnp.float32)
    for pos, tok in enumerate([1, 5, 9, 13]):
        got = engine_q.decode_step(tok)
        want = engine_f.decode_step(tok)
        # same dequantized weights; differences only from bf16 activations
        # in the quantized path's non-matmul ops
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2, err_msg=f"pos {pos}")


def test_q40_generate_on_device(tmp_path):
    spec = tiny_spec(weights_float_type=FloatType.Q40)
    tensors = random_tensors(spec, seed=1)
    path = str(tmp_path / "model.m")
    write_model_file(path, spec, tensors)
    engine = InferenceEngine(path, dtype="q40")
    engine.prefill([1, 2, 3])
    tokens = engine.generate_on_device(4, 6, temperature=0.0)
    assert tokens.shape == (6,)
    assert engine.pos == 9


def _file_columns(reader, names):
    """The file's own dequantised tensors in ``x @ W`` orientation, side by
    side: what a (fused) leaf of the engine has to dequantise to."""
    return np.concatenate([reader.tensor(n).T for n in names], axis=1)


def _assert_leaf_is_the_files(reader, leaf, names):
    from distributed_llama_tpu.ops.q40 import dequantize_tpu

    np.testing.assert_array_equal(
        dequantize_tpu(leaf), _file_columns(reader, names), err_msg=str(names)
    )


def test_q40_engine_leaves_are_the_files_tensors_at_kernel_widths(tmp_path):
    """A Q40 engine at kernel-eligible widths (512 / 1024) holds leaves that
    dequantise to the file's own dequantised tensors row for row, the fused
    ``qkv`` and ``gate_up`` to the concatenation of theirs: no load-time
    permutation of rows or columns stands between the file and the kernel."""
    from distributed_llama_tpu.formats.model_file import ModelFileReader

    spec = tiny_spec(
        dim=512, hidden_dim=1024, n_heads=4, n_kv_heads=4, vocab_size=96,
        seq_len=24, weights_float_type=FloatType.Q40,
    )
    path = str(tmp_path / "wide.m")
    write_model_file(path, spec, random_tensors(spec, seed=3))

    engine = InferenceEngine(path, dtype="q40")
    reader = ModelFileReader(path)
    for l, lp in enumerate(engine.params["layers"]):
        p = f"layers.{l}."
        _assert_leaf_is_the_files(reader, lp["qkv"], [p + "q", p + "k", p + "v"])
        _assert_leaf_is_the_files(reader, lp["wo"], [p + "wo"])
        _assert_leaf_is_the_files(reader, lp["gate_up"], [p + "gate", p + "up"])
        _assert_leaf_is_the_files(reader, lp["down"], [p + "down"])
        for k in ("rms_att", "rms_ffn"):
            np.testing.assert_array_equal(np.asarray(lp[k]), reader.tensor(p + k))
    _assert_leaf_is_the_files(reader, engine.params["wcls"], ["wcls"])
    np.testing.assert_array_equal(
        np.asarray(engine.params["embedding"]), reader.tensor("embedding")
    )
    reader.close()
    assert np.all(np.isfinite(np.asarray(engine.forward([1, 5, 9, 13]))))


def test_q40_engine_leaves_are_the_files_tensors_at_kernel_widths_moe(tmp_path):
    """The same for per-expert leaves (fused gate|up + down of each of four
    experts) and the router."""
    from distributed_llama_tpu.formats.model_file import ArchType, HiddenAct, ModelFileReader

    spec = tiny_spec(
        arch_type=ArchType.MIXTRAL, n_experts=4, n_active_experts=2,
        hidden_act=HiddenAct.SILU, dim=512, hidden_dim=512, n_heads=4,
        n_kv_heads=4, vocab_size=96, seq_len=48,
        weights_float_type=FloatType.Q40,
    )
    path = str(tmp_path / "wide_moe.m")
    write_model_file(path, spec, random_tensors(spec, seed=5))

    engine = InferenceEngine(path, dtype="q40")
    reader = ModelFileReader(path)
    for l, lp in enumerate(engine.params["layers"]):
        p = f"layers.{l}."
        _assert_leaf_is_the_files(reader, lp["qkv"], [p + "q", p + "k", p + "v"])
        router = _file_columns(reader, [p + "moe_router"])  # held in bfloat16
        np.testing.assert_array_equal(
            np.asarray(lp["router"]), np.asarray(jnp.asarray(router, lp["router"].dtype))
        )
        for e, ew in enumerate(lp["experts"]):
            ep = f"{p}experts.{e}."
            _assert_leaf_is_the_files(reader, ew["gate_up"], [ep + "gate", ep + "up"])
            _assert_leaf_is_the_files(reader, ew["down"], [ep + "down"])
    reader.close()

    prompt = list(np.random.RandomState(2).randint(1, 96, 34))
    assert np.all(np.isfinite(np.asarray(engine.forward(prompt))))
