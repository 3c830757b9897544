"""Configuration file -> the seeded Q40 ``.m`` and ``.t`` the server loads.
The file's header and tensor order come from the package's ``ModelFileWriter``
(the format is the program's input); the WEIGHTS are the benchmark's own, so
that a later change to the package's synthetic writer cannot move them.
Imports numpy and the package's format modules only: no JAX, so the parent
may call it.

How the weights are drawn, and why (PERF.md, PR 22, "How close"):

* every Q40 value is ``scale * v`` with ``v`` SYMMETRIC about zero: the 16
  nibble codes are -8..7, and the code for -8 is rewritten to the code for 0.
  Uniform nibbles have mean -0.5: a common negative mean in every matrix is a
  rank-one term along the all-ones direction whose gain over the random part
  is 0.5 * sqrt(d_in) / 4.6, about 7 at width 4096. It swallows the network:
  every position's state collapses onto +-ones, the logits share one offset,
  and the SIGN is decided by rounding, so the served model and a float32
  reference pick mirrored answers at isolated positions;
* per-block scales vary by +-50 % so the scale path of every kernel matters;
  a matrix's values have variance 1 / d_in, but for the two matrices that write
  into the residual stream (``wo``, ``down``), which get RESIDUAL_GAIN**2 / d_in:
  each block then adds a modest share to the stream, as in a trained model,
  and a rounding error is carried along rather than amplified layer by layer;
* the embedding is N(0, 1), so the stream starts at the size the blocks add to;
* the output rows of every token that is not a ``<filler_N>`` piece (specials,
  EOS among them, bytes, word pieces) are zero: their logit is 0 where the best
  is about 4, so a greedy answer is fillers only. Every stream then runs to
  its asked length, one delta is one token, and an answer's text says its
  token ids for certain (the server drops control bytes from the text, and a
  letter may be a piece or a byte). The rows are read and multiplied like any.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.harness.traffic import FIRST_FILLER_ID

_ARCH = {"llama": "LLAMA", "mixtral": "MIXTRAL"}
RESIDUAL_GAIN = 0.5
# byte -> byte with each nibble's code 0 (value -8) rewritten to code 8 (value 0)
_SYMMETRIC = np.array([(b | (0x08 if b & 0x0F == 0 else 0) | (0x80 if b & 0xF0 == 0 else 0))
                       for b in range(256)], np.uint8)
# values -7..7 once each and 0 twice, of 16 codes
_VALUE_STD = float(np.sqrt(2 * sum(v * v for v in range(1, 8)) / 16.0))


def model_spec(config: dict, seq_len: int):
    from distributed_llama_tpu.formats.model_file import ArchType, HiddenAct, ModelSpec, RopeType
    from distributed_llama_tpu.quants import FloatType

    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("only silu configurations are known to this builder")
    arch = ArchType[_ARCH[config["arch"]]]
    return ModelSpec(
        arch_type=arch, dim=config["hidden_size"], hidden_dim=config["intermediate_size"],
        n_layers=config["num_hidden_layers"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], vocab_size=config["vocab_size"],
        seq_len=seq_len, n_experts=config.get("num_local_experts", 0),
        n_active_experts=config.get("num_experts_per_tok", 0), hidden_act=HiddenAct.SILU,
        rope_theta=float(config["rope_theta"]),
        rope_type=RopeType.LLAMA if arch == ArchType.LLAMA else RopeType.FALCON,
        weights_float_type=FloatType.Q40,
    )


def q40_blocks(rng: np.random.Generator, n_blocks: int, d_in: int, gain: float) -> np.ndarray:
    """``n_blocks`` seeded Q40 records (f16 scale + 16 bytes of two nibbles),
    uint8 [n_blocks, 18]; the dequantized values have mean 0 and variance
    about gain**2 / d_in."""
    # 64 bits a draw: three times faster than drawing bytes
    raw = rng.integers(0, 1 << 64, (n_blocks * 18 + 7) // 8, dtype=np.uint64).view(np.uint8)
    blocks = _SYMMETRIC[raw[:n_blocks * 18].reshape(n_blocks, 18)]
    base = gain / (np.sqrt(d_in) * _VALUE_STD * np.sqrt(13.0 / 12.0))  # E[u**2] of u ~ U(0.5, 1.5)
    scales = (base * rng.uniform(0.5, 1.5, n_blocks)).astype(np.float16)
    blocks[:, :2] = scales.view(np.uint8).reshape(n_blocks, 2)
    return blocks


def write_model(path: str, spec, seed: int) -> str:
    """The seeded Q40 ``.m`` for ``spec``: the same seed gives the same bytes."""
    from distributed_llama_tpu.formats.model_file import ModelFileWriter
    from distributed_llama_tpu.quants import FloatType

    rng = np.random.default_rng(seed)
    with open(path, "wb") as f:
        w = ModelFileWriter(f, spec)
        for e in list(w.remaining()):
            if e.float_type == FloatType.Q40:
                gain = RESIDUAL_GAIN if e.name.endswith((".wo", ".down")) else 1.0
                blocks = q40_blocks(rng, e.n_values // 32, e.shape[-1], gain)
                if e.name == "wcls":
                    blocks[:FIRST_FILLER_ID * (e.shape[-1] // 32), :2] = 0  # scale 0: the row is 0
                w.write_raw(blocks, e.name)
            elif "rms" in e.name:
                w.write_tensor(1.0 + 0.1 * rng.standard_normal(e.shape, dtype=np.float32), e.name)
            else:  # the embedding
                w.write_tensor(rng.standard_normal(e.shape, dtype=np.float32), e.name)
        w.finish()
    return path


def write_artifacts(config: dict, seed: int, directory: str, seq_len: int) -> tuple[str, str]:
    """Write ``<config>.m`` and ``<config>.t`` for ``seed`` into ``directory``
    (one seed's files at a time: they are gigabytes). Returns their paths."""
    from distributed_llama_tpu.formats.synthetic import synthetic_tokenizer_data
    from distributed_llama_tpu.formats.tokenizer_file import write_tokenizer_file

    os.makedirs(directory, exist_ok=True)
    model = os.path.join(directory, f"{config['name']}.m")
    tokenizer = os.path.join(directory, f"{config['name']}.t")
    with open(tokenizer, "wb") as f:
        write_tokenizer_file(f, synthetic_tokenizer_data(vocab_size=config["tokenizer_vocab"]))
    write_model(model, model_spec(config, seq_len), seed)
    return model, tokenizer
