"""Configuration file -> the seeded Q40 ``.m`` and ``.t`` the server loads.
The file's header and tensor order come from the package's ``ModelFileWriter``
(the format is the program's input); the WEIGHTS are the benchmark's own, so
that a later change to the package's synthetic writer cannot move them.
Imports numpy and the package's format modules only: no JAX, so the parent
may call it.

The drawing rules below are one piece of code for every family of
architectures. What a family supplies (``families/<family>/modelfile.py``):
the configuration's ``ModelSpec``, each tensor's ROLE under these rules, and
the draw of any tensor that has none (a filter, a decay, a bias). A tensor
with neither is an error, not a default.

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

from benchmark import families
from benchmark.harness.traffic import FIRST_FILLER_ID

RESIDUAL_GAIN = 0.5
# role -> gain of a Q40 matrix: "residual" are the matrices that write into the residual stream
_Q40_GAIN = {"matrix": 1.0, "head": 1.0, "residual": RESIDUAL_GAIN}
# byte -> byte with each nibble's code 0 (value -8) rewritten to code 8 (value 0)
_SYMMETRIC = np.array([(b | (0x08 if b & 0x0F == 0 else 0) | (0x80 if b & 0xF0 == 0 else 0))
                       for b in range(256)], np.uint8)
# values -7..7 once each and 0 twice, of 16 codes
_VALUE_STD = float(np.sqrt(2 * sum(v * v for v in range(1, 8)) / 16.0))


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


def write_model(path: str, config: dict, seq_len: int, seed: int,
                bench_dir: str = families.BENCH_DIR) -> str:
    """The seeded Q40 ``.m`` of ``config``: the same seed gives the same bytes.
    One generator draws the tensors in file order, each by its role."""
    from distributed_llama_tpu.formats.model_file import ModelFileWriter
    from distributed_llama_tpu.quants import FloatType

    family = families.load(config, "modelfile", bench_dir)
    rng = np.random.default_rng(seed)
    with open(path, "wb") as f:
        w = ModelFileWriter(f, family.model_spec(config, seq_len))
        for e in list(w.remaining()):
            role = family.role(e.name)
            if role in _Q40_GAIN and e.float_type == FloatType.Q40:
                blocks = q40_blocks(rng, e.n_values // 32, e.shape[-1], _Q40_GAIN[role])
                if role == "head":
                    blocks[:FIRST_FILLER_ID * (e.shape[-1] // 32), :2] = 0  # scale 0: the row is 0
                w.write_raw(blocks, e.name)
            elif role == "norm" and e.float_type == FloatType.F32:
                w.write_tensor(1.0 + 0.1 * rng.standard_normal(e.shape, dtype=np.float32), e.name)
            elif role == "embedding" and e.float_type == FloatType.F32:
                w.write_tensor(rng.standard_normal(e.shape, dtype=np.float32), e.name)
            elif role is None and hasattr(family, "draw"):
                w.write_tensor(family.draw(e, rng), e.name)
            else:
                raise families.FamilyError(
                    f"tensor {e.name!r} ({e.float_type.name}) of configuration {config.get('name')!r}: "
                    f"role {role!r} is no drawing rule for it, and family "
                    f"{families.family_of(config)!r} draws none of its own")
        w.finish()
    return path


def write_artifacts(config: dict, seed: int, directory: str, seq_len: int,
                    bench_dir: str = families.BENCH_DIR) -> tuple[str, str]:
    """Write ``<config>.m`` and ``<config>.t`` for ``seed`` into ``directory``
    (one seed's files at a time: they are gigabytes). Returns their paths."""
    from distributed_llama_tpu.formats.synthetic import synthetic_tokenizer_data
    from distributed_llama_tpu.formats.tokenizer_file import write_tokenizer_file

    os.makedirs(directory, exist_ok=True)
    model = os.path.join(directory, f"{config['name']}.m")
    tokenizer = os.path.join(directory, f"{config['name']}.t")
    with open(tokenizer, "wb") as f:
        write_tokenizer_file(f, synthetic_tokenizer_data(vocab_size=config["tokenizer_vocab"]))
    write_model(model, config, seq_len, seed, bench_dir)
    return model, tokenizer
