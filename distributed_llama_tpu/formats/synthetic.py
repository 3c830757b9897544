"""Synthetic `.m` models: random seeded weights in the real file format.

The one shared implementation behind the test suite's tiny golden models
(tests/model_utils.py re-exports these) and the load generator's
self-hosted server (``loadgen/selfhost.py``) — the analogue of the
reference's synthetic-spec
golden tests (src/llama2-tasks-test.cpp:531-565), with the xorshift weight
fill replaced by seeded numpy. Keeping it next to ModelFileWriter means the
init rules (rms weights near 1, everything else ~N(0, 1/sqrt(d_in))) and
the tensor-name layout cannot drift between consumers.
"""

from __future__ import annotations

import numpy as np

from distributed_llama_tpu.formats.model_file import (
    ArchType,
    HiddenAct,
    ModelFileWriter,
    ModelSpec,
    RopeType,
    tensor_layout,
)
from distributed_llama_tpu.quants import FloatType


def tiny_spec(**overrides) -> ModelSpec:
    """A CPU-friendly llama spec; override any field (seq_len, dims, ...)."""
    defaults = dict(
        arch_type=ArchType.LLAMA,
        dim=32,
        hidden_dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        vocab_size=64,
        seq_len=24,
        hidden_act=HiddenAct.SILU,
        rope_theta=10000.0,
        rope_type=RopeType.UNKNOWN,
        weights_float_type=FloatType.F32,
    )
    defaults.update(overrides)
    return ModelSpec(**defaults)


def random_tensors(spec: ModelSpec, seed: int = 0) -> dict[str, np.ndarray]:
    """Random weights keyed by the `.m` layout names, shaped [d_out, d_in]."""
    rng = np.random.RandomState(seed)
    out: dict[str, np.ndarray] = {}
    for e in tensor_layout(spec):
        if e.name.startswith("rms") or ".rms" in e.name:
            t = 1.0 + 0.1 * rng.randn(*e.shape)
        else:
            t = rng.randn(*e.shape) / np.sqrt(e.shape[-1])
        out[e.name] = t.astype(np.float32)
    return out


def write_model_file(path: str, spec: ModelSpec, tensors: dict[str, np.ndarray]) -> None:
    with open(path, "wb") as f:
        w = ModelFileWriter(f, spec)
        for e in w.remaining():
            w.write_tensor(tensors[e.name], e.name)


def write_synthetic_model(path: str, spec: ModelSpec, seed: int = 0) -> str:
    """One-call helper: random weights for ``spec`` written to ``path``."""
    write_model_file(path, spec, random_tensors(spec, seed=seed))
    return path


def llama2_7b_spec(n_layers: int = 32, seq_len: int = 2048) -> ModelSpec:
    """Llama-2-7B at its published widths (meta-llama/Llama-2-7b
    ``params.json``: dim 4096, 32 heads MHA, hidden 11008, vocab 32000),
    Q40 weights. Depth is the one thing a caller may cut."""
    return ModelSpec(
        arch_type=ArchType.LLAMA, dim=4096, hidden_dim=11008, n_layers=n_layers,
        n_heads=32, n_kv_heads=32, vocab_size=32000, seq_len=seq_len,
        hidden_act=HiddenAct.SILU, rope_theta=10000.0, rope_type=RopeType.LLAMA,
        weights_float_type=FloatType.Q40,
    )


def write_random_q40_model(path: str, spec: ModelSpec, seed: int = 0) -> str:
    """A full-size Q40 `.m` in seconds: every Q40 tensor is written as
    seeded random BlockQ40 records directly (uniform nibbles, per-block f16
    scales sized so the dequantized weights are ~N(0, 1/d_in) like
    :func:`random_tensors`) — quantizing billions of host floats buys
    nothing when the weights are random anyway. F32 tensors (embedding,
    norms) follow the :func:`random_tensors` rules."""
    from distributed_llama_tpu.quants import Q40_BLOCK_BYTES, QK

    rng = np.random.default_rng(seed)
    nibble_std = np.sqrt((16**2 - 1) / 12.0)  # uniform 0..15
    with open(path, "wb") as f:
        w = ModelFileWriter(f, spec)
        for e in w.remaining():
            if e.float_type == FloatType.Q40:
                n_blocks = e.n_values // QK
                blocks = rng.integers(
                    0, 256, (n_blocks, Q40_BLOCK_BYTES), dtype=np.uint8
                )
                base = 1.0 / (np.sqrt(e.shape[-1]) * nibble_std)
                scales = (base * rng.uniform(0.5, 1.5, n_blocks)).astype(np.float16)
                blocks[:, :2] = scales.view(np.uint8).reshape(n_blocks, 2)
                w.write_raw(blocks, e.name)
            elif e.name.startswith("rms") or ".rms" in e.name:
                t = 1.0 + 0.1 * rng.standard_normal(e.shape, dtype=np.float32)
                w.write_tensor(t, e.name)
            else:
                t = rng.standard_normal(e.shape, dtype=np.float32)
                w.write_tensor(t / np.float32(np.sqrt(e.shape[-1])), e.name)
        w.finish()
    return path


# the tiniest template the ChatTemplate sniffer classifies as CHATML
# (tokenizer.detect_chat_template matches on the "<|im_start|>" substring)
SYNTHETIC_CHAT_TEMPLATE = (
    "{{bos_token}}{% for m in messages %}<|im_start|>...{% endfor %}"
)


def synthetic_tokenizer_data(vocab_size: int | None = None):
    """A sentencepiece-style synthetic vocab with full byte fallback:
    <unk>/<s>/</s>, 256 byte tokens, a few merge-scored words — every
    string encodes (1 token per byte for novel text), so synthetic prompts
    need no real tokenizer. The chatml template makes it chat-servable:
    the one shared tokenizer behind the loadgen self-host server
    (loadgen/selfhost.py) and CI-scale serving smokes. ``vocab_size`` pads
    the vocab with never-merged filler pieces up to a model's width (the
    loader insists the two agree)."""
    from distributed_llama_tpu.formats.tokenizer_file import TokenizerData

    vocab: list[bytes] = [b"<unk>", b"<s>", b"</s>"]
    scores: list[float] = [0.0, 0.0, 0.0]
    for b in range(256):
        vocab.append(f"<0x{b:02X}>".encode())
        scores.append(0.0)
    for tok, score in (
        (b" ", -1.0), (b"h", -2.0), (b"e", -2.0), (b"l", -2.0),
        (b"o", -2.0), (b"he", -3.0), (b"ll", -4.0), (b"hell", -5.0),
        (b"hello", -6.0), (b" hello", -7.0), (b"w", -2.0), (b"r", -2.0),
        (b"d", -2.0), (b"wo", -3.0), (b"wor", -4.0), (b"worl", -5.0),
        (b"world", -6.5), (b" world", -7.5),
    ):
        vocab.append(tok)
        scores.append(score)
    if vocab_size is not None:
        if vocab_size < len(vocab):
            raise ValueError(f"vocab_size {vocab_size} < the {len(vocab)} base pieces")
        for i in range(len(vocab), vocab_size):
            vocab.append(f"<filler_{i}>".encode())
            scores.append(-1e9)
    return TokenizerData(
        vocab=vocab, scores=scores, bos_id=1, eos_id=2, chat_eos_id=2,
        chat_template=SYNTHETIC_CHAT_TEMPLATE,
    )
