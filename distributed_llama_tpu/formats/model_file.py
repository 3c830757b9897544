"""`.m` model file format: header + flat tensor stream.

Layout (reference: src/transformer.cpp:12-148 for the reader,
converter/writer.py:109-143 for the writer):

  int32 magic = 0xA00ABCD
  int32 header_size            # bytes, including magic and this field
  (int32 key, int32 value) *   # TransformerHeaderKey pairs
  tensor bytes ...             # fixed order, see tensor_layout()

A legacy fixed-struct header (magic 0xABCD00/0xABCD01) is also supported
(reference: src/transformer.cpp:28-43).

Tensor order (reference: src/transformer.cpp:479-540 Transformer::loadRoot):

  embedding (F32) [vocab, dim]
  per layer:
    q [dim, dim], k [kv_dim, dim], v [kv_dim, dim], wo [dim, dim]
    if moe:  router [n_experts, dim];
             per expert: up [hidden, dim], gate [hidden, dim], down [dim, hidden]
    else:    gate/w1 [hidden, dim], down/w2 [dim, hidden], up/w3 [hidden, dim]
    rms_att (F32) [dim], rms_ffn (F32) [dim]
    if grok1: rms_moe (F32) [dim], rms_ffn2 (F32) [dim]
  rms_final (F32) [dim]
  wcls [vocab, dim]

``ArchType.SOLAR_OPEN2`` (no reference counterpart; :func:`_hybrid_layer`)
mixes two kinds of layer and holds a SHARE of the routed experts; its files
carry the header keys from ``HEAD_SIZE`` up, which no other arch writes:

  softmax layer (l % attn_period == 0): q, k, v, gate [H*hd, dim], wo
  linear layer: q, k, v [Hl*dl, dim], conv (F32) [3*Hl*dl, taps],
    f_down [rank, dim], f_up [Hl*dl, rank], dt_bias (F32) [Hl*dl],
    a_log (F32) [Hl], beta [Hl, dim], g_down [rank, dim], g_up [Hl*dl, rank],
    o_norm (F32) [dl], wo [dim, Hl*dl]
  every layer: moe_router [n_routed, dim], router_bias (F32) [n_routed],
    per HELD expert: up, gate [moe_hidden, dim], down [dim, moe_hidden],
    shared.up, shared.gate, shared.down (width n_shared * moe_hidden),
    rms_att, rms_ffn

``ArchType.EXAONE_MOE`` (no reference counterpart; :func:`_window_layer`)
mixes window and full attention (layer l is FULL where ``l % window_period ==
window_period - 1``), normalises every q and k head, leads with
``first_dense`` dense layers and holds a share of the routed experts after
them; its files carry the keys of ``_ARCH_KEYS[ArchType.EXAONE_MOE]``:

  every layer: rms_att, rms_ffn, q [H*hd, dim], k, v [K*hd, dim],
    q_norm (F32) [hd], k_norm (F32) [hd], wo [dim, H*hd]
  dense layer (l < first_dense): gate, down, up (width hidden_dim)
  expert layer: moe_router [n_routed, dim], router_bias (F32) [n_routed],
    per HELD expert: up, gate [moe_hidden, dim], down [dim, moe_hidden],
    shared.up, shared.gate, shared.down (width n_shared * moe_hidden)

``ArchType.EVABYTE`` (no reference counterpart; :func:`_eva_layer`) is a dense
byte-level model whose every layer mixes by EVA attention: a query reads the
keys of its own ALIGNED window of ``window`` positions exactly and of every
earlier window one learned summary per ``eva_chunk`` positions, in one
softmax. Its norms multiply by ``1 + weight`` (``ArchFlags.NORM_UNIT_OFFSET``)
and its output matrix holds ``n_pred_heads`` heads of ``vocab_size`` rows, of
which rows 0 .. vocab_size - 1 are the next-token head; its files carry the
keys of ``_ARCH_KEYS[ArchType.EVABYTE]``:

  every layer: rms_att, rms_ffn, q, k, v [H*hd, dim], eva_phi (F32) [H, hd]
    (what a chunk's keys are pooled against), eva_mu (F32) [H, hd] (added to
    the pooled key), wo, gate, down, up
  wcls [n_pred_heads * vocab_size, dim]

``ArchType.GLM4_MOE_LITE`` (no reference counterpart; :func:`_latent_layer`)
mixes by latent attention: a layer's cache holds, a position, one row of
``kv_lora_rank + qk_rope_head_dim`` values (the normed latent and ONE rotated
key slice that every head shares), no head axis and no key or value; leading
``first_dense`` dense layers, then expert layers that hold ``n_experts`` of
the router's ``n_routed_experts`` (all of them where the two are equal) beside
a shared one; its files carry the keys of ``_ARCH_KEYS[ArchType.GLM4_MOE_LITE]``:

  every layer: rms_att, rms_ffn, q_a [q_lora_rank, dim], q_a_norm (F32)
    [q_lora_rank], q_b [H*(nope+rope), q_lora_rank], kv_a [kv_lora_rank+rope,
    dim], kv_a_norm (F32) [kv_lora_rank], kv_b [H*(nope+v), kv_lora_rank] (a
    head's rows: its nope key rows, then its value rows), wo [dim, H*v]
  dense layer / expert layer: as ``ArchType.EXAONE_MOE``

A file of this arch whose header carries the optional keys ``INDEX_N_HEADS``,
``INDEX_HEAD_DIM`` and ``INDEX_TOPK`` (GLM-5, ``glm_moe_dsa``) has in EVERY
layer a learned sparse selection in front of the latent attention: an indexer
of ``index_n_heads`` heads scores every earlier position for a query and the
attention reads the ``index_topk`` best of them; the layer's cache holds, a
position, an index key of ``index_head_dim`` values beside the latent row.
Between ``wo`` and the feed-forward such a layer has four tensors more:

  index_q [index_n_heads*index_head_dim, q_lora_rank] (read by the normed
    query latent), index_k [index_head_dim, dim], index_k_norm (F32)
    [2, index_head_dim] (a LayerNorm's weight, then its bias), index_w
    [index_n_heads, dim] (the heads' weights; both read by the block's normed
    input)

``ArchType.GRANITE_HYBRID`` (no reference counterpart; :func:`_ssm_layer`)
mixes state-space layers (Mamba-2's SSD recurrence: ``ssm_heads`` heads of
``ssm_head_dim`` values, a state of ``ssm_state`` values a head value, ONE
input and one output projection of the state for all heads) with softmax
layers that do not rotate (layer l is a softmax layer where ``l % attn_period
== attn_offset``); every layer's feed-forward is a dense SwiGLU (the dense
members: ``n_experts`` 0) or, where the header carries the expert keys, a
softmax router over ``n_routed_experts`` of which this file HOLDS
``n_experts`` (``first_expert`` onwards) beside a shared expert, with no
selection bias; four multipliers (of the embedding, of every block's output
before the residual add, of the attention scores, and a divisor of the
logits) ride the header in millionths, the attention scores' in billionths
where it is no whole millionth; its files carry the keys of
``_ARCH_KEYS[ArchType.GRANITE_HYBRID]`` and, of
``_ARCH_OPTIONAL_KEYS[ArchType.GRANITE_HYBRID]``, those that are not zero:

  state-space layer: ssm_in [2*Hs*P + 2*N + Hs, dim] (rows: the gate z, then
    x|B|C which the convolution reads, then dt), conv (F32) [Hs*P + 2*N, taps],
    conv_bias (F32) [Hs*P + 2*N], dt_bias, a_log, ssm_d (F32) [Hs], ssm_norm
    (F32) [Hs*P], wo [dim, Hs*P]
  softmax layer: q [H*hd, dim], k, v [K*hd, dim], wo [dim, H*hd]
  every layer: gate, down, up (width hidden_dim) or, in a file with experts,
    moe_router [n_routed, dim], per HELD expert: up, gate [moe_hidden, dim],
    down [dim, moe_hidden], shared.up, shared.gate, shared.down (width
    n_shared * moe_hidden); then rms_att, rms_ffn

All matrices are row-major [d_out, d_in] — a matmul computes y = W @ x.
Q/K projections are stored pre-permuted for interleaved-pair rope
(reference: converter/convert-hf.py:12-15).
"""

from __future__ import annotations

import dataclasses
import enum
import struct
from typing import BinaryIO, Iterator

import numpy as np

from distributed_llama_tpu.quants import FloatType, deserialize_tensor, serialize_tensor, tensor_bytes

MAGIC_KV = 0xA00ABCD
LEGACY_MAGICS = (0xABCD00, 0xABCD01)


class ArchType(enum.IntEnum):
    """reference: src/transformer.hpp:44-48"""

    LLAMA = 0xABCD00
    GROK1 = 0xABCD01
    MIXTRAL = 0xABCD02
    # softmax and gated delta-rule layers in one model, a share of the
    # routed experts and a shared one; not a reference arch
    SOLAR_OPEN2 = 0xABCD03
    # window and full attention layers in one model, q/k norm, leading dense
    # layers, a share of the routed experts and a shared one; not a reference arch
    EXAONE_MOE = 0xABCD04
    # every layer EVA attention (exact keys inside an aligned window, learned
    # summaries of the windows before it), norms with a unit offset, several
    # prediction heads on the output matrix; not a reference arch
    EVABYTE = 0xABCD05
    # every layer latent attention (two low-rank projections with a norm inside
    # each, one rotated key slice a position for all heads, a cache of latents),
    # a leading dense layer, every routed expert held beside a shared one; not
    # a reference arch
    GLM4_MOE_LITE = 0xABCD06
    # state-space (Mamba-2 SSD) layers with a softmax layer at one index of
    # every period, no rotation, in every layer a dense SwiGLU or a share of
    # the routed experts beside a shared one, multipliers on embedding,
    # residual branches, attention scores and logits; not a reference arch
    GRANITE_HYBRID = 0xABCD07


class HiddenAct(enum.IntEnum):
    """reference: src/transformer.hpp:50-53"""

    GELU = 0
    SILU = 1


class RopeType(enum.IntEnum):
    """reference: src/transformer.hpp:55-60"""

    UNKNOWN = -1
    LLAMA = 0
    FALCON = 1
    LLAMA3_1 = 2


class HeaderKey(enum.IntEnum):
    """reference: src/transformer.hpp:10-30"""

    VERSION = 0
    ARCH_TYPE = 1
    DIM = 2
    HIDDEN_DIM = 3
    N_LAYERS = 4
    N_HEADS = 5
    N_KV_HEADS = 6
    N_EXPERTS = 7
    N_ACTIVE_EXPERTS = 8
    VOCAB_SIZE = 9
    SEQ_LEN = 10
    HIDDEN_ACT = 11
    ROPE_THETA = 12
    WEIGHTS_FLOAT_TYPE = 13
    ROPE_SCALING_FACTOR = 14
    ROPE_SCALING_LOW_FREQ_FACTOR = 15
    ROPE_SCALING_HIGH_FREQ_FACTORY = 16
    ROPE_SCALING_ORIG_MAX_SEQ_LEN = 17
    ROPE_TYPE = 18
    # from here on: written only by the archs of _ARCH_KEYS, each its own keys
    HEAD_SIZE = 19  # a head size that is not dim / n_heads
    MOE_HIDDEN_DIM = 20  # width of one expert
    N_SHARED_EXPERTS = 21
    N_ROUTED_EXPERTS = 22  # the router's published width; n_experts are HELD here
    FIRST_EXPERT = 23  # index of the first held expert
    ATTN_PERIOD = 24  # layer l is a softmax layer where l % period == 0, else linear
    LIN_HEADS = 25
    LIN_HEAD_DIM = 26
    LIN_CONV = 27  # taps of the causal depthwise convolution
    LIN_RANK = 28  # rank of the decay's and the output gate's low-rank pairs
    FLAGS = 29  # ArchFlags bits
    WINDOW = 30  # positions a window layer's query sees, itself included
    WINDOW_PERIOD = 31  # layer l is a FULL layer where l % period == period - 1, else window
    FIRST_DENSE = 32  # leading layers whose FFN is dense (hidden_dim wide); experts after them
    ROUTED_SCALE_MILLI = 33  # the chosen experts' weights are multiplied by this / 1000
    EVA_CHUNK = 34  # positions one summary of an EVA layer stands for (WINDOW: its aligned window)
    PRED_HEADS = 35  # heads of vocab_size rows on the output matrix; the first is the next token's
    Q_LORA_RANK = 36  # width of the query's latent (a latent-attention layer)
    KV_LORA_RANK = 37  # width of the keys' and values' latent: what the cache holds of a position
    QK_NOPE_HEAD_DIM = 38  # values of a q/k head that are not rotated
    QK_ROPE_HEAD_DIM = 39  # values of a q head that are; the ONE key slice of that width is cached
    V_HEAD_DIM = 40
    ATTN_OFFSET = 41  # with ATTN_PERIOD: layer l is a softmax layer where l % period == offset
    SSM_HEADS = 42  # heads of a state-space layer
    SSM_HEAD_DIM = 43  # values of one head
    SSM_STATE = 44  # state values a head value keeps; width of the shared B and C
    EMBED_SCALE_MICRO = 45  # the embedding row is multiplied by this / 1e6
    RESIDUAL_SCALE_MICRO = 46  # a block's output is, before it is added to the stream
    ATTN_SCALE_MICRO = 47  # the softmax scale, where it is not head_size ** -0.5
    LOGITS_DIVISOR_MICRO = 48  # the logits are divided by this / 1e6
    ATTN_SCALE_NANO = 49  # the softmax scale in billionths, where it is no whole millionth
    INDEX_N_HEADS = 50  # heads of a latent layer's indexer (a learned sparse selection)
    INDEX_HEAD_DIM = 51  # values of an indexer head, and of the ONE index key a position caches
    INDEX_TOPK = 52  # positions a query's attention reads: the best by the indexer's score


class ArchFlags(enum.IntFlag):
    """Bits of ``HeaderKey.FLAGS``."""

    USE_ROPE = 1  # softmax layers rotate q and k
    GQA_GATE = 2  # softmax layers gate their output per channel
    NEG_EIGVAL = 4  # beta = 2 * sigmoid(.), so a state transition may reflect
    NORM_TOPK = 8  # the chosen experts' weights are renormalised to sum to one
    SIGMOID_ROUTER = 16  # router score = sigmoid, chosen with a selection bias
    QK_NORM = 32  # every q and k head is RMS-normalised with a learned weight
    ROPE_WINDOW_ONLY = 64  # of the layers, only the window layers rotate q and k
    NORM_UNIT_OFFSET = 128  # an RMS norm multiplies by 1 + its stored weight


_EXTRA_KEYS = {
    HeaderKey.HEAD_SIZE: "head_dim",
    HeaderKey.MOE_HIDDEN_DIM: "moe_hidden_dim",
    HeaderKey.N_SHARED_EXPERTS: "n_shared_experts",
    HeaderKey.N_ROUTED_EXPERTS: "n_routed_experts",
    HeaderKey.FIRST_EXPERT: "first_expert",
    HeaderKey.ATTN_PERIOD: "attn_period",
    HeaderKey.LIN_HEADS: "lin_heads",
    HeaderKey.LIN_HEAD_DIM: "lin_head_dim",
    HeaderKey.LIN_CONV: "lin_conv",
    HeaderKey.LIN_RANK: "lin_rank",
    HeaderKey.FLAGS: "flags",
}
_WINDOW_KEYS = {
    HeaderKey.HEAD_SIZE: "head_dim",
    HeaderKey.MOE_HIDDEN_DIM: "moe_hidden_dim",
    HeaderKey.N_SHARED_EXPERTS: "n_shared_experts",
    HeaderKey.N_ROUTED_EXPERTS: "n_routed_experts",
    HeaderKey.FIRST_EXPERT: "first_expert",
    HeaderKey.FLAGS: "flags",
    HeaderKey.WINDOW: "window",
    HeaderKey.WINDOW_PERIOD: "window_period",
    HeaderKey.FIRST_DENSE: "first_dense",
    HeaderKey.ROUTED_SCALE_MILLI: "routed_scale_milli",
}
_EVA_KEYS = {
    HeaderKey.FLAGS: "flags",
    HeaderKey.WINDOW: "window",
    HeaderKey.EVA_CHUNK: "eva_chunk",
    HeaderKey.PRED_HEADS: "n_pred_heads",
}
_LATENT_KEYS = {
    HeaderKey.HEAD_SIZE: "head_dim",  # of a q/k head: nope + rope
    HeaderKey.MOE_HIDDEN_DIM: "moe_hidden_dim",
    HeaderKey.N_SHARED_EXPERTS: "n_shared_experts",
    HeaderKey.N_ROUTED_EXPERTS: "n_routed_experts",
    HeaderKey.FIRST_EXPERT: "first_expert",
    HeaderKey.FLAGS: "flags",
    HeaderKey.FIRST_DENSE: "first_dense",
    HeaderKey.ROUTED_SCALE_MILLI: "routed_scale_milli",
    HeaderKey.Q_LORA_RANK: "q_lora_rank",
    HeaderKey.KV_LORA_RANK: "kv_lora_rank",
    HeaderKey.QK_NOPE_HEAD_DIM: "qk_nope_head_dim",
    HeaderKey.QK_ROPE_HEAD_DIM: "qk_rope_head_dim",
    HeaderKey.V_HEAD_DIM: "v_head_dim",
}
_SSM_KEYS = {
    HeaderKey.ATTN_PERIOD: "attn_period",
    HeaderKey.ATTN_OFFSET: "attn_offset",
    HeaderKey.LIN_CONV: "lin_conv",
    HeaderKey.SSM_HEADS: "ssm_heads",
    HeaderKey.SSM_HEAD_DIM: "ssm_head_dim",
    HeaderKey.SSM_STATE: "ssm_state",
    HeaderKey.EMBED_SCALE_MICRO: "embed_scale_micro",
    HeaderKey.RESIDUAL_SCALE_MICRO: "residual_scale_micro",
    HeaderKey.ATTN_SCALE_MICRO: "attn_scale_micro",
    HeaderKey.LOGITS_DIVISOR_MICRO: "logits_divisor_micro",
}
# a state-space arch's expert tail (the keys the other share-holding archs' files use) and
# its softmax scale where ATTN_SCALE_MICRO cannot state it (1/128 is 7812.5 millionths)
_SSM_OPTIONAL_KEYS = {
    HeaderKey.MOE_HIDDEN_DIM: "moe_hidden_dim",
    HeaderKey.N_SHARED_EXPERTS: "n_shared_experts",
    HeaderKey.N_ROUTED_EXPERTS: "n_routed_experts",
    HeaderKey.FIRST_EXPERT: "first_expert",
    HeaderKey.ATTN_SCALE_NANO: "attn_scale_nano",
}
# a latent arch's indexer: the three keys of the learned sparse selection
_INDEX_KEYS = {
    HeaderKey.INDEX_N_HEADS: "index_n_heads",
    HeaderKey.INDEX_HEAD_DIM: "index_head_dim",
    HeaderKey.INDEX_TOPK: "index_topk",
}
# the keys past ROPE_TYPE an arch's files carry, in the order they are written;
# an arch that is not here writes none of them
_ARCH_KEYS = {ArchType.SOLAR_OPEN2: _EXTRA_KEYS, ArchType.EXAONE_MOE: _WINDOW_KEYS,
              ArchType.EVABYTE: _EVA_KEYS, ArchType.GLM4_MOE_LITE: _LATENT_KEYS,
              ArchType.GRANITE_HYBRID: _SSM_KEYS}
# ... and behind them the keys a file carries only where its value is not zero, so that a
# file that states none of them (a dense member of the arch) is byte for byte what it was
_ARCH_OPTIONAL_KEYS = {ArchType.GRANITE_HYBRID: _SSM_OPTIONAL_KEYS,
                       ArchType.GLM4_MOE_LITE: _INDEX_KEYS}


@dataclasses.dataclass
class ModelSpec:
    """Parsed model header ≈ the reference's TransformerSpec
    (reference: src/transformer.hpp:62-90)."""

    arch_type: ArchType
    dim: int
    hidden_dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    vocab_size: int
    seq_len: int
    n_experts: int = 0
    n_active_experts: int = 0
    hidden_act: HiddenAct = HiddenAct.SILU
    rope_theta: float = 10000.0
    rope_type: RopeType = RopeType.UNKNOWN
    rope_scaling_factor: float = 0.0
    rope_scaling_low_freq_factor: float = 0.0
    rope_scaling_high_freq_factor: float = 0.0
    rope_scaling_orig_max_seq_len: int = 0
    weights_float_type: FloatType = FloatType.Q40
    version: int = 0
    header_size: int = 0
    file_size: int = 0
    orig_seq_len: int = 0
    # the _EXTRA_KEYS: 0 = the header does not carry it
    head_dim: int = 0
    moe_hidden_dim: int = 0
    n_shared_experts: int = 0
    n_routed_experts: int = 0
    first_expert: int = 0
    attn_period: int = 0
    lin_heads: int = 0
    lin_head_dim: int = 0
    lin_conv: int = 0
    lin_rank: int = 0
    flags: int = 0
    window: int = 0
    window_period: int = 0
    first_dense: int = 0
    routed_scale_milli: int = 0
    eva_chunk: int = 0
    n_pred_heads: int = 0
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    attn_offset: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    embed_scale_micro: int = 0
    residual_scale_micro: int = 0
    attn_scale_micro: int = 0
    logits_divisor_micro: int = 0
    attn_scale_nano: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0

    @property
    def head_size(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    @property
    def kv_dim(self) -> int:
        # reference: src/transformer.cpp:103-104
        return self.head_size * self.n_kv_heads

    def resolved_rope_type(self) -> RopeType:
        """Default rope by arch when the header has none
        (reference: src/transformer.cpp:91-99)."""
        if self.rope_type != RopeType.UNKNOWN:
            return self.rope_type
        if self.arch_type == ArchType.LLAMA:
            return RopeType.LLAMA
        return RopeType.FALCON

    def clamp_seq_len(self, max_seq_len: int | None) -> "ModelSpec":
        """Apply the `--max-seq-len` clamp (reference: src/transformer.cpp:100-103)."""
        spec = dataclasses.replace(self)
        spec.orig_seq_len = self.seq_len if self.orig_seq_len == 0 else self.orig_seq_len
        if max_seq_len and spec.seq_len > max_seq_len:
            spec.seq_len = max_seq_len
        return spec


def _header_pairs(spec: ModelSpec) -> list[tuple[int, int]]:
    pairs = [
        (HeaderKey.VERSION, spec.version),
        (HeaderKey.ARCH_TYPE, int(spec.arch_type)),
        (HeaderKey.DIM, spec.dim),
        (HeaderKey.HIDDEN_DIM, spec.hidden_dim),
        (HeaderKey.N_LAYERS, spec.n_layers),
        (HeaderKey.N_HEADS, spec.n_heads),
        (HeaderKey.N_KV_HEADS, spec.n_kv_heads),
        (HeaderKey.N_EXPERTS, spec.n_experts),
        (HeaderKey.N_ACTIVE_EXPERTS, spec.n_active_experts),
        (HeaderKey.VOCAB_SIZE, spec.vocab_size),
        (HeaderKey.SEQ_LEN, spec.seq_len),
        (HeaderKey.HIDDEN_ACT, int(spec.hidden_act)),
        (HeaderKey.ROPE_THETA, int(spec.rope_theta)),
        (HeaderKey.WEIGHTS_FLOAT_TYPE, int(spec.weights_float_type)),
    ]
    if spec.rope_type != RopeType.UNKNOWN:
        pairs.append((HeaderKey.ROPE_TYPE, int(spec.rope_type)))
    if spec.rope_scaling_factor:
        # header values are int32 — the reference converter truncates the float
        # scaling params to int (reference: converter/convert-hf.py:190-196)
        pairs += [
            (HeaderKey.ROPE_SCALING_FACTOR, int(spec.rope_scaling_factor)),
            (HeaderKey.ROPE_SCALING_LOW_FREQ_FACTOR, int(spec.rope_scaling_low_freq_factor)),
            (HeaderKey.ROPE_SCALING_HIGH_FREQ_FACTORY, int(spec.rope_scaling_high_freq_factor)),
            (HeaderKey.ROPE_SCALING_ORIG_MAX_SEQ_LEN, spec.rope_scaling_orig_max_seq_len),
        ]
    pairs += [(key, getattr(spec, name)) for key, name in _ARCH_KEYS.get(spec.arch_type, {}).items()]
    pairs += [(key, getattr(spec, name))
              for key, name in _ARCH_OPTIONAL_KEYS.get(spec.arch_type, {}).items() if getattr(spec, name)]
    return pairs


def write_header(f: BinaryIO, spec: ModelSpec) -> int:
    """reference: converter/writer.py:109-143 (header_size = 8 + kv bytes)."""
    pairs = _header_pairs(spec)
    data = b"".join(struct.pack("<ii", int(k), int(v)) for k, v in pairs)
    header_size = 8 + len(data)
    f.write(struct.pack("<i", MAGIC_KV))
    f.write(struct.pack("<i", header_size))
    f.write(data)
    return header_size


def read_spec(path: str, weights_float_type: FloatType | None = None) -> ModelSpec:
    """Parse the `.m` header (reference: src/transformer.cpp:12-148).

    ``weights_float_type`` must be given for legacy-magic files, whose header
    has no dtype field — mirroring the reference's CLI-supplied
    `--weights-float-type` (reference: src/transformer.cpp:28-43,
    src/app.cpp:141-143)."""
    import os

    fields: dict = dict(
        hidden_act=HiddenAct.SILU,
        rope_type=RopeType.UNKNOWN,
        rope_theta=10000.0,
        n_experts=0,
        n_active_experts=0,
    )
    with open(path, "rb") as f:
        (magic,) = struct.unpack("<i", f.read(4))
        if magic in LEGACY_MAGICS:
            vals = struct.unpack("<9i", f.read(36))
            (
                fields["dim"],
                fields["hidden_dim"],
                fields["n_layers"],
                fields["n_heads"],
                fields["n_kv_heads"],
                fields["n_experts"],
                fields["n_active_experts"],
                fields["vocab_size"],
                fields["seq_len"],
            ) = vals
            fields["arch_type"] = ArchType(magic)
            fields["header_size"] = 4 + 36
            fields["weights_float_type"] = (
                None if weights_float_type is None else int(weights_float_type)
            )
        elif magic == MAGIC_KV:
            (header_size,) = struct.unpack("<i", f.read(4))
            n_ints = (header_size - 8) // 4
            raw = struct.unpack(f"<{n_ints}i", f.read(n_ints * 4))
            fields["header_size"] = header_size
            key_map = {
                HeaderKey.VERSION: "version",
                HeaderKey.ARCH_TYPE: "arch_type",
                HeaderKey.DIM: "dim",
                HeaderKey.HIDDEN_DIM: "hidden_dim",
                HeaderKey.N_LAYERS: "n_layers",
                HeaderKey.N_HEADS: "n_heads",
                HeaderKey.N_KV_HEADS: "n_kv_heads",
                HeaderKey.N_EXPERTS: "n_experts",
                HeaderKey.N_ACTIVE_EXPERTS: "n_active_experts",
                HeaderKey.VOCAB_SIZE: "vocab_size",
                HeaderKey.SEQ_LEN: "seq_len",
                HeaderKey.HIDDEN_ACT: "hidden_act",
                HeaderKey.ROPE_THETA: "rope_theta",
                HeaderKey.WEIGHTS_FLOAT_TYPE: "weights_float_type",
                HeaderKey.ROPE_SCALING_FACTOR: "rope_scaling_factor",
                HeaderKey.ROPE_SCALING_LOW_FREQ_FACTOR: "rope_scaling_low_freq_factor",
                HeaderKey.ROPE_SCALING_HIGH_FREQ_FACTORY: "rope_scaling_high_freq_factor",
                HeaderKey.ROPE_SCALING_ORIG_MAX_SEQ_LEN: "rope_scaling_orig_max_seq_len",
                HeaderKey.ROPE_TYPE: "rope_type",
                **_EXTRA_KEYS,
                **_WINDOW_KEYS,
                **_EVA_KEYS,
                **_LATENT_KEYS,
                **_SSM_KEYS,
                **_SSM_OPTIONAL_KEYS,
                **_INDEX_KEYS,
            }
            for i in range(0, n_ints, 2):
                key, value = raw[i], raw[i + 1]
                try:
                    name = key_map[HeaderKey(key)]
                except ValueError:
                    raise ValueError(f"unsupported header key: {key}") from None
                fields[name] = value
        else:
            raise ValueError(f"unsupported model file magic: {magic & 0xFFFFFFFF:#x}")
        fields["file_size"] = os.fstat(f.fileno()).st_size

    fields["arch_type"] = ArchType(fields["arch_type"])
    fields["hidden_act"] = HiddenAct(fields["hidden_act"])
    fields["rope_type"] = RopeType(fields.get("rope_type", -1))
    fields["rope_theta"] = float(fields["rope_theta"])
    if fields.get("weights_float_type") is None:
        raise ValueError("legacy header does not carry a weights float type; pass it explicitly")
    fields["weights_float_type"] = FloatType(fields["weights_float_type"])
    fields["orig_seq_len"] = fields["seq_len"]
    return ModelSpec(**fields)


@dataclasses.dataclass(frozen=True)
class TensorEntry:
    name: str
    shape: tuple[int, ...]
    float_type: FloatType
    offset: int  # absolute byte offset in file
    nbytes: int

    @property
    def n_values(self) -> int:
        return int(np.prod(self.shape))


def tensor_layout(spec: ModelSpec) -> list[TensorEntry]:
    """The fixed tensor order of the `.m` stream
    (reference: src/transformer.cpp:479-540)."""
    wt = spec.weights_float_type
    dim, hidden, kv_dim, vocab = spec.dim, spec.hidden_dim, spec.kv_dim, spec.vocab_size
    entries: list[TensorEntry] = []
    offset = spec.header_size

    def add(name: str, shape: tuple[int, ...], ft: FloatType):
        nonlocal offset
        nbytes = tensor_bytes(ft, int(np.prod(shape)))
        entries.append(TensorEntry(name, shape, ft, offset, nbytes))
        offset += nbytes

    add("embedding", (vocab, dim), FloatType.F32)
    for l in range(spec.n_layers):
        p = f"layers.{l}."
        if spec.arch_type == ArchType.SOLAR_OPEN2:
            _hybrid_layer(spec, l, add)
            continue
        if spec.arch_type == ArchType.EXAONE_MOE:
            _window_layer(spec, l, add)
            continue
        if spec.arch_type == ArchType.EVABYTE:
            _eva_layer(spec, l, add)
            continue
        if spec.arch_type == ArchType.GLM4_MOE_LITE:
            _latent_layer(spec, l, add)
            continue
        if spec.arch_type == ArchType.GRANITE_HYBRID:
            _ssm_layer(spec, l, add)
            continue
        add(p + "q", (dim, dim), wt)
        add(p + "k", (kv_dim, dim), wt)
        add(p + "v", (kv_dim, dim), wt)
        add(p + "wo", (dim, dim), wt)
        if spec.n_experts > 0:
            add(p + "moe_router", (spec.n_experts, dim), wt)
            for e in range(spec.n_experts):
                ep = f"{p}experts.{e}."
                add(ep + "up", (hidden, dim), wt)
                add(ep + "gate", (hidden, dim), wt)
                add(ep + "down", (dim, hidden), wt)
        else:
            add(p + "gate", (hidden, dim), wt)  # w1
            add(p + "down", (dim, hidden), wt)  # w2
            add(p + "up", (hidden, dim), wt)  # w3
        add(p + "rms_att", (dim,), FloatType.F32)
        add(p + "rms_ffn", (dim,), FloatType.F32)
        if spec.arch_type == ArchType.GROK1:
            add(p + "rms_moe", (dim,), FloatType.F32)
            add(p + "rms_ffn2", (dim,), FloatType.F32)
    add("rms_final", (dim,), FloatType.F32)
    add("wcls", (max(1, spec.n_pred_heads) * vocab, dim), wt)
    return entries


# the mixers whose cache is a state that is not addressed by position
STATE_MIXERS = ("linear", "ssm")


def layer_kind(spec, l: int) -> tuple[str, str]:
    """What layer ``l`` is (``spec``: a ModelSpec or a LlamaConfig), the ONE
    table of layer kinds: how it mixes positions (``full``: softmax attention
    over every earlier position; ``window``: over the last ``window``;
    ``linear``: a gated delta-rule recurrence; ``ssm``: a state-space (SSD)
    recurrence; ``eva``: exact keys inside an aligned window and summaries of
    the windows before it; ``latent``: softmax attention over every earlier
    position whose cache holds one latent row a position and no key or value)
    and what its feed-forward is (``dense`` or ``experts``). An arch without a
    period has full layers only; one with experts has them in every layer past
    ``first_dense``."""
    if spec.kv_lora_rank:
        mixer = "latent"
    elif spec.eva_chunk:
        mixer = "eva"
    elif spec.attn_period:
        # the period's one softmax layer sits at ``attn_offset``; the others keep a state
        mixer = ("full" if l % spec.attn_period == spec.attn_offset
                 else "ssm" if spec.ssm_state else "linear")
    elif spec.window_period:
        mixer = "full" if l % spec.window_period == spec.window_period - 1 else "window"
    else:
        mixer = "full"
    return mixer, "experts" if spec.n_experts > 0 and l >= spec.first_dense else "dense"


def is_softmax_layer(spec, l: int) -> bool:
    """Whether layer ``l`` mixes by softmax attention (full or window), and so
    keeps keys and values and no recurrent state."""
    return layer_kind(spec, l)[0] not in STATE_MIXERS


def _hybrid_layer(spec: ModelSpec, l: int, add) -> None:
    """One ``ArchType.SOLAR_OPEN2`` layer's tensors (the module docstring's
    list), through the layout's ``add(name, shape, float_type)``."""
    wt, f32, dim = spec.weights_float_type, FloatType.F32, spec.dim
    p = f"layers.{l}."
    if is_softmax_layer(spec, l):
        q_dim = spec.n_heads * spec.head_size
        add(p + "q", (q_dim, dim), wt)
        add(p + "k", (spec.kv_dim, dim), wt)
        add(p + "v", (spec.kv_dim, dim), wt)
        add(p + "gate", (q_dim, dim), wt)
        add(p + "wo", (dim, q_dim), wt)
    else:
        lin = spec.lin_heads * spec.lin_head_dim
        for name in ("q", "k", "v"):
            add(p + name, (lin, dim), wt)
        add(p + "conv", (3 * lin, spec.lin_conv), f32)
        add(p + "f_down", (spec.lin_rank, dim), wt)
        add(p + "f_up", (lin, spec.lin_rank), wt)
        add(p + "dt_bias", (lin,), f32)
        add(p + "a_log", (spec.lin_heads,), f32)
        add(p + "beta", (spec.lin_heads, dim), wt)
        add(p + "g_down", (spec.lin_rank, dim), wt)
        add(p + "g_up", (lin, spec.lin_rank), wt)
        add(p + "o_norm", (spec.lin_head_dim,), f32)
        add(p + "wo", (dim, lin), wt)
    _held_experts(spec, p, add)
    add(p + "rms_att", (dim,), f32)
    add(p + "rms_ffn", (dim,), f32)


def _held_experts(spec: ModelSpec, p: str, add) -> None:
    """Router, selection bias (a sigmoid router's: a softmax router chooses by
    its scores alone), the HELD experts and the shared one of an expert layer
    that holds a share (the module docstring's lists)."""
    wt, dim, width = spec.weights_float_type, spec.dim, spec.moe_hidden_dim
    add(p + "moe_router", (spec.n_routed_experts, dim), wt)
    if spec.flags & ArchFlags.SIGMOID_ROUTER:
        add(p + "router_bias", (spec.n_routed_experts,), FloatType.F32)
    for e in range(spec.n_experts):
        ep = f"{p}experts.{e}."
        add(ep + "up", (width, dim), wt)
        add(ep + "gate", (width, dim), wt)
        add(ep + "down", (dim, width), wt)
    if spec.n_shared_experts:
        shared = spec.n_shared_experts * width
        add(p + "shared.up", (shared, dim), wt)
        add(p + "shared.gate", (shared, dim), wt)
        add(p + "shared.down", (dim, shared), wt)


def _window_layer(spec: ModelSpec, l: int, add) -> None:
    """One ``ArchType.EXAONE_MOE`` layer's tensors (the module docstring's
    list). Window and full layers hold the same tensors."""
    wt, f32, dim, hidden = spec.weights_float_type, FloatType.F32, spec.dim, spec.hidden_dim
    p = f"layers.{l}."
    q_dim = spec.n_heads * spec.head_size
    add(p + "rms_att", (dim,), f32)
    add(p + "rms_ffn", (dim,), f32)
    add(p + "q", (q_dim, dim), wt)
    add(p + "k", (spec.kv_dim, dim), wt)
    add(p + "v", (spec.kv_dim, dim), wt)
    add(p + "q_norm", (spec.head_size,), f32)
    add(p + "k_norm", (spec.head_size,), f32)
    add(p + "wo", (dim, q_dim), wt)
    if layer_kind(spec, l)[1] == "dense":
        add(p + "gate", (hidden, dim), wt)
        add(p + "down", (dim, hidden), wt)
        add(p + "up", (hidden, dim), wt)
    else:
        _held_experts(spec, p, add)


def _latent_layer(spec: ModelSpec, l: int, add) -> None:
    """One ``ArchType.GLM4_MOE_LITE`` layer's tensors (the module docstring's
    list): seven of attention, the indexer's four where the file has one, then
    a dense SwiGLU or the expert layer."""
    wt, f32, dim, hidden = spec.weights_float_type, FloatType.F32, spec.dim, spec.hidden_dim
    p = f"layers.{l}."
    heads, rope = spec.n_heads, spec.qk_rope_head_dim
    add(p + "rms_att", (dim,), f32)
    add(p + "rms_ffn", (dim,), f32)
    add(p + "q_a", (spec.q_lora_rank, dim), wt)
    add(p + "q_a_norm", (spec.q_lora_rank,), f32)
    add(p + "q_b", (heads * (spec.qk_nope_head_dim + rope), spec.q_lora_rank), wt)
    add(p + "kv_a", (spec.kv_lora_rank + rope, dim), wt)
    add(p + "kv_a_norm", (spec.kv_lora_rank,), f32)
    add(p + "kv_b", (heads * (spec.qk_nope_head_dim + spec.v_head_dim), spec.kv_lora_rank), wt)
    add(p + "wo", (dim, heads * spec.v_head_dim), wt)
    if spec.index_n_heads:
        add(p + "index_q", (spec.index_n_heads * spec.index_head_dim, spec.q_lora_rank), wt)
        add(p + "index_k", (spec.index_head_dim, dim), wt)
        add(p + "index_k_norm", (2, spec.index_head_dim), f32)
        add(p + "index_w", (spec.index_n_heads, dim), wt)
    if layer_kind(spec, l)[1] == "dense":
        add(p + "gate", (hidden, dim), wt)
        add(p + "down", (dim, hidden), wt)
        add(p + "up", (hidden, dim), wt)
    else:
        _held_experts(spec, p, add)


def _ssm_layer(spec: ModelSpec, l: int, add) -> None:
    """One ``ArchType.GRANITE_HYBRID`` layer's tensors (the module docstring's
    list): a state-space or a softmax mixer, then a dense SwiGLU or, where the
    one table says ``experts``, the router, the held experts and the shared one."""
    wt, f32, dim, hidden = spec.weights_float_type, FloatType.F32, spec.dim, spec.hidden_dim
    p = f"layers.{l}."
    if is_softmax_layer(spec, l):
        q_dim = spec.n_heads * spec.head_size
        add(p + "q", (q_dim, dim), wt)
        add(p + "k", (spec.kv_dim, dim), wt)
        add(p + "v", (spec.kv_dim, dim), wt)
        add(p + "wo", (dim, q_dim), wt)
    else:
        inner = spec.ssm_heads * spec.ssm_head_dim
        conv = inner + 2 * spec.ssm_state
        add(p + "ssm_in", (inner + conv + spec.ssm_heads, dim), wt)
        add(p + "conv", (conv, spec.lin_conv), f32)
        add(p + "conv_bias", (conv,), f32)
        add(p + "dt_bias", (spec.ssm_heads,), f32)
        add(p + "a_log", (spec.ssm_heads,), f32)
        add(p + "ssm_d", (spec.ssm_heads,), f32)
        add(p + "ssm_norm", (inner,), f32)
        add(p + "wo", (dim, inner), wt)
    if layer_kind(spec, l)[1] == "experts":
        _held_experts(spec, p, add)
    else:
        add(p + "gate", (hidden, dim), wt)
        add(p + "down", (dim, hidden), wt)
        add(p + "up", (hidden, dim), wt)
    add(p + "rms_att", (dim,), f32)
    add(p + "rms_ffn", (dim,), f32)


def _eva_layer(spec: ModelSpec, l: int, add) -> None:
    """One ``ArchType.EVABYTE`` layer's tensors (the module docstring's list)."""
    wt, f32, dim, hidden = spec.weights_float_type, FloatType.F32, spec.dim, spec.hidden_dim
    p = f"layers.{l}."
    q_dim = spec.n_heads * spec.head_size
    add(p + "rms_att", (dim,), f32)
    add(p + "rms_ffn", (dim,), f32)
    add(p + "q", (q_dim, dim), wt)
    add(p + "k", (spec.kv_dim, dim), wt)
    add(p + "v", (spec.kv_dim, dim), wt)
    add(p + "eva_phi", (spec.n_kv_heads, spec.head_size), f32)
    add(p + "eva_mu", (spec.n_kv_heads, spec.head_size), f32)
    add(p + "wo", (dim, q_dim), wt)
    add(p + "gate", (hidden, dim), wt)
    add(p + "down", (dim, hidden), wt)
    add(p + "up", (hidden, dim), wt)


class ModelFileReader:
    """mmap-backed random access to the tensors of a `.m` file.

    The reference streams the file sequentially through sockets
    (reference: src/transformer.cpp:432-451); on TPU each host instead reads
    only the byte ranges of its own shards, so this reader exposes per-tensor
    (and per-row-range) random access over a single mmap.
    """

    def __init__(
        self,
        path: str,
        spec: ModelSpec | None = None,
        weights_float_type: FloatType | None = None,
    ):
        self.path = path
        self.spec = spec or read_spec(path, weights_float_type=weights_float_type)
        self.entries = {e.name: e for e in tensor_layout(self.spec)}
        last = max(self.entries.values(), key=lambda e: e.offset)
        expected = last.offset + last.nbytes
        if self.spec.file_size and expected != self.spec.file_size:
            raise ValueError(
                f"model file size mismatch: layout expects {expected} bytes, file has {self.spec.file_size}"
            )
        self._mmap = np.memmap(path, dtype=np.uint8, mode="r")
        self.bytes_read = 0  # logical bytes served (sharded-load accounting)

    def names(self) -> list[str]:
        return list(self.entries)

    def raw(self, name: str) -> np.ndarray:
        e = self.entries[name]
        self.bytes_read += e.nbytes
        return self._mmap[e.offset : e.offset + e.nbytes]

    def raw_rows(self, name: str, row_start: int, row_end: int) -> np.ndarray:
        """Raw bytes of a contiguous row (output-dim) range — the exact-repack
        shard read for output-sharded Q40 matrices (the read-time analogue of
        RowMatmulSlice, reference: src/commands.cpp:22-43)."""
        e = self.entries[name]
        n = e.shape[1]
        row_bytes = tensor_bytes(e.float_type, n)
        start = e.offset + row_start * row_bytes
        nbytes = (row_end - row_start) * row_bytes
        self.bytes_read += nbytes
        return self._mmap[start : start + nbytes]

    def raw_row_blocks(self, name: str, col_start: int, col_end: int) -> np.ndarray:
        """Raw bytes of a column (input-dim) range of every row, sliced on
        quant-block boundaries — the shard read for input-sharded Q40
        matrices (ColMatmulSlice applied at read time, reference:
        src/commands.cpp:57-73). Returns [d_out, col_bytes] bytes."""
        from distributed_llama_tpu.quants import QK

        e = self.entries[name]
        d_out, d_in = e.shape
        if col_start % QK or col_end % QK:
            raise ValueError(f"column range ({col_start},{col_end}) not {QK}-aligned")
        row_bytes = tensor_bytes(e.float_type, d_in)
        lo = tensor_bytes(e.float_type, col_start)
        hi = tensor_bytes(e.float_type, col_end)
        rows = self._mmap[e.offset : e.offset + e.nbytes].reshape(d_out, row_bytes)
        out = np.ascontiguousarray(rows[:, lo:hi])
        self.bytes_read += out.nbytes
        return out

    def tensor(self, name: str) -> np.ndarray:
        """Dequantized float32 tensor in its logical shape."""
        e = self.entries[name]
        flat = deserialize_tensor(self.raw(name), e.float_type, e.n_values)
        return flat.reshape(e.shape)

    def tensor_rows(self, name: str, row_start: int, row_end: int) -> np.ndarray:
        """Read a contiguous row range without touching the rest of the tensor.

        This is the sharded-load path: the byte math mirrors the reference's
        RowMatmulSlice offset computation (reference: src/commands.cpp:22-43)
        but is applied at read time on each host instead of at scatter time on
        a root node.
        """
        e = self.entries[name]
        if len(e.shape) != 2:
            raise ValueError(f"tensor_rows on non-matrix {name}")
        n = e.shape[1]
        row_bytes = tensor_bytes(e.float_type, n)
        start = e.offset + row_start * row_bytes
        nrows = row_end - row_start
        buf = self._mmap[start : start + nrows * row_bytes]
        self.bytes_read += nrows * row_bytes
        flat = deserialize_tensor(buf, e.float_type, nrows * n)
        return flat.reshape(nrows, n)

    def tensor_cols(self, name: str, col_start: int, col_end: int) -> np.ndarray:
        """Read a column (input-dim) range of every row — the input-sharded
        analogue of :meth:`tensor_rows` (ColMatmulSlice applied at read
        time). Works for every on-disk dtype: block formats (Q40/Q80) slice
        on quant-block boundaries via :meth:`raw_row_blocks` when the range
        is aligned, else fall back to decoding whole rows (correct, just
        full-row file traffic — counted honestly in ``bytes_read``).
        Returns f32 [d_out, cols]."""
        from distributed_llama_tpu.quants import QK

        e = self.entries[name]
        if len(e.shape) != 2:
            raise ValueError(f"tensor_cols on non-matrix {name}")
        d_out, d_in = e.shape
        ncols = col_end - col_start
        if e.float_type in (FloatType.Q40, FloatType.Q80):
            if col_start % QK == 0 and col_end % QK == 0:
                buf = self.raw_row_blocks(name, col_start, col_end)
                flat = deserialize_tensor(buf.reshape(-1), e.float_type, d_out * ncols)
                return flat.reshape(d_out, ncols)
            return self.tensor(name)[:, col_start:col_end]
        row_bytes = tensor_bytes(e.float_type, d_in)
        lo = tensor_bytes(e.float_type, col_start)
        hi = tensor_bytes(e.float_type, col_end)
        rows = self._mmap[e.offset : e.offset + e.nbytes].reshape(d_out, row_bytes)
        buf = np.ascontiguousarray(rows[:, lo:hi])
        self.bytes_read += buf.nbytes
        flat = deserialize_tensor(buf.reshape(-1), e.float_type, d_out * ncols)
        return flat.reshape(d_out, ncols)

    def close(self):
        del self._mmap


class ModelFileWriter:
    """Sequential `.m` writer used by the converter toolchain
    (reference: converter/writer.py)."""

    def __init__(self, f: BinaryIO, spec: ModelSpec):
        self.f = f
        self.spec = spec
        self.header_size = write_header(f, spec)
        self._layout = tensor_layout(
            dataclasses.replace(spec, header_size=self.header_size)
        )
        self._next = 0

    def write_tensor(self, array: np.ndarray, name: str | None = None) -> TensorEntry:
        """Write the next tensor in layout order; `name` is checked if given."""
        entry = self._layout[self._next]
        if name is not None and name != entry.name:
            raise ValueError(f"expected tensor {entry.name!r}, got {name!r}")
        if tuple(array.shape) != entry.shape and array.size != entry.n_values:
            raise ValueError(
                f"tensor {entry.name}: shape {array.shape} incompatible with {entry.shape}"
            )
        self.f.write(serialize_tensor(array, entry.float_type))
        self._next += 1
        return entry

    def write_raw(self, buf: bytes | np.ndarray, name: str | None = None) -> TensorEntry:
        """Write the next tensor from bytes ALREADY in its file encoding
        (e.g. BlockQ40 records) — no float round trip."""
        entry = self._layout[self._next]
        if name is not None and name != entry.name:
            raise ValueError(f"expected tensor {entry.name!r}, got {name!r}")
        view = memoryview(buf).cast("B")
        if view.nbytes != entry.nbytes:
            raise ValueError(
                f"tensor {entry.name}: {view.nbytes} raw bytes, layout wants {entry.nbytes}"
            )
        self.f.write(view)
        self._next += 1
        return entry

    def expected(self) -> TensorEntry:
        return self._layout[self._next]

    def remaining(self) -> Iterator[TensorEntry]:
        return iter(self._layout[self._next :])

    def finish(self):
        if self._next != len(self._layout):
            missing = [e.name for e in self._layout[self._next :]]
            raise ValueError(f"model file incomplete, missing tensors: {missing[:5]}...")
