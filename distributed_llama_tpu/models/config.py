"""Static (hashable) model configuration used as a jit static argument.

Derived from the `.m` header's ModelSpec (reference: src/transformer.hpp:62-90)
but frozen, so traced functions can specialize on it.
"""

from __future__ import annotations

import dataclasses
import math

from distributed_llama_tpu.formats.model_file import (
    ArchFlags,
    STATE_MIXERS,
    ArchType,
    HiddenAct,
    ModelSpec,
    RopeType,
    layer_kind,
)

# what a window layer's ring holds beyond its window: the largest piece of a
# prompt written at once (the scheduler's prefill chunk and its bucket), and
# the positions a publish reads back out of the ring after the last piece (the
# pages before a prompt's end that a later prompt's prefix hit can end on)
RING_PIECE = 256
RING_TAIL = 512


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    arch: ArchType
    dim: int
    hidden_dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    vocab_size: int
    seq_len: int
    head_size: int
    kv_dim: int
    n_experts: int = 0
    n_active_experts: int = 0
    hidden_act: HiddenAct = HiddenAct.SILU
    rope_type: RopeType = RopeType.LLAMA
    rope_theta: float = 10000.0
    rope_scaling_factor: float = 0.0
    rope_scaling_low_freq_factor: float = 0.0
    rope_scaling_high_freq_factor: float = 0.0
    rope_scaling_orig_max_seq_len: int = 0
    # bug-for-bug compat with the reference's Llama3_1RopeCommand, which
    # applies its frequency-scaling formula to the *rotated values* instead of
    # the frequencies (reference: src/commands.cpp:224-225). Off by default:
    # the correct frequency scaling matches HF and gives the intended
    # long-context behavior.
    rope_llama3_reference_quirk: bool = False
    # the layer table and the expert share (ArchType.SOLAR_OPEN2; 0 elsewhere):
    # layer l is a softmax layer where l % attn_period == 0, else a gated
    # delta-rule layer of lin_heads x lin_head_dim; the router is
    # n_routed_experts wide and this process HOLDS experts first_expert ..
    # first_expert + n_experts - 1, each moe_hidden_dim wide
    attn_period: int = 0
    lin_heads: int = 0
    lin_head_dim: int = 0
    lin_conv: int = 0
    lin_rank: int = 0
    moe_hidden_dim: int = 0
    n_shared_experts: int = 0
    n_routed_experts: int = 0
    first_expert: int = 0
    flags: int = 0
    # window attention beside full attention (ArchType.EXAONE_MOE; 0 elsewhere):
    # layer l is a FULL layer where l % window_period == window_period - 1, else
    # its query sees the last ``window`` positions and its cache is a ring of
    # ``ring_len`` slots (position p at slot p % ring_len); the first
    # ``first_dense`` layers have a dense FFN hidden_dim wide, the expert layers
    # after them multiply the chosen experts' weights by ``routed_scale``
    window: int = 0
    window_period: int = 0
    ring_len: int = 0
    first_dense: int = 0
    routed_scale: float = 1.0
    # EVA attention (ArchType.EVABYTE; 0 elsewhere): a query reads the keys of
    # its own ALIGNED window of ``window`` positions exactly and, of every
    # earlier window, one learned summary per ``eva_chunk`` positions, in one
    # softmax. A layer's cache is one leaf a row: ``window`` slots of keys and
    # values (position p at slot p % window) and behind them ``seq_len /
    # eva_chunk`` summaries (chunk m at slot window + m)
    eva_chunk: int = 0
    # latent attention (ArchType.GLM4_MOE_LITE; 0 elsewhere): the query goes
    # through a latent of ``q_lora_rank`` values, keys and values through one
    # of ``kv_lora_rank``; a head's q and k are ``qk_nope_head_dim`` values
    # that are not rotated and ``qk_rope_head_dim`` that are (``head_size`` is
    # their sum), its value ``v_head_dim``. A layer's cache holds a position as
    # ONE row of ``latent_dim`` values: the normed latent and, behind it, the
    # rotated key slice every head shares
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # a learned sparse selection in front of the latent attention (a file of
    # that arch with the indexer's header keys; 0 elsewhere): every layer's
    # indexer scores the earlier positions for a query with ``index_n_heads``
    # heads of ``index_head_dim`` values against ONE index key a position,
    # which the layer's cache holds beside the latent row, and the attention
    # reads the ``index_topk`` positions of largest score (every visible one
    # while there are no more than that)
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # state-space layers beside softmax ones (ArchType.GRANITE_HYBRID; 0
    # elsewhere): layer l is a softmax layer where l % attn_period ==
    # attn_offset, else Mamba-2's SSD recurrence over ``ssm_heads`` heads of
    # ``ssm_head_dim`` values, each value with a state of ``ssm_state`` (ONE B
    # and C for all heads), behind a causal convolution of ``lin_conv`` taps.
    # The four multipliers: ``embed_scale`` on the embedding row,
    # ``residual_scale`` on a block's output before it joins the stream,
    # ``attn_scale`` the softmax scale where it is not head_size ** -0.5 (0:
    # it is), ``logits_divisor`` under the logits. A file of this arch whose
    # header carries the expert keys has, in every layer, a softmax router
    # over ``n_routed_experts`` with ``n_experts`` of them held and a shared
    # expert where the dense members have their SwiGLU
    attn_offset: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    attn_scale: float = 0.0
    logits_divisor: float = 1.0
    # kv heads that share one row of the cache's minor axis (1: each its own):
    # a head of 64 values fills half a lane tile, and the v5e compiler copies a
    # whole [.., 8, 64] leaf into another layout in every decode step; two such
    # heads side by side are a row of 128 that every scan and the row-bounded
    # kernel read as stored. A query head then scores the pair's row with its
    # own half and zeros in the other: the same products, and zeros
    kv_head_pack: int = 1

    @property
    def cache_kv_heads(self) -> int:
        """Rows a position has in a softmax layer's cache: the kv heads, ``kv_head_pack`` to a row."""
        return self.n_kv_heads // self.kv_head_pack

    @property
    def cache_head_size(self) -> int:
        """Values of one such row."""
        return self.head_size * self.kv_head_pack

    @property
    def softmax_scale(self) -> float:
        """What a softmax layer's scores are multiplied by: ``head_size **
        -0.5`` unless the file states another (``attn_scale``)."""
        return self.attn_scale or self.head_size ** -0.5

    @property
    def ssm_inner(self) -> int:
        """Width of a state-space layer's inner stream: heads x head values."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the state-space layer's convolution reads: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_state

    @property
    def kv_mul(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_recurrent(self) -> bool:
        """Whether some layer keeps a state that is not addressed by position:
        a period's layers other than its softmax one do (``linear`` or
        ``ssm``, by the arch)."""
        return self.attn_period > 1

    @property
    def state_mixer(self) -> str | None:
        """The kind of this arch's recurrent layers (``linear`` | ``ssm``), or None."""
        if not self.is_recurrent:
            return None
        return "ssm" if self.ssm_state else "linear"

    def layer_kind(self, l: int) -> tuple[str, str]:
        """(``full`` | ``window`` | ``linear`` | ``ssm`` | ``eva`` | ``latent``, ``dense`` | ``experts``): how
        layer ``l`` mixes positions and what its feed-forward is
        (``formats.model_file.layer_kind``, the one table)."""
        return layer_kind(self, l)

    def layers_of(self, mixer: str) -> tuple[int, ...]:
        return tuple(l for l in range(self.n_layers) if self.layer_kind(l)[0] == mixer)

    def is_softmax_layer(self, l: int) -> bool:
        return self.layer_kind(l)[0] not in STATE_MIXERS

    def is_window_layer(self, l: int | None) -> bool:
        """Whether layer ``l`` is a window layer; a caller that does not say
        which layer it runs (None) runs a full one."""
        return l is not None and self.layer_kind(l)[0] == "window"

    @property
    def has_window(self) -> bool:
        """Whether some layer keeps only the last ``window`` positions."""
        return self.window_period > 0

    @property
    def kv_read_kinds(self) -> tuple[str, ...]:
        """The kinds a batched decode step's attention counts its cache reads
        by (``ops.attention.note_kv_read``; empty: it counts none): by layer
        kind where window layers stand beside full ones, by store where the
        layers are EVA's; the few softmax layers among state-space ones as
        ``full``."""
        if self.has_window:
            return ("full", "window")
        if self.ssm_state:
            return ("full",)
        if self.has_indexer:
            # latent: the rows a layer's scan reads; index: the index keys its
            # indexer scores; latent_selected: the rows the softmax runs over;
            # dsa_visible: the positions a query could see
            return ("latent", "index", "latent_selected", "dsa_visible")
        if self.has_latent:
            return ("latent",)
        return ("eva_window", "eva_summary") if self.has_eva else ()

    @property
    def has_latent(self) -> bool:
        """Whether the layers keep one latent row a position and no key or value."""
        return self.kv_lora_rank > 0

    @property
    def has_indexer(self) -> bool:
        """Whether a latent layer's attention reads a selection of the earlier
        positions, chosen by an indexer over cached index keys."""
        return self.index_topk > 0

    @property
    def latent_dim(self) -> int:
        """Values a latent layer's cache holds of one position: the latent,
        then the shared rotated key slice (512 + 64 = 576 as published)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def rope_dim(self) -> int:
        """Values of a head that are rotated: all of them, but for a latent
        layer's ``qk_rope_head_dim``."""
        return self.qk_rope_head_dim or self.head_size

    @property
    def has_eva(self) -> bool:
        """Whether the layers keep the current window and summaries of the past."""
        return self.eva_chunk > 0

    @property
    def eva_summaries(self) -> int:
        """Summaries a row can hold: one per ``eva_chunk`` positions."""
        return self.seq_len // self.eva_chunk if self.eva_chunk else 0

    @property
    def eva_slots(self) -> int:
        """Slots of an EVA layer's leaf a row: the window, then the summaries."""
        return self.window + self.eva_summaries

    @property
    def eva_scan_chunk(self) -> int:
        """Slots one step of an EVA scan reads: the largest power of two up to
        512 that divides window and summary store alike, so that no step
        straddles the two (512 at a window of 2048 and 16384 positions)."""
        return math.gcd(512, self.window, self.eva_summaries)

    @property
    def piece_limit(self) -> int:
        """The most tokens of one row a single dispatch may write (0: any
        number): what fits a window layer's ring beside its window
        (``ring_piece``); what an EVA layer's window store takes without two
        tokens meeting in one slot, at most 256."""
        if self.has_window:
            return self.ring_piece
        return min(256, 1 << self.window.bit_length() - 1) if self.has_eva else 0

    @property
    def ring_piece(self) -> int:
        """The most tokens of one row a single dispatch may write into a ring:
        the largest power of two (a prompt piece is padded to one) that fits
        the ring beside the window its first query sees."""
        return 1 << (self.ring_len - self.window + 1).bit_length() - 1

    @property
    def rewinds_by_position(self) -> bool:
        """Whether a row can be moved back to any earlier position: not where
        some layer keeps a recurrent state, a ring, or one window and summaries
        of the rest instead of every position."""
        return not (self.is_recurrent or self.has_window or self.has_eva)

    @property
    def use_rope(self) -> bool:
        return (
            self.arch not in (ArchType.SOLAR_OPEN2, ArchType.GRANITE_HYBRID)
            or self.has(ArchFlags.USE_ROPE)
        )

    def rotates(self, l: int) -> bool:
        """Whether layer ``l`` rotates its q and k."""
        return self.use_rope and (
            not self.has(ArchFlags.ROPE_WINDOW_ONLY) or self.is_window_layer(l)
        )

    @property
    def router_sigmoid(self) -> bool:
        """Router score: sigmoid with a selection bias (else softmax)."""
        return self.has(ArchFlags.SIGMOID_ROUTER)

    @property
    def norm_topk(self) -> bool:
        """Whether the chosen experts' weights are renormalised to sum to one
        (always, for the archs that have no flag to say otherwise)."""
        return self.arch not in (
            ArchType.SOLAR_OPEN2, ArchType.EXAONE_MOE, ArchType.GLM4_MOE_LITE
        ) or self.has(
            ArchFlags.NORM_TOPK
        )

    def has(self, flag: ArchFlags) -> bool:
        return bool(self.flags & flag)

    @property
    def router_width(self) -> int:
        return self.n_routed_experts or self.n_experts


def next_pow2(n: int) -> int:
    """The smallest power of two that is at least ``n`` (1 for n <= 1)."""
    return 1 << max(0, n - 1).bit_length()


def _micro(value: int, default: float) -> float:
    """A header value in millionths (0: the header does not carry it)."""
    return value / 1e6 if value else default


def config_from_spec(spec: ModelSpec, **overrides) -> LlamaConfig:
    if spec.kv_lora_rank and spec.head_size != spec.qk_nope_head_dim + spec.qk_rope_head_dim:
        raise ValueError(
            f"a latent-attention head of {spec.head_size} values is not its "
            f"{spec.qk_nope_head_dim} unrotated and {spec.qk_rope_head_dim} rotated ones"
        )
    if spec.index_topk and not (spec.kv_lora_rank and spec.index_n_heads and spec.index_head_dim
                                and spec.qk_rope_head_dim <= spec.index_head_dim):
        raise ValueError(
            "an indexer (index_topk) stands in front of latent attention and needs its heads "
            "and a head at least as wide as the rotated slice"
        )
    if spec.eva_chunk and (spec.window % spec.eva_chunk or spec.seq_len % spec.eva_chunk):
        raise ValueError(
            f"EVA attention needs a window ({spec.window}) and a context ({spec.seq_len}) "
            f"of whole chunks of {spec.eva_chunk} positions"
        )
    if spec.window_period:
        # a ring never needs more slots than the row has positions
        overrides.setdefault(
            "ring_len", min(next_pow2(spec.window + RING_PIECE + RING_TAIL), spec.seq_len)
        )
    if spec.arch_type == ArchType.GRANITE_HYBRID:
        # heads narrower than a lane tile share one (two of 64 at the published sizes)
        overrides.setdefault("kv_head_pack", math.gcd(spec.n_kv_heads, max(1, 128 // spec.head_size)))
    return LlamaConfig(
        arch=spec.arch_type,
        dim=spec.dim,
        hidden_dim=spec.hidden_dim,
        n_layers=spec.n_layers,
        n_heads=spec.n_heads,
        n_kv_heads=spec.n_kv_heads,
        vocab_size=spec.vocab_size,
        seq_len=spec.seq_len,
        head_size=spec.head_size,
        kv_dim=spec.kv_dim,
        n_experts=spec.n_experts,
        n_active_experts=spec.n_active_experts,
        hidden_act=spec.hidden_act,
        rope_type=spec.resolved_rope_type(),
        rope_theta=spec.rope_theta,
        rope_scaling_factor=spec.rope_scaling_factor,
        rope_scaling_low_freq_factor=spec.rope_scaling_low_freq_factor,
        rope_scaling_high_freq_factor=spec.rope_scaling_high_freq_factor,
        rope_scaling_orig_max_seq_len=spec.rope_scaling_orig_max_seq_len,
        attn_period=spec.attn_period,
        lin_heads=spec.lin_heads,
        lin_head_dim=spec.lin_head_dim,
        lin_conv=spec.lin_conv,
        lin_rank=spec.lin_rank,
        moe_hidden_dim=spec.moe_hidden_dim,
        n_shared_experts=spec.n_shared_experts,
        n_routed_experts=spec.n_routed_experts,
        first_expert=spec.first_expert,
        flags=spec.flags,
        window=spec.window,
        window_period=spec.window_period,
        first_dense=spec.first_dense,
        routed_scale=spec.routed_scale_milli / 1000.0 if spec.routed_scale_milli else 1.0,
        eva_chunk=spec.eva_chunk,
        q_lora_rank=spec.q_lora_rank,
        kv_lora_rank=spec.kv_lora_rank,
        qk_nope_head_dim=spec.qk_nope_head_dim,
        qk_rope_head_dim=spec.qk_rope_head_dim,
        v_head_dim=spec.v_head_dim,
        index_n_heads=spec.index_n_heads,
        index_head_dim=spec.index_head_dim,
        index_topk=spec.index_topk,
        attn_offset=spec.attn_offset,
        ssm_heads=spec.ssm_heads,
        ssm_head_dim=spec.ssm_head_dim,
        ssm_state=spec.ssm_state,
        embed_scale=_micro(spec.embed_scale_micro, 1.0),
        residual_scale=_micro(spec.residual_scale_micro, 1.0),
        attn_scale=(spec.attn_scale_nano / 1e9 if spec.attn_scale_nano
                    else _micro(spec.attn_scale_micro, 0.0)),
        logits_divisor=_micro(spec.logits_divisor_micro, 1.0),
        **overrides,
    )
