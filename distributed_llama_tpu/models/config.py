"""Static (hashable) model configuration used as a jit static argument.

Derived from the `.m` header's ModelSpec (reference: src/transformer.hpp:62-90)
but frozen, so traced functions can specialize on it.
"""

from __future__ import annotations

import dataclasses

from distributed_llama_tpu.formats.model_file import (
    ArchFlags,
    ArchType,
    HiddenAct,
    ModelSpec,
    RopeType,
    is_softmax_layer,
)


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
    # MoE prefill/dispatch capacity factor: per-expert bucket size is
    # ceil(factor * tokens * k / E) rows, overflow rows DROP (standard
    # capacity semantics — faster, but lossy under routing imbalance).
    # 0.0 (default) = exact: drop-free buckets sized for the worst case
    # (the parity-with-the-reference default); opt into e.g. 2.0 via the
    # CLI/server --moe-capacity flag for the measured prefill speedup.
    moe_capacity_factor: float = 0.0
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

    @property
    def kv_mul(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_recurrent(self) -> bool:
        """Whether some layer keeps a state that is not addressed by position."""
        return self.attn_period > 1

    def is_softmax_layer(self, l: int) -> bool:
        return is_softmax_layer(self, l)

    @property
    def softmax_layers(self) -> tuple[int, ...]:
        return tuple(l for l in range(self.n_layers) if self.is_softmax_layer(l))

    @property
    def use_rope(self) -> bool:
        return self.arch != ArchType.SOLAR_OPEN2 or self.has(ArchFlags.USE_ROPE)

    @property
    def router_sigmoid(self) -> bool:
        """Router score: sigmoid with a selection bias (else softmax)."""
        return self.has(ArchFlags.SIGMOID_ROUTER)

    @property
    def norm_topk(self) -> bool:
        """Whether the chosen experts' weights are renormalised to sum to one
        (always, for the archs that have no flag to say otherwise)."""
        return self.arch != ArchType.SOLAR_OPEN2 or self.has(ArchFlags.NORM_TOPK)

    def has(self, flag: ArchFlags) -> bool:
        return bool(self.flags & flag)

    @property
    def router_width(self) -> int:
        return self.n_routed_experts or self.n_experts


def config_from_spec(spec: ModelSpec, **overrides) -> LlamaConfig:
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
        **overrides,
    )
