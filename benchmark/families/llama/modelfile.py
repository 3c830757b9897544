"""The Llama lineage's model file: the dense GQA transformer (``arch``
``llama``: Mistral) and its sparse-expert sibling (``arch`` ``mixtral``),
which share every tensor but the feed-forward's."""

from __future__ import annotations

_ARCH = {"llama": "LLAMA", "mixtral": "MIXTRAL"}


def model_spec(config: dict, seq_len: int):
    from distributed_llama_tpu.formats.model_file import ArchType, HiddenAct, ModelSpec, RopeType
    from distributed_llama_tpu.quants import FloatType

    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("only silu configurations are known to this builder")
    if config.get("sliding_window") is not None or config.get("tie_word_embeddings"):
        raise ValueError("this family has no sliding window and no tied head")
    if config["arch"] not in _ARCH:
        raise ValueError(f"this family builds the arch values {sorted(_ARCH)}, not {config['arch']!r}")
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


def role(name: str) -> str | None:
    """Which shared drawing rule a tensor of the file falls under."""
    if name == "embedding":
        return "embedding"
    if name == "wcls":
        return "head"
    if "rms" in name:
        return "norm"
    # the two matrices that write into the residual stream
    return "residual" if name.endswith((".wo", ".down")) else "matrix"
