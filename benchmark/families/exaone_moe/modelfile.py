"""K-EXAONE's model file: window and full attention layers (``layer_types``:
every ``len(sliding_window_pattern)``-th layer is full), q/k norm, leading
dense layers (``first_k_dense_replace``), and after them expert layers that
hold a share of the routed experts (``num_experts`` HELD here, the router's
width from ``reduced_from``, the first held expert's index
``first_routed_expert``) beside a shared one. The multi-token-prediction layer
is a drafting head and no part of the logits: it is not written."""

from __future__ import annotations

import numpy as np


def model_spec(config: dict, seq_len: int):
    from distributed_llama_tpu.formats import model_file
    from distributed_llama_tpu.quants import FloatType

    if not hasattr(model_file.ArchType, "EXAONE_MOE"):
        # a program from before the arch was added: stop before gigabytes are written
        raise ValueError(
            f"unknown architecture EXAONE_MOE: this program's .m format knows "
            f"{[a.name for a in model_file.ArchType]} only and cannot build or serve "
            f"configuration {config.get('name')!r}")
    ArchFlags, ArchType, HiddenAct = model_file.ArchFlags, model_file.ArchType, model_file.HiddenAct
    ModelSpec, RopeType = model_file.ModelSpec, model_file.RopeType

    depth, period = config["num_hidden_layers"], len(config["sliding_window_pattern"])
    kinds = ["full_attention" if l % period == period - 1 else "sliding_attention"
             for l in range(len(config["layer_types"]))]
    if config["sliding_window_pattern"] != "L" * (period - 1) + "G" or config["layer_types"] != kinds \
            or config["sliding_windows"] != [0 if k == "full_attention" else config["sliding_window"]
                                             for k in kinds]:
        raise ValueError("layer_types / sliding_windows are not the pattern's: window layers and "
                         "then one full layer, period after period")
    dense = config["first_k_dense_replace"]
    if config["mlp_layer_types"] != ["dense"] * dense + ["sparse"] * (len(kinds) - dense):
        raise ValueError("mlp_layer_types is not first_k_dense_replace dense layers, then sparse ones")
    if config["hidden_act"] != "silu" or config["tie_word_embeddings"] or not config["norm_topk_prob"] \
            or config["scoring_func"] != "sigmoid" or (config["n_group"], config["topk_group"]) != (1, 1) \
            or config["rope_parameters"]["rope_type"] != "default":
        raise ValueError("this family builds SiLU, an untied head, a sigmoid router without groups "
                         "whose top k is renormalised, and the default rotation")
    routed = config.get("reduced_from", {}).get("num_experts", config["num_experts"])
    if not 0 <= config["first_routed_expert"] <= routed - config["num_experts"]:
        raise ValueError("the held experts do not lie inside the router's width")
    return ModelSpec(
        arch_type=ArchType.EXAONE_MOE, dim=config["hidden_size"],
        hidden_dim=config["intermediate_size"], n_layers=depth,
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        vocab_size=config["vocab_size"], seq_len=seq_len,
        n_experts=config["num_experts"], n_active_experts=config["num_experts_per_tok"],
        hidden_act=HiddenAct.SILU, rope_theta=float(config["rope_parameters"]["rope_theta"]),
        rope_type=RopeType.FALCON, weights_float_type=FloatType.Q40,
        head_dim=config["head_dim"], moe_hidden_dim=config["moe_intermediate_size"],
        n_shared_experts=config["num_shared_experts"], n_routed_experts=routed,
        first_expert=config["first_routed_expert"],
        window=config["sliding_window"], window_period=period, first_dense=dense,
        routed_scale_milli=round(1000 * config["routed_scaling_factor"]),
        flags=int(ArchFlags.USE_ROPE | ArchFlags.ROPE_WINDOW_ONLY | ArchFlags.QK_NORM
                  | ArchFlags.NORM_TOPK | ArchFlags.SIGMOID_ROUTER),
    )


def role(name: str) -> str | None:
    """Which shared drawing rule a tensor of the file falls under; None for
    the tensors :func:`draw` draws."""
    if name == "embedding":
        return "embedding"
    if name == "wcls":
        return "head"
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("rms_att", "rms_ffn", "rms_final", "q_norm", "k_norm"):
        return "norm"
    if leaf == "router_bias":
        return None
    # the matrices that write into the residual stream
    return "residual" if leaf in ("wo", "down") else "matrix"


def draw(entry, rng: np.random.Generator) -> np.ndarray:
    """The router's selection bias: small against the sigmoid scores' spread,
    so it decides near-ties only."""
    if entry.name.rsplit(".", 1)[-1] == "router_bias":
        return (0.02 * rng.standard_normal(entry.shape)).astype(np.float32)
    raise ValueError(f"no draw for tensor {entry.name!r}")
