"""GLM-4.7-Flash's model file (``model_type`` ``glm4_moe_lite``): every layer
latent attention (a low-rank query projection and a low-rank key/value
projection with an RMS norm inside each, ``qk_rope_head_dim`` rotated values a
head beside ``qk_nope_head_dim`` that are not, ONE rotated key slice a position
for all heads), ``first_k_dense_replace`` leading dense layers, and after them
expert layers: a sigmoid router over ``n_routed_experts`` with a selection
bias, the top ``num_experts_per_tok`` renormalised and scaled, every routed
expert written (or, with ``reduced_from`` / ``first_routed_expert``, a share),
beside a shared one. The multi-token-prediction layer is a drafting head and
no part of the logits: it is not written."""

from __future__ import annotations

import numpy as np


def model_spec(config: dict, seq_len: int):
    from distributed_llama_tpu.formats import model_file
    from distributed_llama_tpu.quants import FloatType

    if not hasattr(model_file.ArchType, "GLM4_MOE_LITE"):
        # a program from before the arch was added: stop before gigabytes are written
        raise ValueError(
            f"unknown architecture GLM4_MOE_LITE: this program's .m format knows "
            f"{[a.name for a in model_file.ArchType]} only and cannot build or serve "
            f"configuration {config.get('name')!r}")
    ArchFlags, ArchType, HiddenAct = model_file.ArchFlags, model_file.ArchType, model_file.HiddenAct
    ModelSpec, RopeType = model_file.ModelSpec, model_file.RopeType

    if config["hidden_act"] != "silu" or config["tie_word_embeddings"] or not config["norm_topk_prob"] \
            or config["topk_method"] != "noaux_tc" or (config["n_group"], config["topk_group"]) != (1, 1) \
            or config["rope_scaling"] is not None or config["partial_rotary_factor"] != 1 \
            or config["attention_bias"]:
        raise ValueError("this family builds SiLU, an untied head, projections without bias, a "
                         "noaux_tc router without groups whose top k is renormalised, and the "
                         "plain rotation of the whole rope slice")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention expands one key and one value a head")
    routed = config.get("reduced_from", {}).get("n_routed_experts", config["n_routed_experts"])
    first = config.get("first_routed_expert", 0)
    if not 0 <= first <= routed - config["n_routed_experts"]:
        raise ValueError("the held experts do not lie inside the router's width")
    return ModelSpec(
        arch_type=ArchType.GLM4_MOE_LITE, dim=config["hidden_size"],
        hidden_dim=config["intermediate_size"], n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        vocab_size=config["vocab_size"], seq_len=seq_len,
        n_experts=config["n_routed_experts"], n_active_experts=config["num_experts_per_tok"],
        hidden_act=HiddenAct.SILU, rope_theta=float(config["rope_theta"]),
        rope_type=RopeType.FALCON, weights_float_type=FloatType.Q40,
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        moe_hidden_dim=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"], n_routed_experts=routed, first_expert=first,
        first_dense=config["first_k_dense_replace"],
        routed_scale_milli=round(1000 * config["routed_scaling_factor"]),
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"], qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        flags=int(ArchFlags.USE_ROPE | ArchFlags.NORM_TOPK | ArchFlags.SIGMOID_ROUTER),
    )


def role(name: str) -> str | None:
    """Which shared drawing rule a tensor of the file falls under; None for
    the tensors :func:`draw` draws."""
    if name == "embedding":
        return "embedding"
    if name == "wcls":
        return "head"
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("rms_att", "rms_ffn", "rms_final", "q_a_norm", "kv_a_norm"):
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
