"""GLM-5's model file (``model_type`` ``glm_moe_dsa``): GLM-4.7-Flash's layers
(family ``glm4_moe_lite`` has their description: latent attention, leading
dense layers, a sigmoid ``noaux_tc`` router, a share of the routed experts
beside a shared one) with, in EVERY layer, a learned sparse selection in front
of the attention (DeepSeek Sparse Attention): an indexer of ``index_n_heads``
heads of ``index_head_dim`` values scores every earlier position for a query
and the attention reads the ``index_topk`` best. Four tensors a layer more:
``index_q`` (read by the normed query latent), ``index_k`` and its LayerNorm
``index_k_norm``, ``index_w`` (the heads' weights; both read by the block's
normed input). The multi-token-prediction layer is not written."""

from __future__ import annotations

import numpy as np


def model_spec(config: dict, seq_len: int):
    from distributed_llama_tpu.formats import model_file
    from distributed_llama_tpu.quants import FloatType

    if not hasattr(model_file.HeaderKey, "INDEX_TOPK"):
        # a program from before latent files could carry an indexer: stop before gigabytes are written
        raise ValueError(
            f"unknown header keys for a latent-attention file with an indexer (family glm_moe_dsa): "
            f"this program's .m format knows {[a.name for a in model_file.ArchType]}, none of which "
            f"selects the positions a query reads, and cannot build or serve configuration "
            f"{config.get('name')!r}")
    ArchFlags, ArchType, HiddenAct = model_file.ArchFlags, model_file.ArchType, model_file.HiddenAct
    ModelSpec, RopeType = model_file.ModelSpec, model_file.RopeType

    rope = config["rope_parameters"]
    if config["model_type"] != "glm_moe_dsa" or config["hidden_act"] != "silu" \
            or config["tie_word_embeddings"] or not config["norm_topk_prob"] \
            or config["topk_method"] != "noaux_tc" or config["scoring_func"] != "sigmoid" \
            or (config["n_group"], config["topk_group"]) != (1, 1) or config["moe_layer_freq"] != 1 \
            or rope["rope_type"] != "default" or config["attention_bias"]:
        raise ValueError("this family builds SiLU, an untied head, projections without bias, a "
                         "sigmoid noaux_tc router without groups whose top k is renormalised, "
                         "experts in every layer past the dense ones, and the plain rotation")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention expands one key and one value a head")
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    if config["qk_head_dim"] != nope + rot or config["head_dim"] != rot:
        raise ValueError("qk_head_dim is a head's unrotated and rotated values, head_dim the rotated ones")
    if not 0 < rot <= config["index_head_dim"] or not config["index_n_heads"] or not config["index_topk"]:
        raise ValueError("the indexer needs heads, a head at least as wide as the rotated slice, and a top k")
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
        hidden_act=HiddenAct.SILU, rope_theta=float(rope["rope_theta"]),
        rope_type=RopeType.FALCON, weights_float_type=FloatType.Q40,
        head_dim=nope + rot, moe_hidden_dim=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"], n_routed_experts=routed, first_expert=first,
        first_dense=config["first_k_dense_replace"],
        routed_scale_milli=round(1000 * config["routed_scaling_factor"]),
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=nope, qk_rope_head_dim=rot, v_head_dim=config["v_head_dim"],
        index_n_heads=config["index_n_heads"], index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        flags=int(ArchFlags.USE_ROPE | ArchFlags.NORM_TOPK | ArchFlags.SIGMOID_ROUTER),
    )


def role(name: str) -> str | None:
    """Which shared drawing rule a tensor of the file falls under; None for
    the tensors :func:`draw` draws. The indexer's three matrices are matrices
    like any: over normed inputs their products are of unit size."""
    if name == "embedding":
        return "embedding"
    if name == "wcls":
        return "head"
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("rms_att", "rms_ffn", "rms_final", "q_a_norm", "kv_a_norm"):
        return "norm"
    if leaf in ("router_bias", "index_k_norm"):
        return None
    # the matrices that write into the residual stream
    return "residual" if leaf in ("wo", "down") else "matrix"


def draw(entry, rng: np.random.Generator) -> np.ndarray:
    """The router's selection bias: small against the sigmoid scores' spread,
    so it decides near-ties only. The index key's LayerNorm: a weight about
    one and a bias about a tenth of it, so that both are load-bearing."""
    leaf = entry.name.rsplit(".", 1)[-1]
    if leaf == "router_bias":
        return (0.02 * rng.standard_normal(entry.shape)).astype(np.float32)
    if leaf == "index_k_norm":
        weight_bias = 0.1 * rng.standard_normal(entry.shape)
        weight_bias[0] += 1.0
        return weight_bias.astype(np.float32)
    raise ValueError(f"no draw for tensor {entry.name!r}")
