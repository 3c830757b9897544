"""Solar-Open2's model file: softmax and gated delta-rule layers in one model
(``gqa_layers``: every ``gqa_interval + 1``-th layer is softmax), a share of
the routed experts (``n_routed_experts`` HELD here, the router's width from
``reduced_from``, the first held expert's index ``first_routed_expert``) and
a shared expert in every layer."""

from __future__ import annotations

import numpy as np


def model_spec(config: dict, seq_len: int):
    from distributed_llama_tpu.formats import model_file
    from distributed_llama_tpu.quants import FloatType

    if not hasattr(model_file.ArchType, "SOLAR_OPEN2"):
        # a program from before the arch was added: stop before gigabytes are written
        raise ValueError(
            f"unknown architecture SOLAR_OPEN2: this program's .m format knows "
            f"{[a.name for a in model_file.ArchType]} only and cannot build or serve "
            f"configuration {config.get('name')!r}")
    ArchFlags, ArchType, HiddenAct = model_file.ArchFlags, model_file.ArchType, model_file.HiddenAct
    ModelSpec, RopeType = model_file.ModelSpec, model_file.RopeType

    lin = config["linear_attn_config"]
    period = config["gqa_interval"] + 1
    depth = config["num_hidden_layers"]
    if [l for l in config["gqa_layers"] if l < depth] != list(range(0, depth, period)):
        raise ValueError("gqa_layers is not every (gqa_interval + 1)-th layer from 0")
    if config["first_k_dense_replace"] or config["tie_word_embeddings"] or config["use_rope"]:
        raise ValueError("this family builds no leading dense layer, no tied head and no rope")
    if not (config["use_gqa_gate"] and config["kda_allow_neg_eigval"] and config["norm_topk_prob"]) \
            or config["kda_use_full_proj"] or config["routed_scaling_factor"] != 1:
        raise ValueError("this family builds one set of flags: gated softmax layers, beta in (0, 2), "
                         "renormalised top k, low-rank decay and gate projections, scaling factor 1")
    if lin["num_kv_heads"] not in (None, lin["num_heads"]):
        raise ValueError("linear layers with fewer key/value heads than heads are not built")
    routed = config.get("reduced_from", {}).get("n_routed_experts", config["n_routed_experts"])
    if not 0 <= config["first_routed_expert"] <= routed - config["n_routed_experts"]:
        raise ValueError("the held experts do not lie inside the router's width")
    return ModelSpec(
        arch_type=ArchType.SOLAR_OPEN2, dim=config["hidden_size"],
        hidden_dim=config["intermediate_size"], n_layers=depth,
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        vocab_size=config["vocab_size"], seq_len=seq_len,
        n_experts=config["n_routed_experts"], n_active_experts=config["num_experts_per_tok"],
        hidden_act=HiddenAct.SILU, rope_theta=float(config["rope_theta"]),
        rope_type=RopeType.FALCON, weights_float_type=FloatType.Q40,
        head_dim=config["head_dim"], moe_hidden_dim=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"], n_routed_experts=routed,
        first_expert=config["first_routed_expert"], attn_period=period,
        lin_heads=lin["num_heads"], lin_head_dim=lin["head_dim"],
        lin_conv=lin["short_conv_kernel_size"], lin_rank=config["kda_low_rank_dim"],
        flags=int(ArchFlags.GQA_GATE | ArchFlags.NEG_EIGVAL | ArchFlags.NORM_TOPK
                  | ArchFlags.SIGMOID_ROUTER),
    )


def role(name: str) -> str | None:
    """Which shared drawing rule a tensor of the file falls under; None for
    the tensors :func:`draw` draws."""
    if name == "embedding":
        return "embedding"
    if name == "wcls":
        return "head"
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("rms_att", "rms_ffn", "rms_final", "o_norm"):
        return "norm"
    if leaf in ("conv", "dt_bias", "a_log", "router_bias"):
        return None
    # the matrices that write into the residual stream
    return "residual" if leaf in ("wo", "down") else "matrix"


def draw(entry, rng: np.random.Generator) -> np.ndarray:
    """The tensors that are no matrix. The decay of a channel is ``alpha =
    exp(-exp(a_log) * softplus(x + dt_bias))`` with ``x`` of about unit
    variance: ``a_log`` and ``dt_bias`` are drawn so that, at ``x = 0``,
    ``alpha`` spans 0.5 to 0.999 over heads and channels (log-uniform in
    ``-log alpha``), so some channels forget within a few tokens and some
    carry their state across a whole prompt, and the state neither dies nor
    grows (``beta < 2`` and unit keys keep every transition a contraction or
    a reflection). Conv taps: the newest input near 1, the older ones small.
    The router's selection bias: small against the sigmoid scores' spread,
    so it decides near-ties only."""
    leaf = entry.name.rsplit(".", 1)[-1]
    if leaf == "conv":
        taps = 0.15 * rng.standard_normal(entry.shape, dtype=np.float32)
        taps[:, -1] += 1.0
        return taps
    if leaf == "a_log":
        return np.zeros(entry.shape, np.float32)  # exp(0) = 1: dt_bias alone sets the span
    if leaf == "dt_bias":
        # softplus(dt_bias) = -log(alpha) in [0.001, 0.693], log-uniform
        rate = np.exp(rng.uniform(np.log(1e-3), np.log(-np.log(0.5)), entry.shape))
        return np.log(np.expm1(rate)).astype(np.float32)
    if leaf == "router_bias":
        return (0.02 * rng.standard_normal(entry.shape)).astype(np.float32)
    raise ValueError(f"no draw for tensor {entry.name!r}")
