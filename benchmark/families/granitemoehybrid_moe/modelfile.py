"""Granite-4.0-H's model file, the members WITH experts (``model_type``
``granitemoehybrid``, ``num_local_experts`` > 0): the dense members' mixers
(Mamba-2's SSD state-space layers with a softmax layer that does not rotate
wherever ``layer_types`` says ``attention``; family ``granitemoehybrid`` has
their description) and, in every layer, a router over the PUBLISHED number of
experts (``reduced_from``'s ``num_local_experts``) of which the file holds
``num_local_experts`` from ``first_routed_expert`` on, each
``intermediate_size`` wide, ``num_experts_per_tok`` chosen a token by a
softmax over the chosen logits, beside a shared SwiGLU of
``shared_intermediate_size``; the four multipliers of the lineage; a head that
IS the embedding (``tie_word_embeddings``)."""

from __future__ import annotations

import os

import numpy as np

from benchmark import families
from benchmark.harness.traffic import FIRST_FILLER_ID

# std of an embedding value. A tied head scores the token just fed by 12 |E_t|^2 / rms(stream),
# ``hidden_size`` values of one sign, against std sqrt(hidden_size) for every other token: a bonus
# of 12 std sqrt(hidden_size) / rms(stream) of the others' standard deviations, so what decides
# whether a greedy answer echoes its last token is std x sqrt(hidden_size). The sibling's 1/384 at
# 2048 wide is 0.118. Measured here (the share of positions whose greedy token is the token just
# fed; PERF.md section 4): at 512 wide 0 % at 1/768 (0.029), 3 % at 1/384, 100 % at 1/96; at 1024
# wide 0.5 % at 1/768 (0.042), 8 % at 1/384 (0.083); on the chip at 4096 wide 27 % of the probes'
# answered positions repeated their predecessor at 1/768 (0.083). So 1/1536 at 4096 wide (0.042):
# see :func:`draw`
EMBEDDING_STD = 1.0 / 1536.0
_embedding: np.ndarray | None = None  # what draw("embedding") drew, until draw("wcls") has tied it


def _scaled(config: dict, key: str, unit: float) -> int:
    """A multiplier in ``unit``s (1e6: millionths; 1e9: billionths), as the
    header carries it; 0 where it is no positive whole number of them."""
    value = round(config[key] * unit)
    return value if value > 0 and abs(value - config[key] * unit) <= 1e-6 else 0


def _micro(config: dict, key: str) -> int:
    value = _scaled(config, key, 1e6)
    if not value:
        raise ValueError(f"{key} = {config[key]} is no positive whole number of millionths")
    return value


def _sibling():
    """The dense members' builder, found beside this family's directory: the
    layer pattern's reading and the draws of the recurrence's vectors are one
    piece of code for the lineage."""
    bench_dir = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return families.load({"family": "granitemoehybrid", "name": "the dense members"}, "modelfile", bench_dir)


def model_spec(config: dict, seq_len: int):
    from distributed_llama_tpu.formats import model_file
    from distributed_llama_tpu.quants import FloatType

    if not hasattr(model_file.HeaderKey, "ATTN_SCALE_NANO"):
        # a program from before this arch's files could carry experts: stop before gigabytes are written
        raise ValueError(
            f"unknown header keys for ArchType GRANITE_HYBRID with experts: this program's .m format "
            f"knows {[a.name for a in model_file.ArchType]}, of which a state-space file has a dense "
            f"feed-forward only, and cannot build or serve configuration {config.get('name')!r}")
    ArchType, HiddenAct, ModelSpec, RopeType = (
        model_file.ArchType, model_file.HiddenAct, model_file.ModelSpec, model_file.RopeType)

    if config["model_type"] != "granitemoehybrid" or config["hidden_act"] != "silu" \
            or config["normalization_function"] != "rmsnorm" or not config["tie_word_embeddings"] \
            or config["position_embedding_type"] != "nope" or config["attention_bias"] \
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"] or config["mamba_n_groups"] != 1:
        raise ValueError("this family builds SiLU, RMS norms, a tied head, softmax layers without "
                         "rotation or bias, and state-space layers of one group whose convolution has "
                         "a bias and whose projections have none")
    held, width = config["num_local_experts"], config["intermediate_size"]
    routed = config.get("reduced_from", {}).get("num_local_experts", held)
    if not held or not 0 < config["num_experts_per_tok"] <= routed:
        raise ValueError("this family builds the members of the lineage with experts (family "
                         "granitemoehybrid builds the dense ones)")
    if not 0 <= config["first_routed_expert"] <= routed - held:
        raise ValueError("the held experts do not lie inside the router's width")
    if config["shared_intermediate_size"] % width:
        raise ValueError("the file lays the shared expert out as a whole number of experts' widths "
                         "side by side (n_shared_experts x moe_hidden_dim)")
    if config["mamba_n_heads"] * config["mamba_d_head"] != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
    period, offset = _sibling().attention_period(config)
    # the softmax scale in millionths where it is a whole number of them, else in billionths
    # (1/128 is 7812.5 millionths)
    micro = _scaled(config, "attention_multiplier", 1e6)
    nano = 0 if micro else _scaled(config, "attention_multiplier", 1e9)
    if not micro and not nano:
        raise ValueError(f"attention_multiplier = {config['attention_multiplier']} is no whole "
                         f"number of billionths")
    return ModelSpec(
        arch_type=ArchType.GRANITE_HYBRID, dim=config["hidden_size"],
        hidden_dim=config["shared_intermediate_size"], n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        vocab_size=config["vocab_size"], seq_len=seq_len, hidden_act=HiddenAct.SILU,
        n_experts=held, n_active_experts=config["num_experts_per_tok"],
        rope_theta=float(config["rope_theta"]), rope_type=RopeType.FALCON,
        weights_float_type=FloatType.Q40, attn_period=period, attn_offset=offset,
        lin_conv=config["mamba_d_conv"], ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"], ssm_state=config["mamba_d_state"],
        moe_hidden_dim=width, n_shared_experts=config["shared_intermediate_size"] // width,
        n_routed_experts=routed, first_expert=config["first_routed_expert"],
        embed_scale_micro=_micro(config, "embedding_multiplier"),
        residual_scale_micro=_micro(config, "residual_multiplier"),
        attn_scale_micro=micro, attn_scale_nano=nano,
        logits_divisor_micro=_micro(config, "logits_scaling"),
    )


def role(name: str) -> str | None:
    """Which shared drawing rule a tensor of the file falls under; None for
    the tensors :func:`draw` draws (the embedding and the head among them:
    they are one matrix). The router is a matrix like any: its logits over a
    normed input are of unit size, so the ten chosen of 72 weigh between a
    twentieth and a third."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("rms_att", "rms_ffn", "rms_final", "ssm_norm"):
        return "norm"
    if leaf in ("embedding", "wcls", "conv", "conv_bias", "dt_bias", "a_log", "ssm_d"):
        return None
    # the matrices that write into the residual stream
    return "residual" if leaf in ("wo", "down") else "matrix"


def draw(entry, rng: np.random.Generator) -> np.ndarray:
    """The tensors that are no matrix, and the tied pair: the sibling
    family's draws (``granitemoehybrid/modelfile.py`` says why each), the
    embedding at this width's size (``EMBEDDING_STD``)."""
    global _embedding
    leaf = entry.name.rsplit(".", 1)[-1]
    if leaf == "embedding":
        _embedding = (EMBEDDING_STD * rng.standard_normal(entry.shape, dtype=np.float32))
        return _embedding
    if leaf == "wcls":
        if _embedding is None or _embedding.shape != entry.shape:
            raise ValueError("the head is the embedding's matrix, and no embedding of its shape was drawn")
        tied, _embedding = _embedding.copy(), None
        tied[:FIRST_FILLER_ID] = 0.0
        return tied
    return _sibling().draw(entry, rng)  # conv taps and bias, A_log, dt_bias, D: the lineage's
