"""Granite-4.0-H's model file (``model_type`` ``granitemoehybrid`` with no
experts: ``num_local_experts`` 0): state-space layers (Mamba-2's SSD: one
input projection to ``z | x B C | dt``, a causal depthwise convolution with a
bias over ``x B C``, ``mamba_n_heads`` heads of ``mamba_d_head`` values with
``mamba_d_state`` state values each and ONE ``B`` and ``C`` for all heads, a
skip ``D``, a gated RMS norm over the whole inner width) with a softmax layer
(no rotation: ``position_embedding_type`` ``nope``) wherever ``layer_types``
says ``attention``; a dense SwiGLU ``shared_intermediate_size`` wide in every
layer; the four multipliers of the lineage; a head that IS the embedding
(``tie_word_embeddings``)."""

from __future__ import annotations

import numpy as np

from benchmark.harness.traffic import FIRST_FILLER_ID

# std of an embedding value: the stream starts at 12 x this (``embedding_multiplier``), 1/32,
# a twentieth of what the 40 layers' branches add to it (each 0.22 x a unit-sized output through
# a matrix of gain 0.5): see :func:`draw`
EMBEDDING_STD = 1.0 / 384.0
_embedding: np.ndarray | None = None  # what draw("embedding") drew, until draw("wcls") has tied it


def _micro(config: dict, key: str) -> int:
    """A multiplier in millionths, as the header carries it; it has to be one exactly."""
    value = round(config[key] * 1e6)
    if value <= 0 or abs(value - config[key] * 1e6) > 1e-6:
        raise ValueError(f"{key} = {config[key]} is no positive whole number of millionths")
    return value


def attention_period(config: dict) -> tuple[int, int]:
    """(period, offset): ``layer_types`` says ``attention`` at ``offset``,
    ``offset + period``, ... and ``mamba`` everywhere else. Read off the whole
    published list, so that a file of fewer layers (its leading ones) keeps
    the pattern, whether or not it reaches the first attention layer."""
    kinds = config["layer_types"]
    at = [l for l, kind in enumerate(kinds) if kind == "attention"]
    if len(kinds) < config["num_hidden_layers"] or len(at) < 1 or set(kinds) != {"attention", "mamba"}:
        raise ValueError("layer_types names fewer layers than the depth, no attention layer, or a third kind")
    period = at[1] - at[0] if len(at) > 1 else len(kinds)
    if at != list(range(at[0], len(kinds), period)) or at[0] >= period:
        raise ValueError(f"the attention layers {at} are not one at the same index of every period")
    return period, at[0]


def model_spec(config: dict, seq_len: int):
    from distributed_llama_tpu.formats import model_file
    from distributed_llama_tpu.quants import FloatType

    if not hasattr(model_file.ArchType, "GRANITE_HYBRID"):
        # a program from before the arch was added: stop before gigabytes are written
        raise ValueError(
            f"unknown architecture GRANITE_HYBRID: this program's .m format knows "
            f"{[a.name for a in model_file.ArchType]} only and cannot build or serve "
            f"configuration {config.get('name')!r}")
    ArchType, HiddenAct, ModelSpec, RopeType = (
        model_file.ArchType, model_file.HiddenAct, model_file.ModelSpec, model_file.RopeType)

    if config["model_type"] != "granitemoehybrid" or config["hidden_act"] != "silu" \
            or config["normalization_function"] != "rmsnorm" or not config["tie_word_embeddings"] \
            or config["position_embedding_type"] != "nope" or config["attention_bias"] \
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"] or config["mamba_n_groups"] != 1:
        raise ValueError("this family builds SiLU, RMS norms, a tied head, softmax layers without "
                         "rotation or bias, and state-space layers of one group whose convolution has "
                         "a bias and whose projections have none")
    if config["num_local_experts"] or config["num_experts_per_tok"]:
        raise ValueError("this family builds the dense members of the lineage (num_local_experts 0)")
    if config["shared_intermediate_size"] != config["intermediate_size"]:
        raise ValueError("the dense feed-forward is shared_intermediate_size wide, and so is intermediate_size")
    if config["mamba_n_heads"] * config["mamba_d_head"] != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
    period, offset = attention_period(config)
    return ModelSpec(
        arch_type=ArchType.GRANITE_HYBRID, dim=config["hidden_size"],
        hidden_dim=config["shared_intermediate_size"], n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        vocab_size=config["vocab_size"], seq_len=seq_len, hidden_act=HiddenAct.SILU,
        rope_theta=float(config["rope_theta"]), rope_type=RopeType.FALCON,
        weights_float_type=FloatType.Q40, attn_period=period, attn_offset=offset,
        lin_conv=config["mamba_d_conv"], ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"], ssm_state=config["mamba_d_state"],
        embed_scale_micro=_micro(config, "embedding_multiplier"),
        residual_scale_micro=_micro(config, "residual_multiplier"),
        attn_scale_micro=_micro(config, "attention_multiplier"),
        logits_divisor_micro=_micro(config, "logits_scaling"),
    )


def role(name: str) -> str | None:
    """Which shared drawing rule a tensor of the file falls under; None for
    the tensors :func:`draw` draws (the embedding and the head among them:
    they are one matrix)."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("rms_att", "rms_ffn", "rms_final", "ssm_norm"):
        return "norm"
    if leaf in ("embedding", "wcls", "conv", "conv_bias", "dt_bias", "a_log", "ssm_d"):
        return None
    # the matrices that write into the residual stream
    return "residual" if leaf in ("wo", "down") else "matrix"


def draw(entry, rng: np.random.Generator) -> np.ndarray:
    """The tensors that are no matrix, and the tied pair.

    The recurrence's vectors follow the lineage's own initialisation: ``A``
    uniform in 1 .. 16 (``a_log`` its log), ``dt`` log-uniform in 0.001 ..
    0.1 at a zero input (``dt_bias`` its inverse softplus; the projection adds
    about unit variance before the softplus, so a step's decay ``exp(-dt A)``
    spans 0.9996 down to under 0.01: some heads carry their state across a
    whole prompt and some forget within a token), ``D`` near one. Conv taps:
    the newest input near 1, the older ones small; a small bias.

    The embedding and the head are ONE matrix (``tie_word_embeddings``): the
    file holds it as the f32 ``embedding`` and, Q40, as ``wcls``. Two things
    the seeded draw has to see to, which training sees to in the published
    weights. A tied head scores the token just fed by ``|E_t|^2``, 2048
    values of one sign, against sqrt(2048) for every other token: at an
    embedding the size of the stream every greedy answer would repeat its
    last token whatever the layers compute, and ``correct`` would see no
    fault in them. So the embedding is small (``EMBEDDING_STD``): the stream
    a layer reads is then what the layers before it added (layer 0 reads the
    normed embedding), the fed token's bonus is about two of the other tokens'
    standard deviations, and the answer is the layers'. And the head's rows
    below the first filler are zero, as in every configuration
    (``harness/modelfile.py``: an answer is fillers only), while the
    embedding's are not: they are the prompt's tokens."""
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
    if leaf == "conv":
        taps = 0.15 * rng.standard_normal(entry.shape, dtype=np.float32)
        taps[:, -1] += 1.0
        return taps
    if leaf == "conv_bias":
        return 0.1 * rng.standard_normal(entry.shape, dtype=np.float32)
    if leaf == "a_log":
        return np.log(rng.uniform(1.0, 16.0, entry.shape)).astype(np.float32)
    if leaf == "dt_bias":
        rate = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), entry.shape))
        return np.log(np.expm1(rate)).astype(np.float32)
    if leaf == "ssm_d":
        return (1.0 + 0.1 * rng.standard_normal(entry.shape)).astype(np.float32)
    raise ValueError(f"no draw for tensor {entry.name!r}")
