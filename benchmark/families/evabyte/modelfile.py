"""EvaByte's model file: a dense byte-level model whose every layer mixes by
EVA attention (``attention_class`` ``eva``: exact keys inside an aligned
window of ``window_size`` positions, one learned summary for every
``chunk_size`` positions of the windows before it), norms with a unit offset
(``norm_add_unit_offset``), and ``num_pred_heads`` prediction heads of
``vocab_size`` rows on one output matrix, of which the first is the next
byte's. All of them are written; the server loads the first."""

from __future__ import annotations

import numpy as np

# tensors of a layer that are neither a matrix nor drawn as a norm is elsewhere
_OFFSET_NORMS = ("rms_att", "rms_ffn", "rms_final")


def model_spec(config: dict, seq_len: int):
    from distributed_llama_tpu.formats import model_file
    from distributed_llama_tpu.quants import FloatType

    if not hasattr(model_file.ArchType, "EVABYTE"):
        # a program from before the arch was added: stop before gigabytes are written
        raise ValueError(
            f"unknown architecture EVABYTE: this program's .m format knows "
            f"{[a.name for a in model_file.ArchType]} only and cannot build or serve "
            f"configuration {config.get('name')!r}")
    ArchFlags, ArchType, HiddenAct = model_file.ArchFlags, model_file.ArchType, model_file.HiddenAct

    if config["attention_class"] != "eva" or config["hidden_act"] != "silu" \
            or config["tie_word_embeddings"] or config["attention_bias"] \
            or config["rope_scaling"] is not None or not config["norm_add_unit_offset"]:
        raise ValueError("this family builds EVA attention without biases, SiLU, an untied head, "
                         "the default rotation and norms with a unit offset")
    if config["num_chunks"] is not None:
        raise ValueError("num_chunks is set: the summaries follow chunk_size, one per chunk")
    window, chunk = config["window_size"], config["chunk_size"]
    if window % chunk or seq_len % chunk:
        raise ValueError(f"a window of {window} and a context of {seq_len} positions are not "
                         f"whole chunks of {chunk}")
    return model_file.ModelSpec(
        arch_type=ArchType.EVABYTE, dim=config["hidden_size"],
        hidden_dim=config["intermediate_size"], n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        vocab_size=config["vocab_size"], seq_len=seq_len, hidden_act=HiddenAct.SILU,
        rope_theta=float(config["rope_theta"]), rope_type=model_file.RopeType.FALCON,
        weights_float_type=FloatType.Q40, window=window, eva_chunk=chunk,
        n_pred_heads=config["num_pred_heads"],
        flags=int(ArchFlags.USE_ROPE | ArchFlags.NORM_UNIT_OFFSET),
    )


def role(name: str) -> str | None:
    """Which shared drawing rule a tensor of the file falls under; None for
    the tensors :func:`draw` draws (the norms too: the shared rule draws a
    norm's weight about 1, and this model's weight is what is ADDED to 1)."""
    if name == "embedding":
        return "embedding"
    if name == "wcls":
        return "head"
    leaf = name.rsplit(".", 1)[-1]
    if leaf in _OFFSET_NORMS or leaf in ("eva_phi", "eva_mu"):
        return None
    # the matrices that write into the residual stream
    return "residual" if leaf in ("wo", "down") else "matrix"


def draw(entry, rng: np.random.Generator) -> np.ndarray:
    """A norm's offset weight about 0 (so the norm multiplies by about 1, as
    the shared rule's norms do). ``eva_phi`` and ``eva_mu`` of unit variance:
    a key's and a query's values have about unit variance, so the 16 pooling
    logits ``<k_j, phi> / sqrt(head)`` spread by about 1 (a summary is a
    pooling that depends on its keys, not their mean) and ``mu`` moves a
    summary's score by about 1 (it is felt, and a summary's key is still
    mostly its chunk's)."""
    leaf = entry.name.rsplit(".", 1)[-1]
    if leaf in _OFFSET_NORMS:
        return (0.1 * rng.standard_normal(entry.shape)).astype(np.float32)
    if leaf in ("eva_phi", "eva_mu"):
        return rng.standard_normal(entry.shape).astype(np.float32)
    raise ValueError(f"no draw for tensor {entry.name!r}")
