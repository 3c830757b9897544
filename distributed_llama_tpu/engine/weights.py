"""Load `.m` weights into the stacked pytree consumed by the model functions.

The reference root node mmaps the file and streams per-matrix slices to
workers over TCP (reference: src/transformer.cpp:432-616). On TPU the same
file is read once per host; matrices are transposed to (d_in, d_out) so the
hot matmul is ``x @ W`` with no transposes in the compiled program, layers are
stacked on a leading axis for ``lax.scan``, and the result is `device_put`
(optionally with a NamedSharding so XLA places each shard directly).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu.formats.model_file import (
    ArchFlags,
    ArchType,
    ModelFileReader,
    ModelSpec,
)
from distributed_llama_tpu.models.config import LlamaConfig, config_from_spec
from distributed_llama_tpu.models.rope import build_rope_table

Params = dict[str, Any]


def _t(x: np.ndarray, dtype) -> np.ndarray:
    """File stores [d_out, d_in] (y = W @ x); we store [d_in, d_out]."""
    return np.ascontiguousarray(x.T).astype(dtype)


QUANTIZED_DTYPE = "q40"  # sentinel: keep matmul weights 4-bit on device


def load_params(
    reader: ModelFileReader,
    cfg: LlamaConfig | None = None,
    dtype=jnp.bfloat16,
    tp: int = 1,
    mesh=None,
) -> Params:
    """Build the host-side params pytree (numpy, not yet on device).

    dtype applies to the matmul weights; embeddings and norm scales stay f32
    (they are F32 in the file too — reference: src/transformer.cpp:296-310).
    ``dtype="q40"`` keeps the attention/FFN/wcls matrices packed 4-bit
    (QuantizedMatrix leaves, fed to the fused Pallas matmul), including the
    MoE expert banks (per-expert fused gate|up + down leaves).

    ``tp > 1`` builds every matmul weight as per-shard reads in sharded
    layout — q40 as per-shard packs (raw_rows / raw_row_blocks), bf16/f32
    via row/column-range reads (tensor_rows / tensor_cols) — the read-time
    equivalent of the reference's RowMatmulSlice/ColMatmulSlice scatter
    (src/commands.cpp:11-108 + src/transformer.cpp:432-451). With ``mesh``
    set, shards are placed via ``jax.make_array_from_callback``: each
    PROCESS builds (and reads) only the shards of its addressable devices —
    per-host RAM and file traffic are O(model/tp), the property that makes
    a 238 GB 405B file loadable across a pod. Without a mesh they are
    concatenated on host for a later NamedSharding device_put (single-host
    fallback).
    """
    spec = reader.spec
    cfg = cfg or config_from_spec(spec)
    quantized = dtype == QUANTIZED_DTYPE
    shard_vocab = tp > 1 and cfg.vocab_size % tp == 0
    rule_table = None
    if tp > 1:
        from distributed_llama_tpu.parallel.tensor_parallel import validate_tp

        validate_tp(cfg, tp, quantized=quantized)
        from distributed_llama_tpu.parallel import sharding as sharding_rules

        # the ONE sharding authority (ISSUE 15): the rule table decides
        # every leaf's layout; the load-time shard DIRECTION (row-range
        # "out" reads vs column-range "in" reads) is DERIVED from the
        # resolved spec below, never hand-rolled here
        rule_table = sharding_rules.param_rules(
            cfg, "q40" if quantized else "layered", shard_vocab
        )
    np_dtype = np.dtype(jnp.bfloat16 if quantized else dtype)

    def leaf_spec(path: str):
        return rule_table.spec(path, {"model": "tp"})

    def shard_direction(spec_) -> str:
        # every matmul layout here stores the output dim LAST (q40 packs
        # [n/2, d_out], plain [d_in, d_out], expert stacks [E, d_in,
        # d_out]), so the model axis landing on the last dim means
        # output-sharded (RowMatmulSlice); anywhere else, input-sharded
        # (ColMatmulSlice). An unsharded matmul leaf would be a rule-table
        # bug — surface it as the typed error class
        if "tp" not in spec_:
            from distributed_llama_tpu.parallel import sharding as sharding_rules

            raise sharding_rules.ShardingRuleError(
                f"matmul leaf resolved to replicated spec {spec_} under tp={tp}"
            )
        return "out" if spec_[-1] == "tp" else "in"

    def cast(x: np.ndarray) -> np.ndarray:
        return x.astype(np_dtype)

    def weight(name: str):
        """A matmul weight in x@W orientation: QuantizedMatrix or numpy."""
        if quantized:
            from distributed_llama_tpu.ops.q40 import pack_q40_raw, quantize_q40_tpu
            from distributed_llama_tpu.quants import FloatType

            e = reader.entries[name]
            if e.float_type == FloatType.Q40:
                return pack_q40_raw(reader.raw(name), e.shape)  # exact repack
            return quantize_q40_tpu(_t(reader.tensor(name), np.float32))
        return cast(_t(reader.tensor(name), np.float32))

    def weight_fused(names: list[str]):
        """Several matrices sharing an input dim, packed as ONE matmul with
        their output dims concatenated (q|k|v, gate|up). Merging the small
        per-token matvecs into one big one keeps the Q40 kernel in its
        bandwidth-efficient regime. The file stores [d_out, d_in] row-major
        blocks, so the Q40-exact concat is a plain byte concat."""
        from distributed_llama_tpu.ops.q40 import pack_q40_raw, quantize_q40_tpu
        from distributed_llama_tpu.quants import FloatType

        entries = [reader.entries[n] for n in names]
        if all(e.float_type == FloatType.Q40 for e in entries):
            raw = np.concatenate([reader.raw(n) for n in names])
            d_out = sum(e.shape[0] for e in entries)
            return pack_q40_raw(raw, (d_out, entries[0].shape[1]))
        mats = [_t(reader.tensor(n), np.float32) for n in names]
        return quantize_q40_tpu(np.concatenate(mats, axis=1))

    def shard_out(names: list[str], s: int):
        """Output-dim shard s of (fused) matrices: each source contributes
        rows [s*d/tp, (s+1)*d/tp) (RowMatmulSlice, src/commands.cpp:11-43)."""
        from distributed_llama_tpu.ops.q40 import pack_q40_raw, quantize_q40_tpu
        from distributed_llama_tpu.quants import FloatType

        entries = [reader.entries[n] for n in names]
        if all(e.float_type == FloatType.Q40 for e in entries):
            raws, d_out = [], 0
            for nm, e in zip(names, entries):
                lo, hi = e.shape[0] * s // tp, e.shape[0] * (s + 1) // tp
                raws.append(reader.raw_rows(nm, lo, hi))
                d_out += hi - lo
            return pack_q40_raw(np.concatenate(raws), (d_out, entries[0].shape[1]))
        mats = []
        for nm, e in zip(names, entries):
            lo, hi = e.shape[0] * s // tp, e.shape[0] * (s + 1) // tp
            mats.append(np.ascontiguousarray(reader.tensor_rows(nm, lo, hi).T))
        return quantize_q40_tpu(np.concatenate(mats, axis=1).astype(np.float32))

    def shard_in(name: str, s: int):
        """Input-dim shard s: quant-block-aligned column range of every row
        (ColMatmulSlice, src/commands.cpp:45-73)."""
        from distributed_llama_tpu.ops.q40 import pack_q40_raw, quantize_q40_tpu
        from distributed_llama_tpu.quants import FloatType

        e = reader.entries[name]
        d_out, d_in = e.shape
        lo, hi = d_in * s // tp, d_in * (s + 1) // tp
        if e.float_type == FloatType.Q40:
            sl = reader.raw_row_blocks(name, lo, hi)
            return pack_q40_raw(sl.reshape(-1), (d_out, hi - lo))
        w = _t(reader.tensor(name), np.float32)[lo:hi]
        return quantize_q40_tpu(np.ascontiguousarray(w))

    def sharded(path: str, names):
        """Sharded q40 leaf for destination ``path``: the rule table's
        resolved spec picks the slicing direction (out = fused row-range
        reads, in = quant-block column ranges) and the placement layout."""
        from distributed_llama_tpu.ops.q40 import (
            QuantizedMatrix,
            _d_padded,
            _n_padded,
            concat_shard_packs,
        )

        spec = leaf_spec(path)
        axis = shard_direction(spec)
        if axis == "out":
            names_l = names if isinstance(names, list) else [names]
            builder, args = shard_out, (names_l,)
        else:
            builder, args = shard_in, (names,)
        if mesh is None:
            return concat_shard_packs([builder(*args, s) for s in range(tp)], axis)

        # lazy per-shard placement: analytic shard shapes + a callback that
        # builds (reads) one shard's pack only when a local device asks
        import jax.sharding as shd

        if axis == "out":
            entries_ = [reader.entries[nm] for nm in args[0]]
            d_shard = sum(e.shape[0] for e in entries_) // tp
            n_shard = entries_[0].shape[1]
        else:
            e = reader.entries[args[0]]
            d_shard = e.shape[0]
            n_shard = e.shape[1] // tp
        np_, dp = _n_padded(n_shard), _d_padded(d_shard)
        qs_shard = (np_ // 2, dp)
        sc_shard = (np_ // 32, dp)
        ax = 1 if axis == "out" else 0
        qs_gshape = tuple(
            s * tp if i == ax else s for i, s in enumerate(qs_shard)
        )
        sc_gshape = tuple(
            s * tp if i == ax else s for i, s in enumerate(sc_shard)
        )
        built: dict[int, tuple[np.ndarray, np.ndarray]] = {}

        def build(s: int):
            if s not in built:
                qm = builder(*args, s)
                qs_np, sc_np = np.asarray(qm.qs), np.asarray(qm.scales)
                # a real error, not an assert: under python -O a
                # builder/analytic-shape desync would otherwise surface as an
                # opaque make_array_from_callback failure far from the cause
                if qs_np.shape != qs_shard or sc_np.shape != sc_shard:
                    raise ValueError(
                        f"analytic shard shape mismatch: built {qs_np.shape}/"
                        f"{sc_np.shape}, expected {qs_shard}/{sc_shard}"
                    )
                built[s] = (qs_np, sc_np)
            return built[s]

        def qs_cb(idx):
            return build((idx[ax].start or 0) // qs_shard[ax])[0]

        def sc_cb(idx):
            return build((idx[ax].start or 0) // sc_shard[ax])[1]

        ns = shd.NamedSharding(mesh, spec)
        qs_g = jax.make_array_from_callback(qs_gshape, ns, qs_cb)
        sc_g = jax.make_array_from_callback(sc_gshape, ns, sc_cb)
        built.clear()  # free host copies; the data lives on device now
        return QuantizedMatrix(qs_g, sc_g, n_logical=n_shard, d_logical=d_shard)

    def _read_shard(name: str, axis: str, s: int) -> np.ndarray:
        """Shard ``s`` of one file matrix in logical (x@W) orientation: an
        independent row-range (out) or column-range (in) read."""
        e = reader.entries[name]
        d_out, d_in = e.shape  # file orientation; logical is [d_in, d_out]
        if axis == "out":
            lo, hi = d_out * s // tp, d_out * (s + 1) // tp
            return reader.tensor_rows(name, lo, hi).T
        lo, hi = d_in * s // tp, d_in * (s + 1) // tp
        return reader.tensor_cols(name, lo, hi).T

    def _place_shards(gshape, ax: int, spec, build):
        """Shared placement scaffold of the plain sharded loads: with a mesh,
        each PROCESS builds (reads) only its addressable devices' shards via
        make_array_from_callback; without one, shards concatenate on host
        for a later NamedSharding device_put."""
        import jax.sharding as shd

        built: dict[int, np.ndarray] = {}

        def cached(s: int) -> np.ndarray:
            if s not in built:
                built[s] = build(s)
            return built[s]

        if mesh is None:
            out = np.concatenate([cached(s) for s in range(tp)], axis=ax)
            built.clear()
            return out
        shard_len = gshape[ax] // tp

        def cb(idx):
            return cached((idx[ax].start or 0) // shard_len)

        arr = jax.make_array_from_callback(
            gshape, shd.NamedSharding(mesh, spec), cb
        )
        built.clear()
        return arr

    def sharded_plain(path: str, name: str):
        """Per-shard lazy read of a bf16/f32 matmul weight: the non-quantized
        analogue of ``sharded()`` (reader.tensor_rows / tensor_cols range
        reads) — O(model/tp) file traffic per host for every dtype, not just
        q40 (replacing the reference's root-reads-everything scatter for
        bf16 as well, src/transformer.cpp:432-451). Direction and spec come
        from the rule table, keyed by the destination leaf path."""
        spec = leaf_spec(path)
        axis = shard_direction(spec)
        d_out, d_in = reader.entries[name].shape
        ax = 1 if axis == "out" else 0
        return _place_shards(
            (d_in, d_out), ax, spec,
            lambda s: np.ascontiguousarray(_read_shard(name, axis, s)).astype(np_dtype),
        )

    def sharded_plain_expert_stack(path: str, expert_names: list[str]):
        """Sharded read of a stacked MoE expert bank: [E, d_in, d_out] with
        the matmul dim sharded (moe_up/gate: out; moe_down: in). Each shard
        stacks its per-expert row/column-range reads."""
        spec = leaf_spec(path)
        axis = shard_direction(spec)
        d_out, d_in = reader.entries[expert_names[0]].shape
        ax = 2 if axis == "out" else 1
        return _place_shards(
            (len(expert_names), d_in, d_out), ax, spec,
            lambda s: np.ascontiguousarray(
                np.stack([_read_shard(nm, axis, s) for nm in expert_names])
            ).astype(np_dtype),
        )

    def fused(names: list[str]):
        """:func:`weight_fused` for every dtype: the plain-array form is the
        same matrices side by side."""
        if quantized:
            return weight_fused(names)
        return cast(np.concatenate([_t(reader.tensor(n), np.float32) for n in names], axis=1))

    def f32(name: str) -> np.ndarray:
        return reader.tensor(name).astype(np.float32)

    def norm(name: str) -> np.ndarray:
        """A block norm's weight as the forward multiplies by it: ONE PLUS the
        stored weight where the file says its norms carry a unit offset
        (``ArchFlags.NORM_UNIT_OFFSET``), added here once so that every norm
        of the forward stays the one norm."""
        return 1.0 + f32(name) if cfg.has(ArchFlags.NORM_UNIT_OFFSET) else f32(name)

    def hybrid_layer(l: int) -> dict:
        """One ``ArchType.SOLAR_OPEN2`` layer: every input projection of a
        mixer as ONE matrix (``qkvg``: q|k|v|gate of a softmax layer;
        ``lin_in``: q|k|v|f_down|g_down|beta of a linear one), the held
        experts as a list of fused gate|up + down leaves, the shared expert
        as a dense SwiGLU. The router stays float32 at its published width:
        a top 8 of 320 is decided by margins that bf16 weights would move."""
        p = f"layers.{l}."
        if cfg.is_softmax_layer(l):
            lp = {"qkvg": fused([p + "q", p + "k", p + "v", p + "gate"])}
        else:
            lp = {
                "lin_in": fused([p + n for n in ("q", "k", "v", "f_down", "g_down", "beta")]),
                "conv": f32(p + "conv"),
                "f_up": weight(p + "f_up"),
                "dt_bias": f32(p + "dt_bias"),
                "a_log": f32(p + "a_log"),
                "g_up": weight(p + "g_up"),
                "o_norm": f32(p + "o_norm"),
            }
        lp["wo"] = weight(p + "wo")
        lp.update(held_experts(p))
        lp["rms_att"] = norm(p + "rms_att")
        lp["rms_ffn"] = norm(p + "rms_ffn")
        return lp

    def held_experts(p: str) -> dict:
        """The expert half of a layer that holds a share: the router (float32
        at its published width) and, of a sigmoid router, its selection bias,
        the held experts as one bank of fused gate|up and one of down, the
        shared expert as a dense SwiGLU."""
        from distributed_llama_tpu.ops.q40 import stack_bank

        lp = {"router": _t(reader.tensor(p + "moe_router"), np.float32)}
        if p + "router_bias" in reader.entries:
            lp["router_bias"] = f32(p + "router_bias")
        held = [f"{p}experts.{e}." for e in range(cfg.n_experts)]
        bank = stack_bank if quantized else np.stack
        lp["experts_gate_up"] = bank([fused([ep + "gate", ep + "up"]) for ep in held])
        lp["experts_down"] = bank([weight(ep + "down") for ep in held])
        if cfg.n_shared_experts:
            lp["shared_gate_up"] = fused([p + "shared.gate", p + "shared.up"])
            lp["shared_down"] = weight(p + "shared.down")
        return lp

    def window_layer(l: int) -> dict:
        """One ``ArchType.EXAONE_MOE`` layer: q|k|v as one matrix and the
        heads' norm weights, whatever the layer's attention kind (the kind
        decides its cache leaf and its mask, not its tensors); a dense SwiGLU
        in the leading layers, the held experts after them."""
        p = f"layers.{l}."
        lp = {"qkv": fused([p + "q", p + "k", p + "v"]), "q_norm": f32(p + "q_norm"),
              "k_norm": f32(p + "k_norm"), "wo": weight(p + "wo"),
              "rms_att": norm(p + "rms_att"), "rms_ffn": norm(p + "rms_ffn")}
        if cfg.layer_kind(l)[1] == "dense":
            lp["gate_up"] = fused([p + "gate", p + "up"])
            lp["down"] = weight(p + "down")
        else:
            lp.update(held_experts(p))
        return lp

    def eva_layer(l: int) -> dict:
        """One ``ArchType.EVABYTE`` layer: q|k|v and gate|up as one matrix
        each, and the summariser's two vectors a head in float32."""
        p = f"layers.{l}."
        return {"qkv": fused([p + "q", p + "k", p + "v"]), "wo": weight(p + "wo"),
                "eva_phi": f32(p + "eva_phi"), "eva_mu": f32(p + "eva_mu"),
                "gate_up": fused([p + "gate", p + "up"]), "down": weight(p + "down"),
                "rms_att": norm(p + "rms_att"), "rms_ffn": norm(p + "rms_ffn")}

    def latent_layer(l: int) -> dict:
        """One ``ArchType.GLM4_MOE_LITE`` layer: the two down-projections as
        ONE matrix (``qkv_a``: q_a|kv_a, read by the normed input), the
        query's up-projection ``q_b``, and the keys' and values' up-projection
        ``kv_b`` SLICED by head into ``w_uk`` [H, nope, rank] and ``w_uv`` [H,
        rank, v] for the absorbed attention (``models.llama.latent_project``:
        no key or value of a cached position is ever expanded). The two are
        multiplied per head against 20 small operands, which no Q40 kernel
        tiles, so they are kept DEQUANTISED in the matmul dtype: 2 B a weight
        where the file has 18/32 B (9.2 MB a layer against 2.6 MB at the
        published widths; a step reads them once). A layer with an indexer
        (``cfg.has_indexer``) rides the same two launches: the index key's and
        the index heads' weights' matrices (128 and 32 columns as published,
        narrower than any tile) stand behind q_a|kv_a in ``qkv_a``, the index
        heads' queries behind ``q_b``; the index key's LayerNorm stays f32."""
        p = f"layers.{l}."
        H, nope, v = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
        kv_b = reader.tensor(p + "kv_b").reshape(H, nope + v, cfg.kv_lora_rank)
        down, up = [p + "q_a", p + "kv_a"], [p + "q_b"]
        if cfg.has_indexer:
            down, up = down + [p + "index_k", p + "index_w"], up + [p + "index_q"]
        lp = {"qkv_a": fused(down), "q_a_norm": f32(p + "q_a_norm"),
              "kv_a_norm": f32(p + "kv_a_norm"),
              "q_b": fused(up) if cfg.has_indexer else weight(p + "q_b"),
              "w_uk": cast(np.ascontiguousarray(kv_b[:, :nope])),
              "w_uv": cast(np.ascontiguousarray(kv_b[:, nope:].transpose(0, 2, 1))),
              "wo": weight(p + "wo"),
              "rms_att": norm(p + "rms_att"), "rms_ffn": norm(p + "rms_ffn")}
        if cfg.has_indexer:
            lp["index_k_norm"] = f32(p + "index_k_norm")
        if cfg.layer_kind(l)[1] == "dense":
            lp["gate_up"] = fused([p + "gate", p + "up"])
            lp["down"] = weight(p + "down")
        else:
            lp.update(held_experts(p))
        return lp

    def ssm_layer(l: int) -> dict:
        """One ``ArchType.GRANITE_HYBRID`` layer: the state-space mixer's ONE
        input matrix as the file has it (``ssm_in``: z | x|B|C | dt, 8512 rows
        as published, which the Q40 kernel pads to 9 tiles of 1024: one launch
        with 8 % of its columns zero, against three launches with more padding
        each if it were split) or q|k|v of a softmax layer as one matrix, and a
        dense SwiGLU or, in a file with experts, the held experts' leaves behind
        either mixer; the recurrence's vectors stay float32."""
        p = f"layers.{l}."
        if cfg.is_softmax_layer(l):
            lp = {"qkv": fused([p + "q", p + "k", p + "v"])}
        else:
            lp = {"ssm_in": weight(p + "ssm_in")}
            lp.update({k: f32(p + k) for k in
                       ("conv", "conv_bias", "dt_bias", "a_log", "ssm_d", "ssm_norm")})
        lp["wo"] = weight(p + "wo")
        if cfg.layer_kind(l)[1] == "experts":
            lp.update(held_experts(p))
        else:
            lp.update({"gate_up": fused([p + "gate", p + "up"]), "down": weight(p + "down")})
        lp.update({"rms_att": norm(p + "rms_att"), "rms_ffn": norm(p + "rms_ffn")})
        return lp

    def next_token_head():
        """Rows 0 .. vocab_size - 1 of an output matrix of several prediction
        heads: the next token's. The others (self-speculation over the tokens
        after it) are in the file and not served: a step yields one token."""
        if quantized:
            from distributed_llama_tpu.ops.q40 import pack_q40_raw

            return pack_q40_raw(reader.raw_rows("wcls", 0, cfg.vocab_size), (cfg.vocab_size, cfg.dim))
        return cast(_t(reader.tensor_rows("wcls", 0, cfg.vocab_size), np.float32))

    by_layer = {ArchType.SOLAR_OPEN2: hybrid_layer, ArchType.EXAONE_MOE: window_layer,
                ArchType.EVABYTE: eva_layer, ArchType.GLM4_MOE_LITE: latent_layer,
                ArchType.GRANITE_HYBRID: ssm_layer}
    if cfg.arch in by_layer:
        from distributed_llama_tpu.models.llama import refuse_latent, refuse_recurrent

        if tp > 1:
            refuse_recurrent(cfg, f"tensor parallelism (--tp {tp})")
            refuse_latent(cfg, f"tensor parallelism (--tp {tp})")
        layer = by_layer[cfg.arch]
        return {
            "embedding": reader.tensor("embedding").astype(np.float32),
            "layers": [layer(l) for l in range(cfg.n_layers)],
            "rms_final": norm("rms_final"),
            "wcls": next_token_head() if cfg.arch == ArchType.EVABYTE else weight("wcls"),
            "rope_table": build_rope_table(cfg),
        }
    if cfg.has(ArchFlags.NORM_UNIT_OFFSET):
        raise ValueError("norms with a unit offset (ArchFlags.NORM_UNIT_OFFSET) are read for the "
                         "archs whose layers load one by one, not for this one")

    layers: dict[str, list] = {}

    def add(key: str, value) -> None:
        layers.setdefault(key, []).append(value)

    for l in range(cfg.n_layers):
        p = f"layers.{l}."
        lpath = f"layers/{l}"
        if quantized and tp > 1:
            add("qkv", sharded(f"{lpath}/qkv", [p + "q", p + "k", p + "v"]))
            add("wo", sharded(f"{lpath}/wo", p + "wo"))
        elif quantized:
            add("qkv", weight_fused([p + "q", p + "k", p + "v"]))
            add("wo", weight(p + "wo"))
        elif tp > 1:
            add("q", sharded_plain(f"{lpath}/q", p + "q"))
            add("k", sharded_plain(f"{lpath}/k", p + "k"))
            add("v", sharded_plain(f"{lpath}/v", p + "v"))
            add("wo", sharded_plain(f"{lpath}/wo", p + "wo"))
        else:
            add("q", weight(p + "q"))
            add("k", weight(p + "k"))
            add("v", weight(p + "v"))
            add("wo", weight(p + "wo"))
        add("rms_att", reader.tensor(p + "rms_att").astype(np.float32))
        add("rms_ffn", reader.tensor(p + "rms_ffn").astype(np.float32))
        if cfg.is_moe and quantized:
            # per-expert fused gate|up + down QuantizedMatrix leaves: the
            # expert banks stay 4-bit in HBM (the reference keeps experts Q40
            # too, src/transformer.cpp:335-353) and the top-k decode path
            # switches between per-expert kernels (models/moe.py)
            add("router", cast(_t(reader.tensor(p + "moe_router"), np.float32)))
            experts = []
            for e in range(cfg.n_experts):
                ep = f"{p}experts.{e}."
                if tp > 1:
                    experts.append({
                        "gate_up": sharded(
                            f"{lpath}/experts/{e}/gate_up", [ep + "gate", ep + "up"]
                        ),
                        "down": sharded(f"{lpath}/experts/{e}/down", ep + "down"),
                    })
                else:
                    experts.append({
                        "gate_up": weight_fused([ep + "gate", ep + "up"]),
                        "down": weight(ep + "down"),
                    })
            add("experts", experts)
        elif cfg.is_moe and tp > 1:
            add("router", cast(_t(reader.tensor(p + "moe_router"), np.float32)))
            enames = [f"{p}experts.{e}." for e in range(cfg.n_experts)]
            add("moe_up", sharded_plain_expert_stack(
                f"{lpath}/moe_up", [n + "up" for n in enames]))
            add("moe_gate", sharded_plain_expert_stack(
                f"{lpath}/moe_gate", [n + "gate" for n in enames]))
            add("moe_down", sharded_plain_expert_stack(
                f"{lpath}/moe_down", [n + "down" for n in enames]))
        elif cfg.is_moe:
            add("router", cast(_t(reader.tensor(p + "moe_router"), np.float32)))
            ups, gates, downs = [], [], []
            for e in range(cfg.n_experts):
                ep = f"{p}experts.{e}."
                ups.append(_t(reader.tensor(ep + "up"), np.float32))
                gates.append(_t(reader.tensor(ep + "gate"), np.float32))
                downs.append(_t(reader.tensor(ep + "down"), np.float32))
            add("moe_up", cast(np.stack(ups)))
            add("moe_gate", cast(np.stack(gates)))
            add("moe_down", cast(np.stack(downs)))
        elif quantized and tp > 1:
            add("gate_up", sharded(f"{lpath}/gate_up", [p + "gate", p + "up"]))
            add("down", sharded(f"{lpath}/down", p + "down"))
        elif quantized:
            add("gate_up", weight_fused([p + "gate", p + "up"]))
            add("down", weight(p + "down"))
        elif tp > 1:
            add("gate", sharded_plain(f"{lpath}/gate", p + "gate"))
            add("down", sharded_plain(f"{lpath}/down", p + "down"))
            add("up", sharded_plain(f"{lpath}/up", p + "up"))
        else:
            add("gate", weight(p + "gate"))
            add("down", weight(p + "down"))
            add("up", weight(p + "up"))
        if cfg.arch == ArchType.GROK1:
            add("rms_moe", reader.tensor(p + "rms_moe").astype(np.float32))
            add("rms_ffn2", reader.tensor(p + "rms_ffn2").astype(np.float32))

    # layers stay UNSTACKED for every dtype (a list of per-layer dicts,
    # consumed by an unrolled layer loop). For q40, scan-slicing a stacked
    # array would make XLA hoist layout copies of every sliced Pallas operand
    # (observed OOM on v5e); for bf16, the lax.scan-over-stacked-layers path
    # showed ~19 ms/token of pipeline stalls on v5e (profiled round 3) —
    # per-layer leaves keep weight streams and cache updates alias-friendly.
    layers_out: Any = [
        {k: vs[l] for k, vs in layers.items()} for l in range(cfg.n_layers)
    ]
    if quantized and shard_vocab:
        wcls = sharded("wcls", ["wcls"])  # vocab-sharded logits head
    elif shard_vocab:
        wcls = sharded_plain("wcls", "wcls")
    else:
        wcls = weight("wcls")
    return {
        "embedding": reader.tensor("embedding").astype(np.float32),
        "layers": layers_out,
        "rms_final": reader.tensor("rms_final").astype(np.float32),
        "wcls": wcls,
        "rope_table": build_rope_table(cfg),
    }


def _synthetic_params(
    cfg: LlamaConfig, mat, ones, embedding, rope_table, layered: bool = False
) -> Params:
    """Shared structure for the synthetic-param builders: the single source of
    truth for the pytree shape, kept in lockstep with load_params. ``mat``,
    ``ones``, ``embedding`` are array factories (host numpy or on-device).

    ``layered=True`` builds the production per-layer-list layout directly
    (generating stacked then slicing would transiently double HBM on a
    7B-scale synthetic model)."""
    D, H, K, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_size
    L, F, V = cfg.n_layers, cfg.hidden_dim, cfg.vocab_size

    def layer_tree():
        tree = {
            "q": mat(D, H * hd),
            "k": mat(D, K * hd),
            "v": mat(D, K * hd),
            "wo": mat(H * hd, D),
            "rms_att": ones(D),
            "rms_ffn": ones(D),
        }
        if cfg.is_moe:
            E = cfg.n_experts
            tree.update(
                router=mat(D, E),
                moe_up=mat(E, D, F),
                moe_gate=mat(E, D, F),
                moe_down=mat(E, F, D),
            )
        else:
            tree.update(gate=mat(D, F), down=mat(F, D), up=mat(D, F))
        if cfg.arch == ArchType.GROK1:
            tree.update(rms_moe=ones(D), rms_ffn2=ones(D))
        return tree

    if layered:
        layers: Any = [layer_tree() for _ in range(L)]
    else:
        per_layer = [layer_tree() for _ in range(L)]
        layers = {
            k: np.stack([pl[k] for pl in per_layer])
            if isinstance(per_layer[0][k], np.ndarray)
            else jnp.stack([pl[k] for pl in per_layer])
            for k in per_layer[0]
        }
    return {
        "embedding": embedding(V, D),
        "layers": layers,
        "rms_final": ones(D),
        "wcls": mat(D, V),
        "rope_table": rope_table,
    }


def q40_padded_bytes(params: Params) -> dict[str, int]:
    """Bytes of the Q40 leaves of ``params`` that are tile padding, by the
    leaf's name (``dllama_q40_padded_weight_bytes{role}``): a pack's nibbles
    and scales hold ``n_padded x d_padded`` weights of which ``n x d`` are the
    matrix's (``ops.q40._n_padded`` / ``_d_padded``); the rest are zero-scale
    rows and columns the kernel's tiles read like any. Every Q40 leaf's name
    is in the result, 0 where its matrices divide their tiles."""
    from distributed_llama_tpu.ops.q40 import QuantizedMatrix

    out: dict[str, int] = {}

    def walk(name: str, node) -> None:
        if isinstance(node, QuantizedMatrix):
            held = math.prod(node.qs.shape) + 4 * math.prod(node.scales.shape)
            used = node.n * node.d / (node.n_padded * node.d_padded)
            out[name] = out.get(name, 0) + round(held * (1.0 - used))
        elif isinstance(node, dict):
            for key, value in node.items():
                walk(key, value)
        elif isinstance(node, (list, tuple)):
            for value in node:
                walk(name, value)

    walk("", params)
    return out


def random_params(
    cfg: LlamaConfig, dtype=jnp.bfloat16, seed: int = 0, layered: bool = False
) -> Params:
    """Synthetic host-side params pytree with the exact structure/shapes of
    load_params. Used by tests and the multichip dry-run."""
    rng = np.random.RandomState(seed)
    np_dtype = np.dtype(dtype)

    def mat(*shape):
        scale = 1.0 / np.sqrt(shape[-2] if len(shape) > 1 else shape[-1])
        return (rng.randn(*shape) * scale).astype(np_dtype)

    def ones(*shape):
        return np.ones(shape, np.float32)

    def embedding(V, D):
        return (rng.randn(V, D) * 0.02).astype(np.float32)

    return _synthetic_params(
        cfg, mat, ones, embedding, build_rope_table(cfg), layered=layered
    )


def random_params_on_device(
    cfg: LlamaConfig, dtype=jnp.bfloat16, seed: int = 0, layered: bool = False
) -> Params:
    """Like :func:`random_params` but generated with jax.random directly on
    the accelerator — no host RNG time and no host-to-device transfer. Used by
    the benchmark, where a 7B-parameter tree would otherwise take minutes to
    synthesize and ship."""
    import jax

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16 * cfg.n_layers + 16))

    def mat(*shape):
        scale = 1.0 / np.sqrt(shape[-2] if len(shape) > 1 else shape[-1])
        # generate directly in the target dtype: an f32 intermediate of the
        # largest stacked tensor would transiently cost 2x its bf16 size
        return jax.random.normal(next(keys), shape, dtype=dtype) * jnp.asarray(scale, dtype)

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    def embedding(V, D):
        return jax.random.normal(next(keys), (V, D), dtype=jnp.float32) * 0.02

    return _synthetic_params(
        cfg, mat, ones, embedding, jnp.asarray(build_rope_table(cfg)), layered=layered
    )

