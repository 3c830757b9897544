"""Llama-family transformer forward pass as a pure, jit-compiled function.

Capability parity with the reference's root+worker task lists
(reference: src/llama2-tasks.cpp:241-298) re-designed TPU-first:

* The reference runs 25 host tasks per layer in thread lock-step; here one
  ``lax.scan`` over stacked layer weights compiles the whole token step into a
  single XLA program (weights stacked on a leading layer axis).
* The reference prefills one token at a time (src/apps/dllama/dllama.cpp:45-59);
  ``forward_tokens`` takes T tokens at once, so prefill is a batched matmul
  workload that actually uses the MXU.
* The reference's sync tasks (llamaSyncAtt/llamaSyncFfn2 gathers + merge adds,
  src/llama2-tasks.cpp:115-131, 196-212) collapse into ``jax.lax.psum`` calls
  keyed by ``axis_name`` — a single ICI all-reduce instead of two TCP hops.
  With ``axis_name=None`` the same code is the single-chip program.

Numerical conventions matching the reference kernels:
  rmsnorm eps 1e-5 added to mean-square (src/funcs.cpp:120-122);
  attention scores scaled by 1/sqrt(head_size) (src/llama2-tasks.cpp:72);
  SwiGLU silu(w1 x) * (w3 x) then w2 (src/llama2-tasks.cpp:158-189). The
  reference's `hiddenDim == GELU` comparison bug (src/llama2-tasks.cpp:169)
  means its runtime always takes the silu path; we dispatch on hidden_act
  correctly.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from distributed_llama_tpu.formats.model_file import STATE_MIXERS, HiddenAct
from distributed_llama_tpu.models.config import LlamaConfig
from distributed_llama_tpu.models.rope import apply_rope

Params = dict[str, Any]

# key-axis chunk of the blocked dense attention (ops.attention): caches whose
# seq_len is a multiple of this use the online-softmax path with a dynamic
# chunk bound; smaller/odd caches (tiny test models) keep the full-S einsum.
# Measured on the real v5e (7B q40, S=2048, round 5): decode 10.0 vs 17.8
# ms/token at pos 256 and 11.6 vs 18.2 at pos 1800 — the full-S einsum both
# reads dead slots AND runs the masked softmax over all of S. For batched
# prefill the fori_loop serialization loses slightly (17.1 vs 15.2 ms at
# T=64), so T>8 keeps the einsum until S is long enough that dead-slot reads
# dominate (ATT_BLOCK_PREFILL_S). chunk 1024 measured no better (11.5 late,
# 10.8 early).
ATT_CHUNK = 512
ATT_BLOCK_PREFILL_S = 4096  # blocked attention for T>8 from this seq_len up


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """y = w * x / sqrt(mean(x^2) + eps), computed in f32
    (reference: src/funcs.cpp:95-146 — note eps is added to the mean square).
    Delegates to ``ops.q40.rmsnorm_ref`` — the ONE rmsnorm definition, so
    the fused rmsnorm→Q80→matmul entry (:func:`_norm_matmul`) is
    bit-identical to this by construction."""
    from distributed_llama_tpu.ops.q40 import rmsnorm_ref

    return rmsnorm_ref(x, weight, eps)


def _activation(x: jax.Array, act: HiddenAct) -> jax.Array:
    if act == HiddenAct.GELU:
        # tanh-approximated gelu (reference: src/funcs.cpp:501-509)
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def _matmul(x: jax.Array, w, role: str | None = None) -> jax.Array:
    """x [T, n] @ w [n, d] with f32 accumulation on the MXU. ``role``
    (``wqkv``, ``wo``, ``gate_up``, ``down``, ``experts``, ``logits``) names
    the matrix in the device trace: a static string, in the Q40 kernel's
    name and as a named scope around the plain dot.

    ``w`` is a plain array (bf16/f32) or a Q40 :class:`QuantizedMatrix`,
    which routes to the fused Pallas kernel (weights stay 4-bit in HBM).
    precision=HIGHEST keeps f32 operands in true f32 on TPU (parity mode);
    it is a no-op for the production bf16 path."""
    from distributed_llama_tpu.ops.q40 import QuantizedMatrix, q40_matmul

    if isinstance(w, QuantizedMatrix):
        return q40_matmul(x, w, role=role)
    with jax.named_scope(role or "matmul"):
        return jax.lax.dot_general(
            x,
            w,
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )


def _norm_matmul(
    x: jax.Array, weight: jax.Array, w, role: str | None = None
) -> jax.Array:
    """rmsnorm(x, weight) @ w — ONE fused program on the q40 int8 path
    (the decode superstep's part (a): the Q80 activation quantize rides
    the rmsnorm epilogue instead of paying its own program dispatch,
    ``ops.q40.rmsnorm_q40_matmul``); the unfused reference sequence for
    plain-array weights. Bit-identical either way (the fused entry inlines
    ``rmsnorm_ref``'s exact ops — test-enforced)."""
    from distributed_llama_tpu.ops.q40 import QuantizedMatrix, rmsnorm_q40_matmul

    if isinstance(w, QuantizedMatrix):
        return rmsnorm_q40_matmul(x, weight, w, role=role)
    return _matmul(rmsnorm(x, weight).astype(w.dtype), w, role)


def project_qkv(
    cfg: LlamaConfig,
    lp: Params,
    x: jax.Array,
    rope_rows: jax.Array,
    layer: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Norm + QKV projection + rope for T tokens: [T, dim] ->
    (q [T, Hl, hd], k [T, Kl, hd], v [T, Kl, hd]). Shared by the dense,
    tensor-parallel and sequence-parallel attention paths (the reference's
    llamaRmsAtt/llamaQkv/llamaRope chain, src/llama2-tasks.cpp:10-52)."""
    return project_qkvg(cfg, lp, x, rope_rows, layer)[:3]


def _head_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMS norm of every head [T, H, hd] over its hd values, times the
    layer's learned weight [hd], in f32."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def project_qkvg(
    cfg: LlamaConfig,
    lp: Params,
    x: jax.Array,
    rope_rows: jax.Array,
    layer: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array | None]:
    """:func:`project_qkv` and, where the layer gates its attention output
    per channel (``qkvg``: q|k|v|gate as one matrix), the gate's
    pre-activation [T, Hl*hd], else None. Where the layer normalises its
    heads (``q_norm``/``k_norm`` in its params) that comes before the
    rotation. ``layer``: the layer's index, for an arch whose layers do not
    all rotate (``cfg.rotates``); None = what the arch says of every layer."""
    T = x.shape[0]
    hd = cfg.head_size
    gate = None
    if "qkvg" in lp:
        fused = _norm_matmul(x, lp["rms_att"], lp["qkvg"], "wqkv")
        d_q = lp["wo"].shape[-2]
        d_kv = cfg.n_kv_heads * hd
        q = fused[:, :d_q]
        k = fused[:, d_q : d_q + d_kv]
        v = fused[:, d_q + d_kv : d_q + 2 * d_kv]
        gate = fused[:, d_q + 2 * d_kv : 2 * d_q + 2 * d_kv]
    elif "qkv" in lp:
        # q|k|v packed as one matmul on the output dim (the q40 path: one
        # large bandwidth-efficient kernel call instead of three small
        # ones) — and the norm + Q80 quantize fused into that same program
        # on the int8 path (_norm_matmul)
        fused = _norm_matmul(x, lp["rms_att"], lp["qkv"], "wqkv")  # [T, (Hl+2*Kl)*hd] f32
        d_q = lp["wo"].shape[-2]  # Hl*hd (wo's input dim)
        d_kv = (fused.shape[-1] - d_q) // 2
        q = fused[:, :d_q]
        k = fused[:, d_q : d_q + d_kv]
        v = fused[:, d_q + d_kv :]
    else:
        # three consumers of one normed activation: the norm cannot ride a
        # single matmul's epilogue here, so it stays standalone
        xc = rmsnorm(x, lp["rms_att"]).astype(lp["q"].dtype)
        q = _matmul(xc, lp["q"], "wqkv")  # [T, Hl*hd] f32
        k = _matmul(xc, lp["k"], "wqkv")  # [T, Kl*hd]
        v = _matmul(xc, lp["v"], "wqkv")  # [T, Kl*hd]
    Hl = q.shape[-1] // hd
    Kl = k.shape[-1] // hd
    q, k = q.reshape(T, Hl, hd), k.reshape(T, Kl, hd)
    if "q_norm" in lp:
        q, k = _head_norm(q, lp["q_norm"]), _head_norm(k, lp["k_norm"])
    if cfg.use_rope if layer is None else cfg.rotates(layer):
        q, k = apply_rope(q, rope_rows, cfg), apply_rope(k, rope_rows, cfg)
    v = v.reshape(T, Kl, hd)
    if cfg.kv_head_pack > 1:
        q, k, v = _share_rows(cfg, q, k, v)
    if cfg.attn_scale or cfg.kv_head_pack > 1:
        # every scan and einsum behind this divides its scores by the root of
        # the head size it is given: a file that states another softmax scale
        # (``cfg.softmax_scale``), and heads that share a row, get the quotient
        # of the two on their q, once, for all of them
        q = q * (cfg.softmax_scale * cfg.cache_head_size ** 0.5)
    return q, k, v, gate


def _share_rows(cfg: LlamaConfig, q: jax.Array, k: jax.Array, v: jax.Array):
    """``cfg.kv_head_pack`` kv heads side by side as one cache row (q [T, H,
    hd], k, v [T, K, hd] -> q [T, H, pack * hd], k, v [T, K / pack, pack *
    hd]): kv head ``k`` is part ``k % pack`` of row ``k // pack``, and a query
    head holds its values in its kv head's part and zeros in the others, so
    its score with the row is its score with its own head. The query heads of
    a row's kv heads are consecutive: the grouped reshape the scans use holds."""
    T, H, hd = q.shape
    K, pack = k.shape[1], cfg.kv_head_pack
    mine = jax.nn.one_hot(_row_part(H, K, pack), pack, dtype=q.dtype)  # [H, pack]
    q = (q[:, :, None, :] * mine[None, :, :, None]).reshape(T, H, pack * hd)
    return q, k.reshape(T, K // pack, pack * hd), v.reshape(T, K // pack, pack * hd)


def _row_part(H: int, K: int, pack: int) -> jax.Array:
    """[H]: which part of its cache row a query head reads: its kv head's."""
    return (jnp.arange(H) // (H // K)) % pack


def _own_part(cfg: LlamaConfig, att: jax.Array, H: int) -> jax.Array:
    """The attention mix over shared rows [T, H * pack * hd] -> [T, H * hd]:
    of the row's weighted sum, a query head keeps its own kv head's part."""
    pack = cfg.kv_head_pack
    if pack == 1:
        return att
    T, hd = att.shape[0], cfg.head_size
    part = _row_part(H, cfg.n_kv_heads, pack)
    parts = att.reshape(T, H, pack, hd)
    return jnp.take_along_axis(parts, part[None, :, None, None], axis=2).reshape(T, H * hd)


def _gated(att: jax.Array, gate: jax.Array | None) -> jax.Array:
    """The attention mix [..., Hl*hd] times sigmoid of its gate, where the
    layer has one."""
    return att if gate is None else att * jax.nn.sigmoid(gate.reshape(att.shape))


def block_tail(
    cfg: LlamaConfig,
    x: jax.Array,
    att: jax.Array,
    lp: Params,
    axis_name: str | None,
    ep_axis: str | None = None,
    n_real: jax.Array | None = None,
) -> jax.Array:
    """Everything after the attention mix: wo projection (+psum under TP),
    the arch-dependent residual/norm placement, and the FFN/MoE half.
    ``att``: [T, Hl*hd]. ``ep_axis``: expert-parallel mesh axis — expert
    banks are sharded over it and the MoE FFN runs the dispatch/combine
    exchange (parallel.expert_parallel). ``n_real``: number of REAL rows in
    a bucket-padded batch (rows >= n_real are engine pad zeros) — the
    bucketed MoE prefill masks pads out of its expert buckets."""
    if axis_name is None:
        out = _matmul(att.astype(lp["wo"].dtype), lp["wo"], "wo")  # [T, dim]
    else:
        # the TP all-reduce: replaces gather + merge-add on root
        # (reference: src/llama2-tasks.cpp:115-131) with one ICI collective,
        # routed through the matmul+all-reduce seam (ops.collectives): the
        # unfused matmul + psum/ring_xla arms off-TPU, and under
        # DLT_ALLREDUCE=ring the fused int8+ring kernel whose per-chunk
        # epilogue starts the reduce-scatter DMAs while the next chunk's
        # MXU work is in flight (decode superstep, part b)
        from distributed_llama_tpu.ops import collectives

        out = collectives.matmul_all_reduce(
            att.astype(lp["wo"].dtype), lp["wo"], axis_name, role="wo"
        )
    if cfg.arch.name == "GROK1":
        # grok rmsnorms the attention output with rmsFfn before the residual
        # add (reference: src/grok1-tasks.cpp:16-41)
        x = x + rmsnorm(out.astype(x.dtype), lp["rms_ffn"])
    else:
        x = x + _branch(cfg, out).astype(x.dtype)
    if cfg.is_moe and "router" in lp:  # a leading dense layer of an expert arch has none
        from distributed_llama_tpu.models import moe

        x = moe.moe_block(cfg, x, lp, axis_name, ep_axis=ep_axis, n_real=n_real)
    else:
        x = x + _branch(cfg, ffn(cfg, x, lp, axis_name)).astype(x.dtype)
    return x


def _branch(cfg: LlamaConfig, out: jax.Array) -> jax.Array:
    """A block's output as it joins the residual stream: times
    ``cfg.residual_scale`` where the file states one."""
    return out if cfg.residual_scale == 1.0 else out * cfg.residual_scale


def final_logits(cfg: LlamaConfig, params: Params, x: jax.Array) -> jax.Array:
    """Final rmsnorm + logits head (+Grok's logit scale),
    reference: src/llama2-tasks.cpp:222-239, src/grok1-tasks.cpp:270-273.
    Norm + quantize + matmul fuse into one program on the q40 int8 path
    (_norm_matmul)."""
    logits = _norm_matmul(x, params["rms_final"], params["wcls"], "logits")
    if cfg.arch.name == "GROK1":
        logits = logits * 0.5773502691896257
    if cfg.logits_divisor != 1.0:
        logits = logits / cfg.logits_divisor
    return logits


def embed(cfg: LlamaConfig, params: Params, tokens: jax.Array) -> jax.Array:
    """Embedding row gather (+Grok's input scale, src/grok1-tasks.cpp:11-14)."""
    x = params["embedding"][tokens].astype(jnp.float32)
    if cfg.arch.name == "GROK1":
        x = x * 78.38367176906169
    if cfg.embed_scale != 1.0:
        x = x * cfg.embed_scale
    return x


def attention(
    cfg: LlamaConfig,
    x: jax.Array,
    lp: Params,
    cache_l,
    pos: jax.Array,
    rope_rows: jax.Array,
    axis_name: str | None,
    paged=None,
    layer: int | None = None,
    n_real: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Causal GQA attention for T new tokens at absolute positions
    pos..pos+T-1. ``cache_l``: this layer's cache — a ``(keys, values)``
    tuple of [S, Kl, hd] arrays (the layered layout, updated in place) or a
    stacked [2, S, Kl, hd] array (the lax.scan-over-layers layout); returns
    (attention mix [T, Hl*hd], updated cache in the same form).

    ``paged``: ``(pool_k, pool_v, table, matched)`` — zero-copy prefix
    aliasing for a slab row whose positions below ``matched`` live in the
    shared page pool (read through the page table) rather than the row
    itself. Blocked caches take the segmented paged scan; small/odd caches
    read a virtual row view (``kv_cache.virtual_row``) through the SAME
    einsum path, so both are bit-identical to a row holding page copies.

    ``layer``: the layer's index (``cfg.layer_kind``). A WINDOW layer's
    ``cache_l`` is a ring [2, R, Kl, hd]: the tokens' K/V go to their
    positions' slots and each query sees the ``cfg.window`` positions up to
    its own (``ops.attention.window_attention``); it reads no pool. An EVA
    layer's is [2, window + summaries, Kl, hd] (``cfg.eva_slots``): the
    tokens' K/V go to their positions' window slots, the chunks they complete
    are summarised behind them, and a query reads its aligned window and the
    earlier windows' summaries (``ops.attention.eva_prefill_attention``); rows
    at and past ``n_real`` (a padded piece) write nothing there. A prefix hit
    COPIES what it needs into the row, so it reads no pool either.

    Mirrors llamaQkv/llamaRope/llamaMultiheadAtt/llamaAtt
    (reference: src/llama2-tasks.cpp:33-108) with the per-timestep score loop
    replaced by one masked einsum over the whole cache.
    """
    from distributed_llama_tpu.ops import kv_cache as kvc

    T = x.shape[0]
    S = cache_l[0].shape[0]  # works for tuple (keys, values) and stacked [2, S, ...] forms
    hd = cfg.cache_head_size  # of a cache row: the head's, or of the heads that share it
    q, k, v, gate = project_qkvg(cfg, lp, x, rope_rows, layer)
    Hl, Kl = q.shape[1], k.shape[1]

    if cfg.is_window_layer(layer):
        from distributed_llama_tpu.ops.attention import window_attention

        if not kvc.is_fused_leaf(cache_l):
            raise ValueError("a window layer's ring is a fused leaf (the layered cache)")
        new_cache = kvc.ring_update_rows(cache_l, k, v, pos)
        qg = q.reshape(T, Kl, Hl // Kl, hd).astype(jnp.float32)
        att = window_attention(qg, new_cache, pos, cfg.window).astype(jnp.float32)
        return _gated(att.reshape(T, Hl * hd), gate), new_cache

    if cfg.has_eva:
        from distributed_llama_tpu.ops.attention import eva_prefill_attention

        att, new_cache = eva_prefill_attention(
            q.reshape(T, Kl, Hl // Kl, hd).astype(jnp.float32), k, v, cache_l, pos, n_real,
            lp["eva_phi"], lp["eva_mu"], cfg.window, cfg.eva_chunk, cfg.eva_scan_chunk,
        )
        return att.reshape(T, Hl * hd), new_cache

    if kvc.is_fused_leaf(cache_l):
        # fused [2, S, Kl, hd] leaf: keys AND values land in ONE coalesced
        # dynamic_update_slice (the leading 2-axis is fully covered, so the
        # donated leaf aliases in place — unlike updating the two halves
        # separately and re-stacking, which copies the layer's entire cache).
        # This halves the per-layer update op count PERF.md puts on the
        # decode critical path, and a T>1 verify window writes all of its
        # draft K/V in the same single update.
        new_cache = kvc.fused_update_rows(cache_l, k, v, pos)
        keys, values = new_cache[0], new_cache[1]
    else:
        # per-layer TUPLE caches (the tp/sp/ep backends' sharded layout)
        # update in place per half
        keys = kvc.update_rows(cache_l[0], k, pos)  # [S, Kl, hd]
        values = kvc.update_rows(cache_l[1], v, pos)
        new_cache = (keys, values)

    kv_mul = Hl // Kl
    # score/value einsums run with operands in the CACHE dtype (bf16 for an
    # i8 cache — the HBM reads stay int8/bf16 either way) and f32
    # accumulation: casting a narrow cache to f32 first would materialize
    # 2-4x the cache bytes per layer per token (the attention reads are the
    # second-largest HBM stream after the weights). f32 caches (parity
    # tests) keep true-f32 multiplies via HIGHEST.
    cdt = kvc.compute_dtype(keys)
    prec = kvc.einsum_precision(keys)
    qg = q.reshape(T, Kl, kv_mul, hd).astype(cdt)
    use_blocked = (
        S % ATT_CHUNK == 0
        and S > ATT_CHUNK
        and (T <= 8 or S >= ATT_BLOCK_PREFILL_S)
    )
    if paged is not None and not (
        use_blocked and ATT_CHUNK % kvc.pool_page_size(paged[0]) == 0
    ):
        # general fallback: a virtual row view selecting pool bytes below
        # ``matched`` and the slab beyond, fed through the unchanged paths
        pool_k, pool_v, table, matched = paged
        keys = kvc.virtual_row(keys, pool_k, table, matched)
        values = kvc.virtual_row(values, pool_v, table, matched)
        paged = None
    if use_blocked:
        # blocked (flash-style) attention with a DYNAMIC chunk bound: no
        # [T, S] score tensor materializes and slots beyond pos+T are never
        # read — the full-S einsum below reads the entire allocated cache
        # every call (S*K*hd*2 dtype-bytes per half per layer), which at
        # long seq_len dwarfs the live context (see ATT_CHUNK note above
        # for the measured decode/prefill split); with ``paged`` still
        # set, the same call reads the matched prefix through the page
        # table (blocked_attention treats paged=None as the plain scan)
        from distributed_llama_tpu.ops.attention import blocked_attention

        att = blocked_attention(
            qg.astype(jnp.float32), keys, values, pos, ATT_CHUNK, paged=paged
        ).astype(jnp.float32).reshape(T, Hl * hd)
        return _gated(_own_part(cfg, att, Hl), gate), new_cache
    scores = kvc.scores_einsum(qg, keys, prec) / jnp.sqrt(jnp.float32(hd))
    # causal mask: query t (absolute pos+t) sees cache slots 0..pos+t
    t_idx = pos + jnp.arange(T)[:, None]
    s_idx = jnp.arange(S)[None, :]
    mask = s_idx <= t_idx  # [T, S]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    att = kvc.mix_einsum(weights, values, cdt, prec).reshape(T, Hl * hd)
    return _gated(_own_part(cfg, att, Hl), gate), new_cache


def _per_head(x: jax.Array, w: jax.Array) -> jax.Array:
    """x [T, H, a] times a matrix a head, w [H, a, b] -> [T, H, b] f32 (the
    head leads both operands: the one batched form every backend's dot takes
    at bfloat16)."""
    out = jnp.einsum(
        "hta,hab->htb", jnp.swapaxes(x, 0, 1).astype(w.dtype), w,
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    return jnp.swapaxes(out, 0, 1)


def _layernorm(x: jax.Array, weight_bias: jax.Array, eps: float = 1e-6) -> jax.Array:
    """LayerNorm over the last axis in f32; ``weight_bias`` [2, n]: the weight, then the bias."""
    x = x.astype(jnp.float32)
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    normed = centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)
    return normed * weight_bias[0] + weight_bias[1]


def _rope_head(x: jax.Array, rope_rows: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """x [T, heads, n]: the first ``cfg.rope_dim`` values of every head rotated, the rest kept."""
    r = cfg.rope_dim
    return jnp.concatenate([apply_rope(x[..., :r], rope_rows, cfg), x[..., r:]], axis=-1)


def latent_project(
    cfg: LlamaConfig, lp: Params, x: jax.Array, rope_rows: jax.Array
) -> tuple[jax.Array, dict, tuple | None]:
    """Norm + the latent layer's projections for T tokens: [T, dim] ->
    (ABSORBED queries [T, H, latent_dim] f32, the tokens' cache rows ``{LATENT:
    [T, latent_dim]}`` f32, None).

    ``[c_q | c | k_r] = rmsnorm(x) W_a`` (one matrix, ``qkv_a``); ``q =
    rmsnorm(c_q) W_qb``, a head ``[q_nope | q_rope]``; ``c_kv = rmsnorm(c)``.
    The cache row of a position is ``[c_kv | rot(k_r)]``: ONE rotated key slice
    for every head. A head's query against such a row is ``[q_nope W_UK |
    rot(q_rope)]`` (``w_uk`` the head's nope-key rows of the keys' and values'
    up-projection): ``q_nope . (c_kv W_UK^T) = (q_nope W_UK) . c_kv``, so no key
    is expanded for a cached position.

    A layer with an indexer (``cfg.has_indexer``) reads two more slices of the
    same two launches: behind ``k_r`` the index key ``k_I = layernorm(u W_Ik)``
    (its first ``rope_dim`` values rotated; the second cache row, ``INDEX``)
    and the index heads' weights ``w = u W_Iw``; behind ``q`` the index heads
    ``q_I = rmsnorm(c_q) W_Iq``, each head's first ``rope_dim`` values rotated.
    The third result is then ``(q_I [T, J, I], w [T, J])``, both f32."""
    from distributed_llama_tpu.ops import kv_cache as kvc

    T = x.shape[0]
    H, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    with jax.named_scope("mla_project"):
        fused = _norm_matmul(x, lp["rms_att"], lp["qkv_a"], "wqkv")
        c_q = rmsnorm(fused[:, :qr], lp["q_a_norm"])
        c_kv = rmsnorm(fused[:, qr : qr + kr], lp["kv_a_norm"])
        k_rope = apply_rope(fused[:, None, qr + kr : qr + kr + rope], rope_rows, cfg)[:, 0]
        up = _matmul(c_q.astype(lp["q_b"].dtype), lp["q_b"], "mla_project")
        q = up[:, : H * (nope + rope)].reshape(T, H, nope + rope)
        q_rope = apply_rope(q[..., nope:], rope_rows, cfg)
        q_abs = _per_head(q[..., :nope], lp["w_uk"])
        queries = jnp.concatenate([q_abs, q_rope], axis=-1)
        rows = {kvc.LATENT: jnp.concatenate([c_kv, k_rope], axis=-1)}
        if not cfg.has_indexer:
            return queries, rows, None
        J, I = cfg.index_n_heads, cfg.index_head_dim
        at = qr + kr + rope
        k_idx = _layernorm(fused[:, at : at + I], lp["index_k_norm"])
        rows[kvc.INDEX] = _rope_head(k_idx[:, None], rope_rows, cfg)[:, 0]
        q_idx = up[:, H * (nope + rope) : H * (nope + rope) + J * I].reshape(T, J, I)
        return queries, rows, (_rope_head(q_idx, rope_rows, cfg), fused[:, at + I : at + I + J])


def latent_output(cfg: LlamaConfig, lp: Params, mix: jax.Array) -> jax.Array:
    """The scan's result [T, H, latent_dim] (softmax-weighted sums of cache
    rows) -> the heads' outputs [T, H * v]: the first ``kv_lora_rank`` columns
    are ``sum p c_kv``, which a head's value rows of the up-projection
    (``w_uv``) take to its ``v_head_dim`` values; the columns behind them (the
    key slice's) are dropped. No value is expanded for a cached position."""
    return _per_head(mix[..., : cfg.kv_lora_rank], lp["w_uv"]).reshape(mix.shape[0], -1)


# positions one step of a latent scan reads: a row of 576 values is a quarter
# of the keys and values the full layers' chunk of 512 positions was measured
# with (8 heads of 128, twice), so four times the positions move the same
# bytes a step; at 512 a step of the scan cost 21 us for 4.7 MB on the chip
# (225 GB/s: fixed costs of its three fusions), PERF.md section 6, PR 43
LATENT_CHUNK = 4 * ATT_CHUNK


def _latent_chunk(S: int) -> int:
    """Positions one step of a latent scan reads: ``LATENT_CHUNK``, the full
    layers' chunk where the cache is no multiple of that, or a small or odd
    cache (the tests' toy models) whole."""
    for chunk in (LATENT_CHUNK, ATT_CHUNK):
        if S % chunk == 0 and S > chunk:
            return chunk
    return S


def latent_attention(
    cfg: LlamaConfig, x: jax.Array, lp: Params, cache_l: dict, pos: jax.Array,
    rope_rows: jax.Array,
) -> tuple[jax.Array, dict]:
    """Latent attention for T new tokens of ONE row at positions pos..pos+T-1.
    ``cache_l``: the row's latent leaf ``{LATENT: [latent_dim, S]}``. The
    tokens' rows are written, then every query reads the row up to itself
    ABSORBED, as decode does and through the same scan
    (``ops.attention.latent_attention_scan``): against expanding a chunk of
    512 cached positions to keys and values for a piece of T rows (9.2 MFLOP a
    position), absorbing costs T x 23 kFLOP a position more in scores and mix,
    less below T of about 400, and a piece has at most 256. The published
    softmax scale is ``head_size ** -0.5`` (``head_size`` = nope + rope).
    A layer with an indexer writes the tokens' index keys too and every
    token's heads read the positions ITS indexer selects
    (``ops.attention.dsa_selection``), as a mask over the same scan.
    Returns (the heads' outputs [T, H * v], the leaf)."""
    from distributed_llama_tpu.ops import kv_cache as kvc
    from distributed_llama_tpu.ops.attention import dsa_selection, latent_attention_scan

    T, H = x.shape[0], cfg.n_heads
    queries, rows, index = latent_project(cfg, lp, x, rope_rows)
    leaf = kvc.latent_update_rows(cache_l, rows, pos)
    latents = leaf[kvc.LATENT][None]  # [1, D, S]
    S = latents.shape[2]
    q_pos = jnp.repeat(pos + jnp.arange(T), H)[None]  # [1, T * H]: a token's heads sit together
    selection = {}  # the scan's one more argument, where the layer has an indexer
    if index is not None:
        selection["selected"], _ = dsa_selection(
            index[0][None], index[1][None], (pos + jnp.arange(T))[None], leaf[kvc.INDEX][None],
            ATT_CHUNK if S % ATT_CHUNK == 0 else S, cfg.index_topk,
        )
    with jax.named_scope("mla_selected" if selection else "mla_prefill"):
        mix, _ = latent_attention_scan(
            queries.reshape(1, T * H, -1), q_pos, latents, _latent_chunk(S), cfg.head_size ** -0.5,
            **selection,
        )
        return latent_output(cfg, lp, mix.reshape(T, H, -1)), leaf


def latent_attention_batched(
    cfg: LlamaConfig, x: jax.Array, lp: Params, cache_l: dict, pos: jax.Array,
    rope_rows: jax.Array, active: jax.Array,
) -> tuple[jax.Array, dict]:
    """One decode step of B independent rows through a latent layer: row ``b``
    writes its latent row at ``pos[b]`` of ``{LATENT: [B_max, latent_dim, S]}``
    and its heads' absorbed queries read its own row up to there, every chunk
    up to the bucket's longest row (``ops.attention.latent_attention_scan``).
    A hit's pages were copied into the row, so it reads no pool. Inactive rows
    write nothing and read from position 0. A layer with an indexer writes the
    row's index key too, scores the row's index keys up to ``pos[b]`` and runs
    the softmax over the ``index_topk`` best (``ops.attention.dsa_selection``),
    MASKED: the scan reads the chunks as before, and the counts say so
    (``latent``: rows read; ``index``: index keys read; ``latent_selected``:
    rows the softmax ran over; ``dsa_visible``: rows the step could see)."""
    from distributed_llama_tpu.ops import kv_cache as kvc
    from distributed_llama_tpu.ops.attention import dsa_selection, latent_attention_scan, note_kv_read

    B, H = x.shape[0], cfg.n_heads
    S = cache_l[kvc.LATENT].shape[2]
    queries, rows, index = latent_project(cfg, lp, x, rope_rows)
    leaf = kvc.latent_update_row_batched(cache_l, rows, jnp.where(active & (pos < S), pos, S))
    at = jnp.where(active, pos, 0)
    q_pos = jnp.broadcast_to(at[:, None], (B, H))
    selection = {}  # the scan's one more argument, where the layer has an indexer
    if index is not None:
        selection["selected"], scored = dsa_selection(
            index[0][:, None], index[1][:, None], at[:, None], leaf[kvc.INDEX], _latent_chunk(S),
            cfg.index_topk,
        )
    with jax.named_scope("mla_selected" if selection else "mla_decode"):
        mix, read = latent_attention_scan(
            queries, q_pos, leaf[kvc.LATENT], _latent_chunk(S), cfg.head_size ** -0.5, **selection
        )
        note_kv_read("latent", B, read)
        if selection:
            # what the ROWS' queries attended and could see: a row that sits the step out has none
            attended = jnp.sum(selection["selected"][:, 0].astype(jnp.int32), axis=-1)
            note_kv_read("index", B, scored)
            note_kv_read("latent_selected", B, jnp.where(active, attended, 0))
            note_kv_read("dsa_visible", B, jnp.where(active, pos + 1, 0))
        return latent_output(cfg, lp, mix), leaf


class RecurrentStateError(RuntimeError):
    """A path that moves or rewinds a row BY POSITION met an arch some of
    whose layers keep a state that is not addressed by position
    (``cfg.is_recurrent``): speculative verify, tensor parallelism, spill
    and a rollback into the middle of a row refuse by this name rather
    than go on with a stale state."""


class WindowRingError(RuntimeError):
    """A path that moves or rewinds a row BY POSITION met an arch whose
    window layers keep a ring of their last positions (``cfg.has_window``):
    what a rewind would need has been overwritten, and a page of the pool
    holds no window layer's keys. The same paths refuse as for a recurrent
    state, by this name."""


class EvaWindowError(RuntimeError):
    """A path that moves or rewinds a row BY POSITION met an arch whose layers
    mix by EVA attention (``cfg.has_eva``): a finished window's keys have been
    overwritten by the next one's and only their summaries are left, and a
    page of the pool holds summaries, not keys. The same paths refuse as for
    a recurrent state or a ring, by this name."""


class LatentCacheError(RuntimeError):
    """A path that shards, narrows or batches a cache BY HEAD, or scores more
    than one new token a row in a step, met an arch whose layers keep latents
    (``cfg.has_latent``): a position's row has no head axis to shard or to
    scale by, and the multi-token verify window has no latent form here. Such
    a row still rewinds by position, as keys and values do."""


def refuse_latent(cfg: LlamaConfig, what: str) -> None:
    """Refuse ``what`` for an arch whose cache holds latents, by name."""
    if cfg.has_latent:
        raise LatentCacheError(
            f"{what} is not supported for arch {cfg.arch.name}: its latent-attention layers "
            f"keep one row of {cfg.latent_dim} values a position, with no head axis and no "
            "key or value"
        )


def refuse_recurrent(cfg: LlamaConfig, what: str) -> None:
    """Refuse ``what`` for an arch that cannot move a row back by position
    (``not cfg.rewinds_by_position``), by the name of what it keeps instead
    of every position."""
    if cfg.is_recurrent:
        raise RecurrentStateError(
            f"{what} is not supported for arch {cfg.arch.name}: its "
            f"{'state-space' if cfg.state_mixer == 'ssm' else 'linear-attention'} "
            "layers keep a recurrent state that cannot be rewound or moved by position"
        )
    if cfg.has_window:
        raise WindowRingError(
            f"{what} is not supported for arch {cfg.arch.name}: its window-attention "
            f"layers keep a ring of {cfg.ring_len} positions, not every position of a row"
        )
    if cfg.has_eva:
        raise EvaWindowError(
            f"{what} is not supported for arch {cfg.arch.name}: its EVA layers keep the "
            f"keys of the current window of {cfg.window} positions and one summary per "
            f"{cfg.eva_chunk} positions of the windows before it, not every position of a row"
        )


def _l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _linear_inputs(cfg: LlamaConfig, lp: Params, x: jax.Array):
    """Norm + the linear layer's input projections for T tokens:
    (q|k|v pre-convolution [T, 3*L], decay pre-activation [T, L], beta
    [T, Hl], output gate [T, L]), ``L = lin_heads * lin_head_dim``. One
    matrix (``lin_in``: q|k|v|f_down|g_down|beta) reads the normed input."""
    from distributed_llama_tpu.formats.model_file import ArchFlags

    L, r, Hl = cfg.lin_heads * cfg.lin_head_dim, cfg.lin_rank, cfg.lin_heads
    fused = _norm_matmul(x, lp["rms_att"], lp["lin_in"], "lin_in")
    qkv = fused[:, : 3 * L]
    f_low = fused[:, 3 * L : 3 * L + r]
    g_low = fused[:, 3 * L + r : 3 * L + 2 * r]
    beta = jax.nn.sigmoid(fused[:, 3 * L + 2 * r : 3 * L + 2 * r + Hl])
    if cfg.has(ArchFlags.NEG_EIGVAL):
        beta = 2.0 * beta
    decay = _matmul(f_low.astype(lp["f_up"].dtype), lp["f_up"], "lin_f")[:, :L] + lp["dt_bias"]
    gate = _matmul(g_low.astype(lp["g_up"].dtype), lp["g_up"], "lin_g")[:, :L]
    return qkv, decay, beta, gate


def _linear_heads(cfg: LlamaConfig, lp: Params, qkv: jax.Array, decay: jax.Array):
    """Convolved q|k|v [T, 3*L] and the decay's pre-activation [T, L] ->
    q, k, a [T, Hl, dl] (q, k normalised per head, q scaled by 1/sqrt(dl);
    ``a`` the log of the per-channel decay, <= 0) and v [T, Hl, dl]."""
    T = qkv.shape[0]
    Hl, dl = cfg.lin_heads, cfg.lin_head_dim
    q, k, v = (t.reshape(T, Hl, dl) for t in jnp.split(jax.nn.silu(qkv), 3, axis=-1))
    q = _l2norm(q) * (1.0 / jnp.sqrt(jnp.float32(dl)))
    k = _l2norm(k)
    a = -jnp.exp(lp["a_log"])[None, :, None] * jax.nn.softplus(decay.reshape(T, Hl, dl))
    return q, k, v, a


def _linear_output(cfg: LlamaConfig, lp: Params, o: jax.Array, gate: jax.Array) -> jax.Array:
    """Per-head RMS norm of the recurrence's output [T, Hl, dl], times the
    sigmoid of the low-rank output gate -> [T, L] (``wo`` follows in
    :func:`block_tail`)."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-5) * lp["o_norm"]
    return o.reshape(gate.shape) * jax.nn.sigmoid(gate)


def linear_attention(
    cfg: LlamaConfig, x: jax.Array, lp: Params, cache_l: dict, pos: jax.Array,
    n_real: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """The gated delta-rule mixer for T new tokens of ONE row whose first
    token sits at ``pos``. ``cache_l``: ``{"S": [Hl, dl, dl] f32, "conv":
    [taps-1, 3*L] f32}``, the row's state after the tokens before ``pos``;
    a row that starts over (``pos == 0``) starts from zeros whatever the
    leaf holds, so a row is reset with its position. Tokens at and past
    ``n_real`` (bucket padding) leave the state untouched. Returns (mix
    [T, L], the state after the last real token)."""
    from distributed_llama_tpu.ops import kda

    fresh = pos == 0
    S0 = jnp.where(fresh, 0.0, cache_l["S"])
    tail = jnp.where(fresh, 0.0, cache_l["conv"])
    qkv, decay, beta, gate = _linear_inputs(cfg, lp, x)
    qkv, tail = kda.causal_conv(qkv, tail, lp["conv"], n_real)
    q, k, v, a = _linear_heads(cfg, lp, qkv, decay)
    o, S = kda.kda_chunk(S0, q, k, v, a, beta, n_real)
    return _linear_output(cfg, lp, o, gate), {"S": S, "conv": tail}


def linear_attention_batched(
    cfg: LlamaConfig, x: jax.Array, lp: Params, cache_l: dict, active: jax.Array,
) -> tuple[jax.Array, dict]:
    """One decode step of B independent rows through the gated delta-rule
    mixer: ``cache_l`` holds ``[b_max, ...]`` leaves of which the first B
    rows step; rows where ``active`` is False keep state and tail."""
    from distributed_llama_tpu.ops import kda

    B = x.shape[0]
    tail_all = cache_l["conv"]
    qkv, decay, beta, gate = _linear_inputs(cfg, lp, x)
    qkv, tail = kda.causal_conv_step(qkv, tail_all[:B], lp["conv"], active)
    q, k, v, a = _linear_heads(cfg, lp, qkv, decay)
    o, S = kda.kda_step(cache_l["S"], q, k, v, a, beta, active)
    if tail_all.shape[0] != B:
        tail = jax.lax.dynamic_update_slice_in_dim(tail_all, tail, 0, axis=0)
    return _linear_output(cfg, lp, o, gate), {"S": S, "conv": tail}


def _ssm_inputs(cfg: LlamaConfig, lp: Params, x: jax.Array):
    """Norm + the state-space layer's ONE input projection for T tokens
    (``ssm_in``, rows in the published order z | xBC | dt): (gate z [T,
    inner], x|B|C before the convolution [T, inner + 2N], dt before its bias
    and softplus [T, Hs])."""
    inner, conv = cfg.ssm_inner, cfg.ssm_conv_dim
    fused = _norm_matmul(x, lp["rms_att"], lp["ssm_in"], "lin_in")
    return (fused[:, :inner], fused[:, inner : inner + conv],
            fused[:, inner + conv : inner + conv + cfg.ssm_heads])


def _ssm_heads(cfg: LlamaConfig, lp: Params, xbc: jax.Array, dt: jax.Array):
    """Convolved x|B|C [T, inner + 2N] (bias and SiLU applied here) and dt's
    pre-activation [T, Hs] -> x [T, Hs, P], B, C [T, N], dt [T, Hs] > 0 and
    the heads' rates ``a`` [Hs] < 0 (a step decays a head's state by
    ``exp(dt * a)``)."""
    T, inner, N = xbc.shape[0], cfg.ssm_inner, cfg.ssm_state
    xbc = jax.nn.silu(xbc + lp["conv_bias"])
    x = xbc[:, :inner].reshape(T, cfg.ssm_heads, cfg.ssm_head_dim)
    return (x, xbc[:, inner : inner + N], xbc[:, inner + N :],
            jax.nn.softplus(dt + lp["dt_bias"]), -jnp.exp(lp["a_log"]))


def _ssm_output(cfg: LlamaConfig, lp: Params, y: jax.Array, x: jax.Array, z: jax.Array):
    """The recurrence's output [T, Hs, P] plus the skip ``D x``, gated by
    ``silu(z)`` and THEN RMS-normalised over the whole inner width (one group)
    with a learned weight -> [T, inner] (``wo`` follows in :func:`block_tail`)."""
    y = (y + lp["ssm_d"][None, :, None] * x).reshape(z.shape)
    return rmsnorm(y * jax.nn.silu(z), lp["ssm_norm"])


def ssm_mixer(
    cfg: LlamaConfig, x: jax.Array, lp: Params, cache_l: dict, pos: jax.Array,
    n_real: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """The state-space (SSD) mixer for T new tokens of ONE row whose first
    token sits at ``pos``. ``cache_l``: ``{"S": the heads' states in the
    layout of ``ops.ssd.state_shape``, f32, "conv": [taps-1, inner + 2N]
    f32}``, as the tokens before ``pos`` left them; a row that starts over
    (``pos == 0``) starts from zeros whatever the leaf holds. Tokens at and
    past ``n_real`` (bucket padding) leave state and tail untouched. Returns
    (mix [T, inner], the leaf after the last real token)."""
    from distributed_llama_tpu.ops import kda, ssd

    fresh = pos == 0
    S0 = jnp.where(fresh, 0.0, cache_l["S"])
    tail = jnp.where(fresh, 0.0, cache_l["conv"])
    z, xbc, dt = _ssm_inputs(cfg, lp, x)
    xbc, tail = kda.causal_conv(xbc, tail, lp["conv"], n_real)
    xs, Bm, Cm, dt, a = _ssm_heads(cfg, lp, xbc, dt)
    y, S = ssd.ssd_chunk(S0, xs, Bm, Cm, dt, a, n_real)
    return _ssm_output(cfg, lp, y, xs, z), {"S": S, "conv": tail}


def ssm_mixer_batched(
    cfg: LlamaConfig, x: jax.Array, lp: Params, cache_l: dict, active: jax.Array,
) -> tuple[jax.Array, dict]:
    """One decode step of B independent rows through the state-space mixer:
    ``cache_l`` holds ``[b_max, ...]`` leaves of which the first B rows step;
    rows where ``active`` is False keep state and tail."""
    from distributed_llama_tpu.ops import kda, ssd

    B = x.shape[0]
    tail_all = cache_l["conv"]
    z, xbc, dt = _ssm_inputs(cfg, lp, x)
    xbc, tail = kda.causal_conv_step(xbc, tail_all[:B], lp["conv"], active)
    xs, Bm, Cm, dt, a = _ssm_heads(cfg, lp, xbc, dt)
    y, S = ssd.ssd_step(cache_l["S"], xs, Bm, Cm, dt, a, active)
    if tail_all.shape[0] != B:
        tail = jax.lax.dynamic_update_slice_in_dim(tail_all, tail, 0, axis=0)
    return _ssm_output(cfg, lp, y, xs, z), {"S": S, "conv": tail}


def ffn(cfg: LlamaConfig, x: jax.Array, lp: Params, axis_name: str | None) -> jax.Array:
    """SwiGLU FFN (reference: src/llama2-tasks.cpp:158-212)."""
    if "gate_up" in lp:
        # gate|up packed as one matmul (see the qkv note in attention),
        # with the norm + Q80 quantize fused in on the int8 path
        fused = _norm_matmul(x, lp["rms_ffn"], lp["gate_up"], "gate_up")
        hidden = fused.shape[-1] // 2
        h = _activation(fused[:, :hidden], cfg.hidden_act) * fused[:, hidden:]
    else:
        xn = rmsnorm(x, lp["rms_ffn"]).astype(lp["gate"].dtype)
        h = _activation(_matmul(xn, lp["gate"], "gate_up"), cfg.hidden_act) * _matmul(
            xn, lp["up"], "gate_up"
        )
    if axis_name is None:
        return _matmul(h.astype(lp["down"].dtype), lp["down"], "down")
    from distributed_llama_tpu.ops import collectives

    # down + TP all-reduce through the fused seam (see block_tail)
    return collectives.matmul_all_reduce(
        h.astype(lp["down"].dtype), lp["down"], axis_name, role="down"
    )


def block_forward(
    cfg: LlamaConfig,
    x: jax.Array,
    lp: Params,
    cache_l,
    pos: jax.Array,
    rope_rows: jax.Array,
    axis_name: str | None,
    ep_axis: str | None = None,
    n_real: jax.Array | None = None,
    paged=None,
    layer: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    # a caller that does not say which layer it runs (a scanned body) runs an
    # arch whose layers are all full ones
    mixer = "full" if layer is None else cfg.layer_kind(layer)[0]
    if mixer == "linear":
        att, new_cache = linear_attention(cfg, x, lp, cache_l, pos, n_real)
    elif mixer == "ssm":
        att, new_cache = ssm_mixer(cfg, x, lp, cache_l, pos, n_real)
    elif mixer == "latent":
        att, new_cache = latent_attention(cfg, x, lp, cache_l, pos, rope_rows)
    else:
        att, new_cache = attention(
            cfg, x, lp, cache_l, pos, rope_rows, axis_name, paged=paged, layer=layer,
            n_real=n_real,
        )
    return (
        block_tail(cfg, x, att, lp, axis_name, ep_axis=ep_axis, n_real=n_real),
        new_cache,
    )


def forward_tokens(
    cfg: LlamaConfig,
    params: Params,
    tokens: jax.Array,
    cache: jax.Array,
    pos: jax.Array,
    axis_name: str | None = None,
    ep_axis: str | None = None,
    n_real: jax.Array | None = None,
    paged=None,
    held_counts: list | None = None,
    piece_paths: list | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Run T tokens through the model starting at absolute position ``pos``.

    tokens: int32 [T]; cache: a list of per-layer ``(keys, values)`` tuples
    (the layered layout) or a stacked [L, 2, S, Kl, hd] array; returns
    (logits f32 [T, vocab], updated cache in the same form). The per-token
    path of the reference's Inference::infer (src/tasks.cpp:173-184) is the
    T=1 case. ``n_real``: real (non-pad) token count of a bucket-padded
    prompt (pad rows write no cache or state and spend no row of an
    expert's bucket); None = all rows real.
    ``paged``: ``(pool, table, matched)`` — this row's cache positions
    below ``matched`` live in the shared prefix-page pool (per-layer
    ``(keys, values)`` halves, read through ``table``); requires the
    layered cache layout. ``held_counts``: a list that receives one int32
    [T] array, per token how many of its expert choices, summed over the
    layers, fell on an expert held here (archs that hold a share).
    ``piece_paths``: a list that receives one int32 [2] array, how many of
    the program's expert layers ran every expert over every row and how
    many ran each expert over its own bucket (``models.moe``; an expert arch
    in the layered params layout); [3] for an arch that holds a share of its
    experts: and the rows their grouped launches multiplied.
    """
    from distributed_llama_tpu.models import moe

    # a scanned layer body cannot hand its tracers to a list outside it
    layered = isinstance(params["layers"], (list, tuple))
    with moe.collect_held(held_counts is not None) as per_layer, \
            moe.collect_piece_paths(piece_paths is not None and layered) as paths, \
            moe.collect_launched(piece_paths is not None and layered) as launched:
        out = _forward_tokens(cfg, params, tokens, cache, pos, axis_name, ep_axis, n_real, paged)
    if per_layer:
        held_counts.append(sum(per_layer))
    if paths:
        every_row = sum(paths)
        piece_paths.append(jnp.stack([every_row, len(paths) - every_row] + ([sum(launched)] if launched else [])))
    return out


def _forward_tokens(cfg, params, tokens, cache, pos, axis_name, ep_axis, n_real, paged):
    T = tokens.shape[0]
    x = embed(cfg, params, tokens)
    rope_rows = jax.lax.dynamic_slice(
        params["rope_table"], (pos, 0, 0), (T,) + params["rope_table"].shape[1:]
    )

    if isinstance(params["layers"], (list, tuple)):
        # unrolled layer loop: used by the q40 path, whose Pallas-call
        # operands must be the resident buffers themselves (scan-slicing a
        # stacked array makes XLA hoist a full copy of every layer's weights).
        # The cache should be a LIST of per-layer arrays here: indexing a
        # stacked cache and re-stacking the updates copies the ENTIRE cache
        # every call (~1.1 GB of HBM traffic per decoded token on a 7B,
        # ~7 ms/token of pure overhead); per-layer leaves alias in place.
        cache_is_list = isinstance(cache, (list, tuple))
        new_layers = []
        for l, lp in enumerate(params["layers"]):
            paged_l = None
            if paged is not None and cfg.layer_kind(l)[0] == "full":
                # the one kind whose scan reads a pool in place; a hit of
                # every other kind was copied into the row
                pool, table, matched = paged
                paged_l = (pool[l][0], pool[l][1], table, matched)
            x, nc = block_forward(
                cfg, x, lp, cache[l], pos, rope_rows, axis_name, ep_axis=ep_axis,
                n_real=n_real, paged=paged_l, layer=l,
            )
            new_layers.append(nc)
        new_cache = type(cache)(new_layers) if cache_is_list else jnp.stack(new_layers)
    else:
        if paged is not None:
            raise ValueError("the paged (pool-aliased) read requires the layered cache")
        if cfg.has_window or cfg.has_latent:
            raise ValueError("layers of two kinds, and latent layers, need the layered cache layout")

        def body(carry, scanned):
            xc = carry
            lp, cache_l = scanned
            xc, new_cache_l = block_forward(
                cfg, xc, lp, cache_l, pos, rope_rows, axis_name, ep_axis=ep_axis,
                n_real=n_real,
            )
            return xc, new_cache_l

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))

    return final_logits(cfg, params, x), new_cache


def attention_batched(
    cfg: LlamaConfig,
    x: jax.Array,  # [B, dim] — one token per independent sequence
    lp: Params,
    cache_l,  # (keys, values) slab halves [B, S, Kl, hd]
    pos: jax.Array,  # [B] per-row absolute positions
    rope_rows: jax.Array,  # [B, hd/2, 2] per-row rope table rows
    active: jax.Array,  # [B] bool — False rows decode garbage, write nothing
    paged=None,  # (pool_k, pool_v, tables [B, n_table], matched [B])
    layer: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One decode step of B INDEPENDENT sequences over a slab cache with a
    leading batch axis: row ``b`` writes its K/V at its own ``pos[b]`` and
    attends over its own cache row masked by ``pos[b]``. Everything outside
    attention (norms, matmuls, FFN) is position-free, so the batch shares
    one weight read per matrix per step — the whole point of batching an
    HBM-bound decode. Inactive rows write at a DROPPED out-of-bounds slot
    (retired caches stay byte-identical for prefix reuse) and their outputs
    are garbage the scheduler discards. ``paged``: row ``b``'s positions
    below ``matched[b]`` are read from the shared page pool through its
    page table (zero-copy prefix aliasing) — bit-identical to a row holding
    copies of the pages. A WINDOW layer (``layer``, ``cfg.layer_kind``) keeps
    a ring [2, B, R, Kl, hd]: row ``b`` writes at slot ``pos[b] % R`` and
    reads its own last ``cfg.window`` positions, no pool and no chunk that
    the mask would hide. An EVA layer keeps [2, B, window + summaries, Kl,
    hd]: row ``b`` writes at slot ``pos[b] % window``, summarises the chunk
    that position ends, and reads its aligned window and the earlier
    windows' summaries (``ops.attention.eva_*``), no pool."""
    from distributed_llama_tpu.ops import kv_cache as kvc

    B = x.shape[0]
    S, cdt, prec = kvc.slab_facts(cache_l)
    hd = cfg.cache_head_size
    q, k, v, gate = project_qkvg(cfg, lp, x, rope_rows, layer)  # [B, Hl, hd], [B, Kl, hd] x2
    Hl, Kl = q.shape[1], k.shape[1]

    if cfg.is_window_layer(layer):
        from distributed_llama_tpu.ops.attention import batched_window_attention

        # S is the ring's length here: an inactive row writes at the dropped slot
        new_cache = kvc.fused_update_row_batched(cache_l, k, v, jnp.where(active, pos % S, S))
        qg = q.reshape(B, Kl, Hl // Kl, hd).astype(jnp.float32)
        att = batched_window_attention(
            qg, new_cache, jnp.where(active, pos, 0), cfg.window
        ).astype(jnp.float32)
        return _gated(att.reshape(B, Hl * hd), gate), new_cache

    if cfg.has_eva:
        from distributed_llama_tpu.ops import attention as att_ops

        new_cache = att_ops.eva_decode_write(
            cache_l, k, v, pos, active, lp["eva_phi"], lp["eva_mu"], cfg.window, cfg.eva_chunk
        )
        qg = q.reshape(B, Kl, Hl // Kl, hd).astype(jnp.float32)
        att = att_ops.eva_batched_decode_attention(
            qg, new_cache, jnp.where(active, pos, 0), cfg.window, cfg.eva_chunk,
            cfg.eva_scan_chunk,
        )
        return att.reshape(B, Hl * hd), new_cache

    write_slot = jnp.where(active & (pos < S), pos, S)  # S = dropped
    if kvc.is_fused_leaf(cache_l):
        # fused slab leaf [2, B, S, Kl, hd]: one coalesced scatter writes
        # every row's key AND value (see the fused note in attention())
        new_cache = kvc.fused_update_row_batched(cache_l, k, v, write_slot)
    else:
        new_cache = (
            kvc.update_row_batched(cache_l[0], k, write_slot),
            kvc.update_row_batched(cache_l[1], v, write_slot),
        )

    kv_mul = Hl // Kl
    qg = q.reshape(B, Kl, kv_mul, hd).astype(cdt)
    # inactive rows read from position 0 so they cannot inflate the shared
    # dynamic chunk bound (their output is garbage either way)
    read_pos = jnp.where(active, pos, 0)
    use_blocked = S % ATT_CHUNK == 0 and S > ATT_CHUNK
    if use_blocked and (
        paged is None or ATT_CHUNK % kvc.pool_page_size(paged[0]) == 0
    ):
        from distributed_llama_tpu.ops.attention import batched_decode_attention

        # the cache goes in AS STORED: the chunk loops slice their chunks
        # out of the leaf; ``keys``/``values`` of a whole slab never form
        att = batched_decode_attention(
            qg.astype(jnp.float32), new_cache, read_pos, ATT_CHUNK, paged=paged
        ).astype(jnp.float32)
        return _gated(_own_part(cfg, att.reshape(B, Hl * hd), Hl), gate), new_cache
    # small/odd caches read all of S anyway: the halves may form here.
    # A dispatch bucket below B_max reads only its own slab rows
    keys, values = new_cache[0], new_cache[1]
    keys_b = keys if keys.shape[0] == B else kvc.slice_rows_batched(keys, 0, S, rows=B)
    values_b = (
        values if values.shape[0] == B else kvc.slice_rows_batched(values, 0, S, rows=B)
    )
    if paged is not None:
        # virtual slab view (pool bytes below matched) through the same
        # einsum/blocked path — the small/odd-cache fallback
        pool_k, pool_v, tables, matched = paged
        keys_b = kvc.virtual_rows_batched(keys_b, pool_k, tables, matched)
        values_b = kvc.virtual_rows_batched(values_b, pool_v, tables, matched)
        if use_blocked:
            from distributed_llama_tpu.ops.attention import batched_decode_attention

            att = batched_decode_attention(
                qg.astype(jnp.float32), (keys_b, values_b), read_pos, ATT_CHUNK
            ).astype(jnp.float32)
            return _gated(_own_part(cfg, att.reshape(B, Hl * hd), Hl), gate), new_cache
    from distributed_llama_tpu.ops.attention import note_kv_read

    note_kv_read("full", B, S)  # a small or odd cache is read whole
    scores = kvc.scores_einsum_batched(qg, keys_b, prec) / jnp.sqrt(jnp.float32(hd))
    mask = jnp.arange(S)[None, :] <= read_pos[:, None]  # [B, S]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    att = kvc.mix_einsum_batched(weights, values_b, cdt, prec).reshape(B, Hl * hd)
    return _gated(_own_part(cfg, att, Hl), gate), new_cache


def forward_step_batched(
    cfg: LlamaConfig,
    params: Params,
    tokens: jax.Array,  # int32 [B]
    cache,  # list of per-layer (keys, values) slab tuples [B, S, Kl, hd]
    pos: jax.Array,  # int32 [B] per-row positions
    active: jax.Array,  # bool [B]
    axis_name: str | None = None,
    paged=None,  # (pool, tables, matched) — zero-copy prefix aliasing
    held_counts: list | None = None,  # receives int32 [B], as in forward_tokens
    kv_reads: dict | None = None,  # receives {kind: int32 [B]}: cache positions read
    every_row: list | None = None,  # receives an int32 [2]: the expert layers that ran every
    # held expert over every row of the step (``models.moe``: no bucket, or one overflowed), and
    # the rows the layers' grouped launches multiplied
) -> tuple[jax.Array, jax.Array]:
    """One batched decode step: B tokens (one per sequence) at per-row
    positions through the whole model, reading each weight matrix ONCE.
    Returns (logits f32 [B, vocab], updated slab cache). Requires the
    layered (per-layer list) cache layout — the only engine layout; a
    stacked slab would copy itself every step (see forward_tokens).

    MoE note: with B > 1 the FFN takes the DENSE expert path (every expert
    computed, zero-weighted ones contributing exact zeros), not the T==1
    top-k switch — per-step expert HBM reads are E shared across B rows vs
    B·k for B separate streams, so batching still wins once B ≥ E/k
    (break-even at B=4 for Mixtral's 2-of-8). Per-row outputs match
    single-stream decode up to expert-sum reordering (the dense mix adds
    experts in bank order, the switch in top-k order); the BIT-parity
    contract of the batched path is exact for dense models only."""
    from distributed_llama_tpu.models import moe
    from distributed_llama_tpu.ops.attention import collect_kv_reads

    with moe.collect_held(held_counts is not None) as per_layer, \
            moe.collect_piece_paths(every_row is not None) as paths, \
            moe.collect_launched(every_row is not None) as launched, \
            collect_kv_reads(kv_reads is not None) as reads:
        out = _forward_step_batched(cfg, params, tokens, cache, pos, active, axis_name, paged)
    if per_layer:
        held_counts.append(sum(per_layer))
    if paths:
        every_row.append(jnp.stack([sum(paths), sum(launched)]))
    for kind, positions in reads or ():
        # per row, over the step's layers of that kind
        kv_reads[kind] = kv_reads.get(kind, 0) + positions
    return out


def _forward_step_batched(cfg, params, tokens, cache, pos, active, axis_name, paged):
    if not isinstance(cache, (list, tuple)):
        raise ValueError("batched decode requires the layered (per-layer list) cache")
    x = embed(cfg, params, tokens)  # [B, dim]
    rope_rows = params["rope_table"][jnp.clip(pos, 0, cfg.seq_len - 1)]
    layers = params["layers"]
    if not isinstance(layers, (list, tuple)):
        raise ValueError("batched decode requires the per-layer-list params layout")
    new_layers = []
    for l, lp in enumerate(layers):
        mixer = cfg.layer_kind(l)[0]
        paged_l = None
        if paged is not None and mixer == "full":
            pool, tables, matched = paged
            paged_l = (pool[l][0], pool[l][1], tables, matched)
        if mixer == "linear":
            att, nc = linear_attention_batched(cfg, x, lp, cache[l], active)
        elif mixer == "ssm":
            att, nc = ssm_mixer_batched(cfg, x, lp, cache[l], active)
        elif mixer == "latent":
            att, nc = latent_attention_batched(cfg, x, lp, cache[l], pos, rope_rows, active)
        else:
            att, nc = attention_batched(
                cfg, x, lp, cache[l], pos, rope_rows, active, paged=paged_l, layer=l
            )
        x = block_tail(cfg, x, att, lp, axis_name)
        new_layers.append(nc)
    return final_logits(cfg, params, x), type(cache)(new_layers)


def attention_verify_batched(
    cfg: LlamaConfig,
    x: jax.Array,  # [B, T, dim] — T-token verify window per sequence
    lp: Params,
    cache_l,  # fused [2, B, S, Kl, hd] slab leaf (or (keys, values) tuple)
    pos: jax.Array,  # [B] absolute position of each row's window start
    rope_rows: jax.Array,  # [B, T, hd/2, 2] per-(row, offset) rope rows
    active: jax.Array,  # [B] bool — False rows verify garbage, write nothing
    paged=None,  # (pool_k, pool_v, tables [B, n_table], matched [B])
) -> tuple[jax.Array, jax.Array]:
    """One speculative-verify attention step of B independent T-token
    windows (T = draft k + 1): row ``b``'s query ``t`` sits at ``pos[b]+t``,
    writes its K/V there, and attends its own slab row causally. The write
    is ONE coalesced scatter per layer covering all B·T keys AND values;
    out-of-bounds slots (inactive rows, context-limit clamps) drop, so a
    retired row's cache stays byte-identical. Returns
    (attention mix [B, T, Hl*hd], updated cache)."""
    from distributed_llama_tpu.ops import kv_cache as kvc

    B, T = x.shape[0], x.shape[1]
    S, cdt, prec = kvc.slab_facts(cache_l)
    hd = cfg.head_size
    # projections/rope are position-free per row: run them on the flattened
    # [B*T] token axis (one matmul per matrix — the whole point of scoring
    # draft + bonus positions in a single weight read)
    q, k, v = project_qkv(
        cfg, lp, x.reshape(B * T, -1), rope_rows.reshape(B * T, *rope_rows.shape[2:])
    )
    Hl, Kl = q.shape[1], k.shape[1]
    q = q.reshape(B, T, Kl * (Hl // Kl), hd)
    k = k.reshape(B, T, Kl, hd)
    v = v.reshape(B, T, Kl, hd)

    slots = pos[:, None] + jnp.arange(T)[None, :]  # [B, T]
    slots = jnp.where(active[:, None] & (slots < S), slots, S)  # S = dropped
    if kvc.is_fused_leaf(cache_l):
        new_cache = kvc.fused_update_verify_batched(cache_l, k, v, slots)
    else:
        b_idx = jnp.arange(B)[:, None]
        new_cache = (
            kvc.scatter_verify_rows(cache_l[0], b_idx, slots, k),
            kvc.scatter_verify_rows(cache_l[1], b_idx, slots, v),
        )

    kv_mul = Hl // Kl
    qg = q.reshape(B, T, Kl, kv_mul, hd).astype(cdt)
    read_pos = jnp.where(active, pos, 0)
    use_blocked = S % ATT_CHUNK == 0 and S > ATT_CHUNK
    if use_blocked and (
        paged is None or ATT_CHUNK % kvc.pool_page_size(paged[0]) == 0
    ):
        from distributed_llama_tpu.ops.attention import batched_verify_attention

        att = batched_verify_attention(
            qg.astype(jnp.float32), new_cache, read_pos, ATT_CHUNK, paged=paged
        ).astype(jnp.float32)
        return att.reshape(B, T, Hl * hd), new_cache
    keys, values = new_cache[0], new_cache[1]
    keys_b = keys if keys.shape[0] == B else kvc.slice_rows_batched(keys, 0, S, rows=B)
    values_b = (
        values if values.shape[0] == B else kvc.slice_rows_batched(values, 0, S, rows=B)
    )
    if paged is not None:
        pool_k, pool_v, tables, matched = paged
        keys_b = kvc.virtual_rows_batched(keys_b, pool_k, tables, matched)
        values_b = kvc.virtual_rows_batched(values_b, pool_v, tables, matched)
        if use_blocked:
            from distributed_llama_tpu.ops.attention import batched_verify_attention

            att = batched_verify_attention(
                qg.astype(jnp.float32), (keys_b, values_b), read_pos, ATT_CHUNK
            ).astype(jnp.float32)
            return att.reshape(B, T, Hl * hd), new_cache
    scores = kvc.scores_einsum_verify(qg, keys_b, prec) / jnp.sqrt(jnp.float32(hd))
    # causal mask per (row, offset): query t of row b sees slots 0..pos[b]+t
    q_pos = read_pos[:, None] + jnp.arange(T)[None, :]  # [B, T]
    mask = jnp.arange(S)[None, None, :] <= q_pos[:, :, None]  # [B, T, S]
    scores = jnp.where(mask[:, :, None, None, :], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    att = kvc.mix_einsum_verify(weights, values_b, cdt, prec).reshape(B, T, Hl * hd)
    return att, new_cache


def forward_verify_batched(
    cfg: LlamaConfig,
    params: Params,
    tokens: jax.Array,  # int32 [B, T] — [prev, draft_1..draft_k] per row
    cache,  # list of per-layer fused slab leaves (llama.init_batch_cache)
    pos: jax.Array,  # int32 [B] per-row positions of tokens[:, 0]
    active: jax.Array,  # bool [B]
    axis_name: str | None = None,
    paged=None,  # (pool, tables, matched) — zero-copy prefix aliasing
) -> tuple[jax.Array, jax.Array]:
    """The speculative-decode verify forward: score every row's T-token
    window (previous token + k prompt-lookup drafts) in ONE weight read.
    ``logits[b, t]`` is the next-token distribution after consuming
    ``tokens[b, :t+1]`` — the accept/reject pass (sampling._spec_accept_row)
    compares drafts against it positionwise. Causally masked at a per-row
    position offset, so it is the batched multi-token generalization of
    :func:`forward_step_batched` (whose T == 1 case it reproduces
    bit-exactly); the chunked-prefill machinery supplies the attention and
    cache-write building blocks. Returns (logits f32 [B, T, vocab],
    updated slab cache)."""
    if not isinstance(cache, (list, tuple)):
        raise ValueError("batched verify requires the layered (per-layer list) cache")
    refuse_recurrent(cfg, "speculative verify (a rejected draft rewinds the row)")
    refuse_latent(cfg, "speculative verify (a window of several new tokens a row)")
    B, T = tokens.shape
    x = embed(cfg, params, tokens.reshape(-1)).reshape(B, T, -1)
    offsets = pos[:, None] + jnp.arange(T)[None, :]
    rope_rows = params["rope_table"][jnp.clip(offsets, 0, cfg.seq_len - 1)]
    layers = params["layers"]
    if not isinstance(layers, (list, tuple)):
        raise ValueError("batched verify requires the per-layer-list params layout")
    new_layers = []
    for l, lp in enumerate(layers):
        paged_l = None
        if paged is not None:
            pool, tables, matched = paged
            paged_l = (pool[l][0], pool[l][1], tables, matched)
        att, nc = attention_verify_batched(
            cfg, x, lp, cache[l], pos, rope_rows, active, paged=paged_l
        )
        x = block_tail(
            cfg, x.reshape(B * T, -1), att.reshape(B * T, -1), lp, axis_name
        ).reshape(B, T, -1)
        new_layers.append(nc)
    logits = final_logits(cfg, params, x.reshape(B * T, -1))
    return logits.reshape(B, T, -1), type(cache)(new_layers)


def init_batch_cache(
    cfg: LlamaConfig,
    b_max: int,
    n_kv_heads_local: int | None = None,
    dtype=jnp.float32,
) -> list[tuple[jax.Array, jax.Array]]:
    """Slab KV cache for ``b_max`` concurrent decode streams: a list of
    per-layer FUSED [2, b_max, S, Kl, hd] leaves (keys and values on the
    leading 2-axis — one coalesced scatter per layer per step; i8 slabs
    quantize per (row, slot, head) exactly like the single-stream i8
    cache). ``leaf[0]``/``leaf[1]`` are the (keys, values) halves. The tp
    backend keeps its own sharded (keys, values)-tuple slab."""
    from distributed_llama_tpu.ops import kv_cache as kvc

    kl = n_kv_heads_local if n_kv_heads_local is not None else cfg.cache_kv_heads
    return [_init_layer_leaf(cfg, l, (b_max,), kl, dtype) for l in range(cfg.n_layers)]


def _init_layer_leaf(cfg: LlamaConfig, l: int, lead: tuple[int, ...], kl: int, dtype):
    """Layer ``l``'s cache leaf by its kind: every position of a row for a
    full layer, a ring of ``cfg.ring_len`` slots for a window layer (it does
    not grow with ``seq_len``), a state for a linear one, the window store and
    the summaries behind it (``cfg.eva_slots``) for an EVA one, one row of
    ``cfg.latent_dim`` values a position, with no head axis, for a latent one."""
    from distributed_llama_tpu.ops import kv_cache as kvc

    mixer = cfg.layer_kind(l)[0]
    if mixer in STATE_MIXERS:
        return init_state_leaf(cfg, lead)
    if mixer == "latent":
        if kvc.is_quantized_cache_dtype(dtype):
            refuse_latent(cfg, "an i8 cache (its scales are one a head)")
        return kvc.init_latent(lead, cfg.seq_len, cfg.latent_dim, dtype, cfg.index_head_dim)
    if mixer == "eva" and kvc.is_quantized_cache_dtype(dtype):
        raise ValueError("an EVA layer's summaries have no i8 form: serve it with a plain KV dtype")
    slots = {"window": cfg.ring_len, "eva": cfg.eva_slots}.get(mixer, cfg.seq_len)
    return kvc.init_fused(lead + (slots, kl, cfg.cache_head_size), dtype)


def kv_slab_bytes(cfg: LlamaConfig, rows: int, dtype) -> dict[str, int]:
    """Bytes of keys and values ``rows`` slab rows hold, by layer kind
    (``full``, ``window``): a full layer's grow with ``seq_len``, a window
    layer's are its ring's. An EVA arch: by store (``eva_window``: the window's
    slots, which do not grow with ``seq_len``; ``eva_summary``: one entry per
    ``eva_chunk`` positions). A latent arch: ``latent``, every position's row,
    and ``index``, every position's index key, where its layers have an indexer."""
    per_slot = page_pool_bytes(cfg, 1, dtype, layers=1)
    if cfg.has_latent:
        per_value = rows * cfg.seq_len * cfg.n_layers * jnp.dtype(dtype).itemsize
        held = {"latent": per_value * cfg.latent_dim}
        if cfg.has_indexer:
            held["index"] = per_value * cfg.index_head_dim
        return held
    if cfg.has_eva:
        return {"eva_window": rows * cfg.window * per_slot * cfg.n_layers,
                "eva_summary": rows * cfg.eva_summaries * per_slot * cfg.n_layers}
    return {
        "full": rows * cfg.seq_len * per_slot * len(cfg.layers_of("full")),
        "window": rows * cfg.ring_len * per_slot * len(cfg.layers_of("window")),
    }


def init_state_leaf(cfg: LlamaConfig, lead: tuple[int, ...] = ()) -> dict:
    """A recurrent layer's cache (``cfg.state_mixer``): the state ``S`` and
    the convolution's last inputs ``conv`` [*lead, taps-1, channels], both f32
    whatever the K/V dtype. It does not grow with the row's length. A linear
    layer: ``S`` [*lead, Hl, dl, dl], channels 3*L; a state-space one: ``S``
    in the layout of ``ops.ssd.state_shape`` (Hs * P * N values), channels
    inner + 2N."""
    S, channels = _state_shape(cfg)
    return {
        "S": jnp.zeros(lead + S, jnp.float32),
        "conv": jnp.zeros(lead + (cfg.lin_conv - 1, channels), jnp.float32),
    }


def _state_shape(cfg: LlamaConfig) -> tuple[tuple[int, ...], int]:
    """(a row's state shape in one recurrent layer, its convolution's channels)."""
    if cfg.state_mixer == "ssm":
        from distributed_llama_tpu.ops import ssd

        return ssd.state_shape(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), cfg.ssm_conv_dim
    Hl, dl = cfg.lin_heads, cfg.lin_head_dim
    return (Hl, dl, dl), 3 * Hl * dl


def recurrent_state_bytes(cfg: LlamaConfig, rows: int) -> int:
    """Bytes of recurrent state and convolution tails ``rows`` rows hold."""
    if not cfg.is_recurrent:
        return 0
    S, channels = _state_shape(cfg)
    per_layer = 4 * (math.prod(S) + (cfg.lin_conv - 1) * channels)
    return rows * per_layer * len(cfg.layers_of(cfg.state_mixer))


def init_page_pool(
    cfg: LlamaConfig,
    n_pages: int,
    page: int,
    n_kv_heads_local: int | None = None,
    dtype=jnp.float32,
) -> list[tuple[jax.Array, jax.Array]]:
    """Prefix-cache page pool: a list of per-layer ``(keys, values)`` halves
    of [n_pages, page, Kl, hd] (engine.prefix_cache). Pages hold immutable,
    refcounted KV prefixes published from slab rows; a prefix hit copies
    them into its row at admission (one chip) or reads them in place through
    its page table (the tp backend: ops.attention paged variants). The HBM
    budget is
    n_pages * :func:`page_pool_bytes` — configured with ``--kv-pages`` on
    the serving surface."""
    from distributed_llama_tpu.ops import kv_cache as kvc

    kl = n_kv_heads_local if n_kv_heads_local is not None else cfg.cache_kv_heads
    # the pool holds the FULL layers' pages: a linear layer has no keys and
    # values, a window layer's are in its own small pool
    # (:func:`init_window_pool`); their entries are None. An EVA layer's page
    # here holds the block's SUMMARIES (page / eva_chunk entries, a sixteenth
    # of a page of keys and values at a chunk of 16): what a later prompt needs
    # of every block it shares; the keys themselves, which it needs of its last
    # window only, are in the window pool
    if cfg.has_eva:
        return _init_pool(cfg, "eva", n_pages, page // cfg.eva_chunk, kl, dtype)
    if cfg.has_latent:
        # a latent layer's page: ``page`` rows of latent_dim values, ONE half, flat; behind it,
        # where the layer has an indexer, the rows' index keys as a second flat half
        if kvc.is_quantized_cache_dtype(dtype):
            refuse_latent(cfg, "an i8 cache (its scales are one a head)")
        dims = (cfg.latent_dim, cfg.index_head_dim) if cfg.has_indexer else (cfg.latent_dim,)
        return [tuple(kvc.init_latent_pool(n_pages, page, dim, dtype) for dim in dims)
                for _ in range(cfg.n_layers)]
    return _init_pool(cfg, "full", n_pages, page, kl, dtype)


def _init_pool(cfg: LlamaConfig, mixer: str, n_pages: int, page: int, kl: int, dtype) -> list:
    from distributed_llama_tpu.ops import kv_cache as kvc

    return [
        (
            kvc.init_page_pool_half(n_pages, page, kl, cfg.cache_head_size, dtype),
            kvc.init_page_pool_half(n_pages, page, kl, cfg.cache_head_size, dtype),
        )
        if cfg.layer_kind(l)[0] == mixer else None
        for l in range(cfg.n_layers)
    ]


def init_window_pool(cfg: LlamaConfig, n_pages: int, page: int, dtype=jnp.float32) -> list:
    """The window layers' page pool: ``(keys, values)`` halves of [n_pages,
    page, K, hd] for a window layer, None for every other. A prefix hit that
    ends at a page boundary needs these layers' keys and values of the
    ``cfg.window`` positions before it and nothing older, so this pool holds
    the last pages of the prompts published lately (``engine.prefix_cache``:
    its own recency order) and does not grow with ``--kv-pages``. For an EVA
    arch every layer has one: a hit inside a window needs the keys and values
    of that window's positions before it (at most ``window / page`` pages)."""
    return _init_pool(cfg, "eva" if cfg.has_eva else "window", n_pages, page, cfg.n_kv_heads, dtype)


def page_pool_bytes(cfg: LlamaConfig, page: int, dtype, layers: int | None = None) -> int:
    """Logical KV bytes one pool page holds across the pool's layers (the
    full ones; ``layers``: of that many instead) and both halves (the
    telemetry accounting unit for pool occupancy and for the bytes a prefix
    hit copies into its row)."""
    from distributed_llama_tpu.ops import kv_cache as kvc

    kl, hd = cfg.n_kv_heads, cfg.head_size
    if cfg.has_latent:
        # one row a position and layer, no halves (and its index key, where there is an indexer)
        return ((cfg.n_layers if layers is None else layers) * page
                * (cfg.latent_dim + cfg.index_head_dim) * jnp.dtype(dtype).itemsize)
    if layers is None and cfg.has_eva:
        # an EVA arch's pool page holds the block's summaries, in every layer
        layers, page = cfg.n_layers, page // cfg.eva_chunk
    if kvc.is_quantized_cache_dtype(dtype):
        per_half = page * kl * hd + page * kl * 4  # int8 data + f32 scales
    else:
        per_half = page * kl * hd * jnp.dtype(dtype).itemsize
    return 2 * (len(cfg.layers_of("full")) if layers is None else layers) * per_half


def init_cache(
    cfg: LlamaConfig,
    n_kv_heads_local: int | None = None,
    dtype=jnp.float32,
    layered: bool = False,
) -> jax.Array | list[tuple[jax.Array, jax.Array]]:
    """Preallocated KV cache [L, 2, S, Kl, hd]
    (reference: KvCacheSlice, src/commands.cpp:97-102).

    ``layered=True`` returns a list of per-layer FUSED [2, S, Kl, hd]
    leaves (``leaf[0]``/``leaf[1]`` = keys/values) — the form the unrolled
    forward needs so in-place cache updates alias per leaf instead of
    copying the whole cache each step, with each layer's K/V pair written
    by ONE coalesced dynamic_update_slice (see attention). ``dtype="i8"``
    builds a quantized cache
    (:class:`distributed_llama_tpu.ops.kv_cache.QuantizedKV` with fused
    [2, S, Kl, hd] data — half the HBM of bf16; layered only). The tp/sp/ep
    backends build their own sharded ``(keys, values)``-tuple caches."""
    from distributed_llama_tpu.ops import kv_cache as kvc

    kl = n_kv_heads_local if n_kv_heads_local is not None else cfg.cache_kv_heads
    shape = (cfg.seq_len, kl, cfg.cache_head_size)
    if kvc.is_quantized_cache_dtype(dtype) and not layered:
        raise ValueError("the i8 KV cache requires the layered cache layout")
    if layered:
        return [_init_layer_leaf(cfg, l, (), kl, dtype) for l in range(cfg.n_layers)]
    if cfg.is_recurrent or cfg.has_window or cfg.has_latent:
        raise ValueError("layers of two kinds, and latent layers, need the layered cache layout")
    return jnp.zeros((cfg.n_layers, 2) + shape, dtype=dtype)
