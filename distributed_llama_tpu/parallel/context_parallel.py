"""Sequence/context parallelism: ring attention and sharded-KV decode.

The reference has NO long-context strategy — every node holds the full
sequence in its KV slice and attention is quadratic on one node
(SURVEY.md §5: "No ring attention / blockwise / Ulysses / CP anywhere"); its
only levers are --max-seq-len and a disc-backed KV cache. Here sequence
parallelism is first-class:

* :func:`ring_attention` — causal blockwise attention for prefill with the
  sequence sharded over an ``sp`` mesh axis. KV chunks rotate around the
  ring with ``jax.lax.ppermute`` while each device accumulates its query
  chunk's output with an online (flash-style) softmax — compute overlaps the
  ICI transfer, and no device ever materializes the full sequence.
* :func:`sp_sharded_attention` — Tq query rows against a sequence-sharded
  KV cache: each device attends over its local cache slice, then the
  partial (max, denominator, numerator) triples merge across the ring with
  one pmax + two psums. Tq==1 (:func:`sp_decode_attention`) is the decode
  step; Tq>1 drives the chunked mid-context prefill (:func:`_sp_chunk_forward`)
  that consumes chat/API delta prompts against a live cache in
  ceil(T/chunk) dispatches.

All run inside ``shard_map`` and are validated against full attention on a
virtual CPU mesh (tests/test_context_parallel.py).
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from distributed_llama_tpu.ops import kv_cache as kvc
from distributed_llama_tpu.ops.attention import (
    blocked_partials,
    chunk_attention,
    merge_partials,
)
from distributed_llama_tpu.parallel.tensor_parallel import TransferProbeMixin

# the online-softmax primitives live in ops.attention (shared with the dense
# blocked-attention path); keep the historical local names — they are part
# of this module's documented surface
_chunk_attention = chunk_attention
_merge = merge_partials


def ring_attention(
    q: jax.Array,  # [Tq, H, hd] local query chunk
    k: jax.Array,  # [Tk, K, hd] local key chunk
    v: jax.Array,  # [Tk, K, hd] local value chunk
    axis_name: str,
    chunk_offset: jax.Array | None = None,
) -> jax.Array:
    """Causal blockwise attention with the sequence sharded over
    ``axis_name``. Device i holds positions [i*Tq, (i+1)*Tq). Returns the
    local output chunk [Tq, H, hd] (f32).
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    Tq = q.shape[0]
    Tk = k.shape[0]
    H = q.shape[1]
    K = k.shape[1]
    kv_mul = H // K

    qg = q.reshape(Tq, K, kv_mul, q.shape[-1]).astype(jnp.float32)
    base = idx * Tq if chunk_offset is None else chunk_offset
    q_pos = base + jnp.arange(Tq)

    def step(s, carry):
        kc, vc, m, l, o = carry
        src_chunk = (idx - s) % n  # whose kv chunk we currently hold
        k_pos = src_chunk * Tk + jnp.arange(Tk)
        # kc/vc stay in cache dtype: the ring ppermute then moves half the
        # bytes for a bf16 cache, and _chunk_attention accumulates in f32
        ms, ls, os_ = _chunk_attention(qg, kc, vc, q_pos, k_pos)
        m, l, o = _merge(m, l, o, ms, ls, os_)
        # rotate kv around the ring: device i sends to i+1 (so chunks walk
        # backwards relative to each device's view)
        perm = [(j, (j + 1) % n) for j in range(n)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return kc, vc, m, l, o

    m0 = jnp.full((Tq, K, kv_mul), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((Tq, K, kv_mul), jnp.float32)
    o0 = jnp.zeros((Tq, K, kv_mul, q.shape[-1]), jnp.float32)
    _, _, m, l, o = jax.lax.fori_loop(0, n, step, (k, v, m0, l0, o0))

    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(Tq, H, q.shape[-1])


# key-axis chunk of the blocked local-slice scan (see ops.attention): local
# slices that are a multiple of this use a dynamic chunk bound — slots past
# the live position are never read, so sp decode cost follows the LIVE
# context, not the allocated S/sp slice (the dense path's round-5 blocked-
# attention win applied to the sequence-parallel slice scan)
SP_ATT_CHUNK = 512


def sp_sharded_attention(
    q: jax.Array,  # [Tq, H, hd] query rows (replicated across the axis)
    k_local: jax.Array,  # [Sl, K, hd] local KV-cache slice (sequence-sharded)
    v_local: jax.Array,  # [Sl, K, hd]
    q_pos: jax.Array,  # [Tq] absolute positions (each attends s <= its pos)
    axis_name: str,
) -> jax.Array:
    """Attention of Tq query rows over a sequence-sharded KV cache. Every
    device computes partials over its slice — blocked with a dynamic bound
    when the slice is chunk-divisible, one masked pass otherwise — and one
    pmax + two psums merge them (cross-device online-softmax merge).
    Returns [Tq, H, hd] (replicated). Tq==1 is the decode step; Tq>1 is
    the chunked mid-context prefill."""
    idx = jax.lax.axis_index(axis_name)
    Sl, K, hd = k_local.shape
    Tq, H = q.shape[0], q.shape[1]
    kv_mul = H // K
    qg = q.reshape(Tq, K, kv_mul, hd).astype(jnp.float32)
    base = idx * Sl
    if Sl % SP_ATT_CHUNK == 0 and Sl > SP_ATT_CHUNK:
        m, l, o = blocked_partials(qg, k_local, v_local, q_pos, base, SP_ATT_CHUNK)
        # the cross-shard pmax needs a finite max everywhere (a no-live-slot
        # shard reports -inf); the merge algebra is invariant to which
        # reference max is used, so clamp like chunk_attention's safe_m
        m = jnp.where(jnp.isfinite(m), m, 0.0)
    else:
        positions = base + jnp.arange(Sl)
        m, l, o = _chunk_attention(qg, k_local, v_local, q_pos, positions)
    g_m = jax.lax.pmax(m, axis_name)
    scale = jnp.exp(m - g_m)
    g_l = jax.lax.psum(l * scale, axis_name)
    g_o = jax.lax.psum(o * scale[..., None], axis_name)
    out = g_o / jnp.maximum(g_l, 1e-30)[..., None]
    return out.reshape(Tq, H, hd)


def sp_decode_attention(
    q: jax.Array,  # [H, hd] the single decode query (replicated)
    k_local: jax.Array,  # [Sl, K, hd] local KV-cache slice (sequence-sharded)
    v_local: jax.Array,  # [Sl, K, hd]
    pos: jax.Array,  # scalar: current absolute position (attend s <= pos)
    axis_name: str,
) -> jax.Array:
    """One-token attention over a sequence-sharded KV cache: the Tq==1 case
    of :func:`sp_sharded_attention`. Returns [H, hd] (replicated)."""
    return sp_sharded_attention(
        q[None], k_local, v_local, jnp.asarray([pos]), axis_name
    )[0]


# ---------------------------------------------------------------------------
# Sequence-parallel engine backend
# ---------------------------------------------------------------------------


class SequenceParallelForward(TransferProbeMixin):
    """Sequence/context parallelism as an engine backend: the KV cache is
    sharded along the SEQUENCE axis over an ``sp`` mesh (device i owns slots
    [i*S/n, (i+1)*S/n)), weights are replicated, prefill runs
    :func:`ring_attention` over position chunks, and decode attends its local
    cache slice with the cross-device online-softmax merge of
    :func:`sp_decode_attention`.

    This is the long-context strategy the reference lacks entirely
    (SURVEY.md §5): per-device KV memory drops to 1/n — the same memory
    shape as the reference's per-node KvCacheSlice (src/commands.cpp:97-102)
    but over the sequence instead of heads, so it composes with long
    contexts rather than head counts.

    Prefill routing: a prompt that fills a large fraction of the context
    (T*RING_PREFILL_FRACTION >= seq_len) takes the ring-attention path,
    which processes the FULL padded context (the prompt is padded to
    seq_len so every device owns exactly its cache slice's positions —
    uniform chunks are what make the ring collective regular; its blockwise
    causal attention and overlapped ppermutes are what win at that scale).
    SHORT prompts instead run the same fixed-width masked-scatter chunk
    path as mid-context prompts (ceil(T/32) dispatches, cost O(prompt) +
    O(S/sp) local attention per chunk) — previously every prompt paid the
    O(S) padded ring pass, which made sp serving of short prompts
    pathological (round-4 verdict item 5).

    ``tp > 1`` composes tensor parallelism on a 2-D ``(tp, sp)`` mesh — the
    scaling-book recipe the reference's 1-D TCP star cannot express: weights
    and attention heads shard over ``tp`` (psum after wo/down rides one mesh
    axis), the sequence and KV cache shard over ``sp`` (ring/online-softmax
    collectives ride the other), and the KV cache shrinks by tp*sp per
    device (heads AND sequence).
    """

    def __init__(self, cfg, sp: int, tp: int = 1, quantized: bool = False, devices=None):
        import functools

        from jax.experimental import mesh_utils
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from distributed_llama_tpu.parallel.tensor_parallel import (
            param_specs_layered,
            q40_param_specs,
            validate_tp,
        )

        if cfg.seq_len % sp:
            raise ValueError(f"sp={sp} must divide seq_len={cfg.seq_len}")
        if tp > 1:
            validate_tp(cfg, tp, quantized=quantized)
        self.cfg = cfg
        self.sp = sp
        self.tp = tp
        self.quantized = quantized
        n_dev = tp * sp
        if devices is None:
            devices = jax.devices()[:n_dev]
        if len(devices) < n_dev:
            raise ValueError(f"need {n_dev} devices (tp*sp), have {len(devices)}")
        self.mesh = Mesh(
            mesh_utils.create_device_mesh((tp, sp), devices=devices[:n_dev]),
            ("tp", "sp"),
        )
        self._P = P
        self._NamedSharding = NamedSharding
        self.shard_vocab = tp > 1 and cfg.vocab_size % tp == 0
        # per-layer (keys, values) tuples of [S, K, hd]: sequence slots
        # shard over sp, KV heads over tp (one spec is the pytree prefix
        # covering both tuple leaves)
        cache_ax = P("sp", "tp", None) if tp > 1 else P("sp", None, None)
        self._cache_spec = [cache_ax] * cfg.n_layers
        if tp == 1:
            self._pspecs = P()  # fully replicated params
        elif quantized:
            self._pspecs = q40_param_specs(cfg, cfg.n_layers, self.shard_vocab)
        else:
            self._pspecs = param_specs_layered(cfg, cfg.n_layers, self.shard_vocab)
        self._tp_axis = "tp" if tp > 1 else None
        self._decode_cache: dict = {}
        # the engine must not bucket-pad mid-context prompts for this
        # backend: it chunks them itself (fixed-size masked-scatter passes,
        # see _sp_chunk_forward) so only one program shape compiles
        self.prefers_exact_mid_prefill = True
        # chunk width of the mid-context prefill: one dispatch consumes up
        # to this many tokens (padded to exactly this many)
        self.mid_prefill_chunk = 32
        # dispatches issued by the most recent forward() call ON THIS THREAD
        # — the engine scales its measured per-dispatch transfer estimate by
        # it. Thread-local: concurrent serving streams call forward() from
        # their own request threads, and a shared counter would let stream
        # A's chunked mid-prefill count leak into stream B's I/T stats split.
        # Each thread reads back exactly what its own forward issued;
        # threads that never forwarded read the 1-dispatch default.
        self._dispatch_local = threading.local()

        prefill = jax.shard_map(
            functools.partial(_sp_prefill, cfg, self._tp_axis),
            mesh=self.mesh,
            in_specs=(self._pspecs, P("sp"), self._cache_spec),
            out_specs=(P("sp"), self._cache_spec),
            check_vma=False,
        )
        self._prefill = jax.jit(prefill, donate_argnums=(2,))

        step = jax.shard_map(
            functools.partial(_sp_decode_step, cfg, self._tp_axis),
            mesh=self.mesh,
            in_specs=(self._pspecs, P(), self._cache_spec, P()),
            out_specs=(P(), self._cache_spec),
            check_vma=False,
        )
        self._step = jax.jit(step, donate_argnums=(2,))

        chunk_fwd = jax.shard_map(
            functools.partial(_sp_chunk_forward, cfg, self._tp_axis),
            mesh=self.mesh,
            in_specs=(self._pspecs, P(), self._cache_spec, P()),
            out_specs=(P(), self._cache_spec),
            check_vma=False,
        )
        self._chunk_fwd = jax.jit(chunk_fwd, donate_argnums=(2,))

    # -- engine interface ---------------------------------------------------

    @property
    def last_forward_dispatches(self) -> int:
        """Dispatch count of the calling thread's most recent forward()
        (per-thread snapshot — see the ``_dispatch_local`` note)."""
        return getattr(self._dispatch_local, "n", 1)

    def shard_params(self, host_params):
        from distributed_llama_tpu.parallel.tensor_parallel import place_params

        return place_params(host_params, self._pspecs, self.mesh)

    def init_cache(self, dtype=jnp.float32):
        import numpy as np

        cfg = self.cfg
        shape = (cfg.seq_len, cfg.n_kv_heads, cfg.head_size)
        sharding = self._NamedSharding(self.mesh, self._cache_spec[0])

        def zeros(gshape, dt):
            # gshape is GLOBAL; (sequence, kv-head) shard per device — the
            # spec prefix covers QuantizedKV's rank-3 scales leaf too
            local = np.zeros(
                (gshape[0] // self.sp, gshape[1] // self.tp) + gshape[2:], dt
            )
            return jax.make_array_from_callback(gshape, sharding, lambda idx: local)

        return [
            (kvc.init_half(shape, dtype, zeros=zeros),
             kvc.init_half(shape, dtype, zeros=zeros))
            for _ in range(cfg.n_layers)
        ]

    # a prompt whose length * this fraction reaches seq_len takes the ring
    # path; shorter prompts take the O(prompt) chunked path (see class
    # docstring)
    RING_PREFILL_FRACTION = 4

    def forward(self, params, tokens, cache, pos):
        """Engine forward: T==1 routes to the decode step; a long T at pos 0
        (T*RING_PREFILL_FRACTION >= seq_len) is the ring-attention
        full-context prefill (tokens padded to seq_len — every device owns
        exactly its cache slice's positions). Every other multi-token
        forward — short initial prompts AND chat/API delta prompts against
        a live cache — runs chunked: ceil(T/mid_prefill_chunk) fixed-width
        masked-scatter dispatches (see _sp_chunk_forward) instead of the
        O(S) padded ring pass or one dispatch per token."""
        tokens = jnp.asarray(tokens)
        T = tokens.shape[0]
        self._dispatch_local.n = 1
        if T == 1:
            return self._step(params, tokens, cache, jnp.asarray(pos))
        S = self.cfg.seq_len
        if int(pos) != 0 or T * self.RING_PREFILL_FRACTION < S:
            CH = self.mid_prefill_chunk
            rows = []
            p = int(pos)
            for i in range(0, T, CH):
                chunk = tokens[i : i + CH]
                c = chunk.shape[0]
                if c < CH:
                    # pad to the one compiled width; pad rows write stale
                    # cache slots beyond pos+T, unreachable per the engine's
                    # rollback contract (overwritten before pos crosses them)
                    chunk = jnp.pad(chunk, (0, CH - c))
                logits, cache = self._chunk_fwd(
                    params, chunk, cache, jnp.asarray(p)
                )
                rows.append(logits[:c])
                p += c
            self._dispatch_local.n = (T + CH - 1) // CH
            return jnp.concatenate(rows, axis=0), cache
        if T != S:
            tokens = jnp.pad(tokens, (0, S - tokens.shape[0]))
        return self._prefill(params, tokens, cache)

    def decode_loop(
        self, params, first_token, cache, pos, n_steps, temperature, topp,
        seed: int = 0, topk: int = 0,
    ):
        from distributed_llama_tpu import prng

        tokens, cache = self._decode_scan(
            int(n_steps), float(temperature), float(topp), int(topk)
        )(
            params, jnp.asarray(first_token), cache, jnp.asarray(pos),
            jnp.uint32(prng.fold_seed(seed)),
        )
        return tokens, cache

    def decode_chunk(
        self, params, first_token, cache, pos, n_steps, temperature, topp,
        topk, seed32,
    ):
        jitted = self._decode_scan(int(n_steps), None, None, None)
        return jitted(
            params, jnp.asarray(first_token), cache, jnp.asarray(pos),
            jnp.float32(temperature), jnp.float32(topp), jnp.int32(topk),
            jnp.asarray(seed32, jnp.uint32),
        )

    def _decode_scan(self, n_steps: int, temperature, topp, topk):
        """Jitted on-device decode loop; sampler params static when given
        (decode_loop) or traced scalars when None (decode_chunk — one
        compiled program per chunk size serves every sampler setting).
        Coins come from the stateless counter PRNG keyed (seed, position),
        so nothing threads between chunks (ISSUE 13)."""
        from distributed_llama_tpu.models import sampling

        P = self._P
        key_ = (n_steps, temperature, topp, topk)
        cached = self._decode_cache.get(key_)
        if cached is not None:
            return cached
        cfg = self.cfg

        tp_axis = self._tp_axis

        def scan_body(params, first_token, cache, pos, seed, t, p, k_top):
            def step(carry, _):
                token, cache_c, pp = carry
                logits, cache_c = _sp_decode_step(
                    cfg, tp_axis, params, token[None], cache_c, pp
                )
                nxt = sampling.sample_token(logits[0], seed, pp, t, p, k_top)
                return (nxt, cache_c, pp + 1), nxt

            (_, cache, _), tokens = jax.lax.scan(
                step, (first_token.astype(jnp.int32), cache, pos.astype(jnp.int32)),
                None, length=n_steps,
            )
            return tokens, cache

        if temperature is None:  # dynamic sampler params

            def fn(params, first_token, cache, pos, t_in, p_in, k_in, seed):
                return scan_body(
                    params, first_token, cache, pos, seed, t_in, p_in, k_in
                )

            in_specs = (self._pspecs, P(), self._cache_spec, P(), P(), P(), P(), P())
        else:

            def fn(params, first_token, cache, pos, seed):
                return scan_body(
                    params, first_token, cache, pos, seed, temperature, topp,
                    topk,
                )

            in_specs = (self._pspecs, P(), self._cache_spec, P(), P())
        mapped = jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs,
            out_specs=(P(), self._cache_spec), check_vma=False,
        )
        jitted = jax.jit(mapped, donate_argnums=(2,))
        self._decode_cache[key_] = jitted
        return jitted

    def transfer_probe(self, n_tokens: int = 32):
        """(jitted_fn, example_args) replaying the sp decode's collective
        sequence: per layer one pmax + two psums of the online-softmax
        partials (see sp_sharded_attention), plus the two tp all-reduces
        when a 2-D mesh is in use. Exposed so tests can compile it and
        assert the collectives survive XLA DCE (the keep-alive arithmetic
        is what the timing validity rests on)."""
        cfg = self.cfg
        H, hd = cfg.n_heads, cfg.head_size
        K = cfg.n_kv_heads // self.tp  # local KV heads under the 2-D mesh
        M = max(1, (H // self.tp) // max(K, 1))
        tp_axis = self._tp_axis

        def token_step(carry, _):
            m, o, z = carry

            def layer(c, _):
                mm, oo, zz = c
                g_m = jax.lax.pmax(mm, "sp")
                g_l = jax.lax.psum(mm * 0.5, "sp")
                g_o = jax.lax.psum(oo, "sp")
                if tp_axis is not None:
                    # the wo/down all-reduces carry a FULL [1, dim]
                    # activation each (llama.block_tail), not the smaller
                    # attention partials — model them at true size
                    zz = jax.lax.psum(zz, tp_axis) * 0.5
                    zz = jax.lax.psum(zz, tp_axis) * 0.5
                return (g_m + g_l * 1e-9, g_o * 0.5, zz), None

            (m, o, z), _ = jax.lax.scan(layer, (m, o, z), None, length=cfg.n_layers)
            return (m, o, z), None

        def fn(m, o, z):
            (m, o, z), _ = jax.lax.scan(token_step, (m, o, z), None, length=n_tokens)
            return m, o, z

        P = self._P
        mapped = jax.shard_map(
            fn, mesh=self.mesh, in_specs=(P(), P(), P()), out_specs=(P(), P(), P()),
            check_vma=False,
        )
        m = jnp.ones((1, K, M), jnp.float32)
        o = jnp.ones((1, K, M, hd), jnp.float32)
        z = jnp.ones((1, cfg.dim), jnp.float32)
        return jax.jit(mapped), (m, o, z)

    def transfer_bytes_per_token(self) -> int:
        """The probed sp decode sequence per layer: pmax + psum of the
        online-softmax max/normalizer partials ([1, K, M] each) and a psum
        of the output partial ([1, K, M, hd]) over sp, plus the two full
        [1, dim] tp all-reduces on a 2-D mesh (see :meth:`transfer_probe`)."""
        cfg = self.cfg
        K = cfg.n_kv_heads // self.tp
        M = max(1, (cfg.n_heads // self.tp) // max(K, 1))
        per_layer = (2 * K * M + K * M * cfg.head_size) * 4
        if self._tp_axis is not None:
            per_layer += 2 * cfg.dim * 4
        return cfg.n_layers * per_layer


def _sp_logits(cfg, tp_axis, params, x):
    """Final logits with the optional tp vocab-shard all-gather."""
    from distributed_llama_tpu.models import llama

    logits = llama.final_logits(cfg, params, x)
    if tp_axis is not None and logits.shape[-1] != cfg.vocab_size:
        logits = jax.lax.all_gather(logits, tp_axis, axis=1, tiled=True)
    return logits


def _sp_prefill(cfg, tp_axis, params, tokens_local, cache):
    """Per-shard prefill body: ring attention over position chunks. Device i
    processes positions [i*Tl, (i+1)*Tl) — exactly its cache slice. Block
    wiring (norms, projections, residuals, FFN/MoE, logits) is shared with
    the dense path via llama's helpers; only attention differs. Under a 2-D
    mesh, projections/FFN are tp-sharded (psum over ``tp_axis``) while the
    ring rides ``sp`` — the two collective families never mix."""
    from distributed_llama_tpu.models import llama

    idx = jax.lax.axis_index("sp")
    Tl = tokens_local.shape[0]
    offset = idx * Tl
    x = llama.embed(cfg, params, tokens_local)
    rope_rows = jax.lax.dynamic_slice(
        params["rope_table"], (offset, 0, 0),
        (Tl,) + params["rope_table"].shape[1:],
    )

    new_cache = []
    for lp, cache_l in zip(params["layers"], cache):
        q, k, v = llama.project_qkv(cfg, lp, x, rope_rows)
        H = q.shape[1]
        if isinstance(cache_l[0], kvc.QuantizedKV):
            # each device's fresh chunk IS its whole cache slice: store it
            # quantized; the ring below attends the raw rows (bf16 on the
            # wire — quantizing the ring would only trade accuracy for ICI
            # bytes the prefill doesn't bottleneck on)
            kq, ks = kvc.quantize_rows(k)
            vq, vs = kvc.quantize_rows(v)
            new_cache.append(
                (kvc.QuantizedKV(kq, ks), kvc.QuantizedKV(vq, vs))
            )
        else:
            cdt = cache_l[0].dtype
            k = k.astype(cdt)
            v = v.astype(cdt)
            new_cache.append((k, v))
        att = ring_attention(
            q.astype(jnp.float32), k, v, "sp", chunk_offset=offset
        ).reshape(Tl, H * cfg.head_size)
        x = llama.block_tail(cfg, x, att, lp, tp_axis)

    return _sp_logits(cfg, tp_axis, params, x), new_cache


def _sp_chunk_forward(cfg, tp_axis, params, tokens, cache, pos):
    """Per-shard mid-context chunk forward: C tokens at global positions
    pos..pos+C-1 against the LIVE sequence-sharded cache (a chat/API delta
    prompt). Compute is replicated across ``sp`` except attention:

    * each shard masked-scatters the chunk's new K/V rows into its own cache
      slice (rows owned by other shards — or pad rows past seq_len — drop
      via an out-of-bounds sentinel index),
    * then attends the C queries over its updated local slice and merges
      partials across the ring with the same pmax/psum online-softmax merge
      as :func:`sp_decode_attention` (generalized to C query rows).

    One dispatch consumes C tokens — replacing the one-dispatch-per-token
    fallback that made ``--sp`` unusable for multi-turn chat."""
    from distributed_llama_tpu.models import llama

    idx = jax.lax.axis_index("sp")
    C = tokens.shape[0]
    hd = cfg.head_size
    x = llama.embed(cfg, params, tokens)  # [C, dim]
    gpos = pos + jnp.arange(C)
    # gather (not dynamic_slice): a padded chunk near the context limit would
    # clamp a slice's START and shift every real token's rope row
    rope_rows = jnp.take(
        params["rope_table"], jnp.clip(gpos, 0, cfg.seq_len - 1), axis=0
    )

    new_cache = []
    for lp, cache_l in zip(params["layers"], cache):
        Sl = cache_l[0].shape[0]
        q, k, v = llama.project_qkv(cfg, lp, x, rope_rows)
        H, K = q.shape[1], k.shape[1]

        local = gpos - idx * Sl
        in_range = (local >= 0) & (local < Sl)
        slot = jnp.where(in_range, local, Sl)  # Sl is out of bounds -> drop
        keys = kvc.scatter_rows(cache_l[0], slot, k)
        values = kvc.scatter_rows(cache_l[1], slot, v)
        new_cache.append((keys, values))

        att = sp_sharded_attention(
            q.astype(jnp.float32), keys, values, gpos, "sp"
        ).reshape(C, H * hd)
        x = llama.block_tail(cfg, x, att, lp, tp_axis)

    return _sp_logits(cfg, tp_axis, params, x), new_cache


def _sp_decode_step(cfg, tp_axis, params, tokens, cache, pos):
    """Per-shard single-token decode: replicated compute except attention,
    which reads only the local cache slice and merges partials across the
    ring. The new token's K/V row is written on the owning shard only."""
    from distributed_llama_tpu.models import llama

    idx = jax.lax.axis_index("sp")
    x = llama.embed(cfg, params, tokens)  # [1, dim]
    rope_rows = jax.lax.dynamic_slice(
        params["rope_table"], (pos, 0, 0), (1,) + params["rope_table"].shape[1:]
    )
    hd = cfg.head_size

    new_cache = []
    for lp, cache_l in zip(params["layers"], cache):
        Sl = cache_l[0].shape[0]
        q, k, v = llama.project_qkv(cfg, lp, x, rope_rows)
        H, K = q.shape[1], k.shape[1]

        # write the new K/V row on the owning shard: every shard performs the
        # same dynamic_update_slice (aliasing-friendly), non-owners write the
        # row they already had back into place
        owner = (pos >= idx * Sl) & (pos < (idx + 1) * Sl)
        lpos = jnp.clip(pos - idx * Sl, 0, Sl - 1)
        keys = kvc.select_row_update(cache_l[0], k, lpos, owner)
        values = kvc.select_row_update(cache_l[1], v, lpos, owner)
        new_cache.append((keys, values))

        att = sp_decode_attention(
            q[0].astype(jnp.float32), keys, values, pos, "sp"
        ).reshape(1, H * hd)
        x = llama.block_tail(cfg, x, att, lp, tp_axis)

    return _sp_logits(cfg, tp_axis, params, x), new_cache
