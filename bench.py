#!/usr/bin/env python
"""Benchmark: autoregressive decode throughput of the flagship model on the
available accelerator. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Model: Llama 2 7B architecture (the reference's headline benchmark model),
bf16 weights, random-initialized — throughput is a shape problem, checkpoint
bytes don't change it. Decode is the reference's own measured regime: one
token per step, sampling on host (reference: src/apps/dllama/dllama.cpp:45-94).

Baseline: the reference's best published *single-node* Llama 2 7B number,
101.81 ms/token (9.82 t/s) on a GCP c3d-highcpu-30 VM (reference:
README.md:131, weights Q40 buffer Q80). One TPU chip takes the place of one
CPU node — the same 1-device slot in the reference's scaling table.
"""

import json
import os
import sys

import numpy as np

from distributed_llama_tpu import telemetry
from distributed_llama_tpu.stats import median, median_by
from distributed_llama_tpu.telemetry import Stopwatch


BASELINE_TPS = 1000.0 / 101.81  # Llama 2 7B, 1× GCP c3d-highcpu-30 (README.md:131)


def bench_metric(name: str, value: float, unit: str = "") -> float:
    """Record one bench measurement as a registry gauge and read it back.

    The returned value — the one that lands in BENCH_*.json — IS the
    registry value, so the JSON report and live telemetry
    (`python -m distributed_llama_tpu.telemetry.dump`) come from one code
    path instead of bench keeping a private stats stash (ISSUE 1)."""
    g = telemetry.REGISTRY.gauge(
        f"dllama_bench_{name}", f"bench.py measurement{f' ({unit})' if unit else ''}"
    )
    g.set(value)
    return g.value


def params_hbm_bytes(params) -> int:
    """Resident weight bytes one decode step reads (every param leaf once:
    packed nibbles + scales for q40, raw array bytes otherwise — the
    numerator of the decode roofline model). Embedding/rope rows are read
    sparsely per token but included for a conservative (slightly high)
    byte count; decode is weight-read dominated either way."""
    import jax
    import jax.numpy as jnp

    total = 0
    for leaf in jax.tree.leaves(params):
        total += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    return total


# HBM peak for the roofline denominator: v5e ≈ 819 GB/s (docs/PERF.md's
# profiled kernel numbers use the same figure). Override for other chip
# generations; on a CPU host the fraction is reported but meaningless
# (there is no 819 GB/s bus — the field exists so TPU runs gate on it).
HBM_PEAK_GBPS = float(os.environ.get("DLT_HBM_GBPS", 819.0))


def roofline_detail(n_bytes: int, tps: float, prefix: str = "") -> dict:
    """The computed decode roofline: achieved HBM bytes/s = model bytes per
    token × measured tok/s, as a fraction of peak — the kernel A/B gate as
    a number in BENCH_*.json instead of prose (ISSUE 14)."""
    achieved = n_bytes * tps
    frac = achieved / (HBM_PEAK_GBPS * 1e9)
    return {
        f"{prefix}model_bytes_per_token": int(
            bench_metric(f"{prefix}model_bytes_per_token", n_bytes, "bytes")),
        f"{prefix}achieved_gbytes_per_sec": round(
            bench_metric(f"{prefix}achieved_gbytes_per_sec", achieved / 1e9,
                         "GB/s"), 3),
        f"{prefix}roofline_fraction": round(
            bench_metric(f"{prefix}roofline_fraction", frac), 4),
        f"{prefix}hbm_peak_gbytes_per_sec": HBM_PEAK_GBPS,
    }


def llama2_7b_config(seq_len: int):
    from distributed_llama_tpu.formats.synthetic import llama2_7b_spec
    from distributed_llama_tpu.models.config import config_from_spec

    return config_from_spec(llama2_7b_spec(seq_len=seq_len))


def mixtral_shaped_config(seq_len: int):
    """A Mixtral-shaped MoE config scaled to one chip's HBM (8 experts
    top-2 like Mixtral 8x7B; dim/head geometry of the 7B class, hidden and
    layer count shrunk so the q40 expert banks fit): the multi-model perf
    probe behind `bench.py --mixtral-only` (BASELINE config 3's shape
    class — the reference publishes no Mixtral number to compare against)."""
    from distributed_llama_tpu.formats.model_file import ArchType, HiddenAct, RopeType
    from distributed_llama_tpu.models.config import LlamaConfig

    return LlamaConfig(
        arch=ArchType.MIXTRAL,
        dim=4096,
        hidden_dim=4096,
        n_layers=8,
        n_heads=32,
        n_kv_heads=8,
        vocab_size=32000,
        seq_len=seq_len,
        head_size=128,
        kv_dim=1024,
        hidden_act=HiddenAct.SILU,
        rope_type=RopeType.FALCON,
        rope_theta=10000.0,
        n_experts=8,
        n_active_experts=2,
    )


def random_q40_params_on_device(cfg):
    """Synthetic Q40 params: random packed nibbles + constant scales, built
    on device, layers UNSTACKED, in the STANDARD activation basis — the
    block-interleaved basis is retired (the int8 MXU scale-product epilogue
    made the permute moot; basis-era checkpoints are de-interleaved at
    load by engine/weights.remove_basis_interleave). Kernel throughput
    does not depend on the values."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models.rope import build_rope_table
    from distributed_llama_tpu.ops.q40 import (
        QuantizedMatrix,
        _d_padded,
        _n_padded,
    )

    keys = iter(jax.random.split(jax.random.PRNGKey(0), (2 * cfg.n_experts + 8) * cfg.n_layers + 8))

    def qmat(n, d):
        # the padding rules live in ops.q40 — a local copy desyncing
        # would silently route the bench onto the slow XLA fallback
        n_pad = _n_padded(n)
        d_pad = _d_padded(d)
        qs = jax.random.bits(next(keys), (n_pad // 2, d_pad), dtype=jnp.uint8)
        scales = jnp.full((n_pad // 32, d_pad), 1.0 / 256, jnp.float32)
        return QuantizedMatrix(qs, scales, n_logical=n, d_logical=d)

    D, F, V, H, K, hd = (
        cfg.dim, cfg.hidden_dim, cfg.vocab_size, cfg.n_heads, cfg.n_kv_heads, cfg.head_size,
    )

    def layer():
        lp = {
            "qkv": qmat(D, (H + 2 * K) * hd),  # fused q|k|v
            "wo": qmat(H * hd, D),
            "rms_att": jnp.ones(D, jnp.float32), "rms_ffn": jnp.ones(D, jnp.float32),
        }
        if cfg.is_moe:
            lp["router"] = jax.random.normal(next(keys), (D, cfg.n_experts), jnp.float32) * 0.05
            lp["experts"] = [
                {"gate_up": qmat(D, 2 * F), "down": qmat(F, D)}
                for _ in range(cfg.n_experts)
            ]
        else:
            lp["gate_up"] = qmat(D, 2 * F)
            lp["down"] = qmat(F, D)
        return lp

    layers = [layer() for _ in range(cfg.n_layers)]
    return {
        "embedding": jax.random.normal(next(keys), (V, D), jnp.float32) * 0.02,
        "layers": layers,
        "rms_final": jnp.ones(D, jnp.float32),
        "wcls": qmat(D, V),
        "rope_table": jnp.asarray(build_rope_table(cfg)),
    }


def run(cfg, name: str, prefill_len: int = 64, steps: int = 128, weights: str = "bf16") -> dict:
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.engine.weights import random_params_on_device
    from distributed_llama_tpu.models import llama

    if weights == "q40":
        params = random_q40_params_on_device(cfg)
    else:
        # layered = the production per-layer-list layout (engine.weights)
        params = random_params_on_device(cfg, dtype=jnp.bfloat16, seed=0, layered=True)
    cache = llama.init_cache(cfg, dtype=jnp.bfloat16, layered=True)

    import functools

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
    def fwd(cfg, params, tokens, cache, pos):
        return llama.forward_tokens(cfg, params, tokens, cache, pos)

    from distributed_llama_tpu.models.sampling import decode_loop

    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, prefill_len, dtype=np.int32))

    with telemetry.trace_span("bench_prefill_cold", tokens=prefill_len):
        sw = Stopwatch()
        logits, cache = fwd(cfg, params, prompt, cache, jnp.int32(0))
        np.asarray(logits[-1])  # fetch ONE row: the serving pattern (engine.prefill)
        prefill_ms = sw.elapsed_ms()  # COLD: includes XLA compile

    # warm prefill: same shape at a later position reuses the executable —
    # this is the steady-state serving number (round-2 verdict item #4).
    # Median of 3.
    warm_times = []
    for i in range(3):
        with telemetry.trace_span("bench_prefill_warm", rep=i):
            sw = Stopwatch()
            logits, cache = fwd(cfg, params, prompt, cache, jnp.int32((1 + i) * prefill_len))
            np.asarray(logits[-1])
            warm_times.append(sw.elapsed_ms())
    prefill_warm_ms = median(warm_times)

    # AMORTIZED prefill: K chained dispatches, ONE fence — the per-dispatch
    # host overhead and the fetch are paid once per K, as on the serving
    # path (prefill_device fuses prefill→sample→chunk-1 with no
    # intermediate fetch). Median of 3.
    K = 16
    dev_times = []
    for r in range(3):
        with telemetry.trace_span("bench_prefill_device", rep=r):
            sw = Stopwatch()
            for i in range(K):
                logits, cache = fwd(cfg, params, prompt, cache, jnp.int32((i % 4) * prefill_len))
            np.asarray(logits[-1])
            dev_times.append(sw.elapsed_ms() / K)
    prefill_device_ms = max(median(dev_times), 1e-3)
    prefill_tps = prefill_len / prefill_device_ms * 1000.0

    token = jnp.int32(np.argmax(np.asarray(logits[-1])))
    single_base = 4 * prefill_len  # fixed window: decode_loop replays 256..384
    chunk_base = single_base + steps  # chunked replays 384..512

    # warmup: n_steps is a static argument, so the warm call must use the
    # SAME step count as the measured call or XLA compiles inside the timing
    import jax.random

    from distributed_llama_tpu.models.sampling import decode_chunk

    warm, cache = decode_loop(cfg, params, token, cache, jnp.int32(single_base), steps,
                              0.0, 0.9, seed=0)
    np.asarray(warm)
    token = warm[-1]
    chunk = 32
    seed32 = jnp.uint32(2)
    toks, cache = decode_chunk(cfg, params, token, cache, jnp.int32(chunk_base), chunk,
                               jnp.float32(0.0), jnp.float32(0.9),
                               jnp.int32(0), seed32)  # warm/compile
    np.asarray(toks)

    # single-dispatch and chunked (user-path) decode, INTERLEAVED with
    # median-of-3: interleaving keeps slow drift of the host from being
    # read as a difference between the two code paths.
    # Every rep replays the same fixed position windows — identical
    # executables and identical work; the KV contents are random-weight
    # garbage either way.
    n_chunks = 4
    single_runs, user_runs = [], []
    for rep in range(3):
        with telemetry.trace_span("bench_decode_single", rep=rep):
            sw = Stopwatch()
            tokens, cache = decode_loop(cfg, params, token, cache, jnp.int32(single_base),
                                        steps, 0.0, 0.9, seed=1)
            np.asarray(tokens)
            single_runs.append(steps / sw.elapsed_s())

        pos = chunk_base
        sw = Stopwatch()
        for _ in range(n_chunks):
            # pipelined like engine.generate_chunks: dispatch the next chunk
            # off the device-resident last token, start the previous chunk's
            # host copy, then block on it — fetch overlaps compute
            nxt, cache = decode_chunk(cfg, params, toks[-1], cache, jnp.int32(pos),
                                      chunk, jnp.float32(0.0), jnp.float32(0.9),
                                      jnp.int32(0), seed32)
            try:
                toks.copy_to_host_async()
            except Exception:
                pass
            np.asarray(toks)
            toks = nxt
            pos += chunk
        np.asarray(toks)  # the last dispatched chunk must finish in-window
        user_runs.append(n_chunks * chunk / sw.elapsed_s())
    tps = median(single_runs)
    user_tps = median(user_runs)

    # secondary: host-sampled stepwise decode (the reference's exact regime,
    # pays a host<->device round trip per token); warm the 1-token shape first
    pos = chunk_base + n_chunks * chunk
    tok = int(np.asarray(tokens[-1]))
    logits, cache = fwd(cfg, params, jnp.asarray([tok], jnp.int32), cache, jnp.int32(pos))
    tok = int(np.argmax(np.asarray(logits[0])))
    pos += 1
    with telemetry.trace_span("bench_decode_host_stepwise"):
        sw = Stopwatch()
        for _ in range(16):
            logits, cache = fwd(cfg, params, jnp.asarray([tok], jnp.int32), cache, jnp.int32(pos))
            tok = int(np.argmax(np.asarray(logits[0])))
            pos += 1
        host_tps = 16 / sw.elapsed_s()

    # every reported number passes through the telemetry registry
    # (bench_metric): the JSON below and a live scrape see the same values
    return {
        "metric": f"{name}_{weights}_decode_tokens_per_sec_1chip",
        "value": round(bench_metric("decode_tokens_per_sec", tps, "tokens/sec"), 2),
        "unit": "tokens/sec",
        "vs_baseline": round(bench_metric("vs_baseline", tps / BASELINE_TPS), 2),
        "detail": {
            # the decode roofline (ISSUE 14): achieved bytes/s from model
            # bytes/token × measured tok/s vs the HBM peak — the kernel
            # A/B gate as a number, not prose
            **roofline_detail(params_hbm_bytes(params), tps),
            "ms_per_token": round(bench_metric("decode_ms_per_token", 1000.0 / tps, "ms"), 2),
            # the CLI/API fast path
            "chunked_decode_tokens_per_sec": round(
                bench_metric("chunked_decode_tokens_per_sec", user_tps, "tokens/sec"), 2),
            "host_sampled_tokens_per_sec": round(
                bench_metric("host_sampled_tokens_per_sec", host_tps, "tokens/sec"), 2),
            # cold includes XLA compile; warm = 1 dispatch + 1 fetch
            "prefill_ms_64_tokens_cold": round(
                bench_metric("prefill_cold_ms", prefill_ms, "ms"), 1),
            "prefill_ms_64_tokens_warm": round(
                bench_metric("prefill_warm_ms", prefill_warm_ms, "ms"), 1),
            # amortized over K chained dispatches
            "prefill_ms_64_tokens_device": round(
                bench_metric("prefill_device_ms", prefill_device_ms, "ms"), 1),
            "prefill_tokens_per_sec": round(
                bench_metric("prefill_tokens_per_sec", prefill_tps, "tokens/sec"), 1),
            "baseline": "Llama 2 7B 101.81 ms/token, 1x GCP c3d-highcpu-30 (reference README.md:131)",
            "device": None,
        },
    }


def run_batch(cfg, name: str, B: int, prefill_len: int = 64, chunk: int = 32,
              n_rounds: int = 4, weights: str = "q40") -> dict:
    """Batched multi-stream decode vs B interleaved single-sequence streams
    (`bench.py --batch-decode B`): the aggregate-tok/s scaling proof of the
    batch scheduler. Decode is HBM-bound, so B interleaved single-sequence
    dispatches serialize on the weight reads (round-5 measured 97.3 vs 95.8
    tok/s — fairness, not tokens); the batched step reads each weight matrix
    once for all B rows. Both paths replay identical fixed position windows,
    interleaved-free medians of 3 like run()."""
    import gc

    import jax
    import jax.numpy as jnp
    import jax.random

    from distributed_llama_tpu.engine.batch import _slab_prefill_single
    from distributed_llama_tpu.engine.weights import random_params_on_device
    from distributed_llama_tpu.models import llama
    from distributed_llama_tpu.models.sampling import decode_chunk, decode_chunk_batched

    if weights == "q40":
        params = random_q40_params_on_device(cfg)
    else:
        params = random_params_on_device(cfg, dtype=jnp.bfloat16, seed=0, layered=True)

    rng = np.random.RandomState(0)
    prompts = [
        jnp.asarray(rng.randint(0, cfg.vocab_size, prefill_len, dtype=np.int32))
        for _ in range(B)
    ]
    base = prefill_len  # decode window [base, base + n_rounds*chunk), replayed per rep

    import functools

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
    def fwd(cfg_, params_, tokens, cache, pos):
        return llama.forward_tokens(cfg_, params_, tokens, cache, pos)

    # ---- baseline: B interleaved single-sequence streams -----------------
    caches = [llama.init_cache(cfg, dtype=jnp.bfloat16, layered=True) for _ in range(B)]
    tok_dev = []
    for i in range(B):
        logits, caches[i] = fwd(cfg, params, prompts[i], caches[i], jnp.int32(0))
        tok_dev.append(jnp.argmax(logits[-1]).astype(jnp.int32))
    seeds32 = [jnp.uint32(i) for i in range(B)]
    # warm/compile the chunk shape once
    warm, caches[0] = decode_chunk(
        cfg, params, tok_dev[0], caches[0], jnp.int32(base), chunk,
        jnp.float32(0.0), jnp.float32(0.9), jnp.int32(0), seeds32[0],
    )
    np.asarray(warm)
    single_runs = []
    for rep in range(3):
        pos = [base] * B
        with telemetry.trace_span("bench_batch_interleaved", rep=rep, b=B):
            sw = Stopwatch()
            last = None
            for _ in range(n_rounds):
                for i in range(B):
                    toks, caches[i] = decode_chunk(
                        cfg, params, tok_dev[i], caches[i], jnp.int32(pos[i]),
                        chunk, jnp.float32(0.0), jnp.float32(0.9),
                        jnp.int32(0), seeds32[i],
                    )
                    tok_dev[i] = toks[-1]
                    pos[i] += chunk
                    last = toks
            np.asarray(last)  # fence: every dispatched chunk must finish
            single_runs.append(B * n_rounds * chunk / sw.elapsed_s())
    interleaved_tps = median(single_runs)
    del caches
    gc.collect()

    # ---- batched: one slab, one dispatch per chunk for all B rows --------
    slab = llama.init_batch_cache(cfg, B, dtype=jnp.bfloat16)
    firsts = []
    for i in range(B):
        logits, slab, _ = _slab_prefill_single(
            cfg, params, prompts[i], slab, jnp.int32(i), jnp.int32(0),
            jnp.int32(prefill_len),
        )
        firsts.append(jnp.argmax(logits[-1]).astype(jnp.int32))
    first = jnp.stack(firsts)
    active = jnp.ones(B, bool)
    temps = jnp.zeros(B, jnp.float32)
    topps = jnp.full(B, 0.9, jnp.float32)
    bseeds = jnp.arange(B, dtype=jnp.uint32)
    btopks = jnp.zeros(B, jnp.int32)
    pos0 = jnp.full(B, base, jnp.int32)
    # the donated first-token vector comes back advanced to each row's last
    # token: the next chunk's feed, never sliced out of the bundle
    toks, slab, nxt = decode_chunk_batched(  # warm/compile
        cfg, params, first, slab, pos0, active, chunk, temps, topps, btopks,
        bseeds,
    )
    np.asarray(toks)
    batch_runs = []
    for rep in range(3):
        pos = pos0
        with telemetry.trace_span("bench_batch_decode", rep=rep, b=B):
            sw = Stopwatch()
            for _ in range(n_rounds):
                toks_r, slab, nxt = decode_chunk_batched(
                    cfg, params, nxt, slab, pos, active, chunk, temps, topps,
                    btopks, bseeds,
                )
                pos = pos + chunk
            np.asarray(toks_r)
            batch_runs.append(B * n_rounds * chunk / sw.elapsed_s())
    batched_tps = median(batch_runs)

    speedup = batched_tps / interleaved_tps if interleaved_tps else 0.0
    return {
        "metric": f"{name}_{weights}_batch_decode_b{B}_aggregate_tokens_per_sec",
        "value": round(bench_metric(f"batch_decode_b{B}_aggregate_tps", batched_tps,
                                    "tokens/sec"), 2),
        "unit": "tokens/sec",
        "vs_baseline": round(bench_metric(f"batch_decode_b{B}_vs_interleaved", speedup), 2),
        "detail": {
            "interleaved_singles_aggregate_tokens_per_sec": round(
                bench_metric(f"batch_decode_b{B}_interleaved_tps", interleaved_tps,
                             "tokens/sec"), 2),
            "per_stream_tokens_per_sec": round(batched_tps / B, 2),
            "b": B,
            "chunk": chunk,
            "baseline": "B round-robin-interleaved single-sequence chunked "
            "decode streams on the same chip (docs/PERF.md round-5 item 4)",
            "device": str(jax.devices()[0]),
        },
    }


def sampled_probe_config(seq_len: int = 512):
    """A CPU-runnable shape with a PRODUCTION-WIDTH vocabulary: the fused
    sampler's cost scales with vocab (top-k window + softmax), so the
    sampled-vs-greedy A/B must not flatter itself on a toy vocab. The
    transformer stack is small on purpose — the question under test is
    what sampling adds to a step, relative, on the same device."""
    from distributed_llama_tpu.formats.model_file import ArchType, HiddenAct, RopeType
    from distributed_llama_tpu.models.config import LlamaConfig

    return LlamaConfig(
        arch=ArchType.LLAMA,
        dim=256,
        hidden_dim=512,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        vocab_size=32000,
        seq_len=seq_len,
        head_size=64,
        kv_dim=256,
        hidden_act=HiddenAct.SILU,
        rope_type=RopeType.LLAMA,
        rope_theta=10000.0,
    )


def run_sampled(cfg, name: str, B: int = 4, prefill_len: int = 32,
                chunk: int = 32, n_rounds: int = 4, weights: str = "bf16") -> dict:
    """``bench.py --sampled``: the ISSUE 13 A/B. Two gates, both relative
    on the SAME device (CPU-host or TPU — no cross-backend games):

    * single-stream: the fused sampled path (temperature/top-p + counter
      PRNG inside the decode scan) vs the greedy argmax path — the fused
      sampler must cost ≤ ~5% of a decode step (``sampled_vs_greedy``).
    * B-row aggregate: the batched DEVICE-sampled decode vs the host
      sampler baseline (per-token logits fetch + host sort, the
      reference's root-node regime, src/apps/dllama/dllama.cpp) —
      the multiplier batching buys once sampling stops serializing rows
      on the host (``device_vs_host_sampler``)."""
    import functools
    import gc

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.engine.batch import _slab_prefill_single
    from distributed_llama_tpu.engine.weights import random_params_on_device
    from distributed_llama_tpu.models import llama
    from distributed_llama_tpu.models.sampling import (
        decode_chunk,
        decode_chunk_batched,
    )
    from distributed_llama_tpu.tokenizer import Sampler

    if weights == "q40":
        params = random_q40_params_on_device(cfg)
    else:
        params = random_params_on_device(
            cfg, dtype=jnp.bfloat16, seed=0, layered=True
        )

    rng = np.random.RandomState(0)
    prompts = [
        jnp.asarray(rng.randint(0, cfg.vocab_size, prefill_len, dtype=np.int32))
        for _ in range(B)
    ]
    base = prefill_len

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
    def fwd(cfg_, params_, tokens, cache, pos):
        return llama.forward_tokens(cfg_, params_, tokens, cache, pos)

    # ---- single-stream: greedy vs sampled, same fixed decode window ------
    cache = llama.init_cache(cfg, dtype=jnp.bfloat16, layered=True)
    logits, cache = fwd(cfg, params, prompts[0], cache, jnp.int32(0))
    tok0 = jnp.argmax(logits[-1]).astype(jnp.int32)
    seed32 = jnp.uint32(7)

    def single_arm(temp, topp, topk=0):
        nonlocal cache
        t = jnp.float32(temp)
        p = jnp.float32(topp)
        k = jnp.int32(topk)
        warm, cache = decode_chunk(
            cfg, params, tok0, cache, jnp.int32(base), chunk, t, p,
            k, seed32,
        )
        np.asarray(warm)
        runs = []
        for rep in range(3):
            pos = base
            tok = tok0
            with telemetry.trace_span("bench_sampled_single", rep=rep, t=temp):
                sw = Stopwatch()
                for _ in range(n_rounds):
                    toks, cache_new = decode_chunk(
                        cfg, params, tok, cache, jnp.int32(pos), chunk, t, p,
                        k, seed32,
                    )
                    cache = cache_new
                    tok = toks[-1]
                    pos += chunk
                np.asarray(toks)
                runs.append(n_rounds * chunk / sw.elapsed_s())
        return median(runs)

    # interleave-free but adjacent: the two arms run the identical
    # windows. The sampled arm uses the production-shaped filter combo
    # (top-p 0.9 ∧ top-k 64): random-weight logits are near-FLAT, so a
    # bare top-p nucleus overflows the fast window every step and the A/B
    # would measure the full-sort fallback, which trained-model logits
    # (peaked; nucleus ≪ 128 wide) never take — the in-window top-k pins
    # the bench to the path production actually runs
    greedy_tps = single_arm(0.0, 0.9, 64)
    sampled_tps = single_arm(0.8, 0.9, 64)
    ratio = sampled_tps / greedy_tps if greedy_tps else 0.0
    del cache
    gc.collect()

    # ---- B-row aggregate: batched device-sampled vs host sampler ---------
    slab = llama.init_batch_cache(cfg, B, dtype=jnp.bfloat16)
    firsts = []
    for i in range(B):
        logits, slab, _ = _slab_prefill_single(
            cfg, params, prompts[i], slab, jnp.int32(i), jnp.int32(0),
            jnp.int32(prefill_len),
        )
        firsts.append(jnp.argmax(logits[-1]).astype(jnp.int32))
    first = jnp.stack(firsts)
    active = jnp.ones(B, bool)
    temps = jnp.full(B, 0.8, jnp.float32)
    topps = jnp.full(B, 0.9, jnp.float32)
    topks = jnp.full(B, 64, jnp.int32)
    bseeds = jnp.arange(B, dtype=jnp.uint32)
    pos0 = jnp.full(B, base, jnp.int32)
    toks, slab, nxt = decode_chunk_batched(  # warm/compile
        cfg, params, first, slab, pos0, active, chunk, temps, topps, topks,
        bseeds,
    )
    np.asarray(toks)
    batch_runs = []
    for rep in range(3):
        pos = pos0
        with telemetry.trace_span("bench_sampled_batched", rep=rep, b=B):
            sw = Stopwatch()
            for _ in range(n_rounds):
                toks_r, slab, nxt = decode_chunk_batched(
                    cfg, params, nxt, slab, pos, active, chunk, temps, topps,
                    topks, bseeds,
                )
                pos = pos + chunk
            np.asarray(toks_r)
            batch_runs.append(B * n_rounds * chunk / sw.elapsed_s())
    batched_tps = median(batch_runs)
    del slab
    gc.collect()

    # host-sampler baseline: B round-robin streams, each token a full-vocab
    # logits fetch + host top-p sort + a dispatch that cannot start until
    # the host sees the previous sample (the strict data dependence the
    # fused path deletes). Fewer steps — it is slow by construction.
    caches = [llama.init_cache(cfg, dtype=jnp.bfloat16, layered=True) for _ in range(B)]
    host_tok = []
    for i in range(B):
        logits, caches[i] = fwd(cfg, params, prompts[i], caches[i], jnp.int32(0))
        host_tok.append(int(np.argmax(np.asarray(logits[-1]))))
    samplers = [
        Sampler(vocab_size=cfg.vocab_size, temperature=0.8, topp=0.9,
                topk=64, seed=i, counter=True)
        for i in range(B)
    ]
    host_steps = max(8, chunk // 2)
    # warm the 1-token forward shape
    logits, caches[0] = fwd(
        cfg, params, jnp.asarray([host_tok[0]], jnp.int32), caches[0],
        jnp.int32(base),
    )
    host_tok[0] = samplers[0].sample(np.asarray(logits[0]), pos=base)
    pos_h = [base + (1 if i == 0 else 0) for i in range(B)]
    with telemetry.trace_span("bench_sampled_host_baseline", b=B):
        sw = Stopwatch()
        done = 0
        for _ in range(host_steps):
            for i in range(B):
                logits, caches[i] = fwd(
                    cfg, params, jnp.asarray([host_tok[i]], jnp.int32),
                    caches[i], jnp.int32(pos_h[i]),
                )
                host_tok[i] = samplers[i].sample(
                    np.asarray(logits[0]), pos=pos_h[i]
                )
                pos_h[i] += 1
                done += 1
        host_tps = done / sw.elapsed_s()
    speedup = batched_tps / host_tps if host_tps else 0.0

    return {
        "metric": f"{name}_{weights}_device_sampled_tokens_per_sec",
        "value": round(bench_metric("sampled_decode_tps", sampled_tps,
                                    "tokens/sec"), 2),
        "unit": "tokens/sec",
        "sampled_vs_greedy": round(bench_metric("sampled_vs_greedy", ratio), 4),
        "device_vs_host_sampler": round(
            bench_metric("device_vs_host_sampler", speedup), 2),
        "detail": {
            "greedy_decode_tokens_per_sec": round(
                bench_metric("greedy_decode_tps", greedy_tps, "tokens/sec"), 2),
            "batched_sampled_aggregate_tokens_per_sec_b4": round(
                bench_metric("batched_sampled_tps", batched_tps, "tokens/sec"), 2),
            "host_sampler_aggregate_tokens_per_sec_b4": round(
                bench_metric("host_sampler_tps", host_tps, "tokens/sec"), 2),
            "b": B,
            "chunk": chunk,
            "sampler": "temperature 0.8, top-p 0.9, top-k 64, counter-PRNG seeds",
            "baseline": "per-token full-vocab logits fetch + host top-p "
            "sort, B round-robin streams (the reference's root-node "
            "sampler regime, src/apps/dllama/dllama.cpp:45-59)",
            "device": str(jax.devices()[0]),
        },
    }


def run_spec(cfg, name: str, k: int, prefill_len: int = 64, n_tokens: int = 128,
             weights: str = "q40") -> dict:
    """``bench.py --spec K``: self-speculative decode (prompt-lookup drafts,
    one batched verify forward per step) vs plain chunked decode, on a
    repetitive-output workload — a periodic prompt plus whatever cycle the
    model's own greedy output settles into (prompt-lookup drafts from BOTH,
    so acceptance reflects the structured/repetitive serving regime the
    technique targets). Reports tok/s for each path and the measured draft
    acceptance rate; ``K = 0`` runs the plain path twice, which is the
    ``--spec-draft 0`` no-regression check (identical machinery, so it must
    match within chip noise)."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.engine.speculative import PromptLookupDrafter
    from distributed_llama_tpu.engine.weights import random_params_on_device
    from distributed_llama_tpu.models import llama
    from distributed_llama_tpu.models.sampling import decode_chunk, spec_verify_step

    if weights == "q40":
        params = random_q40_params_on_device(cfg)
    else:
        params = random_params_on_device(cfg, dtype=jnp.bfloat16, seed=0, layered=True)
    cache = llama.init_cache(cfg, dtype=jnp.bfloat16, layered=True)

    import functools

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
    def fwd(cfg_, params_, tokens, cache_, pos):
        return llama.forward_tokens(cfg_, params_, tokens, cache_, pos)

    # repetitive prompt: an 8-token pattern tiled to prefill_len (the
    # extraction/templated-output shape prompt lookup exploits)
    rng = np.random.RandomState(0)
    pattern = rng.randint(0, cfg.vocab_size, 8, dtype=np.int32)
    # ceil-tile: a floor here would leave the prompt SHORTER than
    # prefill_len while base still assumed full length — slots of
    # zero-initialized K/V inside the live window
    prompt = jnp.asarray(np.tile(pattern, -(-prefill_len // 8))[:prefill_len])
    logits, cache = fwd(cfg, params, prompt, cache, jnp.int32(0))
    first = int(np.argmax(np.asarray(logits[-1])))
    base = prefill_len
    chunk = 32

    # ---- plain chunked decode baseline (the 108.3 tok/s serving path) ----
    seed32 = jnp.uint32(2)
    toks, cache = decode_chunk(  # warm/compile
        cfg, params, jnp.int32(first), cache, jnp.int32(base), chunk,
        jnp.float32(0.0), jnp.float32(0.9), jnp.int32(0), seed32,
    )
    np.asarray(toks)
    n_chunks = max(1, n_tokens // chunk)

    def plain_round(cache_, span_name, rep):
        """One timed plain-decode replay of the fixed window — ONE copy of
        the measurement loop, shared by the baseline arm and the --spec 0
        A/A rerun arm so the comparison is provably the same procedure."""
        pos = base
        tok_dev = jnp.int32(first)
        got = []
        sw = Stopwatch()
        with telemetry.trace_span(span_name, rep=rep):
            for _ in range(n_chunks):
                toks_, cache_ = decode_chunk(
                    cfg, params, tok_dev, cache_, jnp.int32(pos), chunk,
                    jnp.float32(0.0), jnp.float32(0.9), jnp.int32(0), seed32,
                )
                tok_dev = toks_[-1]
                pos += chunk
                got.extend(np.asarray(toks_).tolist())
        return cache_, n_chunks * chunk / sw.elapsed_s(), got

    plain_runs = []
    plain_out = None
    for rep in range(3):
        cache, tps, plain_out = plain_round(cache, "bench_spec_plain", rep)
        plain_runs.append(tps)
    plain_tps = median(plain_runs)

    # ---- speculative decode (one verify forward per step) ----------------
    drafted_total = accepted_total = steps_total = 0
    spec_out = None

    def spec_round(cache_, timed: bool):
        nonlocal drafted_total, accepted_total, steps_total
        drafter = PromptLookupDrafter(max(k, 1))
        history = np.asarray(prompt).tolist() + [first]
        prev = first
        pos = base
        emitted = []
        sw = Stopwatch()
        while len(emitted) < n_tokens:
            T = min(k + 1, cfg.seq_len - pos)
            draft = drafter.draft(history, limit=T - 1) if k > 0 else []
            feed = np.full(T, prev, np.int32)
            feed[1 : 1 + len(draft)] = draft
            out_dev, cache_ = spec_verify_step(
                cfg, params, jnp.asarray(feed), cache_, jnp.int32(pos),
                jnp.int32(len(draft)), jnp.float32(0.0), jnp.float32(0.9),
                jnp.int32(0), jnp.uint32(3),
            )
            out = np.asarray(out_dev)
            n_emit = max(1, min(int(out[0]), T))
            emitted.extend(int(t) for t in out[1 : 1 + n_emit])
            history.extend(int(t) for t in out[1 : 1 + n_emit])
            prev = emitted[-1]
            pos += n_emit
            if timed:
                drafted_total += len(draft)
                accepted_total += n_emit - 1
                steps_total += 1
        return cache_, len(emitted) / sw.elapsed_s(), emitted

    if k > 0:
        cache, _, _ = spec_round(cache, timed=False)  # warm/compile
        spec_runs = []
        for rep in range(3):
            with telemetry.trace_span("bench_spec_verify", rep=rep, k=k):
                cache, tps, spec_out = spec_round(cache, timed=True)
            spec_runs.append(tps)
        spec_tps = median(spec_runs)
    else:
        # --spec 0: the flag gates the speculative path off entirely, so the
        # "spec" arm is a SECOND independent plain measurement — a genuine
        # A/A comparison that can catch a --spec-draft 0 regression instead
        # of reporting 1.0 by construction
        rerun_runs = []
        for rep in range(3):
            cache, tps, spec_out = plain_round(
                cache, "bench_spec_plain_rerun", rep
            )
            rerun_runs.append(tps)
        spec_tps = median(rerun_runs)
    acceptance = accepted_total / drafted_total if drafted_total else 0.0
    greedy_match = (
        plain_out is not None and spec_out is not None
        and spec_out[: len(plain_out)] == plain_out[: len(spec_out)]
    )
    # the in-bench parity gate: this workload is greedy, so speculative and
    # plain MUST produce the same stream — a silent mismatch here would be
    # a correctness regression dressed up as a speedup
    assert greedy_match, (
        "speculative greedy stream diverged from plain decode: "
        f"{spec_out[:16]} vs {plain_out[:16]}"
    )

    speedup = spec_tps / plain_tps if plain_tps else 0.0
    return {
        "metric": f"{name}_{weights}_spec_decode_tokens_per_sec",
        "value": round(bench_metric("spec_decode_tokens_per_sec", spec_tps,
                                    "tokens/sec"), 2),
        "unit": "tokens/sec",
        "vs_baseline": round(bench_metric("spec_vs_plain", speedup), 3),
        "detail": {
            "plain_decode_tokens_per_sec": round(
                bench_metric("spec_plain_tokens_per_sec", plain_tps, "tokens/sec"), 2),
            "acceptance_rate": round(
                bench_metric("spec_acceptance_rate", acceptance), 3),
            "draft_tokens": drafted_total,
            "accepted_tokens": accepted_total,
            "verify_steps": steps_total,
            "avg_advance_per_step": round(
                (accepted_total + steps_total) / steps_total, 2) if steps_total else 1.0,
            "greedy_streams_match": bool(greedy_match),
            "spec_draft_k": k,
            "workload": "periodic 8-token prompt pattern + the model's own "
            "greedy output cycle (repetitive-output regime; medians of 3)",
            "baseline": "plain chunked decode (32/dispatch) on the same "
            "weights/cache — the docs/PERF.md single-stream serving path",
            "device": str(jax.devices()[0]),
        },
    }


CHAOS_PLAN_SPEC = (
    # two transient fetch errors (recovered in place by the bounded retry)
    "batch.fetch:kind=raise,after=1,count=2;"
    # one corrupted row mid-stream (quarantined; its request retries whole)
    "batch.row:kind=nan,row={victim},after=3,count=1"
)


def run_chaos(b: int = 4, n_tokens: int = 64, chunk: int = 8) -> dict:
    """``bench.py --chaos B``: the batched-decode workload through the REAL
    serving stack (InferenceEngine + BatchScheduler) twice — clean, then
    under a fault plan injecting transient fetch errors and one row kill —
    reporting aggregate tok/s degradation and recovery counts (ISSUE 3).

    Uses a tiny synthetic model on purpose: chaos measures the scheduler's
    recovery machinery (retries, quarantine, survivor delivery), not HBM
    bandwidth — the clean-vs-chaos delta is the number, so both runs share
    one config, one process and one compiled-program cache."""
    import os
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.engine import InferenceEngine, faults
    from distributed_llama_tpu.engine.batch import BatchScheduler
    from distributed_llama_tpu.formats.synthetic import (
        tiny_spec,
        write_synthetic_model,
    )

    spec = tiny_spec(
        dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        vocab_size=128, seq_len=max(4 * n_tokens, 256),
    )
    path = write_synthetic_model(
        os.path.join(tempfile.mkdtemp(prefix="dllama-chaos-"), "chaos.m"),
        spec, seed=0,
    )

    prompts = [[1 + i, 5, 9, 2] for i in range(b)]

    def run_round(streams):
        """All B requests concurrently, like the API server's lanes; a
        failed request (quarantined row) resets its stream and retries the
        whole completion once — the 'recovery' being measured."""
        results = {"failed": 0, "recovered": 0, "tokens": 0}
        lock = threading.Lock()

        def one(i):
            for attempt in (0, 1):
                s = streams[i]
                try:
                    s.reset()
                    first = s.prefill_device(prompts[i], 0.0, 0.9, i)
                    got = []

                    def on_token(prev, tok):
                        got.append(tok)
                        return len(got) < n_tokens

                    s.stream_decode(
                        first, on_token, 0.0, 0.9, seed=i,
                        limit=s.pos + n_tokens,
                        first_prev=prompts[i][-1],
                    )
                    with lock:
                        results["tokens"] += len(got)
                        if attempt:
                            results["recovered"] += 1
                    return
                except Exception as e:
                    with lock:
                        results["failed"] += 1
                    sys.stderr.write(
                        f"chaos request {i} attempt {attempt}: "
                        f"{type(e).__name__}: {e}\n"
                    )

        threads = [threading.Thread(target=one, args=(i,)) for i in range(b)]
        sw = Stopwatch()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        results["tps"] = results["tokens"] / max(sw.elapsed_s(), 1e-9)
        return results

    def build():
        engine = InferenceEngine(path, dtype=jnp.float32)
        sched = BatchScheduler(engine, n_rows=b, chunk=chunk)
        return sched, [sched.new_stream() for _ in range(b)]

    def retry_counter(stage):
        try:
            return telemetry.REGISTRY.counter(
                "dllama_batch_retries_total", labelnames=("stage",)
            ).labels(stage=stage).value
        except Exception:
            return 0.0

    # medians of 3 like run(): a shared host CPU jitters several-x
    # on thread-scheduling scales, so single rounds would compare tenancy
    # luck, not fault handling. Every chaos round replays the SAME plan
    # (plan.reset() rewinds its hit counters + RNG), so the three rounds
    # are identical chaos workloads.
    faults.clear()
    sched, streams = build()
    with telemetry.trace_span("bench_chaos_warm", b=b):
        run_round(streams)  # compile every bucket/chunk program untimed
    clean_rounds = []
    for rep in range(3):
        with telemetry.trace_span("bench_chaos_clean", b=b, rep=rep):
            clean_rounds.append(run_round(streams))
    clean = median_by(clean_rounds, key=lambda r: r["tps"])
    # failure/recovery counts are SUMS over the same 3 rounds on both sides
    # (the tps medians stay medians) — summing chaos but not clean would
    # make the report compare incommensurable numbers
    clean["failed"] = sum(r["failed"] for r in clean_rounds)
    clean["recovered"] = sum(r["recovered"] for r in clean_rounds)

    plan_spec = CHAOS_PLAN_SPEC.format(victim=b - 1)
    plan = faults.install(faults.parse(plan_spec, seed=0))
    retries_before = retry_counter("fetch")
    quarantined_before = telemetry.REGISTRY.counter(
        "dllama_rows_quarantined_total"
    ).value
    try:
        sched2, streams2 = build()  # binds the installed plan
        chaos_rounds = []
        for rep in range(3):
            plan.reset()
            with telemetry.trace_span("bench_chaos_faulted", b=b, rep=rep):
                chaos_rounds.append(run_round(streams2))
    finally:
        faults.clear()
    chaos = median_by(chaos_rounds, key=lambda r: r["tps"])
    chaos["failed"] = sum(r["failed"] for r in chaos_rounds)
    chaos["recovered"] = sum(r["recovered"] for r in chaos_rounds)

    ratio = chaos["tps"] / clean["tps"] if clean["tps"] else 0.0
    return {
        "metric": f"chaos_batch_decode_b{b}_aggregate_tokens_per_sec",
        "value": round(bench_metric(f"chaos_b{b}_tps", chaos["tps"], "tokens/sec"), 2),
        "unit": "tokens/sec",
        "vs_baseline": round(bench_metric(f"chaos_b{b}_vs_clean", ratio), 3),
        "detail": {
            "clean_aggregate_tokens_per_sec": round(clean["tps"], 2),
            "degradation_pct": round((1.0 - ratio) * 100.0, 1),
            "faults_injected": plan.injected_total,
            "fetch_retries": int(retry_counter("fetch") - retries_before),
            "rows_quarantined": int(
                telemetry.REGISTRY.counter("dllama_rows_quarantined_total").value
                - quarantined_before
            ),
            "requests_failed": chaos["failed"],
            "requests_recovered": chaos["recovered"],
            "clean_requests_failed": clean["failed"],
            "fault_plan": plan_spec,
            "b": b,
            "chunk": chunk,
            "tokens_per_request": n_tokens,
            "baseline": "the same B-request batched-decode round with no "
            "fault plan installed (same process, same compiled programs)",
            "model": "tiny synthetic llama (chaos measures recovery "
            "machinery, not HBM bandwidth)",
            "device": str(jax.devices()[0]),
        },
    }


def run_prefix_cache(chaos: bool = False) -> dict:
    """``bench.py --prefix-cache``: TTFT on a repeated-prefix workload —
    requests sharing a 64-token prompt prefix with distinct short tails,
    through the REAL serving stack (InferenceEngine + BatchScheduler with
    the radix prefix cache). Reports cold-vs-hit TTFT medians plus the
    hit/miss/eviction counters (ISSUE 4 acceptance: >= 2x TTFT on hits).

    With ``chaos=True`` (``--prefix-cache --chaos``) a fault plan corrupts
    a row mid-decode AFTER it took a prefix hit, and the run ASSERTS that
    quarantining the row frees no pages still referenced by the tree: the
    pages gauge is unchanged, the tree invariants hold, and a follow-up
    request still hits the same prefix and decodes the same greedy stream."""
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.engine import InferenceEngine, faults
    from distributed_llama_tpu.engine.batch import BatchScheduler
    from distributed_llama_tpu.formats.synthetic import (
        tiny_spec,
        write_synthetic_model,
    )

    # big enough that prefill compute dominates dispatch overhead (the
    # cold-vs-hit delta IS prefill compute), small enough for any substrate
    spec = tiny_spec(
        dim=256, hidden_dim=512, n_layers=4, n_heads=8, n_kv_heads=4,
        vocab_size=512, seq_len=512,
    )
    path = write_synthetic_model(
        os.path.join(tempfile.mkdtemp(prefix="dllama-prefix-"), "prefix.m"),
        spec, seed=0,
    )
    engine = InferenceEngine(path, dtype=jnp.bfloat16)
    page = 16
    sched = BatchScheduler(
        engine, n_rows=2, chunk=8, prefix_cache=True, kv_pages=96,
        page_size=page,
    )
    streams = [sched.new_stream() for _ in range(2)]

    rng = np.random.RandomState(7)
    shared_prefix = rng.randint(1, spec.vocab_size, 64).tolist()

    def ttft_ms(stream, tokens, seed: int) -> float:
        """Request-start to first-token-on-host: the serving TTFT path
        (prefill_device fusion + fused first-token fetch)."""
        stream.reset()
        sw = Stopwatch()
        first = stream.prefill_device(tokens, 0.0, 0.9, seed)
        stream.fetch_first_token(first)
        return sw.elapsed_ms()

    def tail(i: int) -> list[int]:
        return rng.randint(1, spec.vocab_size, 8).tolist()

    # warm every compiled shape untimed: the cold bucket-128 prefill, the
    # miss-side publish, and (second same-prefix request) the paged
    # suffix-prefill program reading the matched pages through the row's
    # page table + the bucket-8 suffix shape
    warm_prefix = rng.randint(1, spec.vocab_size, 64).tolist()
    ttft_ms(streams[0], warm_prefix + tail(0), 0)
    ttft_ms(streams[0], warm_prefix + tail(1), 0)

    reg = telemetry.REGISTRY

    def ctr(name: str) -> float:
        return reg.counter(name).value

    # cold: every request a FRESH prefix (guaranteed miss, full prefill)
    cold_runs = []
    for r in range(3):
        fresh = rng.randint(1, spec.vocab_size, 64).tolist()
        with telemetry.trace_span("bench_prefix_cold", rep=r):
            cold_runs.append(ttft_ms(streams[0], fresh + tail(r), r))
    ttft_cold = median(cold_runs)

    # hit: publish the shared prefix once (untimed), then measure requests
    # that reuse it with distinct tails — the chat system-prompt workload
    ttft_ms(streams[0], shared_prefix + tail(100), 0)
    hits_before = ctr("dllama_prefix_cache_hits_total")
    saved_before = ctr("dllama_prefix_cache_copy_bytes_saved_total")
    spans_before = len(telemetry.TRACER.events())
    hit_runs = []
    for r in range(3):
        with telemetry.trace_span("bench_prefix_hit", rep=r):
            hit_runs.append(ttft_ms(streams[1], shared_prefix + tail(200 + r), r))
    ttft_hit = median(hit_runs)
    hits_measured = ctr("dllama_prefix_cache_hits_total") - hits_before
    assert hits_measured >= 3, (
        "repeated-prefix requests did not hit the prefix cache"
    )
    # measured, not assumed: a gather program on the hit path would record a
    # *gather* span (the PR 4 copy design's prefix_gather); observing none
    # across the hit loop is what makes the reported per-hit traffic zero
    hit_gather_spans = sum(
        1
        for ev in telemetry.TRACER.events()[spans_before:]
        if "gather" in ev.name
    )
    if hit_gather_spans:
        saved = ctr("dllama_prefix_cache_copy_bytes_saved_total") - saved_before
        raise AssertionError(
            f"zero-copy regression: {hit_gather_spans} gather dispatches "
            f"across {int(hits_measured)} hits (~{int(saved / hits_measured)} "
            "bytes/hit of copy traffic the page-table read was supposed to "
            "eliminate)"
        )
    gathered_bytes_per_hit = 0  # the measured zero: no gather spans above
    speedup = ttft_cold / max(ttft_hit, 1e-9)

    # tree + alias invariants after the measured workload (no page freed
    # while a live row's table references it)
    sched.check_prefix()
    detail = {
        "ttft_cold_ms": round(bench_metric("prefix_ttft_cold_ms", ttft_cold, "ms"), 2),
        "ttft_hit_ms": round(bench_metric("prefix_ttft_hit_ms", ttft_hit, "ms"), 2),
        "prefix_cache_hits": int(ctr("dllama_prefix_cache_hits_total")),
        "prefix_cache_misses": int(ctr("dllama_prefix_cache_misses_total")),
        "prefix_cache_evictions": int(ctr("dllama_prefix_cache_evictions_total")),
        "prefix_cache_pages": int(reg.gauge("dllama_prefix_cache_pages").value),
        # zero-copy pool accounting: the pool IS the only resident copy of
        # cached prefixes; per-hit gather traffic is measured above (span
        # count over the hit loop) and the saved counter is the copy
        # traffic the old design would have paid for the same hits
        "pool_capacity_pages": sched._prefix.capacity,
        "pool_occupancy": round(
            sched._prefix.pages_in_use() / sched._prefix.capacity, 3
        ),
        "pool_bytes": int(reg.gauge("dllama_prefix_cache_bytes").value),
        "pool_pinned_pages": int(
            reg.gauge("dllama_prefix_cache_pinned_pages").value
        ),
        "gathered_bytes_per_hit": gathered_bytes_per_hit,
        "copy_bytes_saved": int(
            ctr("dllama_prefix_cache_copy_bytes_saved_total")
        ),
        "page_size": page,
        "workload": "64-token shared prefix + distinct 8-token tails "
        "(TTFT = prefill_device dispatch -> first token on host, medians "
        "of 3)",
        "model": "synthetic llama dim=256 L=4 (the cold-vs-hit delta is "
        "prefill compute, not checkpoint bytes)",
        "device": str(jax.devices()[0]),
    }

    if chaos:
        # quarantine a row that took a prefix hit mid-decode; under
        # zero-copy aliasing the victim's attention reads tree pages
        # through its page table, so quarantine must release ITS pins
        # while the pages stay mapped (and pinned) for every other live
        # reader — docs/PERF.md "Zero-copy paged attention"
        def greedy(stream, tokens, n=16):
            stream.reset()
            first = stream.prefill_device(tokens, 0.0, 0.9, 0)
            got = []

            def on_token(prev, tok):
                got.append(tok)
                return len(got) < n

            stream.stream_decode(
                first, on_token, 0.0, 0.9, seed=0, limit=stream.pos + n,
                first_prev=tokens[-1],
            )
            return got

        victim_prompt = shared_prefix + tail(300)
        reference = greedy(streams[0], victim_prompt)
        pages_before = int(reg.gauge("dllama_prefix_cache_pages").value)
        plan = faults.install(
            faults.parse("batch.row:kind=nan,row=1,after=1,count=1", seed=0)
        )
        quarantined = False
        try:
            sched._faults = plan  # bind-once: the scheduler predates the plan
            try:
                greedy(streams[1], victim_prompt)
            except faults.RowQuarantined:
                quarantined = True
        finally:
            faults.clear()
            sched._faults = faults.active_plan()
        assert quarantined, "the chaos plan failed to quarantine the victim row"
        pages_after = int(reg.gauge("dllama_prefix_cache_pages").value)
        assert pages_after == pages_before, (
            f"quarantine freed tree pages: {pages_before} -> {pages_after}"
        )
        # zero-copy contract: the quarantined row's page pins released (the
        # pages stay in the tree for other readers, but nothing pins them
        # on the dead row's behalf) and the alias invariants hold
        assert not streams[1]._alias_ids and streams[1].matched_len == 0, (
            "quarantine left the victim row's page pins held"
        )
        sched.check_prefix()  # no page aliased, leaked, or freed-while-read
        hits_pre = ctr("dllama_prefix_cache_hits_total")
        replay = greedy(streams[0], victim_prompt)
        assert ctr("dllama_prefix_cache_hits_total") > hits_pre, (
            "post-quarantine request no longer hits the published prefix"
        )
        assert replay == reference, (
            "post-quarantine prefix-hit stream diverged from the pre-fault "
            f"reference: {replay} != {reference}"
        )
        detail.update(
            quarantined_rows=1,
            pages_before_quarantine=pages_before,
            pages_after_quarantine=pages_after,
            post_quarantine_hit_parity=True,
        )

    # ------------------------------------------------------------------
    # Spill tier (ISSUE 11): per-tier TTFT breakdown — cold prefill vs
    # device hit (measured above) vs HOST-RELOAD at a deliberately tiny
    # pool. A fresh scheduler with kv_pages=8 forces the shared prefix
    # out of HBM between requests; the re-request re-uploads the spilled
    # bytes (CRC-verified) and prefills only the suffix. The acceptance
    # gate: host-reload TTFT strictly below cold-prefill TTFT at the
    # same --kv-pages (re-upload ≪ re-prefill).
    # ------------------------------------------------------------------
    spill_sched = BatchScheduler(
        engine, n_rows=1, chunk=8, prefix_cache=True, kv_pages=8,
        page_size=page, host_spill_bytes=64 << 20,
    )
    spill_stream = spill_sched.new_stream()
    spill_prefix = rng.randint(1, spec.vocab_size, 64).tolist()

    def fill_pool(r: int):
        # two fresh 64-token prefixes overrun the 8-page pool: the
        # shared prefix's 4 pages evict (and spill) every round
        for j in range(2):
            fresh = rng.randint(1, spec.vocab_size, 64).tolist()
            ttft_ms(spill_stream, fresh + tail(500 + 10 * r + j), 0)

    # warm the spill-path shapes untimed (upload program + suffix shapes)
    ttft_ms(spill_stream, spill_prefix + tail(490), 0)
    fill_pool(9)
    ttft_ms(spill_stream, spill_prefix + tail(491), 0)

    reloads_before = ctr("dllama_prefix_spill_reloads_total")
    reload_runs = []
    for r in range(3):
        fill_pool(r)
        with telemetry.trace_span("bench_prefix_host_reload", rep=r):
            reload_runs.append(
                ttft_ms(spill_stream, spill_prefix + tail(600 + r), r)
            )
    ttft_reload = median(reload_runs)
    reloads_measured = ctr("dllama_prefix_spill_reloads_total") - reloads_before
    assert reloads_measured >= 3 * (64 // page), (
        f"host-reload rounds only reloaded {int(reloads_measured)} pages — "
        "the measured TTFT is not the spill tier's"
    )
    assert ttft_reload < ttft_cold, (
        f"host-reload TTFT {ttft_reload:.1f} ms is not below cold prefill "
        f"{ttft_cold:.1f} ms: the spill tier buys nothing"
    )
    spill_sched.check_prefix()
    detail["ttft_host_reload_ms"] = round(
        bench_metric("prefix_ttft_host_reload_ms", ttft_reload, "ms"), 2
    )
    # the per-tier ladder in one place (stats.py medians of 3 each)
    detail["tiers"] = {
        "cold_prefill_ms": round(ttft_cold, 2),
        "device_hit_ms": round(ttft_hit, 2),
        "host_reload_ms": round(ttft_reload, 2),
    }
    detail["spill_pages"] = int(ctr("dllama_prefix_spill_pages_total"))
    detail["spill_reloads"] = int(ctr("dllama_prefix_spill_reloads_total"))
    detail["spill_dropped"] = int(ctr("dllama_prefix_spill_dropped_total"))

    return {
        "metric": "prefix_cache_ttft_speedup"
        + ("_chaos" if chaos else ""),
        "value": round(bench_metric("prefix_ttft_speedup", speedup), 2),
        "unit": "x (cold TTFT / hit TTFT)",
        "vs_baseline": round(speedup, 2),
        "detail": detail,
    }


def run_pod(data: int = 2, model: int = 2, parallel: int = 4,
            chunk: int = 32, n_rounds: int = 6) -> dict:
    """One-process pod vs N-process-style replicas at MATCHED total lanes
    (`bench.py --pod`, ISSUE 15; numbers -> BENCH_POD_r08.json + PERF.md).

    Baseline arm: ``data`` INDEPENDENT engines, each with its OWN params
    tree sharded over ``model`` devices, ``parallel`` lanes each — where
    `--replicas N --tp model` lands this codebase (one weight copy and
    one dispatch stream per replica). Pod arms, same total lanes on ONE
    ('data','model') mesh sharing ONE params tree:

    * **consolidated** (headline; serving: ``--pod DxM --replicas 1``) —
      every lane in ONE batched-decode program per chunk, rows sharded
      over 'data': the batch-consolidation shape the mesh exists for.
    * **sliced** (detail; serving default) — one scheduler per data
      slice (the per-slice failover domain), each dispatching its own
      chunk program; buys slice-level fault isolation for a per-dispatch
      tax that CPU mesh mocks overstate (every partition shares the
      host's cores, so extra program launches serialize; on real chips
      the slices' programs land on disjoint rows of the mesh).

    Gates: consolidated aggregate tok/s no worse than the baseline;
    resident weight bytes per replica ~N x lower (per-process tree
    accounting), CROSS-CHECKED by max_device_weight_bytes_* — a measured
    per-device walk of every leaf's addressable shards that a broken
    rule table (silent replication) cannot satisfy by arithmetic."""
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.formats.synthetic import (
        tiny_spec,
        write_synthetic_model,
    )
    from distributed_llama_tpu.parallel.pod import (
        PodGroup,
        max_device_weight_bytes,
        tree_weight_bytes,
    )

    spec = tiny_spec(
        dim=512, hidden_dim=1536, n_layers=8, n_heads=8, n_kv_heads=8,
        vocab_size=4096, seq_len=256,
    )
    path = write_synthetic_model(
        os.path.join(tempfile.mkdtemp(prefix="dllama-podbench-"), "m.m"),
        spec, seed=0,
    )
    prefill_len = 32
    rng = np.random.RandomState(0)

    def make_state(group, lanes: int) -> dict:
        be = group.backend
        slab = be.init_batch_cache(lanes, dtype=jnp.float32)
        firsts = []
        for i in range(lanes):
            prompt = jnp.asarray(
                rng.randint(1, spec.vocab_size, prefill_len, dtype=np.int32)
            )
            logits, slab = be.slab_forward(
                group.params, prompt, slab, i, 0, prefill_len
            )
            firsts.append(jnp.argmax(logits[prefill_len - 1]).astype(jnp.int32))
        return {
            "g": group, "be": be, "slab": slab, "lanes": lanes,
            "first": jnp.stack(firsts),
            "active": jnp.ones(lanes, bool),
            "temps": jnp.zeros(lanes, jnp.float32),
            "topps": jnp.full(lanes, 0.9, jnp.float32),
            "topks": jnp.zeros(lanes, jnp.int32),
            "seeds": jnp.arange(lanes, dtype=jnp.uint32),
        }

    def measure_once(states) -> float:
        """One timed pass: decode ``n_rounds`` chunks per scheduler
        state, all dispatch streams interleaved on the device queues
        (dispatch is async, so concurrent schedulers overlap exactly as
        the pool's do). Aggregate tok/s of the pass."""
        for st in states:
            st["pos"] = jnp.full(st["lanes"], prefill_len, jnp.int32)
            # a copy: the chunk program donates its first-token vector
            st["nxt"] = jnp.array(st["first"])
        sw = Stopwatch()
        for _ in range(n_rounds):
            for st in states:  # async: chunks interleave on device
                packed, st["slab"], st["nxt"] = st["be"].batched_decode_chunk(
                    st["g"].params, st["nxt"], st["slab"], st["pos"],
                    st["active"], chunk, st["temps"], st["topps"],
                    st["topks"], st["seeds"],
                )
                st["pos"] = st["pos"] + chunk
                st["last"] = packed
        for st in states:
            np.asarray(st["last"])  # fence every stream
        return sum(st["lanes"] for st in states) * n_rounds * chunk / sw.elapsed_s()

    total_lanes = data * parallel

    # all three arms built up front, then measured INTERLEAVED (arm A
    # rep k, arm B rep k, ...) with per-arm medians: a shared CPU box
    # drifts over a multi-minute bench, and sequential per-arm timing
    # would fold that drift into the A/B ratio
    #
    # baseline: N independent model-sharded engines (own weights each) on
    # jax.devices()[:model] — exactly where `--replicas N --tp model`
    # lands every replica engine in this codebase (InferenceEngine takes
    # the first tp devices): N weight copies AND N dispatch streams
    # stacked on one model group, the shape ISSUE 15 replaces
    lone = [PodGroup.build(path, 1, model, dtype=jnp.float32)
            for _ in range(data)]
    base_states = [make_state(g, parallel) for g in lone]
    base_bytes = sum(tree_weight_bytes(g.params) for g in lone) // len(lone)
    # MEASURED device residency (addressable shards, not attribution):
    # the pool's N trees stack on the shared model group's devices
    base_dev_bytes = max_device_weight_bytes([g.params for g in lone])
    # pod, consolidated: ONE program for all lanes (--pod DxM --replicas 1)
    group_c = PodGroup.build(path, data, model, dtype=jnp.float32)
    cons_states = [make_state(group_c, total_lanes)]
    pod_bytes = group_c.resident_weight_bytes_per_replica()
    pod_total_bytes = group_c.weight_bytes
    pod_dev_bytes = max_device_weight_bytes([group_c.params])
    # pod, sliced (the per-slice failover serving default): one scheduler
    # per data slice — a fresh group because the slab layout pins at
    # first use (every slice shares the backend's compiled programs)
    group_s = PodGroup.build(path, data, model, dtype=jnp.float32)
    sliced_states = [make_state(group_s, parallel) for _ in range(data)]

    arms = {"base": base_states, "cons": cons_states, "sliced": sliced_states}
    for states in arms.values():
        measure_once(states)  # warm/compile pass, untimed
    runs: dict = {k: [] for k in arms}
    for rep in range(5):
        for name, states in arms.items():
            with telemetry.trace_span("bench_pod_arm", rep=rep, arm=name):
                runs[name].append(measure_once(states))
    base_tps = median(runs["base"])
    pod_tps = median(runs["cons"])
    sliced_tps = median(runs["sliced"])

    ratio = pod_tps / base_tps if base_tps else 0.0
    mem_ratio = base_bytes / pod_bytes if pod_bytes else 0.0
    return {
        "metric": f"pod_{data}x{model}_aggregate_tokens_per_sec",
        "value": round(bench_metric("pod_aggregate_tps", pod_tps, "tokens/sec"), 2),
        "unit": "tokens/sec",
        "vs_baseline": round(bench_metric("pod_vs_replicas_tps", ratio), 3),
        "detail": {
            "replicas_aggregate_tokens_per_sec": round(
                bench_metric("pod_replicas_tps", base_tps, "tokens/sec"), 2),
            "pod_sliced_aggregate_tokens_per_sec": round(
                bench_metric("pod_sliced_tps", sliced_tps, "tokens/sec"), 2),
            "pod_sliced_vs_replicas": round(
                sliced_tps / base_tps if base_tps else 0.0, 3),
            "resident_weight_bytes_per_replica_pod": int(bench_metric(
                "pod_resident_weight_bytes_per_replica", pod_bytes, "bytes")),
            "resident_weight_bytes_per_replica_replicas": int(bench_metric(
                "replicas_resident_weight_bytes_per_replica", base_bytes, "bytes")),
            "pod_weight_bytes_total": int(pod_total_bytes),
            "weight_memory_reduction_x": round(
                bench_metric("pod_weight_memory_reduction", mem_ratio), 2),
            # MEASURED device residency (max over devices, summed from
            # every leaf's addressable shards): the gate a broken rule
            # table cannot satisfy by attribution arithmetic
            "max_device_weight_bytes_pod": int(bench_metric(
                "pod_max_device_weight_bytes", pod_dev_bytes, "bytes")),
            "max_device_weight_bytes_replicas": int(bench_metric(
                "replicas_max_device_weight_bytes", base_dev_bytes, "bytes")),
            "max_device_weight_reduction_x": round(
                bench_metric(
                    "pod_max_device_weight_reduction",
                    base_dev_bytes / pod_dev_bytes if pod_dev_bytes else 0.0,
                ), 2),
            "data": data, "model": model, "total_lanes": total_lanes,
            "chunk": chunk,
            "baseline": f"{data} independent engines (one full weight tree "
            f"each, sharded over model={model}, {parallel} lanes each) "
            "driven concurrently on the devices the in-repo replica pool "
            "uses — the N-process ReplicaPool shape at the same total "
            "lane count",
            "note": "value/vs_baseline = the consolidated pod (all lanes "
            "in one batched program, rows data-sharded; serving: --pod "
            "DxM --replicas 1). pod_sliced_* = the per-slice failover "
            "default (one scheduler per data slice); its per-dispatch tax "
            "is overstated on CPU mesh mocks, where every partition "
            "timeshares the host cores",
            "device": str(jax.devices()[0]),
        },
    }



def run_kernels() -> dict:
    """``bench.py --kernels``: the Pallas-kernel A/B gate (ISSUE 14, grown
    by the ISSUE 17 decode-superstep fusions) as one committed JSON — each
    kernel measured against the path it replaces IN THE SAME PROCESS with
    parity asserted, plus the computed roofline fields for the matmul arms
    and the fused-vs-unfused per-layer program-dispatch count. On a CPU
    host the kernels run in Pallas interpret mode: the timings are
    mechanism-relative (interpret has per-op overhead the chip doesn't),
    the PARITY gates and dispatch counts are authoritative, and the
    roofline fractions are denominated against the v5e peak so the TPU
    rerun drops into the same fields (chip numbers not measured)."""
    import functools

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models.sampling import _pick_sorted, _topp_partition_pick
    from distributed_llama_tpu.ops import attention as att
    from distributed_llama_tpu.ops import collectives
    from distributed_llama_tpu.ops.q40 import (
        dequantize_tpu,
        q40_matmul,
        quantize_q40_tpu,
        rmsnorm_q40_matmul,
        rmsnorm_ref,
    )

    rng = np.random.RandomState(0)
    detail: dict = {"device": str(jax.devices()[0])}

    def timed(fn, reps: int = 3) -> float:
        np.asarray(fn())  # warm/compile
        times = []
        for _ in range(reps):
            sw = Stopwatch()
            np.asarray(fn())
            times.append(sw.elapsed_ms())
        return median(times)

    # ---- q40 matmul: int8 MXU path vs f32-dequant kernel vs XLA fallback -
    n, d, T = 4096, 4096, 1
    w = rng.randn(n, d).astype(np.float32) / np.sqrt(n)
    qm = quantize_q40_tpu(w)
    x = jnp.asarray(rng.randn(T, n).astype(np.float32))
    want = np.asarray(x @ jnp.asarray(dequantize_tpu(qm)))
    arms = {}
    for path in ("f32", "int8"):
        fn = functools.partial(q40_matmul, x, qm, path=path)
        got = np.asarray(fn())
        rel = float(np.abs(got - want).max() / np.abs(want).max())
        ms = timed(fn)
        q40_bytes = params_hbm_bytes({"qs": qm.qs, "scales": qm.scales})
        arms[path] = {
            "ms": round(ms, 2),
            "max_rel_err_vs_dequant": round(rel, 5),
            **roofline_detail(q40_bytes, 1000.0 / ms, prefix=f"q40_{path}_"),
        }
        assert rel < 2e-2, f"q40 {path} kernel drifted from dequant: {rel}"
    detail["q40_matmul"] = {
        **arms,
        "int8_vs_f32_speedup": round(
            bench_metric("kernels_q40_int8_vs_f32", arms["f32"]["ms"] / arms["int8"]["ms"]), 3),
        "shape": f"[{T},{n}]x[{n},{d}] q40, standard basis, interpret on CPU",
    }

    # ---- fused rmsnorm→Q80 epilogue vs the standalone chain (ISSUE 17) --
    # the 7B layer shape again: the fusion deletes the separate rmsnorm
    # program ahead of every decode matmul (T=1), bit-identically
    wgt = jnp.asarray(rng.rand(n).astype(np.float32) + 0.5)

    def fused_norm():
        return rmsnorm_q40_matmul(x, wgt, qm, path="int8")

    def standalone_norm():
        return q40_matmul(rmsnorm_ref(x, wgt).astype(jnp.bfloat16), qm, path="int8")

    assert np.array_equal(
        np.asarray(fused_norm()), np.asarray(standalone_norm())
    ), "fused rmsnorm epilogue broke bit-parity"
    ms_fn, ms_sn = timed(fused_norm), timed(standalone_norm)
    detail["rmsnorm_fusion"] = {
        "standalone_ms": round(ms_sn, 2),
        "fused_ms": round(ms_fn, 2),
        "fused_vs_standalone_speedup": round(
            bench_metric("kernels_fusedq_vs_standalone", ms_sn / ms_fn), 3),
        "bit_identical": True,
        **roofline_detail(q40_bytes, 1000.0 / ms_sn, prefix="standalone_"),
        **roofline_detail(q40_bytes, 1000.0 / ms_fn, prefix="fusedq_"),
        "shape": f"rmsnorm+[{T},{n}]x[{n},{d}] q40 int8, interpret on CPU",
    }

    # ---- fused paged decode-attention vs the segmented-scan chain --------
    B, S, K, M, hd, chunk, page, P_ = 4, 1024, 4, 2, 64, 512, 64, 32
    qg = jnp.asarray(rng.randn(B, K, M, hd).astype(np.float32))
    keys = jnp.asarray(rng.randn(B, S, K, hd).astype(np.float32))
    values = jnp.asarray(rng.randn(B, S, K, hd).astype(np.float32))
    pool_k = jnp.asarray(rng.randn(P_, page, K, hd).astype(np.float32))
    pool_v = jnp.asarray(rng.randn(P_, page, K, hd).astype(np.float32))
    tables = jnp.asarray(rng.randint(0, P_, (B, S // page)).astype(np.int32))
    matched = jnp.asarray(np.array([512, 0, 384, 64], np.int32))
    pos = jnp.asarray(np.array([900, 140, 700, 80], np.int32))
    paged = (pool_k, pool_v, tables, matched)

    def scan_arm():
        prev = os.environ.get("DLT_FUSED_PAGED")
        os.environ["DLT_FUSED_PAGED"] = "0"
        try:
            return att.batched_decode_attention(qg, (keys, values), pos, chunk, paged=paged)
        finally:
            if prev is None:
                os.environ.pop("DLT_FUSED_PAGED", None)
            else:
                os.environ["DLT_FUSED_PAGED"] = prev

    def fused_arm():
        return att.fused_paged_decode_attention(qg, keys, values, pos, chunk, paged)

    ref, got = scan_arm(), fused_arm()
    assert bool(jnp.all(ref == got)), "fused paged attention broke bit-parity"
    scan_jit, fused_jit = jax.jit(scan_arm), jax.jit(fused_arm)
    ms_scan, ms_fused = timed(scan_jit), timed(fused_jit)
    detail["paged_attention"] = {
        "segmented_scan_ms": round(ms_scan, 2),
        "fused_kernel_ms": round(ms_fused, 2),
        "fused_vs_scan_speedup": round(
            bench_metric("kernels_fused_paged_vs_scan", ms_scan / ms_fused), 3),
        "bit_identical": True,
        "shape": f"B={B} S={S} chunk={chunk} page={page} f32, interpret on CPU",
    }

    # ---- double-buffered vs serial page-DMA schedule (tentpole c) -------
    def db_arm():
        return att.fused_paged_decode_attention(
            qg, keys, values, pos, chunk, paged, double_buffer=True)

    def serial_arm():
        return att.fused_paged_decode_attention(
            qg, keys, values, pos, chunk, paged, double_buffer=False)

    assert bool(jnp.all(db_arm() == serial_arm())), "DMA schedule changed bytes"
    ms_db, ms_serial = timed(jax.jit(db_arm)), timed(jax.jit(serial_arm))
    detail["paged_dma_overlap"] = {
        "serial_ms": round(ms_serial, 2),
        "double_buffered_ms": round(ms_db, 2),
        "bit_identical": True,
        "note": "interpret mode runs DMAs synchronously, so the CPU A/B "
        "pins bytes + dispatch overhead only; the chunk i+1 loads-under-"
        "compute overlap shows on chip",
    }

    # ---- spec-verify fused kernel vs the segmented verify scan (d) ------
    Tv = 4
    qgv = jnp.asarray(rng.randn(B, Tv, K, M, hd).astype(np.float32))
    posv = jnp.maximum(matched, pos - Tv)  # verify windows sit past matched

    def verify_scan():
        prev = os.environ.get("DLT_FUSED_PAGED")
        os.environ["DLT_FUSED_PAGED"] = "0"
        try:
            return att.batched_verify_attention(
                qgv, (keys, values), posv, chunk, paged=paged)
        finally:
            if prev is None:
                os.environ.pop("DLT_FUSED_PAGED", None)
            else:
                os.environ["DLT_FUSED_PAGED"] = prev

    def verify_fused():
        return att.fused_paged_verify_attention(qgv, keys, values, posv, chunk, paged)

    # the two DMA schedules are bit-identical by construction; the XLA
    # scan's fori_loop codegen can reassociate the merge by ulps at T>1
    # (the mechanism _segmented_batched_scan documents), so the scan arm
    # is pinned to within-ulp with the divergence recorded
    v_fused = np.asarray(verify_fused())
    v_serial = np.asarray(att.fused_paged_verify_attention(
        qgv, keys, values, posv, chunk, paged, double_buffer=False))
    assert np.array_equal(v_fused, v_serial), "verify DMA schedule changed bytes"
    v_scan = np.asarray(verify_scan())
    v_div = float(np.abs(v_scan - v_fused).max())
    assert v_div < 1e-6, f"fused verify drifted from the scan: {v_div}"
    ms_vscan, ms_vfused = timed(jax.jit(verify_scan)), timed(jax.jit(verify_fused))
    detail["spec_verify_attention"] = {
        "segmented_scan_ms": round(ms_vscan, 2),
        "fused_kernel_ms": round(ms_vfused, 2),
        "fused_vs_scan_speedup": round(
            bench_metric("kernels_fused_verify_vs_scan", ms_vscan / ms_vfused), 3),
        "dma_schedules_bit_identical": True,
        "max_abs_divergence_vs_scan": v_div,
        "shape": f"B={B} T={Tv} S={S} chunk={chunk} page={page} f32, "
        "interpret on CPU",
    }

    # ---- ring all-reduce vs psum on the mesh ----------------------------
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh, PartitionSpec as P

    n_dev = len(jax.devices())
    mesh = Mesh(mesh_utils.create_device_mesh((n_dev,)), ("tp",))
    xa = jnp.asarray(rng.randn(1, 4096).astype(np.float32))

    def wrap(impl):
        return jax.jit(jax.shard_map(
            lambda y: collectives.all_reduce(y, "tp", impl=impl),
            mesh=mesh, in_specs=P(None, None), out_specs=P(None, None),
            check_vma=False,
        ))

    f_psum, f_ring = wrap("psum"), wrap("ring_xla")
    assert bool(jnp.all(f_psum(xa) == f_ring(xa))), "ring all-reduce != psum"
    ms_psum = timed(lambda: f_psum(xa))
    ms_ring = timed(lambda: f_ring(xa))
    detail["all_reduce"] = {
        "psum_ms": round(ms_psum, 3),
        "ring_xla_ms": round(ms_ring, 3),
        "bit_identical": True,
        "devices": n_dev,
        "note": "ring_xla = the ring schedule in XLA ppermute steps (the "
        "CPU-mesh realization); the pallas remote-DMA ring compiles on "
        "TPU only — its schedule is pinned by this parity",
    }

    # ---- matmul+all-reduce seam: overlapped vs sequential (tentpole b) --
    # the wo shard shape of the 7B layer: each device holds 4096/n_dev rows
    # of the q40 pack; the seam either composes matmul→all_reduce or (on
    # TPU, int8 path) runs the fused ring epilogue. CPU pins the arms.
    n_sh = 4096 // n_dev
    packs = [
        quantize_q40_tpu(rng.randn(n_sh, 4096).astype(np.float32) / 64.0)
        for _ in range(n_dev)
    ]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *packs)
    xs_sh = jnp.asarray(rng.randn(n_dev, 1, n_sh).astype(np.float32))

    def seam(impl):
        def f(xsh, qm_):
            qm0 = jax.tree.map(lambda a: a[0], qm_)
            return collectives.matmul_all_reduce(xsh[0], qm0, "tp", impl=impl)
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("tp"), P("tp")), out_specs=P(None, None),
            check_vma=False))

    seam_psum, seam_ring = seam("psum"), seam("ring_xla")
    out_psum = np.asarray(seam_psum(xs_sh, stacked))
    out_ring = np.asarray(seam_ring(xs_sh, stacked))
    sc = np.abs(out_psum).max()
    np.testing.assert_allclose(out_ring / sc, out_psum / sc, atol=1e-5)
    ms_seq = timed(lambda: seam_psum(xs_sh, stacked))
    ms_ovl = timed(lambda: seam_ring(xs_sh, stacked))
    detail["matmul_allreduce_seam"] = {
        "sequential_psum_ms": round(ms_seq, 2),
        "ring_schedule_ms": round(ms_ovl, 2),
        "max_rel_divergence": round(float(np.abs(out_ring - out_psum).max() / sc), 8),
        "devices": n_dev,
        "shape": f"[1,{n_sh}]x[{n_sh},4096] q40 per shard",
        "note": "arms agree within f32 summation-order tolerance; the fused "
        "remote-DMA epilogue (fused_ring) is TPU-compiled only and falls "
        "back to this composition elsewhere — its tile accumulation order "
        "is pinned bit-exact vs the unfused int8 matmul per chunk",
    }

    # ---- superstep program dispatches: fused vs unfused (acceptance) ----
    # one decode layer at the 7B shape, counted via dllama_kernel_path_total
    # — the counter notes one label per dispatch decision, so with the
    # segmented scan weighted by its 3 segment programs (pool/mixed/slab)
    # the sum IS the per-layer program count.
    def superstep():
        h = rmsnorm_q40_matmul(x, wgt, qm, path="int8")       # attn norm+qkv
        a_ = att.batched_decode_attention(qg, (keys, values), pos, chunk, paged=paged)
        o = q40_matmul(x, qm, path="int8")                    # wo
        g = rmsnorm_q40_matmul(x, wgt, qm, path="int8")       # ffn norm+gate_up
        dn = q40_matmul(x, qm, path="int8")                   # down
        return h, a_, o, g, dn

    _LABELS = {
        "q40_matmul": ("mxu_int8", "mxu_int8_fusedq", "vpu_f32", "xla_fallback"),
        "paged_attention": ("pallas_fused", "pallas_fused_verify", "xla_segmented"),
        "all_reduce": ("ici_ring", "fused_ring", "ring_xla", "psum"),
        "rmsnorm": ("xla_standalone",),
    }
    _WEIGHT = {"xla_segmented": 3}  # pool/mixed/slab segment programs

    def count_dispatches(env: dict) -> tuple[int, dict]:
        prev = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        telemetry.enable()
        try:
            telemetry.reset()
            for out in superstep():
                np.asarray(out)
            ctr = telemetry.REGISTRY.counter(
                "dllama_kernel_path_total", labelnames=("kernel", "path"))
            programs = {}
            for kern, paths in _LABELS.items():
                for p in paths:
                    v = int(ctr.labels(kernel=kern, path=p).value)
                    if v:
                        programs[f"{kern}/{p}"] = v * _WEIGHT.get(p, 1)
            return sum(programs.values()), programs
        finally:
            telemetry.reset()
            telemetry.disable()
            for k, v in prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    fused_n, fused_programs = count_dispatches({})
    unfused_n, unfused_programs = count_dispatches(
        {"DLT_FUSED_Q80": "0", "DLT_FUSED_PAGED": "0"})
    assert fused_n < unfused_n, (
        f"fused superstep must strictly reduce dispatches: {fused_n} vs {unfused_n}"
    )
    detail["superstep_dispatches"] = {
        "fused_programs_per_layer": fused_n,
        "unfused_programs_per_layer": unfused_n,
        "reduction": round(
            bench_metric("kernels_superstep_dispatch_reduction",
                         unfused_n / fused_n), 3),
        "fused_breakdown": fused_programs,
        "unfused_breakdown": unfused_programs,
        "note": "counted via dllama_kernel_path_total over one decode layer "
        "(qkv, attention, wo, gate_up, down) at the 7B shape; xla_segmented "
        "weighted 3 for its pool/mixed/slab segment programs",
    }

    # ---- partition-based bare-top-p vs the full-vocab sort ---------------
    Bs, V = 8, 32000
    logits = jnp.asarray(rng.randn(Bs, V).astype(np.float32) * 0.05)  # near-flat
    probs = jax.nn.softmax(logits, axis=-1)
    coin = jnp.asarray(rng.rand(Bs).astype(np.float32))
    topp = jnp.full(Bs, 0.9, jnp.float32)
    topk0 = jnp.zeros(Bs, jnp.int32)

    @jax.jit
    def sort_pick():
        fi = jax.lax.top_k(logits, V)[1]
        return _pick_sorted(jnp.take_along_axis(probs, fi, axis=-1), fi, coin, topp, topk0)

    @jax.jit
    def part_pick():
        return _topp_partition_pick(probs, logits, coin, topp)

    assert bool(jnp.all(sort_pick() == part_pick())), "partition top-p != full sort"
    detail["topp_fallback"] = {
        "full_sort_ms": round(timed(sort_pick), 2),
        "partition_ms": round(timed(part_pick), 2),
        "picks_identical": True,
        "shape": f"B={Bs} V={V} near-flat logits (the overflow regime)",
    }

    speed = detail["q40_matmul"]["int8_vs_f32_speedup"]
    return {
        "metric": "pallas_kernel_ab_gates",
        "value": speed,
        "unit": "x (int8 MXU kernel vs f32 kernel, same shape/process)",
        "vs_baseline": speed,
        "detail": detail,
    }


def main_chaos(b: int):
    print(json.dumps(run_chaos(b)))


def require_accelerator():
    """The 7B modes are measurements: with no accelerator they fail instead
    of timing XLA's CPU backend (or a smaller model) under a chip's name."""
    import jax

    device = jax.devices()[0]
    if device.platform == "cpu":
        raise SystemExit(
            "bench.py: this mode measures an accelerator and JAX found none "
            f"(jax.devices()[0] is {device}); nothing was measured"
        )
    return device


def main_spec(k: int):
    require_accelerator()
    print(json.dumps(run_spec(llama2_7b_config(1024), "llama2_7b", k, weights="q40")))


def main_batch(b: int):
    require_accelerator()
    print(json.dumps(run_batch(llama2_7b_config(1024), "llama2_7b", b, weights="q40")))


def main():
    """Default mode: the Q40 line with the bf16 arm's numbers folded in.
    A chip belongs to one process at a time and 7B bf16 + Q40 do not fit
    its memory together, so this parent never touches JAX: the two arms
    run as two child invocations, one after the other, and a failure of
    either fails the run."""
    import subprocess

    def arm(flag: str) -> dict:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), flag],
            stdout=subprocess.PIPE, text=True, check=True,
        )
        return json.loads(out.stdout.strip().splitlines()[-1])

    # PRIMARY metric: Q40 — the reference's own headline weight format, so
    # vs_baseline is an apples-to-apples Q40-vs-Q40 comparison
    result = arm("--q40-only")
    bf16 = arm("--bf16-only")
    result["detail"]["bf16_decode_tokens_per_sec"] = bf16["value"]
    for key in ("chunked_decode_tokens_per_sec", "prefill_ms_64_tokens_warm"):
        result["detail"][f"bf16_{key}"] = bf16["detail"].get(key)
    print(json.dumps(result))


def main_single(weights: str):
    import jax

    device = require_accelerator()
    # seq_len: position budget 4x64 prefill + 128-wide decode window +
    # 128-wide chunk window (both replayed per rep) + 17 stepwise = 529;
    # a multiple of 512 (llama.ATT_CHUNK) so the bench runs the production
    # blocked-attention decode path
    result = run(llama2_7b_config(1024), "llama2_7b", weights=weights)
    result["detail"]["device"] = {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    if "--pod" in sys.argv and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        # the pod A/B needs a ('data','model') host mesh; 8 virtual devices
        # covers the default 2x2 with room (same conftest shape)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    if "--kernels" in sys.argv and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        # the ring-vs-psum parity gate needs a mesh; give the host platform
        # the same 8 virtual devices the test conftest uses (no effect on a
        # real TPU platform — the flag only shapes the HOST device list)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    # the cold-prefill metric measures what a fresh process pays: with the
    # persistent cache populated by a previous run, that is cache
    # deserialization, not a full XLA compile
    from distributed_llama_tpu.platform import enable_compilation_cache

    MODES = (
        "--q40-only", "--bf16-only", "--batch-decode", "--sampled", "--spec",
        "--prefix-cache", "--chaos", "--pod", "--kernels", "--mixtral-only",
    )
    if not any(m in sys.argv for m in MODES):
        main()  # spawns the two arms; this process stays off JAX
        sys.exit(0)
    enable_compilation_cache()
    # the bench IS an observability consumer: its numbers flow through the
    # telemetry registry (bench_metric) and its phases record trace spans
    telemetry.enable()
    if "--q40-only" in sys.argv:
        main_single("q40")
    elif "--bf16-only" in sys.argv:
        main_single("bf16")
    elif "--batch-decode" in sys.argv:
        # batched multi-stream decode vs B interleaved single streams (the
        # ISSUE 2 aggregate-throughput proof; numbers → docs/PERF.md)
        idx = sys.argv.index("--batch-decode")
        b = int(sys.argv[idx + 1]) if idx + 1 < len(sys.argv) else 4
        main_batch(b)
    elif "--sampled" in sys.argv:
        # device-resident sampling A/B (ISSUE 13): fused sampled vs greedy
        # single-stream, batched device-sampled vs host-sampler baseline
        # at B=4 — both relative, same device (numbers → docs/PERF.md)
        result = run_sampled(sampled_probe_config(512), "sampled_probe")
        print(json.dumps(result))
    elif "--spec" in sys.argv:
        # self-speculative decode (ISSUE 6): prompt-lookup drafts verified
        # k at a time vs plain chunked decode, acceptance rate in the JSON;
        # --spec 0 is the no-regression check (plain path, flag-gated)
        idx = sys.argv.index("--spec")
        k = int(sys.argv[idx + 1]) if idx + 1 < len(sys.argv) else 4
        main_spec(k)
    elif "--prefix-cache" in sys.argv:
        # prefix-cache TTFT proof (ISSUE 4): cold vs repeated-prefix hit,
        # hit/miss/eviction counts in the JSON; with --chaos also asserts a
        # quarantined row never frees pages the radix tree still references
        print(json.dumps(run_prefix_cache(chaos="--chaos" in sys.argv)))
    elif "--chaos" in sys.argv:
        # batched decode under an active fault plan: aggregate tok/s
        # degradation + recovery counts vs the clean round (ISSUE 3;
        # docs/ROBUSTNESS.md "Chaos bench")
        idx = sys.argv.index("--chaos")
        b = int(sys.argv[idx + 1]) if idx + 1 < len(sys.argv) else 4
        main_chaos(b)
    elif "--pod" in sys.argv:
        # one-process pod vs N-process-style replicas at matched lanes
        # (ISSUE 15): aggregate tok/s + resident weight bytes per replica
        # — committed as BENCH_POD_*.json
        print(json.dumps(run_pod()))
    elif "--kernels" in sys.argv:
        # Pallas kernel A/B gates (ISSUE 14 + the ISSUE 17 superstep
        # fusions): int8-MXU vs f32 q40 kernel, fused rmsnorm→Q80 epilogue
        # vs standalone chain, fused paged attention vs the segmented scan
        # (decode AND spec-verify, bit-parity asserted), double-buffered vs
        # serial page DMAs, matmul+all-reduce seam arms, partition top-p vs
        # full sort, and the fused-vs-unfused superstep program-dispatch
        # count — committed as BENCH_KERNELS_*.json
        print(json.dumps(run_kernels()))
    elif "--mixtral-only" in sys.argv:
        # multi-model probe (BASELINE config 3's shape class): one-chip
        # Mixtral-shaped MoE decode/prefill; not part of the default line —
        # run on demand, numbers recorded in docs/PERF.md
        require_accelerator()
        print(json.dumps(run(mixtral_shaped_config(1024), "mixtral_shaped_moe", weights="q40")))

    trace_path = os.environ.get("DLLAMA_BENCH_TRACE")
    if trace_path:  # phase spans as Chrome trace JSON (docs/OBSERVABILITY.md)
        telemetry.export_chrome_trace(trace_path)
        sys.stderr.write(f"bench trace written to {trace_path}\n")
