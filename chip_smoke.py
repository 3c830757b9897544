#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

    python chip_smoke.py             # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   # tensor parallelism across four chips

Default run, Llama-2-7B Q40 at full width (random weights from ``--seed``):

  a. device: fail at once unless JAX's first device is a TPU
  b. artifacts: a 32-layer and a 2-layer `.m` plus a `.t`, written with the
     repo's own writers into ``.chip_smoke_tmp/`` (git-ignored, deleted at
     the end; never under ``chiprun_out/``)
  c. kernels alone, COMPILED, against the plain XLA path at the 7B shapes
  d. the 2-layer model through ``InferenceEngine`` vs the independent numpy
     oracle (tests/reference_impl.py) on the host
  e. ``python -m distributed_llama_tpu.server.api`` on the 32-layer file,
     a few HTTP requests, ``/metrics``, SIGTERM → clean drain, exit code 0
  f. smoke timings (NOT benchmark numbers) on earlier lines

A chip belongs to one process at a time, so this parent never imports JAX:
phases a-d and f run in one child, then the server runs as the next child,
one after the other. The last line of stdout is the result object; it is
printed only if every phase passed, and any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
TMP = os.path.join(ROOT, ".chip_smoke_tmp")
MODEL_32 = os.path.join(TMP, "llama2_7b_q40_32l.m")
MODEL_2 = os.path.join(TMP, "llama2_7b_q40_2l.m")
TOKENIZER = os.path.join(TMP, "synthetic_32000.t")

# the CPU parity tests' tolerance (tests/test_q40_ops.py,
# tests/test_kernel_parity.py): |got - want| <= 2e-2 * max|want|
KERNEL_TOL = 2e-2
# Q40 weights are exact on both sides; the engine adds bf16 activations and
# Q80 (int8) activation rounding per matmul, the oracle is f32 throughout —
# the same budget tests/test_q40_model.py gives q40-vs-f32 engines
ORACLE_TOL = 2e-2
PROMPT = [1, 15043, 3186, 29892, 445, 338, 263, 1243]  # 8 arbitrary in-vocab ids


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"  ok: {what}")


# ---------------------------------------------------------------------------
# children: the only code here that touches JAX
# ---------------------------------------------------------------------------


def _device(min_count: int) -> dict:
    """(a) — fail at once unless the first device is a TPU."""
    import importlib.metadata as md

    import jax
    import jaxlib

    d = jax.devices()[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    log(f"[a] device: {json.dumps(info)} jax {jax.__version__} "
        f"jaxlib {jaxlib.__version__} libtpu {libtpu}")
    if d.platform != "tpu":
        raise SmokeFailure(f"no accelerator: jax.devices()[0].platform is {d.platform!r}")
    if info["count"] < min_count:
        raise SmokeFailure(f"need {min_count} chips, JAX reports {info['count']}")
    return info


def _setup_child() -> str:
    from distributed_llama_tpu import native, telemetry
    from distributed_llama_tpu.platform import enable_compilation_cache

    telemetry.enable()  # kernel-path and compile-cache-hit counters
    cache = enable_compilation_cache()
    log(f"[f] compile cache directory: {cache} "
        f"(JAX_COMPILATION_CACHE_DIR {'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    log(f"[f] q40 repack served by: {'native' if native.available() else 'numpy'}")
    return cache


def _write_artifacts(seed: int, two_layer: bool) -> None:
    """(b) — the repo's own writers; random Q40 blocks written directly."""
    from distributed_llama_tpu.formats.synthetic import (
        llama2_7b_spec,
        synthetic_tokenizer_data,
        write_random_q40_model,
    )
    from distributed_llama_tpu.formats.tokenizer_file import write_tokenizer_file

    t0 = time.perf_counter()
    with open(TOKENIZER, "wb") as f:
        write_tokenizer_file(f, synthetic_tokenizer_data(vocab_size=32000))
    write_random_q40_model(MODEL_32, llama2_7b_spec(32), seed=seed)
    if two_layer:
        write_random_q40_model(MODEL_2, llama2_7b_spec(2), seed=seed)
    sizes = {
        os.path.basename(p): f"{os.path.getsize(p) / 1e9:.2f} GB"
        for p in (MODEL_32, MODEL_2, TOKENIZER) if os.path.exists(p)
    }
    log(f"[b] artifacts from seed {seed} in {time.perf_counter() - t0:.1f} s: {sizes}")


def _kernel_counts() -> dict[str, int]:
    from distributed_llama_tpu import telemetry

    out = {}
    for line in telemetry.REGISTRY.prometheus_text().splitlines():
        if line.startswith("dllama_kernel_path_total{"):
            labels, value = line[len("dllama_kernel_path_total"):].rsplit(" ", 1)
            out[labels] = int(float(value))
    return out


# Llama-2-7B's five matmuls: fused qkv, wo, fused gate|up, down, wcls
SHAPES_7B = ((4096, 12288), (4096, 4096), (4096, 22016), (11008, 4096), (4096, 32000))


def _kernels_alone(seed: int, shapes=SHAPES_7B, interpret: bool = False) -> None:
    """(c) — each kernel of the served path, compiled, vs plain XLA.
    (``shapes``/``interpret`` exist for the CPU rehearsal of this code.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_tpu.ops import attention as att
    from distributed_llama_tpu.ops import q40

    key = jax.random.PRNGKey(seed)
    log(f"[c] q40 int8 kernel vs the XLA fallback; tolerance {KERNEL_TOL} of max|want|")
    for n, d in shapes:
        np_, dp = q40._n_padded(n), q40._d_padded(d)
        key, k1, k2 = jax.random.split(key, 3)
        scales = jax.random.uniform(k2, (np_ // 32, dp), jnp.float32, 0.5, 1.5) / (64.0 * 4.6)
        # zero-scale padding, like the packer's
        scales = scales * (jnp.arange(np_ // 32)[:, None] < n // 32) * (jnp.arange(dp)[None, :] < d)
        qm = q40.QuantizedMatrix(
            jax.random.bits(k1, (np_ // 2, dp), dtype=jnp.uint8), scales, n, d
        )
        for T in (1, 64):
            key, kx = jax.random.split(key)
            x = jax.random.normal(kx, (T, n), jnp.float32).astype(jnp.bfloat16)
            want = np.asarray(q40._q40_matmul_fallback_jit(x, qm))
            scale = float(np.abs(want).max())
            bn, bd = q40._int8_tiles(qm, T, q40.BLOCK_N, q40.BLOCK_D)
            compiled = q40._q40_matmul_int8.lower(
                x, qm, block_n=bn, block_d=bd, interpret=interpret).compile()
            if not interpret and "tpu_custom_call" not in compiled.as_text():
                raise SmokeFailure(f"q40 int8 {n}x{d} T={T}: no tpu_custom_call in the compiled text")
            err = float(np.abs(np.asarray(compiled(x, qm)) - want).max()) / scale
            log(f"[c] q40_matmul int8 {n}->{d} T={T} "
                f"tiles ({bn},{bd}) max err {err:.2e} of max|want|")
            if not (np.isfinite(err) and err <= KERNEL_TOL):
                raise SmokeFailure(f"q40 int8 {n}x{d} T={T}: err {err} > {KERNEL_TOL}")

    # the chip's attention: the blocked XLA scan (prefill) and the segmented
    # paged scan (batched decode) vs one full-S softmax einsum in f32
    B, S, K, M, hd, chunk, page = 4, 2048, 32, 1, 128, 512, 64
    key, kq, kk, kv, kp = jax.random.split(key, 5)
    keys = jax.random.normal(kk, (B, S, K, hd), jnp.float32).astype(jnp.bfloat16)
    values = jax.random.normal(kv, (B, S, K, hd), jnp.float32).astype(jnp.bfloat16)
    qg = jax.random.normal(kq, (B, K, M, hd), jnp.float32)
    pos = jnp.asarray([2047, 1500, 700, 63], jnp.int32)
    # rows read their first `matched` positions through the page table out
    # of a pool that holds the same bytes (what a prefix hit publishes)
    n_tab = S // page
    tables = jnp.arange(B * n_tab, dtype=jnp.int32).reshape(B, n_tab)
    pool_k = keys.reshape(B * n_tab, page, K, hd)
    pool_v = values.reshape(B * n_tab, page, K, hd)
    matched = jnp.asarray([1024, 512, 0, 64], jnp.int32)

    @jax.jit
    def reference(q, k, v, p):  # one query [K, M, hd] at position p over [S, K, hd]
        s = jnp.einsum("kmh,skh->kms", q, k.astype(jnp.float32),
                       precision="highest") / jnp.sqrt(jnp.float32(hd))
        w = jax.nn.softmax(jnp.where(jnp.arange(S) <= p, s, -jnp.inf), axis=-1)
        return jnp.einsum("kms,skh->kmh", w, v.astype(jnp.float32), precision="highest")

    want = np.asarray(jax.vmap(reference)(qg, keys, values, pos))
    # bf16 storage, bf16 multiplies with f32 accumulation: 2^-8 per product
    att_tol = 2e-2
    got = jax.jit(lambda *a: att.batched_decode_attention(
        a[0], (a[1], a[2]), a[3], chunk, paged=(a[4], a[5], a[6], a[7])
    ))(qg, keys, values, pos, pool_k, pool_v, tables, matched)
    err = float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())
    log(f"[c] paged decode attention (xla_segmented) B={B} S={S} max err {err:.2e} of max|want|")
    if not err <= att_tol:
        raise SmokeFailure(f"paged decode attention err {err} > {att_tol}")
    T = 64
    qg_t = jax.random.normal(kp, (T, K, M, hd), jnp.float32)
    got = jax.jit(lambda q, k, v: att.blocked_attention(q, k, v, jnp.int32(1000), chunk))(
        qg_t, keys[0], values[0]
    )
    err = 0.0
    for t in (0, T - 1):
        w = np.asarray(reference(qg_t[t], keys[0], values[0], jnp.int32(1000 + t)))
        err = max(err, float(np.abs(np.asarray(got[t]) - w).max() / np.abs(w).max()))
    log(f"[c] blocked prefill attention T={T} pos=1000 max err {err:.2e} of max|want|")
    if not err <= att_tol:
        raise SmokeFailure(f"blocked attention err {err} > {att_tol}")


def _oracle(seed: int) -> None:
    """(d) — 2-layer full-width engine on the chip vs NumpyLlama on the host."""
    import gc

    import numpy as np

    from distributed_llama_tpu.engine import InferenceEngine
    from distributed_llama_tpu.formats.model_file import ModelFileReader
    from tests.reference_impl import NumpyLlama

    steps = 6
    engine = InferenceEngine(MODEL_2, dtype="q40")
    logits = [engine.prefill(PROMPT)]
    tokens = [int(np.argmax(logits[0]))]
    for _ in range(steps - 1):
        logits.append(engine.decode_step(tokens[-1]))
        tokens.append(int(np.argmax(logits[-1])))

    reader = ModelFileReader(MODEL_2)
    oracle = NumpyLlama(reader.spec, {name: reader.tensor(name) for name in reader.names()})
    reader.close()
    want = None
    for p, tok in enumerate(PROMPT):
        want = oracle.forward(tok, p)
    worst, ref_tokens = 0.0, []
    # the oracle is fed the ENGINE's tokens, so every step compares the same
    # context; its own argmax is the reference greedy token
    for step in range(steps):
        got = np.asarray(logits[step], np.float32)
        if not np.all(np.isfinite(got)) or got.shape != (32000,):
            raise SmokeFailure(f"engine logits at step {step}: shape {got.shape}, finite {np.isfinite(got).all()}")
        worst = max(worst, float(np.abs(got - want).max() / np.abs(want).max()))
        ref_tokens.append(int(np.argmax(want)))
        if ref_tokens[-1] != tokens[step]:
            margin = float(want[ref_tokens[-1]] - want[tokens[step]]) / float(np.abs(want).max())
            log(f"[d] greedy token differs at step {step}: oracle margin {margin:.2e} of max|logit|")
            if margin > 2 * ORACLE_TOL:
                raise SmokeFailure(f"greedy disagreement at step {step} is not a near-tie")
        if step + 1 < steps:
            want = oracle.forward(tokens[step], len(PROMPT) + step)
    log(f"[d] 2-layer 7B-width logits vs NumpyLlama: max err {worst:.2e} of max|logit| "
        f"over {steps} steps (tolerance {ORACLE_TOL}); greedy engine {tokens} oracle {ref_tokens}")
    if not worst <= ORACLE_TOL:
        raise SmokeFailure(f"logit error {worst} > {ORACLE_TOL}")
    del engine, oracle
    gc.collect()


def _engine_timings() -> None:
    """(f) — smoke timings on the 32-layer model, single stream."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_tpu import prng
    from distributed_llama_tpu.engine import InferenceEngine

    t0 = time.perf_counter()
    engine = InferenceEngine(MODEL_32, dtype="q40")
    jax.block_until_ready(engine.params)
    log(f"[f] smoke timing: 32-layer weight load + placement {time.perf_counter() - t0:.1f} s")
    prompt = (PROMPT * 8)[:64]
    t0 = time.perf_counter()
    engine.prefill(prompt)
    cold = time.perf_counter() - t0
    engine.reset()
    t0 = time.perf_counter()
    logits = engine.prefill(prompt)
    warm = time.perf_counter() - t0
    log(f"[f] smoke timing: prefill(64) first call {cold:.2f} s (compile or cache load + run), "
        f"second {warm * 1e3:.1f} ms -> compile ~{cold - warm:.2f} s")
    tok = int(np.argmax(logits))
    stream = engine.default_stream
    t0 = time.perf_counter()
    toks = np.asarray(stream.decode_chunk(tok, 32, 0.0, 0.9, seed=1))
    cold = time.perf_counter() - t0
    seed32 = jnp.uint32(prng.fold_seed(1))
    # the same chunk program, once fenced with block_until_ready and once
    # with a host fetch: on a directly attached chip both wait for the device
    t0 = time.perf_counter()
    dev = stream._dispatch_chunk(int(toks[-1]), 32, 0.0, 0.9, 0, seed32)
    dev.block_until_ready()
    bur = time.perf_counter() - t0
    last = int(np.asarray(dev)[-1])
    t0 = time.perf_counter()
    host = np.asarray(stream._dispatch_chunk(last, 32, 0.0, 0.9, 0, seed32))
    fetch = time.perf_counter() - t0
    log(f"[f] smoke timing: decode chunk(32) first call {cold:.2f} s -> compile ~{cold - fetch:.2f} s; "
        f"warm chunk fenced by block_until_ready {bur * 1e3:.1f} ms, by host fetch {fetch * 1e3:.1f} ms "
        f"({32 / fetch:.1f} tok/s single stream)")
    check(bool(np.all((host >= 0) & (host < 32000))), "decode chunk tokens in vocab")
    # one request the way the CLI drives it: fused prefill -> first token
    # sampled on device -> pipelined chunks
    # (twice: the first pass compiles the fused sampling program)
    for attempt in ("first pass, compiles", "second pass, warm"):
        engine.reset()
        stamps: list[float] = []

        def on_token(prev: int, tok: int) -> bool:
            stamps.append(time.perf_counter())
            return True

        t0 = time.perf_counter()
        first = engine.prefill_device(prompt, 0.0, 0.9, seed=1)
        engine.stream_decode(first, on_token, 0.0, 0.9, seed=1, chunk=32,
                             limit=len(prompt) + 96, first_prev=prompt[-1])
        total = time.perf_counter() - t0
        check(len(stamps) >= 96, f"one request generated {len(stamps)} tokens")
        log(f"[f] smoke timing: one request ({attempt}; 64 prompt tokens, {len(stamps)} generated): "
            f"time to first token {(stamps[0] - t0) * 1e3:.1f} ms, "
            f"{len(stamps) / total:.1f} tok/s end to end")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[f] peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')} "
        f"bytes_limit {stats.get('bytes_limit', 'not reported')}")


def _finish_child(name: str, device: dict) -> None:
    from distributed_llama_tpu import telemetry

    counts = _kernel_counts()
    log(f"[{name}] dllama_kernel_path_total: {json.dumps(counts, sort_keys=True)}")
    hits = telemetry.REGISTRY.get("dllama_compile_cache_hits_total")
    log(f"[f] compile cache hits in this process: {int(hits.value) if hits is not None else 0}")
    with open(os.path.join(TMP, f"{name}.json"), "w") as f:
        json.dump({"device": device, "kernel_paths": counts}, f)


def engine_phase(seed: int) -> None:
    """Phases a-d and f in ONE process that holds the chip."""
    device = _device(1)
    _setup_child()
    _write_artifacts(seed, two_layer=True)
    _kernels_alone(seed)
    _oracle(seed)
    _engine_timings()
    _finish_child("engine", device)


def tp_phase(seed: int) -> None:
    """``--chips 4``: the 32-layer file through tp=4 in one process that
    drives all four chips, compared with tp=1 on one of them."""
    import jax
    import numpy as np

    from distributed_llama_tpu.engine import InferenceEngine
    from distributed_llama_tpu.ops import collectives

    device = _device(4)
    _setup_child()
    _write_artifacts(seed, two_layer=False)
    steps = 32

    def greedy(engine):
        logits = [engine.prefill(PROMPT)]
        for _ in range(steps):
            logits.append(engine.decode_step(int(np.argmax(logits[-1]))))
        return np.stack(logits)

    t0 = time.perf_counter()
    e4 = InferenceEngine(MODEL_32, dtype="q40", tp=4)
    jax.block_until_ready(e4.params)
    log(f"[tp] smoke timing: tp=4 load + placement {time.perf_counter() - t0:.1f} s; "
        f"all-reduce arm: {collectives.default_impl()}")

    def placement(label, arr):
        devs = sorted(d.id for d in arr.sharding.device_set)
        shard = arr.addressable_shards[0].data.shape
        log(f"[tp] {label}: global {tuple(arr.shape)} shard {tuple(shard)} on devices {devs}")
        return devs

    layer0 = e4.params["layers"][0]
    for name in ("qkv", "wo", "gate_up", "down"):
        devs = placement(f"layers[0].{name}.qs", layer0[name].qs)
        check(len(devs) == 4, f"{name} has shards on four distinct devices")
        placement(f"layers[0].{name}.scales", layer0[name].scales)
    check(len(placement("wcls.qs", e4.params["wcls"].qs)) == 4, "wcls has shards on four distinct devices")
    placement("embedding (replicated)", e4.params["embedding"])
    placement("layers[0].rms_att (replicated)", layer0["rms_att"])
    l4 = greedy(e4)
    kv_leaf = jax.tree.leaves(e4.default_stream.cache)[0]
    check(len(placement("kv cache leaf 0", kv_leaf)) == 4, "KV cache has shards on four distinct devices")
    for d in jax.devices():
        s = d.memory_stats() or {}
        log(f"[tp] device {d.id}: bytes_in_use {s.get('bytes_in_use')} peak_bytes_in_use {s.get('peak_bytes_in_use')}")
    log(f"[tp] dllama_kernel_path_total under tp=4: {json.dumps(_kernel_counts(), sort_keys=True)}")

    e1 = InferenceEngine(MODEL_32, dtype="q40")
    l1 = greedy(e1)
    scale = float(np.abs(l1[0]).max())
    err = float(np.abs(l4[0] - l1[0]).max()) / scale
    log(f"[tp] prefill logits tp=4 vs tp=1: max err {err:.2e} of max|logit| (tolerance {KERNEL_TOL})")
    check(bool(np.isfinite(l4).all()) and err <= KERNEL_TOL, "tp=4 prefill logits agree with tp=1")
    t4, t1 = l4.argmax(-1), l1.argmax(-1)
    diff = np.nonzero(t4 != t1)[0]
    if diff.size:
        i = int(diff[0])
        margin = float(l1[i, t1[i]] - l1[i, t4[i]]) / float(np.abs(l1[i]).max())
        log(f"[tp] greedy tokens agree for {i} of {steps + 1} steps; at step {i} tp=1 prefers its "
            f"token by {margin:.2e} of max|logit|")
        check(margin <= 2 * KERNEL_TOL, "first greedy disagreement is a near-tie")
    else:
        log(f"[tp] greedy tokens identical for all {steps + 1} steps: {t1.tolist()}")
    _finish_child("tp", device)


# ---------------------------------------------------------------------------
# parent: no JAX from here on
# ---------------------------------------------------------------------------


def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def run_child(func: str, seed: int, name: str) -> dict:
    code = f"import chip_smoke; chip_smoke.{func}({seed})"
    rc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=_child_env()).returncode
    if rc != 0:
        raise SmokeFailure(f"{func} exited with code {rc}")
    with open(os.path.join(TMP, f"{name}.json")) as f:
        return json.load(f)


def rebuild_native() -> None:
    """The host library from the committed sources, never a binary that came
    along with the tree."""
    from distributed_llama_tpu import native

    for path in (native._LIB_PATH, native._KEY_PATH):
        if os.path.exists(path):
            os.remove(path)
    t0 = time.perf_counter()
    built = native.available()
    log(f"[f] native host library: {'rebuilt from sources' if built else 'no toolchain, numpy serves'} "
        f"in {time.perf_counter() - t0:.1f} s")


def _http(port: int, method: str, path: str, body: dict | None = None, timeout: float = 600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = json.dumps(body) if body is not None else None
        conn.request(method, path, payload, {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _sse(port: int, body: dict) -> tuple[int, list[str], float, float]:
    """One streamed completion: (status, deltas, seconds to first delta, total)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600.0)
    try:
        t0 = time.perf_counter()
        conn.request("POST", "/v1/chat/completions", json.dumps({**body, "stream": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        deltas, t_first, done = [], None, False
        for raw in resp:
            line = raw.strip()
            if not line.startswith(b"data: "):
                continue
            payload = line[len(b"data: "):]
            if payload == b"[DONE]":
                done = True
                break
            evt = json.loads(payload)
            if "error" in evt:
                raise SmokeFailure(f"SSE error event: {evt['error']}")
            text = (evt["choices"][0].get("delta") or {}).get("content", "")
            if text:
                if t_first is None:
                    t_first = time.perf_counter() - t0
                deltas.append(text)
        if not done:
            raise SmokeFailure("SSE stream ended without [DONE]")
        return resp.status, deltas, t_first or 0.0, time.perf_counter() - t0
    finally:
        conn.close()


def _metric_lines(text: str, prefix: str) -> list[str]:
    return [ln for ln in text.splitlines() if ln.startswith(prefix)]


def server_phase() -> None:
    """(e) — the server through its normal entry point, over HTTP."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cmd = [
        sys.executable, "-m", "distributed_llama_tpu.server.api",
        "--model", MODEL_32, "--tokenizer", TOKENIZER, "--dtype", "q40",
        "--parallel", "2", "--telemetry", "--port", str(port),
    ]
    log(f"[e] starting: {' '.join(cmd[1:])}")
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, cwd=ROOT, env=_child_env())
    try:
        while True:
            if proc.poll() is not None:
                raise SmokeFailure(f"server exited with code {proc.returncode} before it was ready")
            if time.perf_counter() - t0 > 600:
                raise SmokeFailure("server not ready after 600 s")
            try:
                status, _ = _http(port, "GET", "/readyz", timeout=5.0)
                if status == 200:
                    break
            except OSError:
                pass
            time.sleep(1.0)
        log(f"[f] smoke timing: server start to /readyz 200 in {time.perf_counter() - t0:.1f} s "
            "(imports + weight load + placement)")

        n_tok = 16
        system = ("You are a terse assistant for a smoke test of a serving stack. "
                  "Answer in as few words as you can. " * 3)

        def body(user: str, sys_prompt: str | None = None) -> dict:
            msgs = ([{"role": "system", "content": sys_prompt}] if sys_prompt else [])
            return {"messages": msgs + [{"role": "user", "content": user}],
                    "max_tokens": n_tok, "temperature": 0.0, "seed": 1}

        def complete(b: dict, label: str) -> dict:
            t = time.perf_counter()
            status, raw = _http(port, "POST", "/v1/chat/completions", b)
            dt = time.perf_counter() - t
            if status != 200:
                raise SmokeFailure(f"{label}: HTTP {status}: {raw[:300]!r}")
            out = json.loads(raw)
            got = out["usage"]["completion_tokens"]
            log(f"[e] {label}: 200, {out['usage']['prompt_tokens']} prompt + {got} completion tokens, "
                f"finish {out['choices'][0]['finish_reason']!r}, {dt:.2f} s (smoke timing)")
            if got != n_tok:
                raise SmokeFailure(f"{label}: asked {n_tok} tokens, got {got}")
            return out

        first = complete(body("hello world"), "non-streaming (first request: compiles)")
        status, deltas, ttft, total = _sse(port, body("hello there world"))
        check(status == 200 and len(deltas) > 0, f"SSE stream: 200, {len(deltas)} deltas, [DONE]")
        log(f"[f] smoke timing: streamed request time to first delta {ttft * 1e3:.0f} ms, total {total:.2f} s")
        results: dict[str, dict] = {}
        errors: list[BaseException] = []

        def worker(name: str, user: str) -> None:
            try:
                results[name] = complete(body(user, system), f"concurrent {name} (shared prefix)")
            except BaseException as e:  # re-raised on the main thread below
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(n, u))
                   for n, u in (("A", "hello"), ("B", "world"))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        if errors:
            raise errors[0]
        check(len(results) == 2, "two concurrent requests answered")
        # A and B prefilled side by side, so neither could reuse the other's
        # pages; a third request with the same system prompt must hit them
        complete(body("hello again", system), "shared prefix once more (prefix-cache hit)")
        t = time.perf_counter()
        again = complete(body("hello world"), "first request repeated (warm)")
        dt = time.perf_counter() - t
        log(f"[f] smoke timing: warm request {n_tok / dt:.1f} tok/s end to end over HTTP")
        check(
            again["choices"][0]["message"]["content"] == first["choices"][0]["message"]["content"],
            "repeated greedy request returns the same text",
        )

        status, raw = _http(port, "GET", "/metrics")
        check(status == 200, "/metrics scraped")
        text = raw.decode()
        paths = _metric_lines(text, "dllama_kernel_path_total{")
        for ln in paths:
            log(f"[e] {ln}")
        check(any('kernel="q40_matmul"' in ln for ln in paths), "q40_matmul kernel decisions were counted")
        for ln in paths:
            if 'kernel="q40_matmul"' in ln and "xla_fallback" in ln and float(ln.rsplit(" ", 1)[1]) > 0:
                raise SmokeFailure(f"a 7B matmul took the XLA fallback: {ln}")
        for prefix in ("dllama_prefix_cache_hits_total", "dllama_prefix_cache_misses_total",
                       "dllama_prefix_cache_matched_tokens_sum", "dllama_compile_cache_hits_total",
                       "dllama_batch_occupancy_sum", "dllama_batch_occupancy_count"):
            for ln in _metric_lines(text, prefix):
                log(f"[e] {ln}")
        hits = sum(float(ln.rsplit(" ", 1)[1]) for ln in _metric_lines(text, "dllama_prefix_cache_hits_total"))
        check(hits >= 1, f"prefix cache served {int(hits)} hit(s)")

        # the API returns no logprobs or fingerprints; what it says about
        # its own health is /readyz
        status, raw = _http(port, "GET", "/readyz")
        log(f"[e] /readyz {status}: {raw.decode()[:400]}")
        check(status == 200, "/readyz still 200 after the requests")

        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server did not exit within 120 s of SIGTERM")
        check(rc == 0, f"server drained and exited with code {rc} after SIGTERM")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the tensor-parallel phase (tp=4 vs tp=1) on a four-chip host")
    ap.add_argument("--seed", type=int, default=0, help="seed of the random weights")
    args = ap.parse_args()
    t0 = time.perf_counter()
    shutil.rmtree(TMP, ignore_errors=True)
    os.makedirs(TMP)
    try:
        rebuild_native()
        if args.chips == 4:
            device = run_child("tp_phase", args.seed, "tp")["device"]
        else:
            device = run_child("engine_phase", args.seed, "engine")["device"]
            server_phase()
        log(f"[f] chip_smoke wall time {time.perf_counter() - t0:.0f} s")
    except SmokeFailure as e:
        log(f"CHIP SMOKE FAILED: {e}")
        return 1
    finally:
        shutil.rmtree(TMP, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
