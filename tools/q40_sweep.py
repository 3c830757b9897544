"""Device time of ONE launch of the Q40 int8 kernel by rows, output tile and
unpack: the provenance of ``ops/q40.py``'s ``_BLOCK_D_BY_ROWS`` (PERF.md §6, PR 31).

    chiprun --timeout 3000 -- python3 tools/q40_sweep.py [shape ...]

For each shape (Mixtral's two expert widths, Mistral's wqkv and wo, Solar's lin_in and its bank
of held experts, Granite-4.0-H-Small's bank of held experts' gate|up at the 1536 columns its pack
has since PR 51 and at the 2048 it was padded to before, and that bank's two launches with
PER-EXPERT rows ``[18, T, n]``, a held expert's bucket: PR 52), T in ROWS, block_d in TILES (block_n
stays 1024) and the unpack on packed words (``q40._nibbles``) or widened to int32 (the body before
PR 31, kept here): 50 launches over 4 weight buffers in turn under a profiler capture, the median
of the kernel's own device events. One JSON line a point, or a compiler's refusal, on stdout and
appended to ``chiprun_out/q40_sweep.jsonl``.

The ``*_tiled_*`` shapes (PR 54: the provenance of ``q40.GROUPED_ROW_TILE``) time a prompt piece's
bucketed launch of Granite-4.0-H-Small's and GLM-4.7-Flash's banks (buckets of 64 and 128 rows)
as ONE row block (the launch until PR 54) and in row tiles of 16 and 32 rows of which those past
an expert's count are skipped, at counts as a router draws them for a 256-row piece (for a
bucket of 64 of Granite-Small's: a 128-row piece) and all equal to their mean, and hold each tiled
result to the one block's: bit-equal on every live tile, 0.0 on every skipped one.
"""

import json
import os
import re
import statistics
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.harness import trace_reduce  # noqa: E402
from distributed_llama_tpu.ops import q40  # noqa: E402

# name: (n, d, experts in a bank or 0[, the columns the pack holds where not q40._d_padded(d)]);
# the bank goes through q40_grouped_matmul, all on
SHAPES = {"mixtral_gate_up": (4096, 28672, 0), "mixtral_down": (14336, 4096, 0),
          "mistral_wqkv": (4096, 6144, 0), "mistral_wo": (4096, 4096, 0),
          "solar_lin_in": (4096, 25600, 0), "solar_held_bank": (4096, 2560, 20),
          "granite_small_held_bank": (4096, 1536, 18), "granite_small_held_bank_2048": (4096, 1536, 18, 2048),
          "granite_small_bucket_gate_up": (4096, 1536, 18), "granite_small_bucket_down": (768, 4096, 18),
          "granite_small_tiled_gate_up": (4096, 1536, 18), "granite_small_tiled_down": (768, 4096, 18),
          "glm_flash_tiled_gate_up": (2048, 3072, 64), "glm_flash_tiled_down": (1536, 2048, 64)}
ROWS, TILES, LAUNCHES, BUFFERS = (1, 8, 16, 32, 64, 128, 256), (512, 1024, 2048, 4096), 50, 4
# the banks swept with PER-EXPERT rows, x [experts, T, n] (a held expert's bucket: models/moe.py), and their T
BUCKETS, BUCKET_ROWS = ("granite_small_bucket_gate_up", "granite_small_bucket_down"), (8, 16, 32, 64, 128)
# the banks whose bucketed launch is swept by row tile: (experts a token, the router's width); buckets, tiles (0: one block)
ROUTING = {"granite_small": (10, 72), "glm_flash": (4, 64)}
TILED_BUCKETS, ROW_TILES = (64, 128), (0, 16, 32)


def _widen(qs_ref):
    qs = qs_ref[:].astype(jnp.int32)
    return (qs & 0xF).astype(jnp.int8), (qs >> 4).astype(jnp.int8)


UNPACKS = {"packed": q40._nibbles, "widen": _widen}


def _weights(key, n, d, E, dp=None):
    lead, np_, dp = ((E,) if E else ()), q40._n_padded(n), dp or q40._d_padded(d)
    k1, k2 = jax.random.split(key)
    scales = jax.random.uniform(k2, lead + (np_ // 32, dp), jnp.float32, 0.5, 1.5) / 300.0
    scales = jnp.where(jnp.arange(dp) < d, scales, 0.0)  # the padding's columns hold zero scales
    return q40.QuantizedMatrix(jax.random.bits(k1, lead + (np_ // 2, dp), dtype=jnp.uint8), scales, n, d)


def _entry(x, qm, E, bd, role, interpret=q40._interpret_default()):  # True only in a CPU rehearsal
    if E:
        return q40.q40_grouped_matmul.__wrapped__(  # every row of every expert live
            x, qm, jnp.full((E,), x.shape[-2], jnp.int32), interpret=interpret, role=role)
    return q40._q40_matmul_int8.__wrapped__(x, qm, q40.BLOCK_N, bd, interpret, role)


def _captured(points, mats):
    """The device's ops-line events of LAUNCHES calls of every point's ``run(x, weights)``, in one capture."""
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for run, args in points:
            for i in range(LAUNCHES):
                y = run(*args, mats[i % BUFFERS])
            y.block_until_ready()
        jax.profiler.stop_trace()
        planes = trace_reduce.load(trace_dir)
    return [e for k, p in planes.items() if k != "_inventory" for e in p.get(trace_reduce.OPS_LINE, [])]


def _launch_us(events, role):
    # the launch's own event: its consumers' events name it among their operands
    us = [dur / 1e3 for ev, _, dur in events if re.match(rf"%?q40_int8_(grouped_)?{role}(\.\d+)* = ", ev)]
    assert us, f"no event of {role}; the ops line has: {sorted({e[0][:80] for e in events})[:40]}"
    return {"launches": len(us), "median_us": round(statistics.median(us), 2), "min_us": round(min(us), 2)}


def _router_counts(key, tokens, k, routed, E):
    """Rows each of the first ``E`` experts is chosen by when ``tokens`` rows take their top ``k`` of ``routed``."""
    _, idx = jax.lax.top_k(jax.random.normal(key, (tokens, routed)), k)
    return jnp.sum(jax.nn.one_hot(idx, routed, dtype=jnp.int32), axis=(0, 1))[:E]


def sweep_tiled(name):
    n, d, E = SHAPES[name]
    k, routed = ROUTING[name[: name.index("_tiled")]]
    mats = [_weights(jax.random.PRNGKey(i), n, d, E) for i in range(BUFFERS)]
    tile_was, runs = q40.GROUPED_ROW_TILE, {}
    for C in TILED_BUCKETS:
        for tm in ROW_TILES:
            q40.GROUPED_ROW_TILE = tm or C  # read as the fresh jit below traces, at its first call
            runs[C, tm] = jax.jit(lambda x, counts, qm, role=f"sweep_c{C}_tm{tm}": q40.q40_grouped_matmul.__wrapped__(
                x, qm, counts, role=role))
            runs[C, tm](jnp.zeros((E, C, n), jnp.bfloat16), jnp.zeros((E,), jnp.int32), mats[0]).block_until_ready()
    q40.GROUPED_ROW_TILE = tile_was
    # a capture a draw: the two draws' programs are one program, and so are their launches' names
    for draw in ("router", "equal"):
        points = []
        for C in TILED_BUCKETS:
            # the step that takes this bucket: a 256-row piece, or Granite-Small's 128-row one (bucket 64: models/moe.py)
            tokens = 128 if (name.startswith("granite") and C == 64) else 256
            counts = jnp.full((E,), round(tokens * k / routed), jnp.int32)
            if draw == "router":
                counts = _router_counts(jax.random.PRNGKey(C), tokens, k, routed, E)
            counts = jnp.minimum(counts, C)
            x = jax.random.normal(jax.random.PRNGKey(C + 1), (E, C, n), jnp.float32).astype(jnp.bfloat16)
            x = jnp.where((jnp.arange(C) < counts[:, None])[..., None], x, 0)  # a bucket fills from slot 0 up
            whole = np.asarray(runs[C, 0](x, counts, mats[0]))
            for tm in ROW_TILES:
                live = -(-np.asarray(counts) // (tm or C))  # row tiles an expert has rows in
                point = {"shape": name, "n": n, "d": d, "experts": E, "bucket": C, "tm": tm or C, "counts": draw,
                         "rows_chosen": int(counts.sum()), "rows_launched": int(live.sum()) * (tm or C),
                         "live_pairs": int(live.sum()), "pairs": E * (C // (tm or C))}
                if tm:
                    tiled, rows = np.asarray(runs[C, tm](x, counts, mats[0])), np.arange(C) < (live * tm)[:, None]
                    point["live_rows_bit_equal"] = bool((tiled[rows] == whole[rows]).all())
                    point["skipped_rows_zero"] = bool((tiled[~rows] == 0).all())
                points.append((point, f"sweep_c{C}_tm{tm}", runs[C, tm], (x, counts)))
        events = _captured([(run, args) for _, _, run, args in points], mats)
        for point, role, _, _ in points:
            yield {**point, **_launch_us(events, role)}


def sweep(name):
    if "_tiled_" in name:
        yield from sweep_tiled(name)
        return
    n, d, E, *held = SHAPES[name]
    mats = [_weights(jax.random.PRNGKey(i), n, d, E, *held) for i in range(BUFFERS)]
    points = []
    lead = (E,) if name in BUCKETS else ()
    for T in BUCKET_ROWS if lead else ROWS:
        x = jax.random.normal(jax.random.PRNGKey(T), lead + (T, n), jnp.float32).astype(jnp.bfloat16)
        for bd in sorted({q40._largest_divisor_tile(mats[0].d_padded, want, 128) for want in TILES}):
            for unpack, fn in UNPACKS.items():
                q40._nibbles, q40._int8_tiles = fn, lambda *a, bd=bd: (q40.BLOCK_N, bd)
                point = {"shape": name, "n": n, "d": d, "experts": E, "T": T, "bd": bd, "unpack": unpack,
                         "rows": "per_expert" if lead else "shared"}
                role = f"sweep_{unpack}_t{T}_bd{bd}"
                # a fresh jit of the served entry: its first call traces with the patched unpack and dispatch
                run = jax.jit(lambda x, qm, bd=bd, role=role: _entry(x, qm, E, bd, role))
                try:
                    run(x, mats[0]).block_until_ready()
                except Exception as e:  # the compiler's refusal is a result too
                    yield {**point, "refused": str(e).splitlines()[0][:160]}
                    continue
                points.append((point, role, run, x))
    events = _captured([(run, (x,)) for _, _, run, x in points], mats)
    # the file's 18 B per 32 weights over the chip's 819 GB/s (benchmark/peaks.json), as the rooflines count
    floor_us = (E or 1) * (n * d * 18 // 32) / 819e9 * 1e6
    for point, role, _, _ in points:
        launch = _launch_us(events, role)
        yield {**point, **launch, "weights_roofline_pct": round(100 * floor_us / launch["median_us"], 1)}


if __name__ == "__main__":
    assert jax.default_backend() == "tpu", "device times come from the chip only"
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/q40_sweep.jsonl", "a") as out:
        for line in (line for shape in sys.argv[1:] or SHAPES for line in sweep(shape)):
            for to in (sys.stdout, out):
                print(json.dumps(line), file=to, flush=True)
