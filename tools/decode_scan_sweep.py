"""Device time of ONE launch of the row-bounded decode scan beside the XLA loop
it replaces, at the cells' slab shapes (PERF.md §6, PR 44).

    chiprun --timeout 1200 -- python3 tools/decode_scan_sweep.py [case ...]

A case is a slab leaf, a bucket of rows and their positions: ``even`` cases
give every row the same position, so both scans read the same bytes and the
pair compares the kernel with the loop; ``ragged`` cases are a decode step of
the cell as its traffic makes it (a cold row beside deep ones, an inactive
lane), so the pair also holds what the per-row bound saves. Each side runs
REPS launches chained inside one program (the next query depends on the last
output), five times; the median over REPS is one launch. One JSON line a case
on stdout and appended to ``chiprun_out/decode_scan_sweep.jsonl``: us a launch
of each side, the bytes each reads, GB/s on its own bytes, and the largest
difference between the two outputs on the chip.
"""

import json
import os
import statistics
import sys
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from distributed_llama_tpu.ops import attention as att  # noqa: E402
from distributed_llama_tpu.ops import decode_attention  # noqa: E402

CHUNK, REPS, W, C = 512, 50, 2048, 16
# name: (leaf shape, M, eva?, positions of the bucket's rows)
CASES = {
    "long_doc.even": ((2, 8, 8192, 8, 128), 4, False, [5600] * 8),
    "long_doc.ragged": ((2, 8, 8192, 8, 128), 4, False, [5600, 5900, 300, 5200, 0, 4700, 6100, 2500]),
    "evabyte.even": ((2, 8, 3072, 32, 128), 1, True, [7000] * 8),
    "evabyte.ragged": ((2, 8, 3072, 32, 128), 1, True, [6200, 7900, 6500, 0, 7200, 8100, 6900, 4300]),
    "exaone.ragged": ((2, 8, 16384, 8, 128), 8, False, [6400, 8100, 300, 7000, 0, 7700, 6900, 3000]),
    "single.1chunk": ((2, 16, 2048, 8, 128), 4, False, [150]),
    "single.2chunks": ((2, 16, 2048, 8, 128), 4, False, [700]),
    "rows16.even": ((2, 16, 2048, 8, 128), 4, False, [300] * 16),
    "rows16.ragged": ((2, 16, 2048, 8, 128), 4, False, [90 + 40 * i for i in range(16)]),
    "rows32.ragged": ((2, 32, 2048, 8, 128), 8, False, [300 + 33 * i for i in range(32)]),
}


def _attend(qg, leaf, pos, eva):
    if eva:
        return att.eva_batched_decode_attention(qg, leaf, pos, W, C, CHUNK)
    return att.batched_decode_attention(qg, leaf, pos, CHUNK)


def _loop_only():
    """While a program is traced under it, the callers take the XLA loop."""
    return mock.patch.object(decode_attention, "supports", lambda *a: False)


def _kernel_always():
    """While a program is traced under it, a bucket of one row over a short
    slab takes the kernel too (``att.ONE_ROW_LOOP_SLOTS`` keeps it on the loop)."""
    return mock.patch.object(att, "ONE_ROW_LOOP_SLOTS", 0)


def _chained(eva, kernel: bool):
    def run(qg, leaf, pos):
        with _kernel_always() if kernel else _loop_only():
            return jax.lax.fori_loop(0, REPS, lambda _, q: q + 1e-3 * _attend(q, leaf, pos, eva), qg)

    return jax.jit(run)


def _us(fn, *args):
    fn(*args).block_until_ready()
    times = []
    for _ in range(5):
        t = time.perf_counter()
        fn(*args).block_until_ready()
        times.append((time.perf_counter() - t) / REPS * 1e6)
    return statistics.median(times)


def _chunks(pos, eva, slots):
    """(chunks a row the kernel reads, chunks a row the loop reads)."""
    pos = np.asarray(pos)
    if eva:
        win, summ = -(-(pos % W + 1) // CHUNK), -(-((W // C) * (pos // W)) // CHUNK)
        return win + summ, np.full_like(pos, win.max() + summ.max())
    own = -(-np.clip(pos + 1, 0, slots) // CHUNK)
    return own, np.full_like(pos, own.max())


def sweep(name):
    shape, M, eva, pos = CASES[name]
    _, _, slots, K, hd = shape
    B = len(pos)
    key = jax.random.PRNGKey(len(name))
    leaf = jax.random.normal(key, shape, jnp.bfloat16)
    qg = jax.random.normal(jax.random.fold_in(key, 1), (B, K, M, hd), jnp.float32)
    posj = jnp.asarray(pos, jnp.int32)
    with _kernel_always():
        got = jax.jit(lambda q, a, p: _attend(q, a, p, eva))(qg, leaf, posj)
    with _loop_only():
        want = jax.jit(lambda q, a, p: _attend(q, a, p, eva))(qg, leaf, posj)
    own, bucket = _chunks(pos, eva, slots)
    per_chunk = 2 * CHUNK * K * hd * 2
    k_us, x_us = _us(_chained(eva, True), qg, leaf, posj), _us(_chained(eva, False), qg, leaf, posj)
    return {
        "case": name, "leaf": list(shape), "M": M, "rows": B,
        "kernel_us": round(k_us, 2), "xla_loop_us": round(x_us, 2),
        "kernel_bytes": int(own.sum()) * per_chunk, "xla_loop_bytes": int(bucket.sum()) * per_chunk,
        "kernel_GBps": round(int(own.sum()) * per_chunk / k_us / 1e3, 1),
        "xla_loop_GBps": round(int(bucket.sum()) * per_chunk / x_us / 1e3, 1),
        "max_abs_diff": float(jnp.max(jnp.abs(got - want))), "max_abs_out": float(jnp.max(jnp.abs(want))),
        "device": jax.devices()[0].device_kind,
    }


if __name__ == "__main__":
    os.makedirs("chiprun_out", exist_ok=True)
    for name in sys.argv[1:] or CASES:
        line = json.dumps(sweep(name))
        print(line, flush=True)
        with open("chiprun_out/decode_scan_sweep.jsonl", "a") as f:
            f.write(line + "\n")
