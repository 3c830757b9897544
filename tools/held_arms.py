"""The held experts' two arms, compiled, side by side at a share-holding configuration's bank
(``models/moe.py: _held_experts``; Granite-4.0-H-Small's 18 of 72 at 10 a token, 4096 -> 2 x 768
-> 4096 in Q40, Solar-Open2's, K-EXAONE's, GLM-4.7-Flash's): the bucket ``held_bucket_rows``
gives a step of T rows (at 256 rows its second tier compiled behind it) against every held
expert over every row. ``correct`` cannot hold them together where a cell decodes in the
bucketed arm only: the benchmark's probes decode in buckets of 1-8 rows, which have no bucket.

    chiprun --timeout 900 -- python3 tools/held_arms.py [bank:T[:even] ...]   # granite_small:32 and :128 where none is given

For each: the largest distance between the two results over the largest result (float32 sums
in another order: a few 1e-7), and each arm's device time a call, the median of the module's own
events in a profiler capture of 50 calls. One JSON line each on stdout and appended to
``chiprun_out/held_arms.jsonl``. PR 52's readings, with the row-by-row scatter and gather the
bucketed arm had until then beside them, are in PERF.md section 6. Where the bucket has more
than one row tile (``ops/q40.py: grouped_row_tile``, PR 54) the bucketed arm is also compiled
with tiles of 16 rows and as ONE row block (the launch until PR 54), and the served arm's result
is held to the one block's bit for bit (``tiles_bit_equal``). ``bucket_counted`` is the served
arm with ``models.moe.collect_launched`` open and its number returned beside the result: what the
``rows="launched"`` counter's device work costs a layer is ``bucket_counted_us - bucket_us``. The
routing is random top k of the router's width, or with ``:even`` every expert chosen by the same
number of rows.
"""

import json
import os
import statistics
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.harness import trace_reduce  # noqa: E402
from distributed_llama_tpu.formats.model_file import ArchType  # noqa: E402
from distributed_llama_tpu.models import moe  # noqa: E402
from distributed_llama_tpu.models.config import LlamaConfig  # noqa: E402
from distributed_llama_tpu.ops import q40  # noqa: E402
from q40_sweep import _weights  # noqa: E402  (tools/ is the script's directory: a seeded Q40 bank as the loader pads it)

# name: (dim, an expert's width, experts held, experts a token, the router's width)
BANKS = {"granite_small": (4096, 768, 18, 10, 72), "solar": (4096, 1280, 20, 8, 320),
         "k_exaone": (6144, 2048, 16, 8, 128), "glm": (2048, 1536, 64, 4, 64), "glm5": (6144, 2048, 16, 8, 256)}
CALLS = 50


def arms(name: str, T: int, even: bool = False):
    DIM, WIDTH, HELD, K, ROUTED = BANKS[name]
    cfg = LlamaConfig(
        arch=ArchType.GRANITE_HYBRID, dim=DIM, hidden_dim=2 * WIDTH, n_layers=1, n_heads=32, n_kv_heads=8,
        vocab_size=64, seq_len=64, head_size=128, kv_dim=1024, n_experts=HELD, n_active_experts=K,
        moe_hidden_dim=WIDTH, n_routed_experts=ROUTED, first_expert=0)
    keys = jax.random.split(jax.random.PRNGKey(T), 4)
    lp = {"experts_gate_up": _weights(keys[0], DIM, 2 * WIDTH, HELD), "experts_down": _weights(keys[1], WIDTH, DIM, HELD)}
    x = jax.random.normal(keys[2], (T, DIM), jnp.float32).astype(jnp.bfloat16)
    vals, idx = jax.lax.top_k(jax.nn.softmax(jax.random.normal(keys[3], (T, ROUTED)), axis=-1), K)
    vals = vals / vals.sum(-1, keepdims=True)
    if even:  # row t takes experts t * K .. t * K + K - 1 around the router's width
        idx = (jnp.arange(T)[:, None] * K + jnp.arange(K)) % ROUTED
    rule, tile, out = moe.held_bucket_rows, q40.GROUPED_ROW_TILE, {}
    bucket = rule(cfg, T)
    cases = [("bucket", bucket, tile), ("bucket_counted", bucket, tile), ("every_row", T, tile)]
    if q40.grouped_row_tile(bucket) < bucket:
        cases += [("bucket_tm16", bucket, 16), ("bucket_one_block", bucket, bucket)]
    for arm, rows, tm in cases:
        moe.held_bucket_rows, q40.GROUPED_ROW_TILE = (lambda cfg, T, rows=rows: rows), tm
        q40.q40_grouped_matmul.clear_cache()  # its trace read the row tile
        try:
            def run(lp, x, vals, idx, counted=arm.endswith("_counted")):
                with moe.collect_launched(counted) as launched:
                    out = moe._held_experts(cfg, x, lp, vals, idx)
                return (out, launched[0]) if counted else out

            # a jit of its own under its own name: traced here, with this arm's bucket
            run.__name__ = run.__qualname__ = f"held_{arm}_t{T}"
            out[arm] = (jax.jit(run), (lp, x, vals, idx))
            jax.block_until_ready(out[arm][0](*out[arm][1]))
        finally:
            moe.held_bucket_rows, q40.GROUPED_ROW_TILE = rule, tile
    counts = np.bincount(np.asarray(idx).ravel(), minlength=ROUTED)[:HELD]
    return cfg, out, {"bank": name, "T": T, "bucket": bucket, "routing": "even" if even else "random",
                      "most_rows_an_expert": int(counts.max()), "rows_chosen": int(counts.sum())}


def measure(name: str, T: int, even: bool = False) -> dict:
    _, out, point = arms(name, T, even)
    got = {arm: fn(*args) for arm, (fn, args) in out.items()}
    point["launched_rows"] = int(got["bucket_counted"][1])
    got = {arm: np.asarray(y[0] if arm.endswith("_counted") else y) for arm, y in got.items()}
    point["off"] = float(np.abs(got["bucket"] - got["every_row"]).max() / np.abs(got["every_row"]).max())
    if "bucket_one_block" in got:
        point["tiles_bit_equal"] = bool((got["bucket"] == got["bucket_one_block"]).all())
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for fn, args in out.values():
            for _ in range(CALLS):
                y = fn(*args)
            jax.block_until_ready(y)
        jax.profiler.stop_trace()
        planes = trace_reduce.load(trace_dir)
    events = [e for k, p in planes.items() if k != "_inventory" for e in p.get(trace_reduce.MODULES_LINE, [])]
    for arm in out:
        us = [dur / 1e3 for name, _, dur in events if f"held_{arm}_t{T}" in name]
        assert us, f"no module of held_{arm}_t{T}: {sorted({e[0][:60] for e in events})}"
        point[f"{arm}_us"] = round(statistics.median(us), 1)
    return point


if __name__ == "__main__":
    assert jax.default_backend() == "tpu", "device times come from the chip only"
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/held_arms.jsonl", "a") as log:
        for spec in sys.argv[1:] or ("granite_small:32", "granite_small:128"):
            name, T, *routing = spec.split(":")
            line = measure(name, int(T), routing == ["even"])
            for to in (sys.stdout, log):
                print(json.dumps(line), file=to, flush=True)
