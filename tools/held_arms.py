"""The held experts' two arms, compiled, side by side at a share-holding configuration's bank
(``models/moe.py: _held_experts``; Granite-4.0-H-Small's 18 of 72 at 10 a token, 4096 -> 2 x 768
-> 4096 in Q40, Solar-Open2's, K-EXAONE's, GLM-4.7-Flash's): the bucket ``held_bucket_rows``
gives a step of T rows (at 256 rows its second tier compiled behind it) against every held
expert over every row. ``correct`` cannot hold them together where a cell decodes in the
bucketed arm only: the benchmark's probes decode in buckets of 1-8 rows, which have no bucket.

    chiprun --timeout 900 -- python3 tools/held_arms.py [bank:T ...]   # granite_small:32 and :128 where none is given

For each: the largest distance between the two results over the largest result (float32 sums
in another order: a few 1e-7), and each arm's device time a call, the median of the module's own
events in a profiler capture of 50 calls. One JSON line each on stdout and appended to
``chiprun_out/held_arms.jsonl``. PR 52's readings, with the row-by-row scatter and gather the
bucketed arm had until then beside them, are in PERF.md section 6.
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
from q40_sweep import _weights  # noqa: E402  (tools/ is the script's directory: a seeded Q40 bank as the loader pads it)

# name: (dim, an expert's width, experts held, experts a token, the router's width)
BANKS = {"granite_small": (4096, 768, 18, 10, 72), "solar": (4096, 1280, 20, 8, 320),
         "k_exaone": (6144, 2048, 16, 8, 128), "glm": (2048, 1536, 64, 4, 64)}
CALLS = 50


def arms(name: str, T: int):
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
    rule, out = moe.held_bucket_rows, {}
    for arm, rows in (("bucket", rule(cfg, T)), ("every_row", T)):
        moe.held_bucket_rows = lambda cfg, T, rows=rows: rows
        try:
            def run(lp, x, vals, idx):
                return moe._held_experts(cfg, x, lp, vals, idx)

            # a jit of its own under its own name: traced here, with this arm's bucket
            run.__name__ = run.__qualname__ = f"held_{arm}_t{T}"
            out[arm] = (jax.jit(run), (lp, x, vals, idx))
            out[arm][0](*out[arm][1]).block_until_ready()
        finally:
            moe.held_bucket_rows = rule
    counts = np.bincount(np.asarray(idx).ravel(), minlength=ROUTED)[:HELD]
    return cfg, out, {"bank": name, "T": T, "bucket": rule(cfg, T), "most_rows_an_expert": int(counts.max())}


def measure(name: str, T: int) -> dict:
    _, out, point = arms(name, T)
    got = {arm: np.asarray(fn(*args)) for arm, (fn, args) in out.items()}
    point["off"] = float(np.abs(got["bucket"] - got["every_row"]).max() / np.abs(got["every_row"]).max())
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for fn, args in out.values():
            for _ in range(CALLS):
                y = fn(*args)
            y.block_until_ready()
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
            name, T = spec.split(":")
            line = measure(name, int(T))
            for to in (sys.stdout, log):
                print(json.dumps(line), file=to, flush=True)
