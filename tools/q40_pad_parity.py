"""On the chip: a Q40 pack whose columns are padded by ``ops.q40._d_padded`` gives, bit for bit,
the real columns of the same matrix padded to the next multiple of 1024 (the rule before PR 51).
tests/test_kernel_parity.py holds the LAUNCHES to that in interpret mode; the dense entry's bias
correction is an XLA dot outside the launch whose last bit is its backend's (the CPU's follows the
matrix's width between 8 and 32 rows), so the compiled programs are held here.

    chiprun --timeout 900 -- python3 tools/q40_pad_parity.py

The two packs PR 51 moved, at their depths: GLM-4.7-Flash's ``q_a|kv_a`` (2048 -> 1344, dense,
behind the fused rmsnorm as it is served) and Granite-4.0-H-Small's bank of 18 held experts'
gate|up (4096 -> 1536, grouped, shared and per-expert rows), at every row class. One JSON line a
case on stdout and in ``chiprun_out/q40_pad_parity.jsonl``; exits 1 if any case differs.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from distributed_llama_tpu.ops import q40  # noqa: E402

ROWS = (1, 8, 16, 32, 64, 128, 256)


def _repadded(qm, columns):
    pad = ((0, 0),) * (qm.qs.ndim - 1) + ((0, columns - qm.d_padded),)
    return q40.QuantizedMatrix(np.pad(np.asarray(qm.qs), pad), np.pad(np.asarray(qm.scales), pad), qm.n, qm.d)


def cases():
    rng = np.random.RandomState(51)
    draw = lambda n, d: q40.quantize_q40_tpu(rng.randn(n, d).astype(np.float32) / np.sqrt(n))
    qkv_a, bank = draw(2048, 1344), q40.stack_bank([draw(4096, 1536) for _ in range(18)])
    on = jnp.asarray(rng.rand(18) < 0.9)
    for T in ROWS:
        x = jnp.asarray(rng.randn(T, 2048).astype(np.float32)).astype(jnp.bfloat16)
        w = jnp.asarray(1.0 + 0.1 * rng.randn(2048).astype(np.float32))
        for name, fn in (("dense", q40.q40_matmul), ("fused_rmsnorm", lambda x, qm: q40.rmsnorm_q40_matmul(x, w, qm))):
            yield f"qkv_a_1344.{name}", T, 1344, fn(x, qkv_a), fn(x, _repadded(qkv_a, 2048))
        for name, shape in (("shared", (T, 4096)), ("per_expert", (18, T, 4096))):
            x = jnp.asarray(rng.randn(*shape).astype(np.float32))
            rows = jnp.where(on, T, 0)  # every row of a chosen expert's is live
            yield (f"held_gate_up_1536.{name}", T, 1536, q40.q40_grouped_matmul(x, bank, rows),
                   q40.q40_grouped_matmul(x, _repadded(bank, 2048), rows))


if __name__ == "__main__":
    assert jax.default_backend() == "tpu", "the compiled programs' bits come from the chip only"
    os.makedirs("chiprun_out", exist_ok=True)
    differ = 0
    with open("chiprun_out/q40_pad_parity.jsonl", "a") as out:
        for case, T, d, got, before in cases():
            got, before = np.asarray(got)[..., :d], np.asarray(before)[..., :d]
            line = {"case": case, "T": T, "columns": [int(got.shape[-1]), int(before.shape[-1])],
                    "bit_equal": bool(np.array_equal(got, before)),
                    "max_abs_diff": float(np.abs(got - before).max()), "max_abs": float(np.abs(got).max())}
            differ += not line["bit_equal"]
            for to in (sys.stdout, out):
                print(json.dumps(line), file=to, flush=True)
    sys.exit(1 if differ else 0)
