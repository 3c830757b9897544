"""Where the Q40 launches of a batched decode step find their weights, and what
each launch costs, with the row-bounded scan beside them and with the XLA loop
(PERF.md §7, PR 44: `q40_dense_roofline` fell 91.5 -> 86.5 at 8 rows and rose
89.2 -> 92.7 at 1 with no Q40 code touched).

    chiprun --timeout 1500 -- python3 tools/decode_step_ops.py [layers [rows [slots]]]

Builds ``sampling.decode_chunk_batched`` at Mistral-7B's widths (seeded random
Q40 weights, ``layers`` of its 32 so that two programs and their slabs fit the
chip; 8 rows of 8192 slots as `mistral7b.long_doc_qa` dispatches them, or 1 row
of a 16 x 2048 slab as `mistral7b.single_stream`) twice: with the row-bounded
kernel (a bucket of one row over a short slab, which is served by the loop
since this tool's first run, is made to take it too) and with
``decode_attention.supports`` answering no, so that the same step runs the XLA
loop. For each side, one JSON line on stdout and appended to
``chiprun_out/decode_step_ops.jsonl``:

* ``weights_in_vmem``: per role, how many of the step's launches the COMPILED
  program hands weights that XLA's memory-space assignment has already moved
  into VMEM (an operand whose layout says ``S(1)``, made by sliced
  ``slice-start``/``slice-done`` copies or a ``copy-start``/``copy-done``
  behind the ops before it), of how many;
* from a capture of one chunk of 32 steps: per role the launches' median, first
  and last decile in us (a launch that finds its weights in VMEM runs under its
  HBM floor, one that does not runs at it), the seconds the stream waited in
  ``*-done`` ops (a prefetch that did not hide), the scan's own ops, the
  step's ms.
"""

import collections
import json
import os
import re
import statistics
import sys
import tempfile
from unittest import mock

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.harness import trace_reduce  # noqa: E402
from distributed_llama_tpu.formats.model_file import ArchType  # noqa: E402
from distributed_llama_tpu.models import llama, sampling  # noqa: E402
from distributed_llama_tpu.models.config import LlamaConfig  # noqa: E402
from distributed_llama_tpu.ops import attention as att  # noqa: E402
from distributed_llama_tpu.ops import decode_attention, q40  # noqa: E402

STEPS = 32
RAGGED = [5600, 5900, 300, 5200, 0, 4700, 6100, 2500]  # tools/decode_scan_sweep.py's long_doc.ragged
_Q40_CALL = re.compile(r"%(q40_int8_[a-z_]+?)(?:\.\d+)* = \S+ custom-call\(([^)]*)\)")


def _matrix(key, n, d):
    k1, k2 = jax.random.split(key)
    np_, dp = q40._n_padded(n), q40._d_padded(d)
    scales = jax.random.uniform(k2, (np_ // 32, dp), jnp.float32, 0.5, 1.5) / 300.0
    return q40.QuantizedMatrix(jax.random.bits(k1, (np_ // 2, dp), dtype=jnp.uint8), scales, n, d)


def _model(layers, slots, dim=4096, hidden=14336, vocab=32000):
    cfg = LlamaConfig(
        arch=ArchType.LLAMA, dim=dim, hidden_dim=hidden, n_layers=layers, n_heads=dim // 128,
        n_kv_heads=8, vocab_size=vocab, seq_len=slots, head_size=128, kv_dim=1024, rope_theta=1e6,
    )
    key = jax.random.PRNGKey(44)
    ones = jnp.ones((dim,), jnp.float32)
    layer = lambda l: dict(  # noqa: E731
        qkv=_matrix(jax.random.fold_in(key, 4 * l), dim, dim + 2 * cfg.kv_dim),
        wo=_matrix(jax.random.fold_in(key, 4 * l + 1), dim, dim),
        gate_up=_matrix(jax.random.fold_in(key, 4 * l + 2), dim, 2 * hidden),
        down=_matrix(jax.random.fold_in(key, 4 * l + 3), hidden, dim), rms_att=ones, rms_ffn=ones,
    )
    angle = jnp.arange(slots, dtype=jnp.float32)[:, None] / 1e6 ** (jnp.arange(64) / 64.0)
    params = dict(
        embedding=jax.random.normal(key, (vocab, dim), jnp.float32), layers=[layer(l) for l in range(layers)],
        rms_final=ones, rope_table=jnp.stack([jnp.cos(angle), jnp.sin(angle)], -1),
        wcls=_matrix(jax.random.fold_in(key, 999), dim, vocab),
    )
    return cfg, params


def _weights_in_vmem(hlo):
    """{role: [launches whose weights operand lies in VMEM, launches]} of a compiled program's text."""
    layout = {m.group(1): m.group(2) for m in re.finditer(r"%([\w.\-]+) = (\S+) ", hlo)}
    out = collections.defaultdict(lambda: [0, 0])
    for role, operands in _Q40_CALL.findall(hlo):
        weights = [o for o in re.findall(r"%([\w.\-]+)", operands) if layout.get(o, "").startswith("u8[")]
        out[role][0] += any("S(1)" in layout[o] for o in weights)
        out[role][1] += 1
    return dict(out)


def _deciles(us):
    q = statistics.quantiles(us, n=10) if len(us) > 1 else [us[0]] * 9
    return {"launches": len(us), "median_us": round(statistics.median(us), 2), "p10_us": round(q[0], 2),
            "p90_us": round(q[-1], 2)}


def measure(side, cfg, params, rows, b_max):
    pos = jnp.asarray(RAGGED[:rows] if rows > 1 else [150], jnp.int32)
    active = jnp.ones((rows,), bool) if rows == 1 else jnp.arange(rows) != 4  # an inactive lane among them
    args = (jnp.ones((rows,), jnp.float32), jnp.full((rows,), 0.9, jnp.float32), jnp.zeros((rows,), jnp.int32),
            jnp.arange(rows, dtype=jnp.uint32))
    slab = llama.init_batch_cache(cfg, b_max, dtype=jnp.bfloat16)
    carry = jnp.ones((b_max,), jnp.int32)
    loop_only = mock.patch.object(decode_attention, "supports", lambda *a: False)
    kernel_always = mock.patch.object(att, "ONE_ROW_LOOP_SLOTS", 0)
    jax.clear_caches()  # the step's inner jits keep the trace of the side before
    with loop_only if side == "xla_loop" else kernel_always:
        run = sampling.decode_chunk_batched.lower(cfg, params, carry, slab, pos, active, STEPS, *args).compile()
    hlo = run.as_text()
    line = {"side": side, "rows": rows, "layers": cfg.n_layers, "slots": cfg.seq_len,
            "holds_kernel": "slab_decode_scan" in hlo, "weights_in_vmem": _weights_in_vmem(hlo),
            "device": jax.devices()[0].device_kind}
    if jax.default_backend() != "tpu":
        return line  # a rehearsal of the reader: device times come from the chip only
    for _ in range(2):
        out, slab, carry = run(params, carry, slab, pos, active, *args)
    out.block_until_ready()
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        out, slab, carry = run(params, carry, slab, pos, active, *args)
        out.block_until_ready()
        jax.profiler.stop_trace()
        planes = trace_reduce.load(trace_dir)
    lines = next(p for k, p in planes.items() if k != "_inventory")
    events = lines.get(trace_reduce.OPS_LINE, [])
    by_role = collections.defaultdict(list)
    for name, _, dur in events:
        m = re.match(r"%?(q40_int8_[a-z_]+?|slab_decode_scan)(\.\d+)* = ", name)
        if m:
            by_role[m.group(1)].append(dur / 1e3)
    kinds = sorted(trace_reduce._by_kind(trace_reduce._self_times(events)).items(), key=lambda kv: -kv[1])
    module_ns = max(dur for _, _, dur in lines.get(trace_reduce.MODULES_LINE, [["", 0, 0]]))
    line.update(
        launches={role: _deciles(us) for role, us in sorted(by_role.items())},
        waited_in_done_ops_ms=round(sum(ns for kind, ns in kinds if "-done" in kind) / 1e6, 3),
        q40_ms=round(sum(sum(us) for role, us in by_role.items() if role.startswith("q40")) / 1e3, 3),
        top_ops_ms=[[kind, round(ns / 1e6, 3)] for kind, ns in kinds[:12]],
        step_ms=round(module_ns / 1e6 / STEPS, 4),
    )
    return line


if __name__ == "__main__":
    layers, rows, slots = (int(a) for a in (sys.argv[1:] + ["16", "8", "8192"][len(sys.argv) - 1:])[:3])
    cfg, params = _model(layers, slots)
    os.makedirs("chiprun_out", exist_ok=True)
    for side in ("pallas_rowbound", "xla_loop"):
        line = json.dumps(measure(side, cfg, params, rows, 8 if rows > 1 else 16))
        print(line, flush=True)
        with open("chiprun_out/decode_step_ops.jsonl", "a") as f:
            f.write(line + "\n")
