"""The texts of ``jax.make_jaxpr`` of five served programs, written into a
directory: ``q40.q40_matmul`` at 1 and 256 rows (4096 -> 12288),
``q40.rmsnorm_q40_matmul`` at 1 row, and ``moe.moe_ffn`` on a Q40 layer of 8
experts top 2 at Mixtral's widths at 16 and 256 rows. A change that says it
leaves the served programs as they were shows it by running this from two
trees and comparing the files (a leaf's pytree aux is not in a jaxpr, and a
moved line number is not in its text); nothing is computed, shapes only:

    git archive <parent> | tar -x -C .parent_check
    (cd .parent_check && PYTHONPATH=$PWD python3 ../tools/jaxpr_texts.py /tmp/jaxpr/parent)
    PYTHONPATH=$PWD python3 tools/jaxpr_texts.py /tmp/jaxpr/change
    diff -r /tmp/jaxpr/parent /tmp/jaxpr/change && echo byte-equal
"""
import hashlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
import jax.numpy as jnp

from distributed_llama_tpu.formats.model_file import ArchType, HiddenAct
from distributed_llama_tpu.formats.synthetic import tiny_spec
from distributed_llama_tpu.models import moe
from distributed_llama_tpu.models.config import config_from_spec
from distributed_llama_tpu.ops import q40
from distributed_llama_tpu.quants import FloatType

out = sys.argv[1]
print("tree:", os.path.dirname(os.path.dirname(q40.__file__)))
os.makedirs(out, exist_ok=True)
S = jax.ShapeDtypeStruct


def qm(n, d):
    return q40.QuantizedMatrix(
        S((n // 2, d), jnp.uint8), S((n // 32, d), jnp.float32), n_logical=n, d_logical=d
    )


def write(name, fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    with open(os.path.join(out, name + ".txt"), "w") as f:
        f.write(text)
    print(name, len(text), hashlib.sha256(text.encode()).hexdigest()[:16])


w = qm(4096, 12288)
for rows in (1, 256):
    write(f"q40_matmul_{rows}", lambda x, w: q40.q40_matmul(x, w, role="wqkv"),
          S((rows, 4096), jnp.bfloat16), w)
write("rmsnorm_q40_matmul_1",
      lambda x, g, w: q40.rmsnorm_q40_matmul(x, g, w, 1e-5, role="wqkv"),
      S((1, 4096), jnp.bfloat16), S((4096,), jnp.float32), w)

spec = tiny_spec(arch_type=ArchType.MIXTRAL, n_experts=8, n_active_experts=2,
                 hidden_act=HiddenAct.SILU, dim=4096, hidden_dim=14336, n_heads=32,
                 n_kv_heads=8, vocab_size=32000, seq_len=2048,
                 weights_float_type=FloatType.Q40)
cfg = config_from_spec(spec)
lp = {
    "router": S((4096, 8), jnp.bfloat16),
    "experts": [{"gate_up": qm(4096, 2 * 14336), "down": qm(14336, 4096)} for _ in range(8)],
}
for rows in (16, 256):
    write(f"moe_ffn_{rows}",
          lambda x, lp, n: moe.moe_ffn(cfg, x, lp, None, n_real=n),
          S((rows, 4096), jnp.bfloat16), lp, S((), jnp.int32))
