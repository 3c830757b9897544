"""The texts of ``jax.make_jaxpr`` of the two big SERVED programs, the batched
decode chunk (``sampling.decode_chunk_batched``, 32 rows) and a 256-row prompt
piece (``engine.batch._slab_prefill_single_paged``), for the five of the benchmark's
accepted configurations that hold experts or a recurrent state
(Granite-4.0-H-Micro, Solar-Open2, K-EXAONE, GLM-4.7-Flash, GLM-5), written into a
directory. ``tools/jaxpr_texts.py``'s method for a change to ``models/moe.py``,
the held experts' file layout or the state-space loader that says it leaves
those programs as they were: run it from two trees and compare the files.

Each configuration is its family's toy of ``tests/benchmark/`` (a seeded Q40
file of a few megabytes, loaded by the tree's own loader) with the REAL
configuration's experts a token, router width and held experts over it (8 of
320 with 20 held, 8 of 128 with 16, 4 of 64 with 64, 8 of 256 with 16): what a bucket rule reads.
Widths are the toys': no line of the compared code reads one.

    git archive <parent> | tar -x -C .parent_check
    (cd .parent_check && PYTHONPATH=$PWD python3 tools/served_jaxpr_texts.py /tmp/served/parent)   # the tool copied in
    PYTHONPATH=$PWD python3 tools/served_jaxpr_texts.py /tmp/served/change
    diff -r /tmp/served/parent /tmp/served/change && echo byte-equal

Beside each text a ``<name>.launches.txt``: the program's kernel launches alone (every
``pallas_call``'s parameters, the kernel's body among them, its blocks' index maps, which a
``GridMapping``'s own text leaves out, and its operands' and results' shapes, in program order, no
variable names), for a change that moves a program's text by something else (a counter's row, PR
54) and has to say which of its launches are the parent's to the letter.

With ``--published`` (PR 51: a change to the rule that pads a Q40 pack, which reads WIDTHS) the
same two programs of every file of ``benchmark/configs/`` at its published widths, the params as
shapes from the tree's own loader over a reader that holds no bytes (``tests/q40_leaf_shapes.py``,
copied into the other tree beside the tool): nothing is written or allocated, a minute for all.
"""
import hashlib
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
TREE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [TREE, os.path.join(TREE, "tests", "benchmark")]

import jax
import jax.numpy as jnp
from jax._src import core as jax_core

import exaone_tiny
import glm5_tiny
import glm_tiny
import granite_tiny
import solar_tiny
from benchmark import families
from benchmark.harness import modelfile
from distributed_llama_tpu.engine import InferenceEngine, batch
from distributed_llama_tpu.models import llama, sampling
from distributed_llama_tpu.models.config import config_from_spec

out = sys.argv[1]
print("tree:", TREE)
os.makedirs(out, exist_ok=True)
ROWS, PIECE, PAGE, PAGES, SEQ = 32, 256, 16, 64, 2048
CONFIGS = {
    "granite-h-micro": granite_tiny.CONFIG,
    "solar-open2": {**solar_tiny.CONFIG, "n_routed_experts": 20, "num_experts_per_tok": 8,
                    "reduced_from": {"n_routed_experts": 320}, "first_routed_expert": 40},
    "k-exaone": {**exaone_tiny.CONFIG, "num_experts": 16, "num_experts_per_tok": 8,
                 "reduced_from": {"num_experts": 128}, "first_routed_expert": 64},
    "glm-4.7-flash": {**glm_tiny.CONFIG, "n_routed_experts": 64, "num_experts_per_tok": 4},
    "glm-5": {**glm5_tiny.CONFIG, "n_routed_experts": 16, "num_experts_per_tok": 8,
              "reduced_from": {"n_routed_experts": 256}, "first_routed_expert": 0},
}


def launches(jaxpr):
    """The texts of a jaxpr's ``pallas_call`` equations, those of its sub-jaxprs among them, in order."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            avals = [[str(v.aval) for v in vs] for vs in (eqn.invars, eqn.outvars)]
            maps = [f"  index_map={m.index_map_jaxpr}" for m in eqn.params["grid_mapping"].block_mappings]
            yield "\n".join([f"pallas_call {avals}"] + [f"  {k}={v}" for k, v in sorted(eqn.params.items())] + maps)
        for sub in jax_core.jaxprs_in_params(eqn.params):
            yield from launches(sub)


def write(name, fn, static, *args):
    jaxpr = jax.make_jaxpr(fn, static_argnums=static)(*args)
    for suffix, text in ((".txt", str(jaxpr)), (".launches.txt", "\n".join(launches(jaxpr.jaxpr)))):
        with open(os.path.join(out, name + suffix), "w") as f:
            f.write(text)
        print(name + suffix, len(text), hashlib.sha256(text.encode()).hexdigest()[:16])


def shapes(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def served(name, cfg, params):
    slab = shapes(jax.eval_shape(lambda: llama.init_batch_cache(cfg, ROWS, dtype=jnp.bfloat16)))
    pool = shapes(jax.eval_shape(lambda: llama.init_page_pool(cfg, PAGES, PAGE, dtype=jnp.bfloat16)))
    s = jax.ShapeDtypeStruct
    rows = lambda dt: s((ROWS,), dt)
    write(f"{name}.decode_chunk_{ROWS}", sampling.decode_chunk_batched, (0, 6), cfg, params, rows(jnp.int32),
          slab, rows(jnp.int32), rows(jnp.bool_), 32, rows(jnp.float32), rows(jnp.float32),
          rows(jnp.int32), rows(jnp.uint32))
    scalar = s((), jnp.int32)
    write(f"{name}.piece_{PIECE}", batch._slab_prefill_single_paged, (0,), cfg, params,
          s((PIECE,), jnp.int32), slab, pool, scalar, scalar, scalar, s((SEQ // PAGE,), jnp.int32), scalar)


if "--published" in sys.argv[2:]:
    from tests import q40_leaf_shapes

    for name in q40_leaf_shapes.CONFIGS:
        config = q40_leaf_shapes.config_of(name)
        cfg = config_from_spec(families.load(config, "modelfile").model_spec(config, SEQ))
        served(name, cfg, shapes(q40_leaf_shapes.param_shapes(name)))
else:
    with tempfile.TemporaryDirectory() as tmp:
        for name, config in CONFIGS.items():
            model = modelfile.write_model(os.path.join(tmp, name + ".m"), {**config, "name": name}, SEQ, 7)
            engine = InferenceEngine(model, dtype="q40", max_seq_len=SEQ)
            served(name, engine.cfg, shapes(engine.params))
