#!/usr/bin/env python3
"""Rehearsal without the chip: compile the server's two big programs of each
configuration for a DESCRIBED v5e (``v5e:2x2``, one chip of it) and print the
compiler's memory analysis. Run by hand before a chip call:

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py [config | cell ...]

Nothing runs and no time means anything; what it shows is whether the batched
decode chunk and the 256-row paged prefill chunk lower at the published
widths, and whether weights + slab + page pool + the program's temporaries
fit the chip's 16 GB as the cells' memory arithmetic says. A configuration is
rehearsed at 16 rows of 2048 positions and 384 pool pages; a cell
(``benchmark/workloads/<cell>.json``) at its own flags' rows, positions and
pages: a cell's context is its own.

It is a script and not a test because tests/test_chip_compile.py holds the
suite's one topology fixture: a second file lands on another worker, where
libtpu is already taken, and skips in silence.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 16e9
ROWS, PAGES, PAGE, SEQ, PREFILL_ROWS = 16, 384, 64, 2048, 256


def rehearse(name: str) -> bool:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import families
    from benchmark.harness import modelfile
    from distributed_llama_tpu.engine import InferenceEngine
    from distributed_llama_tpu.engine import batch
    from distributed_llama_tpu.models import llama, sampling
    from distributed_llama_tpu.ops import q40

    from benchmark.harness.cell import Cell

    rows, pages, seq = ROWS, PAGES, SEQ
    if os.path.isfile(os.path.join(ROOT, "benchmark", "workloads", f"{name}.json")):
        cell = Cell(ROOT, name)  # a cell: its configuration at the sizes its flags launch
        name, config = cell.config["name"], cell.config
        rows, pages, seq = (cell.flag("--parallel", ROWS), cell.flag("--kv-pages", PAGES),
                            cell.flag("--max-seq-len", SEQ))
    else:
        with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
            config = json.load(f)
    # the tree's shapes from a ONE-layer file loaded on the CPU; every layer has them
    one = dict(config, num_hidden_layers=1, name=f"{name}.1l")
    directory = os.path.join(ROOT, "benchmark", ".cache", "rehearse")
    families.counts(config)  # a configuration its family does not know stops here, by name
    model, _ = modelfile.write_artifacts(one, 0, directory, config["max_position_embeddings"])
    engine = InferenceEngine(model, dtype="q40", max_seq_len=seq)
    os.remove(model)
    layers = config["num_hidden_layers"]
    cfg = dataclasses.replace(engine.cfg, n_layers=layers)
    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    # the CPU process would choose Pallas interpret mode; the chip compiles the kernels
    q40._interpret_default = lambda: False

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    params = abstract({**engine.params, "layers": [engine.params["layers"][0]] * layers})
    slab = abstract(jax.eval_shape(lambda: llama.init_batch_cache(cfg, rows, dtype=engine.cache_dtype)))
    pool = abstract(jax.eval_shape(lambda: llama.init_page_pool(cfg, pages, PAGE, dtype=engine.cache_dtype)))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    n_table = -(-seq // PAGE)
    programs = {
        f"prefill chunk, {PREFILL_ROWS} rows, paged": lambda: batch._slab_prefill_single_paged.lower(
            cfg, params, s((PREFILL_ROWS,), jnp.int32), slab, pool, s((), jnp.int32), s((), jnp.int32),
            s((), jnp.int32), s((n_table,), jnp.int32), s((), jnp.int32)),
        f"decode chunk, {rows} rows x 32 steps, paged": lambda: sampling.decode_chunk_batched_paged.lower(
            cfg, params, s((rows,), jnp.int32), slab, s((rows,), jnp.int32), s((rows,), jnp.bool_),
            pool, 32, s((rows,), jnp.float32), s((rows,), jnp.float32), s((rows,), jnp.int32),
            s((rows,), jnp.uint32), s((rows, n_table), jnp.int32), s((rows,), jnp.int32)),
    }
    resident = nbytes(params) + nbytes(slab) + nbytes(pool)
    print(f"{name}: {layers} layers, {rows} rows of {seq} positions, {pages} pool pages; weights {nbytes(params) / 1e9:.2f} GB + slab "
          f"{nbytes(slab) / 1e9:.2f} GB + pool {nbytes(pool) / 1e9:.2f} GB = {resident / 1e9:.2f} GB resident")
    ok = True
    for label, lower in programs.items():
        t = time.perf_counter()
        compiled = lower().compile()
        mem = compiled.memory_analysis()
        kernels = compiled.as_text().count("tpu_custom_call")
        total = resident + mem.temp_size_in_bytes
        fits = total <= HBM
        ok &= fits and kernels > 0
        print(f"  {label}: compiled for the described v5e in {time.perf_counter() - t:.0f} s; "
              f"{kernels} tpu_custom_call sites; arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, resident + temporaries "
              f"{total / 1e9:.2f} GB of {HBM / 1e9:.0f} -> {'fits' if fits else 'DOES NOT FIT'}")
    return ok


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    names = argv or sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmark", "configs")))
    return 0 if all([rehearse(n) for n in names]) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
