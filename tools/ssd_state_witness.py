#!/usr/bin/env python3
"""The state-space layers' recurrent state held to the reference by LOGITS, on
the chip: what ``correct`` cannot see (a rule over greedy tokens is blind to a
state kept in bfloat16: ``benchmark/workloads/granite-4.0-h-micro.batch_prompted.json``).

    chiprun --timeout 1200 -- python3 tools/ssd_state_witness.py [--variants served,state_bf16,state_e4m3]
    chiprun --timeout 1500 -- python3 tools/ssd_state_witness.py --config granite-4.0-h-small-q40-10l-ep4 --held 8

Granite-4.0-H-Micro's published widths (``--config``: another configuration
of the lineage, as Granite-4.0-H-Small's 128 heads, a state of ``[64, 128,
128]`` a row and layer, behind whose mixers stand held experts; ``--held``
holds fewer of them, since float32 weights are 4 B where the served Q40 is
0.6: 18 experts a layer in float32 are 11.6 GB beside the state, 8 are 7.9),
its first period of ten layers (nine
state-space, the softmax layer at index 5), seeded weights, the program's own
forwards in FLOAT32 (so that no Q80 rounding hides the state): a few rows are
prefilled in pieces of 256 tokens through ``llama.forward_tokens`` (the state
handed from piece to piece, kernel ``ssd_chunk``), then decoded for hundreds of
steps through ``llama.forward_step_batched`` over a slab (kernel ``ssd_step``,
the state updated in place every step), teacher-forced; the logits of the last
prompt token and of every 64th step are held to the family's plain float32
reference over the whole sequence (a token-by-token recurrence, no cache).

Variants: ``served`` is the program as it is (the state float32, ``assumed``
in the configuration's file) and has to read float32's own rounding (1.5e-4
of max|logit| on the chip); ``state_bf16`` and ``state_e4m3`` keep what a step
or a piece hands on in bfloat16 / at three mantissa bits, and have to read NOT
ok: more than ``LIMIT`` of max|logit| off (4.5e-2 and 0.85 after 512 steps). Prints one JSON line a variant and exits non-zero
if a verdict is not the expected one.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# between its two readings on the chip (my chip run, PR 45, call pr45e: ten layers at the published
# widths, 4 rows, 512 tokens in pieces and 512 decode steps): the program as it is 1.5e-4 of
# max|logit| at the worst of nine checkpoints (3.5e-5 to 1.3e-4 in decode; 2e-6 on the CPU at the
# toy size), a bfloat16 state 9.3e-3 after 64 steps and 4.5e-2 after 512
LIMIT = 1e-3
PIECE = 256


def run(config: dict, model: str, variants: list[str], rows: int, prompt: int, steps: int,
        every: int, seed: int) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import families
    from benchmark.reference.qfile import QFile
    from distributed_llama_tpu.engine import InferenceEngine
    from distributed_llama_tpu.models import llama
    from distributed_llama_tpu.ops import ssd

    rng = np.random.default_rng(seed)
    tokens = rng.integers(300, config["vocab_size"], (rows, prompt + steps)).astype(np.int32)
    at = [prompt - 1] + list(range(prompt + every - 1, prompt + steps, every))
    ref = families.load(config, "reference")
    t = time.monotonic()
    want = ref.forward(QFile(model, ref), tokens, np.asarray(at))  # [rows, len(at), vocab]
    ref_s = time.monotonic() - t
    engine = InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32, max_seq_len=prompt + steps)
    cfg, params = engine.cfg, engine.params
    roundings = {
        "served": None,
        # reduce_precision and not a cast there and back: the TPU compiler removes such a pair
        # (the first chip run of this tool read the planted bfloat16 state equal to the served
        # one in every digit)
        "state_bf16": lambda S: jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7),
        "state_e4m3": lambda S: jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=3),
    }
    step_real, chunk_real = ssd.ssd_step, ssd.ssd_chunk

    def _row_axis(leaf) -> int:
        return 1 if leaf.ndim == 5 else 0  # a fused K/V leaf [2, rows, ...]; a state leaf [rows, ...]

    def _take(leaf, row):
        return jnp.take(leaf, row, axis=_row_axis(leaf))

    def _put(leaf, new, row):
        return jax.lax.dynamic_update_index_in_dim(leaf, new, row, _row_axis(leaf))

    out = []
    for name in variants:
        rounding = roundings[name]
        if rounding is not None:
            ssd.ssd_step = lambda *a, **k: (lambda y, S: (y, rounding(S)))(*step_real(*a, **k))
            ssd.ssd_chunk = lambda *a, **k: (lambda y, S: (y, rounding(S)))(*chunk_real(*a, **k))
        jax.clear_caches()
        try:
            # the weights are an ARGUMENT: closed over, 4.6 GB of them were lowered as constants
            @functools.partial(jax.jit, donate_argnums=(1,))
            def piece(params, slab, row, toks, pos):
                row_cache = jax.tree.map(lambda leaf: _take(leaf, row), slab)
                logits, new = llama.forward_tokens(cfg, params, toks, row_cache, pos)
                return logits[-1], jax.tree.map(lambda leaf, r: _put(leaf, r, row), slab, new)

            @functools.partial(jax.jit, donate_argnums=(1,))
            def decode(params, slab, toks, pos):
                def step(carry, tok):
                    slab, pos, kept = carry
                    logits, slab = llama.forward_step_batched(
                        cfg, params, tok, slab, pos[0], jnp.ones((rows,), bool))
                    # every ``every``-th step's logits, kept as they come: not 512 steps' of them
                    at = pos[1] // every
                    kept = jnp.where((pos[1] % every == every - 1),
                                     jax.lax.dynamic_update_index_in_dim(kept, logits, at, 0), kept)
                    return (slab, (pos[0] + 1, pos[1] + 1), kept), None
                kept = jnp.zeros((toks.shape[0] // every, rows, cfg.vocab_size), jnp.float32)
                (slab, _, kept), _ = jax.lax.scan(step, (slab, (pos, jnp.int32(0)), kept), toks)
                return slab, kept

            slab = llama.init_batch_cache(cfg, rows, dtype=jnp.float32)
            t = time.monotonic()
            got = np.zeros_like(want)
            for r in range(rows):
                for lo in range(0, prompt, PIECE):
                    last, slab = piece(params, slab, jnp.int32(r), jnp.asarray(tokens[r, lo:lo + PIECE]),
                                       jnp.int32(lo))
                got[r, 0] = np.asarray(last)
            slab, logits = decode(params, slab, jnp.asarray(tokens[:, prompt:].T),
                                  jnp.full((rows,), prompt, jnp.int32))
            got[:, 1:] = np.moveaxis(np.asarray(logits), 0, 1)
            took = time.monotonic() - t
        finally:
            ssd.ssd_step, ssd.ssd_chunk = step_real, chunk_real
        err = np.abs(got - want).max(-1) / np.abs(want).max(-1)  # [rows, len(at)]
        out.append({"variant": name, "ok": bool(err.max() <= LIMIT), "limit": LIMIT,
                    "after_prompt": float(err[:, 0].max()),
                    "by_step": {str(p - prompt + 1): float(err[:, i].max()) for i, p in enumerate(at) if i},
                    "tokens_equal": float((got.argmax(-1) == want.argmax(-1)).mean()),
                    "seconds": round(took, 1), "reference_seconds": round(ref_s, 1)})
    return out


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--variants", default="served,state_bf16,state_e4m3")
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=512)
    ap.add_argument("--steps", type=int, default=512)
    ap.add_argument("--every", type=int, default=64)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--config", default="granite-4.0-h-micro-q40",
                    help="a configuration of the lineage under benchmark/configs/")
    ap.add_argument("--held", type=int, help="hold this many of the configuration's routed experts")
    args = ap.parse_args(argv)
    import jax

    from benchmark.harness import modelfile

    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}), flush=True)
    with open(os.path.join(ROOT, "benchmark", "configs", f"{args.config}.json")) as f:
        config = dict(json.load(f), num_hidden_layers=10, name=f"{args.config}.10l")
    if args.held:
        config["num_local_experts"] = args.held
    cache = os.path.join(ROOT, "benchmark", ".cache")  # where a run's own files go; git ignores it
    os.makedirs(cache, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=cache) as tmp:
        model = modelfile.write_model(os.path.join(tmp, "witness.m"), config, args.prompt + args.steps, args.seed)
        results = run(config, model, args.variants.split(","), args.rows, args.prompt, args.steps,
                      args.every, args.seed)
    wrong = 0
    for r in results:
        print(json.dumps(r), flush=True)
        wrong += r["ok"] != (r["variant"] == "served")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
