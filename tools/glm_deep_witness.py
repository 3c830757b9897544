#!/usr/bin/env python3
"""The latent cache's deep context held to the reference by LOGITS, on the chip (PR 43).

The benchmark's ``correct`` compares greedy tokens, and on seeded weights a
token rule is nearly blind to a fault that touches only far positions: the
family's reference with every key past position 2048 never read, read as
zeros or read from the first scan chunk again puts its logits 4e-2 to 4.5e-1
of max|logit| off at EVERY compared position and still reads 0 of 9 positions
over the cell's miss line (PERF.md section 6, PR 43). This script
compares what the served path computes, not what it samples: the cell's
configuration at its published widths, weights from ``--seed``, the engine and
the scheduler the server builds (slab, pool, pieces of at most 256 rows, the
absorbed scan in chunks of 2048 positions), ``--rows`` prompts of random
tokens prefilled in segments of ``--segment`` tokens, so that a logits row
comes back every 64 positions up to ``--prompt``: half of them past the first
scan chunk, the odd rows' first ``--deep`` + 64 tokens in one prefill (pieces
of 256 rows), the rest in pieces of 64. The family's float32 reference (a
child on the host's CPU, one pass a prompt) scores the same contexts.

A checkpoint is compared where its routing gap is at least the cell's
``router_tie``, as the harness does it. Per variant the line says, for the
checkpoints at and past ``--deep`` and for those before: the logit error
(max |engine - reference| over max|logit|) at the median and the worst, the
share inside ``logit_tol``, and how many of the engine's greedy tokens lie more
than 1e-2 / 3e-2 under the reference's best. ``ok``: the deep checkpoints'
median error is at most twice that of the checkpoints BEFORE (which no far
position can touch: they read the engine's own rounding at this width and
vocabulary) and at most ``DEEP_TOL``. The two readings that limit lies
between (PERF.md section 6, PR 43): the served engine on the chip 1.54e-2
past position 2048 and 1.59e-2 before it, with far keys never read 4.5e-2;
the planted faults' medians in the reference at the published widths 4.6e-2
(zeros), 5.2e-2 (the wrong chunk) and 1.2e-1 (never read).

Variants (``--variants``, each builds its own programs):
  served   the engine as the server runs it
  f32_up   ``w_uk`` / ``w_uv`` (the up-projection sliced for absorption) in
           float32 from the file instead of bfloat16
  f32_all  that, and the cache and the absorbed queries in float32
  drop | zero | dup   the served engine with a fault PLANTED in the scan for
           positions at and past ``--deep`` only (never read; read as zeros;
           the first chunk read again): has to come out NOT ok. (Far rows that
           each read their NEIGHBOUR move the logits by 1.2e-3, under the
           engine's own rounding: attention over thousands of seeded positions
           is diffuse, and nothing sees that fault but a test of the cache's
           bytes.)

    python3 tools/glm_deep_witness.py [--workload glm-4.7-flash.doc_sessions] [--seed N]

Last line of stdout: one JSON object; exit code 1 unless every sound variant
is ok and every planted one is not.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.getcwd()
sys.path.insert(0, ROOT)

SOUND = ("served", "f32_up", "f32_all")
PLANTED = ("drop", "zero", "dup")
DEEP_TOL = 3e-2


def reference_child(argv: list[str]) -> int:
    """``--reference-child <bench_dir> <config.json> <model> <tokens.npy> <positions.npy> <out.npz>``:
    the family's reference over the prompts, logits and routing gaps at the
    checkpoints (JAX_PLATFORMS=cpu: the chip is the parent's)."""
    bench_dir, config_path, model, tokens_path, positions_path, out_path = argv
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) > 4:
        os.sched_setaffinity(0, cores[2:])
    import numpy as np

    from benchmark import families
    from benchmark.reference.qfile import QFile

    with open(config_path) as f:
        family = families.load(json.load(f), "reference", bench_dir)
    tokens, positions = np.load(tokens_path), np.load(positions_path)
    t0, logits, gaps = time.monotonic(), [], []
    for row in tokens:  # a prompt a pass: one prompt's attention fits the host beside the engine's load
        g: list = []
        logits.append(family.forward(QFile(model, family), row[None], positions, g)[0])
        gaps.append(np.min(g, axis=0)[0] if g else np.full(len(positions), np.inf))
    np.savez(out_path, logits=np.stack(logits), gaps=np.stack(gaps), seconds=time.monotonic() - t0)
    return 0


def plant(fault: str, deep: int):
    """``ops.attention.latent_attention_scan`` with ``fault`` in, for the
    positions at and past ``deep`` only; returns the function to restore."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops import attention as attn_ops

    real = attn_ops.latent_attention_scan

    def faulty(q, q_pos, latents, chunk, scale):
        far = (jnp.arange(latents.shape[2]) >= deep)[None, None, :]
        if fault == "drop":
            return real(q, jnp.minimum(q_pos, deep - 1), latents, chunk, scale)
        if fault == "zero":
            latents = jnp.where(far, jnp.zeros_like(latents), latents)
        elif fault == "dup":
            latents = jnp.where(far, jnp.roll(latents, deep, axis=2), latents)
        else:
            raise ValueError(fault)
        return real(q, q_pos, latents, chunk, scale)

    attn_ops.latent_attention_scan = faulty
    return real


def float32_up_projections(engine, model: str) -> dict:
    """The engine's params with every latent layer's ``w_uk`` / ``w_uv`` sliced
    from the file's ``kv_b`` in float32 (``engine/weights.py`` keeps them in
    the matmul dtype)."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_tpu.formats.model_file import ModelFileReader

    cfg, reader = engine.cfg, ModelFileReader(model)
    H, nope, v = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    layers = []
    for l, lp in enumerate(engine.params["layers"]):
        kv_b = np.asarray(reader.tensor(f"layers.{l}.kv_b"), np.float32).reshape(H, nope + v, cfg.kv_lora_rank)
        layers.append({**lp, "w_uk": jnp.asarray(np.ascontiguousarray(kv_b[:, :nope])),
                       "w_uv": jnp.asarray(np.ascontiguousarray(kv_b[:, nope:].transpose(0, 2, 1)))})
    return {**engine.params, "layers": layers}


def engine_logits(engine, tokens, segment: int, deep: int, pages: int):
    """[rows, checkpoints, vocab] float32: each prompt prefilled into its own
    slab row a segment at a time, the last position's logits of every segment;
    an odd row's first ``deep + segment`` tokens go in ONE prefill (pieces of
    the scheduler's 256 rows)."""
    import numpy as np

    from distributed_llama_tpu.engine.batch import BatchScheduler

    sched = BatchScheduler(engine, n_rows=len(tokens), chunk=32, prefix_cache=True, kv_pages=pages,
                           page_size=64, prefill_chunk=256)
    out = []
    for r, row in enumerate(tokens):
        stream, got, start = sched.new_stream(), [], 0
        if r % 2:
            start = deep + segment
            got += [None] * (start // segment - 1) + [stream.prefill(row[:start])]
        for s in range(start, len(row), segment):
            got.append(stream.prefill(row[s:s + segment]))
        out.append(got)
    vocab = next(g for g in out[0] if g is not None).shape[-1]
    sched.close()
    return np.stack([np.stack([np.full(vocab, np.nan, np.float32) if g is None else np.asarray(g, np.float32)
                               for g in got]) for got in out])


def reading(got, want, gaps, positions, deep: int, tie: float, tol: float) -> dict:
    """One variant's numbers over the compared checkpoints, deep and before."""
    import numpy as np

    out = {}
    for name, where in (("deep", positions >= deep), ("before", positions < deep)):
        keep = (gaps >= tie) & where[None, :] & ~np.isnan(got[..., 0])
        g, w = got[keep], want[keep]
        if not len(g):
            out[name] = {"compared": 0}
            continue
        scale = np.abs(w).max(-1)
        err = np.abs(g - w).max(-1) / scale
        deficit = (w.max(-1) - np.take_along_axis(w, g.argmax(-1)[:, None], -1)[:, 0]) / scale
        out[name] = {"compared": int(keep.sum()), "of": int((where[None, :] & ~np.isnan(got[..., 0])).sum()),
                     "err_median": float(np.median(err)), "err_worst": float(err.max()),
                     "err_least": float(err.min()), "inside_tol": float(np.mean(err <= tol)),
                     "over_1e-2": int((deficit > 1e-2).sum()), "over_3e-2": int((deficit > 3e-2).sum()),
                     "deficit_worst": float(deficit.max())}
    d, b = out["deep"], out["before"]
    out["ok"] = bool(d["compared"] and b["compared"] and d["err_median"] <= min(2 * b["err_median"], DEEP_TOL))
    return out


def main(argv: list[str]) -> int:
    if argv[:1] == ["--reference-child"]:
        return reference_child(argv[1:])
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="glm-4.7-flash.doc_sessions")
    ap.add_argument("--seed", type=int, default=2147484501)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--variants", default="served,f32_up,drop")
    ap.add_argument("--prompt", type=int, help="tokens a prompt (the cell's long_probe_prompt + probe_tokens)")
    ap.add_argument("--segment", type=int, default=64)
    ap.add_argument("--deep", type=int, default=2048, help="where the deep context starts (a scan chunk)")
    ap.add_argument("--pages", type=int, default=256)
    ap.add_argument("--platform", default="tpu", help="the platform it has to run on (cpu: a rehearsal)")
    args = ap.parse_args(argv)
    import numpy as np

    from benchmark.harness import cell as cell_mod
    from benchmark.harness import modelfile

    cell = cell_mod.Cell(ROOT, args.workload)
    check, config = cell.check, cell.config
    context = cell.flag("--max-seq-len", 0)
    n = args.prompt or check["long_probe_prompt"] + check["probe_tokens"]
    n -= n % args.segment
    variants = args.variants.split(",")
    unknown = sorted(set(variants) - set(SOUND) - set(PLANTED))
    if unknown or not args.deep < n <= context or args.deep % args.segment:
        ap.error(f"variants {unknown}, or no room past --deep {args.deep} in a prompt of {n} of {context}")
    cache = os.path.join(cell.dir, ".cache", "deep_witness")
    os.makedirs(cache, exist_ok=True)
    model, _ = modelfile.write_artifacts(config, args.seed, cache, context)
    rng = np.random.default_rng(args.seed)
    tokens = rng.integers(3, config["vocab_size"], (args.rows, n)).astype(np.int32)
    tokens[:, 0] = 1
    positions = np.arange(args.segment - 1, n, args.segment)
    paths = {k: os.path.join(cache, f"{k}.{ext}") for k, ext in
             (("tokens", "npy"), ("positions", "npy"), ("reference", "npz"))}
    np.save(paths["tokens"], tokens)
    np.save(paths["positions"], positions)
    # the reference first, beside the engine's load and builds; the chip is this process's
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--reference-child", cell.dir, cell.config_path, model,
         paths["tokens"], paths["positions"], paths["reference"]],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    try:
        import jax
        import jax.numpy as jnp

        from distributed_llama_tpu.engine import InferenceEngine

        dev = jax.devices()[0]
        print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}), flush=True)
        if dev.platform != args.platform:
            return 3
        engine = InferenceEngine(model, dtype="q40", max_seq_len=context)
        served, bf16 = engine.params, engine.cache_dtype
        logits, seconds = {}, {}
        for name in variants:
            t0 = time.monotonic()
            engine.params = served if name in ("served", *PLANTED) else float32_up_projections(engine, model)
            engine.cache_dtype = jnp.float32 if name == "f32_all" else bf16
            real = plant(name, args.deep) if name in PLANTED else None
            if real is not None:
                jax.clear_caches()  # the programs were traced with the sound scan
            try:
                logits[name] = engine_logits(engine, tokens, args.segment, args.deep, args.pages)
            finally:
                if real is not None:
                    from distributed_llama_tpu.ops import attention as attn_ops

                    attn_ops.latent_attention_scan = real
                    jax.clear_caches()
            seconds[name] = round(time.monotonic() - t0, 1)
            print(f"[witness] {name}: logits of {logits[name].shape[:2]} checkpoints in {seconds[name]} s",
                  flush=True)
        if child.wait() != 0:
            print(f"[witness] the reference child exited with code {child.returncode}", file=sys.stderr)
            return 1
    finally:
        if child.poll() is None:
            child.kill()
    ref = np.load(paths["reference"])
    result = {"workload": args.workload, "seed": args.seed, "rows": args.rows, "prompt": n,
              "checkpoints": len(positions), "deep": args.deep, "router_tie": check["router_tie"],
              "logit_tol": check["logit_tol"], "reference_s": round(float(ref["seconds"]), 1),
              "seconds": seconds, "variants": {}}
    for name in variants:
        r = reading(logits[name], ref["logits"], ref["gaps"], positions, args.deep, check["router_tie"],
                    check["logit_tol"])
        result["variants"][name] = r
        print(f"[witness] {name}: {'ok' if r['ok'] else 'NOT ok'}: {json.dumps(r)}", flush=True)
    result["ok"] = all(result["variants"][v]["ok"] == (v in SOUND) for v in variants)
    out_dir = os.path.join(ROOT, "chiprun_out")
    if os.path.isdir(out_dir):
        with open(os.path.join(out_dir, "glm_deep_witness.json"), "w") as f:
            json.dump(result, f)
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
