"""Device time of the parts of ONE layer's selected attention in a decode step
of ``glm-5.doc_sessions`` (8 rows of 16384 positions, 64 heads over latent rows
of 576 values, 32 index heads over index keys of 128, the 2048 best), and of
the two forms the selected attention can take (PERF.md section 6, PR 53):

    chiprun --timeout 1200 -- python3 tools/dsa_select_sweep.py [context ...]

``index``: the indexer's scores over the visible index keys. ``select``: the
served selection (the k-th largest score's bits found one by one, a mask out)
beside ``top_k`` (``jax.lax.top_k``: the indices a gather needs). Then
attention over the selection as served, MASKED (the latent scan over every
visible chunk, the softmax over the marked positions), beside GATHERED (the
2048 selected rows taken out of the positions-minor leaf by ``top_k``'s
indices, then the same scan over them alone) and the DENSE scan a model
without an indexer runs. Each part runs REPS times chained inside one program
(the next input depends on the last output), five times; the median over REPS
is one launch. One JSON line a context (every row at that position) on stdout
and appended to ``chiprun_out/dsa_select_sweep.jsonl``: us a launch of each
part (``gathered_us`` is the gather AND the scan over what it took;
``masked_us`` at context 2048 is that scan alone), and the largest difference
between the masked and the gathered outputs.
"""

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from distributed_llama_tpu.ops import attention as att  # noqa: E402

B, H, D, I, J, S, K, CHUNK, REPS = 8, 64, 576, 128, 32, 16384, 2048, 2048, 20
SCALE = 256 ** -0.5


def chained(fn, reps=REPS):
    """``fn(x, *rest) -> y`` run ``reps`` times, each input nudged by the last
    output so that no launch can be dropped or overlapped."""
    def run(x, *rest):
        def body(_, carry):
            x, acc = carry
            y = fn(x, *rest)
            first = jax.tree.leaves(y)[0]
            nudge = (jnp.sum(first.astype(jnp.float32)) * 1e-30).astype(x.dtype)
            return x + nudge, acc + nudge.astype(jnp.float32)
        return jax.lax.fori_loop(0, reps, body, (x, jnp.float32(0)))[1]
    return jax.jit(run)


def timed(fn, *args):
    run = chained(fn)
    run(*args).block_until_ready()
    laps = []
    for _ in range(5):
        t = time.perf_counter()
        run(*args).block_until_ready()
        laps.append((time.perf_counter() - t) / REPS * 1e6)
    return statistics.median(laps)


def gathered_attention(q, idx, valid, latents):
    rows = jnp.take_along_axis(latents[:B], idx[:, None, :], axis=2)  # [B, D, K]
    # the scan over the gathered rows: every one is seen, the invalid ones (a row short of K) masked
    mix, _ = att.latent_attention_scan(q, jnp.full((B, H), K - 1), rows, K, SCALE, selected=valid[:, None, :])
    return mix


def main(contexts):
    rng = np.random.default_rng(0)
    bf = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    latents, keys = bf(B, D, S), bf(B, I, S)
    q, q_idx, w_idx = f32(B, H, D) * 0.2, f32(B, 1, J, I), f32(B, 1, J)
    os.makedirs("chiprun_out", exist_ok=True)
    for context in contexts:
        pos = jnp.full((B,), context - 1, jnp.int32)
        q_pos = jnp.broadcast_to(pos[:, None], (B, H))
        scores, _ = jax.jit(att.dsa_index_scores, static_argnums=4)(q_idx, w_idx, pos[:, None], keys, CHUNK)
        mask = jax.jit(att.dsa_select, static_argnums=1)(scores, K) & jnp.isfinite(scores)  # as dsa_selection
        vals, idx = jax.lax.top_k(scores[:, 0], K)
        valid = jnp.isfinite(vals)
        line = {"context": context, "selected": int(mask.sum()) // B, "device": jax.devices()[0].device_kind}
        line["index_us"] = timed(lambda x: att.dsa_index_scores(x, w_idx, pos[:, None], keys, CHUNK)[0], q_idx)
        line["select_us"] = timed(lambda x: att.dsa_select(x, K), scores)
        line["top_k_us"] = timed(lambda x: jax.lax.top_k(x[:, 0], K)[1], scores)
        line["masked_us"] = timed(
            lambda x: att.latent_attention_scan(x, q_pos, latents, CHUNK, SCALE, selected=mask)[0], q)
        line["dense_us"] = timed(lambda x: att.latent_attention_scan(x, q_pos, latents, CHUNK, SCALE)[0], q)
        line["gathered_us"] = timed(lambda x: gathered_attention(x, idx, valid, latents), q)
        a = att.latent_attention_scan(q, q_pos, latents, CHUNK, SCALE, selected=mask)[0]
        b = gathered_attention(q, idx, valid, latents)
        line["masked_vs_gathered_max_diff"] = float(jnp.max(jnp.abs(a - b)))
        # the whole of a layer's selected attention, either way (selection included)
        line["served_us"] = line["index_us"] + line["select_us"] + line["masked_us"]
        line["by_gather_us"] = line["index_us"] + line["top_k_us"] + line["gathered_us"]
        print(json.dumps(line), flush=True)
        with open("chiprun_out/dsa_select_sweep.jsonl", "a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:]] or [2048, 6400, 8400])
