#!/usr/bin/env python3
"""The control behind a configuration's ``check`` block: the plain reference
computed in a LOWER precision than the configuration states, judged by the
float32 reference under the cell's own rule (``cell.judge_probes`` with
``cell.load_check`` of the configuration). A limit of the rule lies between
two readings: what a faithful engine gives (the harness prints it in every
run) and what this prints for the nearest precision below, which has to come
out as not correct. Host CPU only; at a published width it takes minutes.

    JAX_PLATFORMS=cpu python3 benchmark/tools/precision_control.py <config | cell> \
        [--probes 4] [--seed 7] [--variants q80,three_mantissa_bits,...]

A cell (``benchmark/workloads/<cell>.json``) is controlled under ITS rule: its
configuration's check block with the cell's own over it, long probes included.

Variants (``VARIANTS``): the input of every Q40 matmul rounded to Q80 (the
engine's own rounding: has to PASS), to bfloat16, to three mantissa bits
(float8 e4m3's, the nearest format below Q80: has to FAIL); and, where the
family's reference hands a recurrent state from step to step (``carry``),
that state held in bfloat16 or float8.

Probes are ``--probes`` rows of ``probe_prompt + probe_tokens`` random
tokens (the last ``long_probes`` of them ``long_probe_prompt + probe_tokens``),
teacher-forced: at every answered position the variant's greedy token is
scored against the float32 reference's logits for the same context.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def q80(x):
    import jax.numpy as jnp

    blocks = x.reshape(x.shape[:-1] + (x.shape[-1] // 32, 32))
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=-1, keepdims=True), 1e-30) / 127.0
    return (jnp.round(blocks / scale) * scale).reshape(x.shape)


def three_mantissa_bits(x):
    import jax.numpy as jnp

    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16) / 16, e)


def bfloat16(x):
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(jnp.float32)


# name -> (what is rounded: "matmul" = the input of every Q40 matmul, "carry" = the recurrent
# state between steps; the rounding)
VARIANTS = {
    "q80": ("matmul", q80),
    "bfloat16": ("matmul", bfloat16),
    "three_mantissa_bits": ("matmul", three_mantissa_bits),
    "state_bfloat16": ("carry", bfloat16),
    "state_three_mantissa_bits": ("carry", three_mantissa_bits),
}


@contextlib.contextmanager
def rounded(ref, name: str):
    """The family's reference ``ref`` with variant ``name``'s rounding in."""
    import jax

    what, rounding = VARIANTS[name]
    plain = getattr(ref, what)
    patched = (lambda x, raw: plain(rounding(x), raw)) if what == "matmul" else (lambda S: rounding(plain(S)))
    setattr(ref, what, patched)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(ref, what, plain)
        jax.clear_caches()


def control(config: dict, model: str, check: dict, seed: int, names: list[str]) -> dict:
    """{variant: (ok, note)} for the seeded ``model`` file of ``config``."""
    import numpy as np

    from benchmark import families
    from benchmark.harness import cell
    from benchmark.reference.probe_child import score
    from benchmark.reference.qfile import QFile

    ref = families.load(config, "reference")
    qf = QFile(model, ref)
    n_ans, n_long = check["probe_tokens"], check["long_probes"]
    rng = np.random.default_rng(seed)
    # the short probes, then the long ones: a pass of the reference for each prompt length
    passes = []
    for count, n_prompt in ((check["probes"] - n_long, check["probe_prompt"]), (n_long, check["long_probe_prompt"])):
        if count:
            tokens = rng.integers(3, config["vocab_size"], (count, n_prompt + n_ans)).astype(np.int32)
            tokens[:, 0] = 1
            positions = np.arange(n_prompt - 1, n_prompt - 1 + n_ans)
            gaps: list = []
            passes.append((tokens, positions, ref.forward(qf, tokens, positions, gaps), gaps))
    out = {}
    for name in names:
        if not hasattr(ref, VARIANTS[name][0]):
            out[name] = (None, f"the reference of family {config['family']!r} has no {VARIANTS[name][0]!r}")
            continue
        rows, off, long_rows = [], 0.0, []
        for tokens, positions, want, gaps in passes:
            with rounded(ref, name):
                got = ref.forward(qf, tokens, positions)
            scored = [r for probe in score(want, got.argmax(-1).tolist(), gaps or None) for r in probe]
            rows += scored
            long_rows = scored if n_long and positions[0] == check["long_probe_prompt"] - 1 else long_rows
            off = max(off, float(np.abs(got - want).max() / np.abs(want).max()))
        ok, note = cell.judge_probes(rows, check)
        if long_rows:
            deficits = [r["deficit"] for r in long_rows]
            note += (f"; the {len(deficits)} positions after a prompt of {check['long_probe_prompt']} tokens: worst "
                     f"{max(deficits):.2e}, {sum(d > check['miss_tol'] for d in deficits)} over the miss line")
        out[name] = (ok, f"{note}; logits off by {off:.2e} of max|logit|")
    return out


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--probes", type=int, default=4)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--model", help="a .m file of this configuration already written (else one is "
                                    "written from --seed into a temporary directory)")
    args = ap.parse_args(argv)
    import jax

    from benchmark.harness import cell, modelfile

    if jax.devices()[0].platform != "cpu":
        print("run with JAX_PLATFORMS=cpu", file=sys.stderr)
        return 3
    if os.path.isfile(os.path.join(ROOT, "benchmark", "workloads", f"{args.config}.json")):
        found = cell.Cell(ROOT, args.config)  # a cell: its configuration, under the cell's own rule
        config, check = found.config, dict(found.check)
    else:
        with open(os.path.join(ROOT, "benchmark", "configs", f"{args.config}.json")) as f:
            config = json.load(f)
        check = cell.load_check(config=config)
    check["probes"] = max(args.probes, check["long_probes"])
    check["min_compared"] = int(args.probes * check["probe_tokens"] * check["min_compared_share"])
    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as tmp:
        model = args.model or modelfile.write_model(
            os.path.join(tmp, "control.m"), config, config.get("assumed", {}).get("context_served", 2048),
            args.seed)
        for name, (ok, note) in control(config, model, check, args.seed, args.variants.split(",")).items():
            print(f"{name}: {'correct' if ok else 'NOT correct' if ok is not None else 'not run'}: {note}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
