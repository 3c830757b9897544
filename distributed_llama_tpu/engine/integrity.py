"""Integrity verification for silent data corruption (ISSUE 10).

The failure-domain ladder (docs/ROBUSTNESS.md) catches *loud* failures —
raises, hangs, NaN logits surfacing as out-of-vocab tokens. A bit-flipped
weight or activation that yields plausible-but-WRONG tokens sails through
every one of those checks: the fleet-scale failure mode of "Cores that
don't count" (Hochschild et al., HotOS '21) and Meta's "Silent Data
Corruptions at Scale" (Dixit et al., 2021). This module supplies the three
detection primitives the serving layer composes into canaries, shadow
votes and restart verification (server/replicas.py):

* **Logit fingerprints** — a per-row FNV-1a fold over each decode step's
  full-vocab logit argmax and sampled token, carried through the batched
  decode scan ON DEVICE and fetched as two extra int32 rows packed into
  the chunk's token array (``pack_chunk_outputs``) — the fetch count per
  chunk (one host sync) is unchanged. Since
  ISSUE 13 the fold shares the scan with the FUSED device sampler: the
  packed bundle's int32 rows are the only bytes a chunk ever sends
  host-ward, and the fold keeps its order-statistic stability across
  bucket shapes (argmax, never a bitwise sum) while the sampler's coins
  come from the stateless counter PRNG beside it. A pinned greedy prompt
  then has ONE expected (tokens, fingerprint) pair per weights+config,
  which is what the canary compares. The fold also carries a per-row
  finiteness flag, closing the sampled-path hole: NaN logits pushed
  through a softmax can launder into a perfectly in-vocab token id that
  the fetch-side vocab check cannot see.
* **Weight checksums** — an order-independent wrapping uint32 word sum
  per leaf (floats bit-cast, so a single mantissa-bit flip ALWAYS moves
  the sum — a float32 accumulation would round it away), folded through
  CRC-32 on the host. Computed once per engine load
  (``InferenceEngine.weights_checksum``) and re-verified by the replica
  supervisor before a rebuilt replica re-enters placement.
* **Deterministic corruption** (``corrupt_params``) — the fault the
  ``engine.sdc`` site injects (``kind=corrupt``): a seeded weight slice
  scaled into finite-but-wrong values. Not NaN on purpose; the point is
  producing outputs every pre-ISSUE-10 check calls healthy.

Everything here is stateless and backend-agnostic; policy (canary
cadence, suspicion walks, failover) lives with the replica pool.
"""

from __future__ import annotations

import random
import zlib

import jax
import jax.numpy as jnp
import numpy as np

# FNV-1a constants: cheap, well-distributed for short folds, and trivially
# reproducible from any other runtime that wants to cross-check a stream
FP_BASIS = 2166136261
FP_PRIME = 16777619

# the reserved internal tenant canary/shadow probes bill to: excluded from
# fair admission and from per-tenant fairness metrics (client-supplied
# tenant names may not start with "_" — server/api.py validates)
CANARY_TENANT = "_integrity"

# the reserved internal tenant rollout certification probes bill to
# (ISSUE 18): same contract as the canary tenant — direct lane claim, no
# admission permit, never a client identity
ROLLOUT_TENANT = "_rollout"

RESERVED_TENANTS = (CANARY_TENANT, ROLLOUT_TENANT)


# ----------------------------------------------------------------------
# Device-side logit fingerprints (ride the batched decode scan)
# ----------------------------------------------------------------------


def fingerprint_init(b: int):
    """Per-row fold state for one chunk: (hash uint32 [b], finite bool [b])."""
    return jnp.full((b,), FP_BASIS, jnp.uint32), jnp.ones((b,), bool)


def fingerprint_fold(h, ok, logits, tokens):
    """Fold one decode step into the chunk fingerprint (inside the scan).

    ``logits`` [B, vocab] f32, ``tokens`` [B] int32 (the step's sampled
    ids). Two per-row reductions ride the step:

    * ``argmax`` — the hashed word. Deliberately an ORDER STATISTIC, not
      a bitwise accumulation: XLA compiles a separate program per row
      bucket, and a row's logit BITS drift by ulps across bucket shapes
      (measured on CPU — a bucket-1 and a bucket-2 dispatch of the same
      row disagree in the last bits of a full-vocab sum), so a
      sum-of-logits fingerprint would make the canary golden flap with
      co-batched traffic. The argmax survives ulp drift while still
      witnessing model-state corruption independently of the SAMPLED
      token (a temperature>0 row's draw hides argmax drift; this
      doesn't). Folding the sampled token too makes the chunk word a
      compact (argmax, token) transcript.
    * ``sum`` — the FINITENESS witness only: IEEE propagation means any
      NaN poisons it and any Inf survives or (meeting its opposite)
      becomes NaN, so ``isfinite(sum)`` is a whole-row non-finite
      detector for the price of one add-reduce."""
    finite = jnp.isfinite(jnp.sum(logits.astype(jnp.float32), axis=-1))
    arg = jnp.argmax(logits, axis=-1).astype(jnp.uint32)
    h = (h * jnp.uint32(FP_PRIME)) ^ arg
    h = (h * jnp.uint32(FP_PRIME)) ^ tokens.astype(jnp.uint32)
    return h, ok & finite


def pack_chunk_outputs(tokens, h, ok, *extra):
    """Append the fingerprint + finiteness rows to a chunk's token array:
    [n_steps, B] int32 → [n_steps + 2, B] int32, so the whole bundle still
    crosses the host in ONE fetch (row ``n_steps`` = fingerprint bits, row
    ``n_steps + 1`` = finite flag). ``extra``: further int32 [B] rows a
    program returns with its tokens (:func:`chunk_extra_rows` reads them)."""
    fp_row = jax.lax.bitcast_convert_type(h, jnp.int32)[None, :]
    ok_row = ok.astype(jnp.int32)[None, :]
    rows = [tokens.astype(jnp.int32), fp_row, ok_row]
    rows += [row.astype(jnp.int32)[None, :] for row in extra]
    return jnp.concatenate(rows, axis=0)


def chunk_extra_rows(arr: np.ndarray, n_steps: int) -> np.ndarray:
    """The rows a program packed past the finite flag, [n, B] (n may be 0)."""
    return np.asarray(arr)[n_steps + 2:]


def split_chunk_outputs(arr: np.ndarray, n_steps: int):
    """Host-side inverse of :func:`pack_chunk_outputs` on the fetched
    array: returns ``(tokens [n_steps, B], fingerprints uint32 [B],
    finite bool [B])``."""
    arr = np.asarray(arr)
    toks = arr[:n_steps]
    fp = (arr[n_steps].astype(np.int64) & 0xFFFFFFFF).astype(np.uint32)
    finite = arr[n_steps + 1] != 0
    return toks, fp, finite


def fold_run_fingerprint(run: int, chunk_fp: int) -> int:
    """Host-side fold of one chunk's fingerprint into a stream-lifetime
    fingerprint (same FNV-1a step, so a stream's value is a pure function
    of its chunk sequence). Streams start from :data:`FP_BASIS`."""
    return ((int(run) * FP_PRIME) ^ int(chunk_fp)) & 0xFFFFFFFF


# ----------------------------------------------------------------------
# Weight checksums (load-time record, restart-time verification)
# ----------------------------------------------------------------------

_UINT_FOR_SIZE = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint32}


def _leaf_word_sum(leaf):
    """Wrapping uint32 sum of a leaf's underlying WORDS: floats (incl.
    bf16) are bit-cast to the same-width unsigned type first, so the sum
    is exact modulo 2**32 — any single flipped bit changes it, which a
    rounding float accumulation cannot promise."""
    x = jnp.asarray(leaf)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    if jnp.issubdtype(x.dtype, jnp.floating) or jnp.issubdtype(
        x.dtype, jnp.signedinteger
    ):
        x = jax.lax.bitcast_convert_type(
            x, _UINT_FOR_SIZE.get(x.dtype.itemsize, jnp.uint32)
        )
    return jnp.sum(x.astype(jnp.uint32))


def params_checksum(params) -> str:
    """Deterministic hex checksum of a whole params pytree: per-leaf
    device-side word sums (one HBM pass over the weights — load-cost
    class, done once per engine build), one stacked fetch, CRC-32 fold on
    the host. Identical weights → identical checksum on every backend;
    the replica pool records replica 0's value as the pool reference and
    the restart supervisor verifies every rebuild against it."""
    sums = [
        _leaf_word_sum(leaf)
        for leaf in jax.tree_util.tree_leaves(params)
        if hasattr(leaf, "dtype")
    ]
    if not sums:
        return "00000000"
    vec = np.asarray(jnp.stack(sums), dtype=np.uint32)
    return f"{zlib.crc32(vec.tobytes()) & 0xFFFFFFFF:08x}"


class ChecksumMismatch(RuntimeError):
    """A rebuilt replica's weight checksum disagrees with the pool
    reference: the rebuild itself is corrupt (bad host RAM, a torn read,
    the same flaky core) and must NOT re-enter placement — the restart
    loop treats this like any other failed build attempt and retries
    under backoff (server/replicas.py)."""


# ----------------------------------------------------------------------
# Deterministic corruption (the engine.sdc fault's payload)
# ----------------------------------------------------------------------


def corrupt_params(params, seed: int = 0, scale: float = -1.7319):
    """Perturb one weight slice into finite-but-wrong values and return
    the new pytree (functional — the caller swaps ``engine.params``).

    The target leaf is drawn from the seeded RNG over floating-point
    leaves, preferring NORMALIZATION weights (rms/norm paths): they scale
    every token's residual stream, so the damage provably reaches the
    canary's pinned prompt — whereas a slice of one attention projection
    (let alone an embedding row the prompt never touches) can leave every
    argmax standing, i.e. corruption the injector itself made
    undetectable, which is a useless chaos stand-in. Falls back to
    non-embedding leaves, then to anything float. Returns
    ``(new_params, description)``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    cand = [
        (i, path)
        for i, (path, leaf) in enumerate(flat)
        if hasattr(leaf, "dtype")
        and jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)
        and getattr(leaf, "size", 0) > 0
    ]
    if not cand:
        raise ValueError("no floating-point weight leaf to corrupt")
    norms = [
        c for c in cand
        if any(k in str(c[1]).lower() for k in ("rms", "norm"))
    ]
    non_embed = [c for c in cand if "embed" not in str(c[1]).lower()]
    pool = norms or non_embed or cand
    rng = random.Random(seed)
    target, path = pool[rng.randrange(len(pool))]
    leaves = [leaf for _, leaf in flat]
    leaf = jnp.asarray(leaves[target])
    vec = leaf.reshape(-1)
    n = max(1, min(256, vec.shape[0]))
    bad = vec[:n].astype(jnp.float32) * jnp.float32(scale) + jnp.float32(0.125)
    leaves[target] = vec.at[:n].set(bad.astype(leaf.dtype)).reshape(leaf.shape)
    desc = f"weight slice [{n}] of {jax.tree_util.keystr(path)}"
    return treedef.unflatten(leaves), desc
