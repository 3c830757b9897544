"""On-device sampling and the fully-jitted decode loop.

The reference samples on the host between every token (reference:
src/apps/dllama/dllama.cpp:45-59), which on TPU costs a host↔device round
trip per token and leaves the device idle while the host samples. Here
the whole decode loop (forward → sample → feed back) runs under one
``lax.scan`` on device; the host dispatches once and fetches N tokens.

Sampling is FUSED into the scan (ISSUE 13): temperature / top-k / top-p
filtering and the categorical draw run per step on device, drawing coins
from the counter-mode PRNG in :mod:`distributed_llama_tpu.prng`. The coin
for the token drawn after consuming stream position ``p`` is a pure
function of ``(request seed, p)`` — no sampler state exists, so:

* a stream is bit-identical however the decode is chunked into dispatches;
* PR 8/9's preemption-requeue and failover replays re-draw the exact coins
  on any replica without shipping sampler state (positions are defined by
  token content, not replica state);
* the host ``Sampler``'s counter mode (tokenizer.py) replays the same
  draws from fetched logits — the xorshift host-parity verification mode.

Candidate semantics (shared with the host counter sampler, and the
contract the parity suite asserts): candidates are ordered by descending
temperature-scaled logit (ties broken by lower token id — ``lax.top_k``
order); top-k keeps the first k; top-p keeps the nucleus prefix
(token ``i`` stays while the mass strictly before it is < topp, the
reference's inclusive-crossing rule, src/tokenizer.cpp:334-369); the draw
is inverse-CDF over the kept prefix with one uniform coin. With both
filters off the draw is inverse-CDF in vocab order (no sort — the
multinomial path). All float math is f32. Host parity on the filtered
paths rests on the f32 softmax (max-subtract, exp, full-vocab sum,
divide) and the ≤``TOPP_FAST_K``-element kept-prefix cumsum reducing
identically in numpy and XLA — measured exact on the CPU backend over
thousands of draws, though a denominator or boundary value landing
within 1 ulp of a coin/topp crossing can in principle flip a pick on
another backend. The full-vocab cumsum paths (the multinomial draw and
nuclei wider than the fast-path window) carry the larger version of the
same caveat: XLA's parallel prefix sum may associate differently from a
sequential host cumsum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from distributed_llama_tpu import prng
from distributed_llama_tpu.engine import integrity
from distributed_llama_tpu.models import llama
from distributed_llama_tpu.models.config import LlamaConfig

# width of the sorted-candidate fast path: when the kept prefix (top-k ∧
# nucleus) provably fits in the largest TOPP_FAST_K candidates (virtually
# always for topp <= 0.95 on a trained model), the pick runs on one top_k
# instead of a full-vocab sort; a lax.cond falls back to the full sort
# otherwise, so the result is EXACT either way
TOPP_FAST_K = 128

# vocab floor for the partition-based bare-top-p fallback: the bit-space
# binary searches add ~400 ops to the decode program (a few seconds of XLA
# compile per decode shape) and only beat the full sort where the sort is
# actually expensive — production-width vocabularies (not measured on the
# chip). Below the floor the routing — and therefore the
# compiled program — is byte-identical to the pre-partition one: tiny test
# models must not pay compile time for a path that would LOSE to their
# cheap sort (a fresh multi-second compile mid-serving is exactly what the
# preemption race tests schedule against).
TOPP_PARTITION_MIN_V = 4096


def _keep_count(vals, cum, topp, topk):
    """Kept-prefix width over descending candidates [rows, K]: the
    inclusive-crossing nucleus count (keep candidate i while the mass
    strictly before it < topp) ∧ top-k, clipped to [1, K]. THE keep rule
    of the host/device/spec parity contract — one definition shared by
    the categorical pick and the speculative filtered distribution
    (tokenizer.Sampler._sample_counter mirrors it in numpy)."""
    K = vals.shape[-1]
    topp = jnp.broadcast_to(jnp.asarray(topp, jnp.float32), vals.shape[:-1])
    topk = jnp.broadcast_to(jnp.asarray(topk, jnp.int32), vals.shape[:-1])
    topp_act = (topp > 0.0) & (topp < 1.0)
    n_nuc = jnp.where(
        topp_act, jnp.sum(cum - vals < topp[..., None], axis=-1), K
    )
    n_k = jnp.where(topk > 0, jnp.minimum(topk, K), K)
    return jnp.clip(jnp.minimum(n_nuc, n_k), 1, K)


def _pick_sorted(vals, idxs, coin, topp, topk):
    """Inverse-CDF pick over descending candidates.

    ``vals`` [B, K] candidate probabilities in canonical order (descending
    scaled logit, ties by lower id), ``idxs`` [B, K] their token ids,
    ``coin`` [B] uniforms, ``topp``/``topk`` [B] runtime filters. Keeps
    the prefix ``min(top-k, nucleus)`` (:func:`_keep_count`) and draws
    ``r = coin * kept_mass``; the pick is the first candidate whose
    cumulative mass exceeds ``r`` — exactly the host counter sampler's
    arithmetic, value for value."""
    K = vals.shape[-1]
    cum = jnp.cumsum(vals, axis=-1)
    n_keep = _keep_count(vals, cum, topp, topk)
    total = jnp.take_along_axis(cum, (n_keep - 1)[:, None], axis=-1)[:, 0]
    r = coin * total
    below = jnp.sum(
        (jnp.arange(K)[None, :] < n_keep[:, None]) & (cum <= r[:, None]),
        axis=-1,
    )
    pick = jnp.minimum(below, n_keep - 1)
    return jnp.take_along_axis(idxs, pick[:, None], axis=-1)[:, 0]


def _desc_key(scaled: jax.Array) -> jax.Array:
    """uint32 key monotone INCREASING in the f32 ``scaled`` logit (the
    classic sign-flip bit trick), so value-threshold searches can walk key
    bits instead of sorting: for non-negative floats the IEEE bits are
    already ordered; negative floats order reversed, so flip all their
    bits and set the sign bit on the rest."""
    b = jax.lax.bitcast_convert_type(scaled.astype(jnp.float32), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def _topp_partition_pick(probs, scaled, coin, topp):
    """EXACT bare-top-p pick by partition (threshold) selection — no
    full-vocab sort anywhere (the ROADMAP item 2 follow-up: near-flat
    untrained-model-shaped logits overflow the ``TOPP_FAST_K`` window on
    every step, and the old fallback paid a full-vocab ``top_k``).

    Two 32-step binary searches over the f32 bit-space of the scaled
    logits (each step one masked full-vocab sum — O(V log V_bits) adds vs
    the sort's O(V log V) compare-exchanges, and no [V]-wide data
    movement), both phrased against the canonical candidate order
    (descending scaled logit, ties by lower id — `_keep_count`'s order):

    1. the nucleus boundary VALUE: the largest key ``v`` whose at-or-above
       mass still reaches ``topp`` (elements strictly above ``v`` are all
       kept; ties AT ``v`` keep the id-ascending prefix while the mass
       strictly before each stays < topp — the inclusive-crossing rule);
    2. the PICK value for ``r = coin × kept_mass``: the largest key whose
       strictly-above mass is ≤ r < at-or-above mass; the id-ascending
       cumsum over the (rare) ties at that value resolves the pick, and
       the result clamps to the last kept candidate exactly like
       `_pick_sorted`'s saturating count.

    Parity scope: identical to the full-sort `_pick_sorted` whenever no
    cumulative mass lands within an ulp of a coin/topp crossing — the
    masked sums here and the sorted prefix cumsum associate differently,
    the same (documented) caveat the multinomial path carries.
    Parity-tested against the sort path in tests/test_sampling.py."""
    V = probs.shape[-1]
    keys = _desc_key(scaled)

    def mass_geq(v):
        """Σ probs over candidates with key ≥ v (strictly-above plus ties)."""
        return jnp.sum(jnp.where(keys >= v[:, None], probs, 0.0), axis=-1)

    def bit_search(pred):
        """Per-row largest uint32 v with pred(v) True (pred monotone
        decreasing in v; pred(0) is True by construction)."""
        v = jnp.zeros(probs.shape[0], jnp.uint32)
        for k in range(31, -1, -1):
            cand = v | jnp.uint32(1 << k)
            v = jnp.where(pred(cand), cand, v)
        return v

    def succ(v):
        """v + 1 saturating at the uint32 max (a wrap to 0 would turn
        "strictly above the top key" into "everything")."""
        return jnp.where(v == jnp.uint32(0xFFFFFFFF), v, v + 1)

    topp = jnp.asarray(topp, jnp.float32)
    # 1. boundary value: largest v with mass(key >= v) >= topp. mass_geq is
    # a right-continuous step function constant between achieved key
    # values, so v_b always LANDS on an achieved key — its tie set is
    # non-empty, and (by maximality) the strictly-above mass is < topp, so
    # the FIRST boundary tie is always kept: the kept prefix and the clamp
    # target below are well defined with no empty-set cases.
    v_b = bit_search(lambda v: mass_geq(v) >= topp)
    above_b = mass_geq(succ(v_b))  # mass strictly above the boundary value
    # ties at the boundary keep while (strictly-before mass) < topp; the
    # id-order cumsum runs over the tie set only (rare — one key value)
    tie_b = jnp.where(keys == v_b[:, None], probs, 0.0)
    tiecum_b = jnp.cumsum(tie_b, axis=-1)
    tie_kept = (keys == v_b[:, None]) & (
        above_b[:, None] + (tiecum_b - tie_b) < topp[:, None]
    )
    kept_tie_mass = jnp.max(jnp.where(tie_kept, tiecum_b, 0.0), axis=-1)
    total = above_b + kept_tie_mass  # the kept prefix's mass
    strictly_above = keys > v_b[:, None]
    # the clamp target = the LAST kept candidate in canonical order: the
    # highest-cumsum kept boundary tie (argmax returns the first of equal
    # cumsums — only reachable through zero-probability ties, which carry
    # no mass either way)
    last_kept = jnp.argmax(
        jnp.where(tie_kept, tiecum_b, -1.0), axis=-1
    ).astype(jnp.int32)

    # 2. the draw: first candidate whose cumulative mass exceeds r
    r = coin * total
    v_p = bit_search(lambda v: mass_geq(v) > r)
    above_p = mass_geq(succ(v_p))
    tie_p = jnp.where(keys == v_p[:, None], probs, 0.0)
    tiecum_p = jnp.cumsum(tie_p, axis=-1)
    hit = (keys == v_p[:, None]) & (above_p[:, None] + tiecum_p > r[:, None])
    found = jnp.any(hit, axis=-1)
    pick = jnp.argmax(hit, axis=-1).astype(jnp.int32)  # first True = lowest id
    pick = jnp.where(found, pick, last_kept)
    # the pick must stay inside the kept prefix (r == total edge): kept
    # means strictly above the boundary, or a kept boundary tie
    in_kept = jnp.take_along_axis(
        strictly_above | tie_kept, pick[:, None], axis=-1
    )[:, 0]
    return jnp.where(in_kept, pick, last_kept)


def fused_pick(probs, scaled, coin, topp, topk, cand=None):
    """The filtered categorical pick on probabilities [B, V] (f32).

    ``scaled`` are the temperature-scaled logits the canonical candidate
    order sorts by (softmax is weakly monotone in f32, so sorting by
    ``scaled`` and reading ``probs`` values keeps host and device on the
    identical candidate sequence). ``cand`` [B, K] optionally supplies the
    candidate ids already reduced over a sharded vocab
    (:func:`sharded_topk_indices` — the tp composition); the full-vocab
    sort fallback still runs on ``probs``/``scaled`` when the kept prefix
    cannot be proven to fit. Rows with both filters inactive draw
    inverse-CDF in vocab order (no sort)."""
    B, V = probs.shape
    K = min(TOPP_FAST_K, V)
    topp_act = (topp > 0.0) & (topp < 1.0)
    topk_act = (topk > 0) & (topk < V)
    filt = topp_act | topk_act

    # multinomial (no filter): vocab-order inverse CDF over the full mass.
    # Behind a cond: the full-vocab cumsum only runs when some row actually
    # has both filters off (never, in the filtered serving default)
    def mult(_):
        cdf = jnp.cumsum(probs, axis=-1)
        r_m = coin * cdf[:, -1]
        return jnp.minimum(
            jnp.sum(cdf <= r_m[:, None], axis=-1), V - 1
        ).astype(jnp.int32)

    idx_m = jax.lax.cond(
        jnp.any(~filt), mult, lambda _: jnp.zeros((B,), jnp.int32), None
    )

    def from_full(_):
        fv, fi = jax.lax.top_k(scaled, V)
        return _pick_sorted(
            jnp.take_along_axis(probs, fi, axis=-1), fi, coin, topp, topk
        )

    if cand is not None:
        idxs = cand
        vals = jnp.take_along_axis(probs, idxs, axis=-1)
    elif K == V:
        fi = jax.lax.top_k(scaled, V)[1]
        idxs, vals = fi, jnp.take_along_axis(probs, fi, axis=-1)
    else:
        idxs = jax.lax.top_k(scaled, K)[1]
        vals = jnp.take_along_axis(probs, idxs, axis=-1)
    if cand is None and K == V:
        tok_f = _pick_sorted(vals, idxs, coin, topp, topk)
    else:
        # the fast window is exact unless a row's kept prefix could extend
        # past it. An overflowing NUCLEUS alone does not force the full
        # sort when an in-window top-k also binds: the nucleus count is
        # then provably > window >= topk, so min(nucleus, topk) = topk and
        # the window has every kept candidate (_pick_sorted's counting
        # saturates at the window, which is exactly right). A BARE top-p
        # whose nucleus overflows (near-flat, untrained-model-shaped
        # logits) takes the exact partition-based selection — no
        # full-vocab sort; only a top-k wider than the window still needs
        # the full order.
        Kw = vals.shape[-1]
        cum_k = jnp.cumsum(vals, axis=-1)
        nucleus_unfit = topp_act & (cum_k[:, -1] < topp)
        wide_topk = topk_act & (topk > Kw)
        narrow_topk = topk_act & (topk <= Kw)
        if V >= TOPP_PARTITION_MIN_V:
            need_part = nucleus_unfit & ~topk_act
            need_sort = wide_topk & (nucleus_unfit | ~topp_act)
        else:
            # small vocab: the sort is cheaper than the partition searches
            # — keep the pre-partition routing (and the identical program)
            need_part = None
            need_sort = (nucleus_unfit & ~narrow_topk) | (~topp_act & wide_topk)
        tok_f = jax.lax.cond(
            jnp.any(need_sort),
            from_full,
            lambda _: _pick_sorted(vals, idxs, coin, topp, topk),
            None,
        )
        if need_part is not None:
            tok_p = jax.lax.cond(
                jnp.any(need_part),
                lambda _: _topp_partition_pick(probs, scaled, coin, topp),
                lambda _: jnp.zeros((B,), jnp.int32),
                None,
            )
            tok_f = jnp.where(need_part, tok_p, tok_f)
    return jnp.where(filt, tok_f, idx_m)


def fused_sample_batched(
    logits,  # [B, vocab]
    seeds,  # uint32 [B] (prng.fold_seed on the host)
    pos,  # int32 [B] — position of the token each row just consumed
    temperature,  # [B]
    topp,  # [B]
    topk,  # int32 [B] (0 = off)
    draw: int = prng.DRAW_SAMPLE,
    cand=None,
) -> jax.Array:
    """Fused temperature/top-k/top-p sampling with the counter PRNG:
    one coin per row keyed ``(seed, pos, draw)``, greedy rows
    (``temperature == 0``) take the exact raw-logits argmax — bit-identical
    to a pure-greedy dispatch, coins never consumed.

    The softmax, the coin and the pick sit in the true arm of ONE
    ``lax.cond`` on "some row samples": a step whose rows all take the
    argmax runs none of them (the top-k over the vocabulary was 19 % of a
    32-row decode step at 100352 logits: PERF.md §6, PR 46). A greedy row
    beside a sampling one still takes ``greedy`` through the arm's
    ``where``, so every row's token is the same in every mix. ``cand``, a
    function of no arguments, gives :func:`fused_pick` its candidate ids
    and is called inside the arm (the tp composition, a collective: every
    shard holds the same temperatures and takes the same arm)."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled(_):
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        probs = jax.nn.softmax(scaled, axis=-1)
        coin = prng.device_coin(seeds, pos, draw)
        tok = fused_pick(
            probs, scaled, coin, topp, topk,
            cand=None if cand is None else cand(),
        )
        return jnp.where(temperature == 0.0, greedy, tok.astype(jnp.int32))

    return jax.lax.cond(
        jnp.any(temperature != 0.0), sampled, lambda _: greedy, None
    )


def sample_token(
    logits, seed, pos, temperature, topp, topk=0
) -> jax.Array:
    """Sample one token id from f32 logits [vocab] with the fused sampler.

    ``temperature``/``topp``/``topk`` may be Python scalars (static under
    jit — a greedy call specializes to a bare argmax) or traced values
    (one compiled program serves every request's sampler settings).
    ``seed`` is the folded uint32 word; ``pos`` the consumed position the
    coin is keyed on."""
    static = not any(
        isinstance(v, jax.Array) for v in (temperature, topp, topk)
    )
    if static and temperature == 0.0:
        return jnp.argmax(logits).astype(jnp.int32)
    return fused_sample_batched(
        logits[None],
        jnp.asarray(seed, jnp.uint32)[None],
        jnp.asarray(pos, jnp.int32)[None],
        jnp.asarray(temperature, jnp.float32)[None],
        jnp.asarray(topp, jnp.float32)[None],
        jnp.asarray(topk, jnp.int32)[None],
    )[0]


def sharded_topk_indices(local_logits, axis_name, k: int):
    """Global top-k token ids composed over a vocab-sharded logits head:
    per-shard ``top_k`` on the LOCAL slice, ONE [B, k]-candidate
    all-gather, and a merge ``top_k`` — the full-vocab sort never runs,
    and only k·tp candidate words ride the collective instead of the
    whole vocabulary. Exactly equal to ``top_k`` over the gathered vocab:
    selection commutes with concatenation, and ties resolve to the lower
    global id on both (shard-major gather order == global id order)."""
    B, vs = local_logits.shape
    kl = min(k, vs)
    lv, li = jax.lax.top_k(local_logits, kl)
    gi = li + jax.lax.axis_index(axis_name) * vs
    av = jax.lax.all_gather(lv, axis_name, axis=1, tiled=True)  # [B, tp*kl]
    ai = jax.lax.all_gather(gi, axis_name, axis=1, tiled=True)
    mi = jax.lax.top_k(av, min(k, av.shape[1]))[1]
    return jnp.take_along_axis(ai, mi, axis=1)


def decode_scan(
    cfg: LlamaConfig,
    params,
    first_token: jax.Array,  # int32 scalar
    cache: jax.Array,
    pos: jax.Array,  # int32 scalar: position of first_token
    seed: jax.Array,  # uint32 scalar (prng.fold_seed on the host)
    n_steps: int,
    temperature,
    topp,
    topk=0,
    axis_name: str | None = None,
):
    """The un-jitted decode scan body: forward → fused sample → feed back.
    Returns (tokens [n_steps], cache). Coins are keyed on the absolute
    position each step consumes, so the token stream is independent of how
    the decode is chunked into dispatches — no sampler state threads
    between calls.

    With ``axis_name`` set it is the per-shard SPMD body for a shard_map'd
    tensor-parallel decode: the forward psums ride the mesh, a
    vocab-sharded logits head is all-gathered, and sampling runs
    identically on every shard (same counter → same token everywhere).
    """

    def step(carry, _):
        token, cache, p = carry
        logits, cache = llama.forward_tokens(
            cfg, params, token[None], cache, p, axis_name=axis_name
        )
        if axis_name is not None and logits.shape[-1] != cfg.vocab_size:
            logits = jax.lax.all_gather(logits, axis_name, axis=1, tiled=True)
        nxt = sample_token(logits[0], seed, p, temperature, topp, topk)
        return (nxt, cache, p + 1), nxt

    (_, cache, _), tokens = jax.lax.scan(
        step,
        (first_token.astype(jnp.int32), cache, pos.astype(jnp.int32)),
        None,
        length=n_steps,
    )
    return tokens, cache


@functools.partial(
    jax.jit, static_argnums=(0, 6, 7, 8, 9), donate_argnums=(3,)
)
def _decode_loop_jit(
    cfg, params, first_token, cache, pos, seed, n_steps, temperature, topp, topk
):
    return decode_scan(
        cfg, params, first_token, cache, pos, seed, n_steps, temperature,
        topp, topk,
    )


def decode_loop(
    cfg: LlamaConfig,
    params,
    first_token: jax.Array,  # int32 scalar
    cache: jax.Array,
    pos: jax.Array,  # int32 scalar: position of first_token
    n_steps: int,
    temperature: float,
    topp: float,
    seed: int = 0,
    topk: int = 0,
):
    """Generate ``n_steps`` tokens autoregressively on device (single chip).

    Returns (tokens [n_steps] int32, final cache). tokens[i] is the token
    sampled after consuming the token at position pos+i. Sampler settings
    are static here (the greedy program specializes to a bare argmax);
    the chunked serving path uses :func:`decode_chunk` instead.
    """
    tokens, cache = _decode_loop_jit(
        cfg, params, jnp.asarray(first_token), cache, jnp.asarray(pos),
        jnp.uint32(prng.fold_seed(seed)), int(n_steps), float(temperature),
        float(topp), int(topk),
    )
    return tokens, cache


def batched_decode_scan(
    cfg: LlamaConfig,
    params,
    first_tokens: jax.Array,  # int32 [B]
    cache,  # slab cache (llama.init_batch_cache)
    pos: jax.Array,  # int32 [B] per-row positions of first_tokens
    active: jax.Array,  # bool [B]
    seeds: jax.Array,  # uint32 [B] per-row folded request seeds
    n_steps: int,
    temperature: jax.Array,  # [B]
    topp: jax.Array,  # [B]
    topk: jax.Array,  # int32 [B]
    axis_name: str | None = None,
    paged=None,  # (pool, tables, matched) — zero-copy prefix aliasing
    fingerprint: bool = True,
):
    """The batched decode body: B sequences step together, each weight
    matrix read once per step. Per row it is the same forward → split →
    sample → feed-back chain as :func:`decode_scan` with the SAME
    position-keyed coins, so a row's token stream is identical to the
    single-stream chunked decode for the same request seed. Inactive rows
    compute garbage (masked out of cache writes and position advances) so
    requests can join/leave between chunks without a recompile. Returns
    (tokens [n_steps, B], cache, fingerprints uint32 [B], finite bool
    [B]) and, for an arch that holds a share of its experts, int32 [B] the
    row's expert choices that fell on a held expert and two int32 [B] (one number
    in every column): the chunk's expert layer-steps that ran every held expert
    over every row of the step, and the rows the held experts' launches
    multiplied, and for one with window
    or EVA layers two more, the cache positions the row's layers read by
    kind (``cfg.kv_read_kinds``) — NOTHING else needs to cross the host per
    chunk: the sampler is
    stateless, so no advanced keys return and no full-vocab logits are
    ever fetched. ``paged``: each row's matched prompt prefix is read from
    the shared page pool through its page table instead of the slab (the
    pool rides the scan as a read-only closure capture — no copy, no
    donation).

    Under a vocab-sharded tp head the candidate top-k is composed over the
    shards (:func:`sharded_topk_indices`), inside the sampler's arm; the
    logits all-gather that the argmax and the fingerprint fold need runs
    every step.

    ``fingerprint`` folds each step's per-row logit argmax + token into an
    FNV-1a hash and a finiteness flag ON DEVICE (engine/integrity.py —
    the SDC detection substrate, ISSUE 10); the sampling itself is
    untouched, so the token stream is bit-identical either way.
    ``fingerprint=False`` skips the fold (same outputs, initial-state
    hashes) — the overhead-bound test compiles both and compares."""

    # an arch that holds a share of its experts: per row, the choices that
    # fell on a held expert, summed over the chunk's steps and layers; and the
    # layer-steps that took the every-row arm (the step's own counts: a bucket
    # that overflowed is not counted as if it fit)
    share = cfg.n_routed_experts > 0
    # an arch with window or EVA layers: per row, the cache positions its
    # layers read, by kind, summed likewise (two more rows of the bundle)
    kinds = cfg.kv_read_kinds

    def step(carry, _):
        tokens, cache_c, p, h, okf, held, whole, kv = carry
        counts, paths = ([], []) if share else (None, None)
        reads = {} if kinds else None
        logits, cache_c = llama.forward_step_batched(
            cfg, params, tokens, cache_c, p, active, axis_name=axis_name,
            paged=paged, held_counts=counts, kv_reads=reads, every_row=paths,
        )
        if share:
            held = held + jnp.where(active, counts[0], 0)
            whole = whole + paths[0]
        if kinds:
            kv = tuple(n + reads[kind] for n, kind in zip(kv, kinds))
        cand = None
        if axis_name is not None and logits.shape[-1] != cfg.vocab_size:
            # the tp top-k composition: candidates reduce over the sharded
            # vocab, not the gathered one (selection by raw logits —
            # temperature scaling is order-preserving), and only in a step
            # where some row samples: the sampler calls it inside its arm
            cand = functools.partial(
                sharded_topk_indices, logits, axis_name,
                min(TOPP_FAST_K, cfg.vocab_size),
            )
            logits = jax.lax.all_gather(logits, axis_name, axis=1, tiled=True)
        nxt = fused_sample_batched(
            logits, seeds, p, temperature, topp, topk, cand=cand
        )
        if fingerprint:
            h, okf = integrity.fingerprint_fold(h, okf, logits, nxt)
        p2 = jnp.where(active, p + 1, p)
        return (nxt.astype(jnp.int32), cache_c, p2, h, okf, held, whole, kv), nxt

    h0, ok0 = integrity.fingerprint_init(first_tokens.shape[0])
    zeros = jnp.zeros(first_tokens.shape, jnp.int32)
    (_, cache, _, h, okf, held, whole, kv), tokens = jax.lax.scan(
        step,
        (
            first_tokens.astype(jnp.int32), cache, pos.astype(jnp.int32),
            h0, ok0, zeros, jnp.zeros(2, jnp.int32) if share else None, tuple(zeros for _ in kinds),
        ),
        None,
        length=n_steps,
    )
    shared = (held, *(jnp.broadcast_to(n, held.shape) for n in whole)) if share else ()
    return (tokens, cache, h, okf) + shared + kv


def batched_chunk_from_carry(
    cfg: LlamaConfig, params, carry, cache, pos, active, seeds, n_steps: int,
    temperature, topp, topk, axis_name: str | None = None, paged=None,
):
    """The body every batched chunk program shares (single chip and tensor
    parallel, paged or not): :func:`batched_decode_scan` fed from the
    scheduler's ``carry`` — int32, one entry a slab row: the token that
    row's next chunk feeds first; the bucket is the rows ``active`` covers —
    and the carry advanced: an active row's entry becomes the chunk's last
    token, every other entry stays (an inactive row's is never read before
    the row's next join overwrites it). Returns ``(out, cache, carry)``,
    ``out`` the packed bundle of ``integrity.pack_chunk_outputs``."""
    b = active.shape[0]
    tokens, cache, *rest = batched_decode_scan(
        cfg, params, carry[:b], cache, pos, active, seeds, n_steps,
        temperature, topp, topk, axis_name=axis_name, paged=paged,
    )
    carry = carry.at[:b].set(
        jnp.where(active, tokens[-1].astype(jnp.int32), carry[:b])
    )
    return integrity.pack_chunk_outputs(tokens, *rest), cache, carry


@functools.partial(jax.jit, static_argnums=(0, 6), donate_argnums=(2, 3))
def decode_chunk_batched(
    cfg: LlamaConfig,
    params,
    carry: jax.Array,
    cache,
    pos: jax.Array,
    active: jax.Array,
    n_steps: int,
    temperature: jax.Array,
    topp: jax.Array,
    topk: jax.Array,
    seeds: jax.Array,
):
    """One chunk of the batched multi-stream decode (single chip): like
    :func:`decode_chunk` but over B concurrent sequences with per-row
    positions, sampler settings and seeds — one compiled program per
    (bucket, chunk) shape serves every mix of requests. The slab cache is
    donated and aliases in place; no sampler state returns — the next
    chunk re-keys its coins from (seed, position).

    ``carry`` is the scheduler's vector of next-chunk first tokens (int32,
    one entry a slab row, at least B long; donated): the bucket's rows of
    it feed the first step, and it returns advanced
    (:func:`batched_chunk_from_carry`), so the host never touches a row's
    token between two chunks.

    Returns ``(out, cache, carry)`` where ``out`` is the packed
    [n_steps + 2, B] int32 bundle of tokens + per-row logit fingerprint +
    finiteness flag (engine/integrity.py ``split_chunk_outputs``) — one
    fetch still moves everything the scheduler needs, and those int32 rows
    are the ONLY bytes that cross the host per chunk."""
    return batched_chunk_from_carry(
        cfg, params, carry, cache, pos, active, seeds, n_steps, temperature,
        topp, topk,
    )


@functools.partial(jax.jit, static_argnums=(0, 7), donate_argnums=(2, 3))
def decode_chunk_batched_paged(
    cfg: LlamaConfig,
    params,
    carry: jax.Array,
    cache,
    pos: jax.Array,
    active: jax.Array,
    pool,  # per-layer (keys, values) page-pool halves — READ-ONLY
    n_steps: int,
    temperature: jax.Array,
    topp: jax.Array,
    topk: jax.Array,
    seeds: jax.Array,
    tables: jax.Array,  # int32 [B, n_table] per-row page tables
    matched: jax.Array,  # int32 [B] aliased prefix lengths (0 = no alias)
):
    """:func:`decode_chunk_batched` with zero-copy prefix aliasing: rows
    whose prompt hit the radix cache read their matched prefix straight out
    of the shared page pool every step — no gathered slab duplicate exists.
    Slab and carry are donated; the pool is shared across every row and
    dispatch, so it must never alias. Same ``(out, cache, carry)`` return
    as :func:`decode_chunk_batched`."""
    return batched_chunk_from_carry(
        cfg, params, carry, cache, pos, active, seeds, n_steps, temperature,
        topp, topk, paged=(pool, tables, matched),
    )


# ---------------------------------------------------------------------------
# Self-speculative decoding (prompt-lookup drafts, Leviathan et al. verify):
# the host proposes up to k draft tokens from the request's own prompt +
# output n-grams (engine/speculative.py — no draft model), one verify
# forward scores [prev, d_1..d_k] in a single weight read, and the
# accept/reject below runs ON DEVICE so only (n_emit, tokens) — a handful
# of int32s — cross the host boundary per step.
# ---------------------------------------------------------------------------


def _filtered_dist(logits, temperature, topp, topk):
    """The renormalized filtered distribution p [T, vocab] the spec
    accept/redraw draws from: the SAME candidate semantics as the fused
    sampler (descending scaled-logit order, top-k ∧ nucleus prefix),
    expressed as a mask + renormalize so per-token acceptance
    probabilities exist. Returns (p, greedy_targets)."""
    T, vocab = logits.shape
    logits = logits.astype(jnp.float32)
    greedy_targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    probs = jax.vmap(jax.nn.softmax)(scaled)
    sv_i = jax.lax.top_k(scaled, vocab)[1]  # [T, V] descending order
    pv = jnp.take_along_axis(probs, sv_i, axis=-1)
    cum = jnp.cumsum(pv, axis=-1)
    n_keep = _keep_count(pv, cum, topp, topk)

    def row_rank(order_row):
        return (
            jnp.zeros((vocab,), jnp.int32)
            .at[order_row]
            .set(jnp.arange(vocab, dtype=jnp.int32))
        )

    ranks = jax.vmap(row_rank)(sv_i)
    keep = ranks < n_keep[:, None]
    filt = jnp.where(keep, probs, 0.0)
    p = filt / jnp.sum(filt, axis=-1, keepdims=True)
    return p, greedy_targets


def _cdf_pick(p, coin):
    """Vocab-order inverse-CDF draw from per-row distributions ``p``
    [T, vocab] with per-row coins [T] (mass renormalized by the row
    total, so zeroed entries never draw)."""
    vocab = p.shape[-1]
    cdf = jnp.cumsum(p, axis=-1)
    r = coin * cdf[:, -1]
    return jnp.minimum(jnp.sum(cdf <= r[:, None], axis=-1), vocab - 1).astype(
        jnp.int32
    )


def _spec_accept_row(logits, draft, draft_len, seed, pos, temperature, topp, topk):
    """Accept/reject one row's draft against its verify logits.

    ``logits``: [T, vocab] f32 (T = k + 1) — ``logits[i]`` is the model's
    next-token distribution after consuming feed position ``i`` (absolute
    stream position ``pos + i``); ``draft``: [k] int32 (entries at or
    beyond ``draft_len`` are pad). Returns ``(n_emit, tokens [T])`` where
    ``tokens[:n_emit]`` are the emitted tokens — ``n_emit - 1`` accepted
    drafts plus one correction/bonus token drawn from the model's own
    distribution.

    Greedy (temperature == 0): longest-matching-prefix against the argmax
    targets — every emitted token IS the plain decode's argmax at its
    position, so the stream is bit-identical to non-speculative decode.

    Sampled: Leviathan-style rejection sampling on counter coins. The
    prompt-lookup draft distribution is the point mass q = δ(draft_i), so
    position i accepts with probability p_i(draft_i) against the coin
    keyed ``(seed, pos + i, DRAW_SPEC_ACCEPT)`` (p = the renormalized
    top-k/top-p-filtered softmax — exactly what the fused sampler draws
    from) and a rejection redraws from the residual norm(max(p - q, 0)) =
    p with draft_i removed on the ``DRAW_SPEC_REDRAW`` coin of the emit
    position; acceptance never biases the output distribution, and the
    whole step consumes no sampler state — a replay re-keys every coin."""
    T, vocab = logits.shape
    k = T - 1
    p, greedy_targets = _filtered_dist(logits, temperature, topp, topk)

    steps = pos + jnp.arange(T, dtype=jnp.int32)
    u = prng.device_coin(
        jnp.broadcast_to(seed, (T,)), steps, prng.DRAW_SPEC_ACCEPT
    )
    redraw = prng.device_coin(
        jnp.broadcast_to(seed, (T,)), steps, prng.DRAW_SPEC_REDRAW
    )

    i_idx = jnp.arange(k)
    in_draft = i_idx < draft_len
    p_draft = p[i_idx, draft]  # [k] acceptance probability per position
    sampled_ok = u[:k] < p_draft if k else jnp.zeros((0,), bool)
    greedy_ok = draft == greedy_targets[:k]
    ok = jnp.where(temperature == 0.0, greedy_ok, sampled_ok) & in_draft
    acc = jnp.cumprod(ok.astype(jnp.int32)) if k else jnp.zeros((0,), jnp.int32)
    n_acc = jnp.sum(acc)  # accepted draft prefix length

    # one inverse-CDF draw per position (T is small): the residual draw
    # for a rejection at i < draft_len, the full draw for the bonus
    # position — both on the emit position's redraw coin
    if k:
        q = jnp.where(
            jnp.arange(vocab)[None, :] == draft[:, None], 0.0, p[:k]
        )
        resid = _cdf_pick(q, redraw[:k])
    else:
        resid = jnp.zeros((0,), jnp.int32)
    full = _cdf_pick(p, redraw)
    resid_padded = jnp.concatenate([resid, jnp.zeros((1,), jnp.int32)])
    rejected = n_acc < draft_len
    corr_sampled = jnp.where(rejected, resid_padded[n_acc], full[n_acc])
    corr = jnp.where(temperature == 0.0, greedy_targets[n_acc], corr_sampled)

    t_idx = jnp.arange(T)
    draft_padded = jnp.concatenate([draft, jnp.zeros((1,), jnp.int32)])
    tokens = jnp.where(t_idx < n_acc, draft_padded, 0)
    tokens = jnp.where(t_idx == n_acc, corr, tokens).astype(jnp.int32)
    return (n_acc + 1).astype(jnp.int32), tokens


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
def spec_verify_step(
    cfg: LlamaConfig,
    params,
    feed: jax.Array,  # int32 [T] — [prev, draft_1..draft_k] (pad beyond draft_len)
    cache,
    pos: jax.Array,  # int32 scalar: position of feed[0]
    draft_len: jax.Array,  # int32 scalar
    temperature: jax.Array,
    topp: jax.Array,
    topk: jax.Array,
    seed: jax.Array,  # uint32 scalar
):
    """One single-stream speculative step: verify forward (the ordinary
    multi-token decode at a position offset — ONE weight read for draft +
    bonus positions) fused with the on-device accept/reject. Returns
    ``(out, cache)`` with ``out = [n_emit, tokens...]`` int32 [T+1] —
    the only bytes that visit the host. Cache slots past the accepted
    prefix hold rejected-draft K/V: stale but unreachable (the next step
    writes at the advanced position before any query can see them — the
    same overshoot contract as the chunked decode's rollback)."""
    logits, cache = llama.forward_tokens(cfg, params, feed, cache, pos)
    n_emit, tokens = _spec_accept_row(
        logits, feed[1:], draft_len, seed, pos, temperature, topp, topk
    )
    return jnp.concatenate([n_emit[None], tokens]), cache


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
def spec_verify_chunk_batched(
    cfg: LlamaConfig,
    params,
    feed: jax.Array,  # int32 [B, T] per-row [prev, drafts...] windows
    cache,
    pos: jax.Array,  # int32 [B]
    active: jax.Array,  # bool [B]
    draft_len: jax.Array,  # int32 [B]
    temperature: jax.Array,  # [B]
    topp: jax.Array,  # [B]
    topk: jax.Array,  # int32 [B]
    seeds: jax.Array,  # uint32 [B]
):
    """One batched speculative step: every joined row's verify window rides
    ONE weight read (llama.forward_verify_batched) and the per-row
    accept/reject runs on device. Returns ``(out [B, T+1], cache)`` with
    ``out[b] = [n_emit_b, tokens_b...]`` — rows advance a VARIABLE number
    of positions per step (the scheduler applies each row's n_emit at
    fetch time). Inactive rows compute garbage into dropped cache slots,
    exactly like the plain batched chunk."""
    logits, cache = llama.forward_verify_batched(
        cfg, params, feed, cache, pos, active
    )
    n_emit, tokens = jax.vmap(_spec_accept_row)(
        logits, feed[:, 1:], draft_len, seeds, pos, temperature, topp, topk
    )
    return jnp.concatenate([n_emit[:, None], tokens], axis=1), cache


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
def spec_verify_chunk_batched_paged(
    cfg: LlamaConfig,
    params,
    feed: jax.Array,  # int32 [B, T] per-row [prev, drafts...] windows
    cache,
    pos: jax.Array,  # int32 [B]
    active: jax.Array,  # bool [B]
    pool,  # per-layer (keys, values) page-pool halves — READ-ONLY
    draft_len: jax.Array,  # int32 [B]
    temperature: jax.Array,  # [B]
    topp: jax.Array,  # [B]
    topk: jax.Array,  # int32 [B]
    seeds: jax.Array,  # uint32 [B]
    tables: jax.Array,  # int32 [B, n_table]
    matched: jax.Array,  # int32 [B]
):
    """:func:`spec_verify_chunk_batched` with zero-copy prefix aliasing:
    verify windows attend over pool pages for the matched prefix and the
    slab row for the private suffix, bit-identical to the copied-prefix
    verify (the spec × prefix-cache parity contract). The paged verify
    attention is the segmented scan of the decode hit path
    (``ops.attention.batched_verify_attention``), under the same
    bit-parity pins."""
    logits, cache = llama.forward_verify_batched(
        cfg, params, feed, cache, pos, active, paged=(pool, tables, matched)
    )
    n_emit, tokens = jax.vmap(_spec_accept_row)(
        logits, feed[:, 1:], draft_len, seeds, pos, temperature, topp, topk
    )
    return jnp.concatenate([n_emit[:, None], tokens], axis=1), cache


@functools.partial(jax.jit, static_argnums=(0, 5), donate_argnums=(3,))
def decode_chunk(
    cfg: LlamaConfig,
    params,
    first_token: jax.Array,
    cache: jax.Array,
    pos: jax.Array,
    n_steps: int,
    temperature: jax.Array,
    topp: jax.Array,
    topk: jax.Array,
    seed: jax.Array,  # uint32 scalar
):
    """One chunk of the user-facing streaming decode (single chip): like
    :func:`decode_loop` but temperature/topp/topk are *traced* scalars —
    one compiled program per chunk size serves every request's sampler
    settings — and coins re-key per position, so the stream continues
    across chunks exactly as a single dispatch would with no state
    returned."""
    return decode_scan(
        cfg, params, first_token, cache, pos, seed, n_steps, temperature,
        topp, topk,
    )
