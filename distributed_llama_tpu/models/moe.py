"""Mixture-of-experts FFN: every expert held (Mixtral, Grok-1; GLM-4.7-Flash's
64 beside a shared one), or a SHARE of the routed experts held beside a
shared expert (:func:`_moe_share`: Solar-Open2, K-EXAONE, Granite-4.0-H-Small,
whose router runs over its whole published width and whose absent experts'
part is left out), behind softmax, window, linear, latent or state-space
mixers alike.

Parity with the reference's MoE task chain (reference:
src/grok1-tasks.cpp:56-263, composed into Mixtral at
src/mixtral-tasks.cpp:25-44): router matmul → softmax (or sigmoid, by the
file's flags) → top-k → renormalized weights → per-expert SwiGLU → weighted
sum of expert downs.

TPU-first design notes:
* The reference routes on the root with scalar code and broadcasts indexes
  (grok1-tasks.cpp:69-126); here routing is `jax.lax.top_k` inside the same
  jitted program — replicated across TP shards, so no broadcast exists.
* Experts are TP-sliced exactly like the reference (every shard holds a
  1/n-of-hidden slice of *all* experts — transformer.cpp:335-353), so the
  expert weighted-sum needs the same single psum as the dense FFN.
* Decode (T == 1) computes ONLY the top-k experts: each selected expert runs
  under a `lax.lax.switch` whose branches close over one expert's weights, so
  HBM reads and MXU flops scale with k, not E (top-2-of-8 Mixtral decode
  touches 4x less expert memory than dense mixing). A few rows (T > 1: the
  decode buckets and small pieces) keep dense one-hot mixing: tokens fan
  out across experts anyway. From 64 rows on (a prompt piece) an expert
  multiplies only the REAL rows that chose it, gathered into its bucket; a
  piece in which some expert has more rows than its bucket runs every expert
  over every row instead, so the result is exact either way.
* Expert banks may be Q40: `engine.weights` loads each expert as fused
  gate|up + down `QuantizedMatrix` leaves (an ``experts`` list in the layer
  params), so a Q40 Mixtral file occupies ~file-size HBM instead of
  inflating 4x to bf16.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from distributed_llama_tpu.formats.model_file import ArchType
from distributed_llama_tpu.models.config import LlamaConfig
from distributed_llama_tpu.ops.q40 import grouped_live_tiles


def router_probs(cfg: LlamaConfig, xn: jax.Array, router: jax.Array) -> jax.Array:
    """[T, E] router scores: softmax over the experts (reference:
    src/grok1-tasks.cpp:62-97) or, where the config says so, a sigmoid of
    each expert's logit."""
    logits = jnp.einsum(
        "td,de->te",
        xn.astype(jnp.float32),
        router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return jax.nn.sigmoid(logits) if cfg.router_sigmoid else jax.nn.softmax(logits, axis=-1)


def router_topk(
    cfg: LlamaConfig, xn: jax.Array, router: jax.Array, bias: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Top-k routing: ([T, k] weights, [T, k] expert ids) — the single home
    of the select-then-renormalize convention (reference:
    src/grok1-tasks.cpp:62-114). The score function, whether the chosen
    weights are renormalised and the factor they are multiplied by after
    that (``routed_scale``) are the config's; ``bias`` [E] is added to the
    scores for CHOOSING only, the weights are the scores themselves."""
    probs = router_probs(cfg, xn, router)
    if bias is None:
        top_vals, top_idx = jax.lax.top_k(probs, cfg.n_active_experts)
    else:
        _, top_idx = jax.lax.top_k(probs + bias, cfg.n_active_experts)
        top_vals = jnp.take_along_axis(probs, top_idx, axis=-1)
    if cfg.norm_topk:
        top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    if cfg.routed_scale != 1.0:
        top_vals = top_vals * cfg.routed_scale
    return top_vals, top_idx


def router_weights(
    cfg: LlamaConfig, xn: jax.Array, router: jax.Array, bias: jax.Array | None = None
) -> jax.Array:
    """[T, E] mixing weights over the router's whole width: top-k selected
    (renormalized to sum to 1 where the config says), zero elsewhere."""
    top_vals, top_idx = router_topk(cfg, xn, router, bias)
    one_hot = jax.nn.one_hot(top_idx, cfg.router_width, dtype=jnp.float32)  # [T, k, E]
    return jnp.einsum("tk,tke->te", top_vals, one_hot)


def _expert_weights(lp, e: int):
    """Weights of expert ``e``: a dict with either fused ``gate_up`` (+
    ``down``) QuantizedMatrix leaves (the q40 layout) or separate
    ``gate``/``up``/``down`` slices of the stacked bf16 banks."""
    if "experts" in lp:
        return lp["experts"][e]
    return {"gate": lp["moe_gate"][e], "up": lp["moe_up"][e], "down": lp["moe_down"][e]}


def _expert_ffn(cfg: LlamaConfig, xn: jax.Array, ew) -> jax.Array:
    """One expert's SwiGLU on normed input [T, D] -> [T, D] f32 (pre-psum,
    pre-weighting). Mirrors the dense FFN's fused-vs-separate dispatch."""
    from distributed_llama_tpu.models.llama import _activation, _matmul

    if "gate_up" in ew:
        fused = _matmul(xn.astype(ew["gate_up"].dtype), ew["gate_up"], "experts")
        hidden = fused.shape[-1] // 2
        h = _activation(fused[:, :hidden], cfg.hidden_act) * fused[:, hidden:]
    else:
        xc = xn.astype(ew["gate"].dtype)
        h = _activation(_matmul(xc, ew["gate"], "experts"), cfg.hidden_act) * _matmul(
            xc, ew["up"], "experts"
        )
    return _matmul(h.astype(ew["down"].dtype), ew["down"], "experts")


def _moe_topk(cfg: LlamaConfig, xn: jax.Array, lp) -> jax.Array:
    """Decode path: run exactly the k selected experts via lax.switch.
    Routing is replicated across shards (same input -> same indexes), the
    reference's index broadcast with the broadcast removed."""
    top_vals, top_idx = router_topk(cfg, xn, lp["router"])  # [1, k]
    top_vals, top_idx = top_vals[0], top_idx[0]
    branches = [
        (lambda x_, e=e: _expert_ffn(cfg, x_, _expert_weights(lp, e)))
        for e in range(cfg.n_experts)
    ]
    out = jnp.zeros(xn.shape, jnp.float32)
    for i in range(cfg.n_active_experts):
        out = out + top_vals[i] * jax.lax.switch(top_idx[i], branches, xn)
    return out


# a program of this many rows or more computes each expert over the REAL rows
# that chose it (exact: the every-row loop is its overflow arm). Below it
# (every decode bucket) the experts' bytes bound the step and nearly every
# expert is hit anyway
MOE_EXACT_MIN_T = 64


def exact_bucket_rows(n_tokens: int, k: int, n_buckets: int) -> int:
    """Rows of an expert's bucket on the exact path: twice an even share of
    a FULL program, as a power of two (128 of 256 rows at top-2 of 8). A
    static function of the padded shape alone; a program in which some
    expert has more REAL rows than this takes the every-row arm."""
    import math

    from distributed_llama_tpu.models.config import next_pow2

    return next_pow2(math.ceil(2 * n_tokens * k / n_buckets))


def bucket_rank(top_idx: jax.Array, n_buckets: int):
    """Rank every (token, choice) within its target expert — the "sort" of
    the compacted buckets without an actual sort. top_idx: [T, k] expert
    ids. Returns (flat_e [T*k], rank [T*k], t_ids [T*k])."""
    T, k = top_idx.shape
    N = T * k
    flat_e = top_idx.reshape(N)
    onehot = jax.nn.one_hot(flat_e, n_buckets, dtype=jnp.int32)  # [N, E]
    rank = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(N), flat_e]
    t_ids = jnp.repeat(jnp.arange(T), k)
    return flat_e, rank, t_ids


def bucket_scatter(
    x: jax.Array, flat_e: jax.Array, rank: jax.Array, t_ids: jax.Array,
    n_buckets: int, C: int,
) -> jax.Array:
    """Gather each expert's routed rows into fixed [n_buckets, C, D]
    buckets; rows ranked past C land in a spill row that is trimmed
    (capacity drop). An expert index >= n_buckets drops the row entirely
    (the pad-token sink of the bucketed prefill)."""
    D = x.shape[-1]
    slot = jnp.where(rank < C, rank, C)
    return (
        jnp.zeros((n_buckets, C + 1, D), x.dtype)
        .at[flat_e, slot]
        .set(x[t_ids], mode="drop")
    )[:, :C]


def bucket_combine(
    outs: jax.Array,  # [n_buckets, C, D] per-expert outputs (f32)
    top_idx: jax.Array,  # [T, k]
    rank: jax.Array,  # [T*k]
    top_vals: jax.Array,  # [T, k] renormalized weights
    C: int,
) -> jax.Array:
    """Combine expert outputs back to token order; dropped choices
    contribute zero. Returns [T, D] f32."""
    T, k = top_idx.shape
    rank = rank.reshape(T, k)
    valid = (rank < C).astype(jnp.float32)
    gathered = outs[top_idx, jnp.minimum(rank, C - 1)]  # [T, k, D]
    return jnp.einsum("tk,tkd->td", top_vals * valid, gathered)


def _all_experts(cfg: LlamaConfig, xn: jax.Array, lp, weights: jax.Array) -> jax.Array:
    """Every expert over every row, mixed by the mostly-zero [T, E] weights."""
    out = jnp.zeros(xn.shape, jnp.float32)
    for e in range(cfg.n_experts):
        out = out + weights[:, e : e + 1] * _expert_ffn(cfg, xn, _expert_weights(lp, e))
    return out


def _moe_dense(
    cfg: LlamaConfig, xn: jax.Array, lp, n_real: jax.Array | None = None
) -> jax.Array:
    """Prefill path. For stacked bf16 banks: every expert computed in one
    batched einsum and mixed by the mostly-zero [T, E] weight matrix. For
    per-expert q40 leaves, in a program of ``MOE_EXACT_MIN_T`` rows or more,
    an expert multiplies the REAL rows that chose it (:func:`_moe_bucketed`:
    buckets of :func:`exact_bucket_rows` rows, the every-row loop where some
    expert overflows its bucket; exact either way); below that, the loop
    over every expert and every row. ``n_real`` marks the real-token prefix
    of a bucket-padded batch: pad rows choose no expert (they must not spend
    a bucket's rows)."""
    if "experts" in lp:
        T, k, E = xn.shape[0], cfg.n_active_experts, cfg.n_experts
        C = exact_bucket_rows(T, k, E)
        if T >= MOE_EXACT_MIN_T and C < T:
            return _moe_bucketed(cfg, xn, lp, C, n_real=n_real)
        _note_piece_path(1)
        return _all_experts(cfg, xn, lp, router_weights(cfg, xn, lp["router"]))
    _note_piece_path(1)
    weights = router_weights(cfg, xn, lp["router"])  # [T, E] f32
    from distributed_llama_tpu.models.llama import _activation

    if lp["moe_up"].dtype == jnp.bfloat16 and jax.default_backend() == "cpu":
        # some XLA:CPU builds cannot EXECUTE bf16xbf16 batched dots
        # ("DotThunk ... BF16 x BF16" runtime errors); f32 operands cost
        # nothing on the dev/test surface and TPU never takes this branch
        lp = dict(lp)
        for k_ in ("moe_up", "moe_gate", "moe_down"):
            lp[k_] = lp[k_].astype(jnp.float32)
    xc = xn.astype(lp["moe_up"].dtype)
    gate = jnp.einsum(
        "td,edh->teh", xc, lp["moe_gate"], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    up = jnp.einsum(
        "td,edh->teh", xc, lp["moe_up"], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    h = _activation(gate, cfg.hidden_act) * up  # [T, E, Hl] f32
    down = jnp.einsum(
        "teh,ehd->ted", h.astype(lp["moe_down"].dtype), lp["moe_down"],
        preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST,
    )
    return jnp.einsum("te,ted->td", weights, down, precision=jax.lax.Precision.HIGHEST)


def _moe_bucketed(
    cfg: LlamaConfig, xn: jax.Array, lp, C: int, n_real: jax.Array | None = None
) -> jax.Array:
    """Bucketed q40 prefill: rank every (token, choice) within its expert,
    gather each expert's rows into a fixed [C, D] bucket, run ONE fused q40
    FFN per expert over its bucket, and combine outputs with the
    renormalized top-k weights. Compute per expert drops from T rows to C;
    the expert-weight HBM reads are identical, so the win scales with T.
    The bucket algebra (bucket_rank/scatter/combine) is shared with the
    held experts' buckets and the expert-parallel dispatch
    (parallel.expert_parallel._ep_dispatch).

    Where some expert has more rows than ``C`` the layer runs every expert
    over every row instead (one ``lax.cond``; both arms live in the one
    program), so no row is ever left out.

    Engine bucket-padding appends zero tokens past ``n_real``; those rows
    route like real tokens (identical embeddings → identical experts), so
    unmasked they would pile into a few experts' buckets. They are routed
    to a sink index E with weight 0 instead: the one-hot rank and the
    counts treat them as absent and the scatter drops them, so a bucket's
    rows are spent ONLY on real tokens (the capacity C itself must stay a
    static function of the padded T)."""
    T, E = xn.shape[0], cfg.n_experts
    top_vals, top_idx = router_topk(cfg, xn, lp["router"])  # [T, k]
    if n_real is not None:
        valid = (jnp.arange(T) < n_real)[:, None]
        top_idx = jnp.where(valid, top_idx, E)  # sink: pads drop
        top_vals = jnp.where(valid, top_vals, 0.0)
    flat_e, rank, t_ids = bucket_rank(top_idx, E + 1)

    def bucketed():
        buckets = bucket_scatter(xn, flat_e, rank, t_ids, E, C)
        outs = jnp.stack([
            _expert_ffn(cfg, buckets[e], _expert_weights(lp, e)) for e in range(E)
        ])  # [E, C, D] f32
        return bucket_combine(outs, jnp.minimum(top_idx, E - 1), rank, top_vals, C)

    def every_row():
        weights = jnp.einsum("tk,tke->te", top_vals, jax.nn.one_hot(top_idx, E))
        return _all_experts(cfg, xn, lp, weights)

    counts = jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.int32), axis=(0, 1))
    over = jnp.max(counts) > C
    _note_piece_path(over)
    return jax.lax.cond(over, every_row, bucketed)


# Trace-time collector of the expert share's routing sums: while one is open
# (:func:`collect_held`), every :func:`_moe_share` appends, per row, how many
# of its top-k choices fell on an expert held here (int32 [T]). The forward
# that opened it sums the layers and returns the sum with its results, so the
# counters ride the fetch a program's tokens make anyway.
_held_counts: list | None = None


@contextlib.contextmanager
def collect_held(enabled: bool = True):
    global _held_counts
    before, _held_counts = _held_counts, [] if enabled else None
    try:
        yield _held_counts
    finally:
        _held_counts = before


# The same for the path an expert layer took: while one is open
# (:func:`collect_piece_paths`), every expert layer of the trace appends an
# int32 scalar, 1 where it ran every expert over every row of the program
# (an overflowing bucket, or a program too small to bucket) and 0 where each
# expert ran over its own bucket.
_piece_paths: list | None = None


@contextlib.contextmanager
def collect_piece_paths(enabled: bool = True):
    global _piece_paths
    before, _piece_paths = _piece_paths, [] if enabled else None
    try:
        yield _piece_paths
    finally:
        _piece_paths = before


def _note_piece_path(every_row) -> None:
    if _piece_paths is not None:
        _piece_paths.append(jnp.asarray(every_row, jnp.int32))


# And for the rows a layer of HELD experts launched: while one is open
# (:func:`collect_launched`), every :func:`_held_experts` appends an int32
# scalar, the rows the arm it took multiplied (:func:`_launched_rows`).
_launched: list | None = None


@contextlib.contextmanager
def collect_launched(enabled: bool = True):
    global _launched
    before, _launched = _launched, [] if enabled else None
    try:
        yield _launched
    finally:
        _launched = before


# Trace-time too: the real rows of the padded piece whose expert share is
# being traced (None: every row is real). :func:`_moe_share` is wrapped with
# its three arguments by the benchmark's tests, so ``moe_ffn`` hands it the
# piece's ``n_real`` here and not as a fourth.
_share_n_real: jax.Array | None = None


@contextlib.contextmanager
def _real_rows(n_real: jax.Array | None):
    global _share_n_real
    before, _share_n_real = _share_n_real, n_real
    try:
        yield
    finally:
        _share_n_real = before


def held_bucket_rows(cfg: LlamaConfig, rows: int) -> int:
    """Rows a held expert's bucket holds in a step of ``rows`` rows. A token
    chooses a given held expert with probability ``k / routed`` (the config's
    experts per token over its router's width), so a step of ``rows`` gives an
    expert ``rows * k / routed`` of them when routing is even; the bucket is
    four times that, reckoned for the largest step of its class (64 rows: the
    decode steps and small pieces; 256: a prefill chunk), as a power of two,
    at least 8. At 8 of 320 that is 8 and 32 (a chunk gives an expert 6.4
    rows, s.d. 2.5), at 8 of 128 and 4 of 64 16 and 64. Where that is MORE
    than half the class (a token chooses a seventh of the experts, 10 of 72:
    36 -> 64 of 64, 142 -> 256 of 256, a bucket of every row that is no
    bucket for any step of the class), the bucket is reckoned from the step's
    own rows instead: twice its even share, as a power of two (16 of a 32-row
    decode step, where an expert expects 4.4 rows, s.d. 1.96; 32 of 64, 64
    of 128, 128 of 256), and a step of fewer than 32 rows keeps every row (a
    launch of at most 16 rows is its weights' bytes: a bucket buys nothing
    and costs its gather and combine). A step in which some expert
    overflows its bucket takes the every-row path instead (exact either
    way)."""
    import math

    from distributed_llama_tpu.models.config import next_pow2

    largest = 64 if rows <= 64 else 256
    share = cfg.n_active_experts / cfg.router_width
    bucket = max(8, next_pow2(math.ceil(4 * largest * share)))
    if bucket <= largest // 2:
        return bucket
    return rows if rows < 32 else next_pow2(math.ceil(2 * rows * share))


def _held_ffn(cfg: LlamaConfig, x: jax.Array, lp, counts: jax.Array, tokens: int) -> jax.Array:
    """SwiGLU of every held expert over its rows, the first ``counts`` [E] of
    them live: ``x`` [T, D] (every expert multiplies the same rows) or
    [E, C, D] -> [E, T or C, D]. Q40 banks go through ONE grouped launch for
    gate|up and one for down (``ops.q40.q40_grouped_matmul``: an expert with
    no live row is neither read nor computed, nor is a row tile past an
    expert's live rows), plain arrays through batched einsums. ``tokens``, the
    rows of the step that routed, goes into the launches' names: how many
    experts a launch reads follows from it, not from a bucket's rows."""
    from distributed_llama_tpu.models.llama import _activation
    from distributed_llama_tpu.ops.q40 import QuantizedMatrix, q40_grouped_matmul

    gate_up, down = lp["experts_gate_up"], lp["experts_down"]
    width, dim = down.shape[-2], x.shape[-1]
    if isinstance(gate_up, QuantizedMatrix):
        role = f"held_experts_t{tokens}"
        fused = q40_grouped_matmul(x, gate_up, counts, role=role)
        h = _activation(fused[..., :width], cfg.hidden_act) * fused[..., width : 2 * width]
        return q40_grouped_matmul(h, down, counts, role=role)[..., :dim]
    hi = jax.lax.Precision.HIGHEST
    rows = "td" if x.ndim == 2 else "etd"
    fused = jnp.einsum(f"{rows},edf->etf", x.astype(gate_up.dtype), gate_up, precision=hi,
                       preferred_element_type=jnp.float32)
    h = _activation(fused[..., :width], cfg.hidden_act) * fused[..., width:]
    return jnp.einsum("etf,efd->etd", h.astype(down.dtype), down, precision=hi,
                      preferred_element_type=jnp.float32)


def _bucket_slots(local: jax.Array, weights: jax.Array, E: int, C: int):
    """A step's buckets as two [T, E * C] matrices over its slots (row ``c``
    of expert ``e``'s bucket is slot ``e * C + c``): ``place`` marks the slot
    each of a token's choices takes, ``mix`` holds the choice's weight there,
    so that gathering the buckets and scattering their results back are two
    small matmuls (``place.T @ x`` picks one row a slot, exactly; ``mix @
    outs`` is the weighted sum) and no row is moved one by one: a step's
    ``T * k`` row-sized scatter updates and gathers cost what its bucket
    saved (PERF.md section 6, PR 52). ``local`` [T, k]: the chosen experts,
    ``E`` the sink (no held expert, a pad row: no slot). A choice ranks
    behind the earlier tokens that chose its expert (a token's k choices are
    distinct); one ranked past ``C`` takes no slot."""
    chose = jnp.sum(jax.nn.one_hot(local, E + 1, dtype=jnp.float32), axis=1)  # [T, E + 1]
    rank = jnp.take_along_axis(jnp.cumsum(chose, axis=0) - chose, local, axis=1).astype(jnp.int32)
    slot = jnp.where((local < E) & (rank < C), local * C + rank, E * C)  # E * C: one_hot's zero row
    hot = jax.nn.one_hot(slot, E * C, dtype=jnp.float32)  # [T, k, E * C]
    return jnp.sum(hot, axis=1), jnp.einsum("tk,tks->ts", weights, hot)


def _launched_rows(counts: jax.Array, rows: int) -> jax.Array:
    """Rows the held experts' grouped launch multiplies of the ``rows`` rows
    each expert has, the first ``counts`` [E] of them live, int32: the whole
    row tiles that hold a live row (``ops.q40.grouped_live_tiles``, the
    launch's own rule)."""
    tile, live = grouped_live_tiles(counts, rows, shared=False)
    return tile * jnp.sum(live, dtype=jnp.int32)


def _held_experts(
    cfg: LlamaConfig, xn: jax.Array, lp, top_vals: jax.Array, top_idx: jax.Array,
    n_real: jax.Array | None = None,
) -> jax.Array:
    """Sum over the chosen experts HELD here of ``w * SwiGLU_e(xn)``, from
    the routing's ([T, k] weights, [T, k] ids over the router's width). Each
    held expert's rows are gathered into a bucket of ``held_bucket_rows``
    rows and computed there, the results scattered back by the weights
    (:func:`_bucket_slots`: both as one matmul over the step's slots): an
    expert computes its own rows, not every row of the step. Where some
    expert has more rows than its bucket, the buckets are twice as large
    (a document of few distinct tokens routes alike: 8 to 28 % of a piece's
    expert layers overflowed a bucket of four times the even share, and the
    every-row arm costs four times a bucket's launch, PERF.md section 6, PR
    43), and where one overflows that too the step is computed with every
    held expert over every row instead: exact each way. Rows at and past
    ``n_real`` (a padded piece) choose no expert: they fill no bucket, trip
    no overflow, and an expert only they chose is not read."""
    T, E = xn.shape[0], cfg.n_experts
    local = top_idx - cfg.first_expert
    is_held = (local >= 0) & (local < E)
    if n_real is not None:
        is_held &= (jnp.arange(T) < n_real)[:, None]
    local = jnp.where(is_held, local, E)  # E: the sink the scatter drops
    weights = jnp.where(is_held, top_vals, 0.0)
    counts = jnp.sum(jax.nn.one_hot(local, E + 1, dtype=jnp.int32), axis=(0, 1))[:E]
    C = held_bucket_rows(cfg, T)

    # each arm: its result, and the rows its launches multiplied
    def every_row():
        rows = jnp.where(counts > 0, T, 0)  # an expert some row chose multiplies them all
        held = jnp.einsum("tk,tke->te", weights, jax.nn.one_hot(local, E + 1)[..., :E])
        out = jnp.einsum("te,etd->td", held, _held_ffn(cfg, xn, lp, rows, T),
                         precision=jax.lax.Precision.HIGHEST)
        return out, _launched_rows(rows, T)

    def bucketed(C):
        def run():
            place, mix = _bucket_slots(local, weights, E, C)
            hi = jax.lax.Precision.HIGHEST
            buckets = jnp.einsum("ts,td->sd", place.astype(xn.dtype), xn, precision=hi,
                                 preferred_element_type=xn.dtype).reshape(E, C, -1)
            outs = _held_ffn(cfg, buckets, lp, counts, T)
            out = jnp.einsum("ts,sd->td", mix, outs.reshape(E * C, -1), precision=hi)
            return out, _launched_rows(counts, C)
        return run

    most = jnp.max(counts)
    if C >= T:
        _note_piece_path(1)
        out, launched = every_row()
    elif T <= 64 or 2 * C >= T:
        # a decode step (the class of at most 64 rows), or no room for a
        # second bucket: the bucket, or every expert over every row
        over = most > C
        _note_piece_path(over)
        out, launched = jax.lax.cond(over, every_row, bucketed(C))
    else:
        # a prompt piece: the bucket; a bucket of twice its rows where some expert
        # overflows the first; every expert over every row where one overflows
        # that too: which of them, from the step's own counts
        level = (most > C).astype(jnp.int32) + (most > 2 * C).astype(jnp.int32)
        _note_piece_path(level == 2)
        out, launched = jax.lax.switch(level, [bucketed(C), bucketed(2 * C), every_row])
    if _launched is not None:
        _launched.append(launched)
    return out


def _moe_share(cfg: LlamaConfig, xn: jax.Array, lp) -> jax.Array:
    """An expert layer that HOLDS a share of the experts: routing, the top k
    and the renormalisation run over the router's whole width; the sum runs
    over the chosen experts held here (``first_expert`` onwards), and what
    the absent ones would add is left out. The shared expert, a dense SwiGLU
    every token takes, is added whole. ``xn`` [T, dim] -> [T, dim] f32. In a
    padded piece (:func:`_real_rows`) the rows past the real ones choose no
    held expert."""
    from distributed_llama_tpu.models.llama import _activation, _matmul

    top_vals, top_idx = router_topk(cfg, xn, lp["router"], lp.get("router_bias"))
    out = jnp.zeros(xn.shape, jnp.float32)
    if cfg.n_experts:
        if _held_counts is not None:
            local = top_idx - cfg.first_expert
            _held_counts.append(
                jnp.sum((local >= 0) & (local < cfg.n_experts), axis=1, dtype=jnp.int32)
            )
        out = _held_experts(cfg, xn, lp, top_vals, top_idx, _share_n_real)
    if "shared_gate_up" in lp:
        fused = _matmul(xn.astype(lp["shared_gate_up"].dtype), lp["shared_gate_up"], "gate_up")
        hidden = lp["shared_down"].shape[-2]
        h = _activation(fused[:, :hidden], cfg.hidden_act) * fused[:, hidden : 2 * hidden]
        out = out + _matmul(h.astype(lp["shared_down"].dtype), lp["shared_down"], "down")
    return out


def moe_ffn(
    cfg: LlamaConfig, xn: jax.Array, lp, axis_name: str | None,
    ep_axis: str | None = None, n_real: jax.Array | None = None,
) -> jax.Array:
    """Expert-mixed SwiGLU. ``xn``: [T, dim] (already normed); returns
    [T, dim] (psum'd over TP shards). With ``ep_axis`` set the expert banks
    in ``lp`` are SHARDED over that mesh axis (device owns E/ep whole
    experts) and the exchange runs in parallel.expert_parallel — the psum
    over ``axis_name`` (hidden-slice partial sums under TP) still applies on
    top. ``n_real`` (bucket-padded prefill): rows at and past it choose no
    expert on the bucketed paths (the expert-parallel exchange and the
    small programs' every-row loop compute pads harmlessly)."""
    if ep_axis is not None:
        from distributed_llama_tpu.parallel.expert_parallel import ep_moe_ffn

        out = ep_moe_ffn(cfg, xn, lp, ep_axis)
    elif cfg.n_routed_experts:
        with _real_rows(n_real):
            out = _moe_share(cfg, xn, lp)
    elif xn.shape[0] == 1:
        out = _moe_topk(cfg, xn, lp)
    else:
        out = _moe_dense(cfg, xn, lp, n_real=n_real)
    if axis_name is not None:
        # the MoE combine rides the same all-reduce seam as the dense FFN
        # (ops.collectives: psum off-TPU, the ICI ring kernel on TPU)
        from distributed_llama_tpu.ops import collectives

        out = collectives.all_reduce(out, axis_name)
    return out


def moe_block(
    cfg: LlamaConfig, x: jax.Array, lp, axis_name: str | None,
    ep_axis: str | None = None, n_real: jax.Array | None = None,
) -> jax.Array:
    """The FFN half of a MoE block, *after* the attention residual has been
    applied by the caller. Handles the Mixtral-vs-Grok norm placement; the
    experts' sum joins the stream as every block's output does (times the
    file's residual multiplier, where it states one)."""
    from distributed_llama_tpu.models.llama import _branch, rmsnorm

    if cfg.arch == ArchType.GROK1:
        xn = rmsnorm(x, lp["rms_moe"])
        out = moe_ffn(cfg, xn, lp, axis_name, ep_axis=ep_axis, n_real=n_real)
        return x + rmsnorm(out.astype(x.dtype), lp["rms_ffn2"])
    xn = rmsnorm(x, lp["rms_ffn"])
    return x + _branch(
        cfg, moe_ffn(cfg, xn, lp, axis_name, ep_axis=ep_axis, n_real=n_real)
    ).astype(x.dtype)
