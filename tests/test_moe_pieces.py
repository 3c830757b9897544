"""A prompt piece's expert layers where the model HOLDS a share of its routed
experts (Solar-Open2's and K-EXAONE's patterns at the toy sizes of
``tests/benchmark``): rows at and past ``n_real`` choose no expert, so the
padding fills no bucket and trips no overflow; what the real rows get is what
they got; a program without ``n_real`` (every decode step) is the parent's,
byte for byte. And what the prefill programs count of it, through the
scheduler, for these and for a model that holds every expert."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark"))

import evabyte_tiny  # noqa: E402
import exaone_tiny  # noqa: E402
import solar_tiny  # noqa: E402
from benchmark.harness import modelfile  # noqa: E402
from distributed_llama_tpu import telemetry  # noqa: E402
from distributed_llama_tpu.engine import InferenceEngine  # noqa: E402
from distributed_llama_tpu.engine import batch  # noqa: E402
from distributed_llama_tpu.engine.batch import BatchScheduler  # noqa: E402
from distributed_llama_tpu.models import llama, moe  # noqa: E402

from tests.test_moe import TestExactBuckets, TestQ40Moe, _program_text  # noqa: E402

padded = TestExactBuckets.rows  # [T, dim] rows: n_real distinct ones, then the padding's identical rows

TOL = 2e-5  # float32 against float32: another order of additions
CONFIGS = {"solar": solar_tiny.CONFIG, "exaone": exaone_tiny.CONFIG, "evabyte": evabyte_tiny.CONFIG}


@pytest.fixture(scope="module")
def engines(tmp_path_factory):
    """name -> engine, built on first use and kept for the module."""
    built = {}

    def get(name):
        if name not in built:
            directory = str(tmp_path_factory.mktemp(name))
            if name in CONFIGS:
                path = modelfile.write_artifacts(CONFIGS[name], 2**31 + 3, directory, 512)[0]
                kw = {"ring_len": 512} if name == "exaone" else {}
                built[name] = InferenceEngine(path, dtype=jnp.float32, cache_dtype=jnp.float32, **kw)
            else:
                from tests.model_utils import random_tensors, tiny_spec, write_model_file

                spec = (TestQ40Moe()._spec(n_experts=8, seq_len=512) if name == "mixtral"
                        else tiny_spec(seq_len=512))
                path = os.path.join(directory, "model.m")
                write_model_file(path, spec, random_tensors(spec, seed=1))
                built[name] = InferenceEngine(path, dtype="q40" if name == "mixtral" else jnp.float32)
        return built[name]

    return get


def expert_layer(engine):
    return next(lp for lp in engine.params["layers"] if "router" in lp)


def pad_row_that_chooses_a_held_expert(cfg, lp, dim):
    for seed in range(200):
        row = np.random.RandomState(seed).randn(1, dim).astype(np.float32)
        _, idx = moe.router_topk(cfg, jnp.asarray(row), lp["router"], lp.get("router_bias"))
        local = np.asarray(idx) - cfg.first_expert
        if ((local >= 0) & (local < cfg.n_experts)).any():
            return row[0]
    raise AssertionError("no row routes to a held expert")


def share(cfg, lp, xn, n_real):
    """(``_moe_share``'s output, 1 where the held experts took every row)."""

    def f(lp, xn, n_real):
        with moe.collect_piece_paths() as paths:
            out = moe.moe_ffn(cfg, xn, lp, None, n_real=n_real)
        return out, paths[0]

    out, every_row = jax.jit(f)(lp, jnp.asarray(xn), None if n_real is None else jnp.int32(n_real))
    return np.asarray(out), int(every_row)


def off(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("share_of", ["all", "two_thirds", "one_third", "one"])
@pytest.mark.parametrize("T", [64, 256])
@pytest.mark.parametrize("arch", ["solar", "exaone"])
def test_real_rows_get_what_they_got_and_pad_rows_fill_no_bucket(engines, arch, T, share_of):
    engine = engines(arch)
    cfg, lp = engine.cfg, expert_layer(engine)
    n_real = {"all": T, "two_thirds": 2 * T // 3, "one_third": T // 3, "one": 1}[share_of]
    xn = padded(T, n_real, cfg.dim, seed=T)
    xn[n_real:] = pad_row_that_chooses_a_held_expert(cfg, lp, cfg.dim)
    got, every_row = share(cfg, lp, xn, n_real)
    want, unmasked = share(cfg, lp, xn, None)  # the parent's program: every row counts
    assert off(got[:n_real], want[:n_real]) <= TOL
    np.testing.assert_array_equal(got[:n_real].argmax(-1), want[:n_real].argmax(-1))
    C = moe.held_bucket_rows(cfg, T)
    assert C < T and every_row == 0
    # the case the parent fails: the padding alone overflows the bucket of the expert it chose
    assert unmasked == (1 if T - n_real > C else 0)


@pytest.mark.parametrize("arch", ["solar", "exaone"])
def test_a_held_expert_with_more_real_rows_than_its_bucket_takes_every_row(engines, arch):
    engine = engines(arch)
    cfg, lp = engine.cfg, expert_layer(engine)
    T, n_real = 256, 200
    xn = padded(T, n_real, cfg.dim, seed=3)
    top_idx = jnp.full((T, cfg.n_active_experts), cfg.first_expert, jnp.int32)  # rigged: all on one
    top_idx = top_idx.at[:, 1:].set(cfg.first_expert + 1 + jnp.arange(cfg.n_active_experts - 1))
    top_vals = jnp.full((T, cfg.n_active_experts), 1.0 / cfg.n_active_experts)

    def f(lp, xn, n_real):
        with moe.collect_piece_paths() as paths:
            out = moe._held_experts(cfg, xn, lp, top_vals, top_idx, n_real)
        return out, paths[0]

    got, every_row = jax.jit(f)(lp, jnp.asarray(xn), jnp.int32(n_real))
    want, _ = jax.jit(f)(lp, jnp.asarray(xn), None)
    assert int(every_row) == 1 and n_real > moe.held_bucket_rows(cfg, T)
    assert off(np.asarray(got)[:n_real], np.asarray(want)[:n_real]) <= TOL
    assert not np.asarray(got)[n_real:].any()  # a pad row chose no expert


@pytest.mark.parametrize("T", [4, 16, 64, 256])
@pytest.mark.parametrize("arch", ["solar", "exaone"])
def test_without_n_real_the_held_experts_lower_to_the_parents_text(engines, arch, T):
    """A decode step passes no ``n_real``: its program is the parent's."""
    engine = engines(arch)
    cfg, lp = engine.cfg, expert_layer(engine)
    E = cfg.n_experts

    def parents(lp, xn, top_vals, top_idx):  # models/moe.py's _held_experts before n_real
        local = top_idx - cfg.first_expert
        is_held = (local >= 0) & (local < E)
        local = jnp.where(is_held, local, E)
        weights = jnp.where(is_held, top_vals, 0.0)
        counts = jnp.sum(jax.nn.one_hot(local, E + 1, dtype=jnp.int32), axis=(0, 1))[:E]
        on = counts > 0
        C = moe.held_bucket_rows(cfg, T)

        def every_row():
            held = jnp.einsum("tk,tke->te", weights, jax.nn.one_hot(local, E + 1)[..., :E])
            return jnp.einsum("te,etd->td", held, moe._held_ffn(cfg, xn, lp, on, T),
                              precision=jax.lax.Precision.HIGHEST)

        if C >= T:
            return every_row()

        def bucketed():  # a bucket's rows by one matmul each way since PR 52 (moe._bucket_slots)
            place, mix = moe._bucket_slots(local, weights, E, C)
            hi = jax.lax.Precision.HIGHEST
            buckets = jnp.einsum("ts,td->sd", place.astype(xn.dtype), xn, precision=hi,
                                 preferred_element_type=xn.dtype).reshape(E, C, -1)
            return jnp.einsum("ts,sd->td", mix, moe._held_ffn(cfg, buckets, lp, on, T).reshape(E * C, -1),
                              precision=hi)

        return jax.lax.cond(jnp.max(counts) > C, every_row, bucketed)

    xn = jnp.asarray(padded(T, T, cfg.dim, seed=1))
    top_vals, top_idx = moe.router_topk(cfg, xn, lp["router"], lp.get("router_bias"))
    args = (lp, xn, top_vals, top_idx)

    def ours(lp, xn, v, i):
        return moe._held_experts(cfg, xn, lp, v, i)

    if T > 64 and 2 * moe.held_bucket_rows(cfg, T) < T:
        # 256 rows are a piece's, and a piece's program did move in PR 43 (a bucket of twice the
        # rows between the bucket and every row): not the parent's text, the parent's numbers
        assert _program_text(ours, *args) != _program_text(parents, *args)
        assert off(np.asarray(jax.jit(ours)(*args)), np.asarray(jax.jit(parents)(*args))) <= TOL
        return
    assert _program_text(ours, *args) == _program_text(parents, *args)


@pytest.mark.parametrize("arch", ["dense", "evabyte"])
def test_a_model_without_expert_layers_never_enters_the_expert_code(engines, arch, monkeypatch):
    """Its prefill and decode programs are the parent's: nothing of
    ``models.moe`` but the collectors is reached, asking for the layers'
    paths adds nothing to the text, and the prefill program returns no
    third value."""
    engine = engines(arch)
    cfg, params = engine.cfg, engine.params
    for name in ("moe_block", "moe_ffn", "_note_piece_path"):
        monkeypatch.setattr(moe, name, lambda *a, **k: pytest.fail(f"{name} reached"))
    slab = llama.init_batch_cache(cfg, 2, dtype=jnp.float32)
    tokens, zero, n = jnp.zeros(64, jnp.int32), jnp.int32(0), jnp.int32(21)

    def prefill(paths):
        def f(params, tokens, cache, pos, n_real):
            return llama.forward_tokens(cfg, params, tokens, cache, pos, n_real=n_real, piece_paths=paths)

        row = [leaf if leaf is None else jax.tree.map(lambda a: a[:, 0], leaf) for leaf in slab]
        return _program_text(f, params, tokens, row, zero, n)

    asked = []
    assert prefill(asked) == prefill(None) and asked == []
    lowered = batch._slab_prefill_single.lower(cfg, params, tokens, slab, zero, zero, n)
    assert jax.tree.structure(lowered.out_info[2]).num_leaves == 0  # None: nothing to read
    assert "stablehlo.case" not in lowered.as_text()
    ones = jnp.ones(2, bool)
    decode = _program_text(
        lambda p, t, c, pos, act: llama.forward_step_batched(cfg, p, t, c, pos, act),
        params, jnp.zeros(2, jnp.int32), slab, jnp.zeros(2, jnp.int32), ones)
    assert "stablehlo.case" not in decode


@pytest.fixture
def enabled():
    """Telemetry on before the scheduler binds its instruments, clean afterwards."""
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.disable()
    telemetry.reset()


def piece_layers() -> dict:
    c = telemetry.REGISTRY.get("dllama_moe_piece_layers_total")
    return {p: c.labels(path=p).value for p in ("bucketed", "every_row")}


def decode(stream, logits_row, n):
    toks = []

    def on_token(prev, tok):
        toks.append(tok)
        return len(toks) < n

    stream.stream_decode(int(np.argmax(logits_row)), on_token, 0.0, 0.9, seed=1, limit=stream.pos + n,
                         first_prev=0)
    return toks


@pytest.mark.parametrize("arch", ["mixtral", "solar", "exaone"])
def test_the_scheduler_counts_a_pieces_expert_layers_by_path(engines, enabled, monkeypatch, arch):
    """70 tokens: one piece of 64 rows, all real (bucketed in every expert
    layer), and one of 6 padded to 8 (too small to bucket: every row). The
    counts are read when a decode chunk is delivered: with a wait where the
    held-choice sum is read anyway, and only if complete where nothing else
    is read (a model that holds every expert)."""
    engine = engines(arch)
    monkeypatch.setattr(engine, "_tel", telemetry.EngineInstruments())  # bound while telemetry is on
    sched = BatchScheduler(engine, n_rows=2, chunk=4, prefill_chunk=64)
    stream = sched.new_stream()
    prompt = np.random.default_rng(5).integers(3, engine.cfg.vocab_size, 70).tolist()
    logits = stream.prefill(prompt)
    assert len(sched._moe_pending) == 2
    jax.block_until_ready([m for m, _ in sched._moe_pending])
    decode(stream, logits, 6)
    layers = sum("router" in lp for lp in engine.params["layers"])
    assert sched._moe_pending == []
    assert piece_layers() == {"bucketed": layers, "every_row": layers}


def test_a_piece_not_yet_complete_is_left_for_the_next_delivery(engines, enabled, monkeypatch):
    """Where nothing else is read at a delivery, the counts add no wait: the
    pieces complete in dispatch order, and from the first that is not
    complete on they stay pending."""
    engine = engines("mixtral")
    monkeypatch.setattr(engine, "_tel", telemetry.EngineInstruments())
    sched = BatchScheduler(engine, n_rows=2, chunk=4, prefill_chunk=64)

    class Queued:
        def is_ready(self):
            return False

        def __array__(self, *a, **k):
            raise AssertionError("read before it was complete")

    done, queued = jnp.asarray([0, 1, 3], jnp.int32), Queued()
    sched._moe_pending = [(done, 64), (queued, 64), (done, 8)]
    sched._count_prefill_moe(wait=False)
    assert sched._moe_pending == [(queued, 64), (done, 8)]
    assert piece_layers() == {"bucketed": 3, "every_row": 1}


@pytest.mark.parametrize("routing,every_row", [("even", 0), ("one_expert", 1)])
def test_the_decode_chunk_returns_the_layer_steps_that_took_every_row(engines, monkeypatch, routing, every_row):
    """A 64-row step at 2 of 16 holds a bucket of 32 and the every-row arm (one
    ``cond`` a layer): the chunk's scan returns, beside the rows' held choices,
    how many of its expert layer-steps took every row, from the steps' own
    counts: none where the rows go round the experts evenly, every one where
    all 64 rows choose one held expert."""
    from distributed_llama_tpu.models import sampling

    engine = engines("solar")
    cfg, B, steps = engine.cfg, 64, 2
    assert moe.held_bucket_rows(cfg, B) == 32 and cfg.n_active_experts == 2 and cfg.router_width == 16

    def router_topk(cfg, xn, router, bias=None):
        T = xn.shape[0]
        first = 2 * jnp.arange(T) % 16 if routing == "even" else jnp.full(T, cfg.first_expert)
        return jnp.full((T, 2), 0.5), jnp.stack([first, first + 1], axis=1).astype(jnp.int32)

    monkeypatch.setattr(moe, "router_topk", router_topk)
    zeros = jnp.zeros(B, jnp.int32)

    def chunk(params, cache):  # a jit of its own: traced here, under the planted routing
        return sampling.batched_decode_scan(
            cfg, params, jnp.arange(B, dtype=jnp.int32) + 5, cache, zeros, jnp.ones(B, bool),
            zeros.astype(jnp.uint32), steps, jnp.zeros(B), jnp.full(B, 0.9), zeros)

    _, _, _, _, held, whole, launched = jax.jit(chunk)(
        engine.params, llama.init_batch_cache(cfg, B, dtype=jnp.float32))
    layers = sum("router" in lp for lp in engine.params["layers"])
    # one number in every column of its row of the bundle
    assert np.asarray(whole).tolist() == [every_row * layers * steps] * B
    # the rows the grouped launches multiplied: the 4 held experts' buckets of 32 rows (one row
    # tile each, 8 rows chose it), or all 64 rows in the 2 experts every row chose
    assert np.asarray(launched).tolist() == [layers * steps * (2 * 64 if every_row else 4 * 32)] * B
    # 4 of 16 experts held: a quarter of the even choices, both of a row's where all choose them
    assert int(np.asarray(held).sum()) == layers * steps * (2 * B if every_row else B // 2)


@pytest.mark.parametrize("chosen", ["one_held_expert", "no_held_expert"])
def test_the_decode_rows_counter_follows_the_chunks_own_arm(engines, enabled, monkeypatch, chosen):
    """``dllama_moe_expert_rows_total{phase="decode"}`` is fed from the row the
    chunk returns, not from the rule: one stream in the LAST of 64 rows decodes
    in the 64-row program (bucket 32, or every row where one overflows). A
    router bias that sends every row, the 63 idle ones too, to one held expert
    overflows it in every layer-step, and every held expert is counted over
    all 64 rows; one that sends them all to absent experts overflows nothing
    and computes nothing."""
    engine = engines("solar")
    cfg = engine.cfg
    monkeypatch.setattr(engine, "_tel", telemetry.EngineInstruments())
    to = cfg.first_expert if chosen == "one_held_expert" else 0
    bias = jnp.zeros(cfg.router_width, jnp.float32).at[to:to + cfg.n_active_experts].set(100.0)
    monkeypatch.setitem(engine.params, "layers", [
        {**lp, "router_bias": bias} if "router" in lp else lp for lp in engine.params["layers"]])
    sched = BatchScheduler(engine, n_rows=64, chunk=2)
    stream = [sched.new_stream() for _ in range(64)][-1]
    decode(stream, stream.prefill([5, 6, 7]), 4)
    rows = telemetry.REGISTRY.get("dllama_moe_expert_rows_total")
    computed, took, launched = (rows.labels(rows=r, phase="decode").value for r in ("computed", "chosen", "launched"))
    layers = sum("router" in lp for lp in engine.params["layers"])
    # the prompt's piece feeds the same three: what the launches multiplied lies between the rows
    # that chose a held expert and every held expert over every row
    piece = [rows.labels(rows=r, phase="piece").value for r in ("chosen", "launched", "computed")]
    assert piece == sorted(piece) and (piece[1] > 0) == (chosen == "one_held_expert")
    if chosen == "no_held_expert":
        assert (computed, took, launched) == (0, 0, 0)
        return
    # whole chunks of 2 steps, every layer-step on the every-row arm: 4 held experts x 64 rows
    chunks = computed / (layers * 2 * cfg.n_experts * 64)
    assert chunks >= 2 and chunks == int(chunks)
    # the one live row's choices, both on held experts, in every layer of every step
    assert took == chunks * 2 * layers * cfg.n_active_experts
    # ... and the launches multiplied all 64 rows in the two experts the rows chose, not in all four
    assert launched == chunks * 2 * layers * cfg.n_active_experts * 64
