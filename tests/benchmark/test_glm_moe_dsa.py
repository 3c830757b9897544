"""GLM-5 (``glm_moe_dsa``) at a toy size on the CPU, WITH AN ``index_topk``
SMALLER THAN THE PROMPTS, so that the selection cuts: the program's engine
against the family's plain EXPANDED reference through every path a served row
takes (prefill, prefill in pieces, decode through the slab, the blocked scans
of a longer cache, a bucket with a masked row, a prefix hit whose pool pages
restore the index keys), that the bit-by-bit selection is the plain top k,
what a position costs the cache, the programs' counts of what a query could
see, scored and attended, the test that ties the held share to the uncut
layer, what refuses by name, that GLM-4.7-Flash's file stays what it was,
that a planted fault in the selection fails, and the cell through the
harness."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import glm5_tiny
import glm_tiny
import tiny_root
from benchmark import families
from benchmark.harness import modelfile
from benchmark.reference.qfile import QFile
from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.models import llama, moe
from distributed_llama_tpu.ops import attention as attn_ops
from distributed_llama_tpu.ops import kv_cache as kvc

CONFIG = glm5_tiny.CONFIG
PAGE = 8
LATENT = CONFIG["kv_lora_rank"] + CONFIG["qk_rope_head_dim"]  # 40 values a position and layer
INDEX = CONFIG["index_head_dim"]  # 16 more
TOPK = CONFIG["index_topk"]  # 48: every prompt below is longer
# float32 against float32: what is left is rounding (measured 4e-7 of max|logit|)
TOL = 2e-5
RNG = np.random.default_rng(11)
PROMPT = RNG.integers(300, 16000, 150).tolist()


def tail(n):
    return RNG.integers(300, 16000, n).tolist()


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    directory = str(tmp_path_factory.mktemp("glm5"))
    return modelfile.write_artifacts(CONFIG, 2**31 + 3, directory, 4096)[0]


@pytest.fixture(scope="module")
def reference(model):
    ref = families.load(CONFIG, "reference")
    qf = QFile(model, ref)

    def logits(tokens, gaps=None, selection_gaps=None):
        return ref.forward(qf, np.asarray([tokens], np.int32), np.arange(len(tokens)), gaps,
                           selection_gaps)[0]

    return logits


@pytest.fixture(scope="module")
def engine(model):
    return InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32, max_seq_len=512)


def scheduler(engine, rows=2, prefill_chunk=32, kv_pages=64, **kw):
    return BatchScheduler(engine, n_rows=rows, chunk=4, prefix_cache=True, kv_pages=kv_pages,
                          page_size=PAGE, prefill_chunk=prefill_chunk, **kw)


def off(got, want) -> float:
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def decode(stream, logits_row, n):
    """Greedy decode of ``n`` tokens after a prefill that returned ``logits_row``."""
    toks = []

    def on_token(prev, tok):
        toks.append(tok)
        return len(toks) < n

    stream.stream_decode(int(np.argmax(logits_row)), on_token, 0.0, 0.9, seed=1,
                         limit=stream.pos + n, first_prev=0)
    return toks


def deficits(reference, prompt, answer):
    """Teacher-forced, as the benchmark's check does it: how far each served
    token lies below the reference's best for the same context, as a share
    of max|logit|."""
    rows = reference(prompt + answer)[len(prompt) - 1:-1]
    return [float(r.max() - r[t]) / float(np.abs(r).max()) for r, t in zip(rows, answer)]


def test_the_table_of_layer_kinds_and_the_indexers_facts(engine):
    cfg = engine.cfg
    assert [cfg.layer_kind(l) for l in range(4)] == [("latent", "dense")] + [("latent", "experts")] * 3
    assert (cfg.latent_dim, cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (LATENT, 4, INDEX, TOPK)
    assert (cfg.n_experts, cfg.router_width, cfg.first_expert, cfg.routed_scale) == (4, 16, 4, 2.5)
    assert cfg.has_latent and cfg.has_indexer and cfg.rewinds_by_position
    assert cfg.kv_read_kinds == ("latent", "index", "latent_selected", "dsa_visible")
    # the indexer's matrices ride the latent layer's two launches: no leaf of their own
    lp = engine.params["layers"][0]
    assert lp["qkv_a"].shape == (64, 32 + LATENT + INDEX + 4) and lp["q_b"].shape == (32, 4 * 32 + 4 * INDEX)
    assert lp["index_k_norm"].shape == (2, INDEX) and not any(k.startswith("index_") and k != "index_k_norm" for k in lp)
    # the sibling file of the same arch has no indexer and says so through the same table
    old = glm_tiny.CONFIG
    from distributed_llama_tpu.models.config import config_from_spec
    sibling = config_from_spec(families.load(old, "modelfile").model_spec(old, 512))
    assert sibling.arch == cfg.arch and not sibling.has_indexer and sibling.kv_read_kinds == ("latent",)


def test_a_rows_cache_is_a_latent_row_and_an_index_key_a_position(engine):
    cfg = engine.cfg
    slab = jax.eval_shape(lambda: llama.init_batch_cache(cfg, 3, dtype=jnp.bfloat16))
    pool = jax.eval_shape(lambda: llama.init_page_pool(cfg, 10, PAGE, dtype=jnp.bfloat16))
    assert [(leaf[kvc.LATENT].shape, leaf[kvc.INDEX].shape) for leaf in slab] == [
        ((3, LATENT, 512), (3, INDEX, 512))] * 4  # positions minor, both
    assert [tuple(h.shape for h in halves) for halves in pool] == [((10, PAGE * LATENT), (10, PAGE * INDEX))] * 4
    assert all(kvc.leaf_arrays(leaf) == (kvc.LATENT, kvc.INDEX) for leaf in slab)
    assert llama.page_pool_bytes(cfg, PAGE, jnp.bfloat16) == 4 * PAGE * (LATENT + INDEX) * 2
    assert llama.kv_slab_bytes(cfg, 3, jnp.bfloat16) == {"latent": 3 * 512 * 4 * LATENT * 2,
                                                          "index": 3 * 512 * 4 * INDEX * 2}


@pytest.mark.parametrize("case", ["prefill alone", "prefill in pieces of 8",
                                  "prefill then decode", "a bucket with a masked row",
                                  "the blocked scans of a cache of 4096 positions"])
def test_engine_against_the_reference(engine, model, reference, case):
    gaps = []
    want = reference(PROMPT, selection_gaps=gaps)
    # the selection cuts: two thirds of the prompt's positions see more than index_topk
    assert all(np.isfinite(g[0, TOPK:]).all() and np.isinf(g[0, :TOPK]).all() for g in gaps) and len(gaps) == 4
    if case == "prefill alone":
        assert off(engine.new_stream().prefill(PROMPT), want[-1]) <= TOL
        return
    if case.startswith("the blocked scans"):
        # 4096 positions: a piece and a decode step read the row a chunk of 2048 positions (the
        # indexer of a piece: of 512) at a time with a dynamic bound, as at the served 16384
        engine = InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32)
        assert engine.cfg.seq_len == 4096
    sched = scheduler(engine, prefill_chunk=8 if "pieces" in case else 32)
    s0, s1 = sched.new_stream(), sched.new_stream()
    got = s0.prefill(PROMPT)
    assert off(got, want[-1]) <= TOL
    if "pieces" in case:
        return
    if case == "a bucket with a masked row":
        other = tail(70)
        got1 = s1.prefill(other)
        assert max(deficits(reference, PROMPT, decode(s0, got, 9))) <= TOL
        # ... and row 1's latents and index keys were not touched by the chunks it sat out
        assert max(deficits(reference, other, decode(s1, got1, 6))) <= TOL
        return
    assert max(deficits(reference, PROMPT, decode(s0, got, 30))) <= TOL


@pytest.mark.parametrize("case", ["distinct scores", "a tie at the cut", "fewer than k", "exactly k",
                                  "all equal", "with -inf behind the visible ones", "negative zero"])
def test_the_bit_by_bit_selection_is_the_plain_top_k(case):
    """``dsa_select`` finds the k-th largest score from its top bit down and
    keeps what lies above it and, of the scores equal to it, the earliest:
    ``jax.lax.top_k``'s set (which breaks ties towards the lower index too)."""
    rng = np.random.default_rng(7)
    k, n = 48, 300
    x = rng.standard_normal((3, 2, n)).astype(np.float32) * 40
    if case == "a tie at the cut":
        x[..., ::3] = np.float32(1.25)  # a hundred equal scores straddle the cut
    elif case == "fewer than k":
        x, n = x[..., :31], 31
    elif case == "exactly k":
        x, n = x[..., :k], k
    elif case == "all equal":
        x[:] = np.float32(-3.5)
    elif case.startswith("with -inf"):
        x[..., 60:] = -np.inf  # 60 visible
    elif case == "negative zero":
        x[..., :100] = np.where(rng.random((3, 2, 100)) < 0.5, np.float32(-0.0), np.float32(0.0))
        x[..., 100:] = -np.abs(x[..., 100:])  # the cut falls among the zeros
    got = np.asarray(attn_ops.dsa_select(jnp.asarray(x), k))
    assert (got.sum(-1) == min(k, n)).all()
    if case == "negative zero":
        # -0.0 orders below +0.0 here and equal to it in top_k: the kept VALUES are the same
        assert (np.take_along_axis(x, np.argsort(~got, axis=-1, kind="stable")[..., :k], -1) == 0).all()
        return
    _, idx = jax.lax.top_k(jnp.asarray(x), min(k, n))
    want = np.zeros(x.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    np.testing.assert_array_equal(got, want)


def test_a_prefix_hit_restores_the_index_keys_with_the_latents(engine, reference):
    sched = scheduler(engine)
    s0, s1 = sched.new_stream(), sched.new_stream()
    got = s0.prefill(PROMPT)  # 18 whole pages published
    assert s0.matched_len == 0 and off(got, reference(PROMPT)[-1]) <= TOL
    # a published page holds its block's rows of BOTH arrays, in every layer
    chain = sched._prefix.walk(PROMPT + [0])
    assert len(chain) == 18
    for block in (0, 7, 17):
        for leaf, halves in zip(sched._slab, sched._pool):
            for name, page, dim in zip((kvc.LATENT, kvc.INDEX), halves, (LATENT, INDEX)):
                np.testing.assert_array_equal(
                    np.asarray(page[chain[block].page_id]).reshape(PAGE, dim),
                    np.asarray(leaf[name][s0.row, :, block * PAGE : (block + 1) * PAGE]).T)
                assert float(jnp.abs(page[chain[block].page_id]).max()) > 0
    # a second ask over the same head: 17 pages are COPIED into its row, index keys and all,
    # and the rest is prefilled: its pieces' indexers score the restored keys
    ask = PROMPT[:140] + tail(9)
    got = s1.prefill(ask)
    assert s1.matched_len == 17 * PAGE and off(got, reference(ask)[-1]) <= TOL
    for leaf in sched._slab:
        for name in (kvc.LATENT, kvc.INDEX):
            np.testing.assert_array_equal(np.asarray(leaf[name][s1.row, :, : 17 * PAGE]),
                                          np.asarray(leaf[name][s0.row, :, : 17 * PAGE]))
    # ... and decodes on from the copied rows and its own suffix
    assert max(deficits(reference, ask, decode(s1, got, 20))) <= TOL
    sched.check_prefix()


def test_a_chat_continues_from_a_rewound_row(engine, reference):
    s = scheduler(engine).new_stream()
    s.prefill(PROMPT)
    s.rollback(100)
    turn = PROMPT[:100] + tail(30)
    got = s.prefill(turn[100:])
    assert off(got, reference(turn)[-1]) <= TOL
    assert max(deficits(reference, turn, decode(s, got, 8))) <= TOL


def test_a_decode_step_counts_what_it_could_see_scored_and_attended(engine, model):
    """The programs' own counts, per row over the step's four layers: the
    latent rows the masked scan read, the index keys scored, the rows the
    softmax ran over (``index_topk`` where the row sees more) and the
    positions the row could see; the scheduler turns them into the series
    the cell's entries divide."""
    from distributed_llama_tpu import telemetry

    cfg = engine.cfg
    slab = llama.init_batch_cache(cfg, 2, dtype=jnp.float32)
    out = {}
    llama.forward_step_batched(cfg, engine.params, jnp.asarray([5, 6]), slab, jnp.asarray([140, 3]),
                               jnp.asarray([True, True]), kv_reads=out)
    assert {k: np.asarray(v).tolist() for k, v in out.items()} == {
        "latent": [4 * cfg.seq_len] * 2, "index": [4 * cfg.seq_len] * 2,
        "latent_selected": [4 * TOPK, 4 * 4], "dsa_visible": [4 * 141, 4 * 4]}
    # no row past index_topk: every visible position is attended and the indexer is not run
    out = {}
    llama.forward_step_batched(cfg, engine.params, jnp.asarray([5, 6]), slab, jnp.asarray([40, 3]),
                               jnp.asarray([True, False]), kv_reads=out)
    assert np.asarray(out["index"]).tolist() == [0, 0]
    assert np.asarray(out["latent_selected"]).tolist() == [4 * 41, 0]
    assert np.asarray(out["dsa_visible"]).tolist() == [4 * 41, 0]
    telemetry.enable()
    try:
        sched = scheduler(InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.bfloat16,
                                          max_seq_len=512))
        assert sched._kv_bytes_by_kind == {kvc.LATENT: LATENT * 2, kvc.INDEX: INDEX * 2}
        s = sched.new_stream()

        def read(name, kind=None):
            if kind is None:
                return telemetry.REGISTRY.counter(name, "").value
            return telemetry.REGISTRY.counter(name, "", ("kind",)).labels(kind=kind).value

        names = [("dllama_attn_kv_read_bytes_total", k) for k in ("latent", "index", "latent_selected")] + [
            ("dllama_attn_kv_read_positions_total", k) for k in ("latent", "index", "latent_selected")] + [
            ("dllama_dsa_visible_positions_total", None)]
        before = [read(*n) for n in names]
        decode(s, s.prefill(PROMPT[:100]), 9)
        lat_b, idx_b, sel_b, lat_p, idx_p, sel_p, visible = (read(*n) - b for n, b in zip(names, before))
        # what the benchmark's entries divide: layers x values x 2 B a position
        assert lat_b / lat_p == 4 * LATENT * 2 and idx_b / idx_p == 4 * INDEX * 2 and sel_b / sel_p == 4 * LATENT * 2
        # 8 steps at positions 100 .. 107 (the ninth token's step is the next chunk's)
        steps = visible / 104.5
        assert steps == pytest.approx(round(steps)) and sel_p == round(steps) * TOPK
        assert 0.44 < sel_p / visible < 0.48  # dsa_selected_share: 48 of 101 .. 108
    finally:
        telemetry.disable()


@pytest.mark.parametrize("what", ["the host spill tier", "an i8 cache", "--tp 2", "--spec-draft",
                                  "speculative verify"])
def test_paths_that_have_no_form_for_two_arrays_or_a_window_of_tokens_refuse_by_name(engine, model, what):
    with pytest.raises(llama.LatentCacheError, match="GLM4_MOE_LITE.*one row of 40 values a position"):
        if what == "the host spill tier":
            scheduler(engine, rows=1, kv_pages=20, host_spill_bytes=4 << 20)
        elif what == "an i8 cache":
            InferenceEngine(model, dtype=jnp.float32, cache_dtype="i8", max_seq_len=512).new_stream()
        elif what == "--tp 2":
            InferenceEngine(model, dtype=jnp.float32, tp=2)
        elif what == "--spec-draft":
            scheduler(engine, spec_draft=4)
        else:
            slab = llama.init_batch_cache(engine.cfg, 2, dtype=jnp.float32)
            llama.forward_verify_batched(engine.cfg, engine.params, jnp.zeros((2, 3), jnp.int32), slab,
                                         jnp.zeros(2, jnp.int32), jnp.ones(2, bool))


def test_the_indexers_keys_are_optional_and_the_siblings_file_is_what_it_was(tmp_path, model):
    from distributed_llama_tpu.formats.model_file import HeaderKey, _header_pairs, read_spec, tensor_layout

    new = read_spec(model)
    assert (new.index_n_heads, new.index_head_dim, new.index_topk) == (4, INDEX, TOPK)
    assert (new.n_experts, new.n_routed_experts, new.first_expert, new.routed_scale_milli) == (4, 16, 4, 2500)
    keys = [int(k) for k, _ in _header_pairs(new)]
    assert keys[-3:] == [HeaderKey.INDEX_N_HEADS, HeaderKey.INDEX_HEAD_DIM, HeaderKey.INDEX_TOPK]
    names = [e.name.split(".", 2)[2] for e in tensor_layout(new) if e.name.startswith("layers.0.")]
    assert names[names.index("wo") + 1 : names.index("wo") + 5] == ["index_q", "index_k", "index_k_norm", "index_w"]
    # GLM-4.7-Flash's file (the same arch, no indexer): no new key, no new tensor, the bytes of before
    old = glm_tiny.CONFIG
    spec = families.load(old, "modelfile").model_spec(old, 512)
    assert max(int(k) for k, _ in _header_pairs(spec)) == HeaderKey.V_HEAD_DIM
    assert not any("index" in e.name for e in tensor_layout(spec))
    path, _ = modelfile.write_artifacts(old, 7, str(tmp_path), 512)
    import hashlib
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == SIBLING_FILE_SHA256
    # a program from before the keys refuses the family at once, by name, before a byte is written
    from distributed_llama_tpu.formats import model_file
    from unittest import mock
    with mock.patch.object(model_file, "HeaderKey", type("HeaderKey", (), {})):
        with pytest.raises(ValueError, match="glm_moe_dsa.*cannot build or serve"):
            families.load(CONFIG, "modelfile").model_spec(CONFIG, 512)


# sha256 of tests/benchmark/glm_tiny.py's file from seed 7 at 512 positions, written by the parent
# commit's tree (772837b): the accepted family's files are byte for byte what they were
SIBLING_FILE_SHA256 = "9c27fadcbc508531f3566731268e9c08f1a81cc0f34f709532df5c06c3ad7e80"


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(engine):
    """The routed parts that all four shares of 4 experts give (the factor 2.5
    in each, once a token's weight), plus the shared expert counted once,
    equal the layer that holds all 16: behind a latent mixer as behind any."""
    cfg, rng = engine.cfg, np.random.default_rng(5)
    D, F, E = cfg.dim, cfg.moe_hidden_dim, cfg.n_routed_experts
    assert (E, cfg.n_experts) == (16, 4)
    mat = lambda *shape: jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[0]), jnp.float32)
    gate_up, down = mat(E, D, 2 * F) * 4, mat(E, F, D) * 4
    bank = lambda lo, hi: {"experts_gate_up": gate_up[lo:hi], "experts_down": down[lo:hi]}
    lp = {"router": mat(D, E) * 4, "router_bias": jnp.asarray(0.02 * rng.standard_normal(E), jnp.float32),
          "shared_gate_up": mat(D, 2 * F), "shared_down": mat(F, D)}
    xn = jnp.asarray(rng.standard_normal((12, D)), jnp.float32)
    routed_only = {k: v for k, v in lp.items() if not k.startswith("shared")}
    whole = moe._moe_share(dataclasses.replace(cfg, n_experts=E, first_expert=0), xn, {**lp, **bank(0, E)})
    parts = sum(
        moe._moe_share(dataclasses.replace(cfg, first_expert=first), xn,
                       {**routed_only, **bank(first, first + cfg.n_experts)})
        for first in range(0, E, cfg.n_experts))
    shared = moe._moe_share(dataclasses.replace(cfg, n_experts=0), xn, lp)
    np.testing.assert_allclose(parts + shared, whole, rtol=1e-5, atol=2e-4)
    assert float(jnp.abs(parts).max()) > 0.1


def _with(monkeypatch, fault):
    if fault == "the most recent k instead of the best k":
        def recent(scores, k):
            seen = jnp.isfinite(scores)
            return seen & (jnp.cumsum(seen[..., ::-1].astype(jnp.int32), axis=-1)[..., ::-1] <= k)

        monkeypatch.setattr(attn_ops, "dsa_select", recent)
    elif fault == "index keys and index heads left unrotated":
        monkeypatch.setattr(llama, "_rope_head", lambda x, rows, cfg: x)
    elif fault == "the index key's LayerNorm without its bias":
        real = llama._layernorm
        monkeypatch.setattr(llama, "_layernorm", lambda x, wb, eps=1e-6: real(x, wb.at[1].set(0.0), eps))
    elif fault == "the index heads unweighted":
        real = attn_ops.dsa_index_scores
        monkeypatch.setattr(attn_ops, "dsa_index_scores",
                            lambda q, w, pos, keys, chunk: real(q, jnp.ones_like(w), pos, keys, chunk))
    elif fault == "no selection: every visible position attended":
        monkeypatch.setattr(attn_ops, "dsa_select", lambda scores, k: jnp.isfinite(scores))
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", ["the most recent k instead of the best k",
                                   "index keys and index heads left unrotated",
                                   "the index key's LayerNorm without its bias",
                                   "the index heads unweighted",
                                   "no selection: every visible position attended"])
def test_a_planted_fault_in_the_selection_fails_the_tolerance(model, reference, monkeypatch, fault):
    from benchmark.harness.cell import load_check

    _with(monkeypatch, fault)
    jax.clear_caches()
    try:
        got = InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32,
                              max_seq_len=512).new_stream().prefill(PROMPT)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    # not by float32's tolerance, and not by the benchmark's for the served Q40 path either
    assert off(got, reference(PROMPT)[-1]) > load_check()["logit_tol"]


def test_how_far_a_flipped_selection_at_the_cut_moves_the_logits(model, reference, monkeypatch):
    """A tie at rank ``index_topk`` may fall either way. With the LAST selected
    position of every query swapped for the FIRST one left out, in every
    layer and at every position (far more than a rounding of the scores does:
    it flips the few queries whose cut is a near-tie), the last logits move by
    5.1e-2 of max|logit| at ``index_topk`` 48: a forty-eighth of every query's
    softmax replaced. At the published 2048 a swap is one row of 2048 (PERF.md
    section 6, PR 53, has the reading at that width)."""
    real = attn_ops.dsa_select

    def flipped(scores, k):
        return real(scores, k + 1) & ~(real(scores, k) & ~real(scores, k - 1))

    want = reference(PROMPT)[-1]
    monkeypatch.setattr(attn_ops, "dsa_select", flipped)
    jax.clear_caches()
    try:
        got = InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32,
                              max_seq_len=512).new_stream().prefill(PROMPT)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    moved = off(got, want)
    assert 1e-3 < moved < 1e-1, moved


def test_q40_engine_stays_inside_the_benchmarks_logit_tolerance(model, reference):
    from benchmark.harness.cell import load_check

    want = reference(PROMPT[:80])
    stream = InferenceEngine(model, dtype="q40", max_seq_len=512).new_stream()
    offs = [off(stream.prefill([tok]), want[i]) for i, tok in enumerate(PROMPT[:80])]
    tol = load_check()["logit_tol"]
    assert np.median(offs) <= tol / 2 and np.mean(np.asarray(offs) <= tol) >= 0.8, offs


def test_the_cell_runs_through_the_harness_on_the_cpu(tmp_path, monkeypatch):
    """The toy configuration as a cell of the miniature checkout (documents
    asked twice), through ``run_cell`` with ``--trace 2``: the family's
    builder, the server child, the probes (one of them answered past
    ``index_topk``) judged by the family's expanded reference, warm-up,
    window, drain, the traced phase: ``correct: true``, and the cell's own
    entries read what the programs counted."""
    import time

    import test_bench_run
    from benchmark.harness import cell as cell_mod

    root = tiny_root.build(str(tmp_path / "checkout"))
    glm5_tiny.lay(root)
    monkeypatch.setattr(cell_mod, "_reduce_trace", test_bench_run._cpu_trace_as_device)
    result = cell_mod.run_cell(root, glm5_tiny.CELL, 2**31 + 26, 3.0, 2, "cpu", time.monotonic())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert {"out_tok_s", "setup_s", "dsa_selected_share", "index_kv_bytes_per_position",
            "latent_kv_bytes_per_position.dsa", "tpot_p50_ms.rows8", "ttft_p50_ms.rows8", "stall_p50_ms.rows8",
            "moe_held_share.dsa", "moe_rows_per_expert_mean.dsa", "prefix_hit_share.open",
            "moe_piece_bucketed_share"} <= set(metrics)
    # 4 layers x 40 and x 16 values x 2 B (the toy cell serves a bfloat16 cache, as the real one does)
    assert metrics["latent_kv_bytes_per_position.dsa"] == 4 * LATENT * 2
    assert metrics["index_kv_bytes_per_position"] == 4 * INDEX * 2
    # documents of 96-160 tokens and their asks: 48 selected of 100 to 200 visible
    assert 20.0 < metrics["dsa_selected_share"] < 55.0
    assert 15.0 < metrics["moe_held_share.dsa"] < 40.0  # 4 of 16 held
    assert "q40_held_experts_roofline.dsa" not in metrics  # the XLA path serves a toy: left out


def test_the_real_cell_and_its_entries():
    """What ISSUE 53 asked the cell to report, BY MEMBERSHIP (the next cell
    must not break this test): the lists it joins, six entries of its own, one
    chip, the accepted mix ``doc_sessions`` with a row for each caller, a long
    probe past ``index_topk``, every published width, and the cut."""
    import json
    import os

    import test_bench_schema
    from benchmark.harness import cell as cell_mod

    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    lists = {m["name"]: m.get("workloads") for m in real["per_layer"]}
    name = glm5_tiny.REAL_CELL
    for entry in ("ttft_p50_ms.rows8", "tpot_p50_ms.rows8", "stall_p50_ms.rows8", "queue_ms_mean.open",
                  "prefix_hit_share.open", "prefill_ms_mean.open", "compiles_in_window.open",
                  "prefill_chunks_ahead_mean.open", "server_ttft_ms_mean.open", "q40_dense_roofline",
                  "moe_piece_bucketed_share"):
        assert lists[entry].count(name) == 1 and len(lists[entry]) > 1
    for entry in ("dsa_selected_share", "index_kv_bytes_per_position", "latent_kv_bytes_per_position.dsa",
                  "q40_held_experts_roofline.dsa", "moe_held_share.dsa", "moe_rows_per_expert_mean.dsa"):
        assert lists[entry] == [name]
    assert lists["decode_hbm_share"] is None  # the whole step's share: reported in every cell
    assert name not in lists["latent_kv_bytes_per_position"]  # GLM-4.7-Flash's own entry stays its own
    cells = [w["name"] for w in real["workloads"]]
    assert cells.count(name) == 1 and len(set(cells)) == len(cells) <= 24
    assert next(w for w in real["workloads"] if w["name"] == name)["chips"] == 1
    entry = next(c for c in real["configs"] if c["name"] == "glm-5-q40-5l-ep16")
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert entry["source"] == "https://huggingface.co/zai-org/GLM-5/blob/main/config.json"
    test_bench_schema.test_a_per_layer_entry_moves_a_metric_that_every_cell_of_its_list_reports(real)
    cell = cell_mod.Cell(tiny_root.REPO, name)
    sibling = cell_mod.Cell(tiny_root.REPO, glm_tiny.REAL_CELL)
    assert cell.launch["flags"] == sibling.launch["flags"] and cell.mix == sibling.mix  # the model alone differs
    assert cell.flag("--parallel", 0) == int(cell.mix["callers"]) == 8
    assert cell.launch["traffic"] == "doc_sessions" and cell.flag("--max-seq-len", 0) == 16384
    assert cell.flag("--kv-pages", 0) == 3072 and cell.flag("--host-spill-mb", 1) == 0
    assert cell.check["long_probe_prompt"] == 4128 > cell.config["index_topk"] and cell.check["long_probes"] >= 1
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["intermediate_size"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["n_shared_experts"], c["routed_scaling_factor"],
            c["index_n_heads"], c["index_head_dim"], c["index_topk"], c["rope_parameters"]["rope_theta"]) == (
        6144, 64, 2048, 512, 192, 64, 256, 12288, 2048, 8, 1, 2.5, 32, 128, 2048, 1000000)
    assert (c["num_hidden_layers"], c["first_k_dense_replace"], c["n_routed_experts"], c["vocab_size"]) == (5, 1, 16, 19360)
    assert c["reduced_from"] == {"num_hidden_layers": 78, "first_k_dense_replace": 3, "n_routed_experts": 256,
                                 "vocab_size": 154880}
    assert c["family"] == "glm_moe_dsa" and c["first_routed_expert"] == 0
    assert all(isinstance(why, (str, int)) and why for why in c["assumed"].values())
    assert "16 chips share each layer" in c["deployment"] and "128 chips" in c["deployment"]
    # the floors of a cut: four expert layers behind the dense one, 8 experts or more, an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 == c["reduced_from"]["vocab_size"]
