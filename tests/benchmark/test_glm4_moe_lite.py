"""GLM-4.7-Flash at a toy size on the CPU: the program's engine against the
family's plain EXPANDED reference through every path a served row takes
(prefill, prefill in pieces, decode through the latent slab, the blocked scans
of a longer cache, a bucket with a masked row, a prefix hit through copied
latent pages, a page's way to the host and back), that absorbed attention is
the expanded one, what a position costs the cache, the test that ties the
expert layer that holds every expert to the uncut layer, what refuses by name
and what works, that the older archs' programs did not move, and that each
piece of the mathematics is load-bearing."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import exaone_tiny
import glm_tiny
import tiny_root
from benchmark import families
from benchmark.harness import modelfile
from benchmark.reference.qfile import QFile
from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.models import llama, moe
from distributed_llama_tpu.models.config import config_from_spec
from distributed_llama_tpu.ops import kv_cache as kvc

CONFIG = glm_tiny.CONFIG
PAGE = 8
LATENT = CONFIG["kv_lora_rank"] + CONFIG["qk_rope_head_dim"]  # 40 values a position and layer
# float32 against float32: what is left is rounding (measured 4e-7 to 6e-7 of max|logit|)
TOL = 2e-5
RNG = np.random.default_rng(11)
PROMPT = RNG.integers(300, 16000, 150).tolist()


def tail(n):
    return RNG.integers(300, 16000, n).tolist()


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    directory = str(tmp_path_factory.mktemp("glm"))
    return modelfile.write_artifacts(CONFIG, 2**31 + 3, directory, 4096)[0]


@pytest.fixture(scope="module")
def reference(model):
    ref = families.load(CONFIG, "reference")
    qf = QFile(model, ref)

    def logits(tokens, gaps=None):
        return ref.forward(qf, np.asarray([tokens], np.int32), np.arange(len(tokens)), gaps)[0]

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


def test_the_table_of_layer_kinds_and_the_latents_facts(engine):
    cfg = engine.cfg
    assert [cfg.layer_kind(l) for l in range(4)] == [("latent", "dense")] + [("latent", "experts")] * 3
    assert (cfg.latent_dim, cfg.kv_lora_rank, cfg.rope_dim, cfg.head_size) == (LATENT, 32, 8, 32)
    assert (cfg.n_experts, cfg.router_width, cfg.first_expert, cfg.routed_scale) == (8, 8, 0, 1.8)
    # a latent row rewinds by position as keys and values do; the scans count its reads
    assert cfg.has_latent and cfg.rewinds_by_position and cfg.kv_read_kinds == ("latent",)
    assert engine.params["rope_table"].shape == (512, 4, 2)  # the rotated slice's pairs, not a head's
    # the accepted archs say their kinds through the same table, and have no latent
    exaone = exaone_tiny.CONFIG
    old = config_from_spec(families.load(exaone, "modelfile").model_spec(exaone, 512))
    assert not old.has_latent and old.latent_dim == 0 and old.rope_dim == old.head_size
    assert {old.layer_kind(l)[0] for l in range(8)} == {"window", "full"}


def test_a_rows_cache_is_one_latent_a_position_with_no_head_axis(engine):
    cfg = engine.cfg
    slab = jax.eval_shape(lambda: llama.init_batch_cache(cfg, 3, dtype=jnp.bfloat16))
    pool = jax.eval_shape(lambda: llama.init_page_pool(cfg, 10, PAGE, dtype=jnp.bfloat16))
    assert [leaf[kvc.LATENT].shape for leaf in slab] == [(3, LATENT, 512)] * 4  # positions minor
    assert [tuple(h.shape for h in halves) for halves in pool] == [((10, PAGE * LATENT),)] * 4  # a page one row
    assert llama.page_pool_bytes(cfg, PAGE, jnp.bfloat16) == 4 * PAGE * LATENT * 2
    assert llama.page_pool_bytes(cfg, 1, jnp.bfloat16, layers=1) == LATENT * 2
    assert llama.kv_slab_bytes(cfg, 3, jnp.bfloat16) == {"latent": 3 * 512 * 4 * LATENT * 2}
    # the model's own expanded keys and values: heads x (nope + rope + v) values, 4.8 times as many
    expanded = cfg.n_heads * (cfg.head_size + cfg.v_head_dim)
    assert expanded / LATENT == 4.8


@pytest.mark.parametrize("case", ["prefill alone", "prefill in pieces of 8",
                                  "prefill then decode", "a bucket with a masked row",
                                  "the blocked scans of a cache of 4096 positions"])
def test_engine_against_the_reference(engine, model, reference, case):
    want = reference(PROMPT)
    if case == "prefill alone":
        assert off(engine.new_stream().prefill(PROMPT), want[-1]) <= TOL
        return
    if case.startswith("the blocked scans"):
        # 4096 positions: a piece and a decode step read the row a chunk of 2048 positions at a
        # time with a dynamic bound, as at the served 16384
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
        # ... and row 1's latents were not touched by the chunks it sat out
        assert max(deficits(reference, other, decode(s1, got1, 6))) <= TOL
        return
    assert max(deficits(reference, PROMPT, decode(s0, got, 30))) <= TOL


def test_absorbed_attention_is_the_expanded_one(engine):
    """The served path never expands a cached position: the nope-key rows of
    the up-projection are folded into the query and the value rows into the
    output. Against keys and values expanded for every head from the same
    latents, and attended as published, it is the same mix."""
    cfg, lp = engine.cfg, engine.params["layers"][1]
    H, nope, rope, v = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rng = np.random.default_rng(3)
    T = 24
    x = jnp.asarray(rng.standard_normal((T, cfg.dim)), jnp.float32)
    rope_rows = engine.params["rope_table"][5 : 5 + T]
    leaf = kvc.init_latent((), 64, cfg.latent_dim, jnp.float32)
    # five earlier positions are in the cache already
    before = jnp.asarray(rng.standard_normal((5, cfg.dim)), jnp.float32)
    _, leaf = llama.latent_attention(cfg, before, lp, leaf, jnp.int32(0), engine.params["rope_table"][:5])
    got, leaf = llama.latent_attention(cfg, x, lp, leaf, jnp.int32(5), rope_rows)
    rows = np.asarray(leaf[kvc.LATENT][:, : 5 + T]).T  # [c_kv | k_rope] of positions 0 .. 28
    # expanded: every position's keys and values for every head, out of the cache's own rows
    k_nope = np.einsum("sr,hnr->shn", rows[:, : cfg.kv_lora_rank], np.asarray(lp["w_uk"]))
    values = np.einsum("sr,hrv->shv", rows[:, : cfg.kv_lora_rank], np.asarray(lp["w_uv"]))
    keys = np.concatenate([k_nope, np.broadcast_to(rows[:, None, cfg.kv_lora_rank:], (5 + T, H, rope))], -1)
    # the queries as published: [q_nope | rot(q_rope)] a head, from the same projections
    fused = llama.rmsnorm(x, lp["rms_att"]) @ lp["qkv_a"]
    q = (llama.rmsnorm(fused[:, : cfg.q_lora_rank], lp["q_a_norm"]) @ lp["q_b"]).reshape(T, H, nope + rope)
    q = np.concatenate([np.asarray(q[..., :nope]),
                        np.asarray(llama.apply_rope(q[..., nope:], rope_rows, cfg))], -1)
    scores = np.einsum("thd,shd->ths", q, keys) / np.sqrt(nope + rope)
    seen = np.arange(5 + T)[None, :] <= 5 + np.arange(T)[:, None]
    scores = np.where(seen[:, None, :], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("ths,shv->thv", p, values).reshape(T, H * v)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    assert float(np.abs(want).max()) > 0.05
    # ... and one decode step of a row at position 29 reads the same cache the same way
    slab = {kvc.LATENT: jnp.stack([leaf[kvc.LATENT], jnp.zeros_like(leaf[kvc.LATENT])])}
    x1 = jnp.asarray(rng.standard_normal((2, cfg.dim)), jnp.float32)
    step, _ = llama.latent_attention_batched(
        cfg, x1, lp, slab, jnp.asarray([29, 0]), engine.params["rope_table"][jnp.asarray([29, 0])],
        jnp.asarray([True, False]))
    one, _ = llama.latent_attention(cfg, x1[:1], lp, leaf, jnp.int32(29), engine.params["rope_table"][29:30])
    np.testing.assert_allclose(np.asarray(step[0]), np.asarray(one[0]), rtol=1e-5, atol=1e-6)


def test_a_prefix_hit_through_copied_latent_pages_gives_a_cold_prefills_logits(engine, reference):
    sched = scheduler(engine)
    s0, s1 = sched.new_stream(), sched.new_stream()
    got = s0.prefill(PROMPT)  # 18 whole pages published
    assert s0.matched_len == 0 and off(got, reference(PROMPT)[-1]) <= TOL
    # a published page holds its block's rows, all LATENT values of them, in every layer
    chain = sched._prefix.walk(PROMPT + [0])
    assert len(chain) == 18
    for block in (0, 7, 17):
        for leaf, (page,) in zip(sched._slab, sched._pool):
            np.testing.assert_array_equal(
                np.asarray(page[chain[block].page_id]).reshape(PAGE, LATENT),
                np.asarray(leaf[kvc.LATENT][s0.row, :, block * PAGE : (block + 1) * PAGE]).T)
            assert page.shape[1:] == (PAGE * LATENT,) and float(jnp.abs(page[chain[block].page_id]).max()) > 0
    # a second ask over the same head: 17 pages are COPIED into its row (PR 40's one-source
    # decode: the programs are handed an empty read alias), the rest is prefilled ...
    ask = PROMPT[:140] + tail(9)
    got = s1.prefill(ask)
    assert s1.matched_len == 17 * PAGE and off(got, reference(ask)[-1]) <= TOL
    for leaf in sched._slab:
        np.testing.assert_array_equal(np.asarray(leaf[kvc.LATENT][s1.row, :, : 17 * PAGE]),
                                      np.asarray(leaf[kvc.LATENT][s0.row, :, : 17 * PAGE]))
    tables, matched = sched._alias_arrays_locked([s0, s1], np.asarray([True, True]))
    assert not tables.any() and not matched.any()
    # ... and decodes on from the copied latents and its own suffix
    assert max(deficits(reference, ask, decode(s1, got, 20))) <= TOL
    sched.check_prefix()


def test_an_evicted_page_goes_to_the_host_and_comes_back_with_its_values(engine, reference):
    """The spill tier takes a latent page as it takes any: one array a layer
    where keys and values give two. A prefix that was evicted to the host is
    reloaded into the pool on the next match and served as a hit."""
    sched = scheduler(engine, rows=1, kv_pages=20, host_spill_bytes=4 << 20)
    s, prefix = sched.new_stream(), sched._prefix
    want = reference(PROMPT)[-1]
    assert off(s.prefill(PROMPT), want) <= TOL  # 18 of the pool's 20 pages
    kept = [np.asarray(page[prefix.walk(PROMPT + [0])[3].page_id]) for (page,) in sched._pool]
    s.reset()
    s.prefill(tail(150))  # another 18: the first prompt's pages leave for the host
    assert prefix.spill.flush(10) and prefix.spill.depth() >= 16
    s.reset()
    got = s.prefill(PROMPT)
    assert s.matched_len >= 16 * PAGE and prefix.spill.reloaded_total >= 16
    assert off(got, want) <= TOL
    back = [np.asarray(page[prefix.walk(PROMPT + [0])[3].page_id]) for (page,) in sched._pool]
    for a, b in zip(kept, back):
        np.testing.assert_array_equal(a, b)
    sched.check_prefix()
    sched.close()


def test_a_chat_continues_from_a_rewound_row(engine, reference):
    """``cfg.rewinds_by_position``: a row moved back to an earlier position
    goes on from there, as a row of keys and values does."""
    s = scheduler(engine).new_stream()
    s.prefill(PROMPT)
    s.rollback(100)
    assert s.pos == 100
    turn = PROMPT[:100] + tail(30)
    got = s.prefill(turn[100:])
    assert off(got, reference(turn)[-1]) <= TOL
    assert max(deficits(reference, turn, decode(s, got, 8))) <= TOL


def test_a_decode_step_reads_one_latent_a_position_and_layer(engine, model):
    """The programs' own count: per row, the cache positions a step's layers
    read; the scheduler multiplies them by what a slot of its slab holds."""
    from distributed_llama_tpu import telemetry

    cfg = engine.cfg
    slab = llama.init_batch_cache(cfg, 2, dtype=jnp.float32)
    out = {}
    llama.forward_step_batched(cfg, engine.params, jnp.asarray([5, 6]), slab, jnp.asarray([40, 3]),
                               jnp.asarray([True, True]), kv_reads=out)
    assert {k: np.asarray(v).tolist() for k, v in out.items()} == {"latent": [4 * cfg.seq_len] * 2}
    telemetry.enable()
    try:
        # the engine binds its instruments when it is built
        sched = scheduler(InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.bfloat16,
                                          max_seq_len=512))
        assert sched._kv_position_bytes == LATENT * 2
        s = sched.new_stream()
        read = lambda name: telemetry.REGISTRY.counter(name, "", ("kind",)).labels(kind="latent").value
        before = read("dllama_attn_kv_read_bytes_total"), read("dllama_attn_kv_read_positions_total")
        decode(s, s.prefill(PROMPT[:40]), 9)
        nbytes = read("dllama_attn_kv_read_bytes_total") - before[0]
        positions = read("dllama_attn_kv_read_positions_total") - before[1]
        # what the benchmark's latent_kv_bytes_per_position divides: layers x LATENT values x 2 B
        assert positions > 0 and nbytes / positions == 4 * LATENT * 2
    finally:
        telemetry.disable()


@pytest.mark.parametrize("what", ["an i8 cache", "--tp 2", "--spec-draft", "speculative verify"])
def test_paths_that_need_a_head_axis_or_a_window_of_tokens_refuse_by_name(engine, model, what):
    with pytest.raises(llama.LatentCacheError, match="GLM4_MOE_LITE.*one row of 40 values a position"):
        if what == "an i8 cache":
            InferenceEngine(model, dtype=jnp.float32, cache_dtype="i8", max_seq_len=512).new_stream()
        elif what == "--tp 2":
            InferenceEngine(model, dtype=jnp.float32, tp=2)
        elif what == "--spec-draft":
            scheduler(engine, spec_draft=4)
        else:
            slab = llama.init_batch_cache(engine.cfg, 2, dtype=jnp.float32)
            llama.forward_verify_batched(engine.cfg, engine.params, jnp.zeros((2, 3), jnp.int32), slab,
                                         jnp.zeros(2, jnp.int32), jnp.ones(2, bool))


def test_the_new_archs_file_and_the_old_files(tmp_path, model):
    from distributed_llama_tpu.formats.model_file import ArchType, HeaderKey, _header_pairs, read_spec, tensor_layout

    new = read_spec(model)
    assert new.arch_type == ArchType.GLM4_MOE_LITE
    assert (new.q_lora_rank, new.kv_lora_rank, new.qk_nope_head_dim, new.qk_rope_head_dim, new.v_head_dim,
            new.head_size) == (32, 32, 24, 8, 16, 32)
    assert (new.n_experts, new.n_routed_experts, new.first_expert, new.first_dense,
            new.routed_scale_milli) == (8, 8, 0, 1, 1800)
    # seven attention tensors a layer where every other arch has four
    names = [e.name for e in tensor_layout(new) if e.name.startswith("layers.1.") and "experts" not in e.name
             and "shared" not in e.name and "router" not in e.name and "rms" not in e.name]
    assert names == [f"layers.1.{n}" for n in ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "wo")]
    # an old file carries none of the keys past ROPE_TYPE, K-EXAONE's none of the latent's
    path, _ = modelfile.write_artifacts(tiny_root.CONFIGS["tiny-moe"], 7, str(tmp_path), 512)
    old = read_spec(path)
    assert max(int(k) for k, _ in _header_pairs(old)) < HeaderKey.HEAD_SIZE and old.kv_lora_rank == 0
    exaone = exaone_tiny.CONFIG
    keys = [int(k) for k, _ in _header_pairs(families.load(exaone, "modelfile").model_spec(exaone, 512))]
    assert max(keys) == HeaderKey.ROUTED_SCALE_MILLI and HeaderKey.KV_LORA_RANK not in keys


def test_with_every_expert_held_the_expert_layer_is_the_uncut_layer(engine):
    """The held-expert layer told that it holds ALL the routed experts (first
    0, held = the router's width) gives the plain sum over every token's
    chosen experts, each over its own rows, plus the shared one: nothing is
    left out, in a decode step (every-row path) and in a piece (buckets)."""
    cfg, rng = engine.cfg, np.random.default_rng(5)
    D, F, E, k = cfg.dim, cfg.moe_hidden_dim, cfg.n_routed_experts, cfg.n_active_experts
    assert (cfg.n_experts, cfg.first_expert) == (E, 0)
    mat = lambda *shape: jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2]), jnp.float32)
    lp = {"router": mat(D, E) * 4, "router_bias": jnp.asarray(0.02 * rng.standard_normal(E), jnp.float32),
          "experts_gate_up": mat(E, D, 2 * F) * 4, "experts_down": mat(E, F, D) * 4,
          "shared_gate_up": mat(D, 2 * F), "shared_down": mat(F, D)}
    for rows in (6, 96):  # a decode step's rows; a piece whose experts take their buckets
        xn = jnp.asarray(rng.standard_normal((rows, D)), jnp.float32)
        got = np.asarray(moe._moe_share(cfg, xn, lp))
        scores = np.asarray(jax.nn.sigmoid(xn @ lp["router"]))
        chosen = np.argsort(-(scores + np.asarray(lp["router_bias"])), axis=-1)[:, :k]
        swiglu = lambda x, gu, dn: (np.asarray(jax.nn.silu(x @ gu[:, :F])) * (x @ gu[:, F:])) @ dn
        want = swiglu(np.asarray(xn), np.asarray(lp["shared_gate_up"]), np.asarray(lp["shared_down"]))
        for t in range(rows):
            w = cfg.routed_scale * scores[t, chosen[t]] / scores[t, chosen[t]].sum()
            for weight, e in zip(w, chosen[t]):
                want[t] += weight * swiglu(np.asarray(xn[t]), np.asarray(lp["experts_gate_up"][e]),
                                           np.asarray(lp["experts_down"][e]))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # every choice falls on a held expert: the share the benchmark reads is 100 %
    counts = []
    with moe.collect_held() as per_layer:
        moe._moe_share(cfg, xn, lp)
        counts = [np.asarray(c) for c in per_layer]
    assert all((c == k).all() for c in counts) and counts
    # a bucket by the config's own ratio: k / routed = 1/4 here, 1/16 as published
    published = dataclasses.replace(cfg, n_active_experts=4, n_routed_experts=64, n_experts=64)
    assert [moe.held_bucket_rows(published, rows) for rows in (8, 64, 128, 256)] == [16, 16, 64, 64]


def test_a_pieces_overflowing_bucket_takes_a_bucket_of_twice_the_rows_before_every_row(engine):
    """A document of few distinct tokens routes alike, and some expert gets
    more than its bucket's rows (four times the even share): the piece's
    buckets are then twice as large, and only past those every expert runs
    over every row. Exact each way; which arm ran is counted."""
    cfg, rng = engine.cfg, np.random.default_rng(9)
    cfg = dataclasses.replace(cfg, n_experts=32, n_routed_experts=32, n_active_experts=2)
    D, F, E, k = cfg.dim, cfg.moe_hidden_dim, 32, 2
    assert moe.held_bucket_rows(cfg, 256) == 64
    mat = lambda *shape: jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2]), jnp.float32)
    lp = {"router": mat(D, E) * 4, "router_bias": jnp.zeros(E, jnp.float32),
          "experts_gate_up": mat(E, D, 2 * F) * 4, "experts_down": mat(E, F, D) * 4}
    kinds = rng.standard_normal((6, D)).astype(np.float32)
    swiglu = lambda x, gu, dn: (np.asarray(jax.nn.silu(x @ gu[:, :F])) * (x @ gu[:, F:])) @ dn
    for repeats, every_row in (([100, 60, 40, 30, 16, 10], 0), ([200, 20, 12, 10, 8, 6], 1)):
        xn = jnp.asarray(np.repeat(kinds, repeats, axis=0))  # 256 rows of 6 kinds
        top_vals, top_idx = moe.router_topk(cfg, xn, lp["router"], lp["router_bias"])
        most = int(np.bincount(np.asarray(top_idx).ravel(), minlength=E).max())
        assert (64 < most <= 128) if not every_row else most > 128
        with moe.collect_piece_paths() as paths:
            got = np.asarray(moe._held_experts(cfg, xn, lp, top_vals, top_idx))
        assert [int(p) for p in paths] == [every_row]
        want = np.zeros((256, D), np.float32)
        for t in range(256):
            for w, e in zip(np.asarray(top_vals[t]), np.asarray(top_idx[t])):
                want[t] += w * swiglu(np.asarray(xn[t]), np.asarray(lp["experts_gate_up"][e]),
                                      np.asarray(lp["experts_down"][e]))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_older_archs_programs_did_not_move():
    """What this arch added to shared code is behind its own leaf and its own
    config values: a leaf of keys and values is sliced and published as
    before, an arch without latents carries no read counts of that kind."""
    exaone = exaone_tiny.CONFIG
    cfg = config_from_spec(families.load(exaone, "modelfile").model_spec(exaone, 512))
    assert cfg.kv_read_kinds == ("full", "window") and not cfg.has_latent
    dense = config_from_spec(families.load(tiny_root.CONFIGS["tiny-dense"], "modelfile").model_spec(
        tiny_root.CONFIGS["tiny-dense"], 512))
    assert dense.kv_read_kinds == () and dense.rope_dim == dense.head_size
    slab = llama.init_batch_cache(dense, 2, dtype=jnp.float32)
    assert not any(kvc.is_latent_leaf(leaf) for leaf in slab) and slab[0].shape[:2] == (2, 2)
    kc, vc = kvc.slab_chunk(slab[0], 0, 16, 2)
    assert kc.shape == vc.shape == (2, 16, dense.n_kv_heads, dense.head_size)
    pool = llama.init_page_pool(dense, 4, PAGE, dtype=jnp.float32)
    assert all(len(halves) == 2 for halves in pool)


def _without(monkeypatch, piece):
    if piece == "the factor 1.8":
        real = moe.router_topk

        def unscaled(cfg, xn, router, bias=None):
            vals, idx = real(cfg, xn, router, bias)
            return vals / cfg.routed_scale, idx

        monkeypatch.setattr(moe, "router_topk", unscaled)
    elif piece == "the norms inside the projections":
        real_norm = llama.rmsnorm
        monkeypatch.setattr(llama, "rmsnorm", lambda x, w, eps=1e-5: x if w.shape[0] == 32 else real_norm(x, w, eps))
    elif piece == "the shared expert":
        real_share = moe._moe_share
        monkeypatch.setattr(moe, "_moe_share", lambda cfg, xn, lp: real_share(
            cfg, xn, {k: v for k, v in lp.items() if not k.startswith("shared")}))
    elif piece == "the rotation":
        monkeypatch.setattr(llama, "apply_rope", lambda x, rows, cfg: x)
    elif piece == "the published softmax scale":
        from distributed_llama_tpu.ops import attention as attn_ops

        real_scan = attn_ops.latent_attention_scan
        monkeypatch.setattr(attn_ops, "latent_attention_scan",
                            lambda q, q_pos, latents, chunk, scale: real_scan(q, q_pos, latents, chunk, 40 ** -0.5))
    elif piece == "the leading dense layer":
        real_ffn = llama.ffn
        monkeypatch.setattr(llama, "ffn", lambda cfg, x, lp, axis: 0 * real_ffn(cfg, x, lp, axis))
    else:
        raise ValueError(piece)


@pytest.mark.parametrize("piece", ["the factor 1.8", "the norms inside the projections", "the shared expert",
                                   "the rotation", "the published softmax scale", "the leading dense layer"])
def test_leaving_a_piece_of_the_mathematics_out_fails_the_tolerance(model, reference, monkeypatch, piece):
    from benchmark.harness.cell import load_check

    _without(monkeypatch, piece)
    jax.clear_caches()
    try:
        got = InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32,
                              max_seq_len=512).new_stream().prefill(PROMPT)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    # not by float32's tolerance, and not by the benchmark's for the served Q40 path either
    assert off(got, reference(PROMPT)[-1]) > load_check()["logit_tol"]


def test_q40_engine_stays_inside_the_benchmarks_logit_tolerance(model, reference):
    from benchmark.harness.cell import load_check

    # token by token, so that every position is compared (a top 2 of 8 is discontinuous: where
    # the reference's routing is a near-tie the Q80 rounding of the activations flips it)
    want = reference(PROMPT[:60])
    stream = InferenceEngine(model, dtype="q40", max_seq_len=512).new_stream()
    offs = [off(stream.prefill([tok]), want[i]) for i, tok in enumerate(PROMPT[:60])]
    tol = load_check()["logit_tol"]
    assert np.median(offs) <= tol / 2 and np.mean(np.asarray(offs) <= tol) >= 0.8, offs


def test_the_cell_runs_through_the_harness_on_the_cpu(tmp_path, monkeypatch):
    """The toy configuration as a cell of the miniature checkout (documents
    asked twice), through ``run_cell`` with ``--trace 2``: the family's
    builder, the server child, the probes judged by the family's expanded
    reference, warm-up, window, drain, the traced phase: ``correct: true``,
    the second asks hit through copied latent pages, and the programs' read
    counts and the expert layer's counters moved."""
    import time

    import test_bench_run
    from benchmark.harness import cell as cell_mod

    root = tiny_root.build(str(tmp_path / "checkout"))
    glm_tiny.lay(root)
    monkeypatch.setattr(cell_mod, "_reduce_trace", test_bench_run._cpu_trace_as_device)
    result = cell_mod.run_cell(root, glm_tiny.CELL, 2**31 + 26, 3.0, 2, "cpu", time.monotonic())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert {"out_tok_s", "setup_s", "latent_kv_bytes_per_position", "tpot_p50_ms.rows8", "ttft_p50_ms.rows8",
            "stall_p50_ms.rows8", "moe_held_share.all64", "moe_rows_per_expert_mean.all64",
            "prefix_hit_share.open"} <= set(metrics)
    # 4 layers x 40 values x 2 B (the toy cell serves a bfloat16 cache, as the real one does)
    assert metrics["latent_kv_bytes_per_position"] == 4 * LATENT * 2
    assert metrics["moe_held_share.all64"] == 100.0  # every routed expert is held
    # the kernels' shares read nothing at a toy size (the XLA path serves): left out
    assert "q40_held_experts_roofline.all64" not in metrics


def test_the_real_cell_and_its_entries():
    """What ISSUE 43 asked the cell to report: the lists it joins (at their
    end), four entries of its own, one chip, the mix ``doc_sessions`` with a
    row for each caller, two long probes (PR 43's review: one left fewer deep
    positions compared than misses allowed), and every published width."""
    import json
    import os

    import test_bench_schema
    from benchmark.harness import cell as cell_mod

    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    lists = {m["name"]: m.get("workloads") for m in real["per_layer"]}
    name = glm_tiny.REAL_CELL
    for entry in ("ttft_p50_ms.rows8", "tpot_p50_ms.rows8", "stall_p50_ms.rows8", "queue_ms_mean.open",
                  "prefix_hit_share.open", "prefill_ms_mean.open", "compiles_in_window.open",
                  "prefill_chunks_ahead_mean.open", "server_ttft_ms_mean.open", "q40_dense_roofline",
                  "moe_piece_bucketed_share"):
        assert lists[entry].count(name) == 1 and len(lists[entry]) > 1
    for entry in ("latent_kv_bytes_per_position", "q40_held_experts_roofline.all64", "moe_held_share.all64",
                  "moe_rows_per_expert_mean.all64"):
        assert lists[entry] == [name]
    assert lists["decode_hbm_share"] is None  # the whole step's share: reported in every cell
    cells = [w["name"] for w in real["workloads"]]
    assert cells.count(name) == 1 and len(set(cells)) == len(cells) <= 24
    assert next(w for w in real["workloads"] if w["name"] == name)["chips"] == 1
    entry = next(c for c in real["configs"] if c["name"] == "glm-4.7-flash-q40-stage0")
    assert entry["reduced"] == ["num_hidden_layers"]
    test_bench_schema.test_a_per_layer_entry_moves_a_metric_that_every_cell_of_its_list_reports(real)
    cell = cell_mod.Cell(tiny_root.REPO, name)
    assert cell.flag("--parallel", 0) == int(cell.mix["callers"]) == 8
    assert cell.launch["traffic"] == "doc_sessions" and cell.flag("--max-seq-len", 0) == 16384
    assert cell.flag("--kv-pages", 0) == 3072
    assert cell.check["long_probe_prompt"] == 4128 and cell.check["long_probes"] == 2
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["intermediate_size"], c["n_routed_experts"],
            c["moe_intermediate_size"], c["num_experts_per_tok"], c["n_shared_experts"],
            c["routed_scaling_factor"], c["vocab_size"], c["rope_theta"]) == (
        2048, 20, 768, 512, 192, 64, 256, 10240, 64, 1536, 4, 1, 1.8, 154880, 1000000)
    assert c["reduced"] == ["num_hidden_layers"] and c["reduced_from"] == {"num_hidden_layers": 47}
    assert all(isinstance(why, (str, int)) and why for why in c["assumed"].values()) and "two v5e" in c["deployment"]
