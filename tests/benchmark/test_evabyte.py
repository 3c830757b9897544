"""EvaByte at a toy size on the CPU (a window of 64 positions, a summary for
every 8, 2 layers): the program's engine against the family's plain reference,
which keeps no cache and recomputes every summary from the whole sequence,
through every path a served row takes (prefill in pieces, a piece that
crosses a window's end, decode through slab and summaries across window
boundaries, rows that cross one at different steps of one decode chunk, a
bucket with a masked row, and the three outcomes of a prefix match); that
under a window larger than the sequence the arch is plain causal attention;
the counts of ``counts.py`` against the programs' own; what refuses by name;
the file; that each piece of the mathematics is load-bearing; and the toy cell
through the harness."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import evabyte_tiny
import tiny_root
from benchmark import families
from benchmark.harness import modelfile
from benchmark.reference.qfile import QFile
from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.engine.batch import BatchScheduler, eva_tail_pages
from distributed_llama_tpu.models import llama
from distributed_llama_tpu.models.config import config_from_spec
from distributed_llama_tpu.ops import attention as attn_ops

CONFIG = evabyte_tiny.CONFIG
PAGE, WINDOW, CHUNK = 16, 64, 8
# float32 against float32: what is left is rounding (measured 2e-7 to 4e-7 of max|logit|)
TOL = 2e-5
RNG = np.random.default_rng(11)
PROMPT = RNG.integers(3, 320, 230).tolist()  # three whole windows, 14 whole pages, two of them in the fourth


def tail(n):
    return RNG.integers(3, 320, n).tolist()


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    directory = str(tmp_path_factory.mktemp("evabyte"))
    return modelfile.write_artifacts(CONFIG, 2**31 + 3, directory, 512)[0]


@pytest.fixture(scope="module")
def reference(model):
    ref = families.load(CONFIG, "reference")
    qf = QFile(model, ref)

    def logits(tokens):
        return ref.forward(qf, np.asarray([tokens], np.int32), np.arange(len(tokens)))[0]

    return logits


@pytest.fixture(scope="module")
def engine(model):
    return InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32)


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


def counter(name, **labels):
    from distributed_llama_tpu import telemetry

    return telemetry.REGISTRY.counter(name, "", tuple(labels)).labels(**labels).value


def test_the_layer_kind_and_what_a_row_holds(engine, model):
    cfg = engine.cfg
    assert [cfg.layer_kind(l) for l in range(2)] == [("eva", "dense")] * 2
    assert (cfg.window, cfg.eva_chunk, cfg.eva_summaries, cfg.eva_slots) == (WINDOW, CHUNK, 64, 128)
    # a piece is at most a window (no two of its rows in one slot), a scan's step divides both stores
    assert (cfg.piece_limit, cfg.eva_scan_chunk) == (64, 64)
    assert not cfg.rewinds_by_position and not cfg.is_recurrent and not cfg.has_window
    assert cfg.kv_read_kinds == ("eva_window", "eva_summary") and cfg.rotates(0)
    # at the published window and the cell's context: pieces of 256, steps of 512, 3072 slots a row
    real = dataclasses.replace(cfg, window=2048, eva_chunk=16, seq_len=16384)
    assert (real.piece_limit, real.eva_scan_chunk, real.eva_slots) == (256, 512, 2048 + 1024)
    # a leaf does not grow with the context but by its summaries: a sixteenth (here an eighth) of it
    for seq_len in (512, 2048):
        c = dataclasses.replace(cfg, seq_len=seq_len)
        slab = jax.eval_shape(lambda c=c: llama.init_batch_cache(c, 4, dtype=jnp.bfloat16))
        assert [leaf.shape for leaf in slab] == [(2, 4, WINDOW + seq_len // CHUNK, 4, 16)] * 2
        nbytes = llama.kv_slab_bytes(c, 4, jnp.bfloat16)
        assert nbytes == {"eva_window": 2 * 4 * WINDOW * 2 * 4 * 16 * 2,
                          "eva_summary": 2 * 4 * (seq_len // CHUNK) * 2 * 4 * 16 * 2}
    # the pools: a page of summaries is a chunk's share of a page of keys and values
    pool = jax.eval_shape(lambda: llama.init_page_pool(cfg, 8, PAGE, dtype=jnp.bfloat16))
    tails = jax.eval_shape(lambda: llama.init_window_pool(cfg, 6, PAGE, dtype=jnp.bfloat16))
    assert [half[0].shape for half in pool] == [(8, PAGE // CHUNK, 4, 16)] * 2
    assert [half[0].shape for half in tails] == [(6, PAGE, 4, 16)] * 2
    assert llama.page_pool_bytes(cfg, PAGE, jnp.bfloat16) * CHUNK == 2 * 2 * PAGE * 4 * 16 * 2
    with pytest.raises(ValueError, match="no i8 form"):
        llama.init_batch_cache(cfg, 2, dtype="i8")


@pytest.mark.parametrize("case", ["prefill alone, in pieces of a window",
                                  "every position's logits, through a piece that starts inside a chunk",
                                  "a piece that crosses a window's end",
                                  "prefill then decode across two window boundaries",
                                  "rows that cross a boundary at different steps of one decode chunk",
                                  "a bucket with a masked row"])
def test_engine_against_the_reference(engine, reference, case):
    want = reference(PROMPT)
    if case.startswith("prefill alone"):
        # 230 tokens: the single-stream path cuts them into pieces of 64, one to a window
        assert off(engine.new_stream().prefill(PROMPT), want[-1]) <= TOL
        return
    if case.startswith("every position"):
        # 45 tokens, then 100 more from position 45: the second call's first chunk began in the
        # first call (its summary pools keys of both), and its pieces cross 64 and 128
        stream = engine.new_stream()
        first = stream.forward(PROMPT[:45])
        rest = stream.forward(PROMPT[45:145])
        assert off(np.concatenate([first, rest]), want[:145]) <= TOL
        return
    if case.startswith("a piece that crosses"):
        # pieces of 32 from position 48 (a hit's depth): 48..79 crosses 64, 112..143 crosses 128
        sched = scheduler(engine)
        s0 = sched.new_stream()
        s0.prefill(PROMPT[:48])
        assert off(s0.prefill(PROMPT[48:]), want[-1]) <= TOL
        return
    sched = scheduler(engine)
    s0, s1 = sched.new_stream(), sched.new_stream()
    if case.startswith("prefill then decode"):
        # 100 + 100 positions: the answer crosses 128 and 192, and reads the summaries of
        # chunks that decode steps completed and wrote
        got = s0.prefill(PROMPT[:100])
        assert off(got, want[99]) <= TOL
        assert max(deficits(reference, PROMPT[:100], decode(s0, got, 100))) <= TOL
        return
    if case.startswith("rows that cross"):
        # decode chunks of 4 steps from 125 and from 126: row 0 enters the next window at the
        # chunk's fourth step, row 1 at its third; each goes on reading its own window
        a, b = PROMPT[:125], PROMPT[20:146]
        got_a, got_b = s0.prefill(a), s1.prefill(b)
        assert off(got_a, want[124]) <= TOL and off(got_b, reference(b)[-1]) <= TOL
        answers = {}
        import threading

        t = threading.Thread(target=lambda: answers.update(b=decode(s1, got_b, 12)))
        t.start()
        answers["a"] = decode(s0, got_a, 12)
        t.join()
        assert max(deficits(reference, a, answers["a"])) <= TOL
        assert max(deficits(reference, b, answers["b"])) <= TOL
        return
    other = tail(70)
    got, got1 = s0.prefill(PROMPT), s1.prefill(other)
    assert max(deficits(reference, PROMPT, decode(s0, got, 9))) <= TOL
    # ... and row 1's window store and summaries were not touched by the chunks it sat out
    assert max(deficits(reference, other, decode(s1, got1, 6))) <= TOL


def test_a_prefix_match_has_three_outcomes_and_each_gives_the_cold_answer(engine, reference):
    from distributed_llama_tpu import telemetry

    telemetry.enable()  # the prefix cache binds its counters when it is built
    try:
        sched = scheduler(engine)
    finally:
        telemetry.disable()
    prefix = sched._prefix
    # a window is 4 pages; the tails' pool: half a window for each of 2 rows and a whole one beside
    assert (prefix.window_align, prefix.window_tail, prefix.window_pages) == (4, 0, 2 * 2 + 4)
    assert prefix.window_pages == eva_tail_pages(2, 4)
    assert [prefix.tail_start(b) for b in (0, 3, 4, 5, 13, 16)] == [0, 0, 4, 4, 12, 16]
    s0, s1 = sched.new_stream(), sched.new_stream()
    before = {o: counter("dllama_prefix_window_tail_total", outcome=o) for o in ("hit", "shortened", "miss")}
    got = s0.prefill(PROMPT)  # 14 whole pages of summaries; keys and values of pages 12, 13 (its last window)
    assert s0.matched_len == 0 and off(got, reference(PROMPT)[-1]) <= TOL
    nodes = prefix.walk(PROMPT + [0])
    assert len(nodes) == 14 and [i for i, nd in enumerate(nodes) if nd.wpage is not None] == [12, 13]
    # HIT at a page inside a window: the same head, differing in its last tokens. 13 pages match;
    # it resumes at 208, 16 positions into the window that starts at 192, from that window's page
    # of keys and from 26 summaries, 24 of which its queries read
    ask = PROMPT[:220] + tail(9)
    got = s1.prefill(ask)
    assert s1.matched_len == 13 * PAGE and off(got, reference(ask)[-1]) <= TOL
    # ... and decodes on, across the next window's start at 256, where the summaries of the
    # chunks it did not compute itself (192..207) become visible
    assert max(deficits(reference, ask, decode(s1, got, 40))) <= TOL
    # SHORTENED to the window's start: the page of keys has aged out, the summaries are there
    s1.reset()
    prefix._drop_window_page(nodes[12])
    again = PROMPT[:220] + tail(9)
    got = s1.prefill(again)
    assert s1.matched_len == 12 * PAGE and off(got, reference(again)[-1]) <= TOL
    assert max(deficits(reference, again, decode(s1, got, 8))) <= TOL
    # MISS: a prompt that leaves the head inside its FIRST window, whose keys no pool holds
    # (2 pages match; the window's start is the row's start)
    s1.reset()
    early = PROMPT[:40] + tail(30)
    got = s1.prefill(early)
    assert s1.matched_len == 0 and off(got, reference(early)[-1]) <= TOL
    # a match that ends where a window ends needs no keys at all
    s1.reset()
    edge = PROMPT[:192] + tail(5)
    got = s1.prefill(edge)
    assert s1.matched_len == 192 and off(got, reference(edge)[-1]) <= TOL
    after = {o: counter("dllama_prefix_window_tail_total", outcome=o) for o in before}
    assert {o: after[o] - before[o] for o in before} == {"hit": 2, "shortened": 1, "miss": 1}
    sched.check_prefix()


def test_tail_pages_age_out_by_their_own_order(engine):
    sched = scheduler(engine, rows=1, kv_pages=40)  # tails' pool: 2 + 4 pages
    s, prefix = sched.new_stream(), sched._prefix
    assert prefix.window_pages == 6
    for _ in range(3):  # three prompts of 7 pages: 4 of a whole window, then 3 of the next
        s.reset()
        s.prefill(tail(7 * PAGE + 3))
        sched.check_prefix()
    kept = [nd for nd in prefix._walk() if nd.wpage is not None]
    assert len(list(prefix._walk())) == 21 and len(kept) == 6 and not prefix.wfree
    newest = max(nd.w_use for nd in kept)
    assert sum(nd.w_use == newest for nd in kept) == 3  # the newest prompt's tail is whole


def test_a_window_larger_than_the_sequence_is_plain_causal_attention(tmp_path):
    """Under ``window_size`` positions no summary is ever read: the reference's
    mixer equals a causal softmax written out here, and the engine equals the
    reference."""
    wide = {**CONFIG, "name": "tiny-evabyte-wide", "window_size": 512}
    path = modelfile.write_artifacts(wide, 5, str(tmp_path), 512)[0]
    ref = families.load(wide, "reference")
    qf = QFile(path, ref)
    prompt = PROMPT[:150]
    want = ref.forward(qf, np.asarray([prompt], np.int32), np.arange(150))[0]
    rng = np.random.default_rng(2)
    xn = jnp.asarray(rng.standard_normal((1, 150, 64)), jnp.float32)
    p = "layers.0."
    args = [qf.raw(p + n) for n in ("q", "k", "v")] + [qf.f32(p + "eva_phi"), qf.f32(p + "eva_mu"),
                                                       qf.raw(p + "wo")]
    got = ref.mixer(xn, *args, heads=4, window=512, chunk=CHUNK, theta=100000.0)
    q, k = (ref.rope(ref.matmul(xn, w).reshape(1, 150, 4, 16), 100000.0) for w in args[:2])
    v = ref.matmul(xn, args[2]).reshape(1, 150, 4, 16)
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision="highest") / 4.0
    s = jnp.where(jnp.tril(jnp.ones((150, 150), bool)), s, -jnp.inf)
    plain = ref.matmul(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v,
                                  precision="highest").reshape(1, 150, 64), args[5])
    np.testing.assert_allclose(got, plain, rtol=1e-5, atol=1e-5)
    eng = InferenceEngine(path, dtype=jnp.float32, cache_dtype=jnp.float32)
    assert off(eng.new_stream().forward(prompt), want) <= TOL
    # ... and with the window of 64 the same tokens give other logits past it, the same before
    narrow = modelfile.write_artifacts({**CONFIG, "name": "tiny-evabyte-narrow"}, 5, str(tmp_path), 512)[0]
    other = ref.forward(QFile(narrow, ref), np.asarray([prompt], np.int32), np.arange(150))[0]
    assert off(other[:WINDOW], want[:WINDOW]) <= TOL and off(other[WINDOW:], want[WINDOW:]) > 1e-2


def test_the_programs_own_counts_and_the_familys(engine):
    """Per row, the entries a decode step's scans visit, by store, against
    what a query must read (``counts.entries_read``): the scans read whole
    chunks up to the bucket's farthest row, never less than a row needs."""
    cfg = engine.cfg
    counts = families.counts(CONFIG)
    slab = llama.init_batch_cache(cfg, 2, dtype=jnp.float32)
    for positions in ([40, 3], [200, 70], [300, 257]):
        out = {}
        llama.forward_step_batched(cfg, engine.params, jnp.asarray([5, 6]), slab,
                                   jnp.asarray(positions), jnp.asarray([True, True]), kv_reads=out)
        reads = {k: np.asarray(v).tolist() for k, v in out.items()}
        step = cfg.eva_scan_chunk
        windows = -(-(max(p % WINDOW for p in positions) + 1) // step) * step
        summaries = -(-max(p // WINDOW * (WINDOW // CHUNK) for p in positions) // step) * step
        assert reads == {"eva_window": [2 * windows] * 2, "eva_summary": [2 * summaries] * 2}
        for p in positions:
            need = counts.entries_read(CONFIG, p)
            assert need == p % WINDOW + 1 + p // WINDOW * (WINDOW // CHUNK)
            assert p / CHUNK <= need <= windows + summaries
    # the step's floor holds wherever the rows stand in their windows
    per_entry = counts.kv_bytes_per_entry(CONFIG)
    assert per_entry == 2 * 2 * 64 * 2
    floor = counts.decode_step_bytes(CONFIG, 2, 270) - counts.weight_bytes_per_step(CONFIG, 2)
    assert floor == 270 / CHUNK * per_entry
    assert floor <= (counts.entries_read(CONFIG, 200) + counts.entries_read(CONFIG, 70)) * per_entry


def test_the_real_configurations_counts_by_hand():
    with open(os.path.join(tiny_root.REPO, "benchmark", "configs", "evabyte-6.5b-q40-16l.json")) as f:
        real = json.load(f)
    counts = families.counts(real)
    q40 = 18 / 32
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008  # ISSUE 39: 202.4 M weights a layer
    assert layer == 202_375_168
    got = counts.weight_bytes_per_step(real, rows=8)
    assert (16 * layer + 320 * 4096) * q40 < got < (16 * layer + 320 * 4096) * q40 * 1.001
    assert counts.kv_bytes_per_entry(real) == 16 * 16384  # 16 KB a position and layer
    # a query at 7500: 1357 keys of its window and 384 summaries of the three before it
    assert counts.entries_read(real, 7500) == 7500 - 6144 + 1 + 3 * 128
    # the floor: 8 rows at 7500 positions, a sixteenth of them
    assert counts.decode_step_bytes(real, 8, 60000) - got == 60000 / 16 * 16 * 16384


@pytest.mark.parametrize("role,shape,d_in,d_held", [
    ("wqkv", [8, 12288], 4096, 12288), ("wo", [8, 4096], 4096, 4096),
    ("gate_up", [8, 22528], 4096, 22016),  # 2 x 11008 columns padded to the kernel's tile
    ("down", [8, 4096], 11008, 4096), ("logits", [8, 320], 4096, 320), ("gate_up", [256, 22528], 4096, 22016)])
def test_a_launch_reads_its_matrix_once(role, shape, d_in, d_held):
    with open(os.path.join(tiny_root.REPO, "benchmark", "configs", "evabyte-6.5b-q40-16l.json")) as f:
        real = json.load(f)
    counts = families.counts(real)
    nbytes, ops = counts.kernel_launch(real, role, shape)
    rows = shape[0]
    assert nbytes == pytest.approx(d_in * d_held * 18 / 32 + rows * d_in + 4 * rows * shape[1])
    assert ops == 2.0 * rows * d_in * d_held
    with pytest.raises(ValueError):
        counts.kernel_launch(real, "held_experts_t8", shape)


@pytest.mark.parametrize("what", ["rollback", "spill", "--spec-draft", "--tp 2", "a second decode"])
def test_paths_that_move_a_row_by_position_refuse_by_name(engine, model, what):
    with pytest.raises(llama.EvaWindowError, match="EVABYTE.*current window of 64 positions"):
        if what == "rollback":
            s = scheduler(engine).new_stream()
            s.prefill(PROMPT)
            s.rollback(10)
        elif what == "spill":
            scheduler(engine, host_spill_bytes=1 << 20)
        elif what == "--spec-draft":
            scheduler(engine, spec_draft=4)
        elif what == "--tp 2":
            InferenceEngine(model, dtype=jnp.float32, tp=2)
        else:
            s = scheduler(engine).new_stream()
            logits = s.prefill(PROMPT)
            decode(s, logits, 3)
            decode(s, logits, 3)
    # a rewind to the start is a reset, not a refusal
    s = scheduler(engine).new_stream()
    s.prefill(PROMPT)
    s.rollback(0)
    assert s.pos == 0


def test_a_piece_longer_than_a_window_and_a_page_of_broken_chunks_are_refused(engine):
    cache = llama.init_cache(engine.cfg, dtype=jnp.float32, layered=True)
    with pytest.raises(ValueError, match="128 tokens does not fit a window store of 64"):
        llama.forward_tokens(engine.cfg, engine.params, jnp.zeros(128, jnp.int32), cache, jnp.int32(0))
    # a page that is not whole chunks: no prefix cache, and the rows are served all the same
    sched = BatchScheduler(engine, n_rows=1, chunk=4, prefix_cache=True, kv_pages=8, page_size=12,
                           prefill_chunk=32)
    assert sched._prefix is None and sched.prefill_chunk == 32


def test_the_new_archs_file_and_the_old_files(tmp_path, model):
    from distributed_llama_tpu.formats.model_file import (
        ArchFlags, ArchType, HeaderKey, ModelFileReader, _header_pairs, read_spec)

    new = read_spec(model)
    assert new.arch_type == ArchType.EVABYTE
    assert (new.window, new.eva_chunk, new.n_pred_heads, new.vocab_size) == (WINDOW, CHUNK, 8, 320)
    assert new.flags == ArchFlags.USE_ROPE | ArchFlags.NORM_UNIT_OFFSET
    entries = ModelFileReader(model).entries
    # all 8 prediction heads are in the file, two float32 vectors a head and layer beside the matrices
    assert entries["wcls"].shape == (8 * 320, 64) and entries["layers.1.eva_phi"].shape == (4, 16)
    assert entries["layers.0.eva_mu"].float_type.name == "F32"
    # an old file carries none of the keys past ROPE_TYPE (test_bench_pins holds its bytes)
    path, _ = modelfile.write_artifacts(tiny_root.CONFIGS["tiny-dense"], 7, str(tmp_path), 512)
    old = read_spec(path)
    assert max(int(k) for k, _ in _header_pairs(old)) < HeaderKey.HEAD_SIZE
    assert (old.window, old.eva_chunk, old.n_pred_heads) == (0, 0, 0)
    dense = config_from_spec(old)
    assert dense.rewinds_by_position and dense.piece_limit == 0 and dense.kv_read_kinds == ()


def test_the_served_head_is_the_next_bytes_and_the_norms_carry_their_offset(engine, model):
    from distributed_llama_tpu.formats.model_file import ModelFileReader

    reader = ModelFileReader(model)
    np.testing.assert_array_equal(np.asarray(engine.params["wcls"]).T, reader.tensor("wcls")[:320])
    g = reader.tensor("layers.0.rms_att")
    assert abs(float(g.mean())) < 0.05  # drawn about 0: what is ADDED to one
    np.testing.assert_array_equal(np.asarray(engine.params["layers"][0]["rms_att"]), 1.0 + g)
    np.testing.assert_array_equal(np.asarray(engine.params["rms_final"]), 1.0 + reader.tensor("rms_final"))
    # the file's flag decides it, in one place: without it the stored weight is the norm's
    from distributed_llama_tpu.engine.weights import load_params
    from distributed_llama_tpu.formats.model_file import ArchFlags

    plain = dataclasses.replace(engine.cfg, flags=int(ArchFlags.USE_ROPE))
    bare = load_params(reader, plain, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(bare["layers"][0]["rms_att"]), g)
    np.testing.assert_array_equal(np.asarray(bare["rms_final"]), reader.tensor("rms_final"))
    # the drawing rule's zero rows hold at this width: 277 pieces, 43 fillers (traffic.FIRST_FILLER_ID)
    from benchmark.harness.traffic import FIRST_FILLER_ID

    head = reader.tensor("wcls")
    assert FIRST_FILLER_ID == 277 and not head[:277].any() and head[277:320].any(axis=1).all()


def _without(monkeypatch, piece):
    """Take one piece of the mathematics out of the program."""
    if piece == "the vector added to a summary's key":
        real = attn_ops.eva_summarise
        monkeypatch.setattr(attn_ops, "eva_summarise",
                            lambda k, v, phi, mu: real(k, v, phi, jnp.zeros_like(mu)))
    elif piece == "the pooling against the learned vector":
        real = attn_ops.eva_summarise
        monkeypatch.setattr(attn_ops, "eva_summarise",
                            lambda k, v, phi, mu: real(k, v, jnp.zeros_like(phi), mu))
    elif piece == "the norm's unit offset":
        from distributed_llama_tpu.ops import q40

        real = q40.rmsnorm_ref
        monkeypatch.setattr(q40, "rmsnorm_ref", lambda x, w, eps=1e-5: real(x, w - 1.0, eps))
    else:
        raise ValueError(piece)


@pytest.mark.parametrize("piece", ["the vector added to a summary's key",
                                   "the pooling against the learned vector",
                                   "the norm's unit offset"])
def test_leaving_a_piece_of_the_mathematics_out_fails_the_tolerance(model, reference, monkeypatch, piece):
    from benchmark.harness.cell import load_check

    _without(monkeypatch, piece)
    jax.clear_caches()
    head = PROMPT[:196]  # its last query reads 4 keys of its own window and 24 summaries
    try:
        got = InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32).new_stream().prefill(head)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    # not by float32's tolerance, and not by the benchmark's for the served Q40 path either
    assert off(got, reference(head)[-1]) > load_check()["logit_tol"]


def test_q40_engine_with_a_bfloat16_cache_stays_inside_the_benchmarks_logit_tolerance(model, reference):
    from benchmark.harness.cell import load_check

    want = reference(PROMPT)
    stream = InferenceEngine(model, dtype="q40").new_stream()
    got = np.concatenate([stream.forward(PROMPT[i:i + 46]) for i in range(0, 230, 46)])
    offs = np.abs(got - want).max(axis=-1) / np.abs(want).max(axis=-1)
    tol = load_check()["logit_tol"]
    # past the first window every position reads summaries pooled from the cache's own bfloat16 keys
    assert np.median(offs[WINDOW:]) <= tol / 2 and np.mean(offs <= tol) >= 0.9, offs


def test_the_cell_runs_through_the_harness_on_the_cpu(tmp_path, monkeypatch):
    """The toy configuration as a cell of the miniature checkout (documents
    asked twice), through ``run_cell`` with ``--trace 2``: the family's
    builder, the server child, the probes judged by the family's reference
    (the long one crosses three windows), warm-up, window, drain, the traced
    phase: ``correct: true``, and the programs' read counts moved."""
    import time

    import test_bench_run
    from benchmark.harness import cell as cell_mod

    root = tiny_root.build(str(tmp_path / "checkout"))
    evabyte_tiny.lay(root)
    monkeypatch.setattr(cell_mod, "_reduce_trace", test_bench_run._cpu_trace_as_device)
    result = cell_mod.run_cell(root, evabyte_tiny.CELL, 2**31 + 39, 3.0, 2, "cpu", time.monotonic())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert {"out_tok_s", "setup_s", "eva_summary_kv_share", "tpot_p50_ms.rows8", "ttft_p50_ms.rows8",
            "stall_p50_ms.rows8", "prefix_hit_share.open"} <= set(metrics)
    # documents of 96-160 positions: rows in their second and third windows read 8 or 16 summaries
    # beside a window store of 64 slots, each as one scan step of 64
    assert 0.0 < metrics["eva_summary_kv_share"] <= 50.0
    # (on a loaded machine a window of 3 s may hold no second ask, and then no match to count)
    if "prefix_window_tail_hit_share" in metrics:
        assert metrics["prefix_window_tail_hit_share"] > 0.0 and metrics["prefix_hit_share.open"] > 20.0


def test_the_chip_witness_of_a_cached_answer_runs_at_the_toy_size(tmp_path):
    """``tools/eva_cache_witness.py`` (the probes of ``correct`` are sent with
    the cache off, so the chip's witness of a resumed row is that script's
    run, PERF.md section 6): its five asks through the toy cell's server, a
    hit inside a window and one shortened to the window's start, each counted
    as what it is and each answer inside the cell's rule against the
    family's reference. The summaries' pool holds every document here, as
    the real cell's does: only the tails' pool turns over."""
    import subprocess
    import sys

    root = tiny_root.build(str(tmp_path / "checkout"))
    evabyte_tiny.lay(root)
    path = os.path.join(root, "benchmark", "workloads", f"{evabyte_tiny.CELL}.json")
    with open(path) as f:
        launch = json.load(f)
    launch["flags"][launch["flags"].index("--kv-pages") + 1] = "96"
    with open(path, "w") as f:
        json.dump(launch, f)
    # 19 positions of template, the document, 28 more that every ask shares: 212, a hit at 208 in
    # the window that starts at 192; the prompt ends at 251, in that window too
    done = subprocess.run(
        [sys.executable, os.path.join(tiny_root.REPO, "tools", "eva_cache_witness.py"),
         "--workload", evabyte_tiny.CELL, "--platform", "cpu", "--seed", str(2**31 + 77),
         "--document", "165", "--question", "4", "--other-document", "164", "--others", "3"],
        cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["ok"] is True
    assert result["hit"]["moved"] == {"hit": 1, "shortened": 0, "miss": 0, "matched_tokens": 208}
    assert result["shortened"]["moved"] == {"hit": 0, "shortened": 1, "miss": 0, "matched_tokens": 192}
    assert result["hit"]["positions"] == result["shortened"]["positions"] == 32


def test_the_real_cells_entries_and_flags():
    """What ISSUE 39 asked the cell to report, and the flags it is served
    under: a row for each caller and no more, the mix K-EXAONE's cell sends,
    unedited, and a context that holds the mix's caps. A name is in a list
    once; how many cells there are and in what order is no test's business
    here."""
    import test_bench_schema
    from benchmark.harness import cell as cell_mod

    root = tiny_root.REPO
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        real = json.load(f)
    lists = {m["name"]: m.get("workloads") for m in real["per_layer"]}
    name = evabyte_tiny.REAL_CELL
    for entry in ("ttft_p50_ms.rows8", "tpot_p50_ms.rows8", "stall_p50_ms.rows8", "queue_ms_mean.open",
                  "prefix_hit_share.open", "prefill_ms_mean.open", "compiles_in_window.open",
                  "prefill_chunks_ahead_mean.open", "server_ttft_ms_mean.open", "q40_dense_roofline",
                  "prefix_window_tail_hit_share", "eva_summary_kv_share"):
        assert lists[entry].count(name) == 1
    assert lists["decode_hbm_share"] is None  # the whole step's share: reported in every cell
    cells = [w["name"] for w in real["workloads"]]
    assert cells.count(name) == 1 and len(set(cells)) == len(cells) <= 24
    assert next(w for w in real["workloads"] if w["name"] == name)["chips"] == 1
    entry = next(c for c in real["configs"] if c["name"] == "evabyte-6.5b-q40-16l")
    assert entry["reduced"] == ["num_hidden_layers"]
    with open(os.path.join(root, "benchmark", "layer_metrics", "eva_summary_kv_share.json")) as f:
        reader = json.load(f)
    metric = next(m for m in real["per_layer"] if m["name"] == "eva_summary_kv_share")
    assert set(reader) == {"unit", "source", "what", "reader"}
    assert (reader["unit"], reader["source"]) == (metric["unit"], metric["source"])
    # every entry the cell is listed in moves a metric the cell is judged on
    test_bench_schema.test_a_per_layer_entry_moves_a_metric_that_every_cell_of_its_list_reports(real)
    cell = cell_mod.Cell(root, name)
    assert cell.flag("--parallel", 0) == int(cell.mix["callers"]) == 8
    assert cell.launch["traffic"] == "doc_sessions" and cell.flag("--max-seq-len", 0) == 16384
    assert cell.mix["context_cap"] <= 16384 - 32 and cell.mix["prompt_cap"] <= 16384 - 256
    # the long probe: two whole windows and 32 positions of a third
    assert cell.check["long_probe_prompt"] == 2 * 2048 + 32 and cell.check["long_probes"] == 1
    # the pools by the rule: 1536 pages of summaries, half a window's pages a row and a window beside
    assert eva_tail_pages(8, 2048 // 64) == 8 * 16 + 32
