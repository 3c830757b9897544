"""Batched multi-stream decode (ISSUE 2): per-row parity with the
single-stream serving flow, join/leave between chunks, retired-row cache
integrity, the blocked batched attention kernel, and the API server's
scheduler-backed concurrent completions."""

import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.engine.batch import BatchScheduler

from tests.model_utils import random_tensors, tiny_spec, write_model_file

PROMPTS = [[1, 5, 9], [2, 4, 6, 8], [3, 7]]
SAMPLING = [(0.0, 0.9, 11), (0.9, 0.8, 13), (0.7, 0.95, 17)]  # (temp, topp, seed)
N_TOKENS = 10


def build_engine(tmp_path, name="model.m", seed=0, seq_len=96):
    spec = tiny_spec(seq_len=seq_len)
    path = str(tmp_path / name)
    write_model_file(path, spec, random_tensors(spec, seed=seed))
    return InferenceEngine(path, dtype=jnp.float32)


def single_stream_tokens(engine, prompt, temp, topp, seed, n):
    """The reference stream: one request through the single-stream fused
    serving flow (prefill_device → stream_decode) on its own EngineStream."""
    s = engine.new_stream()
    first = s.prefill_device(prompt, temp, topp, seed)
    got = []

    def on_token(prev, tok):
        got.append(tok)
        return len(got) < n

    s.stream_decode(first, on_token, temp, topp, seed=seed, chunk=4,
                    limit=s.pos + n, first_prev=prompt[-1])
    return got


def batch_stream_tokens(stream, prompt, temp, topp, seed, n):
    """The same request through a BatchScheduler row."""
    first = stream.prefill_device(prompt, temp, topp, seed)
    got = []

    def on_token(prev, tok):
        got.append(tok)
        return len(got) < n

    stream.stream_decode(first, on_token, temp, topp, seed=seed,
                         limit=stream.pos + n, first_prev=prompt[-1])
    return got


class TestSlabPrefill:
    def test_slab_prefill_matches_single_prefill(self, tmp_path):
        """The slab prefill extracts the row, runs the ORDINARY forward and
        writes it back — its logits must match the single-stream prefill."""
        e1 = build_engine(tmp_path, "a.m")
        want = e1.prefill([1, 5, 9, 2, 8])

        e2 = build_engine(tmp_path, "b.m")
        sched = BatchScheduler(e2, n_rows=2, chunk=4)
        s = sched.new_stream()
        got = s.prefill([1, 5, 9, 2, 8])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert s.pos == 5

    def test_context_overflow_raises(self, tmp_path):
        e = build_engine(tmp_path, seq_len=24)
        sched = BatchScheduler(e, n_rows=1, chunk=4)
        s = sched.new_stream()
        with pytest.raises(ValueError, match="context overflow"):
            s.prefill(list(range(1, 30)))


class TestBatchedParity:
    """Per-row bit-parity of the batched decode with the single-stream
    chunked decode: mixed temperatures, top-p, seeds, prompt lengths and
    positions share one batched program, and every row's token stream is
    identical to its solo run for the same per-row PRNG key."""

    def test_rows_match_single_stream_mixed_params(self, tmp_path):
        ref_engine = build_engine(tmp_path, "ref.m")
        refs = [
            single_stream_tokens(ref_engine, p, t, tp, sd, N_TOKENS)
            for p, (t, tp, sd) in zip(PROMPTS, SAMPLING)
        ]

        engine = build_engine(tmp_path, "bat.m")
        sched = BatchScheduler(engine, n_rows=3, chunk=4)
        streams = [sched.new_stream() for _ in range(3)]
        outs = [None] * 3
        errors = []

        def run(i):
            try:
                t, tp, sd = SAMPLING[i]
                outs[i] = batch_stream_tokens(
                    streams[i], PROMPTS[i], t, tp, sd, N_TOKENS
                )
            except Exception as e:  # pragma: no cover - failure detail
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=180)
        assert not errors, errors
        assert outs == refs

    def test_row_reuse_after_completion(self, tmp_path):
        """A retired row serves its next request from scratch (reset between
        requests mirrors the API server's slot recycling)."""
        ref_engine = build_engine(tmp_path, "ref.m")
        want = single_stream_tokens(ref_engine, [1, 5, 9], 0.0, 0.9, 7, 6)

        engine = build_engine(tmp_path, "bat.m")
        sched = BatchScheduler(engine, n_rows=2, chunk=4)
        s = sched.new_stream()
        first = batch_stream_tokens(s, [1, 5, 9], 0.0, 0.9, 7, 6)
        s.reset()
        second = batch_stream_tokens(s, [1, 5, 9], 0.0, 0.9, 7, 6)
        assert first == want
        assert second == want

    def test_rows_on_lane_wide_heads_decode_through_the_row_bounded_kernel(self, tmp_path):
        """Heads of 128 over a slab of two whole chunks: a bucket of two
        rows attends through ``decode_attention.slab_decode_scan`` (interpret
        mode here), each row bounded by its own position; a bucket of ONE
        row over so short a slab keeps the XLA loop
        (``attention.ONE_ROW_LOOP_SLOTS``); and a greedy row's tokens are its
        solo run's (which reads its cache through the single-stream scan)."""
        from distributed_llama_tpu import telemetry

        def build(name):
            spec = tiny_spec(dim=256, n_heads=2, n_kv_heads=1, seq_len=1024)
            write_model_file(str(tmp_path / name), spec, random_tensors(spec, seed=3))
            return InferenceEngine(str(tmp_path / name), dtype=jnp.float32)

        ref_engine = build("ref.m")
        prompts = [[1, 5, 9, 2, 8, 3], [2, 4]]
        refs = [single_stream_tokens(ref_engine, p, 0.0, 0.9, 5, 6) for p in prompts]
        telemetry.enable()
        try:
            def taken():
                paths = telemetry.REGISTRY.counter(
                    "dllama_kernel_path_total", labelnames=("kernel", "path"))
                return tuple(paths.labels(kernel="decode_attention", path=p).value > 0
                             for p in ("pallas_rowbound", "xla_scan"))

            telemetry.reset()
            sched = BatchScheduler(build("bat.m"), n_rows=2, chunk=4)
            streams = [sched.new_stream() for _ in prompts]
            outs = [join(sched, s, (p, 0.0, 0.9, 5)) for s, p in zip(streams, prompts)]
            sched.kick()
            outs = [out + take(sched, s, 5) for out, s in zip(outs, streams)]
            assert sched._decode_built == {2} and taken() == (True, False)
            telemetry.reset()
            alone = BatchScheduler(build("one.m"), n_rows=2, chunk=4)
            s = alone.new_stream()
            out = join(alone, s, (prompts[0], 0.0, 0.9, 5))
            alone.kick()
            out += take(alone, s, 5)
            assert alone._decode_built == {1} and taken() == (False, True)
        finally:
            telemetry.reset()
            telemetry.disable()
        assert outs == refs and out == refs[0]

    def test_join_mid_stream(self, tmp_path):
        """A second request joining BETWEEN chunks (bucket grows 1 → 2)
        must not perturb the already-running row, and both rows must match
        their solo references."""
        ref_engine = build_engine(tmp_path, "ref.m")
        ref_a = single_stream_tokens(ref_engine, PROMPTS[0], 0.0, 0.9, 11, 12)
        ref_b = single_stream_tokens(ref_engine, PROMPTS[1], 0.9, 0.8, 13, 6)

        engine = build_engine(tmp_path, "bat.m")
        sched = BatchScheduler(engine, n_rows=2, chunk=4)
        sa, sb = sched.new_stream(), sched.new_stream()
        out_a, out_b = [], []
        a_mid = threading.Event()
        errors = []

        def run_a():
            try:
                first = sa.prefill_device(PROMPTS[0], 0.0, 0.9, 11)

                def on_token(prev, tok):
                    out_a.append(tok)
                    if len(out_a) == 5:
                        a_mid.set()
                    return len(out_a) < 12

                sa.stream_decode(first, on_token, 0.0, 0.9, seed=11,
                                 limit=sa.pos + 12,
                                 first_prev=PROMPTS[0][-1])
            except Exception as e:  # pragma: no cover
                errors.append(e)
                a_mid.set()

        def run_b():
            try:
                assert a_mid.wait(timeout=120)
                out_b.extend(
                    batch_stream_tokens(sb, PROMPTS[1], 0.9, 0.8, 13, 6)
                )
            except Exception as e:  # pragma: no cover
                errors.append(e)

        ta, tb = threading.Thread(target=run_a), threading.Thread(target=run_b)
        ta.start(), tb.start()
        ta.join(timeout=180), tb.join(timeout=180)
        assert not errors, errors
        assert out_a == ref_a
        assert out_b == ref_b


class TestMoeBatched:
    def test_moe_rows_track_single_stream_greedy(self, tmp_path):
        """MoE batched decode takes the dense expert path (every expert,
        zero-weighted ones contributing exact zeros) — greedy streams must
        track the single-stream top-k switch (parity up to expert-sum
        reordering; llama.forward_step_batched docstring)."""
        from tests.test_moe import mixtral_spec

        spec = mixtral_spec(seq_len=96)
        path = str(tmp_path / "moe.m")
        write_model_file(path, spec, random_tensors(spec, seed=1))
        ref_engine = InferenceEngine(path, dtype=jnp.float32)
        refs = [
            single_stream_tokens(ref_engine, p, 0.0, 0.9, 5, 8)
            for p in PROMPTS[:2]
        ]

        engine = InferenceEngine(path, dtype=jnp.float32)
        sched = BatchScheduler(engine, n_rows=2, chunk=4)
        streams = [sched.new_stream() for _ in range(2)]
        outs = [None] * 2
        errors = []

        def run(i):
            try:
                outs[i] = batch_stream_tokens(
                    streams[i], PROMPTS[i], 0.0, 0.9, 5, 8
                )
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert outs == refs


class TestRetiredRows:
    def test_retired_row_cache_untouched(self, tmp_path):
        """While another row decodes, a retired row riding the bucket as an
        inactive hole must not see ONE byte of its cache change (its chat
        prefix must stay reusable): inactive rows' writes target a dropped
        out-of-bounds slot."""
        engine = build_engine(tmp_path)
        sched = BatchScheduler(engine, n_rows=2, chunk=4)
        s0, s1 = sched.new_stream(), sched.new_stream()

        # row 0 serves a request and retires
        batch_stream_tokens(s0, PROMPTS[0], 0.0, 0.9, 11, 5)
        before = [
            (np.asarray(k)[0].copy(), np.asarray(v)[0].copy())
            for k, v in sched._slab
        ]
        # row 1 decodes: bucket 2 includes retired row 0 as an inactive hole
        batch_stream_tokens(s1, PROMPTS[1], 0.9, 0.8, 13, 8)
        after = [(np.asarray(k)[0], np.asarray(v)[0]) for k, v in sched._slab]
        for l, ((kb, vb), (ka, va)) in enumerate(zip(before, after)):
            np.testing.assert_array_equal(kb, ka, err_msg=f"layer {l} keys")
            np.testing.assert_array_equal(vb, va, err_msg=f"layer {l} values")


class TestBatchedBlockedAttention:
    def test_matches_masked_einsum_mixed_positions(self):
        """The blocked batched attention (dynamic chunk bound, per-row
        masks) must reproduce the full-S masked softmax einsum for rows at
        wildly different positions — including a fresh row at pos 0 whose
        later chunks are fully masked."""
        from distributed_llama_tpu.ops.attention import batched_decode_attention

        B, K, M, hd, S, chunk = 3, 2, 2, 8, 1024, 256
        rng = np.random.RandomState(0)
        qg = jnp.asarray(rng.randn(B, K, M, hd).astype(np.float32))
        keys = jnp.asarray(rng.randn(B, S, K, hd).astype(np.float32))
        values = jnp.asarray(rng.randn(B, S, K, hd).astype(np.float32))
        pos = jnp.asarray([0, 517, 1023], jnp.int32)

        got = batched_decode_attention(qg, (keys, values), pos, chunk)

        scores = jnp.einsum("bkmh,bskh->bkms", qg, keys) / np.sqrt(hd)
        mask = (jnp.arange(S)[None, :] <= pos[:, None])[:, None, None, :]
        weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        want = jnp.einsum("bkms,bskh->bkmh", weights, values)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_reads_only_bucket_rows_of_larger_slab(self):
        """A dispatch bucket below B_max passes a slab with MORE rows than
        queries: only the first B rows may be read."""
        from distributed_llama_tpu.ops.attention import batched_decode_attention

        B, B_slab, K, M, hd, S, chunk = 2, 4, 2, 1, 8, 512, 256
        rng = np.random.RandomState(1)
        qg = jnp.asarray(rng.randn(B, K, M, hd).astype(np.float32))
        keys = jnp.asarray(rng.randn(B_slab, S, K, hd).astype(np.float32))
        values = jnp.asarray(rng.randn(B_slab, S, K, hd).astype(np.float32))
        pos = jnp.asarray([100, 400], jnp.int32)
        got = batched_decode_attention(qg, (keys, values), pos, chunk)
        want = batched_decode_attention(qg, (keys[:B], values[:B]), pos, chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# The device carry (ISSUE 27): the token a row's next chunk feeds first lives
# on the device in ONE vector the chunk program advances; a join writes its
# row's entry. Driven single-threaded through the scheduler's own steps
# (_join / kick / next_token / _leave: what stream_decode calls), so a test
# decides what is in flight when a row joins or leaves.
# ---------------------------------------------------------------------------

LONG_PROMPTS = [[1, 5, 9, 3, 7], [2, 4, 6, 8, 10, 12], [3, 7, 11], [9, 8, 7, 6]]


def carry_sched(tmp_path, paged: bool, n_rows: int, name="bat.m"):
    kw = dict(prefix_cache=True, kv_pages=24 * n_rows, page_size=4) if paged else {}
    return BatchScheduler(build_engine(tmp_path, name), n_rows=n_rows, chunk=4, **kw)


def reference(tmp_path, requests, n):
    """Each request's solo stream (prompt, temp, topp, seed) → n tokens, the
    fused first token included."""
    engine = build_engine(tmp_path, "ref.m")
    return [single_stream_tokens(engine, p, t, tp, sd, n) for p, t, tp, sd in requests]


def join(sched, stream, request):
    """Admission as the server does it: prefill + fused first token on the
    device, then the join. Returns the request's first token (fetched)."""
    prompt, temp, topp, seed = request
    first = stream.prefill_device(prompt, temp, topp, seed)
    sched._join(stream, first, temp, topp, seed, 0)
    return [stream.fetch_first_token(first)]


def take(sched, stream, n):
    return [sched.next_token(stream) for _ in range(n)]


def requests_for(k):
    return [
        (LONG_PROMPTS[i % len(LONG_PROMPTS)], *SAMPLING[i % len(SAMPLING)][:2], 100 + i)
        for i in range(k)
    ]


@pytest.mark.parametrize("paged", [False, True], ids=["unpaged", "paged"])
class TestCarryParity:
    """Bit-parity of every row's stream with its solo chunked decode across
    the cases the device carry makes new."""

    def test_join_while_a_chunk_is_in_flight(self, tmp_path, paged):
        """B's fused first token reaches the next chunk through the carry:
        its write is queued behind the chunk that runs without B."""
        reqs = requests_for(2)
        want = reference(tmp_path, reqs, 9)
        sched = carry_sched(tmp_path, paged, n_rows=2)
        a, b = sched.new_stream(), sched.new_stream()
        out_a = join(sched, a, reqs[0])
        sched.kick()
        assert sched._pending is not None  # A's chunk, dispatched and unfetched
        out_b = join(sched, b, reqs[1])
        assert sched._pending is not None and len(sched._pending[2]) == 1
        out_a += take(sched, a, 8)
        out_b += take(sched, b, 8)
        assert [out_a, out_b] == want

    def test_slot_retaken_before_the_orphaned_chunk_is_delivered(self, tmp_path, paged):
        """A leaves with a chunk of its row still in flight; B takes the
        slot at once. The orphaned chunk advanced the slot's carry entry
        with A's token; B's join, queued behind it, overwrites that."""
        reqs = requests_for(3)
        want = reference(tmp_path, reqs, 9)
        sched = carry_sched(tmp_path, paged, n_rows=2)
        a, c = sched.new_stream(), sched.new_stream()
        out_a, out_c = join(sched, a, reqs[0]), join(sched, c, reqs[1])
        out_a += take(sched, a, 4)  # chunk 1 fetched and read by A
        sched.kick()
        assert len(sched._pending[2]) == 2  # chunk 2: both rows, unfetched
        sched._leave(a)
        a.reset()
        out_b = join(sched, a, reqs[2])  # same slot, chunk 2 still pending
        assert sched._pending is not None
        out_c += take(sched, c, 8)  # fetches chunk 2: row 0 of it is orphaned
        out_b += take(sched, a, 8)
        assert out_a == want[0][:5]
        assert [out_c, out_b] == want[1:]

    def test_rollback_between_chunks(self, tmp_path, paged):
        """An early stop rewinds the row past tokens a chunk had already
        decoded; the row's next request (reset, same slot) and its
        neighbour, which kept decoding, both read their own streams."""
        reqs = requests_for(3)
        want = reference(tmp_path, reqs, 13)
        sched = carry_sched(tmp_path, paged, n_rows=2)
        a, c = sched.new_stream(), sched.new_stream()
        out_c = join(sched, c, reqs[1])
        got = []
        first = a.prefill_device(*reqs[0])
        a.stream_decode(  # stops after 6 of the 8 tokens its chunks decoded
            first, lambda prev, tok: (got.append(tok), len(got) < 6)[1],
            reqs[0][1], reqs[0][2], seed=reqs[0][3], first_prev=reqs[0][0][-1],
        )
        assert got == want[0][:6] and a.pos == len(reqs[0][0]) + 5
        a.rollback(0)
        out_a = join(sched, a, reqs[2])
        out_c += take(sched, c, 12)
        out_a += take(sched, a, 12)
        assert [out_c, out_a] == want[1:]

    def test_preempted_row_requeues_on_its_slot(self, tmp_path, paged):
        """A preemption between chunks retires the victim with a chunk of
        its row in flight; requeued on the same slot with the same seed it
        streams what an uncontended run streams, and so does the survivor."""
        from distributed_llama_tpu.engine import faults

        reqs = requests_for(2)
        want = reference(tmp_path, reqs, 9)
        sched = carry_sched(tmp_path, paged, n_rows=2)
        a, c = sched.new_stream(), sched.new_stream()
        a.priority, c.priority = 0, 5
        out_a, out_c = join(sched, a, reqs[0]), join(sched, c, reqs[1])
        out_c += take(sched, c, 4)
        sched.kick()
        assert sched.preempt_below(5)
        with pytest.raises(faults.RowPreempted):
            take(sched, a, 8)
        sched._leave(a)
        a.reset()
        a.priority = 0
        out_a = join(sched, a, reqs[0])
        out_c += take(sched, c, 4)
        out_a += take(sched, a, 8)
        assert [out_a, out_c] == want

    def test_bucket_1_to_4_to_16_and_back(self, tmp_path, paged):
        """Row 0 decodes through every bucket its neighbours' joins and
        leaves make (1, 4, 16, 1): the carry is one vector for all of
        them, and each program advances only its bucket's rows."""
        reqs = requests_for(5)
        want = reference(tmp_path, reqs, 17)
        sched = carry_sched(tmp_path, paged, n_rows=16)
        streams = [sched.new_stream() for _ in range(16)]
        buckets = []
        real = sched._note_dispatched
        sched._note_dispatched = lambda bucket, *a: (buckets.append(bucket), real(bucket, *a))[1]
        outs = {0: join(sched, streams[0], reqs[0])}
        outs[0] += take(sched, streams[0], 4)  # bucket 1
        for row, req in ((1, reqs[1]), (3, reqs[2])):
            outs[row] = join(sched, streams[row], req)
        outs[0] += take(sched, streams[0], 4)  # bucket 4
        outs[15] = join(sched, streams[15], reqs[3])
        outs[0] += take(sched, streams[0], 4)  # bucket 16
        for row in (1, 3, 15):
            outs[row] += take(sched, streams[row], 4)
            sched._leave(streams[row])
        outs[0] += take(sched, streams[0], 4)  # alone again
        # row 1 comes back with another request while row 0 runs on
        streams[1].reset()
        outs[1] = outs[1], join(sched, streams[1], reqs[4]) + take(sched, streams[1], 4)
        assert outs[0] == want[0]
        assert outs[1] == (want[1][:5], want[4][:5])
        assert outs[3] == want[2][:5] and outs[15] == want[3][:5]
        assert buckets[0] == 1 and 4 in buckets and 16 in buckets
        assert buckets[buckets.index(16):].count(1) >= 1  # and back


def test_no_chunk_is_enqueued_behind_the_one_being_fetched(tmp_path):
    """One decode chunk on the device queue at a time: while a fetch is in
    flight a dispatch is a no-op, and goes out once that chunk is
    delivered. (A second chunk in the queue stands in front of every
    prompt piece that arrives meanwhile.)"""
    sched = carry_sched(tmp_path, paged=False, n_rows=2)
    a = sched.new_stream()
    join(sched, a, requests_for(1)[0])
    with sched._cond:
        sched._begin_fetch_locked()  # some thread is blocked in a fetch
    sched.kick()
    assert sched._pending is None
    with sched._cond:
        sched._fetching = False  # delivered
    sched.kick()
    assert sched._pending is not None and take(sched, a, 4)


def test_prompt_pieces_queued_at_a_delivery_run_before_the_next_chunk(tmp_path):
    """A decode chunk is not enqueued in front of the prompt pieces that were
    on the device's queue when the last chunk was delivered: the dispatch
    declines, the consumer that asked waits for the piece with the lock free
    and asks again. Pieces dispatched after that delivery are not waited for."""

    class Piece:
        ready, waited = False, 0

        def is_ready(self):
            return self.ready

        def block_until_ready(self):
            self.waited += 1
            self.ready = True

    sched = carry_sched(tmp_path, paged=False, n_rows=2)
    a, b = sched.new_stream(), sched.new_stream()
    reqs = requests_for(2)
    want = reference(tmp_path, reqs, 9)
    out_a = join(sched, a, reqs[0])
    assert sched._last_piece is not None  # the prompt's own piece
    out_a += take(sched, a, 4)  # a delivery: what was queued by then goes first
    assert sched._pieces_first is sched._last_piece
    piece = sched._pieces_first = Piece()
    sched.kick()
    assert sched._pending is None and not piece.waited  # declined, nobody blocked
    out_b = join(sched, b, reqs[1])  # a prompt that arrives meanwhile is dispatched at once
    out_a += take(sched, a, 4)  # waits for the piece, then dispatches for both rows
    assert piece.waited == 1 and sched._pieces_first is not piece
    out_b += take(sched, b, 4)
    assert [out_a, out_b] == [want[0], want[1][:5]]


def test_carry_survives_concurrent_joins_and_leaves(tmp_path):
    """More request threads than cores, a short switch interval, requests of
    unequal length joining and leaving as they please: every join's write
    to the carry and every chunk's advance of it happen under the
    scheduler's lock, so each stream still reads its solo tokens."""
    import sys

    n_rows, rounds = 12, 2
    reqs = requests_for(n_rows * rounds)
    lengths = [5 + (3 * i) % 9 for i in range(len(reqs))]
    want = reference(tmp_path, reqs, max(lengths))
    sched = carry_sched(tmp_path, paged=False, n_rows=n_rows)
    streams = [sched.new_stream() for _ in range(n_rows)]
    got = [None] * len(reqs)
    errors = []

    def run(row):
        try:
            for k in range(rounds):
                i = row * rounds + k
                streams[row].reset()
                got[i] = batch_stream_tokens(streams[row], *reqs[i], lengths[i])
        except Exception as e:  # pragma: no cover - failure detail
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(row,)) for row in range(n_rows)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=240)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors
    assert got == [w[:n] for w, n in zip(want, lengths)]


def device_work(fn):
    """(device programs launched, host-to-device transfers) while ``fn``
    ran, read off the profiler's host trace: every launch is one
    ``...Executable::Execute`` event and every transfer one ``DevicePut...``
    event, from the jit fast path too, which no Python hook sees."""
    import collections
    import glob
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        (trace,) = glob.glob(d + "/plugins/profile/*/*.xplane.pb")
        names = collections.Counter(
            ev.name
            for plane in jax.profiler.ProfileData.from_file(trace).planes
            for line in plane.lines
            for ev in line.events
        )
    programs = sum(n for name, n in names.items() if name.endswith("Executable::Execute"))
    transfers = sum(n for name, n in names.items() if name.startswith("DevicePut"))
    return programs, transfers


@pytest.mark.parametrize("paged", [False, True], ids=["unpaged", "paged"])
@pytest.mark.parametrize("joined", [1, 4, 16])
def test_a_dispatch_issues_the_same_device_work_at_any_row_count(tmp_path, joined, paged):
    """Under the scheduler's lock a decode dispatch launches ONE program
    (the chunk) and moves one host buffer per row vector, whatever the
    bucket: nothing per row. (The parent launched 5 + 3 a row.)"""
    # the hook sees what it should: one launch, one transfer, for a jitted
    # call with one numpy argument
    add = jax.jit(lambda x, y: x + y)
    x = jnp.arange(4)
    add(x, np.arange(4))
    assert device_work(lambda: add(x, np.arange(4))) == (1, 1)

    sched = carry_sched(tmp_path, paged, n_rows=16)
    streams = [sched.new_stream() for _ in range(16)]
    for stream, req in zip(streams[:joined], requests_for(joined)):
        join(sched, stream, req)
    for stream in streams[:joined]:
        take(sched, stream, 4)  # chunk 1: the bucket's program is built and run
    assert sched._pending is None

    def dispatch():
        with sched._cond:
            sched._dispatch_locked()

    programs, transfers = device_work(dispatch)
    assert sched._pending is not None and len(sched._pending[2]) == joined
    # pos, active, temps, topps, topks, seeds; a one-chip scheduler with a pool
    # copies hits into their rows and dispatches the program without pages
    # (no tables, no matched): the same six
    assert (programs, transfers) == (1, 6)


@pytest.fixture
def counted(monkeypatch):
    """Telemetry on, and a function that binds an engine's instruments while
    it is: ``chunks()`` then reads the decode chunks counted by the arm
    their sampler took."""
    from distributed_llama_tpu import telemetry

    telemetry.reset()
    telemetry.enable()

    def bind(engine):
        monkeypatch.setattr(engine, "_tel", telemetry.EngineInstruments())
        return engine

    def chunks() -> dict:
        c = telemetry.REGISTRY.get("dllama_decode_chunk_sampler_total")
        return {p: c.labels(path=p).value for p in ("greedy", "sampled")}

    yield bind, chunks
    telemetry.disable()
    telemetry.reset()


def handed_temperatures(sched) -> list:
    """Record the temperature vector of every decode chunk the scheduler
    hands its program from here on."""
    seen = []
    real = sched._decode_chunk_program
    sched._decode_chunk_program = lambda active, pos, temps, *a: (
        seen.append((active.copy(), temps.copy())), real(active, pos, temps, *a))[1]
    return seen


class TestSamplerArmCounted:
    """ISSUE 46: the program skips the sampler's softmax and top-k in a step
    whose temperatures are all 0, so a row that is not live must not ask for
    a sample, and the chunk is counted from the vector the program reads."""

    def test_a_row_that_is_not_live_asks_for_no_sample(self, tmp_path, counted):
        bind, chunks = counted
        greedy = [(LONG_PROMPTS[0], 0.0, 0.9, 100), (LONG_PROMPTS[2], 0.0, 0.9, 102)]
        want = reference(tmp_path, greedy, 9)
        sched = BatchScheduler(bind(build_engine(tmp_path, "bat.m")), n_rows=4, chunk=4)
        streams = [sched.new_stream() for _ in range(4)]
        seen = handed_temperatures(sched)
        # rows 0 and 2 live, row 1 never joined: a bucket of 4 with two dead rows
        outs = [join(sched, streams[0], greedy[0]), join(sched, streams[2], greedy[1])]
        outs[0] += take(sched, streams[0], 8)
        outs[1] += take(sched, streams[2], 8)
        assert outs == want
        assert seen and all(active.tolist() == [True, False, True, False] for active, _ in seen)
        assert all(temps.tolist() == [0.0] * 4 for _, temps in seen)
        assert chunks() == {"greedy": len(seen), "sampled": 0}

    def test_one_sampling_row_counts_the_chunk_sampled_and_moves_no_greedy_token(
            self, tmp_path, counted):
        bind, chunks = counted
        reqs = [(LONG_PROMPTS[0], 0.0, 0.9, 100), (LONG_PROMPTS[1], 0.9, 0.8, 101),
                (LONG_PROMPTS[2], 0.0, 0.9, 102)]
        want = reference(tmp_path, reqs, 9)  # each request alone
        sched = BatchScheduler(bind(build_engine(tmp_path, "bat.m")), n_rows=4, chunk=4)
        streams = [sched.new_stream() for _ in range(4)]
        seen = handed_temperatures(sched)
        outs = [join(sched, s, r) for s, r in zip(streams, reqs)]
        for out, s in zip(outs, streams):
            out += take(sched, s, 8)
        assert outs == want
        # the sampling row's temperature as it is; the dead row's, 0
        assert seen and all(
            temps.tolist() == [0.0, np.float32(0.9), 0.0, 0.0] for _, temps in seen)
        assert chunks() == {"greedy": 0, "sampled": len(seen)}
        # the sampling request gone, the greedy ones' next chunks skip the sampler again
        streams[1].reset()
        seen.clear()
        take(sched, streams[0], 4)
        assert seen and all(not temps.any() for _, temps in seen)
        assert chunks()["greedy"] == len(seen)


class TestBuiltBuckets:
    def test_a_bucket_never_built_rides_a_larger_one_that_was(self, tmp_path):
        """Under load a program build stalls every lane: a row bucket first
        met after a larger one has run dispatches the larger program (the
        extra rows masked), and the tokens are the same."""
        prompt, n = PROMPTS[0], 6
        temp, topp, seed = SAMPLING[0]
        want = single_stream_tokens(build_engine(tmp_path, "a.m"), prompt, temp, topp, seed, n)

        sched = BatchScheduler(build_engine(tmp_path, "b.m"), n_rows=4, chunk=4)
        streams = [sched.new_stream() for _ in range(4)]
        assert sched._built_bucket(2) == 2  # nothing built: the bucket itself
        sched._decode_built = {4}
        buckets = []
        real = sched._note_dispatched
        sched._note_dispatched = lambda bucket, *a: (buckets.append(bucket), real(bucket, *a))[1]
        assert batch_stream_tokens(streams[1], prompt, temp, topp, seed, n) == want
        assert set(buckets) == {4} and sched._decode_built == {4}
        # a bucket that has run is used as it is
        sched._decode_built = {2, 4}
        buckets.clear()
        streams[1].reset()
        assert batch_stream_tokens(streams[1], prompt, temp, topp, seed, n) == want
        assert set(buckets) == {2}


    @pytest.mark.parametrize("paged", [False, True], ids=["plain", "paged"])
    def test_the_widest_program_is_built_before_traffic_and_noted_nowhere(self, tmp_path, paged):
        """``build_widest_decode_program`` runs the all-rows program with
        every row inactive: slab and carry keep their bytes, no bucket is
        noted as built (a lone stream still builds and runs the one-row
        program), and the first chunk of all rows builds nothing."""
        from distributed_llama_tpu.models import sampling

        prompt, n = PROMPTS[0], 6
        temp, topp, seed = SAMPLING[0]
        # a context of its own (and one a case, now that both build the same
        # program): the programs' cache is the process's, and keyed by the config
        seq_len = 80 if paged else 88
        want = single_stream_tokens(
            build_engine(tmp_path, "a.m", seq_len=seq_len), prompt, temp, topp, seed, n)
        # with a pool too: hits are copied into rows, the chunk reads no pages
        program = sampling.decode_chunk_batched
        kw = dict(prefix_cache=True, kv_pages=8, page_size=8) if paged else {}
        sched = BatchScheduler(build_engine(tmp_path, "b.m", seq_len=seq_len), n_rows=4, chunk=4, **kw)
        streams = [sched.new_stream() for _ in range(4)]
        before = [np.asarray(a).copy() for a in jax.tree.leaves((sched._slab, sched._carry))]
        built = program._cache_size()
        sched.build_widest_decode_program()
        assert program._cache_size() == built + 1 and sched._decode_built == set()
        for was, now in zip(before, jax.tree.leaves((sched._slab, sched._carry))):
            np.testing.assert_array_equal(was, np.asarray(now))
        buckets = []
        real = sched._note_dispatched
        sched._note_dispatched = lambda bucket, *a: (buckets.append(bucket), real(bucket, *a))[1]
        assert batch_stream_tokens(streams[0], prompt, temp, topp, seed, n) == want
        assert set(buckets) == {1} and program._cache_size() == built + 2
        # a chunk of all four rows takes the same vectors to the same program: it is there
        sched.build_widest_decode_program()
        assert program._cache_size() == built + 2


class TestBatchApi:
    """The API server's StreamSlots submit into the shared scheduler:
    completions through the batched path match the classic per-stream
    path, and concurrent requests coalesce."""

    def _state(self, tmp_path, name, batch: bool):
        from distributed_llama_tpu.formats.tokenizer_file import (
            TokenizerData,
            write_tokenizer_file,
        )
        from distributed_llama_tpu.server.api import ApiState
        from distributed_llama_tpu.tokenizer import Sampler, Tokenizer

        from tests.test_tokenizer import make_sentencepiece_like_tokenizer

        base = make_sentencepiece_like_tokenizer()
        spec = tiny_spec(seq_len=160, vocab_size=base.vocab_size)
        model_path = str(tmp_path / f"{name}.m")
        write_model_file(model_path, spec, random_tensors(spec, seed=0))
        data = TokenizerData(
            vocab=base.vocab, scores=base.scores, bos_id=1, eos_id=2,
            chat_eos_id=2,
            chat_template="{{bos_token}}{% for m in messages %}<|im_start|>...{% endfor %}",
        )
        tok_path = str(tmp_path / f"{name}.t")
        with open(tok_path, "wb") as f:
            write_tokenizer_file(f, data)
        engine = InferenceEngine(model_path, dtype=jnp.float32)
        tokenizer = Tokenizer.from_file(tok_path)
        sampler = Sampler(vocab_size=spec.vocab_size, temperature=0.0,
                          topp=0.9, seed=1)
        args = types.SimpleNamespace(
            temperature=0.0, topp=0.9, seed=1, chat_template=None,
            parallel=2, batch_decode=batch, decode="device", decode_chunk=4,
        )
        return ApiState(engine, tokenizer, sampler, args)

    def test_batched_completion_matches_classic(self, tmp_path):
        body = {"messages": [{"role": "user", "content": "hello"}],
                "max_tokens": 6, "temperature": 0.0}
        classic = self._state(tmp_path, "classic", batch=False)
        want = classic.complete(dict(body), lambda s: None)
        batched = self._state(tmp_path, "batched", batch=True)
        assert batched.batch is not None  # the scheduler actually engaged
        got = batched.complete(dict(body), lambda s: None)
        assert got["choices"][0]["message"]["content"] == \
            want["choices"][0]["message"]["content"]
        assert got["usage"] == want["usage"]

    def test_concurrent_completions_match_sequential(self, tmp_path):
        """--parallel concurrent completions through the scheduler must
        produce exactly what sequential single-request runs produce (greedy:
        batching may never change a stream's tokens)."""
        state = self._state(tmp_path, "conc", batch=True)
        bodies = [
            {"messages": [{"role": "user", "content": f"hello {i}"}],
             "max_tokens": 5, "temperature": 0.0}
            for i in range(2)
        ]
        sequential = []
        for b in bodies:
            sequential.append(state.complete(dict(b), lambda s: None))
            for slot in state.slots:
                slot.stream.reset()
                slot.cache.clear()

        results = [None] * 2
        errors = []

        def run(i):
            try:
                results[i] = state.complete(dict(bodies[i]), lambda s: None)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        got = sorted(r["choices"][0]["message"]["content"] for r in results)
        want = sorted(r["choices"][0]["message"]["content"] for r in sequential)
        assert got == want

    def test_streaming_sse_through_scheduler(self, tmp_path):
        state = self._state(tmp_path, "sse", batch=True)
        chunks = []
        out = state.complete(
            {"messages": [{"role": "user", "content": "hi"}],
             "max_tokens": 4, "stream": True},
            chunks.append,
        )
        assert out is None
        assert chunks[-1] == "[DONE]"
        import json

        final = json.loads(chunks[-2])
        assert final["choices"][0]["finish_reason"] in ("stop", "length")
