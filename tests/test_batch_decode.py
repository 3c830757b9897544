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
