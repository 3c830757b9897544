"""Self-speculative decoding (ISSUE 6): prompt-lookup drafting, the
on-device accept/reject (greedy longest-prefix + Leviathan rejection
sampling), greedy bit-parity of speculative vs plain decode (single-stream,
batched, i8 cache), mixed spec/non-spec rows in one slab, the
``engine.spec_verify`` chaos contract, and the coalesced (fused) K/V cache
layout the verify path writes through."""

import threading

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu.engine import InferenceEngine, faults
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.engine.speculative import PromptLookupDrafter

from tests.model_utils import random_tensors, tiny_spec, write_model_file

PROMPTS = [[1, 5, 9], [2, 4, 6, 8], [3, 7]]
N_TOKENS = 10
K = 3  # draft length under test (T = 4 verify windows)


def build_engine(tmp_path, name="model.m", seed=0, seq_len=96, cache_dtype=None):
    spec = tiny_spec(seq_len=seq_len)
    path = str(tmp_path / name)
    write_model_file(path, spec, random_tensors(spec, seed=seed))
    return InferenceEngine(path, dtype=jnp.float32, cache_dtype=cache_dtype)


def plain_stream(engine, prompt, temp, topp, seed, n):
    """The non-speculative reference: prefill_device → chunked stream."""
    s = engine.new_stream()
    first = s.prefill_device(prompt, temp, topp, seed)
    got = []

    def on_token(prev, tok):
        got.append(tok)
        return len(got) < n

    s.stream_decode(first, on_token, temp, topp, seed=seed, chunk=4,
                    limit=s.pos + n, first_prev=prompt[-1])
    return got


def spec_stream(stream, prompt, temp, topp, seed, n, spec_draft=K):
    """The same request through the speculative path."""
    first = stream.prefill_device(prompt, temp, topp, seed)
    got = []

    def on_token(prev, tok):
        got.append(tok)
        return len(got) < n

    stream.stream_decode(first, on_token, temp, topp, seed=seed,
                         limit=stream.pos + n, first_prev=prompt[-1],
                         spec_draft=spec_draft, prompt_tokens=prompt)
    return got


class TestPromptLookupDrafter:
    def test_matches_most_recent_ngram(self):
        d = PromptLookupDrafter(3, max_ngram=2)
        # tail (7, 8) occurred earlier, followed by 9, 1, 2
        assert d.draft([7, 8, 9, 1, 2, 7, 8]) == [9, 1, 2]

    def test_most_recent_occurrence_wins(self):
        d = PromptLookupDrafter(1, max_ngram=1)
        assert d.draft([5, 1, 5, 2, 5]) == [2]  # the later 5→2, not 5→1

    def test_falls_through_to_shorter_ngram(self):
        d = PromptLookupDrafter(2, max_ngram=3)
        # no 3- or 2-gram of the tail recurs, but 4 does (followed by 6)
        assert d.draft([4, 6, 1, 2, 3, 4]) == [6, 1]

    def test_periodic_overlap_predicts_cycle(self):
        d = PromptLookupDrafter(4, max_ngram=2)
        assert d.draft([1, 2, 1, 2, 1, 2]) == [1, 2, 1, 2]

    def test_no_match_returns_empty(self):
        d = PromptLookupDrafter(4)
        assert d.draft([1, 2, 3, 4, 5]) == []
        assert d.draft([1]) == []
        assert d.draft([]) == []

    def test_limit_caps_draft(self):
        d = PromptLookupDrafter(4, max_ngram=1)
        assert d.draft([9, 1, 2, 3, 4, 9], limit=2) == [1, 2]
        assert d.draft([9, 1, 2, 3, 4, 9], limit=0) == []


class TestSpecAccept:
    """The on-device accept/reject, unit-level (models.sampling)."""

    def _accept(self, logits, draft, draft_len, seed, temp, topp, topk=0, pos=0):
        from distributed_llama_tpu.models.sampling import _spec_accept_row

        n, toks = _spec_accept_row(
            jnp.asarray(logits, jnp.float32), jnp.asarray(draft, jnp.int32),
            jnp.int32(draft_len), jnp.uint32(seed), jnp.int32(pos),
            jnp.float32(temp), jnp.float32(topp), jnp.int32(topk),
        )
        return int(n), np.asarray(toks), None

    def _greedy_logits(self, targets, vocab=16):
        out = np.full((len(targets), vocab), -5.0, np.float32)
        for i, t in enumerate(targets):
            out[i, t] = 5.0
        return out

    def test_greedy_full_accept_emits_bonus(self):
        logits = self._greedy_logits([3, 6, 9, 12])
        n, toks, _ = self._accept(logits, [3, 6, 9], 3, 0, 0.0, 0.9)
        assert n == 4
        assert toks[:4].tolist() == [3, 6, 9, 12]  # drafts + bonus

    def test_greedy_rejection_emits_correction(self):
        logits = self._greedy_logits([3, 7, 9, 12])
        n, toks, _ = self._accept(logits, [3, 6, 9], 3, 0, 0.0, 0.9)
        assert n == 2  # d1 accepted, d2 rejected → correction 7
        assert toks[:2].tolist() == [3, 7]

    def test_greedy_immediate_rejection(self):
        logits = self._greedy_logits([5, 7, 9, 12])
        n, toks, _ = self._accept(logits, [3, 6, 9], 3, 0, 0.0, 0.9)
        assert n == 1 and toks[0] == 5

    def test_zero_draft_is_plain_step(self):
        logits = self._greedy_logits([5, 0, 0, 0])
        n, toks, _ = self._accept(logits, [3, 6, 9], 0, 0, 0.0, 0.9)
        assert n == 1 and toks[0] == 5

    def test_sampled_first_token_distribution_preserved(self):
        """Leviathan rejection sampling with the prompt-lookup point-mass
        draft: the emitted first token's distribution over many keys must
        match the target softmax regardless of the draft token."""
        rng = np.random.RandomState(0)
        vocab = 8
        logits = rng.randn(3, vocab).astype(np.float32)
        target = np.asarray(jax.nn.softmax(jnp.asarray(logits[0])))
        from distributed_llama_tpu.models.sampling import _spec_accept_row

        accept = jax.jit(
            lambda seed: _spec_accept_row(
                jnp.asarray(logits), jnp.asarray([2, 5], jnp.int32),
                jnp.int32(2), seed, jnp.int32(0), jnp.float32(1.0),
                jnp.float32(1.0), jnp.int32(0),
            )
        )
        counts = np.zeros(vocab)
        n_draws = 1500
        for i in range(n_draws):
            _, toks = accept(jnp.uint32(i))
            counts[int(toks[0])] += 1
        np.testing.assert_allclose(counts / n_draws, target, atol=0.05)

    def test_sampled_acceptance_probability(self):
        """A draft token of target probability p must be accepted with
        frequency ~p (the q = point-mass acceptance rule)."""
        vocab = 4
        logits = np.zeros((2, vocab), np.float32)
        logits[0] = [2.0, 0.0, 0.0, 0.0]
        p_draft = float(jax.nn.softmax(jnp.asarray(logits[0]))[0])
        from distributed_llama_tpu.models.sampling import _spec_accept_row

        accept = jax.jit(
            lambda seed: _spec_accept_row(
                jnp.asarray(logits), jnp.asarray([0], jnp.int32), jnp.int32(1),
                seed, jnp.int32(0), jnp.float32(1.0), jnp.float32(1.0),
                jnp.int32(0),
            )
        )
        accepted = sum(
            int(accept(jnp.uint32(i))[0]) == 2 for i in range(1200)
        )
        np.testing.assert_allclose(accepted / 1200, p_draft, atol=0.05)


class TestSingleStreamParity:
    def test_greedy_bit_parity(self, tmp_path):
        ref_engine = build_engine(tmp_path, "ref.m")
        want = plain_stream(ref_engine, [1, 5, 9], 0.0, 0.9, 7, N_TOKENS)

        engine = build_engine(tmp_path, "spec.m")
        got = spec_stream(engine.new_stream(), [1, 5, 9], 0.0, 0.9, 7, N_TOKENS)
        assert got == want

    def test_greedy_bit_parity_i8_cache(self, tmp_path):
        ref_engine = build_engine(tmp_path, "ref8.m", cache_dtype="i8")
        want = plain_stream(ref_engine, [2, 4, 6], 0.0, 0.9, 5, N_TOKENS)

        engine = build_engine(tmp_path, "spec8.m", cache_dtype="i8")
        got = spec_stream(engine.new_stream(), [2, 4, 6], 0.0, 0.9, 5, N_TOKENS)
        assert got == want

    def test_greedy_bit_parity_blocked_attention(self, tmp_path):
        """seq_len a multiple of ATT_CHUNK exercises the BLOCKED verify
        attention, whose larger dynamic chunk bound must merge fully-masked
        chunks as exact identities (ops.attention.merge_partials)."""
        from distributed_llama_tpu.models.llama import ATT_CHUNK

        ref_engine = build_engine(tmp_path, "refb.m", seq_len=2 * ATT_CHUNK)
        want = plain_stream(ref_engine, [1, 5, 9], 0.0, 0.9, 3, N_TOKENS)

        engine = build_engine(tmp_path, "specb.m", seq_len=2 * ATT_CHUNK)
        got = spec_stream(engine.new_stream(), [1, 5, 9], 0.0, 0.9, 3, N_TOKENS)
        assert got == want

    def test_sampled_stream_runs_and_rolls_back(self, tmp_path):
        engine = build_engine(tmp_path, "samp.m")
        s = engine.new_stream()
        got = spec_stream(s, [1, 5, 9], 0.8, 0.9, 11, N_TOKENS)
        assert len(got) == N_TOKENS
        assert all(0 <= t < engine.cfg.vocab_size for t in got)
        # rollback contract: position == prompt + consumed tokens' feeds
        assert s.pos == 3 + N_TOKENS - 1  # the last token is not yet fed

    def test_context_tail_shrinks_window(self, tmp_path):
        """Near seq_len the verify window shrinks instead of writing past
        the cache; the stream still reaches the context limit."""
        engine = build_engine(tmp_path, "tail.m", seq_len=24)
        ref_engine = build_engine(tmp_path, "tailref.m", seq_len=24)
        want = plain_stream(ref_engine, [1, 5, 9], 0.0, 0.9, 3, 24)
        got = spec_stream(engine.new_stream(), [1, 5, 9], 0.0, 0.9, 3, 24)
        assert got == want


class TestBatchedParity:
    def test_rows_match_plain_batched(self, tmp_path):
        """Batched speculative rows (variable per-row advance) must be
        bit-identical to the plain streams — greedy, mixed prompts."""
        ref_engine = build_engine(tmp_path, "ref.m", seed=3)
        refs = [
            plain_stream(ref_engine, p, 0.0, 0.9, 11 + i, N_TOKENS)
            for i, p in enumerate(PROMPTS)
        ]

        engine = build_engine(tmp_path, "bat.m", seed=3)
        sched = BatchScheduler(engine, n_rows=3, chunk=4, spec_draft=K)
        assert sched.spec_draft == K
        streams = [sched.new_stream() for _ in range(3)]
        outs = [None] * 3
        errors = []

        def run(i):
            try:
                outs[i] = spec_stream(
                    streams[i], PROMPTS[i], 0.0, 0.9, 11 + i, N_TOKENS
                )
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert outs == refs

    def test_rows_match_plain_batched_i8(self, tmp_path):
        ref_engine = build_engine(tmp_path, "ref8.m", seed=5, cache_dtype="i8")
        refs = [
            plain_stream(ref_engine, p, 0.0, 0.9, 7, N_TOKENS)
            for p in PROMPTS[:2]
        ]

        engine = build_engine(tmp_path, "bat8.m", seed=5, cache_dtype="i8")
        sched = BatchScheduler(engine, n_rows=2, chunk=4, spec_draft=K)
        streams = [sched.new_stream() for _ in range(2)]
        outs = [None] * 2
        errors = []

        def run(i):
            try:
                outs[i] = spec_stream(streams[i], PROMPTS[i], 0.0, 0.9, 7, N_TOKENS)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert outs == refs

    def test_mixed_spec_and_plain_rows_one_slab(self, tmp_path):
        """A spec row and an opted-out row (zero drafts) share the verify
        dispatches; both must match their plain references bit-exactly."""
        ref_engine = build_engine(tmp_path, "ref.m", seed=9)
        want_spec = plain_stream(ref_engine, PROMPTS[0], 0.0, 0.9, 21, N_TOKENS)
        want_plain = plain_stream(ref_engine, PROMPTS[1], 0.0, 0.9, 23, N_TOKENS)

        engine = build_engine(tmp_path, "mix.m", seed=9)
        sched = BatchScheduler(engine, n_rows=2, chunk=4, spec_draft=K)
        s_spec, s_plain = sched.new_stream(), sched.new_stream()
        outs = [None, None]
        errors = []

        def run(i):
            try:
                if i == 0:
                    outs[0] = spec_stream(
                        s_spec, PROMPTS[0], 0.0, 0.9, 21, N_TOKENS, spec_draft=K
                    )
                else:
                    # spec_draft=0 on the call: the row rides the shared
                    # verify dispatches with an empty draft every step
                    outs[1] = spec_stream(
                        s_plain, PROMPTS[1], 0.0, 0.9, 23, N_TOKENS, spec_draft=0
                    )
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert outs[0] == want_spec
        assert outs[1] == want_plain

    def test_row_reuse_after_spec_completion(self, tmp_path):
        ref_engine = build_engine(tmp_path, "ref.m")
        want = plain_stream(ref_engine, [1, 5, 9], 0.0, 0.9, 7, 6)

        engine = build_engine(tmp_path, "bat.m")
        sched = BatchScheduler(engine, n_rows=2, chunk=4, spec_draft=K)
        s = sched.new_stream()
        first = spec_stream(s, [1, 5, 9], 0.0, 0.9, 7, 6)
        s.reset()
        second = spec_stream(s, [1, 5, 9], 0.0, 0.9, 7, 6)
        assert first == want and second == want

    def test_spec_disabled_on_moe(self, tmp_path):
        from tests.test_moe import mixtral_spec

        spec = mixtral_spec(seq_len=96)
        path = str(tmp_path / "moe.m")
        write_model_file(path, spec, random_tensors(spec, seed=1))
        engine = InferenceEngine(path, dtype=jnp.float32)
        sched = BatchScheduler(engine, n_rows=2, chunk=4, spec_draft=K)
        assert sched.spec_draft == 0  # soft-disabled, batched decode intact

    def test_single_stream_moe_falls_back_to_plain(self, tmp_path):
        """A T>1 verify window would route MoE through the prefill expert
        path (no decode parity contract): the single-stream spec route must
        fall back to the chunked path, matching plain decode exactly."""
        from tests.test_moe import mixtral_spec

        spec = mixtral_spec(seq_len=96)
        path = str(tmp_path / "moe1.m")
        write_model_file(path, spec, random_tensors(spec, seed=1))
        ref_engine = InferenceEngine(path, dtype=jnp.float32)
        want = plain_stream(ref_engine, [1, 5, 9], 0.0, 0.9, 5, 8)
        engine = InferenceEngine(path, dtype=jnp.float32)
        got = spec_stream(engine.new_stream(), [1, 5, 9], 0.0, 0.9, 5, 8)
        assert got == want


class TestSpecVerifyChaos:
    def test_raise_quarantines_only_victim_row(self, tmp_path):
        """The FLT-001 contract of the new ``engine.spec_verify`` site: a
        row-targeted raise during verify retires ONLY that row (typed
        RowQuarantined), and the surviving row's stream is bit-identical
        to a fault-free run."""
        ref_engine = build_engine(tmp_path, "ref.m", seed=3)
        want_survivor = plain_stream(ref_engine, PROMPTS[0], 0.0, 0.9, 11, N_TOKENS)

        plan = faults.install(
            faults.parse("engine.spec_verify:kind=raise,row=1,after=2,count=1")
        )
        try:
            engine = build_engine(tmp_path, "chaos.m", seed=3)
            sched = BatchScheduler(engine, n_rows=2, chunk=4, spec_draft=K)
            s0, s1 = sched.new_stream(), sched.new_stream()
            out0 = [None]
            victim_error = []
            errors = []

            def run_survivor():
                try:
                    out0[0] = spec_stream(s0, PROMPTS[0], 0.0, 0.9, 11, N_TOKENS)
                except Exception as e:  # pragma: no cover
                    errors.append(e)

            def run_victim():
                try:
                    spec_stream(s1, PROMPTS[1], 0.0, 0.9, 13, N_TOKENS)
                except faults.RowQuarantined as e:
                    victim_error.append(e)
                except Exception as e:  # pragma: no cover
                    errors.append(e)

            t0 = threading.Thread(target=run_survivor)
            t1 = threading.Thread(target=run_victim)
            t0.start(), t1.start()
            t0.join(timeout=180), t1.join(timeout=180)
            assert not errors, errors
            assert plan.injected_total == 1
            assert victim_error, "the victim row was not quarantined"
            assert out0[0] == want_survivor
        finally:
            faults.clear()


class TestFusedStepChaos:
    def test_mid_superstep_raise_quarantines_only_victim_row(self, tmp_path):
        """The FLT-001 contract of the ``engine.fused_step`` site (ISSUE
        17): a row-targeted raise as the fused per-layer superstep programs
        launch retires ONLY that row (typed RowQuarantined), and the
        surviving co-batched row's stream is bit-identical to a fault-free
        run — one row's fused program failing must never corrupt the
        shared dispatch."""
        ref_engine = build_engine(tmp_path, "ref.m", seed=3)
        want_survivor = plain_stream(ref_engine, PROMPTS[0], 0.0, 0.9, 11, N_TOKENS)

        plan = faults.install(
            faults.parse("engine.fused_step:kind=raise,row=1,after=2,count=1")
        )
        try:
            engine = build_engine(tmp_path, "chaos.m", seed=3)
            sched = BatchScheduler(engine, n_rows=2, chunk=4, spec_draft=K)
            s0, s1 = sched.new_stream(), sched.new_stream()
            out0 = [None]
            victim_error = []
            errors = []

            def run_survivor():
                try:
                    out0[0] = spec_stream(s0, PROMPTS[0], 0.0, 0.9, 11, N_TOKENS)
                except Exception as e:  # pragma: no cover
                    errors.append(e)

            def run_victim():
                try:
                    spec_stream(s1, PROMPTS[1], 0.0, 0.9, 13, N_TOKENS)
                except faults.RowQuarantined as e:
                    victim_error.append(e)
                except Exception as e:  # pragma: no cover
                    errors.append(e)

            t0 = threading.Thread(target=run_survivor)
            t1 = threading.Thread(target=run_victim)
            t0.start(), t1.start()
            t0.join(timeout=180), t1.join(timeout=180)
            assert not errors, errors
            assert plan.injected_total == 1
            assert victim_error, "the victim row was not quarantined"
            assert out0[0] == want_survivor
        finally:
            faults.clear()


class TestFusedCacheLayout:
    """The coalesced K/V layout: one stacked update per layer must be
    byte-equivalent to the historical (keys, values)-pair updates."""

    def test_forward_matches_tuple_cache(self, tmp_path):
        from distributed_llama_tpu.models import llama
        from distributed_llama_tpu.ops import kv_cache as kvc

        engine = build_engine(tmp_path, "fused.m")
        cfg, params = engine.cfg, engine.params
        fused = llama.init_cache(cfg, dtype=jnp.float32, layered=True)
        tuples = [
            (kvc.init_half((cfg.seq_len, cfg.n_kv_heads, cfg.head_size), jnp.float32),
             kvc.init_half((cfg.seq_len, cfg.n_kv_heads, cfg.head_size), jnp.float32))
            for _ in range(cfg.n_layers)
        ]
        tokens = jnp.asarray([1, 5, 9, 2], jnp.int32)
        lf, fused = llama.forward_tokens(cfg, params, tokens, fused, jnp.int32(0))
        lt, tuples = llama.forward_tokens(cfg, params, tokens, tuples, jnp.int32(0))
        np.testing.assert_array_equal(np.asarray(lf), np.asarray(lt))
        for l, (leaf, (tk, tv)) in enumerate(zip(fused, tuples)):
            np.testing.assert_array_equal(
                np.asarray(leaf[0]), np.asarray(tk), err_msg=f"layer {l} keys"
            )
            np.testing.assert_array_equal(
                np.asarray(leaf[1]), np.asarray(tv), err_msg=f"layer {l} values"
            )

    def test_fused_take_put_row_roundtrip(self):
        from distributed_llama_tpu.ops import kv_cache as kvc

        rng = np.random.RandomState(0)
        leaf = jnp.asarray(rng.randn(2, 3, 8, 2, 4).astype(np.float32))
        row = kvc.fused_take_row(leaf, jnp.int32(1))
        np.testing.assert_array_equal(np.asarray(row), np.asarray(leaf)[:, 1])
        bumped = row + 1.0
        out = kvc.fused_put_row(leaf, bumped, jnp.int32(1))
        np.testing.assert_array_equal(np.asarray(out)[:, 1], np.asarray(bumped))
        np.testing.assert_array_equal(np.asarray(out)[:, 0], np.asarray(leaf)[:, 0])

    def test_fused_verify_write_drops_out_of_bounds(self):
        from distributed_llama_tpu.ops import kv_cache as kvc

        leaf = jnp.zeros((2, 2, 8, 1, 4))
        k = jnp.ones((2, 3, 1, 4))
        v = jnp.full((2, 3, 1, 4), 2.0)
        slots = jnp.asarray([[5, 6, 7], [7, 8, 9]], jnp.int32)  # 8, 9 drop
        out = np.asarray(kvc.fused_update_verify_batched(leaf, k, v, slots))
        assert (out[0, 0, 5:8] == 1.0).all() and (out[1, 0, 5:8] == 2.0).all()
        assert (out[:, 1, 7] != 0).all() and (out[:, 1, :7] == 0).all()

    def test_retired_row_cache_untouched_in_spec_mode(self, tmp_path):
        """Inactive rows riding a verify dispatch must not see one byte of
        their slab row change (same contract as the plain batched chunk)."""
        engine = build_engine(tmp_path)
        sched = BatchScheduler(engine, n_rows=2, chunk=4, spec_draft=K)
        s0, s1 = sched.new_stream(), sched.new_stream()
        spec_stream(s0, PROMPTS[0], 0.0, 0.9, 11, 5)
        before = [np.asarray(leaf)[:, 0].copy() for leaf in sched._slab]
        spec_stream(s1, PROMPTS[1], 0.0, 0.9, 13, 8)
        after = [np.asarray(leaf)[:, 0] for leaf in sched._slab]
        for l, (b, a) in enumerate(zip(before, after)):
            np.testing.assert_array_equal(b, a, err_msg=f"layer {l}")
