"""On-device sampling + fused decode loop tests (counter-PRNG sampler)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu import prng
from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.models.sampling import (
    TOPP_FAST_K,
    fused_pick,
    fused_sample_batched,
    sample_token,
)

from tests.model_utils import random_tensors, tiny_spec, write_model_file


def build_engine(tmp_path, spec, seed=0):
    tensors = random_tensors(spec, seed=seed)
    path = str(tmp_path / "model.m")
    write_model_file(path, spec, tensors)
    return InferenceEngine(path, dtype=jnp.float32)


class TestSampleToken:
    def test_greedy(self):
        logits = jnp.asarray([0.1, 3.0, -1.0, 2.9])
        tok = sample_token(logits, 0, 0, 0.0, 0.9)
        assert int(tok) == 1

    def test_topp_restricts_to_nucleus(self):
        logits = jnp.full((50,), -10.0).at[7].set(10.0)
        for s in range(10):
            tok = sample_token(logits, s, 0, 1.0, 0.5)
            assert int(tok) == 7

    def test_topk_restricts_to_topk(self):
        logits = jnp.asarray([5.0, 4.0, -10.0, -10.0, -10.0])
        seen = {
            int(sample_token(logits, s, 0, 2.0, 0.0, topk=2))
            for s in range(60)
        }
        assert seen <= {0, 1}

    def test_temperature_sampling_covers_support(self):
        logits = jnp.zeros(4)
        seen = {int(sample_token(logits, s, 0, 1.0, 0.0)) for s in range(50)}
        assert seen == {0, 1, 2, 3}

    def test_coin_varies_with_position_not_state(self):
        """The counter PRNG keys the coin on (seed, pos): same inputs →
        same token, different positions → an eventually-different draw, no
        generator state anywhere."""
        logits = jnp.zeros(8)
        a = [int(sample_token(logits, 3, p, 1.0, 0.0)) for p in range(20)]
        b = [int(sample_token(logits, 3, p, 1.0, 0.0)) for p in range(20)]
        assert a == b  # stateless: replay is trivially identical
        assert len(set(a)) > 1  # positions decorrelate the draws


def _dyadic_probs():
    """Dyadic probabilities (exact in f32, cumsums included): entries
    0..TOPP_FAST_K-1 hold 1/256 each (cumulative exactly 0.5), the 256
    tail entries 1/512 each — no rounding anywhere, so the nucleus
    boundary is bit-exact, not a float knife-edge, and the sorted order
    is the identity (ties never cross the boundary)."""
    probs = np.full(TOPP_FAST_K + 256, 1.0 / 512.0, np.float32)
    probs[:TOPP_FAST_K] = np.float32(0.5) / TOPP_FAST_K  # 1/256
    return probs


# the largest f32 coin the counter PRNG can produce ((2**24 - 1) / 2**24):
# drives the pick to the LAST kept candidate — the boundary witness
_COIN_MAX = np.float32((2**24 - 1) / 2**24)


def _pick(probs, coin, topp, topk):
    """One fused_pick call on explicit probabilities (order = identity:
    ``scaled`` is fed the probs themselves, which sorts identically)."""
    p = jnp.asarray(probs)[None, :]
    tok = fused_pick(
        p, p, jnp.asarray([coin], jnp.float32),
        jnp.asarray([topp], jnp.float32), jnp.asarray([topk], jnp.int32),
    )
    return int(tok[0])


class TestFusedPickBoundary:
    """The dyadic-exact nucleus/top-k boundary contract of the FUSED
    device sampler (the PR 6 threshold tests, extended to the fused path
    per ISSUE 13): when the kept prefix ends exactly at the
    ``TOPP_FAST_K`` fast-path window, the fast path must serve it
    bit-exactly, and one step past the window must route to the full
    sort and keep serving exactly."""

    def test_nucleus_ends_exactly_at_fast_k(self):
        probs = _dyadic_probs()
        # topp = 0.5 = the cumulative mass of exactly the top TOPP_FAST_K
        # entries: the largest nucleus the fast path may legally serve —
        # every coin must land inside the top TOPP_FAST_K candidates, and
        # the max coin must land on the BOUNDARY element itself
        for coin in (0.0, 0.25, 0.75, float(_COIN_MAX)):
            tok = _pick(probs, coin, 0.5, 0)
            assert tok < TOPP_FAST_K, (coin, tok)
        assert _pick(probs, float(_COIN_MAX), 0.5, 0) == TOPP_FAST_K - 1

    def test_nucleus_one_past_fast_k_takes_full_sort(self):
        probs = _dyadic_probs()
        # one half-tail-element of extra mass: cum[TOPP_FAST_K-1] = 0.5 <
        # topp, so the lax.cond must route to the full sort — whose kept
        # prefix is exactly TOPP_FAST_K + 1 wide, and the max coin must
        # land on the first tail element (the one the window cannot see)
        topp = float(np.float32(0.5 + 1.0 / 1024.0))
        assert _pick(probs, float(_COIN_MAX), topp, 0) == TOPP_FAST_K

    def test_topk_exactly_at_fast_k(self):
        probs = _dyadic_probs()
        # bare top-k at the window width: fast path, last kept = K-1
        assert _pick(probs, float(_COIN_MAX), 0.0, TOPP_FAST_K) == TOPP_FAST_K - 1

    def test_topk_one_past_fast_k_takes_full_sort(self):
        probs = _dyadic_probs()
        assert (
            _pick(probs, float(_COIN_MAX), 0.0, TOPP_FAST_K + 1) == TOPP_FAST_K
        )

    def test_topk_composes_with_nucleus_at_boundary(self):
        probs = _dyadic_probs()
        # nucleus says TOPP_FAST_K, top-k says less: min wins exactly
        assert _pick(probs, float(_COIN_MAX), 0.5, 7) == 6

    def test_matches_numpy_reference(self):
        """The device keep-count rule against an independent numpy
        reference (the PR 6 full-sort oracle, restated for the fused
        keep-prefix form) on the dyadic distribution."""
        probs = _dyadic_probs()

        def ref_keep(p, topp, topk):
            s = np.sort(p)[::-1]
            cum = np.cumsum(s)
            n_nuc = int(np.sum(cum - s < topp)) if 0 < topp < 1 else p.size
            n_k = topk if topk > 0 else p.size
            return max(1, min(n_nuc, n_k))

        for topp, topk in [(0.5, 0), (0.25, 0), (0.5, 64), (0.75, 0), (0.0, 130)]:
            n_keep = ref_keep(probs, np.float32(topp), topk)
            # the max coin lands on the last kept candidate = rank n_keep-1
            assert _pick(probs, float(_COIN_MAX), topp, topk) == n_keep - 1


class TestGreedyRowsInSampledBatch:
    """ISSUE 13 satellite: a temperature=0 row co-batched with sampled
    rows must take the exact argmax path — bit-identical to a pure-greedy
    batch — including at the TOPP_FAST_K boundary (dyadic probs, nucleus
    ending exactly at k), where the greedy row must not be routed through
    the sampled pick by the shared program."""

    def test_greedy_row_bit_identical_across_batch_mixes(self):
        rng = np.random.RandomState(0)
        V = TOPP_FAST_K + 256
        logits = rng.randn(4, V).astype(np.float32) * 2.0
        seeds = jnp.asarray([5, 6, 7, 8], jnp.uint32)
        pos = jnp.asarray([3, 9, 2, 7], jnp.int32)
        pure = fused_sample_batched(
            jnp.asarray(logits), seeds, pos, jnp.zeros(4, jnp.float32),
            jnp.full(4, 0.9, jnp.float32), jnp.zeros(4, jnp.int32),
        )
        mixed_t = jnp.asarray([0.0, 0.9, 0.0, 1.3], jnp.float32)
        mixed_k = jnp.asarray([0, 5, 0, 0], jnp.int32)
        mixed = fused_sample_batched(
            jnp.asarray(logits), seeds, pos, mixed_t,
            jnp.full(4, 0.9, jnp.float32), mixed_k,
        )
        want = np.argmax(logits, axis=-1)
        assert np.asarray(pure).tolist() == want.tolist()
        got = np.asarray(mixed)
        assert got[0] == want[0] and got[2] == want[2]

    def test_greedy_row_at_dyadic_boundary(self):
        # row 0 greedy over the dyadic distribution (argmax = index 0, the
        # first max element), row 1 sampled with the nucleus ending exactly
        # at TOPP_FAST_K: the sampled row's full/fast routing must not
        # perturb the greedy row's argmax
        probs = _dyadic_probs()
        logits = np.log(np.stack([probs, probs]))
        out = fused_sample_batched(
            jnp.asarray(logits), jnp.asarray([1, 2], jnp.uint32),
            jnp.asarray([0, 0], jnp.int32),
            jnp.asarray([0.0, 1.0], jnp.float32),
            jnp.asarray([0.9, 0.5], jnp.float32),
            jnp.zeros(2, jnp.int32),
        )
        assert int(out[0]) == 0  # argmax: first of the tied max entries
        assert int(out[1]) < TOPP_FAST_K  # sampled row stays in-nucleus


def _primitives_outside_cond(jaxpr) -> list:
    """Names of a jaxpr's primitives, those of its nested calls (``pjit``,
    ``custom_jvp_call``) included, but nothing inside a ``cond``'s branches."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "cond":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _primitives_outside_cond(sub)
    return names


def _sampling_arm(logits, seeds, pos, temperature, topp, topk):
    """What the sampler computed for EVERY row before the arm had a
    condition in front of it (ISSUE 46): the reference the rows that sample
    are held to, bit for bit."""
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    probs = jax.nn.softmax(scaled, axis=-1)
    coin = prng.device_coin(seeds, pos, prng.DRAW_SAMPLE)
    return fused_pick(probs, scaled, coin, topp, topk).astype(jnp.int32)


class TestSamplerBehindItsCondition:
    """ISSUE 46: the softmax, the coin and the pick run in the true arm of
    one ``lax.cond`` on "some row samples"; a step whose rows all take the
    argmax runs none of them, and no row's token changes in any mix."""

    @pytest.mark.parametrize("rows", [1, 8, 32])
    @pytest.mark.parametrize("vocab", [320, 32768, 100352])
    def test_one_cond_holds_everything_but_the_argmax(self, vocab, rows):
        s = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(fused_sample_batched)(
            s((rows, vocab), jnp.bfloat16), s((rows,), jnp.uint32), s((rows,), jnp.int32),
            s((rows,), jnp.float32), s((rows,), jnp.float32), s((rows,), jnp.int32),
        ).jaxpr
        assert [e.primitive.name for e in jaxpr.eqns].count("cond") == 1
        outside = _primitives_outside_cond(jaxpr)
        assert "argmax" in outside
        assert not {"top_k", "sort", "cumsum", "exp", "div", "reduce_sum"} & set(outside), outside
        # and the arm is the whole sampler, not an empty shell
        (cond,) = (e for e in jaxpr.eqns if e.primitive.name == "cond")
        inside = {
            name for branch in cond.params["branches"]
            for name in _primitives_outside_cond(branch.jaxpr)
        }
        assert {"top_k", "exp"} <= inside

    @pytest.mark.parametrize(
        "topp,topk", [(0.9, 0), (0.0, 40), (0.9, 40), (0.0, 0)],
        ids=["top-p", "top-k", "both", "filters-off"],
    )
    @pytest.mark.parametrize(
        "temps",
        [(0.0, 0.0, 0.0, 0.0), (0.8, 1.3, 0.5, 1.0), (0.0, 0.9, 0.0, 1.3)],
        ids=["all-greedy", "all-sampled", "mixed"],
    )
    def test_every_rows_token_is_what_it_was(self, temps, topp, topk):
        rng = np.random.RandomState(3)
        V = 4352  # past TOPP_PARTITION_MIN_V: every route of the pick is in the program
        logits = jnp.asarray(rng.randn(4, V).astype(np.float32) * 3.0)
        seeds = jnp.asarray([5, 6, 7, 2**31 + 8], jnp.uint32)
        temps = jnp.asarray(temps, jnp.float32)
        topps, topks = jnp.full(4, topp, jnp.float32), jnp.full(4, topk, jnp.int32)
        sample = jax.jit(fused_sample_batched)
        for step in range(6):  # other positions, other coins
            pos = jnp.asarray([3, 9, 2, 7], jnp.int32) + step
            got = np.asarray(sample(logits, seeds, pos, temps, topps, topks))
            arm = np.asarray(_sampling_arm(logits, seeds, pos, temps, topps, topks))
            want = np.where(np.asarray(temps) == 0.0, np.argmax(np.asarray(logits), axis=-1), arm)
            assert got.dtype == np.int32 and got.tolist() == want.tolist()

    def test_a_traced_temperature_of_zero_takes_the_argmax(self):
        rng = np.random.RandomState(4)
        logits = jnp.asarray(rng.randn(700).astype(np.float32))
        one = jax.jit(lambda t, p, k: sample_token(logits, jnp.uint32(9), jnp.int32(5), t, p, k))
        assert int(one(0.0, 0.9, 0)) == int(np.argmax(np.asarray(logits)))
        # the same program samples when asked to
        seen = {int(jax.jit(lambda t, sd: sample_token(logits, sd, jnp.int32(5), t, 0.0, 0))(
            jnp.float32(1.5), jnp.uint32(sd))) for sd in range(12)}
        assert len(seen) > 1


class TestDecodeLoop:
    def test_greedy_loop_matches_stepwise(self, tmp_path):
        spec = tiny_spec()
        engine = build_engine(tmp_path, spec)
        prompt = [1, 5, 9]
        logits = engine.prefill(prompt)
        first = int(np.argmax(logits))
        loop_tokens = engine.generate_on_device(first, 8, temperature=0.0)

        engine2 = build_engine(tmp_path, spec)
        logits = engine2.prefill(prompt)
        token = int(np.argmax(logits))
        step_tokens = []
        for _ in range(8):
            logits = engine2.decode_step(token)
            token = int(np.argmax(logits))
            step_tokens.append(token)
        # loop_tokens[i] = token sampled after consuming position i; the
        # stepwise list is offset by one consume
        assert loop_tokens.tolist() == [int(x) for x in ([first] + step_tokens)[1:9]]

    def test_positions_advance(self, tmp_path):
        spec = tiny_spec()
        engine = build_engine(tmp_path, spec)
        engine.prefill([1, 2, 3])
        engine.generate_on_device(5, 4)
        assert engine.pos == 7

    def test_context_overflow(self, tmp_path):
        spec = tiny_spec(seq_len=8)
        engine = build_engine(tmp_path, spec)
        engine.prefill([1, 2, 3, 4])
        try:
            engine.generate_on_device(5, 10)
            assert False, "expected ValueError"
        except ValueError:
            pass


class TestGenerateChunks:
    """The user-facing chunked fast path (wired into CLI generate/chat and
    the API server): stream correctness, chunk-size independence, and the
    early-stop rollback contract."""

    def _stream(self, engine, first, n, **kw):
        out = []
        for t in engine.generate_chunks(first, **kw):
            out.append(t)
            if len(out) >= n:
                break
        return out

    def test_greedy_matches_single_dispatch(self, tmp_path):
        spec = tiny_spec()
        e1 = build_engine(tmp_path, spec)
        first = int(np.argmax(e1.prefill([1, 5, 9])))
        want = e1.generate_on_device(first, 8, temperature=0.0).tolist()

        e2 = build_engine(tmp_path, spec)
        first2 = int(np.argmax(e2.prefill([1, 5, 9])))
        assert first2 == first
        got = self._stream(e2, first, 8, temperature=0.0, chunk=3)
        assert got == want

    def test_seeded_stream_is_chunk_size_independent(self, tmp_path):
        """Counter coins are keyed on (seed, position), so temperature>0
        streams are identical for any chunk size AND identical to the
        single-dispatch decode with the same seed — with zero sampler
        state threading between dispatches (the round-2 advisor's
        reproducibility complaint, now state-free per ISSUE 13)."""
        spec = tiny_spec()
        e1 = build_engine(tmp_path, spec)
        first = int(np.argmax(e1.prefill([2, 4])))
        want = e1.generate_on_device(first, 9, temperature=0.9, topp=0.8, seed=13).tolist()

        for chunk in (2, 4, 9):
            e = build_engine(tmp_path, spec)
            e.prefill([2, 4])
            got = self._stream(
                e, first, 9, temperature=0.9, topp=0.8, seed=13, chunk=chunk
            )
            assert got == want, f"chunk={chunk}"

    def test_topk_stream_is_chunk_size_independent(self, tmp_path):
        spec = tiny_spec()
        e1 = build_engine(tmp_path, spec)
        first = int(np.argmax(e1.prefill([2, 4])))
        want = e1.generate_on_device(
            first, 9, temperature=0.8, topp=0.0, seed=5, topk=4
        ).tolist()

        for chunk in (2, 9):
            e = build_engine(tmp_path, spec)
            e.prefill([2, 4])
            got = self._stream(
                e, first, 9, temperature=0.8, topp=0.0, seed=5, chunk=chunk,
                topk=4,
            )
            assert got == want, f"chunk={chunk}"

    def test_early_stop_rollback_resumes_equivalently(self, tmp_path):
        """Stop mid-chunk, rollback, continue with decode_step: the stream
        must equal the never-chunked stepwise stream (the cache slots beyond
        the rollback point are overwritten, not trusted)."""
        spec = tiny_spec()
        ref = build_engine(tmp_path, spec)
        token = int(np.argmax(ref.prefill([1, 5, 9])))
        ref_stream = [token]
        for _ in range(8):
            token = int(np.argmax(ref.decode_step(token)))
            ref_stream.append(token)

        e = build_engine(tmp_path, spec)
        first = int(np.argmax(e.prefill([1, 5, 9])))
        start_pos = e.pos
        consumed = 0
        got = [first]
        for t in e.generate_chunks(first, temperature=0.0, chunk=5):
            consumed += 1
            got.append(t)
            if consumed == 3:  # stop mid-chunk (chunk=5)
                break
        e.rollback(start_pos + consumed)
        token = got[-1]
        for _ in range(8 - consumed):
            token = int(np.argmax(e.decode_step(token)))
            got.append(token)
        assert got == ref_stream

    def test_limit_stops_dispatching(self, tmp_path):
        spec = tiny_spec(seq_len=64)
        e = build_engine(tmp_path, spec)
        e.prefill([1, 2, 3])
        drawn = list(e.generate_chunks(4, temperature=0.0, chunk=4, limit=10))
        # pos hits the limit after ceil((10-3)/4)=2 chunks of 4
        assert len(drawn) == 8
        assert e.pos == 11


class TestPartitionToppFallback:
    """The exact partition-based selection replacing the full-vocab sort
    for bare top-p over near-flat logits (ISSUE 14 satellite; ROADMAP
    item 2's named follow-up): picks must match the sort path exactly."""

    def test_partition_matches_full_sort(self):
        from distributed_llama_tpu.models.sampling import (
            _pick_sorted,
            _topp_partition_pick,
        )

        rng = np.random.RandomState(0)
        B, V = 8, 3000
        for trial in range(12):
            scale = (0.01, 0.1, 1.0)[trial % 3]  # near-flat → peaked
            logits = jnp.asarray(rng.randn(B, V).astype(np.float32) * scale)
            probs = jax.nn.softmax(logits, axis=-1)
            coin = jnp.asarray(rng.rand(B).astype(np.float32))
            topp = jnp.full(B, (0.9, 0.99, 0.5)[trial % 3], jnp.float32)
            topk = jnp.zeros(B, jnp.int32)
            fi = jax.lax.top_k(logits, V)[1]
            want = np.asarray(_pick_sorted(
                jnp.take_along_axis(probs, fi, axis=-1), fi, coin, topp, topk
            ))
            got = np.asarray(_topp_partition_pick(probs, logits, coin, topp))
            np.testing.assert_array_equal(got, want, err_msg=f"trial {trial}")

    def test_partition_handles_ties(self):
        from distributed_llama_tpu.models.sampling import (
            _pick_sorted,
            _topp_partition_pick,
        )

        rng = np.random.RandomState(1)
        # blocks of exactly-equal logits: canonical order breaks ties by
        # lower id — the partition path must reproduce that, not just the
        # kept mass
        logits = jnp.asarray(np.repeat(rng.randn(4, 40).astype(np.float32), 10, axis=1))
        probs = jax.nn.softmax(logits, axis=-1)
        coin = jnp.asarray(rng.rand(4).astype(np.float32))
        topp = jnp.full(4, 0.7, jnp.float32)
        fi = jax.lax.top_k(logits, 400)[1]
        want = np.asarray(_pick_sorted(
            jnp.take_along_axis(probs, fi, axis=-1), fi, coin, topp,
            jnp.zeros(4, jnp.int32),
        ))
        got = np.asarray(_topp_partition_pick(probs, logits, coin, topp))
        np.testing.assert_array_equal(got, want)

    def test_fused_pick_routes_bare_topp_overflow_to_partition(self):
        """End to end through fused_pick: near-flat logits with bare top-p
        (the overflow regime) at a vocab ABOVE TOPP_PARTITION_MIN_V must
        produce the same token as the sorted reference pick — the routing
        change is invisible to outputs."""
        from distributed_llama_tpu.models.sampling import (
            TOPP_PARTITION_MIN_V,
            _pick_sorted,
            fused_pick,
        )

        rng = np.random.RandomState(2)
        B, V = 4, TOPP_PARTITION_MIN_V + 4
        logits = jnp.asarray(rng.randn(B, V).astype(np.float32) * 0.02)
        probs = jax.nn.softmax(logits, axis=-1)
        coin = jnp.asarray(rng.rand(B).astype(np.float32))
        topp = jnp.full(B, 0.9, jnp.float32)
        topk = jnp.zeros(B, jnp.int32)
        fi = jax.lax.top_k(logits, V)[1]
        want = np.asarray(_pick_sorted(
            jnp.take_along_axis(probs, fi, axis=-1), fi, coin, topp, topk
        ))
        got = np.asarray(fused_pick(probs, logits, coin, topp, topk))
        np.testing.assert_array_equal(got, want)
