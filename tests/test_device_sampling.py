"""Device-resident sampling (ISSUE 13): counter-PRNG host/device bit
parity, the seeded device-vs-host parity suite (f32 / bf16 / i8 cache,
single-stream / batched / paged / spec-verify), the fused top-p redraw
distribution, sampled failover replay, and the sharded-vocab top-k
composition."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu import prng
from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.tokenizer import Sampler

from tests.model_utils import random_tensors, tiny_spec, write_model_file


def build_engine(tmp_path, name="model.m", seed=0, seq_len=96, dtype=jnp.float32,
                 cache_dtype=None, tp=1):
    spec = tiny_spec(seq_len=seq_len)
    path = str(tmp_path / name)
    write_model_file(path, spec, random_tensors(spec, seed=seed))
    return InferenceEngine(path, dtype=dtype, cache_dtype=cache_dtype, tp=tp)


class TestCounterPrng:
    """The host and device halves of the counter PRNG are the same uint32
    arithmetic: bit parity is the entire contract."""

    def test_u32_and_f32_bit_parity(self):
        for seed in (0, 1, 7, 123456789, 2**31 - 1, 2**63 + 5):
            s32 = prng.fold_seed(seed)
            for draw in (prng.DRAW_SAMPLE, prng.DRAW_SPEC_ACCEPT,
                         prng.DRAW_SPEC_REDRAW):
                pos = np.arange(0, 4096, 31)
                dev = np.asarray(prng.device_coin_u32(
                    jnp.full(pos.shape, s32, jnp.uint32),
                    jnp.asarray(pos, jnp.int32), draw,
                ))
                host = np.array(
                    [prng.coin_u32(s32, int(p), draw) for p in pos], np.uint32
                )
                assert (dev == host).all()
                devf = np.asarray(prng.device_coin(
                    jnp.full(pos.shape, s32, jnp.uint32),
                    jnp.asarray(pos, jnp.int32), draw,
                ))
                hostf = np.array(
                    [prng.coin_f32(s32, int(p), draw) for p in pos], np.float32
                )
                assert (devf == hostf).all()

    def test_fold_seed_distinct_below_2_32(self):
        seeds = [prng.fold_seed(s) for s in range(0, 4096, 7)]
        assert len(set(seeds)) == len(seeds)

    def test_uniformity_and_decorrelation(self):
        s32 = prng.fold_seed(3)
        u = np.array([prng.coin_f32(s32, p) for p in range(8192)])
        assert abs(u.mean() - 0.5) < 0.02
        assert abs(u.var() - 1.0 / 12.0) < 0.005
        assert abs(np.corrcoef(u[:-1], u[1:])[0, 1]) < 0.05
        # draw channels at the same position are independent streams
        a = np.array([prng.coin_f32(s32, p, prng.DRAW_SAMPLE) for p in range(512)])
        b = np.array([prng.coin_f32(s32, p, prng.DRAW_SPEC_ACCEPT) for p in range(512)])
        assert abs(np.corrcoef(a, b)[0, 1]) < 0.1


# ----------------------------------------------------------------------
# Seeded device-vs-host parity: the host counter Sampler, fed the fetched
# f32 logits, must replay a device-sampled stream token for token.
# ----------------------------------------------------------------------

SETTINGS = [
    # (temperature, topp, topk, seed)
    (0.9, 0.8, 0, 13),   # nucleus path
    (0.7, 0.95, 5, 17),  # nucleus ∧ top-k
    (0.8, 0.0, 3, 3),    # bare top-k
    (0.0, 0.9, 0, 11),   # greedy (argmax parity)
]


def _device_stream(engine_or_stream, prompt, t, tp, k, sd, n):
    s = engine_or_stream
    first = s.prefill_device(prompt, t, tp, sd, k)
    if n == 1:
        return [s.fetch_first_token(first)]
    out = []

    def on_token(prev, tok):
        out.append(tok)
        return len(out) < n

    s.stream_decode(first, on_token, t, tp, seed=sd, chunk=4,
                    limit=s.pos + n, first_prev=prompt[-1], topk=k)
    return out


def _host_replay(engine, prompt, t, tp, k, sd, n, vocab):
    """The host half: per-token forward (logits fetched) + counter-mode
    Sampler keyed on the consumed position."""
    s = Sampler(vocab_size=vocab, temperature=t, topp=tp, topk=k, seed=sd,
                counter=True)
    logits = engine.prefill(prompt)
    out = [s.sample(logits, pos=engine.pos - 1)]
    while len(out) < n:
        logits = engine.decode_step(out[-1])
        out.append(s.sample(logits, pos=engine.pos - 1))
    return out


class TestHostDeviceParity:
    @pytest.mark.parametrize("dtype,cache_dtype", [
        (jnp.float32, None),
        (jnp.bfloat16, None),
        (jnp.float32, "i8"),
    ], ids=["f32", "bf16", "i8cache"])
    def test_single_stream_parity(self, tmp_path, dtype, cache_dtype):
        for t, tp, k, sd in SETTINGS:
            dev_e = build_engine(tmp_path, "dev.m", dtype=dtype,
                                 cache_dtype=cache_dtype)
            dev = _device_stream(
                dev_e.default_stream, [1, 5, 9], t, tp, k, sd, 10
            )
            host_e = build_engine(tmp_path, "host.m", dtype=dtype,
                                  cache_dtype=cache_dtype)
            host = _host_replay(
                host_e, [1, 5, 9], t, tp, k, sd, 10, dev_e.cfg.vocab_size
            )
            assert dev == host, (t, tp, k, sd, dev, host)

    def test_batched_parity(self, tmp_path):
        """Every batched row — mixed greedy/sampled/top-k settings in one
        bucket — replays on the host counter sampler."""
        engine = build_engine(tmp_path, "bat.m")
        sched = BatchScheduler(engine, n_rows=3, chunk=4)
        streams = [sched.new_stream() for _ in range(3)]
        prompts = [[1, 5, 9], [2, 4, 6, 8], [3, 7]]
        outs = [None] * 3
        errors = []

        def run(i):
            try:
                t, tp, k, sd = SETTINGS[i]
                outs[i] = _device_stream(
                    streams[i], prompts[i], t, tp, k, sd, 8
                )
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=180)
        assert not errors, errors
        for i in range(3):
            t, tp, k, sd = SETTINGS[i]
            host_e = build_engine(tmp_path, f"host{i}.m")
            host = _host_replay(
                host_e, prompts[i], t, tp, k, sd, 8, engine.cfg.vocab_size
            )
            assert outs[i] == host, (i, outs[i], host)

    def test_paged_parity(self, tmp_path):
        """A sampled prefix-cache HIT (its pages copied into the row, or read
        in place under tp) must still replay on the host — a hit changes
        where KV comes from, never what is sampled."""
        t, tp, k, sd = 0.9, 0.8, 0, 29
        prompt = [1, 5, 9, 2, 8, 4, 6, 3] * 2  # spans full pages
        engine = build_engine(tmp_path, "paged.m")
        sched = BatchScheduler(
            engine, n_rows=2, chunk=4, prefix_cache=True, page_size=8,
        )
        s0 = sched.new_stream()
        warm = _device_stream(s0, prompt, t, tp, k, sd, 8)  # publishes pages
        s0.reset()
        s1 = sched.new_stream()
        hit = _device_stream(s1, prompt, t, tp, k, sd, 8)
        assert s1.matched_len > 0  # the hit actually aliased pool pages
        assert hit == warm
        host_e = build_engine(tmp_path, "paged_host.m")
        host = _host_replay(
            host_e, prompt, t, tp, k, sd, 8, engine.cfg.vocab_size
        )
        assert hit == host

    def test_spec_verify_parity(self, tmp_path):
        """The speculative accept/reject against a pure-numpy mirror fed
        the same logits and counter coins (the spec slice of the parity
        suite: accept coins, residual redraws and the bonus draw all
        re-derive host-side)."""
        from distributed_llama_tpu.models.sampling import _spec_accept_row

        rng = np.random.RandomState(4)
        V, T = 32, 4
        for case in range(20):
            logits = (rng.randn(T, V) * 2.0).astype(np.float32)
            draft = rng.randint(0, V, T - 1).astype(np.int32)
            draft_len = int(rng.randint(0, T))
            t, tp, k = [
                (0.9, 0.8, 0), (0.7, 0.95, 6), (1.2, 0.0, 0), (0.0, 0.9, 0)
            ][case % 4]
            seed32 = prng.fold_seed(100 + case)
            pos = int(rng.randint(0, 50))
            n_dev, toks_dev = _spec_accept_row(
                jnp.asarray(logits), jnp.asarray(draft), jnp.int32(draft_len),
                jnp.uint32(seed32), jnp.int32(pos), jnp.float32(t),
                jnp.float32(tp), jnp.int32(k),
            )
            n_host, toks_host = _np_spec_accept(
                logits, draft, draft_len, seed32, pos, t, tp, k
            )
            assert int(n_dev) == n_host, (case, int(n_dev), n_host)
            assert np.asarray(toks_dev)[: n_host].tolist() == toks_host[: n_host], case


def _np_filtered_dist(logits, t, topp, topk):
    """numpy mirror of sampling._filtered_dist (f32 throughout)."""
    T, V = logits.shape
    logits = logits.astype(np.float32)
    greedy = logits.argmax(-1)
    scaled = (logits / np.float32(max(t, 1e-6))).astype(np.float32)
    p = np.zeros((T, V), np.float32)
    for i in range(T):
        m = scaled[i].max()
        e = np.exp(scaled[i] - m, dtype=np.float32)
        probs = (e / e.sum(dtype=np.float32)).astype(np.float32)
        order = np.argsort(-scaled[i], kind="stable")
        pv = probs[order]
        cum = np.cumsum(pv, dtype=np.float32)
        n_nuc = int(np.sum(cum - pv < np.float32(topp))) if 0 < topp < 1 else V
        n_k = topk if 0 < topk < V else V
        n_keep = max(1, min(n_nuc, n_k))
        keep = np.zeros(V, bool)
        keep[order[:n_keep]] = True
        filt = np.where(keep, probs, np.float32(0.0)).astype(np.float32)
        p[i] = filt / filt.sum(dtype=np.float32)
    return p, greedy


def _np_cdf_pick(p_row, coin):
    cdf = np.cumsum(p_row, dtype=np.float32)
    r = np.float32(coin) * cdf[-1]
    return min(int(np.sum(cdf <= r)), p_row.size - 1)


def _np_spec_accept(logits, draft, draft_len, seed32, pos, t, topp, topk):
    """numpy mirror of sampling._spec_accept_row on the same coins."""
    T, V = logits.shape
    k = T - 1
    p, greedy = _np_filtered_dist(logits, t, topp, topk)
    u = [prng.coin_f32(seed32, pos + i, prng.DRAW_SPEC_ACCEPT) for i in range(T)]
    redraw = [prng.coin_f32(seed32, pos + i, prng.DRAW_SPEC_REDRAW) for i in range(T)]
    n_acc = 0
    for i in range(k):
        if i >= draft_len:
            break
        ok = (
            draft[i] == greedy[i]
            if t == 0.0
            else u[i] < p[i, draft[i]]
        )
        if not ok:
            break
        n_acc += 1
    rejected = n_acc < draft_len
    if t == 0.0:
        corr = int(greedy[n_acc])
    elif rejected:
        q = p[n_acc].copy()
        q[draft[n_acc]] = 0.0
        corr = _np_cdf_pick(q, redraw[n_acc])
    else:
        corr = _np_cdf_pick(p[n_acc], redraw[n_acc])
    toks = [int(draft[i]) for i in range(n_acc)] + [corr]
    return n_acc + 1, toks


# ----------------------------------------------------------------------
# Distribution: the fused sampler must actually sample the filtered,
# renormalized distribution, and the spec redraw must sample the residual.
# ----------------------------------------------------------------------


class TestFusedDistribution:
    def test_topp_draw_matches_renormalized_nucleus(self):
        from distributed_llama_tpu.models.sampling import fused_sample_batched

        rng = np.random.RandomState(0)
        V = 64
        logits = (rng.randn(V) * 1.5).astype(np.float32)
        topp = 0.6
        probs = np.asarray(jax.nn.softmax(jnp.asarray(logits)))
        order = np.argsort(-probs, kind="stable")
        cum = np.cumsum(probs[order])
        n_keep = int(np.sum(cum - probs[order] < topp))
        nucleus = set(order[:n_keep].tolist())
        target = np.zeros(V)
        target[order[:n_keep]] = probs[order[:n_keep]] / cum[n_keep - 1]

        B = 512
        sample = jax.jit(lambda seeds, pos: fused_sample_batched(
            jnp.broadcast_to(jnp.asarray(logits), (B, V)), seeds, pos,
            jnp.ones(B, jnp.float32), jnp.full(B, topp, jnp.float32),
            jnp.zeros(B, jnp.int32),
        ))
        counts = np.zeros(V)
        for rep in range(6):
            seeds = jnp.asarray(
                [prng.fold_seed(rep * B + i) for i in range(B)], jnp.uint32
            )
            pos = jnp.full(B, rep, jnp.int32)
            toks = np.asarray(sample(seeds, pos))
            for tok in toks:
                counts[tok] += 1
        n = counts.sum()
        assert set(np.nonzero(counts)[0].tolist()) <= nucleus  # zero leakage
        np.testing.assert_allclose(counts / n, target, atol=0.03)

    def test_spec_redraw_samples_residual(self):
        """The fused top-p REDRAW (rejection at a draft position): over
        many seeds the correction token must follow the residual —
        p filtered, renormalized, with the draft token's mass removed —
        and must never return the rejected draft itself."""
        from distributed_llama_tpu.models.sampling import _spec_accept_row

        V = 16
        # draft token 0 dominates p so rejections still occur via the coin,
        # and the residual over the rest is nontrivial
        logits = np.zeros((2, V), np.float32)
        logits[0, :8] = np.linspace(2.0, 0.5, 8)
        draft = jnp.asarray([0], jnp.int32)
        topp = 0.95
        p, _ = _np_filtered_dist(logits, 1.0, topp, 0)
        resid = p[0].copy()
        resid[0] = 0.0
        resid /= resid.sum()

        accept = jax.jit(lambda seed: _spec_accept_row(
            jnp.asarray(logits), draft, jnp.int32(1), seed, jnp.int32(0),
            jnp.float32(1.0), jnp.float32(topp), jnp.int32(0),
        ))
        counts = np.zeros(V)
        rejections = 0
        for i in range(4000):
            n_emit, toks = accept(jnp.uint32(prng.fold_seed(i)))
            if int(n_emit) == 1:  # draft rejected → correction from residual
                rejections += 1
                counts[int(toks[0])] += 1
        assert rejections > 300  # the acceptance coin does reject
        assert counts[0] == 0  # the rejected draft can never be redrawn
        np.testing.assert_allclose(
            counts / rejections, resid, atol=0.04
        )


# ----------------------------------------------------------------------
# Failover replay: a SAMPLED stream (temperature > 0, pinned seed) must
# replay bit-identically on the surviving replica — the counter PRNG
# re-keys every coin from (seed, position); no sampler state crossed.
# ----------------------------------------------------------------------


@pytest.mark.chaos
class TestSampledFailoverReplay:
    def test_sampled_stream_replays_bit_identical(self, tmp_path):
        from distributed_llama_tpu.engine import faults
        from tests.test_replicas import (
            SseStream,
            make_replica_state,
            post_raw,
            serve_state,
        )

        body_base = {
            "messages": [{"role": "user", "content": "tell me a story"}],
            "max_tokens": 48, "temperature": 0.9, "top_p": 0.85, "seed": 77,
        }
        clean = make_replica_state(tmp_path, "clean", replicas=2, parallel=2)
        url, server = serve_state(clean)
        try:
            status, _, body = post_raw(url, dict(body_base))
            assert status == 200
            baseline = body["choices"][0]["message"]["content"]
            assert body["usage"]["completion_tokens"] >= 16
        finally:
            server.shutdown()
            clean.pool.close()

        faults.install(faults.parse(
            "replica.crash:kind=raise,row=0,after=8,count=1;"
            "batch.fetch:kind=delay,delay_ms=25,count=-1"
        ))
        try:
            state = make_replica_state(tmp_path, "chaos", replicas=2, parallel=2)
            url, server = serve_state(state)
            try:
                streams = [
                    SseStream(url, dict(body_base, stream=True))
                    for _ in range(4)
                ]
                texts = [s.read_first_delta() + s.read_rest() for s in streams]
                assert all(s.error_type is None for s in streams), [
                    s.error_type for s in streams
                ]
                # the survivor pair AND the replayed victims all stream the
                # seeded sampled completion byte-identically
                assert texts == [baseline] * 4
                assert state.pool.failovers_total == 1
                assert state.pool.replayed_total >= 1
            finally:
                server.shutdown()
                state.pool.close()
        finally:
            faults.install(None)


# ----------------------------------------------------------------------
# Sharded-vocab top-k composition (the tp candidate reduction).
# ----------------------------------------------------------------------


class TestShardedTopK:
    def test_matches_full_vocab_topk(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from distributed_llama_tpu.models.sampling import sharded_topk_indices

        devs = jax.devices()
        if len(devs) < 4:
            pytest.skip("needs the 8-device virtual CPU mesh")
        tp = 4
        B, V, K = 3, 256, 32
        rng = np.random.RandomState(0)
        logits = (rng.randn(B, V) * 2.0).astype(np.float32)
        # inject cross-shard ties: equal values on both sides of a shard
        # boundary must resolve to the lower global id, like lax.top_k
        logits[0, 10] = logits[0, V // tp + 3] = 7.5
        mesh = Mesh(np.array(devs[:tp]), ("tp",))

        fn = jax.shard_map(
            lambda x: sharded_topk_indices(x, "tp", K),
            mesh=mesh, in_specs=(P(None, "tp"),), out_specs=P(),
            check_vma=False,
        )
        got = np.asarray(fn(jnp.asarray(logits)))
        want = np.asarray(jax.lax.top_k(jnp.asarray(logits), K)[1])
        assert (got == want).all()

    def test_tp_rows_sample_through_the_composition_inside_the_arm(self, tmp_path, monkeypatch):
        """ISSUE 46: under tp the candidates' composition (a collective) is
        called inside the sampler's arm. Rows that sample beside a greedy
        one, on a vocab-sharded head, still replay on the host sampler; a
        chunk whose rows are all greedy takes the other arm of the same
        program."""
        from distributed_llama_tpu.models import sampling

        if len(jax.devices()) < 2:
            pytest.skip("needs the 8-device virtual CPU mesh")
        composed = []
        real = sampling.sharded_topk_indices
        monkeypatch.setattr(
            sampling, "sharded_topk_indices",
            lambda *a: (composed.append(a[0].shape), real(*a))[1],
        )
        engine = build_engine(tmp_path, "tp.m", tp=2)
        sched = BatchScheduler(engine, n_rows=2, chunk=4)
        prompts = [[1, 5, 9], [2, 4, 6, 8]]
        streams = [sched.new_stream() for _ in range(2)]
        for settings in ([SETTINGS[3], SETTINGS[1]], [SETTINGS[3], SETTINGS[3]]):
            firsts = [
                s.prefill_device(p, t, tp, sd, k)
                for s, p, (t, tp, k, sd) in zip(streams, prompts, settings)
            ]
            for s, first, (t, tp, k, sd) in zip(streams, firsts, settings):
                sched._join(s, first, t, tp, sd, k)
            outs = [
                [s.fetch_first_token(first)] + [sched.next_token(s) for _ in range(7)]
                for s, first in zip(streams, firsts)
            ]
            for i, (t, tp, k, sd) in enumerate(settings):
                host = _host_replay(
                    build_engine(tmp_path, f"host{i}.m"), prompts[i], t, tp, k, sd, 8,
                    engine.cfg.vocab_size,
                )
                assert outs[i] == host, (settings, i, outs[i], host)
            for s in streams:
                s.reset()
        # the head was sharded: the composition was traced, over half the vocabulary
        assert composed and all(shape == (2, engine.cfg.vocab_size // 2) for shape in composed)
