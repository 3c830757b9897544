"""Zero-copy paged attention (ISSUE 7): bit-parity of page-table reads vs
slab reads at the ops level (decode, verify, blocked prefill; f32 and i8,
segmented and virtual-fallback paths), engine-level hit-vs-cold parity on
the BLOCKED production shape, speculative decode × prefix cache parity,
row-lifetime page pinning (eviction pressure, quarantine pin release via
the ``engine.paged_attn`` chaos site, rollback truncation), alias-extended
tree invariants, and the tensor-parallel sharded pool."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.engine import InferenceEngine, faults
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.ops import kv_cache as kvc
from distributed_llama_tpu.ops.attention import (
    batched_decode_attention,
    batched_verify_attention,
    blocked_attention,
)

from tests.model_utils import random_tensors, tiny_spec, write_model_file

PAGE = 4
PROMPT = [1, 5, 9, 2, 7, 3, 11, 4, 6, 8]  # 10 tokens = 2 full pages + 2


def build_engine(tmp_path, name="model.m", seed=0, seq_len=96, cache_dtype=None,
                 tp=1):
    spec = tiny_spec(seq_len=seq_len)
    path = str(tmp_path / name)
    write_model_file(path, spec, random_tensors(spec, seed=seed))
    return InferenceEngine(path, dtype=jnp.float32, cache_dtype=cache_dtype,
                          tp=tp)


def decode_tokens(stream, prompt, temp, topp, seed, n, spec_draft=None):
    """One request through the fused serving flow on a scheduler row."""
    stream.reset()
    first = stream.prefill_device(prompt, temp, topp, seed)
    got = []

    def on_token(prev, tok):
        got.append(tok)
        return len(got) < n

    kw = {}
    if spec_draft is not None:
        kw = dict(spec_draft=spec_draft, prompt_tokens=prompt)
    stream.stream_decode(first, on_token, temp, topp, seed=seed,
                         limit=stream.pos + n, first_prev=prompt[-1],
                         **kw)
    return got


# ---------------------------------------------------------------------------
# Ops level: the paged read must be BYTE-identical to attending over a slab
# that holds copies of the pages (the copy design's layout)
# ---------------------------------------------------------------------------


class TestOpsBitParity:
    B, S, K, M, HD, CHUNK, P = 2, 64, 2, 2, 8, 16, 16

    def _setup(self, dtype, matched):
        """Full slab (the copy design) vs empty-prefix slab + pool + tables
        (zero-copy), holding byte-identical KV for every live position."""
        rng = np.random.RandomState(42)
        full = kvc.init_half((self.B, self.S, self.K, self.HD), dtype)
        rows = rng.randn(self.B, self.S, self.K, self.HD).astype(np.float32)
        if isinstance(full, kvc.QuantizedKV):
            q, s = jax.vmap(kvc.quantize_rows)(jnp.asarray(rows))
            full = kvc.QuantizedKV(q, s)
        else:
            full = jnp.asarray(rows).astype(full.dtype)
        pool = kvc.init_page_pool_half(self.P, PAGE, self.K, self.HD, dtype)
        n_table = self.S // PAGE
        tables = np.zeros((self.B, n_table), np.int32)
        next_pid = 0
        aliased = full
        for b in range(self.B):
            n_pages = matched[b] // PAGE
            for p in range(n_pages):
                pid = next_pid
                next_pid += 1
                tables[b, p] = pid
                src = full[b, p * PAGE : (p + 1) * PAGE]
                if isinstance(pool, kvc.QuantizedKV):
                    pool = kvc.QuantizedKV(
                        pool.data.at[pid].set(src.data),
                        pool.scales.at[pid].set(src.scales),
                    )
                else:
                    pool = pool.at[pid].set(src)
            # zero the aliased prefix out of the zero-copy slab: the paged
            # read must never touch it (a parity failure would show)
            if n_pages:
                sl = slice(0, n_pages * PAGE)
                if isinstance(aliased, kvc.QuantizedKV):
                    aliased = kvc.QuantizedKV(
                        aliased.data.at[b, sl].set(0),
                        aliased.scales.at[b, sl].set(0),
                    )
                else:
                    aliased = aliased.at[b, sl].set(0)
        return full, aliased, pool, jnp.asarray(tables), jnp.asarray(matched, jnp.int32)

    @pytest.mark.parametrize("dtype", [jnp.float32, "i8"])
    @pytest.mark.parametrize("matched", [[8, 0], [16, 8], [32, 32], [0, 0]])
    def test_batched_decode_paged_matches_copied_slab(self, dtype, matched):
        full, aliased, pool, tables, m = self._setup(dtype, matched)
        rng = np.random.RandomState(7)
        qg = jnp.asarray(
            rng.randn(self.B, self.K, self.M, self.HD).astype(np.float32)
        )
        pos = jnp.asarray([40, 35], jnp.int32)
        want = batched_decode_attention(qg, (full, full), pos, self.CHUNK)
        got = batched_decode_attention(
            qg, (aliased, aliased), pos, self.CHUNK,
            paged=(pool, pool, tables, m),
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("dtype", [jnp.float32, "i8"])
    def test_batched_verify_paged_matches_copied_slab(self, dtype):
        full, aliased, pool, tables, m = self._setup(dtype, [16, 8])
        rng = np.random.RandomState(8)
        T = 3
        qg = jnp.asarray(
            rng.randn(self.B, T, self.K, self.M, self.HD).astype(np.float32)
        )
        pos = jnp.asarray([30, 20], jnp.int32)
        want = batched_verify_attention(qg, (full, full), pos, self.CHUNK)
        got = batched_verify_attention(
            qg, (aliased, aliased), pos, self.CHUNK,
            paged=(pool, pool, tables, m),
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("dtype", [jnp.float32, "i8"])
    def test_blocked_prefill_paged_matches_copied_slab(self, dtype):
        full, aliased, pool, tables, m = self._setup(dtype, [16, 0])
        rng = np.random.RandomState(9)
        T = 5
        qg = jnp.asarray(rng.randn(T, self.K, self.M, self.HD).astype(np.float32))
        pos = jnp.int32(24)  # suffix prefill: queries at 24..28, prefix 0..15
        want = blocked_attention(qg, full[0], full[0], pos, self.CHUNK)
        got = blocked_attention(
            qg, aliased[0], aliased[0], pos, self.CHUNK,
            paged=(pool, pool, tables[0], m[0]),
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # -- the slab AS STORED: a fused [2, B_max, S, K, hd] leaf must read
    # exactly like the (keys, values) tuple of its halves (PR 24: the chunk
    # loops slice the leaf themselves; no half of a whole slab is formed)

    B_MAX = 4  # the fused slab holds more rows than a dispatch bucket reads

    def _fused_leaf(self, dtype, seed=3):
        """A fused slab leaf with distinct keys and values in every slot."""
        rng = np.random.RandomState(seed)
        rows = jnp.asarray(
            rng.randn(2, self.B_MAX, self.S, self.K, self.HD).astype(np.float32)
        )
        if kvc.is_quantized_cache_dtype(dtype):
            q, s = jax.vmap(jax.vmap(kvc.quantize_rows))(rows)
            return kvc.QuantizedKV(q, s)
        return rows.astype(dtype)

    @staticmethod
    def _assert_same_bytes(got, want):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(
                np.asarray(g.astype(jnp.float32)), np.asarray(w.astype(jnp.float32))
            )

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, "i8"])
    @pytest.mark.parametrize("rows", [1, 2, 4])
    @pytest.mark.parametrize("chunk_index", [0, 1, 3], ids=["first", "middle", "last"])
    def test_slab_chunk_reads_leaf_like_its_halves(self, dtype, rows, chunk_index):
        leaf = self._fused_leaf(dtype)
        start = jnp.int32(chunk_index * self.CHUNK)
        read = jax.jit(kvc.slab_chunk, static_argnums=(2, 3))
        got = read(leaf, start, self.CHUNK, rows)
        want = read((leaf[0], leaf[1]), start, self.CHUNK, rows)
        self._assert_same_bytes(got, want)
        # and they are the slots asked for, keys apart from values
        lo = chunk_index * self.CHUNK
        for i in (0, 1):
            self._assert_same_bytes(got[i], leaf[i][:rows, lo : lo + self.CHUNK])
        assert got[0].shape == (rows, self.CHUNK, self.K, self.HD)

    def _paged_for(self, dtype, matched):
        """A pool + tables + matched for ``len(matched)`` rows; the pool holds
        its own random pages (leaf and halves read the SAME pool, so parity
        needs no byte agreement between pool and slab)."""
        rng = np.random.RandomState(11)
        pool = rng.randn(self.P, PAGE, self.K, self.HD).astype(np.float32)
        if kvc.is_quantized_cache_dtype(dtype):
            q, s = jax.vmap(kvc.quantize_rows)(jnp.asarray(pool))
            pool = kvc.QuantizedKV(q, s)
        else:
            pool = jnp.asarray(pool).astype(dtype)
        tables = rng.randint(0, self.P, (len(matched), self.S // PAGE)).astype(np.int32)
        return pool, pool, jnp.asarray(tables), jnp.asarray(matched, jnp.int32)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, "i8"])
    @pytest.mark.parametrize("matched", [None, [0, 0], [16, 8], [32, 32], [20, 0]])
    def test_batched_decode_on_leaf_equals_on_halves(self, dtype, matched):
        leaf = self._fused_leaf(dtype)
        rng = np.random.RandomState(12)
        qg = jnp.asarray(rng.randn(self.B, self.K, self.M, self.HD).astype(np.float32))
        pos = jnp.asarray([60, 35], jnp.int32)
        paged = None if matched is None else self._paged_for(dtype, matched)
        run = jax.jit(lambda c: batched_decode_attention(qg, c, pos, self.CHUNK, paged=paged))
        np.testing.assert_array_equal(
            np.asarray(run(leaf)), np.asarray(run((leaf[0], leaf[1])))
        )

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, "i8"])
    @pytest.mark.parametrize("matched", [None, [16, 8], [32, 0]])
    def test_batched_verify_on_leaf_equals_on_halves(self, dtype, matched):
        leaf = self._fused_leaf(dtype)
        rng = np.random.RandomState(13)
        T = 3
        qg = jnp.asarray(
            rng.randn(self.B, T, self.K, self.M, self.HD).astype(np.float32)
        )
        pos = jnp.asarray([40, 33], jnp.int32)
        paged = None if matched is None else self._paged_for(dtype, matched)
        run = jax.jit(lambda c: batched_verify_attention(qg, c, pos, self.CHUNK, paged=paged))
        np.testing.assert_array_equal(
            np.asarray(run(leaf)), np.asarray(run((leaf[0], leaf[1])))
        )

    def test_paged_scan_reads_the_slab_in_one_loop(self):
        """A leaf that feeds TWO loops is re-laid out for them on the chip
        (PERF.md §5): with ``paged`` the mixed and the slab-only segment
        share one ``while``; the pool-only loop does not take the slab."""
        leaf = self._fused_leaf(jnp.bfloat16)
        qg = jnp.zeros((self.B, self.K, self.M, self.HD), jnp.float32)
        pos = jnp.asarray([60, 35], jnp.int32)
        paged = self._paged_for(jnp.bfloat16, [16, 8])
        jaxpr = jax.make_jaxpr(
            lambda c: batched_decode_attention(qg, c, pos, self.CHUNK, paged=paged)
        )(leaf)
        slab_shape = leaf.shape
        loops = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "while"]
        assert len(loops) == 2
        taking_slab = [
            e for e in loops if any(getattr(v.aval, "shape", None) == slab_shape for v in e.invars)
        ]
        assert len(taking_slab) == 1
        # and no equation outside the loops forms a half of the slab
        assert not [
            e for e in jaxpr.jaxpr.eqns
            if any(getattr(v.aval, "shape", None) == slab_shape[1:] for v in e.outvars)
        ]

    @pytest.mark.parametrize("dtype", [jnp.float32, "i8"])
    def test_virtual_rows_match_copied_slab(self, dtype):
        """The einsum-fallback read (caches too small/odd to block): the
        virtual row view must reproduce the copied slab byte-for-byte."""
        full, aliased, pool, tables, m = self._setup(dtype, [16, 8])
        virt = kvc.virtual_rows_batched(aliased, pool, tables, m)
        if isinstance(full, kvc.QuantizedKV):
            # beyond each row's matched length the slab governs either way
            for b, n in enumerate([16, 8]):
                np.testing.assert_array_equal(
                    np.asarray(virt.data[b, :n]), np.asarray(full.data[b, :n])
                )
                np.testing.assert_array_equal(
                    np.asarray(virt.scales[b]), np.asarray(full.scales[b])
                )
        else:
            np.testing.assert_array_equal(np.asarray(virt), np.asarray(full))


# ---------------------------------------------------------------------------
# Engine level: the BLOCKED production shape (S % ATT_CHUNK == 0,
# page | chunk) — hit streams bit-identical to cold across the segmented
# pool/mixed/slab scan
# ---------------------------------------------------------------------------


class TestBlockedPathParity:
    SEQ = 1024  # ATT_CHUNK = 512 divides; decode takes the segmented scan
    PAGE = 64  # divides ATT_CHUNK: the production page/chunk relation

    def _sched(self, engine, **kw):
        kw.setdefault("prefix_cache", True)
        kw.setdefault("kv_pages", 8)
        kw.setdefault("page_size", self.PAGE)
        return BatchScheduler(engine, n_rows=2, chunk=4, **kw)

    @pytest.mark.parametrize("cache_dtype", [None, "i8"])
    def test_blocked_hit_matches_cold(self, tmp_path, cache_dtype):
        engine = build_engine(
            tmp_path, f"blk{cache_dtype}.m", seq_len=self.SEQ,
            cache_dtype=cache_dtype,
        )
        sched = self._sched(engine)
        s0, s1 = sched.new_stream(), sched.new_stream()
        rng = np.random.RandomState(5)
        prompt = rng.randint(1, 60, self.PAGE + 3).tolist()  # 1 full page + 3
        cold = decode_tokens(s0, prompt, 0.0, 0.9, 7, 8)
        hit = decode_tokens(s1, prompt, 0.0, 0.9, 7, 8)
        assert s1.matched_len == self.PAGE  # the alias actually engaged
        assert hit == cold
        sched.check_prefix()

    def test_blocked_hit_matches_cold_sampled(self, tmp_path):
        engine = build_engine(tmp_path, "blks.m", seq_len=self.SEQ)
        sched = self._sched(engine)
        s0, s1 = sched.new_stream(), sched.new_stream()
        rng = np.random.RandomState(6)
        prompt = rng.randint(1, 60, self.PAGE + 2).tolist()
        cold = decode_tokens(s0, prompt, 0.9, 0.8, 13, 8)
        hit = decode_tokens(s1, prompt, 0.9, 0.8, 13, 8)
        assert hit == cold


# ---------------------------------------------------------------------------
# Speculative decode × prefix cache: a spec-mode row whose prompt hits the
# radix cache must emit the cold spec row's exact greedy stream
# ---------------------------------------------------------------------------


class TestSpecTimesPrefixCache:
    K_DRAFT = 3
    # a repetitive prompt so prompt-lookup actually drafts
    PROMPT = [1, 5, 9, 2, 1, 5, 9, 2, 1, 5]

    def _sched(self, engine, n_rows):
        return BatchScheduler(
            engine, n_rows=n_rows, chunk=4, prefix_cache=True, kv_pages=16,
            page_size=PAGE, spec_draft=self.K_DRAFT,
        )

    @pytest.mark.parametrize("cache_dtype", [None, "i8"])
    def test_single_row_spec_hit_matches_cold(self, tmp_path, cache_dtype):
        engine = build_engine(tmp_path, f"sp{cache_dtype}.m",
                              cache_dtype=cache_dtype)
        sched = self._sched(engine, n_rows=1)
        s = sched.new_stream()
        cold = decode_tokens(s, self.PROMPT, 0.0, 0.9, 7, 10,
                             spec_draft=self.K_DRAFT)
        hit = decode_tokens(s, self.PROMPT, 0.0, 0.9, 7, 10,
                            spec_draft=self.K_DRAFT)
        assert hit == cold
        sched.check_prefix()

    @pytest.mark.parametrize("cache_dtype", [None, "i8"])
    def test_batched_spec_hit_matches_cold(self, tmp_path, cache_dtype):
        """Two co-batched spec rows, one cold and one riding a prefix hit:
        the hit row's verify windows read the pool for the matched prefix
        and must accept/emit identically to its own cold run."""
        engine = build_engine(tmp_path, f"bsp{cache_dtype}.m",
                              cache_dtype=cache_dtype)
        sched = self._sched(engine, n_rows=2)
        s0, s1 = sched.new_stream(), sched.new_stream()
        other = [2, 4, 6, 8, 2, 4, 6]
        want = decode_tokens(s0, self.PROMPT, 0.0, 0.9, 7, 10,
                             spec_draft=self.K_DRAFT)  # publishes the prefix
        got = [None, None]
        errors = []

        def run(idx, stream, prompt, seed):
            try:
                got[idx] = decode_tokens(stream, prompt, 0.0, 0.9, seed, 10,
                                         spec_draft=self.K_DRAFT)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        t0 = threading.Thread(target=run, args=(0, s0, other, 3))
        t1 = threading.Thread(target=run, args=(1, s1, self.PROMPT, 7))
        t0.start(), t1.start()
        t0.join(timeout=180), t1.join(timeout=180)
        assert not errors, errors
        assert got[1] == want  # the hit row, co-batched, is bit-identical
        sched.check_prefix()

    def test_spec_hit_matches_plain_decode_greedy(self, tmp_path):
        """Transitivity gate: spec × prefix-hit greedy == plain non-spec
        decode of the same prompt (the spec parity contract survives the
        paged read)."""
        engine = build_engine(tmp_path, "spp.m")
        plain = BatchScheduler(engine, n_rows=1, chunk=4)
        want = decode_tokens(plain.new_stream(), self.PROMPT, 0.0, 0.9, 7, 10)
        engine2 = build_engine(tmp_path, "spp2.m")
        sched = self._sched(engine2, n_rows=1)
        s = sched.new_stream()
        cold = decode_tokens(s, self.PROMPT, 0.0, 0.9, 7, 10,
                             spec_draft=self.K_DRAFT)
        hit = decode_tokens(s, self.PROMPT, 0.0, 0.9, 7, 10,
                            spec_draft=self.K_DRAFT)
        assert cold == want and hit == want


# ---------------------------------------------------------------------------
# Pin lifetime: row-lifetime refcounts, quarantine release (chaos site),
# rollback truncation
# ---------------------------------------------------------------------------


class TestPinLifetime:
    def _sched(self, engine, **kw):
        kw.setdefault("prefix_cache", True)
        kw.setdefault("kv_pages", 16)
        kw.setdefault("page_size", PAGE)
        return BatchScheduler(engine, n_rows=2, chunk=4, **kw)

    def test_live_row_pages_survive_eviction_pressure(self, tmp_path):
        """A row mid-request aliases its matched pages: churn that wants
        every pool page must NOT evict them (soft-fail instead), and the
        alias-extended check proves they stay mapped and pinned."""
        engine = build_engine(tmp_path)
        sched = self._sched(engine, kv_pages=3)
        s0, s1 = sched.new_stream(), sched.new_stream()
        decode_tokens(s0, PROMPT, 0.0, 0.9, 7, 2)  # publish 2 pages
        s0.reset()
        s0.prefill(PROMPT)  # hit: s0 now aliases both pages, mid-request
        assert s0.matched_len == 2 * PAGE and len(s0._alias_ids) == 2
        rng = np.random.RandomState(3)
        for i in range(4):
            churn = rng.randint(1, 60, 9).tolist()
            decode_tokens(s1, churn, 0.0, 0.9, i, 2)  # wants 2 pages each
            sched.check_prefix()  # s0's pages never freed nor unpinned
        held = set(s0._alias_ids)
        live = {nd.page_id for nd in sched._prefix._walk()}
        assert held <= live
        s0.reset()  # pins release; the pages become evictable
        assert all(nd.refs == 0 for nd in sched._prefix._walk())

    def test_paged_attn_chaos_quarantines_victim_releases_pins(self, tmp_path):
        """The ``engine.paged_attn`` site: a row-targeted raise during a
        paged-decode dispatch retires ONLY the victim, releases its page
        pins, and the co-batched survivor streams bit-identically."""
        engine0 = build_engine(tmp_path, "ref.m")
        ref = self._sched(engine0)
        r0 = ref.new_stream()
        survivor_prompt = [2, 4, 6, 8, 10, 12]
        want = decode_tokens(r0, survivor_prompt, 0.0, 0.9, 11, 10)

        plan = faults.install(
            faults.parse("engine.paged_attn:kind=raise,row=1,after=2,count=1")
        )
        try:
            engine = build_engine(tmp_path, "chaos.m")
            sched = self._sched(engine)
            s0, s1 = sched.new_stream(), sched.new_stream()
            # seed the tree so the victim's request is a prefix HIT (its
            # pins are the thing the quarantine must release)
            decode_tokens(s1, PROMPT, 0.0, 0.9, 7, 2)
            out0 = [None]
            victim_error = []
            errors = []

            def run_survivor():
                try:
                    out0[0] = decode_tokens(s0, survivor_prompt, 0.0, 0.9, 11, 10)
                except Exception as e:  # pragma: no cover
                    errors.append(e)

            def run_victim():
                try:
                    decode_tokens(s1, PROMPT, 0.0, 0.9, 7, 10)
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
            assert not s1._alias_ids and s1.matched_len == 0  # pins released
            assert all(nd.refs == 0 for nd in sched._prefix._walk())
            assert out0[0] == want  # survivor bit-identical
            sched.check_prefix()
        finally:
            faults.clear()

    def test_rollback_below_matched_truncates_alias(self, tmp_path):
        engine = build_engine(tmp_path)
        sched = self._sched(engine)
        s = sched.new_stream()
        decode_tokens(s, PROMPT, 0.0, 0.9, 7, 2)  # publish 2 pages
        s.reset()
        s.prefill(PROMPT)  # hit: matched 8, 2 pages pinned
        assert s.matched_len == 2 * PAGE
        s.rollback(6)  # below matched: alias shrinks to 6, both pages stay
        assert s.matched_len == 6 and len(s._alias_ids) == 2
        s.rollback(4)  # page boundary: the second page's pin releases
        assert s.matched_len == 4 and len(s._alias_ids) == 1
        assert sum(nd.refs for nd in sched._prefix._walk()) == 1
        sched.check_prefix()
        s.rollback(0)
        assert s.matched_len == 0 and not s._alias_ids
        assert all(nd.refs == 0 for nd in sched._prefix._walk())

    def test_rollback_truncation_decode_parity(self, tmp_path):
        """Functional proof of the truncation contract: hit, roll back
        BELOW the matched prefix (mid-page), prefill a DIVERGENT suffix,
        and the stream must match a cold scheduler fed the same final
        token sequence (positions < pos read the still-valid pool bytes,
        positions >= pos the freshly written slab)."""
        shared = PROMPT[:6]  # rollback point 6 is mid-page (page 4)
        divergent = [21, 22, 23, 24]
        full = shared + divergent

        cold_engine = build_engine(tmp_path, "cold.m")
        cold = BatchScheduler(cold_engine, n_rows=1, chunk=4)
        want = decode_tokens(cold.new_stream(), full, 0.0, 0.9, 7, 8)

        engine = build_engine(tmp_path, "roll.m")
        sched = self._sched(engine)
        s = sched.new_stream()
        decode_tokens(s, PROMPT, 0.0, 0.9, 7, 2)  # publish PROMPT's pages
        s.reset()
        first = s.prefill_device(PROMPT, 0.0, 0.9, 7)  # hit: matched 8
        s.fetch_first_token(first)
        assert s.matched_len == 2 * PAGE
        s.rollback(len(shared))  # 6 < 8: truncate the alias mid-page
        assert s.matched_len == 6
        first = s.prefill_device(divergent, 0.0, 0.9, 7)
        got = []

        def on_token(prev, tok):
            got.append(tok)
            return len(got) < 8

        s.stream_decode(first, on_token, 0.0, 0.9, seed=7,
                        limit=s.pos + 8, first_prev=divergent[-1])
        assert got == want
        sched.check_prefix()


# ---------------------------------------------------------------------------
# Telemetry: pool bytes / pinned pages / restored bytes
# ---------------------------------------------------------------------------


class TestPagedTelemetry:
    def test_gauges_and_restored_counter(self, tmp_path):
        from distributed_llama_tpu import telemetry
        from distributed_llama_tpu.models import llama

        telemetry.reset()
        telemetry.enable()
        try:
            engine = build_engine(tmp_path)
            sched = BatchScheduler(
                engine, n_rows=2, chunk=4, prefix_cache=True, kv_pages=16,
                page_size=PAGE,
            )
            page_bytes = llama.page_pool_bytes(engine.cfg, PAGE, engine.cache_dtype)
            assert sched._prefix.page_bytes == page_bytes
            s = sched.new_stream()
            decode_tokens(s, PROMPT, 0.0, 0.9, 7, 2)  # publish 2 pages
            reg = telemetry.REGISTRY
            assert reg.gauge("dllama_prefix_cache_bytes").value == 2 * page_bytes
            assert reg.counter(
                "dllama_prefix_cache_restored_bytes_total"
            ).value == 0  # no hit yet
            s.reset()
            s.prefill(PROMPT)  # hit: 2 pages copied into the row, pinned for it
            assert reg.gauge("dllama_prefix_cache_pinned_pages").value == 2
            assert reg.counter(
                "dllama_prefix_cache_restored_bytes_total"
            ).value == 2 * page_bytes
            assert reg.counter("dllama_prefix_cache_restores_total").value == 1
            s.reset()
            assert reg.gauge("dllama_prefix_cache_pinned_pages").value == 0
        finally:
            telemetry.disable()
            telemetry.reset()


# ---------------------------------------------------------------------------
# Tensor parallel: the sharded pool (per-shard halves, replicated tables)
# ---------------------------------------------------------------------------
class TestTensorParallelPool:
    def _sched(self, engine, **kw):
        kw.setdefault("prefix_cache", True)
        kw.setdefault("kv_pages", 16)
        kw.setdefault("page_size", PAGE)
        return BatchScheduler(engine, n_rows=2, chunk=4, **kw)

    def test_tp_hit_matches_cold(self, tmp_path):
        engine = build_engine(tmp_path, "tp.m", tp=2)
        sched = self._sched(engine)
        assert sched._prefix is not None  # tp no longer disables the cache
        s0, s1 = sched.new_stream(), sched.new_stream()
        cold = decode_tokens(s0, PROMPT, 0.0, 0.9, 7, 8)
        hit = decode_tokens(s1, PROMPT, 0.0, 0.9, 7, 8)
        assert s1.matched_len == 2 * PAGE  # the alias actually engaged
        assert hit == cold
        sched.check_prefix()

    def test_tp_hit_matches_single_chip(self, tmp_path):
        """The sharded pool must not change numerics: a tp=2 prefix-hit
        stream equals the single-chip prefix-hit stream."""
        e1 = build_engine(tmp_path, "sc.m")
        s1 = self._sched(e1)
        a = s1.new_stream()
        decode_tokens(a, PROMPT, 0.0, 0.9, 7, 8)
        want = decode_tokens(a, PROMPT, 0.0, 0.9, 7, 8)  # the hit stream

        e2 = build_engine(tmp_path, "tp2.m", tp=2)
        s2 = self._sched(e2)
        b = s2.new_stream()
        decode_tokens(b, PROMPT, 0.0, 0.9, 7, 8)
        got = decode_tokens(b, PROMPT, 0.0, 0.9, 7, 8)
        np.testing.assert_allclose(got, want, rtol=0, atol=0)

    def test_tp_publish_and_alias_invariants(self, tmp_path):
        engine = build_engine(tmp_path, "tpi.m", tp=2)
        sched = self._sched(engine)
        s = sched.new_stream()
        decode_tokens(s, PROMPT, 0.0, 0.9, 7, 2)
        assert sched._prefix.pages_in_use() == 2
        s.reset()
        s.prefill(PROMPT)  # hit mid-request: pins held
        assert s.matched_len == 2 * PAGE
        sched.check_prefix()
        s.reset()
        assert all(nd.refs == 0 for nd in sched._prefix._walk())
