"""A prefix hit's pages are copied into the row once (ISSUE 40): the row's
slab below ``matched`` is a cold prefill's, byte for byte; the programs are
handed an empty read alias for it, so decode, verify and the suffix prefill
read ONE source; hit and cold streams are bit-identical (bf16, f32 and i8
caches); the copy touches nothing but the chain's blocks of its row; pins and
``matched_len`` are what they were; the tp backend still aliases; and every
shape of the copy is built at construction."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.engine import batch
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.models import sampling
from distributed_llama_tpu.ops import kv_cache as kvc

from tests.test_paged_attention import PAGE, PROMPT, build_engine, decode_tokens

CACHES = [None, jnp.bfloat16, "i8"]  # None: the engine's f32
CACHE_IDS = ["f32", "bf16", "i8"]


def _sched(engine, n_rows=2, **kw):
    kw.setdefault("prefix_cache", True)
    kw.setdefault("kv_pages", 16)
    kw.setdefault("page_size", PAGE)
    return BatchScheduler(engine, n_rows=n_rows, chunk=4, **kw)


def _arrays(leaf):
    """A slab leaf's arrays on the host: one, or data and scales for i8."""
    return [np.asarray(a) for a in jax.tree.leaves(leaf)]


# ---------------------------------------------------------------------------
# The copy itself (ops.kv_cache.restore_row_blocks, engine.batch._restore_pages)
# ---------------------------------------------------------------------------


class TestTheCopy:
    B, S, K, HD, P, PG = 3, 48, 2, 8, 12, 4

    def _slab_and_pool(self, dtype, layers=2):
        rng = np.random.RandomState(3)

        def rand(shape):
            if kvc.is_quantized_cache_dtype(dtype):
                return kvc.QuantizedKV(
                    jnp.asarray(rng.randint(-127, 128, shape).astype(np.int8)),
                    jnp.asarray(rng.rand(*shape[:-1], 1).astype(np.float32)),
                )
            return jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dtype)

        slab = [rand((2, self.B, self.S, self.K, self.HD)) for _ in range(layers)]
        pool = [
            (rand((self.P, self.PG, self.K, self.HD)), rand((self.P, self.PG, self.K, self.HD)))
            for _ in range(layers)
        ]
        return slab, pool

    @pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 11, 12])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, "i8"], ids=CACHE_IDS)
    def test_only_the_chains_blocks_of_its_row_change(self, dtype, n):
        """Blocks 0 .. n - 1 of the row hold the chain's pages, every other
        byte of the slab is what it was: no entry of any shape is padding
        (n = 3, 5, 11: the two runs overlap; 1, 2, 8: they coincide; 12: the
        whole row)."""
        slab, pool = self._slab_and_pool(dtype)
        before = [_arrays(leaf) for leaf in slab]
        chain = np.random.RandomState(n).permutation(self.P)[:n].tolist()
        row = 1
        ids, second = BatchScheduler._restore_runs(chain)
        assert ids.shape[1] <= n < 2 * ids.shape[1] and second == n - ids.shape[1]
        after = batch._restore_pages(slab, pool, ids, second, np.int32(row))
        for l, leaf in enumerate(after):
            pk, pv = (_arrays(h) for h in pool[l])
            for a, (was, k, v) in enumerate(zip(before[l], pk, pv)):
                got = _arrays(leaf)[a]
                want = was.copy()
                pages = np.stack([k[chain], v[chain]])  # [2, n, page, K, x]
                want[:, row, : n * self.PG] = pages.reshape((2, n * self.PG) + pages.shape[3:])
                np.testing.assert_array_equal(got, want)

    def test_a_layer_without_a_pool_half_keeps_its_leaf(self):
        """A linear layer (or a window layer: its pages are in another pool)
        has no half in the pool: its leaf comes back as it went in."""
        slab, pool = self._slab_and_pool(jnp.float32, layers=1)
        state = {"s": jnp.full((self.B, 4, 4), 3.0), "conv": jnp.full((self.B, 3, 8), 5.0)}
        want = np.asarray(slab[0]).copy()
        k, v = np.asarray(pool[0][0]), np.asarray(pool[0][1])
        chain = [4, 7, 2]
        want[:, 2, : 3 * self.PG] = np.stack([k[chain], v[chain]]).reshape(2, -1, self.K, self.HD)
        out = batch._restore_pages(
            [slab[0], state], [pool[0], None], *BatchScheduler._restore_runs(chain), np.int32(2)
        )
        np.testing.assert_array_equal(np.asarray(out[0]), want)
        np.testing.assert_array_equal(np.asarray(out[1]["s"]), np.full((self.B, 4, 4), 3.0))
        np.testing.assert_array_equal(np.asarray(out[1]["conv"]), np.full((self.B, 3, 8), 5.0))

    @pytest.mark.parametrize("n", range(1, 20))
    def test_the_two_runs_cover_the_chain_and_nothing_else(self, n):
        chain = list(range(100, 100 + n))
        ids, second = BatchScheduler._restore_runs(chain)
        h = ids.shape[1]
        assert h & (h - 1) == 0 and h <= n < 2 * h
        placed = {}
        for run, first in ((ids[0], 0), (ids[1], int(second))):
            for i, pid in enumerate(run):
                assert placed.setdefault(first + i, pid) == pid  # an overlap rewrites its own page
        assert placed == {b: 100 + b for b in range(n)}


# ---------------------------------------------------------------------------
# Through the scheduler: the row after a hit is a cold prefill's
# ---------------------------------------------------------------------------


class TestRestoredRow:
    @pytest.mark.parametrize("cache_dtype", CACHES, ids=CACHE_IDS)
    def test_slab_bytes_below_matched_equal_a_cold_prefills(self, tmp_path, cache_dtype):
        engine = build_engine(tmp_path, cache_dtype=cache_dtype)
        sched = _sched(engine)
        s0, s1 = sched.new_stream(), sched.new_stream()
        s0.prefill(PROMPT)  # cold, publishes 2 pages
        s1.prefill(PROMPT)  # hit
        matched = s1.matched_len
        assert matched == 2 * PAGE and s0.matched_len == 0
        for leaf in sched._slab:
            for a in _arrays(leaf):
                np.testing.assert_array_equal(a[:, 1, :matched], a[:, 0, :matched])

    def test_pins_and_matched_len_are_what_they_were(self, tmp_path):
        engine = build_engine(tmp_path)
        sched = _sched(engine)
        s = sched.new_stream()
        decode_tokens(s, PROMPT, 0.0, 0.9, 7, 2)
        s.reset()
        s.prefill(PROMPT)
        assert s.matched_len == 2 * PAGE and len(s._alias_ids) == 2 and len(s._alias_chain) == 2
        assert s._alias_ids == [nd.page_id for nd in s._alias_chain]
        assert sum(nd.refs for nd in sched._prefix._walk()) == 2  # pinned for the row's lifetime
        sched.check_prefix()
        s.reset()
        assert s.matched_len == 0 and not s._alias_ids
        assert all(nd.refs == 0 for nd in sched._prefix._walk())

    def test_the_programs_are_handed_an_empty_read_alias(self, tmp_path, monkeypatch):
        """The hit's suffix prefill gets a zero table and ``matched`` 0 for
        the restored row and every decode chunk is the program WITHOUT pages
        (an empty alias reads what no alias reads, and a scan that is handed
        no pages may bound each row's reads by the row), while
        ``matched_len`` says what the cache saved."""
        seen = {"prefill": [], "decode": 0}
        prefill, decode = batch._slab_prefill_single_paged, sampling.decode_chunk_batched

        def spy_prefill(*args):
            seen["prefill"].append((np.asarray(args[-2]), int(args[-1])))
            return prefill(*args)

        def spy_decode(*args):
            seen["decode"] += 1
            return decode(*args)

        def no_pages(*args):
            raise AssertionError("a decode chunk of restored rows was handed pages")

        monkeypatch.setattr(batch, "_slab_prefill_single_paged", spy_prefill)
        monkeypatch.setattr(sampling, "decode_chunk_batched", spy_decode)
        monkeypatch.setattr(sampling, "decode_chunk_batched_paged", no_pages)
        engine = build_engine(tmp_path)
        sched = _sched(engine)
        assert sched._hit_restores
        s = sched.new_stream()
        decode_tokens(s, PROMPT, 0.0, 0.9, 7, 2)
        seen["prefill"].clear()
        seen["decode"] = 0
        decode_tokens(s, PROMPT, 0.0, 0.9, 7, 6)  # the hit
        assert s.matched_len == 2 * PAGE
        assert seen["prefill"] and seen["decode"]
        for table, matched in seen["prefill"]:
            assert matched == 0 and not table.any()
        with sched._cond:
            table, matched = sched._alias_row_arrays_locked(s)
        assert int(matched) == 0 and not np.asarray(table).any()

    def test_every_shape_of_the_copy_is_built_at_construction(self, tmp_path):
        """One program a power of two up to a row's whole pages, all traced
        when the scheduler is made: the first hit builds nothing."""
        engine = build_engine(tmp_path, seq_len=88)  # 22 pages: shapes 1 .. 16
        before = batch._restore_pages._cache_size()
        sched = _sched(engine, kv_pages=32)
        built = batch._restore_pages._cache_size()
        assert built - before == 5
        s = sched.new_stream()
        long_prompt = np.random.RandomState(2).randint(1, 60, 75).tolist()  # 18 pages: 16 + 16
        for prompt in (PROMPT, long_prompt):  # cold: publish both
            decode_tokens(s, prompt, 0.0, 0.9, 7, 2)
        for prompt in (PROMPT, long_prompt, long_prompt[:30]):
            decode_tokens(s, prompt, 0.0, 0.9, 7, 2)
            assert s.matched_len > 0
        assert batch._restore_pages._cache_size() == built

    def test_the_kernel_path_counter_says_slab_restored(self, tmp_path):
        from distributed_llama_tpu import telemetry

        telemetry.reset()
        telemetry.enable()
        try:
            engine = build_engine(tmp_path, seq_len=72)  # shapes no other test builds
            _sched(engine, kv_pages=24)
            ctr = telemetry.REGISTRY.counter(
                "dllama_kernel_path_total", labelnames=("kernel", "path"))
            assert ctr.labels(kernel="paged_attention", path="slab_restored").value == 5
        finally:
            telemetry.disable()
            telemetry.reset()


# ---------------------------------------------------------------------------
# Hit versus cold, bit for bit, on the blocked production shape
# ---------------------------------------------------------------------------


class TestHitVersusCold:
    SEQ, PG = 1024, 64  # ATT_CHUNK 512 divides: decode takes the segmented scan

    @staticmethod
    def _greedy(stream, logits, prev, n):
        """``n`` greedy tokens of a row whose prompt's last logits are
        ``logits``: what the request would stream."""
        got = []
        stream.stream_decode(int(np.argmax(logits)), lambda p, tok: got.append(tok) or len(got) < n,
                             0.0, 0.9, seed=7, limit=stream.pos + n, first_prev=prev)
        return got

    @pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, "i8"], ids=["bf16", "i8"])
    def test_suffix_logits_and_32_tokens_are_bit_identical(self, tmp_path, cache_dtype):
        """Row 0 prefills three pages cold and then the suffix as a
        continuation; row 1 is sent the whole prompt, matches the three
        pages, has them copied in (two overlapping runs) and prefills the same
        suffix at the same position: the same program over the same bytes, so
        the logits and the 32 tokens after them are equal bit for bit."""
        engine = build_engine(tmp_path, seq_len=self.SEQ, cache_dtype=cache_dtype)
        sched = _sched(engine, kv_pages=8, page_size=self.PG)
        s0, s1 = sched.new_stream(), sched.new_stream()
        prompt = np.random.RandomState(5).randint(1, 60, 3 * self.PG + 9).tolist()
        s0.prefill(prompt[: 3 * self.PG])  # cold, publishes its 3 pages
        cold_logits = s0.prefill(prompt[3 * self.PG:])
        hit_logits = s1.prefill(prompt)
        assert s1.matched_len == 3 * self.PG and s0.matched_len == 0
        np.testing.assert_array_equal(hit_logits, cold_logits)
        cold = self._greedy(s0, cold_logits, prompt[-1], 32)
        hit = self._greedy(s1, hit_logits, prompt[-1], 32)
        assert len(hit) == 32 and hit == cold
        sched.check_prefix()

    @pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, "i8"], ids=["bf16", "i8"])
    def test_a_bucket_of_a_hit_a_cold_and_a_padding_row(self, tmp_path, cache_dtype):
        """Rows 0–2 of a 4-row slab decode together (bucket 4, row 3 is
        padding): the hit row, co-batched with two cold ones, streams what it
        streams alone, and so do they."""
        rng = np.random.RandomState(9)
        prompts = [rng.randint(1, 60, n).tolist() for n in (self.PG + 5, 2 * self.PG + 3, 40)]
        solo = BatchScheduler(
            build_engine(tmp_path, "solo.m", seq_len=self.SEQ, cache_dtype=cache_dtype),
            n_rows=1, chunk=4,
        )
        lane = solo.new_stream()
        want = [decode_tokens(lane, p, 0.0, 0.9, 11 + i, 16) for i, p in enumerate(prompts)]

        engine = build_engine(tmp_path, "mixed.m", seq_len=self.SEQ, cache_dtype=cache_dtype)
        sched = _sched(engine, n_rows=4, kv_pages=8, page_size=self.PG)
        streams = [sched.new_stream() for _ in range(4)]  # row 3 never joins
        decode_tokens(streams[0], prompts[1], 0.0, 0.9, 12, 2)  # publish row 1's prompt
        streams[0].reset()
        buckets = []
        arrays = sched._alias_arrays_locked

        def spy(rows, live):
            buckets.append((len(rows), int(live.sum())))
            out = arrays(rows, live)
            assert not out[0].any() and not out[1].any()
            return out

        sched._alias_arrays_locked = spy
        got, errors = [None] * 3, []

        def run(i):
            try:
                got[i] = decode_tokens(streams[i], prompts[i], 0.0, 0.9, 11 + i, 16)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        assert streams[1].matched_len == 2 * self.PG and streams[0].matched_len == 0
        assert got == want
        assert (4, 3) in buckets, buckets  # three live rows and a padding row in one dispatch
        sched.check_prefix()

    def test_rollback_below_matched_then_a_continuation(self, tmp_path):
        """Hit, roll back below the matched prefix (mid-page), prefill a
        divergent suffix: the restored bytes below the rollback point are
        still the row's, the alias shrinks as it did, and the stream is a
        cold scheduler's over the same final tokens."""
        shared, divergent = PROMPT[:6], [21, 22, 23, 24]
        cold = BatchScheduler(build_engine(tmp_path, "cold.m"), n_rows=1, chunk=4)
        want = decode_tokens(cold.new_stream(), shared + divergent, 0.0, 0.9, 7, 8)

        sched = _sched(build_engine(tmp_path, "roll.m"))
        s = sched.new_stream()
        decode_tokens(s, PROMPT, 0.0, 0.9, 7, 2)
        s.reset()
        s.fetch_first_token(s.prefill_device(PROMPT, 0.0, 0.9, 7))
        assert s.matched_len == 2 * PAGE
        s.rollback(len(shared))
        assert s.matched_len == 6 and len(s._alias_ids) == 2
        first = s.prefill_device(divergent, 0.0, 0.9, 7)
        got = []
        s.stream_decode(first, lambda prev, tok: got.append(tok) or len(got) < 8, 0.0, 0.9,
                        seed=7, limit=s.pos + 8, first_prev=divergent[-1])
        assert got == want
        sched.check_prefix()


# ---------------------------------------------------------------------------
# The tp backend keeps the read alias
# ---------------------------------------------------------------------------


class TestTensorParallelStillAliases:
    def test_tp_rows_read_their_pages_in_place(self, tmp_path):
        from distributed_llama_tpu import telemetry

        telemetry.reset()
        telemetry.enable()
        try:
            engine = build_engine(tmp_path, "tp.m", tp=2)
            sched = _sched(engine)
            assert not sched._hit_restores
            s = sched.new_stream()
            decode_tokens(s, PROMPT, 0.0, 0.9, 7, 2)
            s.reset()
            s.prefill(PROMPT)
            assert s.matched_len == 2 * PAGE
            with sched._cond:
                table, matched = sched._alias_row_arrays_locked(s)
                tables, lens = sched._alias_arrays_locked([s], np.array([True]))
            assert int(matched) == 2 * PAGE and list(np.asarray(table)[:2]) == s._alias_ids
            assert lens[0] == 2 * PAGE and list(tables[0, :2]) == s._alias_ids
            reg = telemetry.REGISTRY
            assert reg.counter("dllama_prefix_cache_restores_total").value == 0
            assert reg.counter("dllama_prefix_cache_restored_bytes_total").value == 0
        finally:
            telemetry.disable()
            telemetry.reset()
