"""Paged prefix cache (ISSUE 4): page-granular gather/scatter round-trips
(bf16 and quantized), radix-tree refcount/eviction invariants, bit-parity of
prefix-hit vs cold-prefill streams, Sarathi-style chunked prefill parity,
per-request opt-out, and the API-level repeated-prefix flow."""

import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.engine.prefix_cache import PrefixCache
from distributed_llama_tpu.ops import kv_cache as kvc

from tests.model_utils import random_tensors, tiny_spec, write_model_file

PAGE = 4
PROMPT = [1, 5, 9, 2, 7, 3, 11, 4, 6, 8]  # 10 tokens = 2 full pages + 2


def build_engine(tmp_path, name="model.m", seed=0, seq_len=96, cache_dtype=None):
    spec = tiny_spec(seq_len=seq_len)
    path = str(tmp_path / name)
    write_model_file(path, spec, random_tensors(spec, seed=seed))
    return InferenceEngine(path, dtype=jnp.float32, cache_dtype=cache_dtype)


def decode_tokens(stream, prompt, temp, topp, seed, n, prefix_enabled=None):
    """One request through the fused serving flow on a scheduler row.
    ``prefix_enabled`` overrides the opt-out AFTER the reset (reset restores
    the default True, mirroring the serving layer's per-request scoping)."""
    stream.reset()
    if prefix_enabled is not None:
        stream.prefix_cache_enabled = prefix_enabled
    first = stream.prefill_device(prompt, temp, topp, seed)
    got = []

    def on_token(prev, tok):
        got.append(tok)
        return len(got) < n

    stream.stream_decode(first, on_token, temp, topp, seed=seed,
                         limit=stream.pos + n, first_prev=prompt[-1])
    return got


# ---------------------------------------------------------------------------
# Page-granular kv_cache ops: publish must store the exact row bytes, and the
# zero-copy paged READ (page-table gather + per-position select) must see
# them bit-identically
# ---------------------------------------------------------------------------


class TestPageOps:
    B, S, K, HD, P = 2, 32, 2, 8, 6

    def _roundtrip(self, dtype):
        rng = np.random.RandomState(0)
        slab = kvc.init_half((self.B, self.S, self.K, self.HD), dtype)
        pool = kvc.init_page_pool_half(self.P, PAGE, self.K, self.HD, dtype)
        rows = jnp.asarray(
            rng.randn(self.S, self.K, self.HD).astype(np.float32)
        )
        # fill slab row 1 via the production write path (quantizes for i8)
        if isinstance(slab, kvc.QuantizedKV):
            q, s = kvc.quantize_rows(rows)
            slab = kvc.QuantizedKV(
                slab.data.at[1].set(q), slab.scales.at[1].set(s)
            )
        else:
            slab = slab.at[1].set(rows.astype(slab.dtype))
        reference = (
            (np.asarray(slab.data[1]).copy(), np.asarray(slab.scales[1]).copy())
            if isinstance(slab, kvc.QuantizedKV)
            else np.asarray(slab[1]).copy()
        )

        # publish row 1's first 3 pages into pool pages [4, 2, 0]
        ids = jnp.asarray([4, 2, 0], jnp.int32)
        src = jnp.asarray([0, 1, 2], jnp.int32)
        pool = kvc.publish_row_pages(pool, slab, jnp.int32(1), src, ids, PAGE)

        n = 3 * PAGE
        # the zero-copy page-table read: the published pages, read back
        # through the table, are the row's exact bytes
        read = kvc.gather_pool_pages(pool, ids)
        # and a virtual row view over an EMPTY slab row sees pool bytes
        # below matched and the (zero) slab bytes beyond
        n_table = -(-self.S // PAGE)
        table = jnp.zeros(n_table, jnp.int32).at[:3].set(ids)
        virt = kvc.virtual_row(slab[0], pool, table, jnp.int32(n))
        if isinstance(slab, kvc.QuantizedKV):
            np.testing.assert_array_equal(np.asarray(read.data), reference[0][:n])
            np.testing.assert_array_equal(np.asarray(read.scales), reference[1][:n])
            np.testing.assert_array_equal(np.asarray(virt.data[:n]), reference[0][:n])
            assert not np.asarray(virt.data[n:]).any()  # slab beyond matched
        else:
            np.testing.assert_array_equal(np.asarray(read), reference[:n])
            np.testing.assert_array_equal(np.asarray(virt[:n]), reference[:n])
            assert not np.asarray(virt[n:].astype(jnp.float32)).any()

    def test_roundtrip_bf16(self):
        self._roundtrip(jnp.bfloat16)

    def test_roundtrip_f32(self):
        self._roundtrip(jnp.float32)

    def test_roundtrip_quantized(self):
        self._roundtrip("i8")

    def test_unaligned_seq_len_hit_parity_and_tail_untouched(self, tmp_path):
        """seq_len not a multiple of the page size: the virtual page table
        covers ceil(S/page) entries and clamps its over-gather back to S —
        a prefix hit must stream bit-identically to the cold run, and the
        row's slab tail holds no stray writes (a hit's copy writes the
        chain's own blocks and nothing past them)."""
        spec = tiny_spec(seq_len=90)  # 90 % 4 != 0
        path = str(tmp_path / "unaligned.m")
        write_model_file(path, spec, random_tensors(spec, seed=0))
        engine = InferenceEngine(path, dtype=jnp.float32)
        sched = BatchScheduler(
            engine, n_rows=1, chunk=4, prefix_cache=True, kv_pages=6,
            page_size=PAGE,
        )
        s = sched.new_stream()
        prompt = list(range(1, 15))  # 14 tokens = 3 full pages + 2
        cold = decode_tokens(s, prompt, 0.0, 0.9, 7, 4)
        s.reset()
        tail_before = [
            (np.asarray(leaf[0])[0, 80:].copy(), np.asarray(leaf[1])[0, 80:].copy())
            for leaf in sched._slab
        ]
        hit = decode_tokens(s, prompt, 0.0, 0.9, 7, 4)  # 3 pages copied into the row
        assert hit == cold
        for l, ((kb, vb), leaf) in enumerate(zip(tail_before, sched._slab)):
            np.testing.assert_array_equal(
                np.asarray(leaf[0])[0, 80:], kb, err_msg=f"layer {l} keys tail"
            )
            np.testing.assert_array_equal(
                np.asarray(leaf[1])[0, 80:], vb, err_msg=f"layer {l} values tail"
            )

    def test_padded_entries_drop(self):
        """Out-of-bounds page ids (publish) are the bucket-padding
        contract: they must write NOTHING; out-of-bounds page-table
        entries (the paged read) clamp and are masked by ``matched``."""
        slab = kvc.init_half((self.B, self.S, self.K, self.HD), jnp.float32)
        pool = kvc.init_page_pool_half(self.P, PAGE, self.K, self.HD, jnp.float32)
        pool = pool + 1.0
        slab = slab + 2.0
        got_pool = kvc.publish_row_pages(
            pool, slab, jnp.int32(0),
            jnp.asarray([0, 0], jnp.int32),
            jnp.asarray([self.P, self.P], jnp.int32),  # both padded
            PAGE,
        )
        np.testing.assert_array_equal(np.asarray(got_pool), np.asarray(pool))
        # a virtual view with matched=0 never exposes pool bytes, whatever
        # garbage the (clamped) table gather returns
        n_table = -(-self.S // PAGE)
        virt = kvc.virtual_row(
            slab[0], pool, jnp.full(n_table, 99, jnp.int32), jnp.int32(0)
        )
        np.testing.assert_array_equal(np.asarray(virt), np.asarray(slab[0]))

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, "i8"], ids=["bf16", "i8"])
    @pytest.mark.parametrize(
        "blocks,ids,slots",
        [
            ([0, 2, 5], [4, 2, 0], {}),
            ([2, 3, 4], [1, 5, 3], {"ring": 18}),  # block 4 (slots 16..19) wraps at 18
            ([0, 3, 1], [0, 1, 2], {"base": 16}),  # behind another store, as EVA's summaries
            ([1, 0, 6], [3, P, P + 2], {}),  # padded ids past the pool: dropped
            ([5, 1, 4], [P, 2, 0], {"ring": 18}),
        ],
        ids=["plain", "ring-wraps", "base", "padded-ids", "ring-padded"],
    )
    def test_a_fused_leafs_publish_is_the_halves_publish(self, dtype, blocks, ids, slots):
        """``publish_leaf_pages`` reads half, row and slots out of the fused
        leaf ``[2, B, S, K, hd]`` in one gather a half; what reaches a pool half is bit
        for bit what ``publish_row_pages`` writes from ``leaf[0]`` and
        ``leaf[1]`` and what the layout says by hand, and nothing else of the
        pool changes."""
        rng = np.random.RandomState(3)
        leaf = kvc.init_fused((self.B, self.S, self.K, self.HD), dtype)
        pool = kvc.init_page_pool_half(self.P, PAGE, self.K, self.HD, dtype)
        fill = lambda a: jnp.asarray(rng.randn(*a.shape) * 7).astype(a.dtype)
        leaf, pool = jax.tree.map(fill, (leaf, pool))  # an i8's data and scales alike
        src, page_ids, row = jnp.asarray(blocks, jnp.int32), jnp.asarray(ids, jnp.int32), jnp.int32(1)
        both = kvc.publish_leaf_pages(pool, pool, leaf, row, src, page_ids, PAGE, **slots)
        for half, got in enumerate(both):
            want = kvc.publish_row_pages(pool, leaf[half], row, src, page_ids, PAGE, **slots)
            # data, and an i8's scales
            for g, w, before, a in zip(*map(jax.tree.leaves, (got, want, pool, leaf))):
                expect = np.asarray(before).copy()  # by hand: the blocks' slots of row 1, page by page
                for block, pid in zip(blocks, ids):
                    at = np.arange(block * PAGE, (block + 1) * PAGE)
                    if pid < self.P:
                        at = at % slots["ring"] if "ring" in slots else at
                        expect[pid] = np.asarray(a)[half, 1, at + slots.get("base", 0)]
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
                np.testing.assert_array_equal(np.asarray(g), expect)


# ---------------------------------------------------------------------------
# Radix tree: match/publish/refcount/LRU-eviction invariants (host-only)
# ---------------------------------------------------------------------------


class TestRadixTree:
    def test_match_is_strictly_shorter_than_prompt(self):
        tree = PrefixCache(8, PAGE)
        toks = list(range(1, 9))  # exactly 2 pages
        ids, blocks = tree.publish(toks, len(toks), [])
        assert blocks == [0, 1] and len(ids) == 2
        # a prompt equal to the published chain may match only n-1 blocks:
        # the last token must prefill to produce the sampling logits
        chain = tree.match(toks)
        assert len(chain) == 1
        tree.release(chain)
        # one token beyond the chain matches all of it
        chain = tree.match(toks + [99])
        assert len(chain) == 2
        assert [nd.page_id for nd in chain] == ids
        tree.release(chain)
        tree.check()

    def test_divergent_suffixes_share_prefix_pages(self):
        tree = PrefixCache(8, PAGE)
        a = [1, 2, 3, 4, 5, 6, 7, 8]
        b = [1, 2, 3, 4, 9, 9, 9, 9]
        tree.publish(a, len(a), [])
        chain_b = tree.match(b + [0])
        assert len(chain_b) == 1  # shared first block only
        tree.release(chain_b)
        tree.publish(b, len(b), chain_b)
        assert tree.pages_in_use() == 3  # shared root + two divergent leaves
        tree.check()

    def test_refcounted_pages_survive_eviction_pressure(self):
        tree = PrefixCache(2, PAGE)
        held_toks = [1, 2, 3, 4]
        tree.publish(held_toks, PAGE, [])
        chain = tree.match(held_toks + [9])  # refs the page
        assert len(chain) == 1
        # churn: each publish needs a page; only the unheld one may recycle
        for i in range(4):
            toks = [10 + i] * PAGE
            ids, _ = tree.publish(toks, PAGE, [])
            assert len(ids) <= 1
            tree.check()
        assert tree.match(held_toks + [9])  # held chain still resident
        tree.release(chain)

    def test_publish_stops_when_everything_pinned(self):
        tree = PrefixCache(1, PAGE)
        tree.publish([1] * PAGE, PAGE, [])
        chain = tree.match([1] * PAGE + [2])
        ids, blocks = tree.publish([5] * PAGE, PAGE, [])
        assert ids == [] and blocks == []  # soft failure, no eviction of held
        tree.release(chain)
        ids, blocks = tree.publish([5] * PAGE, PAGE, [])
        assert len(ids) == 1  # released page was LRU-evicted and reused
        tree.check()

    def test_lru_evicts_least_recently_used_leaf_first(self):
        tree = PrefixCache(2, PAGE)
        a, b = [1] * PAGE, [2] * PAGE
        tree.publish(a, PAGE, [])
        tree.publish(b, PAGE, [])
        tree.release(tree.match(a + [0]))  # touch a: b becomes LRU
        tree.publish([3] * PAGE, PAGE, [])  # needs an eviction
        assert tree.match(a + [0])  # a survived
        assert not tree.match(b + [0])  # b was the victim
        tree.check()

    def test_publish_never_evicts_its_own_growing_chain(self):
        """Regression (review finding): with the pool dry mid-publish, the
        evictor must not reclaim the node publish inserted one block
        earlier — the chain is pinned while it grows. A capacity-1 pool
        publishing a 2-block prompt must yield ONE page, a consistent
        tree, and no double-allocated id."""
        tree = PrefixCache(1, PAGE)
        ids, blocks = tree.publish(list(range(8)), 8, [])
        assert ids == [0] and blocks == [0]  # partial publish, no self-evict
        tree.check()
        assert all(nd.refs == 0 for nd in tree._walk())  # pins released
        assert len(tree.match(list(range(8)) + [99])) == 1

    def test_interior_pages_never_evicted_under_leaves(self):
        tree = PrefixCache(3, PAGE)
        chain2 = [1, 2, 3, 4, 5, 6, 7, 8]
        tree.publish(chain2, len(chain2), [])  # root -> leaf chain of 2
        tree.publish([9] * PAGE, PAGE, [])  # third page
        # allocation pressure: the chain's ROOT has a child, so only its
        # leaf or the independent page are candidates
        tree.publish([8] * PAGE, PAGE, [])
        tree.check()
        for node in tree._walk():
            if node.children:
                assert node.page_id not in tree.free


# ---------------------------------------------------------------------------
# Engine-level: prefix-hit streams are bit-identical to cold streams
# ---------------------------------------------------------------------------


class TestPrefixHitParity:
    def _sched(self, engine, **kw):
        kw.setdefault("prefix_cache", True)
        kw.setdefault("kv_pages", 16)
        kw.setdefault("page_size", PAGE)
        return BatchScheduler(engine, n_rows=2, chunk=4, **kw)

    def test_hit_matches_cold_and_uncached_greedy(self, tmp_path, monkeypatch):
        engine = self._engine_pair(tmp_path)
        uncached = BatchScheduler(engine[0], n_rows=1, chunk=4)
        want = decode_tokens(uncached.new_stream(), PROMPT, 0.0, 0.9, 7, 12)

        sched = self._sched(engine[1])
        suffix_lens = []
        orig = sched._dispatch_prefill_chunks
        monkeypatch.setattr(
            sched, "_dispatch_prefill_chunks",
            lambda stream, toks: (suffix_lens.append(toks.shape[0]), orig(stream, toks))[1],
        )
        s0, s1 = sched.new_stream(), sched.new_stream()
        cold = decode_tokens(s0, PROMPT, 0.0, 0.9, 7, 12)
        hit = decode_tokens(s1, PROMPT, 0.0, 0.9, 7, 12)
        assert cold == want  # publishing changed nothing for the cold run
        assert hit == want  # the prefix-hit stream is bit-identical
        # the hit actually skipped the matched pages: 2 full pages of the
        # 10-token prompt were bound from the tree, 2 tokens prefilled
        assert suffix_lens == [len(PROMPT), len(PROMPT) - 2 * PAGE]
        sched._prefix.check()

    def test_hit_matches_cold_sampled_stream(self, tmp_path):
        """Temperature sampling: the per-row PRNG key stream must line up
        exactly across the page gather (positions, not recomputation,
        drive rope/sampling)."""
        engine = build_engine(tmp_path)
        sched = self._sched(engine)
        s0, s1 = sched.new_stream(), sched.new_stream()
        cold = decode_tokens(s0, PROMPT, 0.9, 0.8, 13, 10)
        hit = decode_tokens(s1, PROMPT, 0.9, 0.8, 13, 10)
        assert cold == hit

    def test_hit_parity_quantized_cache(self, tmp_path):
        """i8 slab: published pages carry the quantized data AND scales
        verbatim, so a hit is bit-identical without requantization."""
        engine = build_engine(tmp_path, cache_dtype="i8")
        sched = self._sched(engine)
        s0, s1 = sched.new_stream(), sched.new_stream()
        cold = decode_tokens(s0, PROMPT, 0.0, 0.9, 7, 10)
        hit = decode_tokens(s1, PROMPT, 0.0, 0.9, 7, 10)
        assert cold == hit

    def test_prefix_hit_across_row_reuse(self, tmp_path):
        """Slot recycling: a row reset between requests re-admits at pos 0
        and must hit the prefix its previous occupant published."""
        engine = build_engine(tmp_path)
        sched = self._sched(engine)
        s = sched.new_stream()
        first = decode_tokens(s, PROMPT, 0.0, 0.9, 7, 8)
        again = decode_tokens(s, PROMPT, 0.0, 0.9, 7, 8)
        assert first == again

    def test_quarantine_after_a_hit_frees_no_page_and_the_next_hit_replays(self, tmp_path):
        """The zero-copy aliasing gate: a row corrupted mid-decode AFTER it
        took a prefix hit is quarantined; no page of the tree is freed, the
        dead row's pins are released, and the next request still hits the
        published prefix (counted) and decodes the pre-fault stream."""
        from distributed_llama_tpu import telemetry
        from distributed_llama_tpu.engine import faults

        telemetry.reset()
        telemetry.enable()
        try:
            reg = telemetry.REGISTRY
            sched = self._sched(build_engine(tmp_path))
            s0, s1 = sched.new_stream(), sched.new_stream()
            reference = decode_tokens(s0, PROMPT, 0.0, 0.9, 7, 12)
            pages_before = reg.gauge("dllama_prefix_cache_pages").value
            assert pages_before == sched._prefix.pages_in_use() == 2
            # bind-once: the scheduler predates the plan
            sched._faults = faults.install(
                faults.parse("batch.row:kind=nan,row=1,after=1,count=1", seed=0))
            with pytest.raises(faults.RowQuarantined):
                decode_tokens(s1, PROMPT, 0.0, 0.9, 7, 12)
            faults.clear()
            sched._faults = faults.active_plan()
            assert reg.gauge("dllama_prefix_cache_pages").value == pages_before
            assert not s1._alias_ids and s1.matched_len == 0  # pins released
            sched.check_prefix()  # no page aliased, leaked or freed while read
            hits = reg.counter("dllama_prefix_cache_hits_total").value
            assert hits == 1  # the victim's own hit
            assert decode_tokens(s0, PROMPT, 0.0, 0.9, 7, 12) == reference
            assert reg.counter("dllama_prefix_cache_hits_total").value == hits + 1
        finally:
            faults.clear()
            telemetry.disable()
            telemetry.reset()

    def test_longer_prompt_extends_published_chain(self, tmp_path):
        """A second request whose prompt extends the published prefix
        publishes only the NEW blocks (the radix property)."""
        engine = build_engine(tmp_path)
        sched = self._sched(engine)
        s = sched.new_stream()
        decode_tokens(s, PROMPT, 0.0, 0.9, 7, 4)
        pages_after_first = sched._prefix.pages_in_use()
        assert pages_after_first == 2
        longer = PROMPT + [12, 13, 14, 15, 16]
        decode_tokens(s, longer, 0.0, 0.9, 7, 4)
        # 15 tokens = 3 full pages; 2 were already published
        assert sched._prefix.pages_in_use() == 3
        sched._prefix.check()

    def test_opt_out_neither_matches_nor_publishes(self, tmp_path):
        engine = build_engine(tmp_path)
        sched = self._sched(engine)
        s = sched.new_stream()
        a = decode_tokens(s, PROMPT, 0.0, 0.9, 7, 8, prefix_enabled=False)
        assert sched._prefix.pages_in_use() == 0  # nothing published
        b = decode_tokens(s, PROMPT, 0.0, 0.9, 7, 8)  # cold (tree empty)
        assert a == b
        assert sched._prefix.pages_in_use() == 2

    def _engine_pair(self, tmp_path):
        return (
            build_engine(tmp_path, "ref.m"),
            build_engine(tmp_path, "pfx.m"),
        )

    def test_suffix_prefill_failure_releases_alias_pins(self, tmp_path, monkeypatch):
        """A failed suffix-prefill dispatch after a prefix hit fails the
        request but must unwind the alias bind: the matched chain's
        row-lifetime pins release (pinned pages can never be evicted — the
        budget would silently leak away), the row's position resets, and
        the next request recovers."""
        from distributed_llama_tpu.engine import batch as batch_mod

        engine = build_engine(tmp_path)
        sched = self._sched(engine)
        s = sched.new_stream()
        want = decode_tokens(s, PROMPT, 0.0, 0.9, 7, 8)  # publish the prefix

        def boom(*a, **kw):
            raise RuntimeError("injected paged prefill failure")

        monkeypatch.setattr(batch_mod, "_slab_prefill_single_paged", boom)
        s.reset()
        with pytest.raises(RuntimeError, match="injected paged"):
            s.prefill(PROMPT)
        assert s.matched_len == 0 and not s._alias_ids and s.pos == 0
        assert all(nd.refs == 0 for nd in sched._prefix._walk())
        sched.check_prefix()
        monkeypatch.undo()
        assert decode_tokens(s, PROMPT, 0.0, 0.9, 7, 8) == want  # recovered

    def test_publish_failure_unwinds_tree(self, tmp_path, monkeypatch):
        """A failed publish copy must detach the just-inserted nodes and
        refund their pages — otherwise future matches would gather pages
        whose KV was never written (silent wrong tokens). The request
        itself succeeds: publishing is an optimization."""
        from distributed_llama_tpu.engine import batch as batch_mod

        engine = build_engine(tmp_path)
        sched = self._sched(engine)
        s = sched.new_stream()

        def boom(*a, **kw):
            raise RuntimeError("injected publish failure")

        monkeypatch.setattr(batch_mod, "_publish_pages", boom)
        a = decode_tokens(s, PROMPT, 0.0, 0.9, 7, 8)
        assert sched._prefix.pages_in_use() == 0  # fully unwound
        assert len(sched._prefix.free) == sched._prefix.capacity
        sched._prefix.check()
        monkeypatch.undo()
        b = decode_tokens(s, PROMPT, 0.0, 0.9, 7, 8)  # publishes for real
        c = decode_tokens(s, PROMPT, 0.0, 0.9, 7, 8)  # prefix hit
        assert a == b == c
        assert sched._prefix.pages_in_use() == 2


class TestMisconfiguration:
    def test_bad_pool_sizing_disables_only_the_prefix_cache(self, tmp_path, capsys):
        """Regression (review finding): --kv-pages 0 / a bad page size must
        disable the prefix cache with a warning — NOT raise out of
        BatchScheduler.__init__, where the server's backend-fallback
        handler would silently lose batched decode entirely."""
        engine = build_engine(tmp_path)
        for kw in (
            dict(kv_pages=0),
            dict(page_size=0),
            dict(page_size=1000),  # > seq_len
        ):
            sched = BatchScheduler(
                engine, n_rows=1, chunk=4, prefix_cache=True,
                **{"page_size": PAGE, **kw},
            )
            assert sched._prefix is None
            assert "prefix cache disabled" in capsys.readouterr().out
            # batched decode still works
            s = sched.new_stream()
            assert decode_tokens(s, PROMPT, 0.0, 0.9, 7, 4)

    def test_default_budget_is_slab_plus_headroom(self, tmp_path):
        """A live row pins the pages it matched for its lifetime and the
        pool holds what later prompts resume from, so the default budget is one slab's
        worth of pages plus 25% headroom (at least one row's worth) for
        prefixes outliving their rows."""
        engine = build_engine(tmp_path, seq_len=96)
        sched = BatchScheduler(
            engine, n_rows=2, chunk=4, prefix_cache=True, page_size=PAGE
        )
        slab_pages = 2 * (96 // PAGE)
        assert sched._prefix.capacity == slab_pages + max(
            slab_pages // 4, 96 // PAGE
        )

    def test_undersized_pool_warns_but_stays_enabled(self, tmp_path, capsys):
        engine = build_engine(tmp_path, seq_len=96)
        sched = BatchScheduler(
            engine, n_rows=2, chunk=4, prefix_cache=True, page_size=PAGE,
            kv_pages=8,  # < one slab's worth (48)
        )
        assert sched._prefix is not None and sched._prefix.capacity == 8
        assert "smaller than one slab" in capsys.readouterr().out


class TestChunkedPrefill:
    def test_chunked_prefill_matches_monolithic(self, tmp_path):
        """Sarathi-style chunked prefill (the lock released between chunk
        dispatches) must leave logits and the decoded stream unchanged."""
        e1 = build_engine(tmp_path, "mono.m")
        mono = BatchScheduler(e1, n_rows=1, chunk=4)
        want_logits = mono.new_stream().prefill(PROMPT)

        e2 = build_engine(tmp_path, "chunk.m")
        chunked = BatchScheduler(e2, n_rows=1, chunk=4, prefill_chunk=PAGE)
        s = chunked.new_stream()
        got_logits = s.prefill(PROMPT)
        np.testing.assert_allclose(got_logits, want_logits, rtol=1e-5, atol=1e-5)
        assert s.pos == len(PROMPT)

    def test_chunked_prefill_stream_parity_with_prefix_cache(self, tmp_path):
        engine = build_engine(tmp_path)
        plain = BatchScheduler(engine, n_rows=1, chunk=4)
        want = decode_tokens(plain.new_stream(), PROMPT, 0.0, 0.9, 7, 10)

        engine2 = build_engine(tmp_path, "c2.m")
        sched = BatchScheduler(
            engine2, n_rows=2, chunk=4, prefix_cache=True, kv_pages=16,
            page_size=PAGE, prefill_chunk=PAGE,
        )
        s0, s1 = sched.new_stream(), sched.new_stream()
        assert decode_tokens(s0, PROMPT, 0.0, 0.9, 7, 10) == want
        assert decode_tokens(s1, PROMPT, 0.0, 0.9, 7, 10) == want

    def test_deadline_enforced_between_prefill_chunks(self, tmp_path):
        """An expired request stops dispatching at the next chunk boundary
        instead of prefilling its whole remaining prompt (review finding:
        PR 3 only enforced deadlines pre-prefill and between decode
        chunks)."""
        import time

        from distributed_llama_tpu.engine.faults import DeadlineExceeded

        engine = build_engine(tmp_path, seq_len=96)
        sched = BatchScheduler(engine, n_rows=1, chunk=4, prefill_chunk=PAGE)
        s = sched.new_stream()
        s.deadline = time.monotonic() - 0.001  # already expired
        with pytest.raises(DeadlineExceeded, match="mid-prefill"):
            s.prefill(list(range(1, 33)))
        s.deadline = None
        s.reset()
        assert s.prefill(PROMPT) is not None  # the row keeps serving

    def test_decode_interleaves_between_prefill_chunks(self, tmp_path):
        """The satellite's point: while one row runs a long chunked
        prefill, another row's decode keeps making progress (the scheduler
        lock is released between prefill chunk dispatches)."""
        engine = build_engine(tmp_path, seq_len=96)
        sched = BatchScheduler(engine, n_rows=2, chunk=2, prefill_chunk=PAGE)
        s0, s1 = sched.new_stream(), sched.new_stream()
        long_prompt = list(range(1, 41))  # 40 tokens = 10 prefill chunks
        decoded_during_prefill = []
        prefill_done = threading.Event()
        errors = []

        def decoder():
            try:
                first = s0.prefill_device([1, 5, 9], 0.0, 0.9, 3)

                def on_token(prev, tok):
                    if not prefill_done.is_set():
                        decoded_during_prefill.append(tok)
                    return not prefill_done.is_set()

                s0.stream_decode(first, on_token, 0.0, 0.9, seed=3,
                                 limit=s0.pos + 40, first_prev=9)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        t = threading.Thread(target=decoder)
        t.start()
        try:
            # wait for the decode stream to produce at least one token
            for _ in range(200):
                if decoded_during_prefill:
                    break
                import time

                time.sleep(0.01)
            s1.prefill(long_prompt)
        finally:
            prefill_done.set()
            t.join(timeout=120)
        assert not errors, errors
        assert decoded_during_prefill  # decode ran while prefill chunked


# ---------------------------------------------------------------------------
# API level: repeated-prefix completions + per-request opt-out
# ---------------------------------------------------------------------------


class TestApiPrefixCache:
    def _state(self, tmp_path, name, **overrides):
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
        defaults = dict(
            temperature=0.0, topp=0.9, seed=1, chat_template=None,
            parallel=2, batch_decode=True, decode="device", decode_chunk=4,
            prefix_cache=True, kv_pages=32, kv_page_size=PAGE,
            prefill_chunk=0,
        )
        defaults.update(overrides)
        return ApiState(engine, tokenizer, sampler, types.SimpleNamespace(**defaults))

    def test_repeated_prompt_hits_and_matches(self, tmp_path):
        state = self._state(tmp_path, "rep")
        assert state.batch is not None and state.batch._prefix is not None
        body = {"messages": [{"role": "user", "content": "hello hello hello"}],
                "max_tokens": 6, "temperature": 0.0}
        first = state.complete(dict(body), lambda s: None)
        for slot in state.slots:
            slot.stream.reset()
            slot.cache.clear()
        second = state.complete(dict(body), lambda s: None)
        assert second["choices"][0]["message"]["content"] == \
            first["choices"][0]["message"]["content"]
        assert state.batch._prefix.pages_in_use() > 0

    def test_cache_off_request_skips_publish(self, tmp_path):
        state = self._state(tmp_path, "off")
        body = {"messages": [{"role": "user", "content": "hello hello hello"}],
                "max_tokens": 4, "temperature": 0.0, "cache": "off"}
        out = state.complete(dict(body), lambda s: None)
        assert out["choices"][0]["finish_reason"] in ("stop", "length")
        assert state.batch._prefix.pages_in_use() == 0
        # the opt-out is per-request: the slot re-enables afterwards
        assert all(s.stream.prefix_cache_enabled for s in state.slots)

    def test_explicit_page_size_zero_reaches_the_diagnostic(self, tmp_path, capsys):
        """--kv-page-size 0 must NOT be silently rewritten to the default
        by a falsy-or (the PR 3 admission_queue=0 bug class): the scheduler
        sees it, warns, and disables only the prefix cache."""
        state = self._state(tmp_path, "pz0", kv_page_size=0)
        assert state.batch is not None  # batched decode survived
        assert state.batch._prefix is None
        assert "prefix cache disabled" in capsys.readouterr().out

    def test_invalid_cache_field_is_400(self, tmp_path):
        from distributed_llama_tpu.server.api import BadRequest

        state = self._state(tmp_path, "bad")
        with pytest.raises(BadRequest, match="'cache'"):
            state._parse({"messages": [{"role": "user", "content": "x"}],
                          "cache": "never"})


# ---------------------------------------------------------------------------
# Eviction stress (slow): churn far beyond the HBM budget, assert no leak
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestEvictionStress:
    def test_churn_beyond_budget_leaks_nothing(self, tmp_path):
        from distributed_llama_tpu import telemetry

        telemetry.reset()
        telemetry.enable()
        try:
            engine = build_engine(tmp_path, seq_len=96)
            budget = 6
            sched = BatchScheduler(
                engine, n_rows=2, chunk=4, prefix_cache=True,
                kv_pages=budget, page_size=PAGE,
            )
            s = sched.new_stream()
            rng = np.random.RandomState(3)
            pages_gauge = telemetry.REGISTRY.gauge("dllama_prefix_cache_pages")
            for i in range(30):
                # distinct 2-page prompts: every admission wants 2 fresh pages
                prompt = rng.randint(1, 60, 9).tolist()
                decode_tokens(s, prompt, 0.0, 0.9, i, 2)
                tree = sched._prefix
                tree.check()  # disjoint free/used, no alias, no leak
                assert tree.pages_in_use() <= budget
                assert pages_gauge.value == tree.pages_in_use()
                assert pages_gauge.value + len(tree.free) == budget
            evictions = telemetry.REGISTRY.counter(
                "dllama_prefix_cache_evictions_total"
            ).value
            assert evictions > 0  # the churn actually exercised the evictor
        finally:
            telemetry.disable()
            telemetry.reset()
