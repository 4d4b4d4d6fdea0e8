"""Paged KV residency subsystem (repro.core.paging + engine integration).

Tentpole acceptance properties:

1. **Gather-level bit-exactness**: a ``paged_*`` format's block-table
   gather reproduces the contiguous ring bit-for-bit — identical stores,
   identical qk/av contractions.

2. **Engine equivalence**: serving under ``paged_int4_bp`` produces the
   same greedy token streams as the contiguous ring, for GQA and MLA,
   including slot reuse and ring-wraparound page recycling.

3. **Prefix sharing**: requests sharing a tokenized prompt prefix map the
   leading block-table entries to the same physical pages (refcounted),
   doubling concurrent slot capacity on a fixed page pool, with COW on
   the first divergent write — all without changing any output token.

4. **Dry-run twin**: ``launch.dryrun.analytic_cache_bytes`` derives cache
   bytes from page-table occupancy and matches
   ``ServeEngine.resident_bytes()["cache"]`` byte-exactly on paged (and
   contiguous) configs.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import kvcache, paging
from repro.launch import dryrun
from repro.models import attention
from repro.models import model as model_lib
from repro.serve.engine import ServeEngine
from repro.sharding import partitioning as P

jax.config.update("jax_platform_name", "cpu")

VOCAB = 128


def _setup(arch="qwen3-1.7b"):
    cfg = get_smoke_config(arch).scaled(n_layers=2, vocab_size=VOCAB)
    params = P.materialize(model_lib.specs(cfg, 1), jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# PagePool / RadixPrefixIndex units
# ---------------------------------------------------------------------------


class TestPagePool:
    def test_alloc_release_refcount_cycle(self):
        pool = paging.PagePool(4, 8)
        a = pool.alloc(2)
        assert list(a) == [0, 1] and pool.pages_in_use == 2
        pool.retain(a[0])
        assert pool.shared_pages() == 1
        assert pool.shared_fraction() == pytest.approx(0.5)
        assert pool.release(a) == [1]          # a[0] still index-held
        assert pool.release([a[0]]) == [0]
        assert pool.pages_in_use == 0 and pool.free_count() == 4
        # freed pages recycle LIFO; fresh ids still come out low-first
        b = pool.alloc(3)
        assert pool.refs[b].tolist() == [1, 1, 1]
        assert pool.peak_in_use == 3

    def test_exhaustion_and_bad_refcounts_raise(self):
        pool = paging.PagePool(2, 8)
        pool.alloc(2)
        with pytest.raises(paging.PoolExhausted, match="need 1 pages"):
            pool.alloc(1)
        pool.release([0, 1])
        with pytest.raises(ValueError, match="release of free page"):
            pool.release([0])
        with pytest.raises(ValueError, match="retain of free page"):
            pool.retain([1])

    def test_stats_surface(self):
        pool = paging.PagePool(4, 8)
        pool.alloc(1)
        st = pool.stats()
        assert st["num_pages"] == 4 and st["page_size"] == 8
        assert st["pages_in_use"] == 1 and st["pages_free"] == 3
        for key in ("peak_in_use", "shared_pages", "shared_fraction",
                    "cow_copies", "evictions", "prefix_hits",
                    "prefix_tokens_saved", "total_allocated", "total_freed"):
            assert key in st

    def test_lifetime_alloc_free_totals(self):
        """stats() distinguishes lifetime churn (total_allocated /
        total_freed monotonically increasing) from instantaneous occupancy
        (pages_in_use) and its high-water mark (peak_in_use)."""
        pool = paging.PagePool(8, 4)
        a = pool.alloc(5)
        pool.release(a[:3])
        pool.alloc(2)
        st = pool.stats()
        assert st["total_allocated"] == 7
        assert st["total_freed"] == 3
        assert st["pages_in_use"] == 4
        assert st["peak_in_use"] == 5
        # note_* hooks feed the same lifetime surface
        pool.note_cow()
        pool.note_eviction(2)
        pool.note_prefix_hit(12)
        st = pool.stats()
        assert st["cow_copies"] == 1 and st["evictions"] == 2
        assert st["prefix_hits"] == 1 and st["prefix_tokens_saved"] == 12


class TestRadixPrefixIndex:
    def test_match_returns_longest_page_aligned_prefix(self):
        idx = paging.RadixPrefixIndex(4)
        toks = np.arange(12, dtype=np.int32)
        assert idx.insert(toks, [10, 11, 12]) == [10, 11, 12]
        np.testing.assert_array_equal(idx.match(toks), [10, 11, 12])
        # partial page at the end never matches; diverging chunk stops walk
        np.testing.assert_array_equal(idx.match(toks[:7]), [10])
        other = toks.copy()
        other[5] = 99
        np.testing.assert_array_equal(idx.match(other), [10])
        assert idx.match(np.array([99, 99, 99, 99])).size == 0

    def test_insert_first_writer_wins(self):
        idx = paging.RadixPrefixIndex(4)
        toks = np.arange(8, dtype=np.int32)
        idx.insert(toks, [1, 2])
        # re-insert with different pages: existing chain keeps its pages,
        # only the extension is newly referenced
        assert idx.insert(np.arange(12, dtype=np.int32), [7, 8, 9]) == [9]
        np.testing.assert_array_equal(
            idx.match(np.arange(12, dtype=np.int32)), [1, 2, 9])
        assert idx.size == 3

    def test_evict_lru_leaf_first_with_predicate(self):
        idx = paging.RadixPrefixIndex(4)
        a = np.arange(8, dtype=np.int32)
        b = np.array([50, 51, 52, 53], np.int32)
        idx.insert(a, [1, 2])
        idx.insert(b, [3])
        idx.match(b)  # touch b: a's leaf (page 2) is now LRU
        assert idx.evict_lru() == 2
        # interior chains stay reachable until their leaves go
        np.testing.assert_array_equal(idx.match(a), [1])
        # the predicate skips pages other holders still map
        assert idx.evict_lru(evictable=lambda p: p != 3) == 1
        assert idx.evict_lru(evictable=lambda p: False) is None
        assert idx.evict_lru() == 3 and idx.size == 0


# ---------------------------------------------------------------------------
# PagedCacheFormat: registry + gather-level bit-exactness
# ---------------------------------------------------------------------------


class TestPagedFormat:
    def test_registry_lifts_every_base_format(self):
        names = kvcache.formats()
        for base in paging.PAGED_BASES:
            assert f"paged_{base}" in names
            fmt = kvcache.get_cache_format(f"paged_{base}")
            assert isinstance(fmt, paging.PagedCacheFormat)
            assert fmt.inner.name == base
            assert fmt.suffixes == fmt.inner.suffixes + ("_pages",)
            assert fmt.supports_fused_decode == \
                fmt.inner.supports_fused_decode
        with pytest.raises(ValueError, match="paged_int4_bp"):
            kvcache.get_cache_format("paged_nope")

    def test_slot_capacity_rounds_to_page_multiple(self):
        fmt = kvcache.get_cache_format("paged_bf16")
        page = fmt.page_size
        assert fmt.slot_capacity(page) == page
        assert fmt.slot_capacity(page + 1) == 2 * page
        assert fmt.pages_per_slot(3 * page - 1) == 3
        # contiguous formats keep the identity default
        assert kvcache.get_cache_format("bf16").slot_capacity(13) == 13

    @pytest.mark.parametrize("base",
                             ["bf16", "int8", "int4_bp", "int4_bp_fused"])
    def test_gather_is_bit_exact_vs_contiguous_ring(self, base):
        """Identity block tables + the same append stream ⇒ the paged
        gather and the contiguous ring hold identical bits, and qk/av
        contract to identical results (wraparound overwrites included) —
        also for the fused format's own ``[pages, *lead, 4·Fw, page]``
        pool, whose gather relays it out to the ring layout."""
        inner = kvcache.get_cache_format(base)
        fmt = kvcache.get_cache_format(f"paged_{base}")
        B, L, lead, feat = 2, 2 * fmt.page_size, (2,), 32
        si = inner.init(B, L, lead, feat)
        sp = fmt.init(B, L, lead, feat)
        rng = np.random.default_rng(0)
        b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
        for step, pos0 in enumerate((0, 6, 12, 20)):  # 20·L/16 wraps
            S = 6
            x = jnp.asarray(
                rng.normal(size=(B, S, *lead, feat)).astype(np.float32))
            pos = pos0 * (L // 16) + np.arange(S)
            slots = np.broadcast_to(pos % L, (B, S)).copy()
            if step == 1:
                slots[0, -1] = L  # a dropped (padded) position
            slots = jnp.asarray(slots.astype(np.int32))
            si = inner.append(si, x, b_idx, slots)
            sp = fmt.append(sp, x, b_idx, slots)
        gathered = fmt._gather(sp)
        for sfx in inner.suffixes:
            np.testing.assert_array_equal(
                np.asarray(gathered[sfx]), np.asarray(si[sfx]))
        q = jnp.asarray(
            rng.normal(size=(B, *lead, 4, feat)).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(inner.qk(q, si)), np.asarray(fmt.qk(q, sp)))
        w = jnp.asarray(
            rng.normal(size=(B, *lead, 4, L)).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(inner.av(w, si, feat)),
            np.asarray(fmt.av(w, sp, feat)))


    def test_fused_fill_matches_append_into_a_fresh_pool(self):
        """``paged_int4_bp_fused``'s prefill write (``fill``: rows built
        token-major, then cut into pages) holds the bits ``append`` writes
        into the same fresh pool — left pads and a part-filled page
        included."""
        fmt = kvcache.get_cache_format("paged_int4_bp_fused")
        b, ln, s = 2, 2 * fmt.page_size, 150
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(b, s, 2, 32)), jnp.float32)
        pos = np.arange(s) - np.array([[7], [0]])  # row 0: 7 pads
        slots = jnp.asarray(np.where(pos >= 0, pos % ln, ln), jnp.int32)
        b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
        fresh = fmt.init(b, ln, (2,), 32)
        want = fmt.append(fresh, x, b_idx, slots)
        got = fmt.fill(fresh, x, b_idx, slots)
        for key in want:
            np.testing.assert_array_equal(np.asarray(got[key]),
                                          np.asarray(want[key]))

    def test_fused_append_in_a_pool_larger_than_its_tables(self):
        """``paged_int4_bp_fused`` under a ``page_pool_pages`` above slots
        × pages per slot: the pages a write touches but leaves unchanged
        are written nowhere — not onto a real page past the tables that
        another row writes in the same step."""
        fmt = kvcache.get_cache_format("paged_int4_bp_fused")
        b, ln, lead, feat = 2, 2 * fmt.page_size, (2,), 32
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(b, 32, *lead, feat)), jnp.float32)
        slots = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (b, 32))
        b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
        ident = fmt.init(b, ln, lead, feat)  # pages 0-1 and 2-3
        want = fmt.append(ident, x, b_idx, slots)
        pool = {k: jnp.zeros((8, *a.shape[1:]), a.dtype)
                for k, a in ident.items() if k != "_pages"}
        # row 0 writes page 5, one past the tables' 4 entries
        pool["_pages"] = jnp.asarray([[5, 6], [1, 2]], jnp.int32)
        got = fmt.append(pool, x, b_idx, slots)
        for key in ("", "_scale"):
            g, w = np.asarray(got[key]), np.asarray(want[key])
            np.testing.assert_array_equal(g[[5, 1]], w[[0, 2]])
            assert not g[[0, 2, 3, 4, 6, 7]].any()


#: (query tokens per slot, tokens written per slot, slot 1 shares slot 0's
#: first 128 positions, slot 1's query rows are pads)
PAGE_AWARE_CASES = {
    "noncontiguous_part_filled_page": (1, (200, 77, 256), False, False),
    "shared_prefix_pages": (1, (150, 140, 60), True, False),
    "pad_positions": (4, (130, 20, 90), False, True),
    "chunk_of_32": (32, (160, 32, 250), False, False),
    "slot_with_no_live_page": (1, (100, 0, 129), False, False),
}


def _shuffle_pages(cache, rng, shared_pages):
    """Move a paged cache's pages to random pool rows (tables follow);
    slot 1's first ``shared_pages`` entries then map slot 0's pages."""
    n = cache["k"].shape[0]
    perm = rng.permutation(n)
    out = dict(cache)
    for name in paging.POOL_KEYS & set(cache):
        out[name] = jnp.zeros_like(cache[name]).at[perm].set(cache[name])
    for name in paging.TABLE_KEYS & set(cache):
        table = perm[np.asarray(cache[name])]
        table[1, :shared_pages] = table[0, :shared_pages]
        out[name] = jnp.asarray(table, jnp.int32)
    return out


class TestPageAwareRead:
    @pytest.mark.parametrize("case", sorted(PAGE_AWARE_CASES))
    def test_page_aware_read_matches_gather_and_ring(self, case):
        """The page-aware kernel (``paged_int4_bp_fused``, pool read in
        place) equals the block-table gather + jnp plane math
        (``paged_int4_bp``) and the unpaged fused kernel
        (``int4_bp_fused``) on the same page contents; a query row that
        sees no position reads zeros."""
        s, lengths, shared, pad = PAGE_AWARE_CASES[case]
        cfg, _ = _setup()
        rng = np.random.default_rng(len(case))
        b, ln = len(lengths), 256
        kv = rng.normal(size=(2, b, ln, cfg.n_kv_heads, cfg.d_head))
        if shared:
            kv[:, 1, :128] = kv[:, 0, :128]
        k, v = (jnp.asarray(a, jnp.float32) for a in kv)
        written = np.where(np.arange(ln)[None] < np.array(lengths)[:, None],
                           np.arange(ln)[None], -1).astype(np.int32)
        cur = np.array([np.arange(n - s, n) for n in lengths], np.int32)
        cur = np.where(cur >= 0, cur, -1)
        if pad:
            cur[1, : s // 2] = -1
        q = jnp.asarray(rng.normal(
            size=(b, s, cfg.n_heads, cfg.d_head)).astype(np.float32))
        outs = {}
        for name in ("int4_bp_fused", "paged_int4_bp", "paged_int4_bp_fused"):
            fmt = kvcache.get_cache_format(name)
            c = dataclasses.replace(cfg, cache_format=name)
            cache = attention._ring_write(
                attention.init_kv_cache(c, b, ln), k, v,
                jnp.asarray(written), fmt)
            if isinstance(fmt, paging.PagedCacheFormat):
                cache = _shuffle_pages(cache, rng, 128 // fmt.page_size
                                       if shared else 0)
            outs[name] = np.asarray(attention._decode_attention(
                q, cache, cur=jnp.asarray(cur), window=None, fmt=fmt))
        sees = cur >= 0  # rows with at least position 0 to read
        got = outs["paged_int4_bp_fused"]
        for ref in ("paged_int4_bp", "int4_bp_fused"):
            np.testing.assert_allclose(got[sees], outs[ref][sees],
                                       rtol=1e-4, atol=1e-4)
        assert not got[~sees].any()


# ---------------------------------------------------------------------------
# Engine equivalence: paged vs contiguous serving
# ---------------------------------------------------------------------------


def _serve(params, cfg, *, cache_format, scheduler="fcfs", slots=2,
           max_len=16, prompts=(), max_news=(), **kw):
    eng = ServeEngine(params, cfg, slots=slots, max_len=max_len,
                      cache_format=cache_format, scheduler=scheduler, **kw)
    reqs = [eng.submit(p, mn) for p, mn in zip(prompts, max_news)]
    eng.run()
    return eng, reqs


class TestPagedEngineEquivalence:
    @pytest.mark.parametrize("arch", ["qwen3-1.7b", "minicpm3-4b"])
    def test_paged_decode_matches_contiguous(self, arch):
        """Acceptance: paged int4_bp decode is token-exact vs the
        contiguous ring on a non-shared trace — GQA and MLA, with slot
        reuse (5 requests over 2 slots) and one request decoding past the
        ring length (wraparound page recycling)."""
        cfg, params = _setup(arch)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, VOCAB, size=(n,)).astype(np.int32)
                   for n in (5, 3, 7, 6, 4)]
        max_news = (6, 2, 4, 12, 3)  # 7 + 12 = 19 > max_len 16: wraps
        outs = {}
        for fmt in ("int4_bp", "paged_int4_bp"):
            _, reqs = _serve(params, cfg, cache_format=fmt,
                             prompts=prompts, max_news=max_news)
            outs[fmt] = [r.out for r in reqs]
            assert all(len(o) == mn for o, mn in zip(outs[fmt], max_news))
        assert outs["paged_int4_bp"] == outs["int4_bp"]

    def test_paged_fused_decode_matches_unfused(self):
        cfg, params = _setup()
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, VOCAB, size=(6,)).astype(np.int32)
                   for _ in range(2)]
        outs = {}
        for fmt in ("paged_int4_bp", "paged_int4_bp_fused"):
            _, reqs = _serve(params, cfg, cache_format=fmt,
                             prompts=prompts, max_news=(5, 5))
            outs[fmt] = [r.out for r in reqs]
        assert outs["paged_int4_bp_fused"] == outs["paged_int4_bp"]


class TestPrefixSharing:
    def _shared_prompts(self, n, prefix_len=24, suffix_len=2):
        rng = np.random.default_rng(0)
        prefix = rng.integers(0, VOCAB, size=(prefix_len,)).astype(np.int32)
        return [
            np.concatenate(
                [prefix,
                 rng.integers(0, VOCAB, size=(suffix_len,)).astype(np.int32)])
            for _ in range(n)
        ]

    def test_sharing_doubles_slot_capacity_at_fixed_pool(self):
        """Acceptance: on a shared-prefix trace, 4 slots decode
        concurrently on a page pool sized for 2 private slots — the
        prefix pages are mapped once and refcounted — and every output
        token matches the unpaged engine under the same scheduler."""
        cfg, params = _setup()
        prompts = self._shared_prompts(6)
        max_news = (3,) * len(prompts)
        _, ref = _serve(params, cfg, cache_format="int4_bp",
                        scheduler="prefix_cache", slots=4, max_len=32,
                        prompts=prompts, max_news=max_news)

        eng = ServeEngine(params, cfg, slots=4, max_len=32,
                          cache_format="paged_int4_bp",
                          scheduler="prefix_cache", page_pool_pages=8)
        reqs = [eng.submit(p, mn) for p, mn in zip(prompts, max_news)]
        concurrent_max, shared_max = 0, 0.0
        while eng.step():
            concurrent_max = max(
                concurrent_max, sum(r is not None for r in eng.active))
            shared_max = max(shared_max,
                             eng.page_pool.stats()["shared_fraction"])
        assert [r.out for r in reqs] == [r.out for r in ref]
        # 4 slots × 4 pages/slot would need 16 private pages; sharing fits
        # them in 8 — ≥ 2× concurrent capacity at fixed cache bytes
        assert concurrent_max == 4 and shared_max > 0.3
        st = eng.stats()
        assert st.pages is not None
        assert st.pages["peak_in_use"] <= 8
        assert st.pages["prefix_hits"] >= 3
        assert st.pages["prefix_tokens_saved"] >= 3 * 24
        assert st.pages["cow_copies"] == 0  # nothing wrote a shared page

    def test_cow_fires_on_wraparound_write_into_shared_page(self):
        """Acceptance: decoding past the ring wraps into page 0 — a page
        the prefix index (and a sibling slot) still references.  The write
        must copy first (cow_copies > 0) and outputs stay token-exact vs
        the unpaged engine."""
        cfg, params = _setup()
        # three requests over two slots: the first two co-refill (and
        # register the prefix); the third arrives into a freed slot and
        # ATTACHES to the now-indexed prefix page before wrapping over it
        prompts = self._shared_prompts(3, prefix_len=8, suffix_len=2)
        max_news = (8, 8, 8)  # 10 + 8 = 18 > max_len 16: every slot wraps
        _, ref = _serve(params, cfg, cache_format="int4_bp",
                        scheduler="prefix_cache", slots=2, max_len=16,
                        prompts=prompts, max_news=max_news)
        eng, reqs = _serve(params, cfg, cache_format="paged_int4_bp",
                           scheduler="prefix_cache", slots=2, max_len=16,
                           page_pool_pages=8,
                           prompts=prompts, max_news=max_news)
        assert [r.out for r in reqs] == [r.out for r in ref]
        st = eng.stats()
        assert st.pages["cow_copies"] >= 1
        assert st.pages["prefix_hits"] >= 1

    def test_prefix_ending_inside_a_page_is_shared(self):
        """Pages of 128 positions under ``paged_int4_bp_fused``: a request
        registers its prompt when its prefill ends, part-filled page
        included; requests arriving while it still decodes (into that
        page) share its first 24 tokens, copy the page the prefix ends
        inside at attach, and every token matches the unpaged engine."""
        cfg, params = _setup()
        prompts = self._shared_prompts(3, prefix_len=24, suffix_len=8)
        _, ref = _serve(params, cfg, cache_format="int4_bp_fused",
                        scheduler="prefix_cache", slots=3, max_len=64,
                        prompts=prompts, max_news=(6, 3, 3))
        eng = ServeEngine(params, cfg, slots=3, max_len=64,
                          cache_format="paged_int4_bp_fused",
                          scheduler="prefix_cache")
        first = eng.submit(prompts[0], 6)
        while first.state != "decoding":
            eng.step()
        eng.step()  # it writes past its prompt into the registered page
        rest = [eng.submit(p, 3) for p in prompts[1:]]
        eng.step()
        assert first.state == "decoding"  # both attached while it runs
        eng.run()
        assert [r.out for r in [first, *rest]] == [r.out for r in ref]
        st = eng.stats().pages
        assert st["prefix_hits"] == 2 and st["prefix_tokens_saved"] == 48
        assert st["cow_copies"] == 2  # each attach copies the shared page

    def test_wrap_copies_a_registered_part_filled_page(self):
        """A request goes on writing past its prompt into its registered
        part-filled page (``paged_int4_bp_fused``, one 128-position page
        per slot) until its ring wraps: the wrap write copies the page
        first, so a later request sharing the prompt still reads it."""
        cfg, params = _setup()
        prompts = self._shared_prompts(2, prefix_len=24, suffix_len=8)
        max_news = (110, 3)  # 32 + 110 > 128: the first request wraps
        _, ref = _serve(params, cfg, cache_format="int4_bp_fused",
                        scheduler="prefix_cache", slots=1, max_len=128,
                        prompts=prompts, max_news=max_news)
        eng, reqs = _serve(params, cfg, cache_format="paged_int4_bp_fused",
                           scheduler="prefix_cache", slots=1, max_len=128,
                           page_pool_pages=3, prompts=prompts,
                           max_news=max_news)
        assert [r.out for r in reqs] == [r.out for r in ref]
        st = eng.stats().pages
        assert st["prefix_hits"] == 1 and st["prefix_tokens_saved"] == 24
        assert st["cow_copies"] == 2  # the wrap, then the attach

    def test_pool_too_small_for_one_request_raises(self):
        cfg, params = _setup()
        eng = ServeEngine(params, cfg, slots=1, max_len=32,
                          cache_format="paged_bf16",
                          scheduler="prefix_cache", page_pool_pages=2)
        eng.submit(np.arange(10, dtype=np.int32), 2)
        with pytest.raises(paging.PoolExhausted):
            eng.run()

    def test_view_and_stats_expose_page_telemetry(self):
        cfg, params = _setup()
        eng, _ = _serve(params, cfg, cache_format="paged_int8",
                        prompts=[np.arange(5, dtype=np.int32)],
                        max_news=(2,))
        assert eng.stats().pages["pages_in_use"] >= 0
        # contiguous configs surface None, not a dict of zeros
        eng2, _ = _serve(params, cfg, cache_format="int8",
                         prompts=[np.arange(5, dtype=np.int32)],
                         max_news=(2,))
        assert eng2.stats().pages is None


# ---------------------------------------------------------------------------
# Dry-run twin: analytic bytes == live engine bytes
# ---------------------------------------------------------------------------


class TestAnalyticCacheBytes:
    CASES = [
        ("qwen3-1.7b", "bf16"), ("qwen3-1.7b", "int8"),
        ("qwen3-1.7b", "paged_bf16"), ("qwen3-1.7b", "paged_int4_bp"),
        ("minicpm3-4b", "int4_bp"), ("minicpm3-4b", "paged_int8"),
        ("minicpm3-4b", "paged_int4_bp"),
    ]

    @pytest.mark.parametrize("arch,fmt", CASES)
    def test_byte_exact_vs_live_engine(self, arch, fmt):
        """Acceptance: the dry-run's closed-form cache bytes derive from
        page-table occupancy (whole pages + block tables for paged
        formats) and match the live engine byte-exactly — max_len 20 is
        deliberately NOT a page multiple, so the page-rounded ring is
        exercised."""
        cfg, params = _setup(arch)
        eng, _ = _serve(params, cfg, cache_format=fmt, slots=2, max_len=20,
                        prompts=[np.arange(5, dtype=np.int32)],
                        max_news=(2,))
        got = eng.resident_bytes()["cache"]
        assert got > 0
        assert dryrun.analytic_cache_bytes(eng.cfg, 2, 20) == got

    def test_non_attention_layers_rejected(self):
        cfg = get_smoke_config("falcon-mamba-7b")
        with pytest.raises(NotImplementedError, match="attention"):
            dryrun.analytic_cache_bytes(cfg, 2, 16)
