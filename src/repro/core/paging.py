"""Paged KV residency: a page-pool subsystem under the cache registry.

The paper's §V argument — allocation as a first-class, placement-aware API
is where systems win after the kernels are fused — applied to the decode
cache.  Under contiguous ring slots every request owns ``max_len`` worth of
HBM even when thousands of requests share a system prompt; this module
breaks the slot→storage identity so physical residency is governed by a
**page pool**, the fourth load-bearing registry concept after weights
(:mod:`repro.core.residency`), caches (:mod:`repro.core.kvcache`) and
schedulers (:mod:`repro.serve.scheduler`).

Three pieces:

* :class:`PagedCacheFormat` — a registered :class:`~repro.core.kvcache.
  CacheFormat` adapter that lifts an inner format (``bf16``, ``int8``,
  bit-plane ``int4_bp``) from ``[B, L, ...]`` ring slots onto a page pool:
  payload/scale arrays become ``[num_pages, page_size, ...]`` (per-page
  quantization scales come for free — the inner format's per-slot scales
  ARE per-page rows now) plus a ``[B, pages_per_slot]`` int32 **block
  table** per channel.  ``append`` translates ring offsets to ``(physical
  page, in-page offset)`` scatters through the table; ``qk``/``av``/
  ``decode_attention`` gather the table back to the contiguous layout and
  delegate to the inner format — so scores are **bit-exact** with the ring
  cache whenever page contents match.

* :class:`PagedPlaneCacheFormat` — ``paged_int4_bp_fused``: the bit-plane
  pool in the fused decode kernel's own lane-dense layout, 128-position
  pages, read in place through the block table (no gather).

* :class:`PagePool` — the host-side physical allocator: refcounts,
  LIFO free list, COW/eviction/prefix-hit telemetry.  Pure numpy; the
  device arrays never resize (JAX pools are preallocated), the pool decides
  which rows are live, shared, or free.

* :class:`RadixPrefixIndex` — a radix tree over token chunks of the
  format's ``prefix_unit`` mapping tokenized prompt prefixes to physical
  pages.  The serving engine registers a request's whole prompt chunks
  after prefill and maps matching leading block-table entries of later
  requests onto the same physical pages (refcounted; copy-on-write on the
  first divergent append: a suffix that starts inside a shared page, or
  under ring recycling the wrap write into a shared page).  Eviction is least-recently-matched leaf first, exposed as
  scheduler data through the pool stats in
  :class:`~repro.serve.scheduler.EngineView`.

Registered names are ``paged_<inner>`` (``paged_bf16``, ``paged_int8``,
``paged_int4_bp``, ``paged_int4_bp_fused``): ``ServeEngine(cache_format=
"paged_int4_bp")``, the dry-run byte accounting, the cache PartitionSpecs
and the benchmark ladders all pick them up through the registry with no
call-site edits.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kvcache
from repro.obs import trace as obs

#: flat-cache keys that live in the page pool under a paged format
#: (payloads + per-page scales; leading dims [num_pages, page_size])
POOL_KEYS = frozenset({"k", "v", "c_kv", "k_scale", "v_scale", "c_scale"})
#: flat-cache keys holding [B, pages_per_slot] int32 block tables
TABLE_KEYS = frozenset({"k_pages", "v_pages", "c_kv_pages"})


class PoolExhausted(RuntimeError):
    """The page pool cannot satisfy an allocation even after eviction."""


# ---------------------------------------------------------------------------
# PagedCacheFormat — the registry adapter
# ---------------------------------------------------------------------------


class PagedCacheFormat(kvcache.CacheFormat):
    """Lift an inner :class:`~repro.core.kvcache.CacheFormat` onto pages.

    Storage per channel (``suffixes = inner.suffixes + ("_pages",)``):

    ``""``/``"_scale"``  the inner format's layout with ``batch →
                         num_pages`` and ``cache_len → page_size`` — i.e.
                         ``inner.init(num_pages, page_size, lead, feat)``.
                         One pool row is one page; per-slot scales become
                         per-page scales with no layout change.
    ``"_pages"``         ``[B, pages_per_slot]`` int32 block table; entry
                         ``j`` is the physical pool row backing ring slots
                         ``[j·page_size, (j+1)·page_size)``.  ``init``
                         starts identity (slot ``b`` owns rows ``b·npp …``)
                         so a standalone paged cache behaves exactly like a
                         ring; the serving engine rewrites tables for
                         dynamic allocation, prefix sharing and COW.

    The ring length is rounded up to a page multiple
    (:meth:`slot_capacity`), so gathered storage and the format-independent
    ``pos_ids`` stay congruent; ring semantics (slot = pos mod L) are
    otherwise unchanged, which is what makes paged vs contiguous decode
    bit-exact at the gather level.
    """

    #: tokens per page (power of two keeps slot→page arithmetic shift/mask)
    page_size: int = 8
    #: a shared prefix may end at any multiple of this many tokens (capped
    #: at the page size); a suffix that then starts inside a shared page
    #: copies it first (copy-on-write)
    prefix_unit: int = 8

    def __init__(self, inner: kvcache.CacheFormat,
                 page_size: Optional[int] = None,
                 name: Optional[str] = None):
        if page_size is not None:
            self.page_size = int(page_size)
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.prefix_unit = min(self.prefix_unit, self.page_size)
        self.inner = inner
        self.name = name or f"paged_{inner.name}"
        self.is_bitplane = inner.is_bitplane
        self.suffixes = tuple(inner.suffixes) + ("_pages",)
        self.supports_fused_decode = inner.supports_fused_decode
        self.kernel_policy = inner.kernel_policy

    # -- page geometry ---------------------------------------------------
    def pages_per_slot(self, cache_len: int) -> int:
        return -(-int(cache_len) // self.page_size)

    def slot_capacity(self, cache_len: int) -> int:
        """Ring length rounded up to a whole number of pages."""
        return self.pages_per_slot(cache_len) * self.page_size

    # -- storage lifecycle ----------------------------------------------
    def init(self, batch, cache_len, lead, feat, dtype=jnp.bfloat16):
        npp = self.pages_per_slot(cache_len)
        store = self.inner.init(batch * npp, self.page_size, lead, feat,
                                dtype=dtype)
        store["_pages"] = jnp.arange(
            batch * npp, dtype=jnp.int32).reshape(batch, npp)
        return store

    def append(self, store, x, b_idx, slots):
        del b_idx  # the block table row IS the batch index
        table = store["_pages"]  # [B, npp]
        npp = table.shape[1]
        ln = npp * self.page_size
        # ring slot → (page slot, in-page offset); dropped writes (slot ==
        # ring length, i.e. negative/padded positions) redirect to offset ==
        # page_size, which the inner format's mode="drop" scatters discard.
        dropped = slots >= ln
        page_slot = jnp.minimum(slots // self.page_size, npp - 1)
        offset = jnp.where(dropped, self.page_size,
                           slots % self.page_size).astype(slots.dtype)
        phys = jnp.take_along_axis(table, page_slot, axis=1)  # [B, S]
        out = dict(self.inner.append(
            {sfx: store[sfx] for sfx in self.inner.suffixes},
            x, phys, offset,
        ))
        out["_pages"] = table
        return out

    def _gather(self, store) -> dict:
        """Block-table gather back to the contiguous ``[B, L, ...]`` layout
        the inner format reads — identical page contents ⇒ identical bits."""
        table = store["_pages"]
        b, npp = table.shape
        out = {}
        for sfx in self.inner.suffixes:
            a = store[sfx][table]  # [B, npp, page, *rest]
            out[sfx] = a.reshape(b, npp * self.page_size, *a.shape[3:])
        return out

    # -- reads: gather + delegate ---------------------------------------
    def qk(self, q, store):
        return self.inner.qk(q, self._gather(store))

    def av(self, w, store, feat):
        return self.inner.av(w, self._gather(store), feat)

    def decode_attention(self, q, k_store, v_store, pos_ids, q_pos, *,
                         window, sm_scale, feat):
        return self.inner.decode_attention(
            q, self._gather(k_store), self._gather(v_store), pos_ids, q_pos,
            window=window, sm_scale=sm_scale, feat=feat,
        )

    def pages_read(self, max_pos, pages_per_slot: int):
        """Pages one decode read touches per slot whose latest query
        position is ``max_pos``: the block-table gather reads them all."""
        return np.full_like(max_pos, pages_per_slot)

    # -- dry-run twin ----------------------------------------------------
    def abstract_state(self, batch, cache_len, lead, feat,
                       dtype=jnp.bfloat16):
        npp = self.pages_per_slot(cache_len)
        ab = self.inner.abstract_state(batch * npp, self.page_size, lead,
                                       feat, dtype=dtype)
        ab["_pages"] = jax.ShapeDtypeStruct((batch, npp), jnp.int32)
        return ab

    # -- sharding --------------------------------------------------------
    def data_axes(self, lead_axes):
        axes = dict(self.inner.data_axes(lead_axes))
        axes["_pages"] = ()
        return axes

    def flat_cache_axes(self, prefix, lead_axes):
        """Paged PartitionSpecs derive from the wrapped format's
        ``data_axes``: pool leaves are ``(pages → the kv_seq rule,
        in-page offset unsharded) + inner payload axes`` (lead axes — e.g.
        ``kv_heads_cache`` → model — shard exactly as unpaged); block
        tables follow the batch axis."""
        data_key, _ = kvcache.CHANNEL_KEYS[prefix]
        keys = self._keys(prefix)
        inner_axes = self.inner.data_axes(lead_axes)
        out = {keys[sfx]: ("kv_seq", None) + tuple(ax)
               for sfx, ax in inner_axes.items()}
        out[data_key + "_pages"] = ("batch", None)
        return out

    # -- flat-cache plumbing ---------------------------------------------
    def _keys(self, prefix):
        data_key, scale_key = kvcache.CHANNEL_KEYS[prefix]
        return {"": data_key, "_scale": scale_key,
                "_pages": data_key + "_pages"}

    def channel(self, cache, prefix):
        keys = self._keys(prefix)
        return {sfx: cache[keys[sfx]] for sfx in self.suffixes}

    def channel_entries(self, prefix, store):
        keys = self._keys(prefix)
        return {keys[sfx]: arr for sfx, arr in store.items()}


class PagedPlaneCacheFormat(PagedCacheFormat):
    """``paged_int4_bp_fused``: a bit-plane page pool in the layout the
    fused decode kernel reads in place.

    Storage per channel, pages leading so that splices, COW copies and
    prefix attaches stay row operations on axis 0:

    ``""``        ``[num_pages, *lead, 4·Fw, page]`` uint32 — plane ``j``,
                  word ``w`` in row ``j·Fw + w``, one position per lane;
    ``"_scale"``  ``[num_pages, *lead, page]`` float32;
    ``"_pages"``  the ``[B, pages_per_slot]`` block table.

    A page holds 128 positions, one lane tile, so every pool leaf is
    lane-dense and a page is one ``[4·Fw, 128]`` tile per kv head — the
    kernel's own tile.  GQA decode reads the pool in place through the
    block table (:func:`repro.core.kvcache.plane_attention`), only the
    pages up to each slot's latest query position.  ``qk``/``av`` (MLA,
    whose score mixes a float rope term) gather back to the inner ring
    layout and run the jnp plane math.
    """

    page_size = 128

    def _pool(self, pages, lead, feat):
        fw = self.inner._words(feat)
        return {"": ((pages, *lead, 4 * fw, self.page_size), jnp.uint32),
                "_scale": ((pages, *lead, self.page_size), jnp.float32)}

    def init(self, batch, cache_len, lead, feat, dtype=jnp.bfloat16):
        npp = self.pages_per_slot(cache_len)
        store = {sfx: jnp.zeros(shape, dt) for sfx, (shape, dt)
                 in self._pool(batch * npp, lead, feat).items()}
        store["_pages"] = jnp.arange(
            batch * npp, dtype=jnp.int32).reshape(batch, npp)
        return store

    def abstract_state(self, batch, cache_len, lead, feat,
                       dtype=jnp.bfloat16):
        npp = self.pages_per_slot(cache_len)
        ab = {sfx: jax.ShapeDtypeStruct(shape, dt) for sfx, (shape, dt)
              in self._pool(batch * npp, lead, feat).items()}
        ab["_pages"] = jax.ShapeDtypeStruct((batch, npp), jnp.int32)
        return ab

    def append(self, store, x, b_idx, slots):
        """Write ``x [B, S, *lead, F]`` at ring ``slots [B, S]``, each row's
        valid slots consecutive (pads and the ring wrap aside), as every
        prefill and decode write is.

        A scatter of single positions would index the lane axis, and XLA
        then relays the whole pool out around it.  Instead each row's
        pages that the write can touch — at most ``(S + page − 2) // page
        + 1`` consecutive ones — are gathered whole, the new positions are
        gathered into place from the row's consecutive tokens (laid out
        position-major, then moved to the lane axis), and only pages that
        took a write are scattered back: whole pages, indexed on the
        leading axis."""
        del b_idx  # the block table row IS the batch index
        table = store["_pages"]
        b, npp = table.shape
        page, s = self.page_size, slots.shape[1]
        ln = npp * page
        n_touch = min(npp, (s + page - 2) // page + 1)
        valid = slots < ln  # slot == ring length: a dropped pad position
        # a row's valid writes: n consecutive tokens from index first_i, at
        # ring slots from `first` on (wrapping); rows with none write nothing
        first_i = jnp.argmax(valid, axis=1)
        first = jnp.take_along_axis(slots, first_i[:, None], axis=1)[:, 0]
        p0 = first // page % npp
        touched = (p0[:, None] + jnp.arange(n_touch)) % npp  # [B, T]
        # position w of the touched pages holds token first_i + off[b, w]
        off = jnp.arange(n_touch * page) - (first - p0 * page)[:, None]
        written = (off >= 0) & (off < valid.sum(axis=1)[:, None])
        src = jnp.clip(first_i[:, None] + off, 0, s - 1)  # [B, T·page]
        written = written.reshape(b, n_touch, page)
        phys = jnp.take_along_axis(table, touched, axis=1)
        hit = written.any(axis=-1)
        planes, scale = self.inner.encode(x)  # [B, S, *lead, 4, Fw]
        words = planes.reshape(*planes.shape[:-2], -1)  # [B, S, *lead, 4Fw]

        def merge(pool, vals):  # vals [B, S, *rest] → pool [P, *rest, page]
            rest = vals.shape[2:]
            new = jnp.take_along_axis(
                vals, src.reshape(b, -1, *(1,) * len(rest)), axis=1)
            new = jnp.moveaxis(new.reshape(b, n_touch, page, *rest), 2, -1)
            mask = written.reshape(b, n_touch, *(1,) * len(rest), page)
            # pages that took no write are not written back (a stale table
            # row may map a page another slot writes this very step)
            dst = jnp.where(hit, phys, pool.shape[0])
            return pool.at[dst].set(jnp.where(mask, new, pool[phys]),
                                    mode="drop")

        return {"": merge(store[""], words),
                "_scale": merge(store["_scale"], scale),
                "_pages": table}

    def fill(self, store, x, b_idx, slots):
        """A prefill's write into a pool fresh from :meth:`init`: the rows
        are built in ring order, token-major (a scatter of whole rows),
        and cut into pages behind the identity table — no page is read."""
        b, npp = store["_pages"].shape
        planes, scale = self.inner.encode(x)  # [B, S, *lead, 4, Fw]

        def pages(vals):  # [B, S, *rest] → [B·npp, *rest, page]
            rest = vals.shape[2:]
            ring = jnp.zeros((b, npp * self.page_size, math.prod(rest)),
                             vals.dtype)
            ring = ring.at[b_idx, slots].set(
                vals.reshape(b, vals.shape[1], -1), mode="drop")
            return jnp.moveaxis(
                ring.reshape(b * npp, self.page_size, *rest), 1, -1)

        return {"": pages(planes.reshape(*planes.shape[:-2], -1)),
                "_scale": pages(scale), "_pages": store["_pages"]}

    def _gather(self, store) -> dict:
        """Block-table gather back to the inner ring layout ``[B, L,
        *lead, 4, Fw]`` / ``[B, L, *lead]`` (the jnp ``qk``/``av`` read)."""
        table = store["_pages"]
        b, npp = table.shape
        words = store[""][table]  # [B, npp, *lead, 4Fw, page]
        scale = store["_scale"][table]  # [B, npp, *lead, page]
        lead = words.shape[2:-2]
        words = jnp.moveaxis(words, -1, 2)  # [B, npp, page, *lead, 4Fw]
        return {
            "": words.reshape(b, npp * self.page_size, *lead, 4, -1),
            "_scale": jnp.moveaxis(scale, -1, 2).reshape(
                b, npp * self.page_size, *lead),
        }

    def decode_attention(self, q, k_store, v_store, pos_ids, q_pos, *,
                         window, sm_scale, feat):
        return kvcache.plane_attention(
            q, k_store[""], k_store["_scale"], v_store[""], v_store["_scale"],
            pos_ids, q_pos, k_store["_pages"], window=window,
            sm_scale=sm_scale, feat=feat)

    def pages_read(self, max_pos, pages_per_slot: int):
        """Pages the kernel reads: those up to the latest query position."""
        from repro.kernels import plane_attn

        return plane_attn.live_blocks(max_pos, self.page_size, pages_per_slot)

    def kv_heads(self, store):
        return store[""].shape[1]

    def data_axes(self, lead_axes):
        return {"": tuple(lead_axes) + (None, None),
                "_scale": tuple(lead_axes) + (None,), "_pages": ()}

    def flat_cache_axes(self, prefix, lead_axes):
        """Pool leaves: ``(pages → kv_seq, *lead, plane rows and in-page
        offset unsharded)``; block tables follow the batch axis."""
        keys = self._keys(prefix)
        axes = self.data_axes(lead_axes)
        return {keys[""]: ("kv_seq",) + axes[""],
                keys["_scale"]: ("kv_seq",) + axes["_scale"],
                keys["_pages"]: ("batch", None)}


# ---------------------------------------------------------------------------
# PagePool — host-side physical page allocator
# ---------------------------------------------------------------------------


class PagePool:
    """Refcounted allocator over a fixed pool of physical pages.

    Pure host-side bookkeeping (the device pool arrays are preallocated and
    never resize): ``alloc``/``retain``/``release`` move pages between the
    LIFO free list and refcounted use.  A page's refcount is the number of
    holders — one per block-table entry referencing it plus one when the
    radix prefix index retains it — so ``refs > 1`` means *shared* and a
    write into it must copy first (COW).  Telemetry is *lifetime*-scoped —
    ``total_allocated``/``total_freed`` monotone counters plus the
    ``peak_in_use`` high-water mark — so pool pressure between two
    ``stats()`` calls is visible, not just the instantaneous snapshot; COW
    copies, evictions and prefix hits are owned here too (the ``note_*``
    methods), feeding ``ServeEngine.stats()``, the scheduler's
    :class:`~repro.serve.scheduler.EngineView`, and the :mod:`repro.obs`
    counter registry (``pages.alloc``/``pages.free``/``pages.cow``/
    ``pages.evict`` counters, ``pages.occupancy``/``pages.high_water``
    gauges) in one place.
    """

    def __init__(self, num_pages: int, page_size: int):
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.refs = np.zeros(self.num_pages, np.int32)
        # LIFO stack ordered so pop() hands out low page ids first
        self._free = list(range(self.num_pages - 1, -1, -1))
        self.cow_copies = 0
        self.evictions = 0
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        self.peak_in_use = 0
        self.total_allocated = 0
        self.total_freed = 0

    def _note_occupancy(self) -> None:
        if obs.active():
            obs.gauge("pages.occupancy", self.pages_in_use)
            obs.gauge("pages.high_water", self.peak_in_use)

    # -- occupancy -------------------------------------------------------
    def free_count(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def shared_pages(self) -> int:
        return int((self.refs > 1).sum())

    def shared_fraction(self) -> float:
        return self.shared_pages() / max(self.pages_in_use, 1)

    # -- lifecycle -------------------------------------------------------
    def alloc(self, n: int) -> np.ndarray:
        """Take ``n`` free pages (refcount 1 each); raises
        :class:`PoolExhausted` when the free list is short — the caller
        (engine) evicts prefix-index entries and retries."""
        if len(self._free) < n:
            raise PoolExhausted(
                f"page pool exhausted: need {n} pages, "
                f"{len(self._free)} free of {self.num_pages}"
            )
        pages = np.array([self._free.pop() for _ in range(n)], np.int64)
        self.refs[pages] = 1
        self.total_allocated += n
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        if obs.active():
            obs.counter("pages.alloc", n)
            self._note_occupancy()
        return pages

    def retain(self, pages) -> None:
        """Add one reference per page (sharing / index registration)."""
        for p in np.atleast_1d(np.asarray(pages, np.int64)):
            if self.refs[p] <= 0:
                raise ValueError(f"retain of free page {int(p)}")
            self.refs[p] += 1

    def release(self, pages) -> list[int]:
        """Drop one reference per page; returns the pages that became free."""
        freed = []
        for p in np.atleast_1d(np.asarray(pages, np.int64)):
            if self.refs[p] <= 0:
                raise ValueError(f"release of free page {int(p)}")
            self.refs[p] -= 1
            if self.refs[p] == 0:
                self._free.append(int(p))
                freed.append(int(p))
        if freed:
            self.total_freed += len(freed)
            if obs.active():
                obs.counter("pages.free", len(freed))
                self._note_occupancy()
        return freed

    # -- telemetry -------------------------------------------------------
    def note_cow(self, n: int = 1) -> None:
        """Record ``n`` copy-on-write page copies (engine calls this at the
        divergent-write site, so the counter lives with the pool)."""
        self.cow_copies += n
        if obs.active():
            obs.counter("pages.cow", n)

    def note_eviction(self, n: int = 1) -> None:
        """Record ``n`` prefix-index evictions forced by pool pressure."""
        self.evictions += n
        if obs.active():
            obs.counter("pages.evict", n)

    def note_prefix_hit(self, tokens_saved: int) -> None:
        """Record one prefix-cache attach that skipped ``tokens_saved``
        prefill positions."""
        self.prefix_hits += 1
        self.prefix_tokens_saved += int(tokens_saved)
        if obs.active():
            obs.counter("pages.prefix_hit")
            obs.counter("pages.prefix_tokens_saved", int(tokens_saved))

    def stats(self) -> dict:
        return {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "pages_in_use": self.pages_in_use,
            "pages_free": self.free_count(),
            "peak_in_use": self.peak_in_use,
            "total_allocated": self.total_allocated,
            "total_freed": self.total_freed,
            "shared_pages": self.shared_pages(),
            "shared_fraction": self.shared_fraction(),
            "cow_copies": self.cow_copies,
            "evictions": self.evictions,
            "prefix_hits": self.prefix_hits,
            "prefix_tokens_saved": self.prefix_tokens_saved,
        }


# ---------------------------------------------------------------------------
# RadixPrefixIndex — page-granular prompt-prefix → physical pages
# ---------------------------------------------------------------------------


class _Node:
    __slots__ = ("children", "page", "stamp")

    def __init__(self, page: int, stamp: int):
        self.children: dict = {}
        self.page = page
        self.stamp = stamp


class RadixPrefixIndex:
    """Radix tree keyed by ``unit``-sized token chunks: a page, or a whole
    fraction of one where the format's pages are longer than its prefix
    unit (:attr:`PagedCacheFormat.prefix_unit`).

    Each node pins one reference to ONE physical page (the pool row
    holding that chunk's K/V across every layer — pool rows index all layer
    pools identically, so one page id is a whole-model page bundle).  ``match`` walks the
    longest registered prefix and LRU-touches it; ``insert`` registers a
    served prompt's pages, returning only the NEWLY referenced ones so the
    caller can bump exactly those refcounts; ``evict_lru`` removes the
    least-recently-matched leaf (leaf-first keeps interior chains
    reachable).
    """

    def __init__(self, unit: int):
        self.unit = int(unit)
        self.root: dict = {}
        self.size = 0
        self._stamp = 0

    def _chunks(self, tokens) -> list[tuple]:
        toks = tuple(int(t) for t in np.asarray(tokens).ravel())
        n = len(toks) // self.unit
        return [toks[i * self.unit:(i + 1) * self.unit]
                for i in range(n)]

    def match(self, tokens) -> np.ndarray:
        """Physical page of each chunk of the longest registered prefix."""
        self._stamp += 1
        pages, level = [], self.root
        for chunk in self._chunks(tokens):
            node = level.get(chunk)
            if node is None:
                break
            node.stamp = self._stamp
            pages.append(node.page)
            level = node.children
        return np.asarray(pages, np.int64)

    def insert(self, tokens, page_ids) -> list[int]:
        """Register ``tokens``' whole chunks, chunk ``i`` backed by page
        ``page_ids[i]``; returns the page ids newly referenced (existing
        chain nodes keep their original pages — first writer wins)."""
        self._stamp += 1
        new, level = [], self.root
        for chunk, page in zip(self._chunks(tokens), np.asarray(page_ids)):
            node = level.get(chunk)
            if node is None:
                node = _Node(int(page), self._stamp)
                level[chunk] = node
                new.append(int(page))
                self.size += 1
            else:
                node.stamp = self._stamp
            level = node.children
        return new

    def evict_lru(self, evictable=None) -> Optional[int]:
        """Drop the least-recently-matched leaf; returns its page id (the
        caller releases the index's reference), or None when no leaf
        qualifies.  ``evictable(page_id)`` filters candidates — the engine
        passes "no live block table maps it", so eviction only ever touches
        pages the index alone holds (evicting a page a live slot still maps
        would burn an index entry without freeing a single byte)."""
        best = None  # (stamp, parent level, key, node)

        def walk(level):
            nonlocal best
            for key, node in level.items():
                if node.children:
                    walk(node.children)
                elif (evictable is None or evictable(node.page)) and (
                        best is None or node.stamp < best[0]):
                    best = (node.stamp, level, key, node)

        walk(self.root)
        if best is None:
            return None
        _, level, key, node = best
        del level[key]
        self.size -= 1
        return node.page


#: inner formats lifted onto pages at import time (registry names
#: ``paged_<inner>``) — every registry consumer picks them up for free
PAGED_BASES = ("bf16", "int8", "int4_bp", "int4_bp_fused")

for _base in PAGED_BASES:
    _inner = kvcache.get_cache_format(_base)
    # the fused kernel reads its own pool layout; the others gather
    _cls = (PagedPlaneCacheFormat
            if isinstance(_inner, kvcache.FusedBitPlaneCacheFormat)
            else PagedCacheFormat)
    kvcache.register_cache_format(_cls(_inner))
