"""Cache-residency subsystem: registered formats for decode K/V caches.

``core/residency.py`` made *weight* residency a registry; this module does
the same for the second-largest resident payload under continuous batching
— the decode caches.  The paper's §IV memory-term argument (bit-plane
residency wins once compute is cheap) applies verbatim: every decode step
reads the whole cache, so cache bytes are decode-bandwidth, and shrinking
them is the same lever as shrinking resident weights.

Every cache format is a :class:`CacheFormat` registered by name in
:data:`FORMATS`; every consumer — the ring caches in
:mod:`repro.models.attention` (GQA **and** the MLA latent twin), the
serving engine's splice/refill, the dry-run byte accounting, the cache
PartitionSpecs — asks the registry instead of switching on ``cfg.kv_quant``
booleans.

A format owns the lifecycle of one *channel*: a ``[B, L, *lead, F]``
per-slot tensor (K, V, or the MLA latent ``c_kv``) stored quantized with
per-slot scales:

``init(b, l, lead, feat)``  allocate the resident storage (suffix → array)
``append(store, x, ...)``   ring-write: encode new slots + scatter them
``qk(q, store)``            gather for scores: contract float queries
                            against stored slots over F, scales folded
                            AFTER the integer contraction (the same
                            scale-in-epilogue trick as the weight kernels)
``av(w, store, feat)``      gather for values: softmax-weighted read,
                            scale folded into the weights
``abstract_state(...)``     ShapeDtypeStruct twin of ``init`` — dry-run
                            cache bytes derive from THIS, so accounting
                            can never drift from real residency
``data_axes(lead_axes)``    logical sharding axes per payload suffix
``resident_bytes(store)``   HBM bytes (identical for real and abstract)

Shipped formats:

* ``bf16``    — plain float cache (the unquantized reference)
* ``int8``    — int8 payload + per-slot scales (subsumes the old
                ``_quant_slots`` / ``cfg.kv_quant`` path, §Perf P1)
* ``int4_bp`` — **bit-plane** K/V: per-slot int4 values stored as
                ``[..., 4, F/32]`` uint32 planes (§IV layout).  Scores are
                computed directly on the planes — int4-quantized queries
                AND+popcount against the stored planes (Algorithm 2), or
                the plane-pair 0/1 GEMM form on the MXU — selected by a
                batch-aware :class:`repro.core.residency.KernelPolicy`
                exactly like the weight formats' kernel dispatch.

Registering a new format is ~15 lines (see ``tests/test_kvcache.py`` for a
worked example)::

    class FP8Cache(BF16CacheFormat):
        name = "fp8"
        dtype = jnp.float8_e4m3fn        # if available
    register_cache_format(FP8Cache())

after which ``ServeEngine(cache_format="fp8")``, ``launch/serve.py
--cache-format`` and the dry-run byte accounting all work with no
call-site edits.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bitplane, bsdp
from repro.core.residency import KernelPolicy, _nbytes

#: scale floor — matches the legacy int8 cache path bit-for-bit
_EPS = 1e-6

#: canonical channel names in the flat cache dict (payload key, scale key)
CHANNEL_KEYS = {
    "k": ("k", "k_scale"),
    "v": ("v", "v_scale"),
    "c_kv": ("c_kv", "c_scale"),
}


class CacheFormat:
    """Base class / protocol for one decode-cache residency format.

    Stores are suffix→array dicts: ``""`` is the payload, ``"_scale"`` the
    per-slot scales (absent for float formats).  The flat cache dict maps
    them onto the canonical channel names via :data:`CHANNEL_KEYS`
    (``"k"``/``"k_scale"``, ``"v"``/``"v_scale"``, ``"c_kv"``/``"c_scale"``)
    so existing cache consumers (splice, pspecs, tests) keep working.
    """

    name: str = ""
    #: payload is the [..., 4, F/32] uint32 bit-plane layout
    is_bitplane: bool = False
    #: suffixes this format stores per channel ("" = payload)
    suffixes: tuple[str, ...] = ("",)
    #: the format fuses qk → softmax → av into one kernel; GQA decode
    #: routes through :meth:`decode_attention` instead of qk/av (MLA keeps
    #: qk/av — its score mixes a float rope term before the softmax)
    supports_fused_decode: bool = False
    kernel_policy: KernelPolicy = KernelPolicy()

    # -- storage lifecycle (per-format) ---------------------------------
    def init(self, batch: int, cache_len: int, lead: tuple[int, ...],
             feat: int, dtype=jnp.bfloat16) -> dict:
        """Allocate ``[batch, cache_len, *lead, feat]`` resident storage."""
        raise NotImplementedError

    def append(self, store: dict, x: jax.Array, b_idx: jax.Array,
               slots: jax.Array) -> dict:
        """Ring-write ``x [B, S, *lead, feat]`` at ``slots [B, S]``.

        Encodes into quantized storage and scatters; ``slots`` equal to the
        ring length are dropped (negative/padded positions)."""
        raise NotImplementedError

    def fill(self, store: dict, x: jax.Array, b_idx: jax.Array,
             slots: jax.Array) -> dict:
        """:meth:`append` into a ``store`` fresh from :meth:`init`, which
        holds nothing to keep (a prefill); a format may build it anew."""
        return self.append(store, x, b_idx, slots)

    def qk(self, q: jax.Array, store: dict) -> jax.Array:
        """Scores: ``q [B, *lead, G, F] · store [B, L, *lead, F] →
        [B, *lead, G, L]`` float32, scales folded after the contraction."""
        raise NotImplementedError

    def av(self, w: jax.Array, store: dict, feat: int) -> jax.Array:
        """Values: ``w [B, *lead, G, L] × store → [B, *lead, G, feat]``
        float32, value scales folded into ``w`` before the contraction."""
        raise NotImplementedError

    def decode_attention(self, q: jax.Array, k_store: dict, v_store: dict,
                         pos_ids: jax.Array, q_pos: jax.Array, *,
                         window: Optional[int], sm_scale: float,
                         feat: int) -> jax.Array:
        """Fused qk → masked softmax → av in one kernel (only when
        ``supports_fused_decode``): ``q [B, H, G, F]`` against both channel
        stores → ``[B, H, G, feat]`` float32.  Query row ``g`` of slot
        ``b`` sits at position ``q_pos [B, G]`` and sees the stored
        positions ``pos_ids [B, L]`` in ``[0, q_pos]`` (within ``window``);
        a row that sees none reads zeros."""
        raise NotImplementedError(
            f"cache format {self.name!r} has no fused decode path"
        )

    def abstract_state(self, batch: int, cache_len: int,
                       lead: tuple[int, ...], feat: int,
                       dtype=jnp.bfloat16) -> dict:
        """ShapeDtypeStruct twin of :meth:`init` (dry-run accounting)."""
        raise NotImplementedError

    def data_axes(self, lead_axes: tuple) -> dict:
        """Suffix → logical axes for the dims after ``(batch, kv_seq)``."""
        raise NotImplementedError

    # -- derived (generic) ----------------------------------------------
    def kv_heads(self, store: dict) -> int:
        """The lead (kv-head) dim of a GQA channel store."""
        return store[""].shape[2]

    def slot_capacity(self, cache_len: int) -> int:
        """Ring length actually allocated for a requested ``cache_len`` —
        identity for contiguous formats; paged formats round up to a whole
        number of pages so storage and ``pos_ids`` stay congruent."""
        return cache_len

    def flat_cache_axes(self, prefix: str, lead_axes: tuple) -> dict:
        """Flat-cache key → FULL logical axes (leading dims included) for
        one channel — what :func:`repro.sharding.partitioning.
        cache_axes_table` consumes.  Contiguous formats prepend the
        canonical ``(batch, kv_seq)``; layouts with different leading dims
        (the paged pool) override."""
        data_key, scale_key = CHANNEL_KEYS[prefix]
        keys = {"": data_key, "_scale": scale_key}
        return {keys[sfx]: ("batch", "kv_seq") + tuple(ax)
                for sfx, ax in self.data_axes(lead_axes).items()}

    def resident_bytes(self, store: dict) -> int:
        """HBM bytes of one channel — real and abstract states account
        identically by construction."""
        return sum(_nbytes(a) for a in store.values())

    def slot_bytes(self, lead: tuple[int, ...], feat: int,
                   dtype=jnp.bfloat16) -> int:
        """Resident bytes of ONE cache slot (analytic-traffic input;
        derives from :meth:`abstract_state` so it cannot drift)."""
        return self.resident_bytes(self.abstract_state(1, 1, lead, feat, dtype))

    # -- flat-cache channel plumbing ------------------------------------
    def channel(self, cache: dict, prefix: str) -> dict:
        """Extract one channel's store from a flat cache dict."""
        data_key, scale_key = CHANNEL_KEYS[prefix]
        keys = {"": data_key, "_scale": scale_key}
        return {sfx: cache[keys[sfx]] for sfx in self.suffixes}

    def channel_entries(self, prefix: str, store: dict) -> dict:
        """Inverse of :meth:`channel`: store → flat cache entries."""
        data_key, scale_key = CHANNEL_KEYS[prefix]
        keys = {"": data_key, "_scale": scale_key}
        return {keys[sfx]: arr for sfx, arr in store.items()}

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"<CacheFormat {self.name!r}>"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

FORMATS: dict[str, CacheFormat] = {}


def register_cache_format(fmt: CacheFormat) -> CacheFormat:
    """Register ``fmt`` under ``fmt.name`` (last registration wins)."""
    if not fmt.name:
        raise ValueError("cache format must set a non-empty .name")
    FORMATS[fmt.name] = fmt
    return fmt


def get_cache_format(name: str) -> CacheFormat:
    try:
        return FORMATS[name]
    except KeyError:
        raise ValueError(
            f"unknown cache format {name!r}; registered: {formats()}"
        ) from None


def formats() -> tuple[str, ...]:
    """Registered cache-format names, in registration order."""
    return tuple(FORMATS)


def format_for(cfg) -> CacheFormat:
    """Resolve a config's cache format (``cfg.cache_format``, falling back
    to the legacy ``cfg.kv_quant`` boolean → ``int8``)."""
    name = getattr(cfg, "cache_format", None)
    if name is None:
        name = "int8" if getattr(cfg, "kv_quant", False) else "bf16"
    return get_cache_format(name)


def cache_resident_bytes(cache) -> int:
    """Total HBM bytes of a cache pytree (payloads + scales + pos_ids).

    Works on real arrays and on ``jax.eval_shape`` outputs, so dry-run
    cache accounting and real engine caches share one code path."""
    return sum(_nbytes(a) for a in jax.tree_util.tree_leaves(cache))


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------


def _to_l_minor(a: jax.Array, payload_dims: int) -> jax.Array:
    """Move the slot axis L from position 1 to just before the payload dims:
    ``[B, L, *lead, *payload] → [B, *lead, L, *payload]``."""
    return jnp.moveaxis(a, 1, a.ndim - 1 - payload_dims)


def _slot_scale(x: jax.Array, qmax: int) -> jax.Array:
    """Per-slot symmetric scale over the feature axis (legacy floor 1e-6)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    return jnp.maximum(amax, _EPS) / qmax


# ---------------------------------------------------------------------------
# The three seed formats
# ---------------------------------------------------------------------------


class BF16CacheFormat(CacheFormat):
    """Plain float ring cache — the unquantized reference residency."""

    name = "bf16"
    dtype: Optional[jnp.dtype] = None  # None → the caller's cache dtype

    def _dtype(self, dtype):
        return self.dtype or dtype

    def init(self, batch, cache_len, lead, feat, dtype=jnp.bfloat16):
        return {"": jnp.zeros((batch, cache_len, *lead, feat),
                              self._dtype(dtype))}

    def append(self, store, x, b_idx, slots):
        data = store[""]
        return {"": data.at[b_idx, slots].set(
            x.astype(data.dtype), mode="drop")}

    def qk(self, q, store):
        t = _to_l_minor(store[""], 1).astype(jnp.float32)  # [B,*lead,L,F]
        return jnp.einsum("...gf,...lf->...gl", q.astype(jnp.float32), t)

    def av(self, w, store, feat):
        t = _to_l_minor(store[""], 1).astype(jnp.float32)
        return jnp.einsum("...gl,...lf->...gf", w, t)

    def abstract_state(self, batch, cache_len, lead, feat, dtype=jnp.bfloat16):
        return {"": jax.ShapeDtypeStruct(
            (batch, cache_len, *lead, feat), self._dtype(dtype))}

    def data_axes(self, lead_axes):
        return {"": tuple(lead_axes) + (None,)}


class Int8CacheFormat(CacheFormat):
    """int8 payload + per-slot scales (the old ``cfg.kv_quant`` path).

    Per-slot scales are constant over the feature dim, so dequantization
    folds AFTER the contraction: ``scores = (q·k_int8)·k_scale`` and
    ``out = (w·v_scale)·v_int8`` — the f32 cache copy never materializes.
    """

    name = "int8"
    suffixes = ("", "_scale")

    def init(self, batch, cache_len, lead, feat, dtype=jnp.bfloat16):
        del dtype
        return {
            "": jnp.zeros((batch, cache_len, *lead, feat), jnp.int8),
            "_scale": jnp.zeros((batch, cache_len, *lead), jnp.float32),
        }

    def append(self, store, x, b_idx, slots):
        scale = _slot_scale(x, 127)
        q = jnp.clip(
            jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
        ).astype(jnp.int8)
        return {
            "": store[""].at[b_idx, slots].set(q, mode="drop"),
            "_scale": store["_scale"].at[b_idx, slots].set(scale, mode="drop"),
        }

    def qk(self, q, store):
        t = _to_l_minor(store[""], 1).astype(jnp.float32)  # [B,*lead,L,F]
        s = _to_l_minor(store["_scale"], 0)  # [B,*lead,L]
        scores = jnp.einsum("...gf,...lf->...gl", q.astype(jnp.float32), t)
        return scores * s[..., None, :]

    def av(self, w, store, feat):
        t = _to_l_minor(store[""], 1).astype(jnp.float32)
        s = _to_l_minor(store["_scale"], 0)
        return jnp.einsum("...gl,...lf->...gf", w * s[..., None, :], t)

    def abstract_state(self, batch, cache_len, lead, feat, dtype=jnp.bfloat16):
        del dtype
        return {
            "": jax.ShapeDtypeStruct((batch, cache_len, *lead, feat), jnp.int8),
            "_scale": jax.ShapeDtypeStruct((batch, cache_len, *lead),
                                           jnp.float32),
        }

    def data_axes(self, lead_axes):
        return {"": tuple(lead_axes) + (None,),
                "_scale": tuple(lead_axes)}


class BitPlaneCacheFormat(CacheFormat):
    """int4 bit-plane K/V — the §IV layout applied to the decode cache.

    Payload is ``[B, L, *lead, 4, ceil(F/32)]`` uint32: per slot, the int4
    feature vector transposed into four 2^j bit-plane words.  4.25 bits per
    element at F=128 vs 16 for bf16 — a >3.7× shrink of the decode-cache
    memory term.

    Score path (``qk``): queries are int4-quantized per vector and the
    contraction runs DIRECTLY on the planes, with both scales folded after:

    * ``popcount`` — Algorithm 2: 16 AND+popcount passes
      (:func:`repro.core.bsdp.bsdp_popcount`), the faithful VPU form and
      the semantics the Pallas kernels in ``kernels/bsdp_*`` reproduce.
    * ``planes_gemm`` — the MXU adaptation: unpack planes to 0/1 bit
      matrices and contract plane pairs as int8 matmuls (the batched form
      of :func:`repro.core.bsdp.bsdp_matmul_planes`).
    * ``planes_gemm_fused`` — the single-contraction twin of the weight
      kernels' ``gemm_fused``: the plane axis interleaves into the row axis
      (``[G·4, F] × [F, L·4]``), ONE integer contraction produces the whole
      ``[G, 4, L, 4]`` plane-pair table, and the ``s_jk·2^{j+k}`` weighting
      collapses to a ``[4, 4]``-weighted elementwise reduce.  Bit-identical
      to the other two forms (asserted in tests).

    The batch-aware :class:`KernelPolicy` picks per decode batch — the same
    "dispatch is data" rule the weight formats use (GEMV-V single-request
    traffic → popcount, multi-slot continuous batching → the fused GEMM).

    Value path (``av``): softmax weights stay float, so the read decodes
    planes to int8 values and folds ``v_scale`` into the weights — same
    epilogue trick as the int8 format.
    """

    name = "int4_bp"
    is_bitplane = True
    suffixes = ("", "_scale")
    kernel_policy = KernelPolicy(gemv="popcount", gemm="planes_gemm_fused")

    def __init__(self, name: Optional[str] = None,
                 kernel_policy: Optional[KernelPolicy] = None):
        if name is not None:
            self.name = name
        if kernel_policy is not None:
            self.kernel_policy = kernel_policy

    @staticmethod
    def _words(feat: int) -> int:
        return -(-feat // bitplane.WORD)

    def init(self, batch, cache_len, lead, feat, dtype=jnp.bfloat16):
        del dtype
        return {
            "": jnp.zeros(
                (batch, cache_len, *lead, 4, self._words(feat)), jnp.uint32),
            "_scale": jnp.zeros((batch, cache_len, *lead), jnp.float32),
        }

    @staticmethod
    def encode(x):
        """``x [..., F]`` → (``[..., 4, Fw]`` uint32 int4 planes, ``[...]``
        per-vector scales)."""
        scale = _slot_scale(x, 7)
        q = jnp.clip(
            jnp.round(x.astype(jnp.float32) / scale[..., None]), -8, 7
        ).astype(jnp.int8)
        return bitplane.encode(bitplane.pad_to_word(q)), scale

    def append(self, store, x, b_idx, slots):
        planes, scale = self.encode(x)
        return {
            "": store[""].at[b_idx, slots].set(planes, mode="drop"),
            "_scale": store["_scale"].at[b_idx, slots].set(scale, mode="drop"),
        }

    def _score_planes(self, q_planes, k_planes, kernel):
        """int32 plane-space scores ``[..., G, 4, Fw] × [..., L, 4, Fw] →
        [..., G, L]``; all three forms are integer-exact and
        interchangeable (``popcount`` / ``planes_gemm`` /
        ``planes_gemm_fused``)."""
        from repro.obs import trace as obs  # deferred: kvcache loads early
        if obs.active():
            obs.counter("kernel.traced", kernel=kernel, fmt=self.name)
        if kernel == "popcount":
            return bsdp.bsdp_popcount(
                q_planes[..., :, None, :, :], k_planes[..., None, :, :, :],
                signed=True,
            )
        if kernel not in ("planes_gemm", "planes_gemm_fused"):
            raise ValueError(
                f"unknown decode-score kernel {kernel!r} (requested via "
                f"cache format {self.name!r}'s KernelPolicy); known: "
                "['planes_gemm', 'planes_gemm_fused', 'popcount']"
            )
        qb = bsdp._bits_to_int8(q_planes)  # [..., G, 4, F] 0/1
        kb = bsdp._bits_to_int8(k_planes)  # [..., L, 4, F] 0/1
        signs = jnp.array(bsdp.plane_signs(True), jnp.int32)
        shifts = jnp.array(
            [[1 << (j + k) for k in range(4)] for j in range(4)], jnp.int32)
        weights = signs * shifts
        if kernel == "planes_gemm_fused":
            # Interleave planes into the row axis and run ONE contraction:
            # [..., G·4, F] × [..., L·4, F] → the full [G, 4, L, 4]
            # plane-pair table, then the [4,4] shift/sign weighting as an
            # elementwise reduce — no second contraction.
            *lead, g, _, f = qb.shape
            l = kb.shape[-3]
            qf = qb.reshape(*lead, g * 4, f)
            kf = kb.reshape(*lead, l * 4, f)
            table = jnp.einsum(
                "...af,...bf->...ab", qf, kf,
                preferred_element_type=jnp.int32,
            ).reshape(*lead, g, 4, l, 4)
            return jnp.sum(table * weights[:, None, :], axis=(-3, -1))
        table = jnp.einsum(
            "...gjf,...lkf->...gljk", qb, kb,
            preferred_element_type=jnp.int32,
        )
        return jnp.einsum("...gljk,jk->...gl", table, weights)

    def qk(self, q, store):
        qq_scale = _slot_scale(q, 7)  # [..., G]
        qq = jnp.clip(
            jnp.round(q.astype(jnp.float32) / qq_scale[..., None]), -8, 7
        ).astype(jnp.int8)
        q_planes = bitplane.encode(bitplane.pad_to_word(qq))  # [...,G,4,Fw]
        k_planes = _to_l_minor(store[""], 2)  # [B,*lead,L,4,Fw]
        k_scale = _to_l_minor(store["_scale"], 0)  # [B,*lead,L]
        kernel = self.kernel_policy.kernel_for(q.shape[0])
        s_int = self._score_planes(q_planes, k_planes, kernel)
        return (s_int.astype(jnp.float32)
                * qq_scale[..., :, None] * k_scale[..., None, :])

    def av(self, w, store, feat):
        vals = bitplane.decode(_to_l_minor(store[""], 2), signed=True)
        v = vals[..., :feat].astype(jnp.float32)  # [B,*lead,L,F]
        s = _to_l_minor(store["_scale"], 0)
        return jnp.einsum("...gl,...lf->...gf", w * s[..., None, :], v)

    def abstract_state(self, batch, cache_len, lead, feat, dtype=jnp.bfloat16):
        del dtype
        return {
            "": jax.ShapeDtypeStruct(
                (batch, cache_len, *lead, 4, self._words(feat)), jnp.uint32),
            "_scale": jax.ShapeDtypeStruct((batch, cache_len, *lead),
                                           jnp.float32),
        }

    def data_axes(self, lead_axes):
        # F lives inside the packed plane words — never sharded
        return {"": tuple(lead_axes) + (None, None),
                "_scale": tuple(lead_axes)}


def plane_attention(q, k_words, k_scale, v_words, v_scale, pos_ids, q_pos,
                    table, *, window, sm_scale, feat, interpret=None):
    """Quantize ``q [B, H, G, F]`` to int4 per vector and run the fused
    page-aware kernel (:func:`repro.kernels.ops.plane_decode_attention`)
    over a ``[pages, H, 4·Fw, page]`` plane pool read through ``table``."""
    from repro.kernels import ops

    qq_scale = _slot_scale(q, 7)  # [B, H, G]
    qq = jnp.clip(
        jnp.round(q.astype(jnp.float32) / qq_scale[..., None]), -8, 7
    ).astype(jnp.int8)
    return ops.plane_decode_attention(
        bitplane.pad_to_word(qq), qq_scale, q_pos, k_words, k_scale,
        v_words, v_scale, pos_ids, table, sm_scale=sm_scale, feat=feat,
        window=window, interpret=interpret,
    )


class FusedBitPlaneCacheFormat(BitPlaneCacheFormat):
    """``int4_bp`` storage + the fused Pallas decode-attention kernel.

    Identical resident layout, bytes, ``append`` and sharding axes to
    ``int4_bp`` (it IS a :class:`BitPlaneCacheFormat`); the difference is
    pure kernel policy: GQA decode routes the whole qk → masked softmax →
    av read through ONE Pallas pass (:func:`plane_attention`), reading the
    stored planes directly — one integer qk contraction, one av
    contraction, per-position scales folded after the integer math.  The
    kernel reads page pools; this ring is handed over as a pool of one
    page per slot (slot-minor words, an identity table).  The paged twin
    (:class:`repro.core.paging.PagedPlaneCacheFormat`) stores its pool in
    the kernel's layout and needs no such relayout.  The jnp plane math of
    the parent class is the reference semantics this kernel reproduces
    (within softmax rounding); MLA decode keeps the parent's qk/av because
    its score mixes a float rope term between the two.
    """

    name = "int4_bp_fused"
    supports_fused_decode = True

    def decode_attention(self, q, k_store, v_store, pos_ids, q_pos, *,
                         window, sm_scale, feat, interpret=None):
        b, ln, h = k_store["_scale"].shape

        def pool(store):  # [B, L, H, 4, Fw] → [B, H, 4·Fw, L]
            words = store[""].reshape(b, ln, h, -1).transpose(0, 2, 3, 1)
            return words, store["_scale"].transpose(0, 2, 1)

        table = jnp.arange(b, dtype=jnp.int32)[:, None]
        return plane_attention(
            q, *pool(k_store), *pool(v_store), pos_ids, q_pos, table,
            window=window, sm_scale=sm_scale, feat=feat, interpret=interpret)


#: the name ISSUE/ROADMAP use for the bit-plane cache format class
Int4BPCacheFormat = BitPlaneCacheFormat

register_cache_format(BF16CacheFormat())
register_cache_format(Int8CacheFormat())
register_cache_format(BitPlaneCacheFormat())
register_cache_format(FusedBitPlaneCacheFormat())

# The paged generation registers its adapters (paged_bf16 … paged_int4_bp_
# fused) on import; importing here keeps "ask the registry" a complete
# answer for every consumer.  The bottom-of-module position makes the
# paging→kvcache back-import see a fully initialized module.
from repro.core import paging as _paging  # noqa: E402,F401
