"""Attention mixers: GQA (+bias/qk_norm/SWA), MLA, cross-attention.

Memory discipline: prefill/train attention is **chunked online-softmax**
(flash-attention recurrence in pure JAX: ``lax.scan`` over KV chunks,
running (m, l, acc) carry) so a 32k-token prefill never materializes an
S×S score matrix — per-step live memory is O(chunk_q × chunk_kv).  The
same code path handles causal masks and sliding windows via position
arithmetic, and shards cleanly when the KV sequence axis is partitioned
(long-context decode: XLA turns the running max/sum reductions into the
flash-decoding partial-softmax combine).

Decode caches are position-indexed ring buffers: a cache of length L holds
(k, v, pos_ids); slot = position mod L.  With L = max_len this is a plain
cache; with L = window it implements sliding-window eviction exactly.
Cache *residency* — how each slot is stored (bf16, int8+per-slot scales,
int4 bit-planes) and how decode attention reads it back — is owned by the
:mod:`repro.core.kvcache` format registry: ``init_kv_cache``/``_ring_write``
/``_decode_attention`` and the MLA twins route every payload touch through
``cfg``'s registered :class:`~repro.core.kvcache.CacheFormat`
(``cfg.cache_format``; the legacy ``cfg.kv_quant`` boolean maps to
``"int8"``).  Negative positions (left-padded microbatched prefill) are
dropped from the ring scatter and masked from attention.

MLA (DeepSeek-V2 / MiniCPM3) caches only the **latent** (kv_lora + rope
key) — itself a "shrink the resident bytes" technique that composes with
the paper's quantization story — and decodes in the *absorbed* form
(q absorbed through W_uk; context read back through W_uv), which is the
production decode path.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import kvcache
from repro.models import layers
from repro.models.layers import dense
from repro.sharding.partitioning import ParamSpec

NEG_INF = -1e30


def attn_dims(cfg, tp: int = 1) -> tuple[int, int, bool]:
    """(padded_heads, padded_kv_heads, shard_kv) for a model-axis of size tp.

    Heads pad to a multiple of tp.  KV heads shard only if padding preserves
    the GQA group structure (Hp/Hkvp == H/kv); otherwise they replicate.
    """
    h, kv = cfg.n_heads, cfg.n_kv_heads
    hp = -(-h // tp) * tp
    if kv % tp == 0:
        return hp, kv, True
    kvp = -(-kv // tp) * tp
    if kv and hp % kvp == 0 and hp // kvp == h // kv:
        return hp, kvp, True
    return hp, kv, False


# ---------------------------------------------------------------------------
# GQA specs / apply
# ---------------------------------------------------------------------------


def gqa_specs(cfg, tp: int = 1) -> dict:
    hp, kvp, _ = attn_dims(cfg, tp)
    dh = cfg.d_head
    d = {
        "wq": ParamSpec((cfg.d_model, hp * dh), cfg.dtype, ("embed", "heads")),
        "wk": ParamSpec((cfg.d_model, kvp * dh), cfg.dtype, ("embed", "kv_heads")),
        "wv": ParamSpec((cfg.d_model, kvp * dh), cfg.dtype, ("embed", "kv_heads")),
        "wo": ParamSpec((hp * dh, cfg.d_model), cfg.dtype, ("heads", "embed")),
    }
    if cfg.qkv_bias:
        d["bq"] = ParamSpec((hp * dh,), jnp.float32, ("heads",), "zeros")
        d["bk"] = ParamSpec((kvp * dh,), jnp.float32, ("kv_heads",), "zeros")
        d["bv"] = ParamSpec((kvp * dh,), jnp.float32, ("kv_heads",), "zeros")
    if cfg.qk_norm:
        d["q_norm"] = ParamSpec((dh,), jnp.float32, ("head_dim",), "ones")
        d["k_norm"] = ParamSpec((dh,), jnp.float32, ("head_dim",), "ones")
    return d


def _project_qkv(params, x, cfg, tp, positions, impl=None):
    hp, kvp, _ = attn_dims(cfg, tp)
    dh = cfg.d_head
    b, s, _ = x.shape
    q = dense(params["wq"], x, impl=impl)
    k = dense(params["wk"], x, impl=impl)
    v = dense(params["wv"], x, impl=impl)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    q = q.reshape(b, s, hp, dh)
    k = k.reshape(b, s, kvp, dh)
    v = v.reshape(b, s, kvp, dh)
    if cfg.qk_norm:
        q = layers.rms_norm(q, params["q_norm"])
        k = layers.rms_norm(k, params["k_norm"])
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_apply(
    params: dict,
    x: jax.Array,
    cfg,
    *,
    tp: int = 1,
    positions: Optional[jax.Array] = None,
    chunk_q: int = 512,
    chunk_kv: int = 1024,
    impl=None,
) -> jax.Array:
    """Full-sequence causal (optionally windowed) attention — train/prefill."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q, k, v = _project_qkv(params, x, cfg, tp, positions, impl=impl)
    out = chunked_attention(
        q, k, v,
        q_pos=positions, kv_pos=positions,
        causal=True, window=cfg.sliding_window,
        chunk_q=chunk_q, chunk_kv=chunk_kv,
    )
    out = out.reshape(b, s, -1)
    return dense(params["wo"], out, impl=impl)


def gqa_prefill(params, x, cfg, *, tp, cache_len, positions=None, impl=None,
                chunk_q=512, chunk_kv=1024):
    """Prefill: returns (output, cache).  Handles cache_len < S (SWA ring)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q, k, v = _project_qkv(params, x, cfg, tp, positions, impl=impl)
    out = chunked_attention(
        q, k, v, q_pos=positions, kv_pos=positions,
        causal=True, window=cfg.sliding_window,
        chunk_q=chunk_q, chunk_kv=chunk_kv,
    )
    out = dense(params["wo"], out.reshape(b, s, -1), impl=impl)
    cache = init_kv_cache(cfg, b, cache_len, tp=tp, dtype=k.dtype)
    cache = _ring_write(cache, k, v, positions, kvcache.format_for(cfg),
                        fresh=True)
    return out, cache


def _decode_positions(pos, b: int, s: int) -> jax.Array:
    """Normalize decode positions to [B, S]: scalar / [B] broadcast, [B, S]
    passed through (chunked prefill: per-token positions, negative = pad)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    if pos.ndim == 1:
        pos = pos[:, None]
    return jnp.broadcast_to(pos, (b, s))


def gqa_decode(params, x, cache, cfg, *, tp, pos, impl=None):
    """Chunked decode against the ring cache.

    x: [B, S, D] — S == 1 is plain continuous-batching decode; S > 1
    appends a prompt chunk (ring-write all S tokens, then causal attention
    of each token against the full cache — numerically the prefill
    semantics, expressed against resident ring storage).  pos: scalar,
    per-slot [B], or per-token [B, S] int32; negative positions are pads
    (rope/mask-ignored, dropped from the ring scatter)."""
    b, s, _ = x.shape
    positions = _decode_positions(pos, b, s)
    q, k, v = _project_qkv(params, x, cfg, tp, positions, impl=impl)
    fmt = kvcache.format_for(cfg)
    cache = _ring_write(cache, k, v, positions, fmt)
    out = _decode_attention(
        q, cache, cur=positions, window=cfg.sliding_window, fmt=fmt,
    )
    out = dense(params["wo"], out.reshape(b, s, -1), impl=impl)
    return out, cache


# ---------------------------------------------------------------------------
# Ring KV cache (residency format owned by repro.core.kvcache)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg, batch: int, cache_len: int, *, tp: int = 1, dtype=None):
    """Allocate the GQA ring cache through ``cfg``'s cache format.

    K and V are two format channels with lead dims ``(kv_heads,)`` and
    feature ``d_head``; ``pos_ids`` (absolute position per slot, -1 = empty)
    is format-independent.  The ring length is the format's
    ``slot_capacity(cache_len)`` — identity for contiguous formats, rounded
    up to a whole number of pages for paged ones, so the block-table gather
    and ``pos_ids`` always cover the same slots (paged appends/reads then
    indirect through the ``[B, pages_per_slot]`` table instead of a ring
    offset, inside the format).
    """
    _, kvp, _ = attn_dims(cfg, tp)
    dtype = dtype or cfg.dtype
    fmt = kvcache.format_for(cfg)
    cache_len = fmt.slot_capacity(cache_len)
    cache = {}
    for prefix in ("k", "v"):
        store = fmt.init(batch, cache_len, (kvp,), cfg.d_head, dtype=dtype)
        cache.update(fmt.channel_entries(prefix, store))
    cache["pos_ids"] = jnp.full((batch, cache_len), -1, jnp.int32)
    return cache


def _ring_slots(positions, ln):
    """slots = position mod L; negative (padded) positions → L, which the
    ``mode="drop"`` scatters discard — exact SWA eviction, pad-safe."""
    return jnp.where(positions >= 0, positions % ln, ln)


def _ring_write(cache, k, v, positions, fmt, fresh=False):
    """Scatter S new (k, v) at slots = position mod L through the format
    (``fresh``: into a cache just built by :func:`init_kv_cache`)."""
    ln = cache["pos_ids"].shape[1]
    slots = _ring_slots(positions, ln)  # [B, S]
    b_idx = jnp.arange(k.shape[0], dtype=jnp.int32)[:, None]
    write = fmt.fill if fresh else fmt.append
    out = dict(cache)
    for prefix, x in (("k", k), ("v", v)):
        store = write(fmt.channel(cache, prefix), x, b_idx, slots)
        out.update(fmt.channel_entries(prefix, store))
    out["pos_ids"] = cache["pos_ids"].at[b_idx, slots].set(
        positions, mode="drop")
    return out


def _decode_attention(q, cache, *, cur, window, fmt):
    """q: [B,S,H,D] vs the full ring cache; mask by stored positions.

    cur: per-token position [B, S] (or per-row [B]); the (S, G) axes fold
    into the cache format's single gather/group axis, so each token in a
    chunk attends causally (``pos_ids <= its own position``) against the
    just-written ring — S == 1 reduces bit-for-bit to single-token decode.
    When the cache L axis is sharded (long-context sequence parallelism)
    the max/sum reductions below become the flash-decoding combine.

    The score and value reads go through the cache format's ``qk``/``av``
    gather paths: quantized formats fold per-slot scales AFTER the integer
    contraction (``scores = (q·k_int)·scale``, ``out = (w·v_scale)·v_int``)
    and the bit-plane format contracts directly on the stored planes — the
    f32 cache copy is never materialized.  A format declaring
    ``supports_fused_decode`` (``int4_bp_fused`` and its paged twin)
    instead takes the whole qk → masked softmax → av read in one fused
    kernel call, which builds the position mask itself from ``pos_ids``
    and each query row's position — same semantics, one kernel instead of
    three XLA computations.
    """
    b, s, hq, dh = q.shape
    hkv = fmt.kv_heads(fmt.channel(cache, "k"))
    g = hq // hkv
    ln = cache["pos_ids"].shape[1]
    qg = q.reshape(b, s, hkv, g, dh).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, hkv, s * g, dh).astype(jnp.float32)
    cur = jnp.asarray(cur, jnp.int32)
    cur = jnp.broadcast_to(cur[:, None] if cur.ndim == 1 else cur, (b, s))
    pos_ids = cache["pos_ids"]
    if fmt.supports_fused_decode:
        out = fmt.decode_attention(
            qg, fmt.channel(cache, "k"), fmt.channel(cache, "v"), pos_ids,
            jnp.repeat(cur, g, axis=1),  # position of folded row s·G + g
            window=window, sm_scale=1.0 / math.sqrt(dh), feat=dh,
        )  # [B, Hkv, S·G, D]
    else:
        valid = ((pos_ids[:, None, :] >= 0)
                 & (pos_ids[:, None, :] <= cur[..., None]))
        if window is not None:
            valid &= pos_ids[:, None, :] > (cur[..., None] - window)
        scores = fmt.qk(qg, fmt.channel(cache, "k"))  # [B, Hkv, S·G, L]
        scores = scores / math.sqrt(dh)
        scores = scores.reshape(b, hkv, s, g, ln)
        scores = jnp.where(valid[:, None, :, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).reshape(b, hkv, s * g, ln)
        out = fmt.av(w, fmt.channel(cache, "v"), dh)  # [B, Hkv, S·G, D]
    out = out.reshape(b, hkv, s, g, dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, s, hq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (flash recurrence in pure JAX)
# ---------------------------------------------------------------------------


def chunked_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Skv, Hkv, D]
    v: jax.Array,  # [B, Skv, Hkv, D]
    *,
    q_pos: jax.Array,  # [B, Sq]
    kv_pos: jax.Array,  # [B, Skv]
    causal: bool = True,
    window: Optional[int] = None,
    chunk_q: int = 512,
    chunk_kv: int = 1024,
) -> jax.Array:
    b, sq, hq, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    cq = min(chunk_q, sq)
    ckv = min(chunk_kv, skv)
    # pad seq dims to chunk multiples (padded kv masked out via positions)
    pq, pkv = (-sq) % cq, (-skv) % ckv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pq)), constant_values=0)
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pkv)), constant_values=-1)
    nq, nkv = q.shape[1] // cq, k.shape[1] // ckv

    qc = q.reshape(b, nq, cq, hkv, g, dh).astype(jnp.float32)
    qp = q_pos.reshape(b, nq, cq)
    kc = k.reshape(b, nkv, ckv, hkv, dh).astype(jnp.float32)
    vc = v.reshape(b, nkv, ckv, hkv, dh).astype(jnp.float32)
    kp = kv_pos.reshape(b, nkv, ckv)
    scale = 1.0 / math.sqrt(dh)

    def one_q_chunk(qi, qpi):
        # qi: [B, cq, Hkv, G, D]; scan the flash recurrence over KV chunks.
        def body(carry, inp):
            m, l, acc = carry
            kj, vj, kpj = inp  # [B, ckv, Hkv, D], ..., [B, ckv]
            s = jnp.einsum("bqhgd,bshd->bhgqs", qi, kj) * scale
            mask = kpj[:, None, None, None, :] >= 0
            if causal:
                mask &= qpi[:, None, None, :, None] >= kpj[:, None, None, None, :]
            if window is not None:
                mask &= (
                    qpi[:, None, None, :, None] - kpj[:, None, None, None, :]
                ) < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqs,bshd->bhgqd", p, vj
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            body,
            (m0, l0, a0),
            (
                jnp.moveaxis(kc, 1, 0),
                jnp.moveaxis(vc, 1, 0),
                jnp.moveaxis(kp, 1, 0),
            ),
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.einsum("bhgqd->bqhgd", out)  # [B, cq, Hkv, G, D]

    out = jax.vmap(one_q_chunk, in_axes=(1, 1), out_axes=1)(qc, qp)
    out = out.reshape(b, nq * cq, hq, dh)[:, :sq]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_dims(cfg, tp: int = 1) -> int:
    return -(-cfg.n_heads // tp) * tp


def mla_specs(cfg, tp: int = 1) -> dict:
    hp = mla_dims(cfg, tp)
    dn, dr, dv, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    d: dict = {
        "w_dkv": ParamSpec((cfg.d_model, r + dr), cfg.dtype, ("embed", "kv_lora")),
        "kv_norm": ParamSpec((r,), jnp.float32, ("norm",), "ones"),
        "w_uk": ParamSpec((r, hp * dn), cfg.dtype, ("kv_lora", "heads")),
        "w_uv": ParamSpec((r, hp * dv), cfg.dtype, ("kv_lora", "heads")),
        "wo": ParamSpec((hp * dv, cfg.d_model), cfg.dtype, ("heads", "embed")),
    }
    if cfg.q_lora_rank:
        d["w_dq"] = ParamSpec(
            (cfg.d_model, cfg.q_lora_rank), cfg.dtype, ("embed", "kv_lora")
        )
        d["q_norm"] = ParamSpec((cfg.q_lora_rank,), jnp.float32, ("norm",), "ones")
        d["w_uq"] = ParamSpec(
            (cfg.q_lora_rank, hp * (dn + dr)), cfg.dtype, ("kv_lora", "heads")
        )
    else:
        d["wq"] = ParamSpec(
            (cfg.d_model, hp * (dn + dr)), cfg.dtype, ("embed", "heads")
        )
    return d


def _mla_q(params, x, cfg, hp, positions, impl=None):
    b, s, _ = x.shape
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        cq = layers.rms_norm(dense(params["w_dq"], x, impl=impl), params["q_norm"])
        q = dense(params["w_uq"], cq, impl=impl)
    else:
        q = dense(params["wq"], x, impl=impl)
    q = q.reshape(b, s, hp, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(params, x, cfg, positions, impl=None):
    b, s, _ = x.shape
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    ckv = dense(params["w_dkv"], x, impl=impl)
    c_kv = layers.rms_norm(ckv[..., :r], params["kv_norm"])
    k_rope = ckv[..., r:].reshape(b, s, 1, dr)
    k_rope = layers.apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0]
    return c_kv, k_rope  # [B,S,r], [B,S,dr]


def mla_apply(params, x, cfg, *, tp=1, positions=None, impl=None, cache_len=None,
              chunk_q=512, chunk_kv=1024):
    """Train/prefill MLA.  Returns output (and cache if cache_len given)."""
    b, s, _ = x.shape
    hp = mla_dims(cfg, tp)
    dn, dr, dv, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q_nope, q_rope = _mla_q(params, x, cfg, hp, positions, impl=impl)
    c_kv, k_rope = _mla_latent(params, x, cfg, positions, impl=impl)
    # expand latent -> per-head k/v (standard prefill form)
    k_nope = dense(params["w_uk"], c_kv, impl=impl).reshape(b, s, hp, dn)
    v = dense(params["w_uv"], c_kv, impl=impl).reshape(b, s, hp, dv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, hp, dr))], axis=-1
    )
    # pad v to q_head_dim for the shared kernel, slice after
    out = chunked_attention(
        q, k, jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv))),
        q_pos=positions, kv_pos=positions, causal=True,
        chunk_q=chunk_q, chunk_kv=chunk_kv,
    )[..., :dv]
    out = dense(params["wo"], out.reshape(b, s, hp * dv), impl=impl)
    if cache_len is None:
        return out
    cache = init_mla_cache(cfg, b, cache_len, dtype=c_kv.dtype)
    cache = _mla_write(cache, c_kv, k_rope, positions, kvcache.format_for(cfg))
    return out, cache


def init_mla_cache(cfg, batch, cache_len, dtype=None):
    """MLA latent cache: the ``c_kv`` channel (lead ``()``, feature = lora
    rank) goes through ``cfg``'s cache format; the tiny rope key stays float
    (phase precision), exactly as the int8 path always did."""
    dtype = dtype or cfg.dtype
    fmt = kvcache.format_for(cfg)
    cache_len = fmt.slot_capacity(cache_len)
    cache = dict(fmt.channel_entries(
        "c_kv", fmt.init(batch, cache_len, (), cfg.kv_lora_rank, dtype=dtype)
    ))
    cache["k_rope"] = jnp.zeros((batch, cache_len, cfg.qk_rope_dim), dtype)
    cache["pos_ids"] = jnp.full((batch, cache_len), -1, jnp.int32)
    return cache


def _mla_write(cache, c_kv, k_rope, positions, fmt):
    ln = cache["pos_ids"].shape[1]
    slots = _ring_slots(positions, ln)
    b_idx = jnp.arange(c_kv.shape[0], dtype=jnp.int32)[:, None]
    out = dict(cache)
    store = fmt.append(fmt.channel(cache, "c_kv"), c_kv, b_idx, slots)
    out.update(fmt.channel_entries("c_kv", store))
    out["k_rope"] = cache["k_rope"].at[b_idx, slots].set(
        k_rope.astype(cache["k_rope"].dtype), mode="drop"
    )
    out["pos_ids"] = cache["pos_ids"].at[b_idx, slots].set(
        positions, mode="drop")
    return out


def mla_decode(params, x, cache, cfg, *, tp=1, pos, impl=None):
    """Absorbed-form MLA decode: score and read in the latent space.

    x: [B, S, D] — S == 1 single-token decode, S > 1 appends a prompt chunk
    (causal per-token masking against the latent ring, like
    :func:`gqa_decode`).  The latent cache reads route through the cache
    format's ``qk``/``av`` gathers with lead dims ``()`` — the (S, heads)
    axes fold into the gather's group axis — so int8 scale folding and the
    bit-plane popcount/GEMM score path apply to the MLA latent exactly as
    to K/V.
    """
    b, s, _ = x.shape
    hp = mla_dims(cfg, tp)
    dn, dr, dv, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    positions = _decode_positions(pos, b, s)
    q_nope, q_rope = _mla_q(params, x, cfg, hp, positions, impl=impl)  # [B,S,H,*]
    c_kv_new, k_rope_new = _mla_latent(params, x, cfg, positions, impl=impl)
    fmt = kvcache.format_for(cfg)
    cache = _mla_write(cache, c_kv_new, k_rope_new, positions, fmt)
    ln = cache["pos_ids"].shape[1]

    # absorbed decode requires the float matrix; quantized residency applies
    # to the projections above, while absorption stays in the latent space.
    w_uk_f = _as_float(params["w_uk"], (r, hp, dn), x.dtype)
    w_uv_f = _as_float(params["w_uv"], (r, hp, dv), x.dtype)

    q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                       w_uk_f.astype(jnp.float32))  # [B,S,H,r]
    store = fmt.channel(cache, "c_kv")
    s_nope = fmt.qk(q_abs.reshape(b, s * hp, r), store)  # scales folded
    s_nope = s_nope.reshape(b, s, hp, ln)
    krope = cache["k_rope"].astype(jnp.float32)  # [B,L,dr]
    scores = (
        s_nope
        + jnp.einsum("bqhd,bld->bqhl", q_rope.astype(jnp.float32), krope)
    ) / math.sqrt(dn + dr)
    pos_ids = cache["pos_ids"]
    valid = (pos_ids[:, None, :] >= 0) & (
        pos_ids[:, None, :] <= positions[..., None])  # [B,S,L]
    scores = jnp.where(valid[:, :, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    ctx_lat = fmt.av(w.reshape(b, s * hp, ln), store, r)
    ctx_lat = ctx_lat.reshape(b, s, hp, r)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx_lat, w_uv_f.astype(jnp.float32))
    out = dense(params["wo"], out.reshape(b, s, hp * dv).astype(x.dtype), impl=impl)
    return out, cache


def _as_float(w, shape3, dtype):
    """Reshape a (possibly quantized) up-projection to [r, H, d] float.

    Capability-gated on the residency registry: a format that cannot be
    dequantized to a dense matrix declares ``supports_absorbed_decode =
    False`` and fails loudly here instead of silently falling through to a
    wrong decode path.
    """
    from repro.core import residency

    if isinstance(w, residency.QuantLinearState):
        fmt = residency.get_format(w.mode)
        if not fmt.supports_absorbed_decode:
            raise NotImplementedError(
                f"residency format {w.mode!r} does not support absorbed MLA "
                "decode (supports_absorbed_decode=False); keep the latent "
                "up-projections in a dequantizable format via ResidencySpec"
            )
        return fmt.to_float(w).reshape(shape3).astype(dtype)
    return w.reshape(shape3).astype(dtype)


# ---------------------------------------------------------------------------
# Cross-attention (vision / encoder-decoder memory)
# ---------------------------------------------------------------------------


def cross_specs(cfg, tp: int = 1) -> dict:
    hp, kvp, _ = attn_dims(cfg, tp)
    dh = cfg.d_head
    return {
        "wq": ParamSpec((cfg.d_model, hp * dh), cfg.dtype, ("embed", "heads")),
        "wk": ParamSpec((cfg.d_model, kvp * dh), cfg.dtype, ("embed", "kv_heads")),
        "wv": ParamSpec((cfg.d_model, kvp * dh), cfg.dtype, ("embed", "kv_heads")),
        "wo": ParamSpec((hp * dh, cfg.d_model), cfg.dtype, ("heads", "embed")),
        "gate": ParamSpec((), jnp.float32, (), "zeros"),  # llama-vision tanh gate
    }


def cross_kv(params, ctx: jax.Array, cfg, *, tp=1, impl=None):
    """Project encoder memory once; reused across decode steps."""
    b, s, _ = ctx.shape
    _, kvp, _ = attn_dims(cfg, tp)
    k = dense(params["wk"], ctx, impl=impl).reshape(b, s, kvp, cfg.d_head)
    v = dense(params["wv"], ctx, impl=impl).reshape(b, s, kvp, cfg.d_head)
    return {"ck": k, "cv": v}


def cross_apply(params, x, kv, cfg, *, tp=1, gated=True, impl=None,
                chunk_q=512, chunk_kv=1024):
    """x: [B,S,D] attends over precomputed ctx kv (no mask, no rope)."""
    b, s, _ = x.shape
    hp, kvp, _ = attn_dims(cfg, tp)
    dh = cfg.d_head
    q = dense(params["wq"], x, impl=impl).reshape(b, s, hp, dh)
    k, v = kv["ck"], kv["cv"]
    skv = k.shape[1]
    qpos = jnp.zeros((b, s), jnp.int32)
    kpos = jnp.zeros((b, skv), jnp.int32)
    out = chunked_attention(
        q, k, v, q_pos=qpos, kv_pos=kpos, causal=False, window=None,
        chunk_q=chunk_q, chunk_kv=chunk_kv,
    )
    out = dense(params["wo"], out.reshape(b, s, -1), impl=impl)
    if gated:
        out = jnp.tanh(params["gate"]).astype(out.dtype) * out
    return out
