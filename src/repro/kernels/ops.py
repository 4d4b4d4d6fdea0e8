"""Public jit'd wrappers for the Pallas kernels.

These handle everything the raw kernels require of their callers:

* **padding** of M/N/K to block multiples (zeros are exact for every integer
  path here) and slicing the result back;
* **block-size selection** that respects both the problem shape and MXU/VPU
  tile alignment;
* **interpret-mode dispatch**: on non-TPU backends (this container is
  CPU-only) kernels execute with ``interpret=True``, which runs the kernel
  body in Python per grid step — bit-exact semantics, no TPU required;
* scale plumbing from :class:`repro.core.quant.QuantTensor`.

Every wrapper has a matching oracle in :mod:`repro.kernels.ref` and a
shape/dtype sweep test in ``tests/test_kernels_*.py``.

Each wrapper also emits a ``kernel.traced`` counter (:mod:`repro.obs`)
labelled with the kernel name and resolved block shape.  Because the
wrappers run under ``jax.jit``, the counter fires at **trace time**: it
counts kernel *call sites per compiled program*, not per-step executions
(one compilation of the unrolled BSDP GEMM records 16 plane-pair call
sites where ``gemm_fused`` records 1).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bitplane
from repro.core.quant import QuantTensor
from repro.obs import trace as obs
from repro.kernels import (
    bsdp_gemm,
    bsdp_kernel,
    dequant_gemv,
    dim_kernel,
    gemv_int4,
    gemv_int8,
    plane_attn,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(flag: Optional[bool]) -> bool:
    return (not _on_tpu()) if flag is None else flag


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pick_block(dim: int, preferred: int, align: int) -> int:
    """Largest aligned block ≤ preferred that does not over-pad tiny dims."""
    if dim >= preferred:
        return preferred
    return max(align, _round_up(dim, align))


def _pad2(x: jax.Array, m: int, n: int) -> jax.Array:
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm == 0 and pn == 0:
        return x
    return jnp.pad(x, ((0, pm), (0, pn)))


def _note_traced(kernel: str, *blocks: int) -> None:
    """Count one kernel call site (trace-time under jit; see module doc)."""
    if obs.active():
        obs.counter("kernel.traced", kernel=kernel,
                    blocks="x".join(str(b) for b in blocks))


# ---------------------------------------------------------------------------
# W8A8
# ---------------------------------------------------------------------------


def quant_matmul(
    x: QuantTensor,
    w: QuantTensor,
    *,
    interpret: Optional[bool] = None,
    out_int32: bool = False,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
) -> jax.Array:
    """W8A8 matmul: ``x [M,K] per-token  ×  w [K,N] per-channel → f32 [M,N]``."""
    m, k = x.data.shape
    k2, n = w.data.shape
    assert k == k2
    bm = bm or _pick_block(m, 128, 8)
    bn = bn or _pick_block(n, 128, 128)
    bk = bk or _pick_block(k, 512, 128)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    xd = _pad2(x.data, mp, kp)
    wd = _pad2(w.data, kp, np_)
    xs = _pad2(x.scale.reshape(m, 1), mp, 1)
    ws = _pad2(w.scale.reshape(1, n), 1, np_)
    _note_traced("int8", bm, bn, bk)
    out = gemv_int8.matmul_int8(
        xd, wd, xs, ws, bm=bm, bn=bn, bk=bk,
        interpret=_interpret(interpret), out_int32=out_int32,
    )
    return out[:m, :n]


def matmul_int8_raw(
    x_i8: jax.Array, w_i8: jax.Array, *, interpret: Optional[bool] = None, **blocks
) -> jax.Array:
    """Scale-free exact int32 W8A8 matmul (tests, DIM building block)."""
    m, k = x_i8.shape
    n = w_i8.shape[1]
    ones_m = jnp.ones((m, 1), jnp.float32)
    ones_n = jnp.ones((1, n), jnp.float32)
    x = QuantTensor(data=x_i8, scale=ones_m, bits=8, axis=-1)
    w = QuantTensor(data=w_i8, scale=ones_n, bits=8, axis=0)
    return quant_matmul(x, w, interpret=interpret, out_int32=True, **blocks)


# ---------------------------------------------------------------------------
# W4A8 packed
# ---------------------------------------------------------------------------


def quant_matmul_int4(
    x: QuantTensor,
    w_packed: jax.Array,
    w_scale: jax.Array,
    *,
    interpret: Optional[bool] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
) -> jax.Array:
    """W4A8: ``x [M,K] int8 × packed w [K//2,N] → f32 [M,N]``.

    K must be even (int4 pairs).  Padding K pads *pairs*, which is exact.
    """
    m, k = x.data.shape
    k2, n = w_packed.shape
    assert k == 2 * k2, (x.data.shape, w_packed.shape)
    bm = bm or _pick_block(m, 128, 8)
    bn = bn or _pick_block(n, 128, 128)
    bk = bk or _pick_block(k, 512, 256)  # must stay even after padding
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    xd = _pad2(x.data, mp, kp)
    wd = _pad2(w_packed, kp // 2, np_)
    xs = _pad2(x.scale.reshape(m, 1), mp, 1)
    ws = _pad2(w_scale.reshape(1, n), 1, np_)
    _note_traced("int4_packed", bm, bn, bk)
    out = gemv_int4.matmul_int4_packed(
        xd, wd, xs, ws, bm=bm, bn=bn, bk=bk, interpret=_interpret(interpret)
    )
    return out[:m, :n]


# ---------------------------------------------------------------------------
# BSDP (bit-plane int4 × int4)
# ---------------------------------------------------------------------------

#: per-kernel (preferred bm, bm align, preferred bkw) — bn is shared (128).
#: This is the static FALLBACK table; autotuned winners (benchmarks/
#: autotune.py) are registered per (kernel, shape class) in _BSDP_TUNED and
#: take precedence in :func:`bsdp_blocks_for`.  A word block must be a
#: multiple of 128 or the whole (padded) word axis on the TPU: bkw 256
#: takes the whole axis up to K = 8192, which covers every projection of
#: the configs here without padding the weights.
_BSDP_BLOCKS = {
    "gemv": (8, 8, 256),
    "gemm": (128, 8, 256),
    "gemm_fused": (128, 8, 256),
}

#: kernel name → (module, attr), resolved at call time so tests can
#: monkeypatch the kernel modules and observe dispatch.
_BSDP_KERNEL_IMPLS = {
    "gemv": (bsdp_kernel, "bsdp_matmul"),
    "gemm": (bsdp_gemm, "bsdp_gemm"),
    "gemm_fused": (bsdp_gemm, "bsdp_gemm_fused"),
}

# A kernel registered for blocks but not dispatch (or vice versa) must fail
# at import, not as a KeyError deep in a traced call.
assert _BSDP_KERNEL_IMPLS.keys() == _BSDP_BLOCKS.keys(), (
    "BSDP kernel tables out of sync",
    sorted(_BSDP_KERNEL_IMPLS), sorted(_BSDP_BLOCKS),
)

#: autotuned (kernel name, shape class) → (bm, bn, bkw) preferred blocks.
_BSDP_TUNED: dict[tuple[str, str], tuple[int, int, int]] = {}


def bsdp_shape_class(m: int, n: int, kw: int) -> str:
    """Power-of-two shape bucket — the autotune cache key.

    Problem shapes that round up to the same (M, N, Kw) powers of two share
    tiling behaviour, so winners cache per bucket, not per exact shape.
    """

    def up(v: int) -> int:
        return 1 << max(0, int(v - 1).bit_length())

    return f"m{up(m)}_n{up(n)}_kw{up(kw)}"


def register_tuned_blocks(
    kernel: str, shape_cls: str, blocks: tuple[int, int, int]
) -> None:
    """Install an autotuned (bm, bn, bkw) winner for one shape class.

    Keyed by the :class:`repro.core.residency.KernelPolicy` kernel name, so
    every format that dispatches to that kernel picks the winner up with no
    call-site edits.  ``_BSDP_BLOCKS`` remains the fallback for shape
    classes without a cached winner.
    """
    if kernel not in _BSDP_BLOCKS:
        raise ValueError(
            f"cannot tune unknown kernel {kernel!r}; known: "
            f"{sorted(_BSDP_BLOCKS)}"
        )
    bm, bn, bkw = (int(b) for b in blocks)
    if min(bm, bn, bkw) <= 0:
        raise ValueError(f"blocks must be positive, got {blocks}")
    _BSDP_TUNED[(kernel, shape_cls)] = (bm, bn, bkw)


def clear_tuned_blocks() -> None:
    """Drop all autotuned winners (tests; fall back to _BSDP_BLOCKS)."""
    _BSDP_TUNED.clear()


def bsdp_blocks_for(kernel: str, m: int, n: int, kw: int) -> tuple[int, int, int]:
    """(bm, bn, bkw) for one problem shape: the autotuned winner for the
    shape class when cached, else the static preference — both clamped to
    the actual dims so tiny problems never over-pad."""
    bm_pref, bm_align, bkw_pref = _BSDP_BLOCKS[kernel]
    bn_pref = 128
    tuned = _BSDP_TUNED.get((kernel, bsdp_shape_class(m, n, kw)))
    if tuned is not None:
        bm_pref, bn_pref, bkw_pref = tuned
    return (
        _pick_block(m, bm_pref, bm_align),
        _pick_block(n, bn_pref, 128),
        _pick_block(kw, bkw_pref, 8),
    )


def bsdp_kernel_for(m: int) -> str:
    """Batch-aware kernel choice.

    M == 1 is the paper's GEMV-V request path: the AND+popcount kernel's
    VPU work is minimal and avoids unpacking weight planes to bit matrices.
    At M > 1 the per-(j,k) plane-pair contractions become real int8 MXU
    matmuls whose cost amortizes over the batch — the GEMM kernel wins.
    (``gemm_fused`` — the single-contraction form — is selected by the
    residency formats' :class:`~repro.core.residency.KernelPolicy`, e.g.
    ``bsdp_fused``; this function is the registry-free ops-level default.)
    """
    return "gemv" if m == 1 else "gemm"


def bsdp_matmul_planes(
    x_planes: jax.Array,
    w_planes: jax.Array,
    *,
    signed: bool = True,
    interpret: Optional[bool] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bkw: Optional[int] = None,
    kernel: Optional[str] = None,
    fmt_name: Optional[str] = None,
) -> jax.Array:
    """Plane-form BSDP: ``[M,4,Kw] × [N,4,Kw] → int32 [M,N]`` (exact).

    ``kernel``: ``None`` dispatches by batch (:func:`bsdp_kernel_for`);
    ``"gemv"`` forces the faithful popcount kernel, ``"gemm"`` the unrolled
    16-matmul plane-pair kernel, ``"gemm_fused"`` the single-contraction
    form (one MXU call per tile).  Padding and block selection are shared;
    blocks come from the autotune cache when a winner exists for the shape
    class (:func:`bsdp_blocks_for`).  ``fmt_name`` names the residency
    format that routed here — carried into block-selection errors so a
    mixed-``ResidencySpec`` misconfiguration is traceable to its policy
    entry, not just the kernel string.
    """
    m, _, kw = x_planes.shape
    n = w_planes.shape[0]
    kernel = kernel or bsdp_kernel_for(m)
    if kernel not in _BSDP_BLOCKS:
        via = (
            f" (requested via residency format {fmt_name!r}'s KernelPolicy)"
            if fmt_name else ""
        )
        raise ValueError(
            f"unknown BSDP kernel {kernel!r}{via}; registered kernels: "
            f"{sorted(_BSDP_BLOCKS)}"
        )
    bm_auto, bn_auto, bkw_auto = bsdp_blocks_for(kernel, m, n, kw)
    bm = bm or bm_auto
    bn = bn or bn_auto
    bkw = bkw or bkw_auto
    mp, np_, kwp = _round_up(m, bm), _round_up(n, bn), _round_up(kw, bkw)

    def pad3(p, d0, d2):
        return jnp.pad(p, ((0, d0 - p.shape[0]), (0, 0), (0, d2 - p.shape[2])))

    _note_traced(kernel, bm, bn, bkw)
    mod, attr = _BSDP_KERNEL_IMPLS[kernel]
    fn = getattr(mod, attr)
    out = fn(
        pad3(x_planes, mp, kwp),
        pad3(w_planes, np_, kwp),
        bm=bm, bn=bn, bkw=bkw, signed=signed, interpret=_interpret(interpret),
    )
    return out[:m, :n]


def bsdp_matmul(
    x_i4: jax.Array,
    w_planes: jax.Array,
    *,
    signed: bool = True,
    interpret: Optional[bool] = None,
    kernel: Optional[str] = None,
    fmt_name: Optional[str] = None,
) -> jax.Array:
    """End-to-end batch-aware BSDP: raw int4 activations ``[M,K]`` × encoded
    weights ``[N,4,K/32]`` → int32 ``[M,N]``.  Activation bit-plane encode is
    fused under the same jit (the per-request transform the paper calls
    "negligible compared to broadcast cost"); the kernel is chosen per batch
    size unless forced via ``kernel``; ``fmt_name`` tags errors with the
    residency format that routed the call."""
    x_planes = bitplane.encode_acts(bitplane.pad_to_word(x_i4))
    return bsdp_matmul_planes(
        x_planes, w_planes, signed=signed, interpret=interpret, kernel=kernel,
        fmt_name=fmt_name,
    )


def bsdp_gemv(
    x_i4: jax.Array,
    w_planes: jax.Array,
    *,
    signed: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Back-compat alias of :func:`bsdp_matmul` (pre-GEMM entry point)."""
    return bsdp_matmul(x_i4, w_planes, signed=signed, interpret=interpret)


def plane_decode_attention(
    q: jax.Array,          # [B, H, G, Fw·32] int8, int4-valued
    q_scale: jax.Array,    # [B, H, G] f32
    q_pos: jax.Array,      # [B, G] int32 position of each query row
    k_words: jax.Array,    # [P, H, 4·Fw, page] uint32 page pool
    k_scale: jax.Array,    # [P, H, page] f32
    v_words: jax.Array,    # [P, H, 4·Fw, page] uint32
    v_scale: jax.Array,    # [P, H, page] f32
    pos_ids: jax.Array,    # [B, pages_per_slot·page] int32
    table: jax.Array,      # [B, pages_per_slot] int32 block table
    *,
    sm_scale: float,
    feat: int,
    window: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Page-aware fused bit-plane decode attention → ``[B, H, G, feat]`` f32.

    Wraps :func:`repro.kernels.plane_attn.plane_decode_attention`: qk
    scores, the position mask, an online softmax across the slot's pages
    and the av read run in ONE Pallas pass, reading each slot's K/V pages
    from the pool through its block table.  Only the blocks up to the one
    holding the row's latest query position are read
    (:func:`plane_attn.live_blocks`); the word-padded feature axis is
    sliced back to ``feat``.
    """
    b = q.shape[0]
    blk = plane_attn.block_len(k_words.shape[-1])
    n_blocks = pos_ids.shape[1] // blk
    live = plane_attn.live_blocks(jnp.max(q_pos, axis=1), blk, n_blocks)
    _note_traced("plane_attn", n_blocks * blk, feat)
    out = plane_attn.plane_decode_attention(
        q, q_scale[..., None], q_pos[..., None], k_words, k_scale, v_words,
        v_scale, pos_ids.reshape(b, n_blocks, blk), table,
        live.astype(jnp.int32), sm_scale=sm_scale, window=window,
        interpret=_interpret(interpret),
    )
    return out[..., :feat]


# ---------------------------------------------------------------------------
# DIM (W16A8)
# ---------------------------------------------------------------------------


def dim_matmul(
    x_i8: jax.Array,
    w_i16: jax.Array,
    *,
    interpret: Optional[bool] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
) -> jax.Array:
    """Exact ``[M,K] int8 @ [K,N] int16 → int32`` via decomposed int8 passes."""
    m, k = x_i8.shape
    k2, n = w_i16.shape
    assert k == k2
    bm = bm or _pick_block(m, 128, 8)
    bn = bn or _pick_block(n, 128, 128)
    bk = bk or _pick_block(k, 256, 128)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    _note_traced("w16a8_dim", bm, bn, bk)
    out = dim_kernel.matmul_w16a8(
        _pad2(x_i8, mp, kp),
        _pad2(w_i16, kp, np_),
        bm=bm, bn=bn, bk=bk, interpret=_interpret(interpret),
    )
    return out[:m, :n]


# ---------------------------------------------------------------------------
# W8A16 weight-only
# ---------------------------------------------------------------------------


def weight_only_matmul(
    x: jax.Array,
    w: QuantTensor,
    *,
    interpret: Optional[bool] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
) -> jax.Array:
    """W8A16: float activations × int8 weights, dequant fused in-kernel."""
    m, k = x.shape
    k2, n = w.data.shape
    assert k == k2
    bm = bm or _pick_block(m, 128, 8)
    bn = bn or _pick_block(n, 128, 128)
    bk = bk or _pick_block(k, 512, 128)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    _note_traced("w8a16_dequant", bm, bn, bk)
    out = dequant_gemv.dequant_matmul(
        _pad2(x, mp, kp),
        _pad2(w.data, kp, np_),
        _pad2(w.scale.reshape(1, n), 1, np_),
        bm=bm, bn=bn, bk=bk, interpret=_interpret(interpret),
    )
    return out[:m, :n]


__all__ = [
    "quant_matmul",
    "matmul_int8_raw",
    "quant_matmul_int4",
    "bsdp_kernel_for",
    "bsdp_shape_class",
    "bsdp_blocks_for",
    "register_tuned_blocks",
    "clear_tuned_blocks",
    "bsdp_matmul_planes",
    "bsdp_matmul",
    "bsdp_gemv",
    "plane_decode_attention",
    "dim_matmul",
    "weight_only_matmul",
]
