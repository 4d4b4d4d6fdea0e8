"""Bit-serial GEMM Pallas kernel — §IV Algorithm 2 batched onto the MXU.

The faithful popcount kernel (:mod:`repro.kernels.bsdp_kernel`) is the
GEMV port: its AND+popcount inner loop is pure VPU work, so its cost grows
linearly in M and the bit-plane layout's amortization argument dies at
batch > 1.  This kernel is the batched-serving form: it exploits the
identity that for 0/1 bit vectors ``popcount(a AND b) == a · b``, so every
(j, k) plane-pair pass of Algorithm 2 over a *batch* of encoded rows is an
int8 matmul of 0/1 bit matrices — work the MXU executes at full int8 rate.

Per grid step ``(i, j, kk)`` the kernel stages a ``(bm, 4, bkw)``
activation-plane tile and a ``(bn, 4, bkw)`` weight-plane tile into VMEM,
unpacks each uint32 word tile into 0/1 int8 bit matrices ``[bm, bkw·32]`` /
``[bn, bkw·32]`` (VPU shift-and-mask, the transposed-load analogue; both
operands in the same bit-major column order), then
runs the 16 plane-pair contractions

    acc[m, n] += Σ_{j,k} s_jk · 2^{j+k} · (xbits_j @ wbits_k^T)

into a persistent int32 VMEM accumulator.  The K (word) axis is the
innermost grid dimension so the accumulator tile survives the sweep and the
output is written once.  ``s_jk = -1`` iff exactly one of j, k == 3 (signed
int4 two's complement); the ``s_jk·2^{j+k}`` weighting is a trace-time
Python constant folded into the accumulate, exactly like the paper's fully
unrolled shift-accumulate.

Integer-exact: cross-checked against the decoded int32 matmul oracle
(:func:`repro.kernels.ref.bsdp_gemm_ref`) and, at M == 1, bit-for-bit
against the GEMV popcount kernel.

Two kernels share this file:

* :func:`bsdp_gemm` — the unrolled form above: 16 per-(j, k) plane-pair
  ``dot_general`` calls per tile (one MXU dispatch per pair).
* :func:`bsdp_gemm_fused` — the single-contraction form (the paper's §IV
  "one dense pass instead of many scalar ones" restructuring, applied to
  the MXU): the 4 planes are *stacked along the row axis* of the bit
  matrices, so one ``[4·bm, K] × [K, 4·bn]`` contraction computes all 16
  plane-pair popcount sums at once, and the ``s_jk · 2^{j+k}`` weighting
  collapses to a weighted sum of the table's 16 aligned ``[bm, bn]``
  blocks — ONE MXU invocation per tile instead of 16.
  Bit-identical to :func:`bsdp_gemm` (asserted in tests and by the
  ``hlo_stats`` dot-count guard in ``tests/test_bsdp_gemm.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bsdp import plane_signs

_WORD = 32

#: Scoped-VMEM limit for both GEMM kernels.  A full-K word block at
#: K = 6144 with 128-row tiles unpacks to ~18 MiB of bit matrices per step
#: in the unrolled kernel, past the 16 MiB default; a TPU v5e core has
#: 128 MiB of VMEM.
_VMEM_LIMIT = 64 * 1024 * 1024


def _unpack_bits(words: jax.Array) -> jax.Array:
    """``[R, Kw] uint32 → [R, 32·Kw] 0/1 int8``, bit-major: bit ``b`` of
    word ``w`` lands in column ``b·Kw + w``.

    Any column order gives the same contraction as long as both operands
    share it; bit-major is the one Mosaic lowers, since each bit slice is a
    whole ``[R, Kw]`` tile concatenated along lanes (the word-major
    ``[R, Kw, 32] → [R, Kw·32]`` reshape is a shape cast it refuses)."""
    return jnp.concatenate(
        [((words >> b) & jnp.uint32(1)).astype(jnp.int8)
         for b in range(_WORD)],
        axis=1,
    )


def _bsdp_gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, signed: bool):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # [bm, 4, bkw] uint32
    w = w_ref[...]  # [bn, 4, bkw] uint32
    signs = plane_signs(signed)
    # Unpack once per plane, reuse across the 4 partner planes.
    xbits = [_unpack_bits(x[:, j, :]) for j in range(4)]  # 4 × [bm, bkw*32]
    wbits = [_unpack_bits(w[:, k, :]) for k in range(4)]  # 4 × [bn, bkw*32]
    acc = acc_ref[...]
    for j in range(4):  # fully unrolled, as in the paper
        for k in range(4):
            # popcount(AND) over the batch == 0/1 int8 MXU matmul.
            pair = jax.lax.dot_general(
                xbits[j],
                wbits[k],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # [bm, bn]
            acc = acc + pair * (signs[j][k] * (1 << (j + k)))
    acc_ref[...] = acc

    @pl.when(k_step == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = acc_ref[...]


def _unpack_planes_stacked(planes: jax.Array) -> jax.Array:
    """``[R, 4, Kw] uint32 → [4·R, 32·Kw] 0/1 int8`` — plane-major rows.

    Row ``j·R + r`` holds the ``2^j`` bit-plane of input row ``r`` (columns
    in :func:`_unpack_bits` order), so a single contraction of two such
    matrices yields every (j, k) plane-pair popcount sum, the pair (j, k)
    as the aligned ``[R, C]`` block ``(j, k)`` of a ``[4·R, 4·C]`` table.
    """
    return _unpack_bits(
        jnp.concatenate([planes[:, j, :] for j in range(4)], axis=0))


def _bsdp_gemm_fused_kernel(x_ref, w_ref, o_ref, acc_ref, *, signed: bool):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # [bm, 4, bkw] uint32
    w = w_ref[...]  # [bn, 4, bkw] uint32
    bm, bn = x.shape[0], w.shape[0]
    # Stack the planes along the row axis: [4·bm, K] and [4·bn, K] 0/1 bit
    # matrices — the fused operand layout.
    xbits = _unpack_planes_stacked(x)  # [4*bm, bkw*32]
    wbits = _unpack_planes_stacked(w)  # [4*bn, bkw*32]
    # ONE MXU contraction computes all 16 plane-pair popcount sums:
    # table[j*bm+m, k*bn+n] == popcount(x_plane_j[m] AND w_plane_k[n]).
    table = jax.lax.dot_general(
        xbits,
        wbits,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [4*bm, 4*bn]
    # Fold the s_jk·2^{j+k} shift/sign weighting over the 16 aligned
    # [bm, bn] blocks of the table (elementwise VPU epilogue — no further
    # MXU work).
    signs = plane_signs(signed)
    acc = acc_ref[...]
    for j in range(4):
        for k in range(4):
            block = table[j * bm:(j + 1) * bm, k * bn:(k + 1) * bn]
            acc = acc + block * (signs[j][k] * (1 << (j + k)))
    acc_ref[...] = acc

    @pl.when(k_step == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bkw", "signed", "interpret")
)
def bsdp_gemm_fused(
    x_planes: jax.Array,
    w_planes: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bkw: int = 256,
    signed: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Fused single-contraction BSDP GEMM: ``[M,4,Kw] × [N,4,Kw] → [M,N]``.

    Same contract as :func:`bsdp_gemm`, but each grid step runs ONE
    ``[4·bm, bkw·32] × [bkw·32, 4·bn]`` int8 contraction (the plane axis
    stacked along the row axis) instead of 16 per-(j,k) matmuls, then
    folds the ``s_jk·2^{j+k}`` weights over the 16 ``[bm, bn]`` blocks of
    the plane-pair table.  Bit-identical output; 1/16th the MXU dispatches
    (asserted via ``hlo_stats`` dot counting in the tests).

    ``bkw`` must be a multiple of 128 or the whole word axis (the TPU
    block rule for a minor dim).  At ``(128, 128, 192)`` (K = 6144): two
    512×6144 int8 bit matrices (6 MiB), a 512×512 int32 pair table (1 MiB)
    and the 64 KiB accumulator.
    """
    m, px, kw = x_planes.shape
    n, pw, kw2 = w_planes.shape
    assert px == 4 and pw == 4 and kw == kw2, (x_planes.shape, w_planes.shape)
    assert m % bm == 0 and n % bn == 0 and kw % bkw == 0, (
        x_planes.shape,
        w_planes.shape,
        (bm, bn, bkw),
    )

    kernel = functools.partial(_bsdp_gemm_fused_kernel, signed=signed)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, kw // bkw),
        in_specs=[
            pl.BlockSpec((bm, 4, bkw), lambda i, j, kk: (i, 0, kk)),
            pl.BlockSpec((bn, 4, bkw), lambda i, j, kk: (j, 0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x_planes, w_planes)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bkw", "signed", "interpret")
)
def bsdp_gemm(
    x_planes: jax.Array,
    w_planes: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bkw: int = 256,
    signed: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """``x_planes [M,4,Kw] × w_planes [N,4,Kw] → [M,N] int32`` (exact).

    ``bkw`` must be a multiple of 128 or the whole word axis (the TPU
    block rule for a minor dim).  A ``(128, 128, 192)`` step (K = 6144)
    stages 128·4·192·4B × 2 = 768 KiB of planes and unpacks them to
    8 × 128×6144 int8 bit matrices (6 MiB VMEM transient), with
    ``[128, 6144] × [6144, 128]`` contractions per plane pair.
    """
    m, px, kw = x_planes.shape
    n, pw, kw2 = w_planes.shape
    assert px == 4 and pw == 4 and kw == kw2, (x_planes.shape, w_planes.shape)
    assert m % bm == 0 and n % bn == 0 and kw % bkw == 0, (
        x_planes.shape,
        w_planes.shape,
        (bm, bn, bkw),
    )

    kernel = functools.partial(_bsdp_gemm_kernel, signed=signed)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, kw // bkw),
        in_specs=[
            pl.BlockSpec((bm, 4, bkw), lambda i, j, kk: (i, 0, kk)),
            pl.BlockSpec((bn, 4, bkw), lambda i, j, kk: (j, 0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x_planes, w_planes)
