"""Bit-serial dot product (BSDP) math — the paper's Algorithm 2, exactly.

Given bit-plane encodings ``a[..., 4, Kw]`` and ``b[..., 4, Kw]`` (uint32,
see :mod:`repro.core.bitplane`), the dot product of the underlying int4
vectors is

    A·B = Σ_{j,k} s_{jk} · 2^{j+k} · popcount(a_plane_j AND b_plane_k)

with the sign matrix ``s_{jk}`` from two's complement
(``v = -8·b3 + 4·b2 + 2·b1 + b0``):

    s_{jk} = -1  if exactly one of j, k equals 3   (the paper's §IV-B rule)
    s_{jk} = +1  otherwise (including j == k == 3, since (-8)·(-8) = +64)

For unsigned uint4 all signs are +1.

Two execution forms are provided:

* :func:`bsdp_popcount` — the faithful UPMEM port: AND + ``population_count``
  + shift-add, pure VPU work.  This is also the reference semantics the
  Pallas kernel (:mod:`repro.kernels.bsdp_kernel`) reproduces tile-by-tile.
* :func:`bsdp_matmul_planes` — the TPU-native adaptation: each (j,k)
  plane-pair contribution for a *matrix* of encoded rows is an int8 matmul
  of 0/1 bit matrices, i.e. the MXU plays the role of a 394-TOPS popcount.
  Exact over integers; preferred at large N where the MXU beats the VPU.

Both are integer-exact and are cross-checked against a plain int32 matmul of
the decoded values in the tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitplane

#: sign[j, k] for signed int4 two's complement.
SIGN_SIGNED = [[1 if ((j == 3) == (k == 3)) else -1 for k in range(4)] for j in range(4)]
SIGN_UNSIGNED = [[1] * 4 for _ in range(4)]


def plane_signs(signed: bool):
    return SIGN_SIGNED if signed else SIGN_UNSIGNED


def bsdp_popcount(
    a_planes: jax.Array, b_planes: jax.Array, *, signed: bool = True
) -> jax.Array:
    """Dot product(s) from bit-planes via AND+popcount (paper Algorithm 2).

    Args:
      a_planes: ``[..., 4, Kw]`` uint32.
      b_planes: ``[..., 4, Kw]`` uint32, broadcast-compatible with a_planes.

    Returns:
      ``[...]`` int32 dot products.
    """
    signs = plane_signs(signed)
    acc = None
    for j in range(4):
        for k in range(4):
            matches = a_planes[..., j, :] & b_planes[..., k, :]
            popc = jax.lax.population_count(matches).astype(jnp.int32)
            # lsl_add analogue: shift-accumulate in one expression.
            term = jnp.sum(popc, axis=-1) << (j + k)
            term = term if signs[j][k] > 0 else -term
            acc = term if acc is None else acc + term
    return acc


def bsdp_gemv_popcount(
    w_planes: jax.Array, x_planes: jax.Array, *, signed: bool = True
) -> jax.Array:
    """GEMV: ``w_planes [N, 4, Kw]`` × ``x_planes [..., 4, Kw]`` → ``[..., N]``."""
    x = x_planes[..., None, :, :]  # [..., 1, 4, Kw]
    return bsdp_popcount(w_planes, x, signed=signed)


def _bits_to_int8(planes: jax.Array) -> jax.Array:
    """Unpack uint32 planes → 0/1 int8 bit matrix ``[..., 4, Kw*32]``."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = ((planes[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.int8)
    return bits.reshape(*planes.shape[:-1], planes.shape[-1] * 32)


def bsdp_matmul_planes(
    x_planes: jax.Array, w_planes: jax.Array, *, signed: bool = True
) -> jax.Array:
    """BSDP as 16 plane-pair int8 MXU matmuls of 0/1 bit matrices.

    Args:
      x_planes: ``[M, 4, Kw]`` uint32 activation planes.
      w_planes: ``[N, 4, Kw]`` uint32 weight planes.

    Returns:
      ``[M, N]`` int32 — exactly ``decode(x) @ decode(w).T``.

    Key identity: for 0/1 bit vectors, ``popcount(a AND b) == a · b`` — so
    every (j,k) popcount pass of Algorithm 2 is an int8 matmul of bit
    matrices, which the MXU executes at 394 TOP/s.  All 16 passes fuse into
    ONE ``[M·4, K] × [K, N·4]`` contraction producing ``[M, 4, N, 4]``
    plane-pair sums, followed by the ``s_jk·2^{j+k}`` weighted reduction
    (tiny VPU epilogue).  Exact over integers.
    """
    from repro.obs import trace as obs
    if obs.active():
        # same trace-time call-site count as the Pallas wrappers in
        # kernels/ops.py — this is the jnp form of the fused contraction
        obs.counter("kernel.traced", kernel="gemm_fused", impl="jnp")
    xb = _bits_to_int8(x_planes)  # [M, 4, K] 0/1 int8
    wb = _bits_to_int8(w_planes)  # [N, 4, K] 0/1 int8
    # One fused contraction over K: [M,4,N,4] popcount table.
    table = jax.lax.dot_general(
        xb,
        wb,
        dimension_numbers=(((2,), (2,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [M, 4, N, 4]
    signs = jnp.array(plane_signs(signed), dtype=jnp.int32)
    shifts = jnp.array([[1 << (j + k) for k in range(4)] for j in range(4)], jnp.int32)
    weight = signs * shifts  # s_jk * 2^(j+k)
    return jnp.einsum("mjnk,jk->mn", table, weight)


def bsdp_gemv(
    w_planes: jax.Array,
    x: jax.Array,
    *,
    signed: bool = True,
    form: str = "popcount",
) -> jax.Array:
    """End-to-end BSDP GEMV from raw int4 activations.

    Args:
      w_planes: pre-encoded weights ``[N, 4, Kw]`` (from
        :func:`repro.core.bitplane.encode_weights` — the amortized one-time
        transform).
      x: raw int4 activations ``[M, K]`` (int8 payload).
      form: ``"popcount"`` (faithful) or ``"matmul"`` (MXU adaptation).

    Returns: ``[M, N]`` int32.
    """
    x_planes = bitplane.encode_acts(x)
    if form == "popcount":
        return bsdp_gemv_popcount(w_planes, x_planes, signed=signed)
    elif form == "matmul":
        return bsdp_matmul_planes(x_planes, w_planes, signed=signed)
    raise ValueError(f"unknown form {form!r}")
