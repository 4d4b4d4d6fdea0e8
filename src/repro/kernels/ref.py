"""Pure-jnp oracles for every Pallas kernel in this package.

Every oracle computes in plain ``jnp`` with no tiling, no Pallas, and no
cleverness — these define correctness.  All integer paths are bit-exact by
construction, so kernel tests assert exact equality on the int32 results and
``allclose`` only after float scales are applied.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitplane, dim
from repro.core.bsdp import plane_signs


def _dot_i32(x, w):
    return jax.lax.dot_general(
        x.astype(jnp.int32),
        w.astype(jnp.int32),
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def matmul_int8_ref(x_i8: jax.Array, w_i8: jax.Array) -> jax.Array:
    """W8A8: ``[M,K] int8 @ [K,N] int8 -> [M,N] int32`` (exact)."""
    return _dot_i32(x_i8, w_i8)


def matmul_int8_scaled_ref(
    x_i8: jax.Array,
    w_i8: jax.Array,
    x_scale: jax.Array,
    w_scale: jax.Array,
) -> jax.Array:
    """W8A8 with per-token [M,1] and per-channel [1,N] scales -> f32 [M,N]."""
    return matmul_int8_ref(x_i8, w_i8).astype(jnp.float32) * x_scale * w_scale


def matmul_int4_packed_ref(x_i8: jax.Array, w_packed: jax.Array) -> jax.Array:
    """W4A8 with 2-per-byte packed weights along K: ``[M,K]i8 @ packed[K//2,N]``."""
    from repro.core.quant import unpack_int4

    w = unpack_int4(w_packed, axis=0)  # [K, N] int8 in [-8,7]
    return _dot_i32(x_i8, w)


def bsdp_ref(
    x_i4: jax.Array, w_i4: jax.Array, *, signed: bool = True
) -> jax.Array:
    """BSDP oracle: the *definition* — decode-free plain integer matmul.

    ``x_i4 [M, K]`` (int8 payload, values in int4 range) × ``w_i4 [K, N]``
    → int32 [M, N].  The bit-plane pipeline must reproduce this exactly.
    """
    del signed  # values already carry their sign in the int8 payload
    return _dot_i32(x_i4, w_i4)


def bsdp_planes_ref(
    x_planes: jax.Array, w_planes: jax.Array, *, signed: bool = True
) -> jax.Array:
    """Plane-level oracle (paper Algorithm 2, unvectorized clarity form).

    x_planes ``[M, 4, Kw]``, w_planes ``[N, 4, Kw]`` → int32 ``[M, N]``.
    """
    signs = plane_signs(signed)
    acc = jnp.zeros((x_planes.shape[0], w_planes.shape[0]), jnp.int32)
    for j in range(4):
        for k in range(4):
            matches = x_planes[:, None, j, :] & w_planes[None, :, k, :]
            popc = jax.lax.population_count(matches).astype(jnp.int32)
            term = jnp.sum(popc, axis=-1) << (j + k)
            acc = acc + (term if signs[j][k] > 0 else -term)
    return acc


def bsdp_gemm_ref(
    x_planes: jax.Array, w_planes: jax.Array, *, signed: bool = True
) -> jax.Array:
    """Batched-GEMM oracle: decode both plane tensors, matmul in int32.

    ``x_planes [M, 4, Kw]`` × ``w_planes [N, 4, Kw]`` → int32 ``[M, N]``.
    This is the *definition* the GEMM kernel must reproduce exactly — no
    plane algebra at all, just decode and contract.
    """
    x = bitplane.decode(x_planes, signed=signed)  # [M, K] int8
    w = bitplane.decode(w_planes, signed=signed)  # [N, K] int8
    return _dot_i32(x, w.T)


def dim_w16a8_ref(x_i8: jax.Array, w_i16: jax.Array) -> jax.Array:
    """DIM oracle is simply the wide integer matmul, computed in int32."""
    return _dot_i32(x_i8, w_i16)


def dequant_matmul_ref(
    x_bf16: jax.Array, w_i8: jax.Array, w_scale: jax.Array
) -> jax.Array:
    """W8A16 weight-only: dequantize then matmul in f32 (reference order)."""
    w = w_i8.astype(jnp.float32) * w_scale  # [K, N]
    return jnp.dot(x_bf16.astype(jnp.float32), w)


def plane_decode_attention_ref(
    q: jax.Array,
    q_scale: jax.Array,
    q_pos: jax.Array,
    k_planes: jax.Array,
    k_scale: jax.Array,
    v_planes: jax.Array,
    v_scale: jax.Array,
    pos_ids: jax.Array,
    *,
    sm_scale: float,
    feat: int,
    window=None,
    signed: bool = True,
) -> jax.Array:
    """Fused bit-plane decode-attention oracle: decode, mask, attend.

    ``q [B, H, G, F]`` int4 values at positions ``q_pos [B, G]``; each
    slot's ring in logical order, ``k/v_planes [B, H, L, 4, F/32]`` with
    scales ``[B, H, L]`` and stored positions ``pos_ids [B, L]``; per-row
    query scales ``[B, H, G]`` → ``[B, H, G, feat]`` f32.  A query row sees
    the positions in ``[0, q_pos]`` (and within ``window``); one that sees
    none reads zeros.  Scores are exact int32 dot products; the value read
    runs at highest matmul precision.
    """
    k = bitplane.decode(k_planes, signed=signed)  # [B, H, L, F] int8
    v = bitplane.decode(v_planes, signed=signed).astype(jnp.float32)
    s_int = jnp.einsum("bhgf,bhlf->bhgl", q.astype(jnp.int32),
                       k.astype(jnp.int32),
                       preferred_element_type=jnp.int32)
    scores = (s_int.astype(jnp.float32) * q_scale[..., None]
              * k_scale[:, :, None, :] * sm_scale)
    pos, cur = pos_ids[:, None, None, :], q_pos[:, None, :, None]
    valid = (pos >= 0) & (pos <= cur)
    if window is not None:
        valid &= pos > cur - window
    w = jax.nn.softmax(jnp.where(valid, scores, -jnp.inf), axis=-1)
    w = jnp.where(valid, w, 0.0) * v_scale[:, :, None, :]
    out = jnp.einsum("bhgl,bhlf->bhgf", w, v,
                     precision=jax.lax.Precision.HIGHEST)
    return out[..., :feat]


def decode_weights_ref(w_planes: jax.Array, *, signed: bool = True) -> jax.Array:
    """[N, 4, Kw] planes → [K, N] int8 — layout round-trip oracle."""
    return bitplane.decode(w_planes, signed=signed).T


__all__ = [
    "matmul_int8_ref",
    "matmul_int8_scaled_ref",
    "matmul_int4_packed_ref",
    "bsdp_ref",
    "bsdp_planes_ref",
    "bsdp_gemm_ref",
    "dim_w16a8_ref",
    "dequant_matmul_ref",
    "plane_decode_attention_ref",
    "decode_weights_ref",
]
