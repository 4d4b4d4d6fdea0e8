"""Fused Pallas decode attention over the bit-plane KV layout.

The ``int4_bp`` cache format stores K and V slots as ``[..., 4, Fw]`` uint32
bit-planes (:mod:`repro.core.bitplane`).  The jnp decode path (the reference
semantics, :class:`repro.core.kvcache.BitPlaneCacheFormat`) computes the qk
scores on the planes and then dequantizes V for the av gather — three
separate XLA computations with the softmax in between.

This kernel fuses the whole decode-attention read into one Pallas pass per
(batch, kv-head) row, reading the stored planes directly:

1. **qk** — the stored K planes decode in VMEM to int4 values
   ``v = 1·b0 + 2·b1 + 4·b2 − 8·b3`` (VPU shift-and-mask), and ONE int8
   contraction against the int4-quantized query gives the exact integer
   scores — the same integers the jnp plane math produces, since every
   plane-pair form of Algorithm 2 sums to the integer dot product.  The
   per-vector query scale and the per-slot K scales fold AFTER the integer
   contraction.
2. **softmax** — masked (additive bias), numerically-stable, in-register.
3. **av** — the per-slot ``v_scale`` folds into the softmax weights, and
   ONE contraction against the decoded V values reads the output.

Layout: the wrapper (:func:`repro.kernels.ops.plane_decode_attention`)
hands each row's planes over as ``[4·Fw, L]`` words — plane ``j``, word
``w`` in row ``j·Fw + w``, one ring slot per lane.  Slots on the lane axis
keep the VMEM tiles dense: the stored ``[L, 4, Fw]`` order would pad its
``(4, Fw)`` minor dims to ``(8, 128)`` tiles, about 4 KiB per slot, so a
1024-slot ring would take 16 MiB for K and V double-buffered; this layout
takes 64 KiB per ring per buffer.  Decoding word ``w`` yields features
``w·32 … w·32+31`` as 32 sublane rows, so the decoded ``[F, L]`` values
keep the natural feature order with no lane shuffles.

Grid: one step per flattened (batch × kv-head) row, the whole ring staged
per step.  The decoded ``[F, L]`` K and V values and the ``[G, L]`` scores
bound the ring length one step can hold: under the 64 MiB limit below a
v5e compiles L = 32768 for a 32-token chunk (G = 64) and L = 131072 for
one decode token (G = 2).  Longer rings need an L-tiled online-softmax
form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_WORD = 32

#: Scoped-VMEM limit.  Under the default 16 MiB a 16384-slot ring for a
#: 32-token chunk (G = 64) already fails on a v5e (16.6 MiB asked); a v5e
#: core has 128 MiB of VMEM.
_VMEM_LIMIT = 64 * 1024 * 1024


def _decode_planes(words: jax.Array, signed: bool) -> jax.Array:
    """``[4·Fw, L]`` uint32 plane words → ``[Fw·32, L]`` int32 int4 values
    (row ``w·32 + b`` is feature ``w·32 + b``)."""
    fw = words.shape[0] // 4
    shifts = jax.lax.broadcasted_iota(jnp.int32, (_WORD, 1), 0).astype(
        jnp.uint32)
    chunks = []
    for w in range(fw):
        val = None
        for j in range(4):
            row = words[j * fw + w:j * fw + w + 1, :]  # [1, L]
            bits = ((row >> shifts) & jnp.uint32(1)).astype(jnp.int32)
            weight = -8 if (signed and j == 3) else 1 << j
            val = bits * weight if val is None else val + bits * weight
        chunks.append(val)  # [32, L]
    return jnp.concatenate(chunks, axis=0)


def _plane_attn_kernel(
    q_ref, qs_ref, k_ref, ks_ref, v_ref, vs_ref, bias_ref, o_ref,
    *, sm_scale: float, signed: bool,
):
    q = q_ref[0]  # [G, F] int8 — int4-quantized query values
    kval = _decode_planes(k_ref[0], signed).astype(jnp.int8)  # [F, L]

    # -- qk: one integer contraction, scales folded after it ------------
    # (precision pinned: an ambient float precision such as "highest"
    # would reach this int8 matmul, which Mosaic then refuses)
    s_int = jax.lax.dot_general(
        q, kval,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32,
    )  # [G, L]
    scores = (
        s_int.astype(jnp.float32) * qs_ref[0] * ks_ref[0] * sm_scale
        + bias_ref[0]  # additive mask (0 / NEG_INF), finite
    )

    # -- masked softmax (bias is a large-negative float, never -inf) ----
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    w = p / jnp.sum(p, axis=-1, keepdims=True)  # [G, L]

    # -- av: v_scale folds into the softmax weights ---------------------
    vval = _decode_planes(v_ref[0], signed).astype(jnp.float32)  # [F, L]
    o_ref[0] = jax.lax.dot_general(
        w * vs_ref[0], vval,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # [G, F]


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "signed", "interpret")
)
def plane_decode_attention(
    q: jax.Array,          # [R, G, F] int8, int4-valued, F = Fw·32
    q_scale: jax.Array,    # [R, G, 1] f32
    k_words: jax.Array,    # [R, 4·Fw, L] uint32
    k_scale: jax.Array,    # [R, 1, L] f32
    v_words: jax.Array,    # [R, 4·Fw, L] uint32
    v_scale: jax.Array,    # [R, 1, L] f32
    bias: jax.Array,       # [R, G, L] f32 additive mask (0 / NEG_INF)
    *,
    sm_scale: float,
    signed: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Fused plane-layout decode attention → ``[R, G, F] f32``.

    ``R`` flattens (batch × kv-head); ``G`` is the folded (chunk × group)
    query axis; ``L`` the ring length.  Inputs are in the kernel layout
    described in the module docstring (``ops.plane_decode_attention``
    builds it from the stored planes); the caller slices the word-padded
    feature axis back to the logical head dim.
    """
    r, g, f = q.shape
    p, l = k_words.shape[1:]
    assert p * _WORD == 4 * f and k_words.shape == v_words.shape, (
        q.shape, k_words.shape, v_words.shape)
    assert bias.shape == (r, g, l), (bias.shape, (r, g, l))
    kernel = functools.partial(
        _plane_attn_kernel, sm_scale=sm_scale, signed=signed
    )
    return pl.pallas_call(
        kernel,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, g, f), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, g, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, p, l), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, l), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, p, l), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, l), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, g, l), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, f), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, g, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(q, q_scale, k_words, k_scale, v_words, v_scale, bias)
