"""Bit-serial dot-product (BSDP) Pallas kernel — faithful port of §IV Alg. 2.

Inputs are bit-plane encoded (see :mod:`repro.core.bitplane`): activations
``x_planes [M, 4, Kw]`` and weights ``w_planes [N, 4, Kw]`` as uint32 words,
``Kw = K/32``.  Each grid step stages a ``(bm, 4, bkw)`` activation tile and
a ``(bn, 4, bkw)`` weight tile into VMEM and computes the 16 plane-pair
terms:

    acc[m, n] += Σ_{j,k} s_jk · (popcount(x[m,j,:] & w[n,k,:]) · 2^{j+k})

with ``s_jk = -1`` iff exactly one of j,k == 3 (signed int4 two's
complement), +1 otherwise.  ``popcount`` is ``lax.population_count`` — the
VPU analogue of UPMEM's ``cao`` instruction; the shift-accumulate mirrors
``lsl_add``.  The j/k loops are Python-level (fully unrolled at trace time),
exactly like the paper's fully-unrolled Algorithm 2.

The K (word) axis is the innermost grid dimension so the int32 accumulator
tile persists in VMEM scratch across the sweep.

This kernel is the *faithful* UPMEM adaptation; the MXU reformulation
(bit-planes as ±2^j-scaled int8 matrices contracted on the MXU) lives in
``repro.core.bsdp.bsdp_matmul_planes`` and wins at large N — §Perf in
EXPERIMENTS.md quantifies the crossover.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bsdp import plane_signs


def _bsdp_kernel(x_ref, w_ref, o_ref, acc_ref, *, signed: bool):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # [bm, 4, bkw] uint32
    w = w_ref[...]  # [bn, 4, bkw] uint32
    signs = plane_signs(signed)
    acc = acc_ref[...]
    for j in range(4):  # fully unrolled, as in the paper
        xj = x[:, j, :]  # [bm, bkw]
        for k in range(4):
            wk = w[:, k, :]  # [bn, bkw]
            matches = xj[:, None, :] & wk[None, :, :]  # [bm, bn, bkw]
            popc = jax.lax.population_count(matches).astype(jnp.int32)
            term = jnp.sum(popc, axis=-1) << (j + k)  # lsl_add analogue
            acc = acc + (term if signs[j][k] > 0 else -term)
    acc_ref[...] = acc

    @pl.when(k_step == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bkw", "signed", "interpret")
)
def bsdp_matmul(
    x_planes: jax.Array,
    w_planes: jax.Array,
    *,
    bm: int = 8,
    bn: int = 128,
    bkw: int = 256,
    signed: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """``x_planes [M,4,Kw] × w_planes [N,4,Kw] → [M,N] int32`` (exact).

    ``bkw`` must be a multiple of 128 or the whole word axis (the TPU
    block rule for a minor dim).  A ``(8, 128, 192)`` step (K = 6144)
    touches 8·4·192·4B + 128·4·192·4B = 417 KB of planes and a 4 KB
    accumulator; each plane pair's AND+popcount works on a
    ``[8, 128, 192]`` uint32 transient (768 KiB).
    """
    m, px, kw = x_planes.shape
    n, pw, kw2 = w_planes.shape
    assert px == 4 and pw == 4 and kw == kw2, (x_planes.shape, w_planes.shape)
    assert m % bm == 0 and n % bn == 0 and kw % bkw == 0, (
        x_planes.shape,
        w_planes.shape,
        (bm, bn, bkw),
    )

    kernel = functools.partial(_bsdp_kernel, signed=signed)
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
        interpret=interpret,
    )(x_planes, w_planes)
