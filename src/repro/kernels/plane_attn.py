"""Page-aware fused Pallas decode attention over bit-plane K/V pages.

The ``int4_bp`` cache formats store every cached position's K and V as
int4 values transposed into four uint32 bit-plane words per 32 features
(:mod:`repro.core.bitplane`).  The jnp decode path (the reference
semantics, :class:`repro.core.kvcache.BitPlaneCacheFormat`) computes the qk
scores on the planes and then dequantizes V for the av gather — three
separate XLA computations with the softmax in between.

This kernel fuses the whole decode-attention read into one Pallas pass and
reads the K/V pages where they lie:

1. **qk** — a page's K planes decode in VMEM to int4 values
   ``v = 1·b0 + 2·b1 + 4·b2 − 8·b3`` (VPU shift-and-mask), and ONE int8
   contraction against the int4-quantized query gives the exact integer
   scores — the same integers the jnp plane math produces, since every
   plane-pair form of Algorithm 2 sums to the integer dot product.  The
   per-vector query scale and the per-position K scales fold AFTER the
   integer contraction.
2. **mask** — built in the kernel from the page's stored positions and
   the query positions: a position counts when ``0 <= pos <= q_pos`` (and,
   with a window, ``pos > q_pos − window``).
3. **online softmax** (flash decoding) — a running max, sum and output
   per query row carry across the pages of a slot, so the kernel never
   holds more than one page of scores.
4. **av** — the per-position ``v_scale`` folds into the softmax weights,
   and ONE contraction against the decoded V values adds the page's share
   of the output.

Layout (the ``paged_int4_bp_fused`` pool, :mod:`repro.core.paging`): plane
words ``[pages, H, 4·Fw, page]`` — plane ``j``, word ``w`` in row
``j·Fw + w``, one cached position per lane — and scales ``[pages, H,
page]``.  A page of 128 positions is one dense ``[4·Fw, 128]`` tile per kv
head, so the pool needs no relayout on the way in.  Decoding word ``w``
yields features ``w·32 … w·32+31`` as 32 sublane rows, so the decoded
``[F, page]`` values keep the natural feature order with no lane shuffles.

Grid: ``(slots, blocks)``.  Step ``(b, j)`` reads lane block ``j`` of slot
``b``'s ring for all kv heads at once (``2 × H × 4·Fw × 128`` words: 128
KiB of K and V at 8 heads of 128 features).  The block table and each
slot's count of live blocks arrive as scalar prefetch: the index map
turns ``(b, j)`` into a physical page through the table, and steps past a
slot's last live block map to that block again, so the pipeline issues no
DMA for them and the body skips them.  An unpaged ring is the same pool
with one page per slot (an identity table).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_WORD = 32
#: positions one grid step reads per kv head (one lane tile)
BLOCK = 128
#: finite "minus infinity" of the running max (never produces NaN)
_NEG = -1e30

#: Scoped-VMEM limit.  A 128-position block keeps the working set near
#: 1 MiB; the headroom serves rings whose length is not a multiple of 128,
#: which take the whole ring as one block.
_VMEM_LIMIT = 64 * 1024 * 1024


def block_len(page: int) -> int:
    """Positions per grid step for pages of ``page`` positions: one lane
    tile where the page splits into them, else the whole page."""
    return BLOCK if page % BLOCK == 0 else page


def live_blocks(max_pos, size: int, n: int):
    """Blocks of ``size`` ring positions a slot reads, of its ``n``, when
    its latest query position is ``max_pos`` (numpy or jax arrays).

    A position ``p <= max_pos`` sits at ring slot ``p mod n·size``: before
    the ring wraps every valid position lies in the first ``max_pos //
    size + 1`` blocks; once it wraps, all ``n``; a slot with no query
    position (``max_pos < 0``) reads none."""
    return (max_pos // size + 1).clip(0, n)


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
    table_ref, live_ref,                     # scalar prefetch
    q_ref, qs_ref, qpos_ref, pos_ref, k_ref, ks_ref, v_ref, vs_ref,
    o_ref, m_ref, l_ref, acc_ref,
    *, sm_scale: float, window: Optional[int], signed: bool,
):
    del table_ref  # consumed by the index maps
    b, j = pl.program_id(0), pl.program_id(1)
    heads = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j < live_ref[b])
    def _page():
        pos = pos_ref[0, pl.ds(j, 1), :]  # [1, blk] stored positions
        qpos = qpos_ref[0]  # [G, 1]
        valid = (pos >= 0) & (pos <= qpos)  # [G, blk]
        if window is not None:
            valid &= pos > qpos - window
        def head(h, carry):
            kval = _decode_planes(k_ref[0, h], signed).astype(jnp.int8)
            # -- qk: one integer contraction, scales folded after it ----
            # (precision pinned: an ambient float precision such as
            # "highest" would reach this int8 matmul, which Mosaic refuses)
            s_int = jax.lax.dot_general(
                q_ref[0, h], kval,
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.int32,
            )  # [G, blk]
            scores = (s_int.astype(jnp.float32) * qs_ref[0, h]
                      * ks_ref[0, pl.ds(h, 1), :] * sm_scale)
            scores = jnp.where(valid, scores, _NEG)
            # -- online softmax over the slot's blocks ----------------
            m_prev = m_ref[h]  # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1,
                                                keepdims=True))
            p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = m_new
            # -- av: v_scale folds into the softmax weights -------------
            vval = _decode_planes(v_ref[0, h], signed).astype(jnp.float32)
            acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
                p * vs_ref[0, pl.ds(h, 1), :], vval,
                dimension_numbers=(((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )  # [G, F]
            return carry

        # a loop, not a Python unroll: the kernel body is traced and
        # lowered once per head count, not once per head
        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _out():
        l = l_ref[...]
        # a query row with no valid position reads 0, not 0/0
        o_ref[0] = acc_ref[...] / jnp.where(l > 0, l, 1.0)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "window", "signed", "interpret")
)
def plane_decode_attention(
    q: jax.Array,          # [B, H, G, F] int8, int4-valued, F = Fw·32
    q_scale: jax.Array,    # [B, H, G, 1] f32
    q_pos: jax.Array,      # [B, G, 1] int32 position of each query row
    k_words: jax.Array,    # [P, H, 4·Fw, page] uint32 page pool
    k_scale: jax.Array,    # [P, H, page] f32
    v_words: jax.Array,    # [P, H, 4·Fw, page] uint32
    v_scale: jax.Array,    # [P, H, page] f32
    pos_ids: jax.Array,    # [B, n_blocks, blk] int32 stored positions
    table: jax.Array,      # [B, pages_per_slot] int32 block table
    live: jax.Array,       # [B] int32 live blocks per slot
    *,
    sm_scale: float,
    window: Optional[int] = None,
    signed: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Page-aware plane-layout decode attention → ``[B, H, G, F] f32``.

    ``G`` is the folded (chunk × group) query axis.  Inputs are in the
    kernel layout described in the module docstring (``ops.
    plane_decode_attention`` builds the query side and the live counts);
    the caller slices the word-padded feature axis back to the logical
    head dim.  A query row with no valid position returns zeros.
    """
    b, h, g, f = q.shape
    _, _, p, page = k_words.shape
    n_blocks, blk = pos_ids.shape[1:]
    per_page = page // blk
    assert p * _WORD == 4 * f and k_words.shape == v_words.shape, (
        q.shape, k_words.shape, v_words.shape)
    assert n_blocks == table.shape[1] * per_page, (pos_ids.shape, table.shape)

    # steps past the last live block re-map to it: same block index, so
    # the pipeline fetches nothing for them
    def page_block(bi, j, table_ref, live_ref):
        jj = jnp.minimum(j, jnp.maximum(live_ref[bi] - 1, 0))
        return table_ref[bi, jj // per_page], jj % per_page

    def words_map(bi, j, table_ref, live_ref):
        pg, off = page_block(bi, j, table_ref, live_ref)
        return pg, 0, 0, off

    def scale_map(bi, j, table_ref, live_ref):
        pg, off = page_block(bi, j, table_ref, live_ref)
        return pg, 0, off

    def per_slot4(bi, j, table_ref, live_ref):
        return bi, 0, 0, 0

    def per_slot3(bi, j, table_ref, live_ref):
        return bi, 0, 0

    kernel = functools.partial(
        _plane_attn_kernel, sm_scale=sm_scale, window=window, signed=signed)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_blocks),
            in_specs=[
                pl.BlockSpec((1, h, g, f), per_slot4),
                pl.BlockSpec((1, h, g, 1), per_slot4),
                pl.BlockSpec((1, g, 1), per_slot3),
                pl.BlockSpec((1, n_blocks, blk), per_slot3),
                pl.BlockSpec((1, h, p, blk), words_map),
                pl.BlockSpec((1, h, blk), scale_map),
                pl.BlockSpec((1, h, p, blk), words_map),
                pl.BlockSpec((1, h, blk), scale_map),
            ],
            out_specs=pl.BlockSpec((1, h, g, f), per_slot4),
            scratch_shapes=[
                pltpu.VMEM((h, g, 1), jnp.float32),
                pltpu.VMEM((h, g, 1), jnp.float32),
                pltpu.VMEM((h, g, f), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, g, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(table, live, q, q_scale, q_pos, pos_ids, k_words, k_scale, v_words,
      v_scale)
