"""Per-kernel shape/dtype sweeps: every Pallas kernel vs its pure-jnp oracle.

All integer paths assert EXACT equality; float epilogues use tolerances that
account for accumulation-order differences (scale-after-sum vs
scale-before-sum reassociation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import bitplane, quant
from repro.kernels import ops, ref

jax.config.update("jax_platform_name", "cpu")

# (M, K, N) sweep: aligned, unaligned, GEMV-shaped, tall/wide.
SHAPES = [
    (1, 128, 128),      # single-token GEMV, aligned
    (1, 300, 513),      # GEMV, unaligned everything
    (8, 256, 128),      # small batch decode
    (16, 512, 256),     # block-multiple
    (17, 96, 130),      # all dims unaligned
    (128, 128, 128),    # one full tile
    (130, 1024, 64),    # K > block, N < block
]


def _rand_int8(rng, shape, lo=-128, hi=128):
    return jnp.array(rng.integers(lo, hi, size=shape).astype(np.int8))


class TestQuantMatmulInt8:
    @pytest.mark.parametrize("m,k,n", SHAPES)
    def test_exact_int32(self, m, k, n):
        rng = np.random.default_rng(m + k + n)
        x, w = _rand_int8(rng, (m, k)), _rand_int8(rng, (k, n))
        out = ops.matmul_int8_raw(x, w)
        assert out.dtype == jnp.int32
        assert bool(jnp.all(out == ref.matmul_int8_ref(x, w)))

    @pytest.mark.parametrize("m,k,n", [(1, 128, 128), (17, 96, 130), (16, 512, 256)])
    def test_scaled_f32(self, m, k, n):
        rng = np.random.default_rng(m * 7 + k + n)
        x = jnp.array(rng.normal(size=(m, k)).astype(np.float32))
        w = jnp.array(rng.normal(size=(k, n)).astype(np.float32))
        xq, wq = quant.quantize_acts(x), quant.quantize_weights(w)
        out = ops.quant_matmul(xq, wq)
        exp = ref.matmul_int8_scaled_ref(
            xq.data, wq.data, xq.scale.reshape(m, 1), wq.scale.reshape(1, n)
        )
        np.testing.assert_allclose(np.array(out), np.array(exp), rtol=1e-6, atol=1e-6)

    def test_block_size_invariance(self):
        """Result must not depend on tiling — catches accumulation bugs."""
        rng = np.random.default_rng(11)
        x, w = _rand_int8(rng, (32, 512)), _rand_int8(rng, (512, 256))
        base = ref.matmul_int8_ref(x, w)
        for bm, bn, bk in [(8, 128, 128), (32, 128, 512), (16, 256, 256)]:
            out = ops.matmul_int8_raw(x, w, bm=bm, bn=bn, bk=bk)
            assert bool(jnp.all(out == base)), (bm, bn, bk)

    def test_approximates_float_matmul(self):
        """End-to-end W8A8 error vs the float matmul it replaces."""
        rng = np.random.default_rng(12)
        x = jnp.array(rng.normal(size=(16, 1024)).astype(np.float32))
        w = jnp.array(rng.normal(size=(1024, 128)).astype(np.float32) / 32)
        out = ops.quant_matmul(quant.quantize_acts(x), quant.quantize_weights(w))
        exact = x @ w
        rel = np.abs(np.array(out - exact)) / (np.abs(np.array(exact)) + 1e-3)
        assert np.median(rel) < 0.02  # int8 quantization noise regime


class TestQuantMatmulInt4Packed:
    @pytest.mark.parametrize("m,k,n", [(1, 128, 128), (4, 96, 130), (16, 512, 256), (17, 300, 64)])
    def test_exact_vs_oracle(self, m, k, n):
        rng = np.random.default_rng(m + 2 * k + 3 * n)
        x = _rand_int8(rng, (m, k))
        q4 = _rand_int8(rng, (k, n), -8, 8)
        wp = quant.pack_int4(q4, axis=0)
        ones_m = jnp.ones((m, 1), jnp.float32)
        ones_n = jnp.ones((1, n), jnp.float32)
        xq = quant.QuantTensor(data=x, scale=ones_m, bits=8, axis=-1)
        out = ops.quant_matmul_int4(xq, wp, ones_n)
        exp = ref.matmul_int4_packed_ref(x, wp).astype(jnp.float32)
        np.testing.assert_allclose(np.array(out), np.array(exp), rtol=0, atol=0)

    def test_packed_matches_unpacked_path(self):
        rng = np.random.default_rng(13)
        x = _rand_int8(rng, (8, 256))
        q4 = _rand_int8(rng, (256, 128), -8, 8)
        wp = quant.pack_int4(q4, axis=0)
        exp = ref.matmul_int8_ref(x, q4)
        got = ref.matmul_int4_packed_ref(x, wp)
        assert bool(jnp.all(got == exp))


class TestBsdpKernel:
    @pytest.mark.parametrize("m,k,n", [(1, 32, 1), (1, 2048, 128), (8, 320, 130), (5, 64, 7)])
    @pytest.mark.parametrize("signed", [True, False])
    def test_exact(self, m, k, n, signed):
        rng = np.random.default_rng(m + k + n + signed)
        lo, hi = (-8, 8) if signed else (0, 16)
        a = _rand_int8(rng, (m, k), lo, hi)
        w = _rand_int8(rng, (k, n), lo, hi)
        wp = bitplane.encode_weights(w)
        out = ops.bsdp_gemv(a, wp, signed=signed)
        assert bool(jnp.all(out == ref.bsdp_ref(a, w)))

    def test_block_size_invariance(self):
        rng = np.random.default_rng(14)
        a = _rand_int8(rng, (8, 4096), -8, 8)
        w = _rand_int8(rng, (4096, 256), -8, 8)
        ap, wp = bitplane.encode(a), bitplane.encode_weights(w)
        base = ref.bsdp_ref(a, w)
        for bm, bn, bkw in [(8, 128, 8), (8, 128, 64), (8, 256, 32)]:
            out = ops.bsdp_matmul_planes(ap, wp, bm=bm, bn=bn, bkw=bkw)
            assert bool(jnp.all(out == base)), (bm, bn, bkw)


def plane_attention_inputs(rng, b, h, g, l, feat, page):
    """Random operands of ``ops.plane_decode_attention`` over a shuffled
    page pool, and the same operands in each slot's logical ring order
    for ``ref.plane_decode_attention_ref``: int4 queries, encoded K/V
    planes, positive scales, query positions and stored positions with
    empty (-1) slots."""
    fw = -(-feat // 32)
    npp = l // page
    q = rng.integers(-8, 8, size=(b, h, g, feat)).astype(np.int8)
    q = np.pad(q, ((0, 0), (0, 0), (0, 0), (0, fw * 32 - feat)))

    def planes():  # [B, H, L, 4, Fw]
        vals = rng.integers(-8, 8, size=(b, h, l, feat)).astype(np.int8)
        return np.asarray(bitplane.encode(bitplane.pad_to_word(
            jnp.asarray(vals))))

    def pool(ring):  # [B, H, L, W] → pages [B·npp, H, W, page] via table
        x = ring.reshape(b, h, npp, page, -1).transpose(0, 2, 1, 4, 3)
        out = np.empty_like(x.reshape(b * npp, h, -1, page))
        out[table.ravel()] = x.reshape(b * npp, h, -1, page)
        return jnp.asarray(out)

    table = rng.permutation(b * npp).reshape(b, npp).astype(np.int32)
    pos_ids = np.where(rng.random((b, l)) < 0.2, -1, np.arange(l)[None])
    q_pos = rng.integers(0, l, size=(b, g)).astype(np.int32)
    q_pos[:, 0] = l - 1  # the last row sees the whole ring
    k, v = planes(), planes()
    ks, vs = (rng.uniform(0.1, 1.0, (b, h, l)).astype(np.float32)
              for _ in range(2))
    qs = rng.uniform(0.1, 1.0, (b, h, g)).astype(np.float32)
    common = dict(q=jnp.asarray(q), q_scale=jnp.asarray(qs),
                  q_pos=jnp.asarray(q_pos),
                  pos_ids=jnp.asarray(pos_ids, jnp.int32))
    kernel = dict(
        common,
        k_words=pool(k.reshape(b, h, l, 4 * fw)), k_scale=pool(ks)[:, :, 0],
        v_words=pool(v.reshape(b, h, l, 4 * fw)), v_scale=pool(vs)[:, :, 0],
        table=jnp.asarray(table))
    oracle = dict(common, k_planes=jnp.asarray(k), k_scale=jnp.asarray(ks),
                  v_planes=jnp.asarray(v), v_scale=jnp.asarray(vs))
    return kernel, oracle


class TestPlaneAttention:
    @pytest.mark.parametrize("b,h,g,l,feat,page", [
        (2, 1, 2, 8, 16, 8),          # one word, word-pad slack, one page
        (2, 2, 6, 384, 128, 128),     # full head dim, three pages a slot
        (1, 3, 64, 256, 96, 256),     # a 32-token chunk, two blocks a page
    ])
    def test_matches_oracle(self, b, h, g, l, feat, page):
        rng = np.random.default_rng(b * 100 + g + l + feat)
        args, oracle = plane_attention_inputs(rng, b, h, g, l, feat, page)
        out = ops.plane_decode_attention(**args, sm_scale=0.125, feat=feat)
        exp = ref.plane_decode_attention_ref(**oracle, sm_scale=0.125,
                                             feat=feat)
        assert out.shape == (b, h, g, feat)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(exp), rtol=1e-4, atol=1e-4)


class TestDimKernel:
    @pytest.mark.parametrize("m,k,n", [(1, 128, 128), (4, 96, 130), (16, 512, 256)])
    def test_exact_full_range(self, m, k, n):
        """Full int16 weight range incl. the 0x7FFF sign-edge cases."""
        rng = np.random.default_rng(m + k + n)
        x = _rand_int8(rng, (m, k))
        w = jnp.array(rng.integers(-32768, 32768, size=(k, n)).astype(np.int16))
        # plant the edge values the lo/hi decomposition can get wrong
        w = w.at[0, 0].set(32767).at[1, min(1, n - 1)].set(-32768).at[2 % k, 0].set(-1)
        out = ops.dim_matmul(x, w)
        assert bool(jnp.all(out == ref.dim_w16a8_ref(x, w)))

    def test_extreme_activations(self):
        x = jnp.full((8, 128), -128, jnp.int8)
        w = jnp.full((128, 128), 32767, jnp.int16)
        assert bool(jnp.all(ops.dim_matmul(x, w) == ref.dim_w16a8_ref(x, w)))


class TestWeightOnlyKernel:
    @pytest.mark.parametrize("m,k,n", [(1, 128, 128), (17, 300, 130), (16, 1024, 256)])
    def test_close_to_ref(self, m, k, n):
        rng = np.random.default_rng(m + k + n)
        x = jnp.array(rng.normal(size=(m, k)).astype(np.float32))
        w = jnp.array(rng.normal(size=(k, n)).astype(np.float32))
        wq = quant.quantize_weights(w)
        out = ops.weight_only_matmul(x, wq)
        exp = ref.dequant_matmul_ref(x, wq.data, wq.scale.reshape(1, n))
        # float reassociation between scale-in-epilogue vs scale-on-weights
        np.testing.assert_allclose(np.array(out), np.array(exp), rtol=1e-3, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=1, max_value=9),
    st.integers(min_value=1, max_value=6),
    st.integers(min_value=1, max_value=9),
    st.integers(min_value=0, max_value=2**31),
)
def test_property_int8_kernel_exact(m, kblocks, n, seed):
    """Pallas W8A8 == oracle for arbitrary small shapes (padding path)."""
    k = kblocks * 17  # deliberately non-aligned K
    rng = np.random.default_rng(seed)
    x = jnp.array(rng.integers(-128, 128, size=(m, k)).astype(np.int8))
    w = jnp.array(rng.integers(-128, 128, size=(k, n)).astype(np.int8))
    assert bool(jnp.all(ops.matmul_int8_raw(x, w) == ref.matmul_int8_ref(x, w)))
