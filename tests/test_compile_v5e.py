"""Compile the main-path kernels for a described TPU v5e at qwen3-1.7b widths.

Interpret mode accepts block shapes, reshapes and VMEM footprints that the
TPU compiler (Mosaic) refuses.  These tests lower each kernel for a v5e
chip that is described, not attached, and compile it — nothing runs, so
they say nothing about results or speed, only that the chip's compiler
takes the program.  This is the only file that describes a TPU topology:
the description loads the TPU library, which one process holds at a time,
so it happens inside a fixture of this file and nowhere at import.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant import QuantTensor
from repro.kernels import ops

D_MODEL, D_FF = 2048, 6144  # qwen3-1.7b projection widths
N_KV, G, D_HEAD = 8, 2, 128  # GQA 16/8: two query rows per kv head


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


PROJECTIONS = [(D_MODEL, D_FF), (D_FF, D_MODEL)]


@pytest.mark.parametrize("m", [1, 8, 128])
@pytest.mark.parametrize("k,n", PROJECTIONS)
def test_quant_matmul_compiles(one_chip, m, k, n):
    def fn(x, xs, w, ws):
        return ops.quant_matmul(
            QuantTensor(x, xs, 8, -1), QuantTensor(w, ws, 8, 0),
            interpret=False)

    _compile(fn, one_chip, ((m, k), jnp.int8), ((m, 1), jnp.float32),
             ((k, n), jnp.int8), ((1, n), jnp.float32))


@pytest.mark.parametrize("kernel,m", [
    ("gemv", 1), ("gemm_fused", 1), ("gemm_fused", 8), ("gemm_fused", 128)])
@pytest.mark.parametrize("k,n", PROJECTIONS)
def test_bsdp_compiles(one_chip, kernel, m, k, n):
    def fn(xp, wp):
        return ops.bsdp_matmul_planes(xp, wp, kernel=kernel, interpret=False)

    _compile(fn, one_chip, ((m, 4, k // 32), jnp.uint32),
             ((n, 4, k // 32), jnp.uint32))


@pytest.mark.parametrize("chunk,precision", [
    (1, None), (32, None),
    (1, "highest"),  # an ambient float precision must not reach the int8 dot
])
def test_plane_attention_compiles_at_ring_1024(one_chip, chunk, precision):
    """Four slots of a 1024-slot ring, one decode token or a 32-token
    chunk per slot."""
    r, g, ln, fw = 4 * N_KV, G * chunk, 1024, D_HEAD // 32

    def fn(q, qs, kp, ks, vp, vs, bias):
        with jax.default_matmul_precision(precision):
            return ops.plane_decode_attention(
                q, qs, kp, ks, vp, vs, bias, sm_scale=D_HEAD ** -0.5,
                feat=D_HEAD, interpret=False)

    compiled = _compile(
        fn, one_chip, ((r, g, D_HEAD), jnp.int8), ((r, g), jnp.float32),
        ((r, ln, 4, fw), jnp.uint32), ((r, ln), jnp.float32),
        ((r, ln, 4, fw), jnp.uint32), ((r, ln), jnp.float32),
        ((r, g, ln), jnp.float32))
    assert compiled.memory_analysis() is not None
