"""Compile the main-path kernels for a described TPU v5e at qwen3-1.7b widths.

Interpret mode accepts block shapes, reshapes and VMEM footprints that the
TPU compiler (Mosaic) refuses.  These tests lower each kernel for a v5e
chip that is described, not attached, and compile it — nothing runs, so
they say nothing about results or speed, only that the chip's compiler
takes the program (and, for the served decode step, how much temporary
memory it asks for).  This is the only file that describes a TPU topology:
the description loads the TPU library, which one process holds at a time,
so it happens inside a fixture of this file and nowhere at import.
"""

import math
import re

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


def _plane_attention_shapes(slots, chunk, ln, page):
    """Operand shapes of ``ops.plane_decode_attention`` for ``slots``
    slots of an ``ln``-position ring in ``page``-position pages."""
    g, fw, npp = G * chunk, D_HEAD // 32, ln // page
    pool = slots * npp
    return (((slots, N_KV, g, D_HEAD), jnp.int8),
            ((slots, N_KV, g), jnp.float32), ((slots, g), jnp.int32),
            ((pool, N_KV, 4 * fw, page), jnp.uint32),
            ((pool, N_KV, page), jnp.float32),
            ((pool, N_KV, 4 * fw, page), jnp.uint32),
            ((pool, N_KV, page), jnp.float32),
            ((slots, ln), jnp.int32), ((slots, npp), jnp.int32))


def _plane_attention(q, qs, qp, kw, ks, vw, vs, pos, table):
    return ops.plane_decode_attention(
        q, qs, qp, kw, ks, vw, vs, pos, table, sm_scale=D_HEAD ** -0.5,
        feat=D_HEAD, interpret=False)


@pytest.mark.parametrize("chunk,precision", [
    (1, None), (32, None),
    (1, "highest"),  # an ambient float precision must not reach the int8 dot
])
def test_plane_attention_compiles_at_ring_1024(one_chip, chunk, precision):
    """Four slots of an unpaged 1024-slot ring (one page per slot), one
    decode token or a 32-token chunk per slot."""
    def fn(*args):
        with jax.default_matmul_precision(precision):
            return _plane_attention(*args)

    compiled = _compile(fn, one_chip,
                        *_plane_attention_shapes(4, chunk, 1024, 1024))
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("chunk", [1, 32])
def test_paged_plane_attention_compiles(one_chip, chunk):
    """The served shape: 8 slots of a 4096-position ring in 128-position
    pages, read in place through the block table."""
    compiled = _compile(_plane_attention, one_chip,
                        *_plane_attention_shapes(8, chunk, 4096, 128))
    assert "plane_decode_attention" in compiled.as_text()


@pytest.mark.parametrize("chunk", [1, 32])
def test_paged_decode_step_keeps_the_pool_in_place(one_chip, monkeypatch,
                                                   chunk):
    """A 2-layer decode step at qwen3-1.7b widths under
    ``paged_int4_bp_fused`` (8 slots × 4096, ``w8a8`` weights, a bf16
    embedding as served): no instruction gathers a whole pool leaf, and
    the step's temporaries stay under 8× one layer's cache — a padded
    relayout of the pool (16× a layer's K or V) cannot hide in them."""
    import dataclasses

    from repro.configs import get_config
    from repro.core import kvcache, residency
    from repro.models import model as model_lib
    from repro.serve import engine
    from repro.sharding import partitioning as P

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)  # Mosaic, not interpret
    slots, layers = 8, 2
    cfg = dataclasses.replace(get_config("qwen3-1.7b"), n_layers=layers,
                              cache_format="paged_int4_bp_fused")
    spec = residency.ResidencySpec.parse("w8a8")
    params = jax.eval_shape(lambda: engine.convert_params(
        P.materialize(model_lib.specs(cfg, 1), jax.random.PRNGKey(0)),
        cfg, spec))
    embed = params["embed"]["embedding"]
    params["embed"]["embedding"] = jax.ShapeDtypeStruct(embed.shape,
                                                        jnp.bfloat16)
    caches = jax.eval_shape(lambda: model_lib.init_cache(cfg, slots, 4096,
                                                         tp=1))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    tok = jax.ShapeDtypeStruct((slots, chunk), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, t, c, pos: model_lib.decode_step(p, t, c, pos, cfg, tp=1,
                                                   impl="jnp")
    ).lower(jax.tree.map(on_chip, params), tok,
            jax.tree.map(on_chip, caches), tok).compile()
    text = compiled.as_text()
    assert "plane_decode_attention" in text
    layer_bytes = kvcache.cache_resident_bytes(caches) // layers
    pool = caches["stack"]["slot0"]["k"]  # [layers, pages, H, 4·Fw, page]
    pool_elems = pool.size // layers
    for shape in re.findall(r"= \w+\[([\d,]+)\][^ ]* gather\(", text):
        assert math.prod(int(d) for d in shape.split(",")) < pool_elems
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * layer_bytes
