"""Multi-device tests: sharded train equivalence, compressed collectives,
pipeline parallelism, resilience/elastic planning.

Runs on 8 forced host devices (see conftest.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.configs import get_smoke_config
from repro.distributed import collectives, pipeline, resilience
from repro.launch.mesh import make_mesh
from repro.models import model as model_lib
from repro.sharding import partitioning as P

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 host devices"
)


def _mesh():
    return make_mesh((2, 2, 2), ("pod", "data", "model"))


class TestShardedTraining:
    def test_sharded_loss_matches_single_device(self):
        """The same model+batch must produce identical loss under a
        (pod,data,model) mesh with TP sharding as on one device."""
        cfg = get_smoke_config("qwen3-1.7b")
        params = P.materialize(model_lib.specs(cfg, tp=1), jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.array(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32),
            "labels": jnp.array(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32),
        }
        l_single, _ = model_lib.loss_fn(params, batch, cfg, tp=1)

        mesh = _mesh()
        rules = P.base_rules(fsdp=False, data_axes=("pod", "data"))
        spec_tree = model_lib.specs(cfg, tp=1)  # dims divisible by tp=2
        with jax.set_mesh(mesh):
            params_sh = jax.device_put(params, P.shardings(spec_tree, mesh, rules))
            batch_sh = {
                k: jax.device_put(
                    v, NamedSharding(mesh, PS(("pod", "data"))))
                for k, v in batch.items()
            }
            loss_fn = jax.jit(
                lambda p, b: model_lib.loss_fn(p, b, cfg, tp=1, rules=rules)[0]
            )
            l_sharded = loss_fn(params_sh, batch_sh)
        np.testing.assert_allclose(
            float(l_single), float(l_sharded), rtol=2e-2, atol=1e-3
        )

    def test_fsdp_rules_shard_params(self):
        cfg = get_smoke_config("qwen3-1.7b")
        mesh = _mesh()
        rules = P.base_rules(fsdp=True, data_axes=("pod", "data"))
        spec_tree = model_lib.specs(cfg, tp=1)
        sh = P.shardings(spec_tree, mesh, rules)
        wq = sh["stack"]["slot0"]["mixer"]["wq"]
        assert "data" in str(wq.spec)  # FSDP sharding present


class TestCompressedCollectives:
    def test_compressed_psum_error_bound(self):
        mesh = _mesh()
        rng = np.random.default_rng(1)
        x = jnp.array(rng.normal(size=(64, 32)).astype(np.float32))
        exact = x  # value replicated across pods -> mean == itself
        out = collectives.compressed_psum_tree({"g": x}, mesh, "pod")["g"]
        # per-chunk quantization error <= scale/2; scale ~ max|x|/127
        bound = float(jnp.max(jnp.abs(x))) / 127
        assert float(jnp.max(jnp.abs(out - exact))) <= bound + 1e-6

    def test_compression_ratio(self):
        r = collectives.compression_ratio((1024, 1024))
        assert 3.5 < r < 4.1  # ~3.94x vs f32

    def test_distinct_values_average(self):
        """Shards differing across the pod axis must average."""
        mesh = _mesh()

        from functools import partial

        @partial(jax.shard_map, mesh=mesh, in_specs=(PS("pod"),),
                 out_specs=PS("pod"), check_vma=False)
        def run(v):
            return collectives.compressed_psum(v[0], "pod")[None]

        x = jnp.stack([jnp.full((8, 16), 1.0), jnp.full((8, 16), 3.0)])
        out = run(x)
        np.testing.assert_allclose(np.asarray(out[0]), 2.0, atol=2.0 / 127 + 1e-6)
        np.testing.assert_allclose(np.asarray(out[1]), 2.0, atol=2.0 / 127 + 1e-6)


class TestPipeline:
    def test_pipeline_matches_sequential(self):
        """GPipe schedule == sequential stage application."""
        mesh = jax.make_mesh((4, 2), ("pipe", "data"))
        p_stages = 4
        rng = np.random.default_rng(2)
        ws = jnp.array(rng.normal(size=(p_stages, 16, 16)) / 4, jnp.float32)
        xs = jnp.array(rng.normal(size=(8, 4, 16)), jnp.float32)  # 8 microbatches

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        out = pipeline.pipeline_apply(stage_fn, ws, xs, mesh, axis="pipe")
        # sequential reference
        ref = xs
        for i in range(p_stages):
            ref = jax.vmap(lambda h: stage_fn(ws[i], h))(ref)
        np.testing.assert_allclose(np.array(out), np.array(ref), rtol=1e-5, atol=1e-5)

    def test_split_stages(self):
        tree = {"w": jnp.zeros((8, 3, 3))}
        out = pipeline.split_stages(tree, 4)
        assert out["w"].shape == (4, 2, 3, 3)


class TestResilience:
    def test_watchdog_flags_straggler(self):
        wd = resilience.StepWatchdog(ratio=2.0)
        for i in range(10):
            wd.observe(i, 1.0)
        rep = wd.observe(10, 5.0)
        assert rep.straggler
        assert wd.straggler_steps == [10]
        # baseline not polluted by the straggler
        assert abs(wd.ewma - 1.0) < 0.1

    def test_failure_sim_fires_once(self):
        sim = resilience.FailureSim(fail_at=(3,))
        for i in range(3):
            sim.check(i)
        with pytest.raises(resilience.SimulatedFailure):
            sim.check(3)
        sim.check(3)  # second pass: already consumed

    def test_elastic_mesh_plan(self):
        assert resilience.plan_elastic_mesh(512, model_parallel=16) == (32, 16)
        assert resilience.plan_elastic_mesh(240, model_parallel=16) == (15, 16)
        assert resilience.plan_elastic_mesh(8, model_parallel=16) is None
