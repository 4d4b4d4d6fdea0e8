"""Residency-format registry + per-layer policy tests.

Two invariants anchor the registry design:

1. **Registry consistency** — for every registered format, the dry-run twin
   (``abstract_state``) must match the real ``encode`` output in shape and
   dtype, and byte accounting must be identical whether computed from real
   arrays, abstract structs, or the dry-run's registry-derived
   ``residency_qbytes`` — the property that killed the hand-maintained
   ``_QBYTES`` table's drift by construction.

2. **Per-layer mixed residency** — a policy map like
   ``{"ffn": "bsdp", "mixer": "w8a16"}`` converts exactly the selected
   subtrees, serves end-to-end through ``ServeEngine`` with logits inside
   int4 tolerance of bf16, and sums resident bytes correctly across the mix.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from repro.configs import get_smoke_config
from repro.core import qlinear, residency
from repro.models import model as model_lib
from repro.serve import engine
from repro.sharding import partitioning as P

jax.config.update("jax_platform_name", "cpu")

VOCAB = 128

# deliberately awkward K: exercises the int4 pair padding (odd → even) and
# the 32-element plane-word padding in the abstract/real comparison
K_ODDISH, N_SMALL = 72, 48


def _small():
    cfg = get_smoke_config("qwen3-1.7b").scaled(n_layers=2, vocab_size=VOCAB)
    params = P.materialize(model_lib.specs(cfg, 1), jax.random.PRNGKey(0))
    return cfg, params


class TestRegistryConsistency:
    """Satellite: abstract_state == encode by construction, per format."""

    @pytest.mark.parametrize("mode", residency.formats())
    def test_abstract_state_matches_encode(self, mode):
        rng = np.random.default_rng(0)
        w = jnp.array(rng.normal(size=(K_ODDISH, N_SMALL)).astype(np.float32))
        fmt = residency.get_format(mode)
        real = fmt.encode(w)
        ab = fmt.abstract_state(K_ODDISH, N_SMALL)
        assert real.data.shape == ab.data.shape, mode
        assert real.data.dtype == ab.data.dtype, mode
        assert real.scale.shape == ab.scale.shape
        assert real.scale.dtype == ab.scale.dtype
        assert (real.mode, real.k, real.n) == (ab.mode, ab.k, ab.n)

    @pytest.mark.parametrize("mode", residency.formats())
    def test_resident_bytes_identical_real_vs_abstract(self, mode):
        rng = np.random.default_rng(1)
        w = jnp.array(rng.normal(size=(K_ODDISH, N_SMALL)).astype(np.float32))
        fmt = residency.get_format(mode)
        real = fmt.encode(w)
        ab = fmt.abstract_state(K_ODDISH, N_SMALL)
        rb = fmt.resident_bytes(real)
        assert rb == fmt.resident_bytes(ab)
        assert rb == qlinear.resident_bytes(real)  # stable re-export agrees
        # the payload really is data+scales: byte-count the arrays directly
        assert rb == real.data.size * real.data.dtype.itemsize + \
            real.scale.size * real.scale.dtype.itemsize

    @pytest.mark.parametrize("mode", residency.formats())
    def test_qbytes_matches_dryrun_accounting(self, mode):
        """residency_qbytes (the _QBYTES replacement) == encoded payload
        bytes per element for aligned shapes — no drift possible."""
        from repro.launch.dryrun import residency_qbytes

        cfg, _ = _small()
        fmt = residency.get_format(mode)
        # every smoke quantizable leaf is >= 16 and 32-aligned, so the
        # walked weighted average collapses to the format's per-element rate
        wq = residency_qbytes(cfg, 1, mode, min_dim=16)
        assert wq == pytest.approx(fmt.qbytes())
        k, n = 256, 128  # aligned: no padding slack
        real = fmt.encode(jnp.ones((k, n), jnp.float32))
        assert wq == pytest.approx(
            real.data.size * real.data.dtype.itemsize / (k * n)
        )
        # the min_dim floor mirrors convert_params: below it every leaf
        # stays at its float spec dtype (bf16 here)
        assert residency_qbytes(cfg, 1, mode, min_dim=10**9) == pytest.approx(2.0)

    def test_dryrun_abstract_tree_matches_real_convert(self):
        """abstract_quant on the spec tree mirrors convert_params on real
        params leaf for leaf: same leaves converted (same min_dim floor —
        the smoke config's 32-wide kv projections stay float at 48), same
        payload shapes/dtypes."""
        from repro.launch.dryrun import abstract_quant

        cfg, params = _small()
        spec = {"ffn": "bsdp", "mixer": "w8a16", "default": "w8a8"}
        real = engine.convert_params(params, cfg, spec, min_dim=48)
        qtree = abstract_quant(model_lib.specs(cfg, 1), spec, min_dim=48)

        def states(tree):
            out = {}

            def walk(t, path):
                if isinstance(t, residency.QuantLinearState):
                    out[".".join(path)] = t
                elif isinstance(t, dict):
                    for k, v in t.items():
                        walk(v, path + (k,))

            walk(tree, ())
            return out

        rs, asrt = states(real), states(qtree)
        assert set(rs) == set(asrt) and rs, (set(rs), set(asrt))
        # the floor actually bit at 48: kv projections (K×32) stayed float
        assert not any(p.endswith(".wk") or p.endswith(".wv") for p in rs)
        for path, st in rs.items():
            ab = asrt[path]
            assert st.mode == ab.mode, path
            assert tuple(st.data.shape) == tuple(ab.data.shape), path
            assert st.data.dtype == jnp.dtype(ab.data.dtype), path

    @pytest.mark.parametrize("mode", residency.formats())
    def test_apply_jnp_matches_kernel_apply(self, mode):
        """Both apply paths are the same semantics (the old layers.dense
        duplication, now a per-format contract)."""
        rng = np.random.default_rng(2)
        w = jnp.array(rng.normal(size=(64, 128)).astype(np.float32))
        x = jnp.array(rng.normal(size=(3, 64)).astype(np.float32))
        st = residency.from_float(w, mode)
        out_kernel = residency.apply(st, x)
        out_jnp = residency.get_format(mode).apply_jnp(st, x)
        np.testing.assert_allclose(
            np.asarray(out_kernel, np.float32), np.asarray(out_jnp, np.float32),
            rtol=5e-2, atol=5e-2,
        )

    @pytest.mark.parametrize("mode", residency.formats())
    def test_to_float_supports_absorbed_decode(self, mode):
        rng = np.random.default_rng(3)
        w = jnp.array(rng.normal(size=(K_ODDISH, N_SMALL)).astype(np.float32))
        fmt = residency.get_format(mode)
        assert fmt.supports_absorbed_decode
        st = fmt.encode(w)
        back = np.asarray(fmt.to_float(st), np.float32)
        assert back.shape == (K_ODDISH, N_SMALL)
        # round-to-nearest error is bounded by scale/2 per output channel
        # (bf16 has unit scales; its mantissa rounding is far below 0.02)
        tol = 0.5 * float(np.max(np.asarray(st.scale))) + 0.02
        assert np.abs(back - np.asarray(w)).max() <= tol

    def test_kernel_policy_is_data(self):
        bsdp = residency.get_format("bsdp")
        faithful = residency.get_format("w4a4_bsdp")
        fused = residency.get_format("bsdp_fused")
        assert bsdp.kernel_policy.kernel_for(1) == "gemv"
        assert bsdp.kernel_policy.kernel_for(8) == "gemm"
        assert faithful.kernel_policy.kernel_for(8) == "gemv"
        assert fused.kernel_policy.kernel_for(1) == "gemv"
        assert fused.kernel_policy.kernel_for(8) == "gemm_fused"
        assert bsdp.is_bitplane and faithful.is_bitplane and fused.is_bitplane
        assert not residency.get_format("w8a8").is_bitplane

    def test_fused_format_keeps_bitplane_layout_contract(self):
        """bsdp_fused is pure KernelPolicy data over the SAME [N, 4, Kw]
        payload: abstract state, byte accounting and the data_axes sharding
        contract (N on the model axis, plane dims unsharded) are identical
        to bsdp — so every sharding/dry-run consumer is untouched."""
        bsdp = residency.get_format("bsdp")
        fused = residency.get_format("bsdp_fused")
        a, b = bsdp.abstract_state(K_ODDISH, N_SMALL), \
            fused.abstract_state(K_ODDISH, N_SMALL)
        assert a.data.shape == b.data.shape and a.data.dtype == b.data.dtype
        assert bsdp.qbytes() == fused.qbytes()
        assert bsdp.data_axes("k", "n") == fused.data_axes("k", "n") == \
            ("n", None, None)
        rng = np.random.default_rng(9)
        w = jnp.array(rng.normal(size=(64, 128)).astype(np.float32))
        # encodings are byte-identical: switching kernels never re-encodes
        np.testing.assert_array_equal(
            np.asarray(bsdp.encode(w).data), np.asarray(fused.encode(w).data))

    def test_unknown_format_raises(self):
        with pytest.raises(ValueError, match="unknown residency format"):
            residency.get_format("w3a3_nope")
        with pytest.raises(ValueError):
            residency.ResidencySpec.parse("ffn=w3a3_nope")

    def test_register_new_format_plugs_into_everything(self):
        """The ≤20-line extension story: a new format registers and
        immediately works through from_float/apply/dense and ServeEngine
        policy parsing with no call-site edits."""

        class HalfScaleBF16(residency.BF16Format):
            name = "bf16_halfscale"

            def encode(self, w):
                st = super().encode(w * 0.5)
                return residency.QuantLinearState(
                    data=st.data, scale=st.scale, mode=self.name,
                    k=st.k, n=st.n,
                )

        try:
            residency.register_format(HalfScaleBF16())
            w = jnp.ones((32, 16), jnp.float32)
            st = residency.from_float(w, "bf16_halfscale")
            out = residency.apply(st, jnp.ones((1, 32), jnp.float32))
            np.testing.assert_allclose(np.asarray(out), 16.0, rtol=1e-2)
            spec = residency.ResidencySpec.parse("ffn=bf16_halfscale")
            assert spec.mode_for("stack.slot0.ffn.w_in") == "bf16_halfscale"
            # back-compat surfaces see post-import registrations too
            assert "bf16_halfscale" in qlinear.MODES
            assert "bf16_halfscale" not in qlinear.BSDP_MODES
        finally:
            residency._REGISTRY.pop("bf16_halfscale", None)


class TestResidencySpec:
    def test_parse_forms_agree(self):
        d = residency.ResidencySpec.parse(
            {"ffn": "bsdp", "mixer": "w8a16", "default": "w8a8"}
        )
        s = residency.ResidencySpec.parse("ffn=bsdp,mixer=w8a16,default=w8a8")
        assert d == s
        assert residency.ResidencySpec.parse(d) is d
        assert residency.ResidencySpec.parse(s.describe()) == s

    def test_uniform_and_trivial(self):
        u = residency.ResidencySpec.parse("bsdp")
        assert u.is_uniform and not u.is_trivial and u.describe() == "bsdp"
        assert residency.ResidencySpec.parse("bf16").is_trivial
        assert residency.ResidencySpec.parse(None).is_trivial

    def test_glob_matching_first_wins(self):
        spec = residency.ResidencySpec.parse(
            "stack.slot0.ffn.*=w4a8,ffn=bsdp,default=w8a8"
        )
        assert spec.mode_for("stack.slot0.ffn.w_in") == "w4a8"
        assert spec.mode_for("prefix.layer0.ffn.w_out") == "bsdp"
        assert spec.mode_for("stack.slot0.mixer.wq") == "w8a8"
        assert spec.modes() == ("w4a8", "bsdp", "w8a8")


class TestMixedResidency:
    """Satellite: per-layer mixed residency end-to-end."""

    SPEC = {"ffn": "bsdp", "mixer": "w8a16", "default": "w8a8"}

    def test_convert_selects_formats_per_path(self):
        cfg, params = _small()
        qparams = engine.convert_params(params, cfg, self.SPEC, min_dim=16)
        modes = {}

        def walk(t, path=()):
            if isinstance(t, residency.QuantLinearState):
                modes[".".join(path)] = t.mode
            elif isinstance(t, dict):
                for k, v in t.items():
                    walk(v, path + (k,))

        walk(qparams)
        ffn = {p: m for p, m in modes.items() if ".ffn." in p}
        attn = {p: m for p, m in modes.items() if ".mixer." in p}
        assert ffn and set(ffn.values()) == {"bsdp"}
        assert attn and set(attn.values()) == {"w8a16"}

    def test_resident_bytes_sum_across_mix(self):
        cfg, params = _small()
        qparams = engine.convert_params(params, cfg, self.SPEC, min_dim=16)
        expected = 0
        for leaf in jax.tree_util.tree_leaves(
            qparams,
            is_leaf=lambda x: isinstance(x, residency.QuantLinearState),
        ):
            if isinstance(leaf, residency.QuantLinearState):
                expected += residency.get_format(leaf.mode).resident_bytes(leaf)
            else:
                expected += leaf.size * leaf.dtype.itemsize
        assert engine.resident_bytes(qparams) == expected
        # the mix sits strictly between all-bsdp and all-w8a16 totals
        lo = engine.resident_bytes(
            engine.convert_params(params, cfg, "bsdp", min_dim=16)
        )
        hi = engine.resident_bytes(
            engine.convert_params(params, cfg, "w8a16", min_dim=16)
        )
        assert lo < engine.resident_bytes(qparams) < hi

    def test_mixed_logits_within_quant_tolerance(self):
        """Mixed-policy prefill logits track bf16 (and each single-mode
        reference) within int4 quantization tolerance."""
        cfg, params = _small()
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.array(rng.integers(0, VOCAB, (1, 12)), jnp.int32)}
        ref, _ = model_lib.prefill(params, batch, cfg, tp=1, max_len=16, impl="jnp")
        outs = {}
        for spec in (self.SPEC, "bsdp", "w8a16"):
            qp = engine.convert_params(params, cfg, spec, min_dim=16)
            out, _ = model_lib.prefill(qp, batch, cfg, tp=1, max_len=16, impl="jnp")
            outs[str(spec)] = np.asarray(out[0, -1], np.float32)
        r = np.asarray(ref[0, -1], np.float32)
        scale = np.abs(r).max() + 1e-6
        for name, o in outs.items():
            assert np.abs(r - o).max() / scale < 0.5, name
            cos = float(r @ o / (np.linalg.norm(r) * np.linalg.norm(o) + 1e-9))
            assert cos > 0.9, (name, cos)

    def test_mixed_serves_end_to_end_vs_bf16(self):
        """Acceptance: a mixed per-layer policy through ServeEngine —
        identical teacher-forced schedule, logits inside int4 tolerance."""
        cfg, params = _small()

        def run(mode):
            rng = np.random.default_rng(0)
            eng = engine.ServeEngine(
                params, cfg, slots=2, max_len=32, mode=mode, min_dim=16,
                trace_logits=True,
            )
            for n, mn in zip((5, 3, 7), (5, 2, 4)):
                eng.submit(
                    rng.integers(0, VOCAB, size=(n,)).astype(np.int32), mn,
                    force=rng.integers(0, VOCAB, size=(mn,)).astype(np.int32),
                )
            eng.run()
            return eng

        ref = run("bf16")
        mix = run(self.SPEC)
        assert mix.mode == "ffn=bsdp,mixer=w8a16,default=w8a8"
        assert [(k, s) for k, s, _ in ref.logit_trace] == \
            [(k, s) for k, s, _ in mix.logit_trace]
        assert sum(1 for k, _, _ in mix.logit_trace if k == "decode") >= 3
        for (_, _, lr), (_, _, lb) in zip(ref.logit_trace, mix.logit_trace):
            lr, lb = np.asarray(lr, np.float32), np.asarray(lb, np.float32)
            scale = np.abs(lr).max() + 1e-6
            assert np.abs(lr - lb).max() / scale < 0.5
            cos = float(
                (lr.ravel() @ lb.ravel())
                / (np.linalg.norm(lr) * np.linalg.norm(lb) + 1e-9)
            )
            assert cos > 0.9, cos

    def test_sharded_bsdp_and_cache_specs_on_two_axis_mesh(self):
        """ROADMAP item (multi-host sharded BSDP residency): on a 2-axis
        (data, model) mesh, the dry-run's ``abstract_quant`` PartitionSpecs
        for bsdp weights must follow ``BitPlaneFormat.data_axes`` (N on the
        model axis, packed plane dims replicated) and the int4_bp cache
        specs must follow ``cache_axes_table`` — validated end-to-end by
        lowering a decode step over ``jax.eval_shape`` inputs."""
        import dataclasses

        from repro.launch import dryrun
        from repro.launch.mesh import make_mesh
        from repro.models.attention import attn_dims

        if jax.device_count() < 4:
            pytest.skip("needs 4 host devices")
        mesh = make_mesh((2, 2), ("data", "model"))
        tp = 2
        cfg = dataclasses.replace(
            get_smoke_config("qwen3-1.7b").scaled(n_layers=2),
            cache_format="int4_bp",
        )
        rules = P.base_rules(data_axes=("data",))
        spec_tree = model_lib.specs(cfg, tp)

        # weight side: abstract_quant pspecs == BitPlaneFormat.data_axes
        qtree = dryrun.abstract_quant(spec_tree, "bsdp", min_dim=16)
        st = qtree["stack"]["slot0"]["ffn"]["w_in"]
        assert isinstance(st, residency.QuantLinearState)
        fmt = residency.get_format("bsdp")
        assert st.data.axes == ("layers",) + fmt.data_axes("embed", "mlp")
        assert P.spec_for(st.data.axes, rules) == \
            PartitionSpec(None, "model", None, None)  # N sharded, planes not

        # cache side: pspecs derive from BitPlaneCacheFormat.data_axes
        from repro.core import kvcache

        table = P.cache_axes_table(cfg)
        bp = kvcache.get_cache_format("int4_bp")
        assert table["k"] == ("batch", "kv_seq") + \
            tuple(bp.data_axes(("kv_heads_cache",))[""])

        # end-to-end: the decode cell lowers under these shardings
        params_abs, params_sh = dryrun._serve_params(
            spec_tree, "bsdp", rules, min_dim=16)
        b = 4
        cache_abs = jax.eval_shape(
            lambda: model_lib.init_cache(cfg, b, 16, tp=tp))
        _, _, shard_kv = attn_dims(cfg, tp)
        cache_sh = P.cache_pspecs(cache_abs, rules, shard_kv, cfg)
        tok_abs = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        pos_abs = jax.ShapeDtypeStruct((b,), jnp.int32)
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                lambda p, t, c, pos: model_lib.decode_step(
                    p, t, c, pos, cfg, tp=tp, rules=rules, impl="jnp"),
                in_shardings=(params_sh, P.spec_for(("batch", None), rules),
                              cache_sh, P.spec_for(("batch",), rules)),
            )
            compiled = jitted.lower(
                params_abs, tok_abs, cache_abs, pos_abs).compile()
        assert compiled is not None

    def test_moe_expert_path_handles_mixed_leaves(self):
        """vmapped expert FFN with w_in quantized and w_out float (and the
        reverse) — the registry dispatches per leaf inside the vmap."""
        from repro.models import moe

        cfg = get_smoke_config("mixtral-8x7b").scaled(
            n_layers=2, vocab_size=64
        )
        specs = moe.moe_specs(cfg)
        params = P.materialize(specs, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        x = jnp.array(rng.normal(size=(1, 8, cfg.d_model)).astype(np.float32))
        ref, _ = moe.moe_apply(params, x, cfg, capacity_factor=8.0)
        for keys in (("w_in",), ("w_out",), ("w_in", "w_out")):
            p = dict(params)
            for key in keys:
                p[key] = engine._convert_leaf(params[key], "w8a8", 1)
                assert isinstance(p[key], residency.QuantLinearState)
            out, _ = moe.moe_apply(p, x, cfg, capacity_factor=8.0)
            err = np.abs(np.asarray(out) - np.asarray(ref)).max()
            assert err / (np.abs(np.asarray(ref)).max() + 1e-6) < 0.2, keys
