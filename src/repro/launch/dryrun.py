"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver:
  1. builds the production mesh (16×16 single-pod or 2×16×16 multi-pod),
  2. derives the per-arch parallelism plan (launch/mesh.py),
  3. constructs ABSTRACT inputs (ShapeDtypeStructs — zero allocation:
     params via ParamSpec metadata, caches via jax.eval_shape),
  4. ``jax.jit(step, in_shardings=…).lower(...).compile()``,
  5. records memory_analysis (fits-in-HBM proof), cost_analysis
     (FLOPs/bytes) and the parsed collective wire bytes into a JSON record
     consumed by benchmarks/roofline.py and EXPERIMENTS.md.

Scan-correct costing: XLA's HloCostAnalysis counts a ``while`` body ONCE
(verified experimentally — see EXPERIMENTS.md §Dry-run), so the scanned
production program under-reports FLOPs by ~n_superblocks×.  The driver
therefore lowers two PROBE programs per cell — identical math with the
stack unrolled at depth 1 and depth 2 and inner scans collapsed — and
differences them:

    body  = probe(2) - probe(1)          # one superblock (incl. its remat,
                                         #   grads, opt slice, collectives)
    fixed = probe(1) - body              # embed/logits/loss/opt once
    total = microbatches × (fixed + n_superblocks × body)

(enc-dec archs add a third probe at encoder depth 2 for the encoder-body
term).  The probe-vs-unrolled validation test lives in
tests/test_dryrun_small.py.

Usage:
    python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k \
        --mesh pod --out results/dryrun
    python -m repro.launch.dryrun --all --mesh both     # the full matrix
"""

import argparse
import dataclasses
import json
import os
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.configs import SHAPES, applicable_shapes, get_config
from repro.configs.base import ModelConfig, ShapeCell
from repro.core import kvcache, residency
from repro.launch import hlo_stats
from repro.launch.mesh import make_mesh, make_production_mesh, plan
from repro.models import model as model_lib
from repro.models.attention import attn_dims
from repro.optim import adamw as optim_lib
from repro.serve import scheduler as sched_lib
from repro.serve.engine import QUANTIZABLE_KEYS
from repro.sharding import partitioning as P
from repro.train.trainstep import TrainStepConfig, make_train_step

DECODE_HORIZON = 64  # decode cells: cache covers seq_len + a small horizon


# ---------------------------------------------------------------------------
# Abstract inputs + shardings
# ---------------------------------------------------------------------------


def batch_specs(cfg: ModelConfig, cell: ShapeCell, rules, batch_override=None):
    b = batch_override or cell.global_batch
    s = cell.seq_len
    abs_, sh = {}, {}
    abs_["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    sh["tokens"] = P.spec_for(("batch", "seq"), rules)
    if cell.kind == "train":
        abs_["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        sh["labels"] = sh["tokens"]
    if cfg.is_enc_dec:
        abs_["enc_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_tokens, cfg.d_model), jnp.float32
        )
        sh["enc_embeds"] = P.spec_for(("batch", None, None), rules)
    if cfg.family == "vlm":
        abs_["ctx_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_tokens, cfg.d_model), jnp.float32
        )
        sh["ctx_embeds"] = P.spec_for(("batch", None, None), rules)
    return abs_, sh


def cache_pspecs(cache_abs, rules, shard_kv: bool, cfg=None):
    """Cache PartitionSpecs — registry-derived, lives in
    :func:`repro.sharding.partitioning.cache_pspecs` (the K/V payload and
    scale axes come from the cache format's ``data_axes``, e.g. the
    ``int4_bp`` plane dims stay unsharded while kv-heads shard on the
    model axis)."""
    return P.cache_pspecs(cache_abs, rules, shard_kv, cfg)


def opt_shardings(spec_tree, rules):
    def mom(s):
        return optim_lib.Moment(P.spec_for(s.axes, rules), PartitionSpec())

    mu = jax.tree_util.tree_map(mom, spec_tree, is_leaf=P.is_spec)
    return optim_lib.AdamState(PartitionSpec(), mu, mu)


# ---------------------------------------------------------------------------
# Quantized-residency abstraction (serve cells, --qmode)
# ---------------------------------------------------------------------------


def abstract_quant(spec_tree, spec, *, min_dim: int = 64):
    """Residency-convert a ParamSpec tree WITHOUT materializing a weight.

    ``spec`` is any :meth:`ResidencySpec.parse` form (format name, policy
    dict, CLI string).  Mirrors :func:`repro.serve.engine.convert_params`
    leaf for leaf — the same dot-joined paths are policy-matched, the same
    ``min_dim`` floor leaves small projections float, and each selected
    format's ``abstract_state``/``data_axes`` supply the payload shapes,
    dtypes and sharding axes — so dry-run residency cannot drift from the
    real one.
    """
    spec = residency.ResidencySpec.parse(spec)

    def walk(tree, path):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for key, sub in tree.items():
            if key in QUANTIZABLE_KEYS and P.is_spec(sub) and len(sub.shape) >= 2:
                out[key] = _quant_leaf(
                    sub, spec.mode_for(".".join(path + (key,))), min_dim
                )
            else:
                out[key] = walk(sub, path + (key,)) if isinstance(sub, dict) else sub
        return out

    return walk(spec_tree, ())


def _quant_leaf(spec, mode: str, min_dim: int):
    fmt = residency.get_format(mode)
    if fmt.keeps_float_params:  # convert_params leaves these as plain floats
        return spec
    if min(spec.shape[-2:]) < min_dim:  # convert_params min_dim floor
        return spec

    *lead, k, n = spec.shape
    lead = tuple(lead)
    lead_axes = spec.axes[:-2]
    k_ax, n_ax = spec.axes[-2], spec.axes[-1]
    st = fmt.abstract_state(k, n)
    data = P.ParamSpec(
        lead + tuple(st.data.shape), st.data.dtype,
        lead_axes + tuple(fmt.data_axes(k_ax, n_ax)),
    )
    scale = P.ParamSpec(
        lead + tuple(st.scale.shape), st.scale.dtype,
        lead_axes + tuple(fmt.scale_axes(n_ax)),
    )
    return residency.QuantLinearState(data=data, scale=scale, mode=mode, k=k, n=n)


def _serve_params(spec_tree, qmode, rules, *, min_dim: int = 64):
    spec = residency.ResidencySpec.parse(qmode)
    if spec.is_trivial:
        return P.abstract(spec_tree), P.pspecs(spec_tree, rules)
    qtree = abstract_quant(spec_tree, spec, min_dim=min_dim)
    abs_tree = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), qtree, is_leaf=P.is_spec
    )
    sh_tree = jax.tree_util.tree_map(
        lambda s: P.spec_for(s.axes, rules), qtree, is_leaf=P.is_spec
    )
    return abs_tree, sh_tree


# ---------------------------------------------------------------------------
# Analytic parameter / model-flops accounting
# ---------------------------------------------------------------------------


def param_counts(cfg: ModelConfig, tp: int) -> dict:
    """(total, active) parameter counts from the spec tree (MoE-aware)."""
    spec_tree = model_lib.specs(cfg, tp)
    total = active = embed = 0
    k_over_e = (
        cfg.experts_per_tok / cfg.n_experts if cfg.n_experts else 1.0
    )

    def visit(path, s):
        nonlocal total, active, embed
        n = 1
        for d in s.shape:
            n *= d
        keys = [getattr(p, "key", None) for p in path]
        total += n
        if "embedding" in keys:
            embed += n
            return
        if "expert" in (s.axes or ()):  # routed expert weights
            active += n * k_over_e
        else:
            active += n

    jax.tree_util.tree_map_with_path(visit, spec_tree, is_leaf=P.is_spec)
    return {"total": total, "active": active, "embedding": embed}


def model_flops(cfg: ModelConfig, cell: ShapeCell, tp: int) -> float:
    """MODEL_FLOPS = 6·N_active·tokens (train) or 2·N_active·tokens (serve)."""
    pc = param_counts(cfg, tp)
    n = pc["active"] - pc["embedding"]
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n * tokens
    tokens = cell.global_batch  # one token per sequence
    return 2.0 * n * tokens


def _spec_nbytes(s) -> float:
    # shared shape×itemsize counter (works on ParamSpecs/SDS alike)
    return residency._nbytes(s)


def residency_qbytes(cfg: ModelConfig, tp: int, spec, *, min_dim: int = 64) -> float:
    """Resident weight bytes per parameter element, derived from the format
    registry (this replaces the old hand-maintained ``_QBYTES`` table).

    Byte-counts the tree :func:`abstract_quant` produces — the SAME walk
    that supplies the lowered serve-cell inputs, with the same policy
    matching and ``min_dim`` floor as ``convert_params`` — so dry-run byte
    accounting cannot drift from real residency: quantized leaves count
    their abstract payload, leaves that stay float count their spec dtype.
    """
    spec_tree = model_lib.specs(cfg, tp)
    qtree = abstract_quant(spec_tree, spec, min_dim=min_dim)
    elems = qbytes_sum = 0.0

    def walk(orig, conv):
        nonlocal elems, qbytes_sum
        for key, sub in orig.items():
            csub = conv[key]
            if key in QUANTIZABLE_KEYS and P.is_spec(sub) and len(sub.shape) >= 2:
                n_el = 1
                for d in sub.shape:
                    n_el *= d
                elems += n_el
                if isinstance(csub, residency.QuantLinearState):
                    qbytes_sum += _spec_nbytes(csub.data)  # payload, no scales
                else:
                    qbytes_sum += _spec_nbytes(csub)  # stayed float
            elif isinstance(sub, dict):
                walk(sub, csub)

    walk(spec_tree, qtree)
    return qbytes_sum / max(elems, 1.0)


def analytic_traffic(
    cfg: ModelConfig, cell: ShapeCell, tp: int, mesh_axes: dict,
    mb: int, qmode: str, min_dim: int = 64,
) -> dict:
    # (the cache term derives from cfg's registered cache format in
    # _cache_bytes_local — int8 halves it, int4_bp quarters it)
    """Minimum HBM traffic model per device per step (fusion-ideal).

    The HLO 'bytes accessed' metric charges every producer/consumer edge as
    if nothing fuses — a gross upper bound on a TPU, where XLA fuses
    elementwise chains and flash attention keeps scores in VMEM.  This
    analytic model is the matching LOWER bound: weights stream from HBM
    once per use, activations make one round trip per layer boundary, and
    caches are read once per decode step.  Real performance sits between
    the two; §Perf iterates on the dominant term of THIS model (the HLO
    number is reported alongside as `hbm_bytes_upper`).
    """
    pc = param_counts(cfg, tp)
    dways = mesh_axes.get("pod", 1) * mesh_axes.get("data", 1)
    # train cells always stream bf16 weights; only serve cells pay for the
    # registry walk that derives the policy's bytes/element
    wq = 2.0 if cell.kind == "train" else residency_qbytes(
        cfg, tp, qmode, min_dim=min_dim
    )
    # TP-local resident weight bytes (what a fwd pass must read)
    w_local = pc["total"] * wq / tp
    act_round = 8  # residual/norm/proj round-trips per layer boundary
    d = cfg.d_model
    L = cfg.n_layers + (cfg.n_enc_layers or 0)

    if cell.kind == "train":
        tokens_local = cell.global_batch * cell.seq_len / dways
        # fwd + remat-fwd + bwd weight reads; f32 grad write+read;
        # bf16 moments read+write (FSDP-sharded over data)
        weight_traffic = 3 * w_local + 2 * (2 * w_local) + 4 * w_local / max(dways, 1)
        act_traffic = tokens_local * d * 2 * L * act_round * 3  # fwd+bwd+remat
        kv_traffic = 0.0
    elif cell.kind == "prefill":
        tokens_local = cell.global_batch * cell.seq_len / dways
        weight_traffic = w_local
        act_traffic = tokens_local * d * 2 * L * act_round
        kv_traffic = tokens_local * d * 2  # cache write
    else:  # decode: the paper's GEMV-V regime — weights dominate
        tokens_local = max(cell.global_batch / dways, 1.0)
        weight_traffic = w_local  # every resident weight read once per step
        act_traffic = tokens_local * d * 2 * L * act_round
        # KV/cache read: sharded over (batch | seq) × kv-head sharding
        kv_traffic = _cache_bytes_local(cfg, cell, tp, mesh_axes)
    total = weight_traffic + act_traffic + kv_traffic
    return {
        "weight_traffic": weight_traffic,
        "act_traffic": act_traffic,
        "cache_traffic": kv_traffic,
        "total": total,
    }


#: synthetic mixed-length arrival trace for the analytic serving model:
#: (arrival_s, prompt_tokens_frac_of_seq_len, max_new) — one long prompt
#: co-arriving with short interactive traffic plus a late second wave.
_SERVE_TRACE = (
    (0.0, 1.00, 16), (0.0, 0.06, 16), (0.0, 0.08, 16), (0.0, 0.04, 16),
    (0.0, 0.05, 16), (0.0, 0.07, 16), (0.0, 0.06, 16), (0.0, 0.05, 16),
)


def analytic_serving(
    cfg: ModelConfig, cell: ShapeCell, tp: int, mesh_axes: dict,
    qmode: str, *, min_dim: int = 64, slots: int = 4,
    scheduler: Optional[str] = None,
) -> dict:
    """Scheduler-aware analytic serving model for a decode cell.

    Replays the synthetic mixed-length trace through the REAL registered
    schedulers (:func:`repro.serve.scheduler.simulate`) under a two-term
    cost model derived from the same analytic-traffic terms as the
    roofline: every model invocation pays the resident weight+cache HBM
    read once (``t_call``) plus per-position activation traffic
    (``t_token``).  This ranks orchestration policies — e.g. token_budget
    chunked prefill vs fcfs p95 TTFT — for a 398B cell without
    materializing a weight, the serving analogue of ``residency_qbytes``.
    """
    traffic = analytic_traffic(cfg, cell, tp, mesh_axes, 1, qmode,
                               min_dim=min_dim)
    bw = hlo_stats.HW["hbm_bw"]
    t_call = (traffic["weight_traffic"] + traffic["cache_traffic"]) / bw
    dways = mesh_axes.get("pod", 1) * mesh_axes.get("data", 1)
    tokens_local = max(cell.global_batch / dways, 1.0)
    t_token = traffic["act_traffic"] / bw / tokens_local
    trace = [(a, max(int(f * cell.seq_len), 1), m)
             for a, f, m in _SERVE_TRACE]
    names = [scheduler] if scheduler else list(sched_lib.schedulers())
    out = {}
    for name in names:
        st = sched_lib.simulate(
            name, trace, slots=slots, t_call=t_call, t_token=t_token,
            max_len=cell.seq_len + DECODE_HORIZON,
        )
        out[st.scheduler] = st.summary()
    return dict(
        t_call_s=t_call, t_token_s=t_token, slots=slots,
        trace=[list(t) for t in trace], schedulers=out,
    )


def _channel_bytes(fmt, eff_len: int, lead, feat, dtype=None) -> int:
    """One cache channel's bytes over ``eff_len`` resident positions,
    occupancy-derived: ``resident_bytes(abstract_state(1, eff_len, ...))``.
    Equal to ``slot_bytes × eff_len`` for every contiguous format; for a
    paged format it is the page-table occupancy — ``pages_per_slot(
    eff_len)`` whole pages (per-page scales included) plus the int32 block
    table — which is exactly what the engine's pool allocates per slot."""
    kw = {} if dtype is None else {"dtype": dtype}
    return fmt.resident_bytes(fmt.abstract_state(1, eff_len, lead, feat, **kw))


def analytic_cache_bytes(cfg, batch: int, cache_len: int, *, tp: int = 1) -> int:
    """Closed-form decode-cache bytes for a ``batch``-slot serving engine.

    Every format channel is occupancy-derived via :func:`_channel_bytes`
    (page tables and page-rounded rings for paged formats, plain rings
    otherwise); the format-independent leaves — ``pos_ids`` and the MLA
    rope ring — are counted at the same ``slot_capacity``-rounded length.
    Byte-exact against ``ServeEngine.resident_bytes()["cache"]`` for
    attention-family configs (tested in ``tests/test_paging.py``) with no
    ``eval_shape``."""
    fmt = kvcache.format_for(cfg)
    ring = fmt.slot_capacity(cache_len)
    total = 0
    for i in range(cfg.n_layers):
        if cfg.mixer_kind(i) not in ("attn", "attn_cross"):
            raise NotImplementedError(
                f"analytic_cache_bytes covers attention layers only; layer "
                f"{i} is {cfg.mixer_kind(i)!r}")
        if cfg.attn_type == "mla":
            total += _channel_bytes(fmt, cache_len, (), cfg.kv_lora_rank,
                                    cfg.dtype) * batch
            total += batch * ring * cfg.qk_rope_dim * 2  # bf16 rope ring
        else:
            _, kvp, _ = attn_dims(cfg, tp)
            total += _channel_bytes(fmt, cache_len, (kvp,), cfg.d_head,
                                    cfg.dtype) * 2 * batch
        total += batch * ring * 4  # pos_ids, int32
    return total


def analytic_weight_bytes(cfg, spec, *, tp: int = 1, min_dim: int = 64,
                          rules=None) -> int:
    """Resident weight bytes for one residency policy, with no weights.

    Walks the abstract ``_serve_params`` tree (the same
    :func:`abstract_quant` conversion the engine applies for real) and
    sums leaf bytes — byte-exact against
    ``ServeEngine.resident_bytes()["weights"]`` for the same ``(cfg, spec,
    min_dim)``, which the obs byte-gauge test asserts: the traced
    ``bytes.weights`` gauge, the engine accounting and this analytic twin
    must all agree to the byte.
    """
    spec_tree = model_lib.specs(cfg, tp)
    abs_tree, _ = _serve_params(
        spec_tree, spec, rules if rules is not None else P.base_rules(),
        min_dim=min_dim)
    return sum(residency._nbytes(a)
               for a in jax.tree_util.tree_leaves(abs_tree))


def _cache_bytes_local(cfg, cell, tp, mesh_axes) -> float:
    """Per-device decode-cache bytes, derived from the cache-format
    registry: each channel comes from the format's ``abstract_state``
    occupancy (:func:`_channel_bytes`) — the cache analogue of
    :func:`residency_qbytes`, drift-killed by construction.  Paged formats
    therefore charge whole pages plus block-table bytes."""
    dways = mesh_axes.get("pod", 1) * mesh_axes.get("data", 1)
    s = cell.seq_len
    b = cell.global_batch
    fmt = kvcache.format_for(cfg)
    per_layer = 0.0
    for i in range(cfg.n_layers):
        kind = cfg.mixer_kind(i)
        if kind in ("attn", "attn_cross"):
            if cfg.attn_type == "mla":
                per_layer += (
                    _channel_bytes(fmt, s, (), cfg.kv_lora_rank)
                    + s * cfg.qk_rope_dim * 2  # rope key stays bf16
                )
            else:
                _, kvp, shard_kv = attn_dims(cfg, tp)
                eff = min(s, cfg.sliding_window or s)
                width = _channel_bytes(fmt, eff, (kvp,), cfg.d_head) * 2
                per_layer += width / (tp if shard_kv else 1)
        elif kind == "mamba":
            per_layer += cfg.d_inner * cfg.d_state * 4 / tp
    return b * per_layer / min(b if b else 1, dways) if b else per_layer


# ---------------------------------------------------------------------------
# Per-cell lowering
# ---------------------------------------------------------------------------


def _probe_cfg(cfg: ModelConfig, d_dec: int, d_enc: int) -> ModelConfig:
    kw = dict(n_layers=cfg.first_k_dense + d_dec * cfg.block_period)
    if cfg.is_enc_dec:
        kw["n_enc_layers"] = d_enc
    return dataclasses.replace(cfg, **kw)


def lower_cell(
    arch: str,
    shape: str,
    *,
    multi_pod: bool = False,
    qmode: str = "bf16",
    microbatches: Optional[int] = None,
    probe: Optional[tuple[int, int]] = None,
    print_analyses: bool = False,
    mesh_shape: Optional[tuple[int, int]] = None,
    kv_quant: bool = False,
    cache_format: Optional[str] = None,
    moe_impl: Optional[str] = None,
    min_dim: int = 64,
) -> dict:
    """Lower one cell.  ``mesh_shape=(data, model)`` overrides the default
    16×16 factorization of the 256-chip pod — the §Perf lever for trading
    TP collective volume against FSDP gather volume at fixed chip count.
    ``cache_format`` selects the decode-cache residency (a name registered
    in ``repro.core.kvcache.FORMATS``; ``kv_quant`` is the legacy boolean
    for ``"int8"``).  The lowered cache inputs AND the analytic cache-byte
    term both derive from the format's ``abstract_state``, so dry-run cache
    accounting equals real cache residency by construction.  ``moe_impl``
    selects the dispatch algorithm (§Perf P4); ``min_dim`` is the
    residency-conversion floor and must match the serving-side
    ``convert_params``/``ServeEngine`` value for drift-free accounting."""
    cfg = get_config(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    if cache_format is not None:
        cfg = dataclasses.replace(cfg, cache_format=cache_format)
    if moe_impl:
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl)
    cell = SHAPES[shape]
    if mesh_shape is not None:
        mesh = make_mesh(mesh_shape, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    pl = plan(cfg, cell, mesh)
    rules, tp = pl.rules, pl.tp
    mb = microbatches if microbatches is not None else pl.microbatches

    is_probe = probe is not None
    batch_override = None
    if is_probe:
        cfg = _probe_cfg(cfg, *probe)
        if cell.kind == "train":
            batch_override = max(
                mesh.shape.get("pod", 1) * mesh.shape["data"],
                cell.global_batch // mb,
            )
        mb_used = 1
    else:
        mb_used = mb if cell.kind == "train" else 1

    spec_tree = model_lib.specs(cfg, tp)
    t0 = time.time()

    if cell.kind == "train":
        params_abs = P.abstract(spec_tree)
        params_sh = P.pspecs(spec_tree, rules)
        opt = optim_lib.adamw(3e-4, moment_dtype="bf16")
        opt_abs = opt.init_abstract(params_abs)
        opt_sh = opt_shardings(spec_tree, rules)
        batch_abs, batch_sh = batch_specs(cfg, cell, rules, batch_override)
        step = make_train_step(
            cfg, opt, tp=tp, rules=rules,
            step_cfg=TrainStepConfig(
                microbatches=mb_used, remat=True, probe=is_probe
            ),
            mesh=mesh,
        )
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                step,
                in_shardings=(params_sh, opt_sh, batch_sh),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
            compiled = lowered.compile()
    elif cell.kind == "prefill":
        params_abs, params_sh = _serve_params(spec_tree, qmode, rules,
                                               min_dim=min_dim)
        batch_abs, batch_sh = batch_specs(cfg, cell, rules)

        def prefill_step(params, batch):
            return model_lib.prefill(
                params, batch, cfg, tp=tp, max_len=cell.seq_len,
                rules=rules, impl="jnp", probe=is_probe,
            )

        with jax.set_mesh(mesh):
            jitted = jax.jit(
                prefill_step,
                in_shardings=(params_sh, batch_sh),
            )
            lowered = jitted.lower(params_abs, batch_abs)
            compiled = lowered.compile()
    else:  # decode
        params_abs, params_sh = _serve_params(spec_tree, qmode, rules,
                                               min_dim=min_dim)
        b = cell.global_batch
        cache_len = cell.seq_len + DECODE_HORIZON
        cache_abs = jax.eval_shape(
            lambda: model_lib.init_cache(cfg, b, cache_len, tp=tp)
        )
        _, _, shard_kv = attn_dims(cfg, tp)
        cache_sh = cache_pspecs(cache_abs, rules, shard_kv, cfg)
        tok_abs = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        pos_abs = jax.ShapeDtypeStruct((b,), jnp.int32)
        tok_sh = P.spec_for(("batch", None), rules)
        pos_sh = P.spec_for(("batch",), rules)

        def serve_step(params, token, caches, pos):
            return model_lib.decode_step(
                params, token, caches, pos, cfg, tp=tp, rules=rules,
                impl="jnp", probe=is_probe,
            )

        with jax.set_mesh(mesh):
            jitted = jax.jit(
                serve_step,
                in_shardings=(params_sh, tok_sh, cache_sh, pos_sh),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(params_abs, tok_abs, cache_abs, pos_abs)
            compiled = lowered.compile()

    lower_s = time.time() - t0
    if print_analyses:
        print(compiled.memory_analysis())
        print({k: v for k, v in compiled.cost_analysis().items()
               if k in ("flops", "bytes accessed")})
    return _collect(
        compiled, mesh=mesh, arch=arch, shape=shape, multi_pod=multi_pod,
        qmode=qmode, cache_format=kvcache.format_for(cfg).name,
        plan_notes=pl.notes, microbatches=mb_used if is_probe else mb,
        lower_seconds=lower_s, kind=cell.kind, probe=probe,
    )


def _collect(compiled, *, mesh, **meta) -> dict:
    ca = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    coll = hlo_stats.collective_stats(compiled.as_text())
    mem_stats = {
        attr: getattr(mem, attr, None)
        for attr in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        )
    }
    return dict(
        meta,
        devices=int(mesh.devices.size),
        mesh_shape=dict(zip(mesh.axis_names, mesh.devices.shape)),
        flops_per_device=float(ca.get("flops", 0.0)),
        hbm_bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        collective_wire_bytes=coll.wire_bytes,
        collectives=coll.by_kind,
        memory=mem_stats,
    )


# ---------------------------------------------------------------------------
# Probe-corrected analysis
# ---------------------------------------------------------------------------

_COST_KEYS = ("flops_per_device", "hbm_bytes_per_device", "collective_wire_bytes")


def analyze_cell(
    arch: str, shape: str, *, multi_pod: bool = False, qmode: str = "bf16",
    microbatches: Optional[int] = None, skip_probes: bool = False,
    mesh_shape: Optional[tuple[int, int]] = None, kv_quant: bool = False,
    cache_format: Optional[str] = None,
    moe_impl: Optional[str] = None, min_dim: int = 64,
    scheduler: Optional[str] = None,
) -> dict:
    cfg = get_config(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    if cache_format is not None:
        cfg = dataclasses.replace(cfg, cache_format=cache_format)
    if moe_impl:
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl)
    cell = SHAPES[shape]
    kw = dict(multi_pod=multi_pod, qmode=qmode, microbatches=microbatches,
              mesh_shape=mesh_shape, kv_quant=kv_quant,
              cache_format=cache_format, moe_impl=moe_impl,
              min_dim=min_dim)
    rec = lower_cell(arch, shape, **kw)
    rec["status"] = "ok"
    if skip_probes:
        return rec

    p1 = lower_cell(arch, shape, probe=(1, 1), **kw)
    p2 = lower_cell(arch, shape, probe=(2, 1), **kw)
    pe = None
    if cfg.is_enc_dec and cell.kind != "decode":
        pe = lower_cell(arch, shape, probe=(1, 2), **kw)

    mb = rec["microbatches"] if cell.kind == "train" else 1
    n_sb = cfg.n_superblocks
    n_enc = cfg.n_enc_layers
    corrected = {}
    for key in _COST_KEYS:
        body = max(p2[key] - p1[key], 0.0)
        enc_body = max(pe[key] - p1[key], 0.0) if pe else 0.0
        fixed = max(p1[key] - body - enc_body, 0.0)
        corrected[key] = mb * (fixed + n_sb * body + n_enc * enc_body)
    rec["corrected"] = corrected
    rec["probe"] = {
        "p1": {k: p1[k] for k in _COST_KEYS},
        "p2": {k: p2[k] for k in _COST_KEYS},
        "pe": {k: pe[k] for k in _COST_KEYS} if pe else None,
        "n_superblocks": n_sb, "microbatches": mb, "n_enc": n_enc,
    }

    tp = rec["mesh_shape"].get("model", 1)
    mf = model_flops(cfg, cell, tp)
    n_dev = rec["devices"]
    traffic = analytic_traffic(
        cfg, cell, tp, rec["mesh_shape"], mb, qmode, min_dim=min_dim
    )
    terms = hlo_stats.roofline_terms(
        corrected["flops_per_device"],
        traffic["total"],
        corrected["collective_wire_bytes"],
    )
    rec["roofline"] = dict(
        terms,
        hbm_bytes_analytic=traffic["total"],
        hbm_bytes_upper=corrected["hbm_bytes_per_device"],
        t_memory_upper=corrected["hbm_bytes_per_device"] / hlo_stats.HW["hbm_bw"],
        traffic_breakdown=traffic,
        model_flops_total=mf,
        model_flops_per_device=mf / n_dev,
        useful_flops_ratio=(mf / n_dev) / max(corrected["flops_per_device"], 1.0),
        model_step_seconds=(mf / n_dev) / hlo_stats.HW["bf16_flops"],
        roofline_fraction=min(
            1.0,
            ((mf / n_dev) / hlo_stats.HW["bf16_flops"])
            / max(terms["step_lower_bound"], 1e-12),
        ),
    )
    if cell.kind == "decode":
        # the scheduler registry's analytic serving model: rank fcfs / sjf /
        # token_budget TTFT+throughput for this cell's byte-derived costs
        rec["serving_model"] = analytic_serving(
            cfg, cell, tp, rec["mesh_shape"], qmode,
            min_dim=min_dim, scheduler=scheduler,
        )
    return rec


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _registry_arg(parse):
    """argparse ``type=`` wrapper: registry ValueErrors (which list the
    registered names) survive as ArgumentTypeError instead of argparse's
    generic "invalid value" — typos fail at parse time with the list."""

    def convert(text):
        try:
            return parse(text)
        except (ValueError, KeyError, TypeError) as e:
            raise argparse.ArgumentTypeError(str(e) or repr(e)) from e

    return convert


def force_host_devices() -> None:
    """Give the CPU backend the 512 devices the production meshes need.

    Entry points call this before anything starts the JAX backend;
    importing this module changes nothing."""
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
    )


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--qmode", default="bf16",
                    type=_registry_arg(
                        lambda s: residency.ResidencySpec.parse(s).describe()),
                    help="registered residency format name (one of "
                         f"{', '.join(residency.formats())}) or a per-layer "
                         "policy like 'ffn=bsdp,default=w8a8'")
    ap.add_argument("--cache-format", default=None,
                    type=_registry_arg(
                        lambda s: kvcache.get_cache_format(s).name),
                    help="decode-cache residency format (one of "
                         f"{', '.join(kvcache.formats())}); decode-cell "
                         "cache inputs and analytic cache bytes both derive "
                         "from its abstract_state (int4_bp_fused shares "
                         "int4_bp's layout — fusion is kernel policy — and "
                         "paged_* formats charge whole pages plus block "
                         "tables, so dry-run accounting matches the pool "
                         "by construction)")
    ap.add_argument("--scheduler", default=None,
                    type=_registry_arg(
                        lambda s: (sched_lib.make_scheduler(s), s)[1]),
                    help="restrict the decode-cell analytic serving model "
                         "to one registered scheduler (one of "
                         f"{', '.join(sched_lib.schedulers())}; default: "
                         "simulate all, for the policy comparison record)")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--min-dim", type=int, default=64,
                    help="residency-conversion floor: quantizable leaves "
                         "with min(K, N) below this stay float; MUST match "
                         "the serving-side convert_params/ServeEngine value "
                         "for drift-free byte accounting")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-probes", action="store_true",
                    help="lower+compile only (multi-pod pass/fail runs)")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    # --qmode/--cache-format/--scheduler were validated + canonicalized at
    # parse time by _registry_arg (typos fail with the registered list)

    from repro.configs import ARCH_NAMES

    cells = []
    archs = ARCH_NAMES if (args.all or args.arch is None) else [args.arch]
    for arch in archs:
        cfg = get_config(arch)
        shapes = applicable_shapes(cfg) if args.shape is None else [args.shape]
        for shape in shapes:
            cells.append((arch, shape))
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    ok = fail = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'multipod' if mp else 'pod'}__{args.qmode}"
            if args.cache_format:
                tag += f"__kv_{args.cache_format}"
            path = os.path.join(args.out, tag + ".json")
            try:
                rec = analyze_cell(
                    arch, shape, multi_pod=mp, qmode=args.qmode,
                    cache_format=args.cache_format,
                    microbatches=args.microbatches,
                    skip_probes=args.skip_probes or mp,
                    min_dim=args.min_dim, scheduler=args.scheduler,
                )
                ok += 1
                dom = rec.get("roofline", {}).get("dominant", "-")
                print(f"[OK] {tag}: dominant={dom} "
                      f"lower={rec['lower_seconds']:.1f}s", flush=True)
            except Exception as e:  # noqa: BLE001 — recorded, run continues
                rec = {
                    "arch": arch, "shape": shape, "multi_pod": mp,
                    "qmode": args.qmode, "status": "fail",
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:],
                }
                fail += 1
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1, default=str)
    print(f"\ndry-run complete: {ok} ok, {fail} failed")
    raise SystemExit(1 if fail else 0)


if __name__ == "__main__":
    main()
