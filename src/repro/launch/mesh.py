"""Production mesh construction + per-arch parallelism plans.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): 16×16 = 256 chips per pod (TPU v5e pod), and the 2-pod
512-chip variant with a leading 'pod' (DCN) axis.

``plan(cfg, shape_cell, mesh)`` centralizes the per-architecture
parallelism decisions the dry-run and launcher share:
  * rule table (TP everywhere; FSDP over 'data' for the train path;
    KV-head sharding only when the GQA group structure survives padding;
    expert sharding only when experts divide the model axis),
  * sequence sharding for long-context decode (batch=1 ⇒ shard the KV
    cache sequence axis over 'data' — flash-decoding combine),
  * microbatch count chosen so per-device live activations fit 16 GB HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh

from repro.configs.base import ModelConfig, ShapeCell
from repro.models.attention import attn_dims
from repro.sharding import partitioning as P


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    The models shard through PartitionSpec rule tables and
    ``with_sharding_constraint`` and leave everything else to the
    compiler's sharding propagation, which is what ``Auto`` axes mean
    (``jax.make_mesh`` defaults to ``Explicit`` axes, under which sharded
    gathers need an ``out_sharding`` at every call site)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_pp_mesh(*, stages: int = 2, data: int = 16, model: int = 16) -> Mesh:
    return make_mesh((stages, data, model), ("pipe", "data", "model"))


@dataclasses.dataclass(frozen=True)
class Plan:
    rules: dict
    tp: int
    microbatches: int
    notes: str


def plan(cfg: ModelConfig, cell: ShapeCell, mesh: Mesh) -> Plan:
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = axes.get("model", 1)
    data_axes = tuple(a for a in ("pod", "data") if a in axes)

    _, _, shard_kv = attn_dims(cfg, tp)
    shard_experts = bool(cfg.n_experts) and cfg.n_experts % tp == 0

    seq_axis = None
    notes = []
    if cell.kind == "decode" and cell.global_batch < _data_ways(axes):
        # long-context single-sequence decode: shard the KV/cache sequence
        # axis instead of the (too small) batch axis — flash-decoding.
        # All data-like axes move to the sequence dim (batch replicates).
        seq_axis = data_axes
        data_axes = ()
        notes.append("seq-parallel KV (flash-decoding combine)")

    fsdp = cell.kind == "train"
    if fsdp:
        notes.append("FSDP over data axis (params+grads+moments sharded)")

    rules = P.base_rules(
        fsdp=fsdp,
        data_axes=data_axes or (),
        model_axis="model",
        shard_kv_heads=shard_kv,
        shard_experts=shard_experts,
        seq_axis=seq_axis,
    )

    mb = 1
    if cell.kind == "train":
        mb = _pick_microbatches(cfg, cell, axes)
        notes.append(f"microbatches={mb}")
    return Plan(rules=rules, tp=tp, microbatches=mb, notes="; ".join(notes))


def _data_ways(axes: dict) -> int:
    return axes.get("pod", 1) * axes.get("data", 1)


def _pick_microbatches(cfg: ModelConfig, cell: ShapeCell, axes: dict) -> int:
    """Keep per-device live activation tokens ≤ ~2k for the biggest models.

    Napkin math (see EXPERIMENTS.md §Dry-run): live activations with
    superblock remat ≈ tokens/device × d_model × block_period × 2B ×
    ~4 residual copies.  Budget ≈ 2 GB of the 16 GB HBM.
    """
    dev_batch = max(1, cell.global_batch // _data_ways(axes))
    tokens = dev_batch * cell.seq_len
    budget = int(2e9)
    per_token = cfg.d_model * max(cfg.block_period, 1) * 2 * 4
    mb = 1
    while tokens // mb * per_token > budget and mb < dev_batch:
        mb *= 2
    return min(mb, dev_batch)
