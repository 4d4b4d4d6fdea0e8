"""Trainer: the long-running loop with checkpointing, watchdog, restart.

Composition of the substrate: data pipeline (prefetch) → jitted train step
(microbatched, FSDP/TP-sharded) → async checkpoint every ``ckpt_every``
steps → watchdog telemetry → automatic restore-from-latest on (simulated
or real) failure, including onto a different mesh (elastic path).

This is the loop examples/train_small_lm.py runs for a few hundred steps on
CPU and the multi-pod dry-run lowers at full scale.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt as ckpt_lib
from repro.data.pipeline import DataConfig, SyntheticLM, prefetch, shard_batch
from repro.distributed.resilience import FailureSim, SimulatedFailure, StepWatchdog
from repro.models import model as model_lib
from repro.optim import adamw as optim_lib
from repro.sharding import partitioning as P
from repro.train.trainstep import TrainStepConfig, make_train_step


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 25
    ckpt_dir: Optional[str] = None
    log_every: int = 10
    seed: int = 0
    peak_lr: float = 3e-4
    warmup: int = 20
    moment_dtype: str = "f32"
    microbatches: int = 1
    max_restarts: int = 3


class Trainer:
    def __init__(
        self,
        cfg,
        data_cfg: DataConfig,
        tcfg: TrainerConfig,
        *,
        mesh=None,
        rules=None,
        tp: int = 1,
        failure_sim: Optional[FailureSim] = None,
    ):
        self.cfg, self.data_cfg, self.tcfg = cfg, data_cfg, tcfg
        self.mesh, self.rules, self.tp = mesh, rules, tp
        self.failure_sim = failure_sim
        self.watchdog = StepWatchdog()
        self.opt = optim_lib.adamw(
            optim_lib.cosine_schedule(tcfg.peak_lr, tcfg.warmup, tcfg.steps),
            moment_dtype=tcfg.moment_dtype,
        )
        self.step_fn = make_train_step(
            cfg, self.opt, tp=tp, rules=rules,
            step_cfg=TrainStepConfig(microbatches=tcfg.microbatches),
            mesh=mesh,
        )
        self.step_fn = jax.jit(self.step_fn, donate_argnums=(0, 1))
        self.ckpt = ckpt_lib.AsyncCheckpointer()
        self.history: list[dict] = []

    # -- state ---------------------------------------------------------

    def init_state(self):
        specs = model_lib.specs(self.cfg, self.tp)
        key = jax.random.PRNGKey(self.tcfg.seed)
        if self.mesh is None:
            params = P.materialize(specs, key)
            return params, self.opt.init(params), 0
        # on a mesh, parameters and moments are created already sharded by
        # the rule table: no device ever holds the whole unsharded model
        from repro.launch.dryrun import opt_shardings

        params = jax.jit(
            lambda k: P.materialize(specs, k),
            out_shardings=P.shardings(specs, self.mesh, self.rules),
        )(key)
        opt_state = jax.jit(
            self.opt.init, out_shardings=opt_shardings(specs, self.rules)
        )(params)
        return params, opt_state, 0

    def _mesh_context(self):
        """The mesh as ambient context, so the step's logical-axis
        activation constraints resolve against it."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def restore_state(self):
        d = self.tcfg.ckpt_dir
        step = ckpt_lib.latest_step(d) if d else None
        if step is None:
            return self.init_state()
        tree, extra = ckpt_lib.restore(d, step)
        return tree["params"], _retuple(tree["opt_state"]), extra.get("step", step)

    # -- loop ----------------------------------------------------------

    def run(self) -> dict:
        restarts = 0
        while True:
            try:
                return self._run_once()
            except SimulatedFailure as e:
                restarts += 1
                if restarts > self.tcfg.max_restarts:
                    raise
                self.ckpt.wait()
                # loop re-enters, restoring from the latest checkpoint

    def _run_once(self) -> dict:
        with self._mesh_context():
            return self._loop()

    def _loop(self) -> dict:
        params, opt_state, start = self.restore_state()
        src = SyntheticLM(self.data_cfg)
        t_tot0 = time.perf_counter()
        step = start
        for step in range(start, self.tcfg.steps):
            batch = src.batch(step)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            if self.mesh is not None:
                batch = shard_batch(batch, self.mesh, self.rules)
            if self.failure_sim is not None:
                self.failure_sim.check(step)
            t0 = time.perf_counter()
            params, opt_state, metrics = self.step_fn(params, opt_state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            rep = self.watchdog.observe(step, dt)
            if step % self.tcfg.log_every == 0 or step == self.tcfg.steps - 1:
                self.history.append(
                    {"step": step, "loss": float(metrics["loss"]),
                     "ce": float(metrics["ce"]), "sec": dt,
                     "straggler": rep.straggler}
                )
            if self.tcfg.ckpt_dir and (step + 1) % self.tcfg.ckpt_every == 0:
                self.ckpt.save(
                    {"params": params, "opt_state": _detuple(opt_state)},
                    self.tcfg.ckpt_dir, step + 1, extra={"step": step + 1},
                )
        self.ckpt.wait()
        if self.tcfg.ckpt_dir:
            ckpt_lib.save(
                {"params": params, "opt_state": _detuple(opt_state)},
                self.tcfg.ckpt_dir, self.tcfg.steps, extra={"step": self.tcfg.steps},
            )
        return {
            "params": params,
            "opt_state": opt_state,
            "history": self.history,
            "total_sec": time.perf_counter() - t_tot0,
            "stragglers": self.watchdog.straggler_steps,
        }


def _detuple(opt_state):
    """NamedTuples → dicts for checkpoint portability."""
    return {
        "step": opt_state.step,
        "mu": _moments_to_dict(opt_state.mu),
        "nu": _moments_to_dict(opt_state.nu),
    }


def _retuple(d):
    return optim_lib.AdamState(
        d["step"], _dict_to_moments(d["mu"]), _dict_to_moments(d["nu"])
    )


def _moments_to_dict(tree):
    return jax.tree_util.tree_map(
        lambda m: {"payload": m.payload, "scale": m.scale},
        tree,
        is_leaf=lambda x: isinstance(x, optim_lib.Moment),
    )


def _dict_to_moments(tree):
    def is_m(x):
        return isinstance(x, dict) and set(x) == {"payload", "scale"}

    return jax.tree_util.tree_map(
        lambda m: optim_lib.Moment(m["payload"], m["scale"]), tree, is_leaf=is_m
    )
