"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the real trainer loop (checkpointing, watchdog, restart) on a reduced
or full config over an explicit mesh.  On this CPU container use
``--smoke`` (reduced config, tiny mesh); on a TPU slice drop the flag and
pass the pod mesh dims.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse

from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.data.pipeline import DataConfig
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh, plan
from repro.configs.base import ShapeCell
from repro.sharding import partitioning as P
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--peak-lr", type=float, default=1e-3)
    ap.add_argument("--moment-dtype", default="f32",
                    choices=["f32", "bf16", "int8"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--data", type=int, default=0,
                    help="data-parallel ways (0 = single device)")
    ap.add_argument("--model", type=int, default=1, help="TP ways")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    data = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.global_batch,
    )

    mesh = rules = None
    tp = args.model
    if args.data:
        mesh = make_mesh((args.data, args.model), ("data", "model"))
        cell = ShapeCell("cli", args.seq_len, args.global_batch, "train")
        rules = plan(cfg, cell, mesh).rules

    tr = Trainer(
        cfg, data,
        TrainerConfig(
            steps=args.steps, ckpt_every=args.ckpt_every,
            ckpt_dir=args.ckpt_dir, peak_lr=args.peak_lr,
            moment_dtype=args.moment_dtype, microbatches=args.microbatches,
        ),
        mesh=mesh, rules=rules, tp=tp,
    )
    out = tr.run()
    for h in out["history"]:
        print(f"step {h['step']:5d}  loss {h['loss']:.4f}  {h['sec']*1e3:.0f} ms")
    print(f"done in {out['total_sec']:.1f}s; stragglers={out['stragglers']}")


if __name__ == "__main__":
    main()
