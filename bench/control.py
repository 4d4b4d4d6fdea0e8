#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds 20 \
        [--control]

Runs the cell once per seed, exactly as ``run.py`` does (set-up, a window
at the cell's own load, the reference check), and prints one JSON line per
run with the compared numbers.  With ``--control`` the configuration's
weight residency is replaced by its ``control_mode``, one precision step
down (w8a8 → w4a8): the control that the limit has to fail.  The benchmark's
own runs never run this.  Each line also gives the spread of the checked
tokens' gaps (mean, quantiles, the share not the reference's best), and,
over an int4 cache, the widest gap against the reference with its query
rounded to int4 and not.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    from bench import harness

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    mode = cell.config["control_mode"] if args.control else None
    for seed in args.seeds:
        out = harness.run(cell, seed, args.seconds, False,
                          t_process=time.perf_counter(),
                          devices=devices[:cell.chips], mode=mode)
        row = {"workload": args.workload, "seed": seed,
               "mode": mode or cell.config["stack"]["mode"],
               "checks": out.checks, "info": out.info}
        if len(out.gaps):
            g = out.gaps
            row["gaps"] = {"mean": float(g.mean()),
                           "p50": float(np.percentile(g, 50)),
                           "p90": float(np.percentile(g, 90)),
                           "p99": float(np.percentile(g, 99)),
                           "not_best": float((g > 0).mean())}
        model = cell.model
        if model["kv_bits"] == 4:
            # the same served tokens against the reference with its query
            # rounded to int4 and not
            for q_bits in (4, 16):
                gaps = harness.reference_gaps(
                    cell.family, cell.config, seed, out.sample,
                    {**model, "q_bits": q_bits})
                row[f"max_logit_gap_q{q_bits}"] = (
                    float(gaps.max()) if len(gaps) else None)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
