#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the program's spans and
counters and from a profiler trace of the window's last seconds.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and the compared numbers under
``checks``); the compared numbers are also the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_process=T_PROCESS, devices=devices[:cell.chips])
    print(json.dumps({"info": out.info}), file=sys.stderr)
    for name, c in out.checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out.line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
