"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

    arith       Fig. 3/6/7/8  native-instruction arithmetic ladder
    bsdp        Fig. 9        bit-serial INT4 dot product vs baselines
                              (+ unrolled vs fused single-contraction GEMM)
    transfer    Fig. 11       topology-aware vs naive host→device feeding
    gemv_e2e    Fig. 12       GEMV-MV vs GEMV-V compute:transfer split
                              (+ per-layer mixed-ResidencySpec serving row,
                              bsdp_fused ladder with per-call dot counts)
    gemv_scale  Fig. 13       full-system GOPS vs CPU server (derived)
    autotune    (ours)        BSDP (bm, bn, bkw) block sweep per shape class
    roofline    (ours)        §Roofline summary from dry-run records

Run all: ``PYTHONPATH=src python -m benchmarks.run``
One:     ``PYTHONPATH=src python -m benchmarks.run --only bsdp``
CI:      ``PYTHONPATH=src python -m benchmarks.run --smoke``  (1 iteration,
         small shapes, interpret-mode kernels — asserted by
         ``tests/test_bench_smoke.py`` so benchmark bit-rot is tier-1)
JSON:    ``--json BENCH_smoke.json`` additionally writes
         ``{"provenance": {...}, "rows": [...]}``; the provenance block
         (git SHA, jax version, backend, hostname, UTC timestamp) makes
         each artifact attributable on the perf trajectory, while the
         checked-in ``BENCH_smoke.json`` records which ladder rows the
         smoke harness produces (timings and provenance are container
         noise — only the row NAMES and derived keys are contract,
         asserted by ``tests/test_bench_smoke.py``).
"""

from __future__ import annotations

import argparse
import datetime
import json
import socket
import subprocess
import sys
import traceback


def provenance() -> dict:
    """Attribution block stamped into every ``--json`` artifact.

    Best-effort by design: a missing git binary or a non-repo checkout
    yields ``"unknown"`` rather than failing the benchmark run.
    """
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10,
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    import jax
    return {
        "git_sha": sha,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "hostname": socket.gethostname(),
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
    }


def _parse_row(line: str) -> dict:
    name, us, derived = line.split(",", 2)
    entry: dict = {"name": name, "us_per_call": float(us)}
    for kv in filter(None, derived.split(";")):
        k, _, v = kv.partition("=")
        entry.setdefault("derived", {})[k] = v
    return entry


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="1 iteration, reduced shapes (CI bit-rot check)")
    ap.add_argument("--json", default=None,
                    help="also write {provenance, rows} to this path; rows "
                         "are {name, us_per_call, derived{...}} records")
    args = ap.parse_args()

    from repro.launch import compile_cache

    compile_cache.enable()
    from benchmarks import (
        arith,
        autotune,
        bsdp,
        common,
        gemv_e2e,
        gemv_scale,
        roofline,
        transfer,
    )

    if args.smoke:
        common.set_smoke(True)

    suites = {
        "arith": arith.run,
        "bsdp": bsdp.run,
        "transfer": transfer.run,
        "gemv_e2e": gemv_e2e.run,
        "gemv_scale": gemv_scale.run,
        "autotune": autotune.run,
        "roofline": roofline.run,
    }
    if args.only:
        suites = {args.only: suites[args.only]}

    print("name,us_per_call,derived")
    failed, rows = [], []
    for name, fn in suites.items():
        try:
            for line in fn():
                print(line, flush=True)
                rows.append(line)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if args.json:
        doc = {"provenance": provenance(),
               "rows": [_parse_row(r) for r in rows]}
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
    if failed:
        raise SystemExit(f"benchmark suites failed: {failed}")


if __name__ == "__main__":
    main()
