#!/usr/bin/env python3
"""Find an open-loop cell's knee: its traffic at several fixed rates.

    python3 bench/sweep.py --workload <cell> --rates 1 2 3 4 --seconds 20

One process sets the cell up once (weights, shapes), then serves
``seconds`` of the mix at each rate in turn, in the order given, the engine
carrying its queue from one rate to the next, and prints one JSON line per
rate: requests sent and finished, the mean answer length asked for, output
tokens per second, the first-token time's median, 90th and 95th
percentiles, the queue left when the window closed, and the mean time of
the steps that only decoded and of those that also prefilled.  A rate past the knee leaves a
growing queue; there the output tokens per second are the saturated
throughput, and that over the mean answer length is the knee, the highest
rate of requests the cell sustains.  The cell runs at four fifths of it.
The benchmark's own runs never run this.
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    from bench import harness, traffic

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    if cell.mix["kind"] != "open":
        print(f"{args.workload}: a sweep of rates needs an open mix",
              file=sys.stderr)
        return 2
    clock = time.perf_counter
    compiles = harness.CompileClock()
    eng = harness.build_engine(cell.family, cell.config, args.seed)
    harness.warm_shapes(eng, cell.config, args.seed)
    drv = harness.Sender(eng, clock)
    log = harness.StepLog(eng, cell.config["stack"]["chunk"])
    vocab = cell.config["vocab_size"]
    for rate in args.rates:
        mix = {**cell.mix, "rate_per_s": rate}
        sched = traffic.schedule(mix, args.seed, args.seconds, vocab)
        log.steps.clear()
        n_compiles = compiles.count
        window, t0 = drv.open(sched.window, args.seconds,
                              lambda: log.step(clock), drain_s=0.0)
        queued = len(eng.queue)
        e2e = harness.end_to_end(drv.sent, window, t0, args.seconds, 0.0)
        ttft = [s.times[0] - s.due for s in window if s.times]
        decode_only = [b - a for a, b, rows in log.steps
                       if rows and all(r.kind == "decode" for r in rows)]
        mixed = [b - a for a, b, rows in log.steps
                 if any(r.kind != "decode" for r in rows)]
        print(json.dumps({
            "rate_per_s": rate, "sent": len(window),
            "finished": sum(1 for s in window if s.req.state == "done"),
            "queued_at_close": queued,
            "mean_output": float(np.mean([s.max_new for s in sched.window])),
            "output_tok_s": e2e["output_tok_s"],
            "itl_p95_ms": e2e["itl_p95_ms"],
            "ttft_p50_ms": float(np.median(ttft)) * 1e3 if ttft else None,
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "ttft_p95_ms": e2e["ttft_p95_ms"],
            "steps": len(log.steps),
            "decode_step_ms": (float(np.mean(decode_only)) * 1e3
                               if decode_only else None),
            "prefill_step_ms": (float(np.mean(mixed)) * 1e3
                                if mixed else None),
            "prefill_steps": len(mixed),
            "compiles": compiles.count - n_compiles,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
