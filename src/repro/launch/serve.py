"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Initializes (or restores) a model, converts weights to the requested
residency policy — the paper's one-time GEMV-V layout transform — and
serves synthetic batched requests through the continuous-batching engine,
reporting throughput and SLO metrics (TTFT/TPOT percentiles from
``ServeEngine.stats()``).  The serving registry concepts each get a flag:
``--mode`` takes a registered *weight-residency* format name (including
``bsdp_fused`` — the single-contraction bit-plane GEMM kernel) or a
per-layer ResidencySpec string; ``--cache-format`` selects the
*decode-cache* residency (``repro.core.kvcache.FORMATS``: bf16 | int8 |
int4_bp | int4_bp_fused, plus their ``paged_*`` liftings whose physical
residency is a refcounted page pool); ``--scheduler`` selects the
*orchestration* policy (``repro.serve.scheduler.SCHEDULERS``: fcfs |
sjf | token_budget | prefix_cache, with CLI kwargs like
``token_budget:budget=16``).  An unknown name on any of the three flags
fails fast with the registered list:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --mode w8a8 --requests 8
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --mode 'ffn=bsdp,mixer=w8a16,default=w8a8' --cache-format int4_bp \
        --scheduler token_budget:budget=16

Observability (:mod:`repro.obs`, the fifth registry concept) wires in via
``--trace out.json`` (Chrome-trace/Perfetto export of the whole run:
step-loop spans, the trace-time ``kernel.traced`` call-site counters,
``jax.compile`` counts labelled by the span that compiled, page-pool
gauges, request lifecycle instants — load it at https://ui.perfetto.dev,
or validate with ``python -m repro.obs.validate out.json``) and
``--stats-every N`` (one serving stats line to stderr every N engine
steps: tokens, compiles and their seconds, pages, cache bytes, step
time).
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import obs
from repro.checkpoint import ckpt as ckpt_lib
from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.core import kvcache, residency
from repro.launch import compile_cache
from repro.models import model as model_lib
from repro.serve import engine
from repro.serve import scheduler as sched_lib
from repro.sharding import partitioning as P


def registry_arg(parse):
    """Wrap a registry parser for argparse ``type=``: argparse reports only
    a generic "invalid value" for ValueError, so re-raise as
    ArgumentTypeError to surface the registry's own message (which lists
    the registered names)."""

    def convert(text):
        try:
            return parse(text)
        except (ValueError, KeyError, TypeError) as e:
            raise argparse.ArgumentTypeError(str(e) or repr(e)) from e

    return convert


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", default="w8a8",
                    type=registry_arg(residency.ResidencySpec.parse),
                    help="registered format name (one of "
                         f"{', '.join(residency.formats())}) or a per-layer "
                         "policy like 'ffn=bsdp,default=w8a8'")
    ap.add_argument("--cache-format", default=None,
                    type=registry_arg(
                        lambda s: kvcache.get_cache_format(s).name),
                    help="decode-cache residency format (one of "
                         f"{', '.join(kvcache.formats())}; default: the "
                         "arch config's; int4_bp = §IV bit-plane K/V, "
                         "int4_bp_fused = the fused Pallas decode kernel, "
                         "paged_* = page-pool block tables)")
    ap.add_argument("--scheduler", default="fcfs",
                    type=registry_arg(sched_lib.make_scheduler),
                    help="orchestration policy (one of "
                         f"{', '.join(sched_lib.schedulers())}), with "
                         "optional kwargs like 'token_budget:budget=16'")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--min-dim", type=int, default=64,
                    help="residency-conversion floor (smaller projections "
                         "stay float); the default matches ServeEngine and "
                         "launch/dryrun.py --min-dim so dry-run byte "
                         "accounting matches what is actually served — "
                         "lower it (e.g. 16) for --smoke configs whose "
                         "projections are tiny")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="export the run as a Chrome-trace/Perfetto JSON "
                         "(spans, counters, request lifecycle) to this path")
    ap.add_argument("--stats-every", type=int, default=0, metavar="N",
                    help="print one serving stats line to stderr every N "
                         "engine steps (0 = off)")
    args = ap.parse_args()
    compile_cache.enable()

    trace_sink = None
    if args.trace:
        trace_sink = obs.register_sink(obs.ChromeTraceSink(args.trace))
    if args.stats_every > 0:
        obs.register_sink(obs.StatsLineSink(every=args.stats_every))

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.is_enc_dec or cfg.family == "vlm":
        raise SystemExit(
            f"{args.arch} needs a frontend-context request path; use the "
            "prefill/decode API directly (examples/serve_quantized.py shows "
            "the decoder-only flow)."
        )
    if args.ckpt_dir:
        tree, _ = ckpt_lib.restore(args.ckpt_dir)
        params = tree["params"]
    else:
        params = P.materialize(model_lib.specs(cfg, 1), jax.random.PRNGKey(0))

    t0 = time.perf_counter()
    qparams = engine.convert_params(params, cfg, args.mode, min_dim=args.min_dim)
    print(f"residency convert ({args.mode.describe()}): "
          f"{time.perf_counter()-t0:.2f}s, "
          f"{engine.resident_bytes(qparams)/1e6:.1f} MB resident")

    eng = engine.ServeEngine(
        qparams, cfg, slots=args.slots, max_len=args.max_len,
        cache_format=args.cache_format, scheduler=args.scheduler,
    )
    print(f"cache format: {eng.cache_format}  "
          f"scheduler: {eng.scheduler.describe()}")
    rng = np.random.default_rng(0)
    reqs = [
        eng.submit(
            rng.integers(0, cfg.vocab_size, size=(int(n),)).astype(np.int32),
            args.max_new,
        )
        for n in rng.integers(4, 16, size=args.requests)
    ]
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out) for r in reqs)
    st = eng.stats()
    print(f"served {len(reqs)} requests / {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s)")

    def ms(v):
        return "-" if v is None else f"{v*1e3:.0f}ms"

    print(f"TTFT p50/p95: {ms(st.percentile('ttft_s', 50))}/"
          f"{ms(st.percentile('ttft_s', 95))}  "
          f"TPOT p50: {ms(st.percentile('tpot_s', 50))}  "
          f"(ttft_work p95: {st.percentile('ttft_work', 95):.0f} positions)")

    if trace_sink is not None:
        trace_sink.close()
        print(f"trace: {len(trace_sink)} records → {args.trace} "
              "(chrome://tracing / ui.perfetto.dev)")


if __name__ == "__main__":
    main()
