"""End-to-end serving driver — the paper's GEMV-V scenario as a service.

    PYTHONPATH=src python examples/serve_quantized.py [--mode w4a4_bsdp]

Serves a small causal LM with BATCHED, continuously-scheduled requests
through :class:`repro.serve.engine.ServeEngine`, exercising all **four
serving registry concepts** — the residency discipline applied to every
resident concern:

* weight residency (:mod:`repro.core.residency`): every registered format
  — including ``bsdp_fused``, whose KernelPolicy routes batched layers to
  the fused single-contraction GEMM kernel (one MXU call per tile instead
  of 16 plane-pair matmuls) — plus a mixed per-layer ResidencySpec policy
  (BSDP for the FFN GEMVs, w8a16 attention, w8a8 default);
* decode-cache residency (:mod:`repro.core.kvcache`): ``--modes`` entries
  may suffix a cache format as ``+kv:int4_bp`` — the default rows end with
  BSDP FFN weights against a bit-plane K/V cache (both dominant resident
  payloads quantized by their registries) and the all-fused pairing
  ``ffn=bsdp_fused × int4_bp_fused``, where decode attention reads the
  stored planes through ONE fused Pallas kernel (qk scores, masked
  softmax and the plane-folded av gather in a single pass);
* paged KV residency (:mod:`repro.core.paging`): every cache format lifts
  to a ``paged_*`` twin whose physical residency is a refcounted page
  pool behind ``[B, pages/slot]`` block tables — the
  ``MIXED+kv:paged_int4_bp`` row serves bit-plane pages through the same
  engine, and with the ``prefix_cache`` scheduler requests sharing a
  prompt prefix map the same physical pages (COW on divergence);
* orchestration (:mod:`repro.serve.scheduler`): ``--scheduler`` selects the
  admission/batching policy (fcfs | sjf | token_budget[:budget=N] |
  prefix_cache) that plans every step — chunked prefill, refill ordering,
  slot reuse and prefix-cache admission are policy, not engine code;
* observability (:mod:`repro.obs`, the fifth registry concept): the final
  all-fused row re-runs with ``trace=True`` and prints a timeline excerpt
  (the step loop decomposed into plan/prefill/decode spans) plus the
  per-kernel table of call sites counted at trace time — the 16→1
  fused-kernel collapse as a measured serving artifact.

Each row reports throughput, resident weight bytes, cache bytes, p50 TTFT
(in the engine's deterministic processed-position work units, from
``ServeEngine.stats()``) and greedy-output agreement vs the bf16
reference: the serving analogue of the paper's Fig. 9/13 ladder.
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.core import residency
from repro.models import model as model_lib
from repro.serve import engine
from repro.sharding import partitioning as P

MIXED = "ffn=bsdp,mixer=w8a16,default=w8a8"
MIXED_FUSED = "ffn=bsdp_fused,mixer=w8a16,default=w8a8"
MODES = list(residency.formats()) + [
    MIXED, MIXED + "+kv:int4_bp", MIXED_FUSED + "+kv:int4_bp_fused",
    MIXED + "+kv:paged_int4_bp",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--modes", nargs="*", default=MODES)
    ap.add_argument("--scheduler", default="fcfs",
                    help="orchestration policy (fcfs | sjf | "
                         "token_budget[:budget=N])")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    cfg = get_smoke_config("qwen3-1.7b").scaled(n_layers=2, vocab_size=256)
    params = P.materialize(model_lib.specs(cfg, 1), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in rng.integers(4, 12, size=args.requests)
    ]

    reference = None
    print(f"{'mode':<57} {'tok/s':>8} {'resident MB':>12} {'cache MB':>9} "
          f"{'ttft p50':>9} {'agree@1':>8}")
    for entry in args.modes:
        # "mode" or "mode+kv:cache_format" — weight × cache residency
        mode, _, cache_fmt = entry.partition("+kv:")
        # residency conversion happens once, inside the engine (amortized)
        eng = engine.ServeEngine(
            params, cfg, slots=3, max_len=64, mode=mode,
            cache_format=cache_fmt or None, scheduler=args.scheduler,
            min_dim=16,
        )
        reqs = [eng.submit(p, args.max_new) for p in prompts]
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        toks = sum(len(r.out) for r in reqs)
        outs = [tuple(r.out) for r in reqs]
        if reference is None:
            reference = outs
            agree = 1.0
        else:
            hits = sum(
                sum(a == b for a, b in zip(o, r)) for o, r in zip(outs, reference)
            )
            agree = hits / max(sum(len(r) for r in reference), 1)
        st = eng.stats()
        breakdown = eng.resident_bytes()  # registry-derived weights/cache
        mb = breakdown["weights"] / 1e6
        cache_mb = breakdown["cache"] / 1e6
        label = eng.mode + (f"+kv:{eng.cache_format}" if cache_fmt else "")
        print(f"{label:<57} {toks/dt:8.1f} {mb:12.2f} {cache_mb:9.3f} "
              f"{st.percentile('ttft_work', 50):9.1f} {agree:8.2f}")
    print(f"scheduler: {eng.scheduler.describe()}")
    _traced_excerpt(params, cfg, prompts, args)
    print("serve_quantized OK")


def _traced_excerpt(params, cfg, prompts, args):
    """Serve the all-fused pairing once more with tracing on and print what
    the observability registry saw: a span summary of the step loop and the
    per-kernel table of traced call sites."""
    import repro.obs as obs

    eng = engine.ServeEngine(
        params, cfg, slots=3, max_len=64, mode=MIXED_FUSED,
        cache_format="int4_bp_fused", scheduler=args.scheduler,
        min_dim=16, trace=True,
    )
    for p in prompts:
        eng.submit(p, args.max_new)
    eng.run()
    timeline = eng.timeline()
    obs.unregister_sink(eng._ring)

    print(f"\ntraced run ({eng.mode}+kv:{eng.cache_format}): "
          f"{len(timeline)} records")
    print(f"{'span':<18} {'count':>5} {'total ms':>9} {'p50 ms':>8}")
    for name, s in sorted(obs.summarize_spans(timeline).items()):
        print(f"{name:<18} {s['count']:>5} {s['total_s']*1e3:>9.1f} "
              f"{s['p50_s']*1e3:>8.2f}")
    print("kernel call sites per compiled program (counted at trace time):")
    for key, count in sorted(obs.traced_kernels(timeline).items()):
        labels = ",".join(f"{k}={v}" for k, v in key)
        print(f"  {labels:<40} {count}")


if __name__ == "__main__":
    main()
