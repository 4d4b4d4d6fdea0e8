"""§Perf hillclimbing driver: hypothesis → change → re-lower → validate.

Three cells (chosen from the §Roofline baseline table per the assignment:
worst roofline fraction / most collective-bound / most representative of
the paper's technique), each with an explicit list of variants and the
napkin-math hypothesis recorded BEFORE the measurement.  Each variant is a
full re-lower + probe-corrected analysis (launch/dryrun.analyze_cell);
results land in results/perf/*.json and a markdown log for
EXPERIMENTS.md §Perf.

Run:  PYTHONPATH=src python -m benchmarks.perf_iterations [--only P1]
"""

from __future__ import annotations

import argparse
import json
import os

from repro.launch import dryrun


CELLS = {
    # P1 — the paper's technique itself: shrink the resident bytes of the
    # weight-stationary decode.  First napkin pass (recorded in P1b below)
    # REFUTED the naive hypothesis: at batch 128 × 32k context the decode
    # traffic is cache-dominated (minicpm3: cache 9.4 GB vs weights 0.55
    # GB/step/dev), so weight quantization alone moved the bound only ~4%.
    # Revised hypothesis: apply the paper's byte-shrinking to the CACHE
    # (int8 payload, scales folded after the integer contraction, same
    # epilogue trick as the matmul kernels).  qwen1.5-32b decode_32k is
    # the forcing case — bf16 baseline needs 30.5 GB/dev and DOES NOT FIT
    # the 16 GB HBM; predicted: kv8 halves cache traffic AND capacity,
    # w8+kv8 brings args to ~15 GB (fits), bound ≈ 0.5× baseline.
    "P1": dict(
        arch="qwen1.5-32b",
        shape="decode_32k",
        variants=[
            ("baseline_bf16", dict(qmode="bf16"),
             "bf16 weights+cache: 30.5 GB/dev args — EXCEEDS 16 GB HBM"),
            ("w8a8_weights", dict(qmode="w8a8"),
             "int8 weights only: weight term halves, cache unchanged (~15%)"),
            ("w8a8_kv8", dict(qmode="w8a8", kv_quant=True),
             "int8 weights + int8 KV: cache term halves -> fits + ~0.5x bound"),
            ("w4a8_kv8", dict(qmode="w4a8", kv_quant=True),
             "int4 weights + int8 KV: weight term quarters on top"),
        ],
    ),
    # P1b — the refuted first pass, kept per the methodology (a refuted
    # hypothesis is as informative): MLA's latent cache is already 35x
    # smaller per token than qwen1.5's GQA cache, yet still dominates its
    # decode traffic at batch 128.
    "P1b": dict(
        arch="minicpm3-4b",
        shape="decode_32k",
        variants=[
            ("baseline_bf16", dict(qmode="bf16"),
             "bf16 resident weights: memory term = (2B/wt . P/tp + cache)/BW"),
            ("w8a8", dict(qmode="w8a8"),
             "REFUTED: int8 weights predicted -45%; measured ~-3% (cache-bound)"),
            ("w8a8_kv8", dict(qmode="w8a8", kv_quant=True),
             "revised: quantize the latent cache too"),
        ],
    ),
    # P2 — most collective-bound: small-model training at TP=16 drowns in
    # per-layer activation all-reduces (2·act_bytes·(tp-1)/tp, twice per
    # layer, fwd+bwd+remat).  Hypothesis: at fixed 256 chips, shifting the
    # factorization toward DP shrinks per-device activations (B_loc ∝
    # 1/data) and removes TP all-reduces entirely at model=1; FSDP gather
    # volume (params·(n-1)/n per pass) grows far slower than the
    # activation volume shrinks for a 1.4B-param model at 65k tokens/dev.
    # Predicted: wire bytes ↓ >10× from (16,16) → (256,1).
    "P2": dict(
        arch="qwen3-1.7b",
        shape="train_4k",
        variants=[
            ("baseline_16x16", dict(mesh_shape=(16, 16)),
             "TP=16: activation all-reduces dominate (measured 196 GB/dev)"),
            ("dp64_tp4", dict(mesh_shape=(64, 4)),
             "TP=4: B_loc 4x smaller, (tp-1)/tp 0.94->0.75: ~5x less AR wire"),
            ("dp256_tp1", dict(mesh_shape=(256, 1)),
             "pure DP+FSDP: zero TP collectives; FSDP gathers ~3·P_bytes"),
        ],
    ),
    # P4 — the MoE-dispatch hypothesis test (identified in §Roofline):
    # mixtral train_4k's 135 s bound traces to the sort-based dispatch's
    # computed-index scatter, which SPMD cannot shard (≈100 GB of
    # all-reduce/permute per superblock).  Hypothesis: the GShard einsum
    # dispatch — despite its O(S·E·C) dispatch tensors — shards cleanly
    # (dispatch lowers to all-to-alls of ≈tokens·d bytes), cutting the
    # collective term by >5×.
    "P4": dict(
        arch="mixtral-8x7b",
        shape="train_4k",
        variants=[
            ("sort_dispatch", dict(moe_impl="sort"),
             "baseline: computed-index scatter -> replicated activations"),
            ("einsum_dispatch", dict(moe_impl="einsum"),
             "GShard one-hot einsums: partitioner-friendly, canonical a2a"),
        ],
    ),
    # P3 — worst roofline fraction (per the baseline table): seamless
    # enc-dec training — a 366M-param model spread over 256 chips is
    # latency/collective-bound, and its d_model=1024 shards to 64 cols per
    # chip at TP=16 (MXU tiles are 128-wide: half-empty systolic passes).
    # Hypothesis: same DP-shift lever as P2 plus the small-model argument
    # is *stronger* (less compute to amortize); (64,4) should beat (16,16)
    # by >5x on the dominant term.
    "P3": dict(
        arch="seamless-m4t-medium",
        shape="train_4k",
        variants=[
            ("baseline_16x16", dict(mesh_shape=(16, 16)),
             "TP=16 on d_model=1024: 64-wide shards underfill 128-wide MXU"),
            ("dp64_tp4", dict(mesh_shape=(64, 4)),
             "TP=4: 256-wide shards, 4x fewer AR bytes/dev"),
            ("dp256_tp1", dict(mesh_shape=(256, 1)),
             "pure DP+FSDP: collective floor = FSDP gathers only"),
        ],
    ),
}


def run_cell(name: str, spec: dict, out_dir: str) -> list[dict]:
    os.makedirs(out_dir, exist_ok=True)
    records = []
    for vname, kw, hypothesis in spec["variants"]:
        tag = f"{name}_{vname}"
        path = os.path.join(out_dir, tag + ".json")
        if os.path.exists(path):
            with open(path) as f:
                rec = json.load(f)
            print(f"[cached] {tag}", flush=True)
            records.append(rec)
            continue
        print(f"[lower] {tag}: {hypothesis}", flush=True)
        try:
            rec = dryrun.analyze_cell(spec["arch"], spec["shape"], **kw)
            rec["variant"] = vname
            rec["hypothesis"] = hypothesis
            rec["status"] = "ok"
        except Exception as e:  # noqa: BLE001
            rec = {"variant": vname, "hypothesis": hypothesis,
                   "status": "fail", "error": str(e)}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
        records.append(rec)
        if rec["status"] == "ok":
            ro = rec["roofline"]
            print(f"    -> c={ro['t_compute']:.3f}s m={ro['t_memory']:.3f}s "
                  f"x={ro['t_collective']:.3f}s dom={ro['dominant']}", flush=True)
    return records


def main():
    dryrun.force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(CELLS))
    ap.add_argument("--out", default="results/perf")
    args = ap.parse_args()
    cells = {args.only: CELLS[args.only]} if args.only else CELLS
    for name, spec in cells.items():
        recs = run_cell(name, spec, args.out)
        base = next((r for r in recs if r["status"] == "ok"), None)
        if base is None:
            continue
        b = base["roofline"]["step_lower_bound"]
        print(f"\n== {name}: {spec['arch']} × {spec['shape']} ==")
        for r in recs:
            if r["status"] != "ok":
                print(f"  {r['variant']:<18} FAILED {r.get('error','')[:60]}")
                continue
            ro = r["roofline"]
            print(f"  {r['variant']:<18} bound={ro['step_lower_bound']:.3f}s "
                  f"({b/max(ro['step_lower_bound'],1e-12):.2f}x vs base) "
                  f"dom={ro['dominant']}")
        print()


if __name__ == "__main__":
    main()
