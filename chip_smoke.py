#!/usr/bin/env python3
"""Chip smoke: serve qwen3-1.7b at full published width on one TPU chip.

    python3 chip_smoke.py               # phases A, B, C on one chip
    python3 chip_smoke.py --four-chips  # sharded training on a 2x2 mesh only

Everything runs in this one process, which holds the chip.  The weights are
random, made from a seed (no checkpoint ships with the repository); the
model is qwen3-1.7b at its published widths and depth: 28 layers,
d_model 2048, GQA 16/8, d_head 128, d_ff 6144, vocab 151,936.

Phases (one chip):

* **A** — the launcher's default stack: ``w8a8`` weights, the config's
  bf16 cache, ``fcfs`` scheduling.
* **B** — the paper's stack: ``ffn=bsdp_fused,mixer=w8a16,default=w8a8``
  weights, the ``paged_int4_bp_fused`` cache (the fused bit-plane decode
  kernel) and ``prefix_cache`` scheduling; then the same traffic, teacher
  forced, through ``paged_int4_bp`` (the kernel's jnp plane-math twin).
* **C** — each kernel at these widths against its ``kernels/ref.py``
  oracle: integer results bit-exact.

A and B each serve 8 requests (prompts of 64–512 tokens sharing a 64-token
system prompt, 16 new tokens each) on 4 slots with a 1024-slot ring,
through ``engine.convert_params`` → ``ServeEngine`` → ``run()``, and compare
every request's first-token logits with a bf16 engine on the chip, within
the bound the CPU tests use.  Phase A is held to it at full depth.  Phase
B's ``bsdp`` weights quantize activations to int4 as well, and that noise
compounds over 28 layers, so B is held to the bound on the model's first
two layers — the depth of the CPU tests — and its full-depth agreement is
printed.  At every depth the fused kernel must match its twin on the
served cache.

``--four-chips`` runs only the sharded training path (``launch/train.py
--data 2 --model 2``): a few steps cut to 4 layers on the 2x2 mesh and on
one device, whose first-step losses must agree, then a few full-depth steps
on the mesh with each device's memory stats.

Per-phase wall and compile seconds are printed as information.  The last
line of standard output is one JSON object naming the device; any failed
check raises, and the script exits non-zero without printing it.
Without a TPU it exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ARCH = "qwen3-1.7b"
SEED = 0
PHASE_B_MODE = "ffn=bsdp_fused,mixer=w8a16,default=w8a8"

#: tolerances the CPU tests use for these comparisons
#: (tests/test_serve_bsdp.py, tests/test_kvcache.py, tests/test_distributed.py)
LOGIT_REL_TOL, LOGIT_COS_MIN = 0.5, 0.9
#: depth of the CPU tests' models, at which they fix that logit bound.
#: ``bsdp`` weights quantize activations to int4 as well (W4A4), and the
#: noise compounds with depth, so phase B's bf16 check serves the first
#: CHECK_LAYERS layers of the same full-width model
CHECK_LAYERS = 2
ATTN_TOL = 1e-4
LOSS_RTOL, LOSS_ATOL = 2e-2, 1e-3


@dataclasses.dataclass(frozen=True)
class Traffic:
    """Serving load of phases A and B: prompts are a shared system prompt
    plus one suffix each (prompt lengths are multiples of the 32-token
    prefill chunk, so chunked decode compiles two shapes)."""

    system_len: int = 64
    suffix_lens: tuple = (448, 32, 192, 64, 320, 96, 384, 160)
    max_new: int = 16
    slots: int = 4
    max_len: int = 1024

    def prompts(self, vocab: int, seed: int = SEED) -> list:
        rng = np.random.default_rng(seed)
        system = rng.integers(0, vocab, self.system_len, dtype=np.int32)
        return [np.concatenate(
            [system, rng.integers(0, vocab, n, dtype=np.int32)])
            for n in self.suffix_lens]


# ---------------------------------------------------------------------------
# Bookkeeping
# ---------------------------------------------------------------------------


def run_phase(name, fn):
    """Run one phase; print its wall seconds and the compile seconds and
    persistent-cache hits that ``repro.obs`` counted during it."""
    from repro.obs import counter_total

    def compiled():
        return (counter_total("jax.compile_s"),
                counter_total("jax.compile_cache_hit"))

    (c0, h0), t0 = compiled(), time.perf_counter()
    out = fn()
    c1, h1 = compiled()
    print(f"phase {name}: ok  wall {time.perf_counter() - t0:.1f}s  "
          f"compile {c1 - c0:.1f}s  cache hits {h1 - h0:.0f}", flush=True)
    return out


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def logit_agreement(ref, got) -> tuple[float, float]:
    """(max |ref − got| / max |ref|, cosine) — the CPU tests' measure."""
    ref = np.asarray(ref, np.float32).ravel()
    got = np.asarray(got, np.float32).ravel()
    rel = float(np.abs(ref - got).max() / (np.abs(ref).max() + 1e-6))
    cos = float(ref @ got / (np.linalg.norm(ref) * np.linalg.norm(got) + 1e-9))
    return rel, cos


def check_logits(tag, refs, gots, *, tolerance=True):
    """Every logit vector finite and, with ``tolerance``, within the CPU
    tests' bound of its reference; prints the worst agreement."""
    worst_rel, worst_cos = 0.0, 1.0
    for i, (r, g) in enumerate(zip(refs, gots)):
        rel, cos = logit_agreement(r, g)
        check(np.isfinite(np.asarray(g)).all(), f"{tag}: request {i} logits not finite")
        check(not tolerance or (rel < LOGIT_REL_TOL and cos > LOGIT_COS_MIN),
              f"{tag}: request {i} logits off (rel {rel:.3g}, cos {cos:.4f})")
        worst_rel, worst_cos = max(worst_rel, rel), min(worst_cos, cos)
    print(f"  {tag}: {len(refs)} logit vectors, worst rel {worst_rel:.4f}, "
          f"worst cos {worst_cos:.5f}"
          + ("" if tolerance else " (information, not checked)"), flush=True)


# ---------------------------------------------------------------------------
# Serving phases
# ---------------------------------------------------------------------------


def serve(params, cfg, traffic, *, mode="bf16", cache_format=None,
          scheduler="fcfs", max_new=None, force=None, roomy=False):
    """Convert, serve ``traffic`` through the engine, return ``(engine,
    requests, first-token logits per request)``.  ``roomy``: a page pool
    that holds every request's ring at once, so no request waits for pages
    and no prefix is evicted."""
    from repro.core import kvcache
    from repro.serve import engine

    qparams = engine.convert_params(params, cfg, mode)
    pool = None
    if roomy:
        fmt = kvcache.get_cache_format(cache_format)
        pool = len(traffic.suffix_lens) * fmt.pages_per_slot(traffic.max_len)
    eng = engine.ServeEngine(
        qparams, cfg, slots=traffic.slots, max_len=traffic.max_len,
        cache_format=cache_format, scheduler=scheduler, trace_logits=True,
        page_pool_pages=pool)
    first = {}

    def on_token(req, _tok):
        if len(req.out) == 1:  # the trace entry just recorded is its TTFT
            first[req.uid] = eng.logit_trace[-1][2]

    max_new = traffic.max_new if max_new is None else max_new
    reqs = [eng.submit(p, max_new, on_token=on_token,
                       force=None if force is None else force[i])
            for i, p in enumerate(traffic.prompts(cfg.vocab_size))]
    eng.run()
    return eng, reqs, [first[r.uid] for r in reqs]


def check_complete(tag, reqs, max_new):
    for r in reqs:
        check(r.state == "done" and len(r.out) == max_new,
              f"{tag}: request {r.uid} ended {r.state} with {len(r.out)} tokens")


def phase_reference(params, cfg, traffic):
    """bf16 weights and cache: first-token logits every phase checks."""
    _, reqs, first = serve(params, cfg, traffic, max_new=2)
    check_complete("reference", reqs, 2)
    return first


def phase_a(params, cfg, traffic, ref_first):
    eng, reqs, first = serve(params, cfg, traffic, mode="w8a8")
    check(eng.cache_format == "bf16", eng.cache_format)
    check_complete("A", reqs, traffic.max_new)
    check_logits("A vs bf16", ref_first, first)


def first_layers(params, cfg, n):
    """The model's first ``n`` layers: the same weights, depth cut."""
    import jax

    stack = jax.tree_util.tree_map(lambda a: a[:n], params["stack"])
    return {**params, "stack": stack}, dataclasses.replace(cfg, n_layers=n)


def phase_b(params, cfg, traffic, ref_first):
    """Serve the paper's stack and its jnp twin.  Logits are held to the
    CPU tests' bound only up to CHECK_LAYERS deep; deeper they are printed
    (W4A4 rounding turns tiny differences into large ones at depth).  The
    fused kernel is always held to its twin directly, on the served cache.

    Both run on roomy page pools: the fused format's pages hold 128
    positions, its twin's 8, so a shared prefix that ends inside a page
    saves the fused run fewer pages, and under a pool that fills the two
    would wait and evict differently; with room, slots and the token
    budget alone set the schedule the twin check needs."""
    checked = cfg.n_layers <= CHECK_LAYERS
    eng, reqs, first = serve(
        params, cfg, traffic, mode=PHASE_B_MODE,
        cache_format="paged_int4_bp_fused", scheduler="prefix_cache",
        roomy=True)
    check_complete("B", reqs, traffic.max_new)
    check_logits(f"B vs bf16, {cfg.n_layers} layers", ref_first, first,
                 tolerance=checked)
    pool = eng.page_pool.stats()
    print(f"  B page pool: {pool}", flush=True)
    check(pool["prefix_hits"] > 0, "B: no request attached the shared prefix")

    # the jnp plane-math twin, fed the same tokens
    twin, twin_reqs, _ = serve(
        params, cfg, traffic, mode=PHASE_B_MODE,
        cache_format="paged_int4_bp", scheduler="prefix_cache",
        force=[np.asarray(r.out) for r in reqs], roomy=True)
    check_complete("B twin", twin_reqs, traffic.max_new)
    kinds = [(k, s) for k, s, _ in eng.logit_trace]
    check(kinds == [(k, s) for k, s, _ in twin.logit_trace],
          "B twin: schedule differs from the fused run")
    check_logits(f"B fused vs twin, {cfg.n_layers} layers, all steps",
                 [lg for _, _, lg in twin.logit_trace],
                 [lg for _, _, lg in eng.logit_trace], tolerance=checked)
    check_fused_attention_on_cache(eng, cfg)
    return eng


def twin_cache(cache):
    """Re-lay one layer of a ``paged_int4_bp_fused`` cache (page pool in
    the kernel's layout) into ``paged_int4_bp``'s: every slot's ring,
    gathered in order through its block table, paged again behind an
    identity table."""
    import jax.numpy as jnp

    from repro.core import kvcache

    fused = kvcache.get_cache_format("paged_int4_bp_fused")
    twin = kvcache.get_cache_format("paged_int4_bp")
    out = {"pos_ids": cache["pos_ids"]}
    for prefix in ("k", "v"):
        ring = fused._gather(fused.channel(cache, prefix))  # [B, L, ...]
        b, ln = ring["_scale"].shape[:2]
        n = b * ln // twin.page_size
        store = {sfx: a.reshape(n, twin.page_size, *a.shape[2:])
                 for sfx, a in ring.items()}
        store["_pages"] = jnp.arange(n, dtype=jnp.int32).reshape(b, -1)
        out.update(twin.channel_entries(prefix, store))
    return out


def check_fused_attention_on_cache(eng, cfg):
    """Read layer 0 of phase B's real page pool through the fused kernel,
    in place, and through the twin's plane math on the same positions
    re-laid into the twin's layout."""
    import jax
    import jax.numpy as jnp

    from repro.core import kvcache
    from repro.models import attention

    slot = eng.caches["stack"]["slot0"]
    cache = jax.tree_util.tree_map(lambda a: a[0], slot.get("self", slot))
    rng = np.random.default_rng(SEED + 1)
    q = jnp.asarray(rng.normal(
        size=(eng.slots, 1, cfg.n_heads, cfg.d_head)).astype(np.float32))
    cur = jnp.asarray(np.maximum(eng.pos - 1, 0), jnp.int32)
    outs = {}
    for name, c in (("paged_int4_bp_fused", cache),
                    ("paged_int4_bp", twin_cache(cache))):
        fmt = kvcache.get_cache_format(name)
        with jax.default_matmul_precision("highest"):
            outs[name] = np.asarray(attention._decode_attention(
                q, c, cur=cur, window=None, fmt=fmt))
    err = np.abs(outs["paged_int4_bp_fused"] - outs["paged_int4_bp"]).max()
    check(np.allclose(outs["paged_int4_bp_fused"], outs["paged_int4_bp"],
                      rtol=ATTN_TOL, atol=ATTN_TOL),
          f"B: fused decode attention off its twin by {err:.3g}")
    print(f"  B fused vs twin decode attention on the served cache "
          f"(L={cache['pos_ids'].shape[1]}): max |diff| {err:.3g}", flush=True)


def check_mosaic_in_decode(eng):
    """The lowered decode step of phase B carries the Pallas TPU kernel."""
    import jax.numpy as jnp

    toks = jnp.zeros((eng.slots, 1), jnp.int32)
    pos = jnp.asarray(eng.pos, jnp.int32)
    text = eng._decode.lower(eng.params, toks, eng.caches, pos).as_text()
    n = text.count("tpu_custom_call")
    check(n > 0, "B: the lowered decode step has no tpu_custom_call")
    print(f"  B decode step: {n} tpu_custom_call site(s)", flush=True)


# ---------------------------------------------------------------------------
# Phase C: kernels against their oracles
# ---------------------------------------------------------------------------


#: (ring length, query tokens per slot) for the decode-attention checks:
#: the served ring, then a 32768-slot ring, each for one decode token and
#: for a 32-token prefill chunk (the kernel streams 128-position blocks,
#: so the ring's length no longer bounds its VMEM)
ATTENTION_CASES = ((1024, 1), (1024, 32), (32768, 1), (32768, 32))


def phase_c(cfg, *, batches=(1, 8, 128), attention_cases=ATTENTION_CASES):
    import jax
    import jax.numpy as jnp

    from repro.core import bitplane
    from repro.core.quant import QuantTensor
    from repro.kernels import ops, plane_attn, ref

    rng = np.random.default_rng(SEED + 2)
    widths = [(cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)]

    def ints(shape, lo, hi):
        return jnp.asarray(rng.integers(lo, hi, size=shape).astype(np.int8))

    n_checked = 0
    for m in batches:
        for k, n in widths:
            x, w = ints((m, k), -128, 128), ints((k, n), -128, 128)
            exact = ref.matmul_int8_ref(x, w)
            check(bool(jnp.all(ops.matmul_int8_raw(x, w) == exact)),
                  f"C: int8 m{m} {k}x{n} not bit-exact")
            xs = jnp.asarray(rng.uniform(0.01, 1, (m, 1)), jnp.float32)
            ws = jnp.asarray(rng.uniform(0.01, 1, (1, n)), jnp.float32)
            out = ops.quant_matmul(QuantTensor(x, xs, 8, -1),
                                   QuantTensor(w, ws, 8, 0))
            check(np.allclose(out, ref.matmul_int8_scaled_ref(x, w, xs, ws),
                              rtol=1e-6, atol=1e-6),
                  f"C: int8 scaled m{m} {k}x{n} off")
            a, q4 = ints((m, k), -8, 8), ints((k, n), -8, 8)
            wp = bitplane.encode_weights(q4)
            exact = ref.bsdp_ref(a, q4)
            for kernel in ("gemv", "gemm", "gemm_fused"):
                out = ops.bsdp_matmul(a, wp, kernel=kernel)
                check(bool(jnp.all(out == exact)),
                      f"C: bsdp {kernel} m{m} {k}x{n} not bit-exact")
            n_checked += 5

    slots, heads, feat = 4, cfg.n_kv_heads, cfg.d_head
    group = cfg.n_heads // cfg.n_kv_heads
    fw = -(-feat // bitplane.WORD)
    for ln, tokens in attention_cases:
        g = tokens * group
        # a ring of 128-position pages behind a shuffled block table, or
        # one page per slot where the ring is no multiple of 128
        page = plane_attn.block_len(ln)
        npp = ln // page
        table = rng.permutation(slots * npp).reshape(slots, npp)

        def pool(ring):  # [slots, H, L, W] → [pages, H, W, page]
            x = ring.reshape(slots, heads, npp, page, -1)
            x = x.transpose(0, 2, 1, 4, 3).reshape(slots * npp, heads, -1,
                                                   page)
            return jnp.zeros_like(x).at[table.ravel()].set(x)

        planes = [bitplane.encode(ints((slots, heads, ln, fw * bitplane.WORD),
                                       -8, 8)) for _ in range(2)]
        scales = [jnp.asarray(rng.uniform(lo, hi, (slots, heads, ln)),
                              jnp.float32) for lo, hi in ((0.01, 0.2),
                                                          (0.1, 1))]
        # slot i's queries sit at the end of its first (i+1)/4 of the
        # ring; the ring's last 7 positions are empty
        pos_ids = np.where(np.arange(ln) < ln - 7, np.arange(ln), -1)
        q_pos = np.repeat((np.arange(1, slots + 1) * ln // slots - 1)[:, None],
                          g, axis=1)
        common = dict(
            q=ints((slots, heads, g, fw * bitplane.WORD), -8, 8),
            q_scale=jnp.asarray(rng.uniform(0.01, 0.2, (slots, heads, g)),
                                jnp.float32),
            q_pos=jnp.asarray(q_pos, jnp.int32),
            pos_ids=jnp.asarray(np.broadcast_to(pos_ids, (slots, ln)),
                                jnp.int32))
        out = ops.plane_decode_attention(
            **common, k_words=pool(planes[0].reshape(slots, heads, ln, -1)),
            k_scale=pool(scales[0])[:, :, 0],
            v_words=pool(planes[1].reshape(slots, heads, ln, -1)),
            v_scale=pool(scales[1])[:, :, 0],
            table=jnp.asarray(table, jnp.int32),
            sm_scale=feat ** -0.5, feat=feat)
        exp = ref.plane_decode_attention_ref(
            **common, k_planes=planes[0], k_scale=scales[0],
            v_planes=planes[1], v_scale=scales[1], sm_scale=feat ** -0.5,
            feat=feat)
        err = float(jnp.max(jnp.abs(out - exp)))
        check(np.allclose(out, exp, rtol=ATTN_TOL, atol=ATTN_TOL),
              f"C: plane attention L{ln} G{g} off by {err:.3g}")
        print(f"  C plane attention L={ln} G={g}: max |diff| {err:.3g}",
              flush=True)
        n_checked += 1
    print(f"  C: {n_checked} kernel checks at d_model {cfg.d_model}, "
          f"d_ff {cfg.d_ff}", flush=True)


# ---------------------------------------------------------------------------
# --four-chips: sharded training
# ---------------------------------------------------------------------------


def train_losses(cfg, *, steps, seq_len, global_batch, mesh_shape=None):
    """Train ``steps`` steps through the launcher's Trainer; returns
    ``(losses, final params)``."""
    from repro.configs.base import ShapeCell
    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import make_mesh, plan
    from repro.train.trainer import Trainer, TrainerConfig

    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                      global_batch=global_batch, seed=SEED)
    mesh = rules = None
    tp = 1
    if mesh_shape is not None:
        mesh = make_mesh(mesh_shape, ("data", "model"))
        rules = plan(cfg, ShapeCell("chip_smoke", seq_len, global_batch,
                                    "train"), mesh).rules
        tp = mesh_shape[1]
    tr = Trainer(cfg, data, TrainerConfig(steps=steps, log_every=1,
                                          seed=SEED),
                 mesh=mesh, rules=rules, tp=tp)
    out = tr.run()
    return [h["loss"] for h in out["history"]], out["params"]


def four_chips(cfg, *, steps=3, seq_len=256, global_batch=8):
    import jax

    check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, "
          f"found {len(jax.devices())}")
    cut = dataclasses.replace(cfg, n_layers=4)
    mesh_losses, _ = train_losses(cut, steps=steps, seq_len=seq_len,
                                  global_batch=global_batch, mesh_shape=(2, 2))
    one_losses, _ = train_losses(cut, steps=steps, seq_len=seq_len,
                                 global_batch=global_batch)
    print(f"  4-layer losses, 2x2 mesh: {mesh_losses}", flush=True)
    print(f"  4-layer losses, 1 device: {one_losses}", flush=True)
    check(all(np.isfinite(mesh_losses + one_losses)), "non-finite loss")
    check(np.allclose(mesh_losses[0], one_losses[0],
                      rtol=LOSS_RTOL, atol=LOSS_ATOL),
          f"first-step losses differ: {mesh_losses[0]} vs {one_losses[0]}")

    losses, params = train_losses(cfg, steps=steps, seq_len=seq_len,
                                  global_batch=global_batch, mesh_shape=(2, 2))
    print(f"  {cfg.n_layers}-layer losses, 2x2 mesh: {losses}", flush=True)
    check(all(np.isfinite(losses)), "non-finite full-depth loss")
    stats = [d.memory_stats() for d in jax.devices()[:4]]
    for d, st in zip(jax.devices(), stats):
        print(f"  {d}: {st}", flush=True)
    if all(stats):  # the CPU backend reports none
        in_use = [st["bytes_in_use"] for st in stats]
        check(max(in_use) < 0.5 * sum(in_use),
              f"parameters not spread over the mesh: {in_use}")
    del params


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training path on 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})",
          flush=True)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    from repro import obs
    from repro.configs import get_config
    from repro.launch import compile_cache
    from repro.models import model as model_lib
    from repro.sharding import partitioning as P

    print(f"compilation cache: {compile_cache.enable()}", flush=True)
    # a sink turns the obs compile counters on; the records are not kept
    obs.register_sink(obs.NullSink())
    cfg = get_config(ARCH)
    t_start = time.perf_counter()

    if args.four_chips:
        run_phase("four_chips", lambda: four_chips(cfg))
    else:
        traffic = Traffic()
        params = run_phase(
            "init", lambda: jax.block_until_ready(P.materialize(
                model_lib.specs(cfg, 1), jax.random.PRNGKey(SEED))))
        ref_first = run_phase(
            "reference", lambda: phase_reference(params, cfg, traffic))
        run_phase("A", lambda: phase_a(params, cfg, traffic, ref_first))
        eng = run_phase("B", lambda: phase_b(params, cfg, traffic, ref_first))
        check_mosaic_in_decode(eng)
        del eng
        cut_params, cut = first_layers(params, cfg, CHECK_LAYERS)
        run_phase(f"B at {CHECK_LAYERS} layers", lambda: phase_b(
            cut_params, cut, traffic,
            phase_reference(cut_params, cut, traffic)))
        del params, cut_params
        run_phase("C", lambda: phase_c(cfg))

    total = time.perf_counter() - t_start
    print(f"total: wall {total:.1f}s  "
          f"compile {obs.counter_total('jax.compile_s'):.1f}s  "
          f"cache hits {obs.counter_total('jax.compile_cache_hit'):.0f}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
