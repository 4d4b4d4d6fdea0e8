"""Operation and byte counts against a case worked by hand, and the readers
that turn them into ``mfu`` and ``plane_attn_roofline``."""

from types import SimpleNamespace as NS

import pytest

from bench import counts, harness

DENSE = harness.load_family("dense")

#: two layers, d 8, 2 query heads over 1 kv head of 4, FFN 16, vocab 10
MODEL = dict(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, d_head=4,
             d_ff=16, vocab_size=10, act="silu")
CHUNK = counts.Rows(start=3, n=2, logits=True, kind="chunk")
DECODE = counts.Rows(start=10, n=1, logits=True, kind="decode")
PREFILL = counts.Rows(start=0, n=4, logits=False, kind="prefill")


def test_matmul_params():
    # per layer: q 8·8 + k,v 2·8·4 + o 8·8 = 192; SwiGLU 3·8·16 = 384
    assert DENSE.matmul_params(MODEL) == 2 * (192 + 384)
    assert DENSE.matmul_params({**MODEL, "act": "gelu"}) == 2 * (192 + 256)


def test_rows_flops():
    # per token 2·1152 = 2304; attention 4·L·H·dh = 64 per context position;
    # head 2·8·10 = 160 once per row whose logits are read
    assert DENSE.rows_flops(MODEL, [CHUNK]) == 2 * 2304 + 64 * (4 + 5) + 160
    assert DENSE.rows_flops(MODEL, [DECODE]) == 2304 + 64 * 11 + 160
    assert DENSE.rows_flops(MODEL, [PREFILL]) == 4 * 2304 + 64 * 10


def test_plane_attn_work():
    # a position holds 2·(4/2 + 4) = 12 bytes per kv head (K and V planes
    # and scales); each query is 4 int8 in and 4 float32 out per feature
    assert counts.plane_position_bytes(4) == 12
    ops, nbytes = counts.plane_attn_work(MODEL, [CHUNK, DECODE, PREFILL])
    assert ops == 2 * 4 * 4 * (4 + 5) + 2 * 4 * 4 * 11
    assert nbytes == (5 * 12 + 2 * 2 * 4 * 5) + (11 * 12 + 2 * 4 * 5)


def test_roofline_seconds():
    assert counts.roofline_seconds(640, 312, 100.0, 10.0) == (31.2, "memory")
    assert counts.roofline_seconds(6400, 312, 100.0, 10.0) == (64.0, "compute")


PEAKS = {"bf16_flops_per_s": 1000.0, "int8_ops_per_s": 2000.0,
         "hbm_bytes_per_s": 100.0}


def _record(**kw):
    cell = NS(model=MODEL, family=DENSE)
    base = dict(cell=cell, seconds=2.0, steps=[], traced_steps=[],
                trace=None, peaks=PEAKS, spans=[], pool_window=None,
                compiles=0)
    base.update(kw)
    return NS(**base)


def test_mfu_reader():
    read = harness.load_reader("mfu")
    rec = _record(steps=[(0, 1, [CHUNK]), (1, 2, [DECODE, PREFILL])])
    flops = 5344 + 3168 + 9856
    assert read(rec) == pytest.approx(100 * flops / 2.0 / 1000.0)
    assert read(_record()) is None


def test_plane_attn_roofline_reader():
    read = harness.load_reader("plane_attn_roofline")
    kernel = "plane_decode_attention"
    trace = NS(kernel_s=lambda pattern: 20.0 if pattern == kernel else 0)
    rec = _record(trace=trace, traced_steps=[(0, 1, [CHUNK, DECODE])])
    # per layer 640 ops and 312 bytes, two layers: memory-bound, 6.24 s
    assert read(rec) == pytest.approx(100 * 6.24 / 20.0)
    assert read(_record(trace=NS(kernel_s=lambda p: 0.0))) is None


def test_span_readers():
    span = lambda name, ts, dur: NS(name=name, ts=ts, dur=dur)  # noqa: E731
    spans = [span("engine.step", 0.0, 1.0), span("engine.prefill", 0.1, 0.2),
             span("engine.decode", 0.4, 0.5), span("engine.step", 2.0, 0.5),
             span("engine.decode", 2.1, 0.3)]
    rec = _record(spans=spans)
    assert harness.load_reader("host_ms_per_step")(rec) == pytest.approx(
        1e3 * ((1.0 - 0.7) + (0.5 - 0.3)) / 2)
    assert harness.load_reader("decode_step_ms")(rec) == pytest.approx(400)


def test_prefix_hit_share_reader():
    read = harness.load_reader("prefix_hit_share")
    rec = _record(pool_window={"prefix_tokens_saved": 96},
                  steps=[(0, 1, [CHUNK, DECODE, PREFILL])])
    assert read(rec) == pytest.approx(100 * 96 / (96 + 2 + 4))
    assert read(_record()) is None
