"""The trace reduction, on a hand-built trace and on a recorded one."""

import pathlib
from types import SimpleNamespace as NS

import pytest

from bench import xplane

RECORDED = pathlib.Path(__file__).parent / "data" / "trace.xplane.pb"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 1_000_000, 1_000_000),      # [1.0, 2.0) ms
        _ev("bench.step", 1_000_000, 500_000),
        _ev("PjitFunction(step)", 1_100_000, 100_000),
        _ev("bench.generate", 1_500_000, 100_000),
        _ev("bench.step", 1_600_000, 400_000),
    ])])
    other = NS(name="/device:CUSTOM:Megascale Trace", lines=[
        NS(name="XLA Ops", events=[_ev("x", 1_000_000, 900_000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_step", 0, 3_000_000)]),
        NS(name="XLA Ops", events=[
            _ev("%fusion.1 = bf16[8] fusion(..)", 900_000, 200_000),  # clipped
            _ev("%while.1 = (s32[]) while(..)", 1_200_000, 300_000),  # parent
            _ev("%plane_decode_attention.3 = f32[8] custom-call(..)",
                1_200_000, 250_000),
            _ev("%fusion.2 = f32[2] fusion(..)", 1_450_000, 50_000),
            _ev("%fusion.1 = bf16[8] fusion(..)", 1_700_000, 200_000),
            _ev("fusion.3", 1_905_000, 5_000),           # 5 us gap before
            _ev("late", 2_500_000, 10_000),              # outside window
        ]),
    ])
    return [host, other, dev]


def test_reduce_hand_built_trace():
    s = xplane.reduce_planes(_planes())
    assert s.devices == 1
    assert s.window_s == pytest.approx(1e-3)
    # busy: [1.0,1.1) + [1.2,1.5) + [1.7,1.9) + [1.905,1.91) ms
    assert s.busy_s == pytest.approx((100 + 300 + 200 + 5) * 1e-6)
    assert s.op_s["fusion.1"] == pytest.approx(300e-6)
    assert s.kernel_s("plane_decode_attention") == pytest.approx(250e-6)
    assert "while.1" not in s.op_s          # its body is counted instead
    assert "late" not in s.op_s
    gaps = dict(s.top_gaps())
    # [1.1,1.2): inside the first step, during its dispatch
    assert gaps["bench.step > PjitFunction(step)"] == pytest.approx(100e-6)
    # [1.5,1.7): generator then the second step's start (midpoint 1.6)
    assert gaps["bench.step"] == pytest.approx(200e-6 + 90e-6)
    assert sum(gaps.values()) + s.busy_s == pytest.approx(s.window_s)
    assert s.top_ops(1) == [["fusion.1", pytest.approx(300e-6)]]


def test_reduce_needs_a_window():
    planes = _planes()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        xplane.reduce_planes(planes)


def test_merge():
    assert xplane.merge([(5, 6), (1, 3), (2, 4), (4, 5)]) == [(1, 6)]
    assert xplane.merge([(1, 2), (3, 4)]) == [(1, 2), (3, 4)]


def test_reduce_recorded_tpu_trace():
    """A trace recorded on a TPU v5e by ``make_trace.py``: a two-layer
    model served for six steps through the paged int4 bit-plane cache."""
    s = xplane.reduce(str(RECORDED))
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.kernel_s("plane_decode_attention") > 0
    assert sum(v for _, v in s.gaps) + s.busy_s == pytest.approx(s.window_s)
    assert any(label.startswith("bench.step") for label, _ in s.top_gaps())
