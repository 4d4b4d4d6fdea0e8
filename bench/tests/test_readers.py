"""The readers of the engine's spans and lifecycle events, on hand-built
run records."""

import pytest

from bench import harness
from repro.obs import trace as obs


def _record(spans, seconds=1.0):
    return harness.RunRecord(
        cell=None, seconds=seconds, steps=[], spans=spans, compiles=0,
        pool_window=None, trace=None, traced_steps=[], peaks={})


def _span(name, ts, dur, depth):
    return obs.SpanRecord(name, ts, dur, depth, {})


def _scheduled(ts, wait_s, uid):
    return obs.PointRecord("event", "request.scheduled", ts, 0.0,
                           {"uid": uid, "state": "queued", "t": ts,
                            "step": 0, "work": 0, "prompt_len": 8,
                            "new_tokens": 0, "wait_s": wait_s})


@pytest.fixture
def ring():
    obs.clear_sinks()
    sink = obs.register_sink(obs.RingSink())
    yield sink
    obs.clear_sinks()
    obs.reset_metrics()


STEPS = [_span("engine.step", 10.0, 1.0, 0),
         _span("engine.step", 12.0, 1.0, 0)]


def test_queue_wait_p90_reads_scheduled_events_inside_the_steps(ring):
    read = harness.load_reader("queue_wait_p90_ms")
    waits = [0.1 * i for i in range(1, 11)]          # 0.1 … 1.0 s
    for i, w in enumerate(waits):
        ring.on_point(_scheduled(10.0 + 0.05 * i, w, i))
    ring.on_point(_scheduled(11.5, 9.0, 20))         # between two steps
    ring.on_point(_scheduled(5.0, 9.0, 21))          # before the window
    ring.on_point(obs.PointRecord("event", "request.arrival", 10.2, 0.0,
                                  {"uid": 30, "t": 10.2}))
    # numpy's linear 90th percentile of 0.1 … 1.0 is 0.91 s
    assert read(_record(STEPS)) == pytest.approx(910.0)


def test_queue_wait_p90_without_scheduled_events_is_absent(ring):
    """A program that emits no ``request.scheduled`` reads nothing."""
    read = harness.load_reader("queue_wait_p90_ms")
    ring.on_point(obs.PointRecord("event", "request.arrival", 10.2, 0.0,
                                  {"uid": 1, "t": 10.2}))
    assert read(_record(STEPS)) is None


def test_prefill_host_ms_subtracts_the_readback():
    read = harness.load_reader("prefill_host_ms_per_step")
    spans = STEPS + [
        _span("engine.prefill", 10.1, 0.5, 1),
        _span("engine.readback", 10.4, 0.2, 2),      # host work: 0.3 s
        _span("engine.decode", 10.7, 0.2, 1),
        _span("engine.readback", 10.8, 0.1, 2),      # decode's: not counted
        _span("engine.prefill", 12.1, 0.3, 1),
        _span("engine.readback", 12.3, 0.05, 2),     # host work: 0.25 s
    ]
    assert read(_record(spans)) == pytest.approx(1e3 * 0.55 / 2)


def test_prefill_host_ms_is_zero_without_prefill():
    read = harness.load_reader("prefill_host_ms_per_step")
    spans = STEPS + [_span("engine.decode", 10.1, 0.5, 1),
                     _span("engine.readback", 10.3, 0.2, 2)]
    assert read(_record(spans)) == 0.0


def test_prefill_host_ms_needs_the_readback_span():
    """A program whose prefill does not split out the readback (or a
    window with no step) reads nothing."""
    read = harness.load_reader("prefill_host_ms_per_step")
    assert read(_record(STEPS + [_span("engine.prefill", 10.1, 0.5, 1)])) \
        is None
    assert read(_record([])) is None


def test_reserve_share_sums_the_reserve_spans_over_the_window():
    spans = STEPS + [_span("engine.reserve", 10.1, 0.25, 1),
                     _span("engine.reserve", 12.1, 0.05, 1)]
    read = harness.load_reader("reserve_share")
    assert read(_record(spans, seconds=2.0)) == pytest.approx(15.0)


def test_reserve_share_without_reserve_spans_is_absent():
    assert harness.load_reader("reserve_share")(_record(STEPS)) is None
