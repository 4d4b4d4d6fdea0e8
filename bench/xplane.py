"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The profiler writes one XSpace per traced host: a plane per device
(``/device:TPU:0`` ...) whose ``XLA Ops`` line holds one event per operation
the device ran, and a host plane (``/host:CPU``) whose thread lines hold the
harness's ``jax.profiler.TraceAnnotation`` spans (``bench.*``) and JAX's own
host events.  All timestamps share one clock.

:func:`reduce` clips everything to the harness's ``bench.window`` span and
gives the device busy time (the union of operation intervals, per device),
the time of each operation by name, and the idle gaps labelled with what the
host was doing at their midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Iterable

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
#: idle gaps shorter than this are summed under one label, not attributed
MIN_GAP_NS = 20_000


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # length of the traced window
    busy_s: float                   # union of op intervals, mean over devices
    devices: int
    op_s: dict                      # op name → seconds, summed over devices
    gaps: list                      # [(host label, seconds)] per idle gap

    def kernel_s(self, pattern: str) -> float:
        """Device seconds of every operation whose name contains
        ``pattern``, summed over devices."""
        return sum(s for n, s in self.op_s.items() if pattern in n)

    def top_ops(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.op_s.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> list:
        """Idle seconds summed by host label, largest first."""
        tot: dict = {}
        for label, s in self.gaps:
            tot[label] = tot.get(label, 0.0) + s
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def merge(intervals: Iterable[tuple]) -> list:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` → ``fusion.12``: the HLO
    instruction's name, without its text."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _leaves(events: list) -> list:
    """The events that contain no other event: a ``while`` op's event
    spans its body's, and counting both would count the body twice."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (s, e, n) in enumerate(events):
        if i + 1 < len(events) and events[i + 1][0] < e:
            continue
        out.append((s, e, n))
    return out


def _host_events(planes) -> list:
    """(start, end, name, depth) of host events on the thread that carries
    the harness's annotations."""
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events]
            if any(n.startswith("bench.") for _, _, n in evs):
                return evs
    return []


class _Labeler:
    """What the host was doing at a time: the innermost ``bench.*``
    annotation covering it, and the innermost other host event inside."""

    def __init__(self, host: list):
        self.bench = sorted((s, e, n) for s, e, n in host
                            if n.startswith("bench.") and n != WINDOW)
        self.other = sorted((s, e, n) for s, e, n in host
                            if not n.startswith("bench."))
        self._bs = [s for s, _, _ in self.bench]
        self._os = [s for s, _, _ in self.other]

    @staticmethod
    def _innermost(events, starts, t, lookback):
        i = bisect.bisect_right(starts, t)
        for s, e, n in reversed(events[max(0, i - lookback):i]):
            if e > t:
                return s, n
        return None

    def __call__(self, t: float) -> str:
        outer = self._innermost(self.bench, self._bs, t, 64)
        inner = self._innermost(self.other, self._os, t, 512)
        label = outer[1] if outer else "no annotation"
        if inner and (outer is None or inner[0] >= outer[0]):
            label += " > " + inner[1]
        return label


def reduce_planes(planes) -> TraceSummary:
    planes = list(planes)
    host = _host_events(planes)
    wins = [(s, e) for s, e, n in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    lo, hi = wins[0]
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    label = _Labeler(host)
    op_s: dict = {}
    busy_total = 0.0
    gaps: list = []
    for plane in devices:
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if e > s:
                    events.append((s, e, op_name(ev.name)))
        for s, e, name in _leaves(events):
            op_s[name] = op_s.get(name, 0.0) + (e - s) * 1e-9
        busy = merge((s, e) for s, e, _ in events)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        short = 0.0
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge - gs >= MIN_GAP_NS:
                gaps.append((label((gs + ge) / 2), (ge - gs) * 1e-9))
            elif ge > gs:
                short += (ge - gs) * 1e-9
        gaps.append((f"between ops (gaps under {MIN_GAP_NS // 1000} us)",
                     short))
    n = max(len(devices), 1)
    return TraceSummary(window_s=(hi - lo) * 1e-9, busy_s=busy_total / n,
                        devices=len(devices), op_s=op_s, gaps=gaps)


def reduce(path: str) -> TraceSummary:
    """Summary of the ``.xplane.pb`` at ``path`` (a file, or a directory the
    profiler wrote into)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return reduce_planes(ProfileData.from_file(path).planes)
