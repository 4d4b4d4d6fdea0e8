"""90th percentile of the queue wait of the requests that got a slot in the
window: each ``request.scheduled`` event's ``wait_s`` (the engine's Stamp
when the request left the queue holding a slot, less its arrival Stamp),
over the events that fell inside the window's ``engine.step`` spans.
Moves ``ttft_p90_ms``.

The run record holds the window's spans only; the lifecycle events are read
from the program's ring, which the harness keeps registered while its
readers run.  A program that emits no ``request.scheduled`` gives nothing.
"""

import bisect

import numpy as np

SCHEDULED = "request.scheduled"


def _scheduled_events(run):
    """The ``request.scheduled`` events inside the window's steps."""
    from repro.obs import trace as obs

    steps = sorted((s.ts, s.ts + s.dur) for s in run.spans
                   if s.name == "engine.step")
    starts = [s for s, _ in steps]
    out = []
    for sink in obs.sinks():
        if not isinstance(sink, obs.RingSink):
            continue
        for r in sink.records():
            if not (isinstance(r, obs.PointRecord) and r.kind == "event"
                    and r.name == SCHEDULED):
                continue
            i = bisect.bisect_right(starts, r.ts) - 1
            if i >= 0 and r.ts <= steps[i][1]:
                out.append(r)
    return out


def read(run):
    waits = [r.labels["wait_s"] for r in _scheduled_events(run)]
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 90))
