"""Host time in prefill per engine step: each ``engine.prefill`` span less
the ``engine.readback`` spans inside it (the wait for the logits on the
host), summed over the window and divided by the window's ``engine.step``
spans.  What is left is the host's own work in prefill: tracing, compiling
or reading the compilation cache, dispatch and the cache splice.  0 when no
prefill ran; added to ``host_ms_per_step`` it gives the host's time per
step outside ``engine.decode``.  Moves ``itl_p95_ms``.

A program whose prefill has no ``engine.readback`` span gives nothing.
"""


def read(run):
    steps = [s for s in run.spans if s.name == "engine.step"]
    prefills = sorted((s.ts, s.dur) for s in run.spans
                      if s.name == "engine.prefill")
    readbacks = sorted((s.ts, s.dur) for s in run.spans
                       if s.name == "engine.readback")
    if not steps or (prefills and not readbacks):
        return None
    total, j = 0.0, 0
    for ts, dur in prefills:
        while j < len(readbacks) and readbacks[j][0] < ts:
            j += 1
        k = j
        while k < len(readbacks) and readbacks[k][0] < ts + dur:
            total -= readbacks[k][1]
            k += 1
        total += dur
    return 1e3 * total / len(steps)
