"""Host time per engine step: each ``engine.step`` span minus the
``engine.prefill`` and ``engine.decode`` spans inside it (which end in the
device wait), averaged over the steps of the window.  Planning, page
bookkeeping and building the step's inputs; moves ``itl_p95_ms``."""


def read(run):
    steps = [s for s in run.spans if s.name == "engine.step"]
    inner = sorted((s.ts, s.dur) for s in run.spans
                   if s.name in ("engine.prefill", "engine.decode"))
    if not steps:
        return None
    total, j = 0.0, 0
    for st in sorted(steps, key=lambda s: s.ts):
        device = 0.0
        while j < len(inner) and inner[j][0] < st.ts:
            j += 1
        k = j
        while k < len(inner) and inner[k][0] < st.ts + st.dur:
            device += inner[k][1]
            k += 1
        total += st.dur - device
    return 1e3 * total / len(steps)
