"""Mean duration of the ``engine.decode`` spans in the window: one model
invocation for the step's chunk and decode rows, its logits read back to
the host included.  Moves ``itl_p95_ms``."""


def read(run):
    spans = [s.dur for s in run.spans if s.name == "engine.decode"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
