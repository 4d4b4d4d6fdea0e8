"""Share of the window the engine spent reserving slots for the requests it
admits: the ``engine.reserve`` spans (prefix attach, page reservation and,
under a full pool, the prefix-index evictions that free pages) inside the
window, over its length.  The reservation runs at the start of a step,
before the step's model call, so the device waits through it.  Moves
``output_tok_s``."""


def read(run):
    spans = [s for s in run.spans if s.name == "engine.reserve"]
    if not spans:
        return None
    return 100.0 * sum(s.dur for s in spans) / run.seconds
