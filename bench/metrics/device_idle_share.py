"""Share of the traced slice in which no operation ran on the device:
1 − (union of the device's operation intervals ÷ the slice).  Moves
``output_tok_s``."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
