"""Programs compiled (or read back from the compilation cache) during the
measured window, from JAX's monitoring events.  Every one is a stall that
set-up should have paid; moves ``itl_p95_ms``."""


def read(run):
    return float(run.compiles)
