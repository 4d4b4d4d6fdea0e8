"""Model FLOPs the window needed, over the window's length and the chip's
bfloat16 peak.  The FLOPs are counted per token processed, at its real
context length, the same whatever formats implement the step (the cell's
family's ``rows_flops``).  Moves ``output_tok_s``."""


def read(run):
    family, model = run.cell.family, run.cell.model
    flops = sum(family.rows_flops(model, rows) for _, _, rows in run.steps)
    if flops == 0:
        return None
    return 100.0 * flops / run.seconds / run.peaks["bf16_flops_per_s"]
