"""Model FLOPs the window needed, over the window's length and the chip's
bfloat16 peak.  The FLOPs are counted per token processed, at its real
context length, the same whatever formats implement the step
(:func:`bench.counts.rows_flops`).  Moves ``output_tok_s``."""

from bench import counts


def read(run):
    flops = sum(counts.rows_flops(run.cell.model, rows)
                for _, _, rows in run.steps)
    if flops == 0:
        return None
    return 100.0 * flops / run.seconds / run.peaks["bf16_flops_per_s"]
