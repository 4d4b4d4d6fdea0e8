"""The bit-plane decode-attention kernel's share of its roofline over the
traced slice: the least time the chip needs for the operations and bytes
of the positions that hold a token (:func:`bench.counts.plane_attn_work`,
once per layer per step), over the kernel's device time.  Moves
``output_tok_s``."""

from bench import counts

#: the Pallas kernel's name as the device trace shows it
#: (``plane_decode_attention.<n>``, a ``tpu_custom_call``)
KERNEL = "plane_decode_attention"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.kernel_s(KERNEL)
    if seconds <= 0:
        return None
    model = run.cell.model
    ops = nbytes = 0
    for _, _, rows in run.traced_steps:
        o, b = counts.plane_attn_work(model, rows)
        ops += o * model["n_layers"]
        nbytes += b * model["n_layers"]
    t_min, _ = counts.roofline_seconds(
        ops, nbytes, run.peaks["int8_ops_per_s"],
        run.peaks["hbm_bytes_per_s"])
    return 100.0 * t_min / seconds
