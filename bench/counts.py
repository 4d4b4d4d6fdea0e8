"""Operations and bytes the work needs, computed from shapes alone.

These are the numerators of each kernel's roofline share, and with a
family's ``rows_flops`` of ``mfu``.  They count what the algorithm needs at
the positions that hold a token, not what an implementation happens to
touch: padding, empty ring slots, masks and logits nobody reads are left
out, so a faster implementation can only raise a share towards 100%, never
past it.
"""

from __future__ import annotations

from typing import NamedTuple


class Rows(NamedTuple):
    """One request's work in one step: ``n`` tokens at positions
    ``start … start+n-1``, and whether the step read logits for it."""

    start: int
    n: int
    logits: bool
    kind: str  # "prefill" | "chunk" | "decode"


def contexts(start: int, n: int) -> int:
    """Sum of the context lengths of tokens at ``start … start+n-1``."""
    return n * start + n * (n + 1) // 2


#: bytes one cached position holds per kv head in an int4 bit-plane cache:
#: four 32-bit plane words per 32 features (d_head / 2 bytes) and a float32
#: scale, for K and for V
def plane_position_bytes(d_head: int) -> int:
    return 2 * (d_head // 2 + 4)


def plane_attn_work(model: dict, rows) -> tuple[int, int]:
    """(operations, bytes) the bit-plane decode attention needs in one call
    of one layer, for the chunk and decode rows of one step.

    Each row reads the K and V of the positions that hold a token after the
    step's writes (``start + n``), once per kv head, and its queries:
    ``n × group`` of them per kv head, each scoring and averaging over the
    positions it may see (``p + 1``).  Query in (int8) and output (float32)
    bytes are counted; masks and empty ring slots are not.
    """
    kv, dh = model["n_kv_heads"], model["d_head"]
    group = model["n_heads"] // kv
    ops = nbytes = 0
    for r in rows:
        if r.kind == "prefill":
            continue  # the first chunk runs the prefill path, not the kernel
        held = r.start + r.n
        ops += kv * group * 4 * dh * contexts(r.start, r.n)
        nbytes += kv * (held * plane_position_bytes(dh)
                        + r.n * group * dh * (1 + 4))
    return ops, nbytes


def roofline_seconds(ops: int, nbytes: int, peak_ops: float,
                     peak_bytes: float) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops, t_bytes = ops / peak_ops, nbytes / peak_bytes
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
