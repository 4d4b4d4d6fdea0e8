"""The plain float32 reference: each served token against its family's
forward pass.

A family (``bench/families/<family>.py``) writes its architecture's forward
pass from the published description, in straightforward ``jax.numpy``,
importing nothing of the serving program; its weights come from
:mod:`bench.weights`.  This module runs it: every matmul at ``highest``
precision, one compiled program per configuration for every request, and
the roundings an int4 decode cache makes, which a family repeats where its
configuration states them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def int4(x):
    """Round each vector (last axis) to int4 on a symmetric absmax scale,
    as an int4 decode cache stores it: ``scale = max|x| / 7``, values
    clipped to ``[-8, 7]``."""
    scale = jnp.maximum(jnp.abs(x).max(-1, keepdims=True), 1e-6) / 7
    return jnp.clip(jnp.round(x / scale), -8, 7) * scale


def _logits_rows(family, model, w, tokens, rows):
    with jax.default_matmul_precision("highest"):
        return family.logits_rows(model, w, tokens, rows)


@functools.lru_cache(maxsize=None)
def _gap_fn(family, frozen_model: tuple):
    model = dict(frozen_model)

    def run(w, tokens, rows, targets):
        """Gap of each target token below the best logit at its row, and
        the best token there."""
        logits = _logits_rows(family, model, w, tokens, rows)
        got = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return logits.max(-1) - got, logits.argmax(-1)

    return jax.jit(run)


def logits_at(family, model: dict, w: dict, tokens, rows) -> np.ndarray:
    """Reference logits ``[len(rows), V]`` of ``tokens`` at ``rows``."""
    fn = jax.jit(functools.partial(_logits_rows, family, model))
    return np.asarray(fn(w, jnp.asarray(tokens, jnp.int32),
                         jnp.asarray(rows, jnp.int32)))


def served_gaps(family, model: dict, w: dict, prompt: np.ndarray,
                served: np.ndarray, pad_to: int,
                row_cap: int) -> tuple[np.ndarray, np.ndarray]:
    """Run the reference once over ``prompt + served`` and return, for each
    served token, its gap below the reference's best logit at the position
    that produced it, and the reference's own best token there.  The
    sequence is padded to ``pad_to`` tokens and the rows to ``row_cap``, so
    one compiled program serves every request."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    n = len(served)
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} tokens exceeds pad {pad_to}")
    toks = np.zeros(pad_to, np.int32)
    toks[: len(seq)] = seq
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + n, dtype=np.int32)
    if n > row_cap:
        raise ValueError(f"{n} served tokens exceed the row cap {row_cap}")
    rows_p = np.zeros(row_cap, np.int32)
    rows_p[:n] = rows
    tg = np.zeros(row_cap, np.int32)
    tg[:n] = served
    gaps, best = _gap_fn(family, tuple(sorted(model.items())))(
        w, jnp.asarray(toks), jnp.asarray(rows_p), jnp.asarray(tg))
    return np.asarray(gaps)[:n], np.asarray(best)[:n]
