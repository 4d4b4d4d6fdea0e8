"""Random weights from the run's seed, made on the device in one jitted call.

No checkpoint ships with the repository.  ``generate`` draws every tensor
of a configuration's published layout (its family's ``shapes``) from the
seed, in the type it is served in.  The plain reference draws them again,
from the same seed, with the same function; ``program_params`` maps the
same draw onto the serving program's parameter tree (the family's
``program_tree``).  Nothing the program makes flows back into the reference.

Scales keep a deep random model in a sane regime: projections are normal
with variance 1/fan-in, the embedding has the published initializer's
spread, and norm gains sit near one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the published initializer range of both models' embeddings (0.02 and
#: 0.018).  A unit-scale embedding would dominate the residual stream, and
#: a tied head would then just repeat each input token with a margin no
#: rounding can flip, leaving the served-token check blind.
EMBED_STD = 0.02


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer (seeds may exceed 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def _draw(key, name, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_bias"):
        return (0.1 * z).astype(dtype)
    if "norm" in name:
        return (1.0 + 0.1 * z).astype(dtype)
    if name == "embed":
        return (EMBED_STD * z).astype(dtype)
    return (z * shape[-2] ** -0.5).astype(dtype)  # fan-in is the K axis


def generate(family, model: dict, key: jax.Array) -> dict:
    """Every tensor of ``family.shapes(model)``, drawn from ``key`` (trace
    under ``jit``)."""
    table = family.shapes(model)
    keys = jax.random.split(key, len(table))
    return {name: _draw(k, name, *table[name])
            for k, name in zip(keys, sorted(table))}


@functools.lru_cache(maxsize=None)
def _jitted(family, kind: str, frozen_model: tuple):
    model = dict(frozen_model)
    if kind == "reference":
        return jax.jit(lambda key: generate(family, model, key))
    return jax.jit(
        lambda key: family.program_tree(model, generate(family, model, key)))


def reference_weights(family, model: dict, seed: int) -> dict:
    return _jitted(family, "reference", tuple(sorted(model.items())))(
        key_for(seed))


def program_params(family, model: dict, seed: int) -> dict:
    return _jitted(family, "program", tuple(sorted(model.items())))(
        key_for(seed))
