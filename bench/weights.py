"""Random weights from the run's seed, made on the device in one jitted call.

No checkpoint ships with the repository.  ``generate`` draws every tensor
of a configuration's published layout from the seed, in the type it is
served in (bfloat16 matrices and embedding, float32 norm parameters).  The
plain reference draws them again, from the same seed, with the same
function; ``program_params`` maps the same draw onto the serving program's
parameter tree.  Nothing the program makes flows back into the reference.

Scales keep a deep random model in a sane regime: projections are normal
with variance 1/fan-in, the embedding has the published initializer's
spread, and norm gains sit near one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
#: the published initializer range of both models' embeddings (0.02 and
#: 0.018).  A unit-scale embedding would dominate the residual stream, and
#: a tied head would then just repeat each input token with a margin no
#: rounding can flip, leaving the served-token check blind.
EMBED_STD = 0.02


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer (seeds may exceed 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def shapes(model: dict) -> dict:
    """Name → (shape, dtype) of every tensor, in the published layout with
    layers stacked on the leading axis."""
    L, d, h, kv, dh, ff, v = (model[k] for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "d_head", "d_ff",
        "vocab_size"))
    f32 = jnp.float32
    out = {
        "embed": ((v, d), BF16),
        "final_norm": ((d,), f32),
        "attn_norm": ((L, d), f32),
        "mlp_norm": ((L, d), f32),
        "wq": ((L, d, h * dh), BF16),
        "wk": ((L, d, kv * dh), BF16),
        "wv": ((L, d, kv * dh), BF16),
        "wo": ((L, h * dh, d), BF16),
        "w_up": ((L, d, ff), BF16),
        "w_down": ((L, ff, d), BF16),
    }
    if model["act"] == "silu":
        out["w_gate"] = ((L, d, ff), BF16)
    if model["norm"] == "layernorm":
        out.update(final_norm_bias=((d,), f32), attn_norm_bias=((L, d), f32),
                   mlp_norm_bias=((L, d), f32))
    if model["qk_norm"]:
        out.update(q_norm=((L, dh), f32), k_norm=((L, dh), f32))
    if not model["tie_embeddings"]:
        out["lm_head"] = ((d, v), BF16)
    return out


def _draw(key, name, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_bias"):
        return (0.1 * z).astype(dtype)
    if "norm" in name:
        return (1.0 + 0.1 * z).astype(dtype)
    if name == "embed":
        return (EMBED_STD * z).astype(dtype)
    return (z * shape[-2] ** -0.5).astype(dtype)  # fan-in is the K axis


def generate(model: dict, key: jax.Array) -> dict:
    """Every tensor of ``model``, drawn from ``key`` (trace under ``jit``)."""
    table = shapes(model)
    keys = jax.random.split(key, len(table))
    return {name: _draw(k, name, *table[name])
            for k, name in zip(keys, sorted(table))}


def _program_tree(model: dict, w: dict) -> dict:
    """The serving program's parameter tree (``repro.models.model.specs``)
    over the same tensors."""
    def norm(prefix):
        out = {"scale": w[prefix]}
        if model["norm"] == "layernorm":
            out["bias"] = w[prefix + "_bias"]
        return out

    mixer = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    if model["qk_norm"]:
        mixer.update(q_norm=w["q_norm"], k_norm=w["k_norm"])
    w_in = (jnp.concatenate([w["w_gate"], w["w_up"]], axis=-1)
            if model["act"] == "silu" else w["w_up"])
    embed = {"embedding": w["embed"]}
    if not model["tie_embeddings"]:
        embed["head"] = w["lm_head"]
    return {
        "embed": embed,
        "final_norm": norm("final_norm"),
        "stack": {"slot0": {
            "ln1": norm("attn_norm"), "mixer": mixer, "ln2": norm("mlp_norm"),
            "ffn": {"w_in": w_in, "w_out": w["w_down"]},
        }},
    }


@functools.lru_cache(maxsize=None)
def _jitted(kind: str, frozen_model: tuple):
    model = dict(frozen_model)
    if kind == "reference":
        return jax.jit(lambda key: generate(model, key))
    return jax.jit(lambda key: _program_tree(model, generate(model, key)))


def reference_weights(model: dict, seed: int) -> dict:
    return _jitted("reference", tuple(sorted(model.items())))(key_for(seed))


def program_params(model: dict, seed: int) -> dict:
    return _jitted("program", tuple(sorted(model.items())))(key_for(seed))
