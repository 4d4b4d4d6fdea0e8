"""The dense decoder family: grouped-query attention and a dense MLP.

A family is what the benchmark knows of one kind of architecture, in six
functions that the harness, the weights, the plain reference and the
counts call by name:

* ``dims(config)``: the sizes the other five read, from the published
  ``config.json`` keys the configuration file carries;
* ``shapes(dims)``: name → (shape, dtype) of every tensor;
* ``program_tree(dims, w)``: the serving program's parameter tree over the
  same tensors;
* ``program_config(config, dims)``: the program's ``ModelConfig``;
* ``logits_rows(dims, w, tokens, rows)``: the plain reference's logits;
* ``rows_flops(dims, rows)``: the model FLOPs of some work.

This family covers two variants, chosen by the configuration file:

* Qwen3 (``norm: rmsnorm``, ``act: silu``, ``qk_norm: true``): pre-norm
  RMSNorm blocks, per-head RMSNorm on q and k before RoPE, SwiGLU MLP,
  embedding tied to the output head.
* StarCoder2 (``norm: layernorm``, ``act: gelu``): pre-norm LayerNorm
  blocks, tanh-approximated GELU MLP, untied output head.

Both use grouped-query attention with rotate-half RoPE.  Departures from the
published models, shared with the program and listed under ``assumed`` in
the configuration files: no linear biases (StarCoder2 publishes them), and
the full causal context (StarCoder2's 4,096-token sliding window never
binds at the benchmark's lengths).  The published tied head is used
unscaled.

The reference runs layer by layer under a ``lax.scan`` over the stacked
weights, each layer's weights cast to float32 only inside its own step, so
it fits beside its weights.  It imports nothing of the serving program.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, reference

BF16 = jnp.bfloat16
NEG = -1e30
#: query rows per attention block
QUERY_BLOCK = 1024

#: dims keys → the program's ModelConfig fields of the same name
_MODEL_FIELDS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_head",
                 "d_ff", "vocab_size", "norm", "act", "qk_norm",
                 "tie_embeddings", "rope_theta")


def dims(config: dict) -> dict:
    heads = config["num_attention_heads"]
    return {
        "n_layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "n_heads": heads,
        "n_kv_heads": config["num_key_value_heads"],
        "d_head": config.get("head_dim", config["hidden_size"] // heads),
        "d_ff": config["intermediate_size"],
        "vocab_size": config["vocab_size"],
        "norm": ("layernorm" if config.get("norm_type") == "layer_norm"
                 else "rmsnorm"),
        "norm_eps": config.get("rms_norm_eps", config.get("norm_epsilon")),
        "act": "gelu" if config["hidden_act"].startswith("gelu") else "silu",
        "qk_norm": config["model_type"] == "qwen3",
        "tie_embeddings": bool(config.get("tie_word_embeddings", False)),
        "rope_theta": float(config["rope_theta"]),
        "kv_bits": config["kv_cache_bits"],
        "q_bits": config.get("attention_query_bits", 16),
    }


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def shapes(model: dict) -> dict:
    """In the published layout, layers stacked on the leading axis;
    bfloat16 matrices and embedding, float32 norm parameters."""
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


def program_tree(model: dict, w: dict) -> dict:
    """``repro.models.model.specs``' tree over the tensors of ``shapes``."""
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


def program_config(config: dict, model: dict):
    """The registered architecture ``program_model`` with the sizes of
    ``model`` (a test may shrink them)."""
    from repro.configs import get_config

    cfg = get_config(config["program_model"])
    return dataclasses.replace(cfg, **{k: model[k] for k in _MODEL_FIELDS})


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------


def _norm(x, scale, bias, model):
    if model["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + model["norm_eps"]) * scale + bias
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + model["norm_eps"]) * scale


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [T, H, D]; rotate-half RoPE at positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(model, x, w):
    """One decoder layer over the whole sequence x: [T, d] float32."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    t = x.shape[0]
    h, kv, dh = model["n_heads"], model["n_kv_heads"], model["d_head"]
    bias = lambda name: f32(w[name]) if name in w else 0.0  # noqa: E731
    a = _norm(x, f32(w["attn_norm"]), bias("attn_norm_bias"), model)
    q = (a @ f32(w["wq"])).reshape(t, h, dh)
    k = (a @ f32(w["wk"])).reshape(t, kv, dh)
    v = (a @ f32(w["wv"])).reshape(t, kv, dh)
    if model["qk_norm"]:
        q = _rms(q, f32(w["q_norm"]), model["norm_eps"])
        k = _rms(k, f32(w["k_norm"]), model["norm_eps"])
    q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    if model["kv_bits"] == 4:   # the configuration's int4 cache
        k, v = reference.int4(k), reference.int4(v)
    if model["q_bits"] == 4:    # int4 queries, where the configuration says
        q = reference.int4(q)
    group = h // kv
    k = jnp.repeat(k, group, axis=1)  # query head i reads kv head i // group
    v = jnp.repeat(v, group, axis=1)
    blocks = []
    for q0 in range(0, t, QUERY_BLOCK):  # bounds the [H, block, T] scores
        qb = q[q0:q0 + QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", qb, k) / np.sqrt(dh)
        causal = (np.arange(t)[None, :]
                  <= np.arange(q0, q0 + qb.shape[0])[:, None])
        s = jnp.where(causal[None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        blocks.append(jnp.einsum("hts,shd->thd", p, v))
    o = jnp.concatenate(blocks).reshape(t, h * dh)
    x = x + o @ f32(w["wo"])
    m = _norm(x, f32(w["mlp_norm"]), bias("mlp_norm_bias"), model)
    if model["act"] == "silu":
        u = jax.nn.silu(m @ f32(w["w_gate"])) * (m @ f32(w["w_up"]))
    else:
        u = jax.nn.gelu(m @ f32(w["w_up"]), approximate=True)
    return x + u @ f32(w["w_down"])


LAYER_KEYS = ("attn_norm", "attn_norm_bias", "mlp_norm", "mlp_norm_bias",
              "wq", "wk", "wv", "wo", "q_norm", "k_norm", "w_gate", "w_up",
              "w_down")


def logits_rows(model, w, tokens, rows):
    """Logits ``[R, V]`` at the given positions of one token sequence."""
    x = w["embed"][tokens].astype(jnp.float32)
    layers = {k: w[k] for k in LAYER_KEYS if k in w}
    x, _ = jax.lax.scan(lambda c, lw: (_layer(model, c, lw), None), x, layers)
    fb = w.get("final_norm_bias", jnp.zeros((), jnp.float32))
    h = _norm(x[rows], w["final_norm"].astype(jnp.float32),
              fb.astype(jnp.float32), model)
    head = w["embed"].T if model["tie_embeddings"] else w["lm_head"]
    return h @ head.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Counts
# ---------------------------------------------------------------------------


def matmul_params(model: dict) -> int:
    """Weights multiplied once per token by every layer's projections."""
    d, h, kv, dh, ff = (model[k] for k in (
        "d_model", "n_heads", "n_kv_heads", "d_head", "d_ff"))
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    mlp = (3 if model["act"] == "silu" else 2) * d * ff
    return model["n_layers"] * (attn + mlp)


def rows_flops(model: dict, rows) -> int:
    """Per token, the projections and the score and value contractions over
    its real context (``position + 1`` keys); the output head once for each
    row whose logits are read."""
    per_ctx = 4 * model["n_layers"] * model["n_heads"] * model["d_head"]
    head = 2 * model["d_model"] * model["vocab_size"]
    dense = 2 * matmul_params(model)
    return sum(r.n * dense + per_ctx * counts.contexts(r.start, r.n)
               + (head if r.logits else 0) for r in rows)
