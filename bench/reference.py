"""Plain float32 reference forward of the benchmark's dense decoders.

Written from the published descriptions, in straightforward ``jax.numpy``,
and importing nothing of the serving program.  Two variants, chosen by the
configuration file's ``model`` block:

* Qwen3 (``norm: rmsnorm``, ``act: silu``, ``qk_norm: true``): pre-norm
  RMSNorm blocks, per-head RMSNorm on q and k before RoPE, SwiGLU MLP,
  embedding tied to the output head.
* StarCoder2 (``norm: layernorm``, ``act: gelu``): pre-norm LayerNorm
  blocks, tanh-approximated GELU MLP, untied output head.

Both use grouped-query attention with rotate-half RoPE.  Departures from the
published models, shared with the program and listed under ``assumed`` in
the configuration files: no linear biases (StarCoder2 publishes them), and
the full causal context (StarCoder2's 4,096-token sliding window never
binds at the benchmark's lengths).  Weights come from
:mod:`bench.weights` and the published tied head is used unscaled.

Every matmul runs at ``highest`` precision, layer by layer under a
``lax.scan`` over the stacked weights, each layer's weights cast to float32
only inside its own step, so the reference fits beside its weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
#: query rows per attention block
QUERY_BLOCK = 1024


def _norm(x, scale, bias, model):
    if model["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + model["norm_eps"]) * scale + bias
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + model["norm_eps"]) * scale


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _int4(x):
    """Round each vector (last axis) to int4 on a symmetric absmax scale,
    as an int4 decode cache stores it: ``scale = max|x| / 7``, values
    clipped to ``[-8, 7]``."""
    scale = jnp.maximum(jnp.abs(x).max(-1, keepdims=True), 1e-6) / 7
    return jnp.clip(jnp.round(x / scale), -8, 7) * scale


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
        k, v = _int4(k), _int4(v)
    if model["q_bits"] == 4:    # int4 queries, where the configuration says
        q = _int4(q)
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


def _logits_rows(model, w, tokens, rows):
    """Logits ``[R, V]`` at the given positions of one token sequence."""
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens].astype(jnp.float32)
        layers = {k: w[k] for k in LAYER_KEYS if k in w}
        x, _ = jax.lax.scan(lambda c, lw: (_layer(model, c, lw), None),
                            x, layers)
        fb = w.get("final_norm_bias", jnp.zeros((), jnp.float32))
        h = _norm(x[rows], w["final_norm"].astype(jnp.float32),
                  fb.astype(jnp.float32), model)
        head = w["embed"].T if model["tie_embeddings"] else w["lm_head"]
        return h @ head.astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _gap_fn(frozen_model: tuple):
    model = dict(frozen_model)

    def run(w, tokens, rows, targets):
        """Gap of each target token below the best logit at its row, and
        the best token there."""
        logits = _logits_rows(model, w, tokens, rows)
        got = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return logits.max(-1) - got, logits.argmax(-1)

    return jax.jit(run)


def logits_at(model: dict, w: dict, tokens, rows) -> np.ndarray:
    """Reference logits ``[len(rows), V]`` of ``tokens`` at ``rows``."""
    fn = jax.jit(functools.partial(_logits_rows, model))
    return np.asarray(fn(w, jnp.asarray(tokens, jnp.int32),
                         jnp.asarray(rows, jnp.int32)))


def served_gaps(model: dict, w: dict, prompt: np.ndarray, served: np.ndarray,
                pad_to: int, row_cap: int) -> tuple[np.ndarray, np.ndarray]:
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
    gaps, best = _gap_fn(tuple(sorted(model.items())))(
        w, jnp.asarray(toks), jnp.asarray(rows_p), jnp.asarray(tg))
    return np.asarray(gaps)[:n], np.asarray(best)[:n]
