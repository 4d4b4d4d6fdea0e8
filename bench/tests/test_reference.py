"""The plain reference agrees with the serving program at smoke width.

Each configuration's stack (weight residency, cache format, scheduler)
serves two requests one after the other, the second sharing the first's
leading chunk, so a prefix cache attaches it.  Every logit vector the
engine produced, at prefill and at each decode step, is compared with the
reference's logits at the same position of prompt plus served tokens.
"""

import numpy as np
import pytest

from bench import harness, reference, weights
from bench.tests.smoke import shrink_config

#: cosine between the program's and the reference's logit vectors.  At
#: these widths w8a8 weights read 0.9998 over a bf16 cache and 0.990 over
#: the int4 cache, whose roundings the reference repeats but cannot meet
#: exactly (a hair's difference moves a value across a rounding edge).
COS_MIN = 0.98


def _cos(a, b):
    a, b = np.ravel(a), np.ravel(b)
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))


@pytest.mark.parametrize("config", ["qwen3-1.7b", "starcoder2-3b"])
def test_engine_logits_match_reference(config):
    cell = {"qwen3-1.7b": "qwen3-1.7b.chat-shared",
            "starcoder2-3b": "starcoder2-3b.code-complete"}[config]
    cell = harness.load_cell(cell)
    family, cfg = cell.family, shrink_config(cell.config)
    model = family.dims(cfg)
    seed = 2**35 + 3
    eng = harness.build_engine(family, cfg, seed)
    eng.trace_logits = True
    rng = np.random.default_rng(0)
    chunk = cfg["stack"]["chunk"]
    first = rng.integers(0, model["vocab_size"], 3 * chunk, dtype=np.int32)
    second = np.concatenate(
        [first[:chunk], rng.integers(0, model["vocab_size"], chunk,
                                     dtype=np.int32)])
    w = weights.reference_weights(family, model, seed)
    worst = 1.0
    for prompt in (first, second):
        eng.logit_trace.clear()
        req = eng.submit(prompt, 4)
        eng.run()
        got = [vec for _, _, vec in eng.logit_trace]
        got = [np.asarray(v).reshape(-1, np.asarray(v).shape[-1])[0]
               for v in got]
        assert len(got) == len(req.out) == 4
        seq = np.concatenate([prompt, np.asarray(req.out[:-1], np.int32)])
        rows = np.arange(len(prompt) - 1, len(seq))
        ref = reference.logits_at(family, model, w, seq, rows)
        for g, r in zip(got, ref):
            worst = min(worst, _cos(g[: model["vocab_size"]], r))
    assert worst > COS_MIN, worst
    if cfg["stack"]["scheduler"].startswith("prefix_cache"):
        assert eng.page_pool.stats()["prefix_hits"] == 1


def test_int4_rounding_grid():
    """Each vector lands on at most 16 multiples of its own scale, each
    value within half a step of where it was."""
    x = np.random.default_rng(1).normal(size=(6, 128)).astype(np.float32)
    r = np.asarray(reference.int4(x))
    scale = np.abs(x).max(-1, keepdims=True) / 7
    steps = r / scale
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-4)
    assert np.all(np.abs(steps) <= 8)
    assert np.all(np.abs(r - x) <= scale / 2 + 1e-6)


@pytest.mark.parametrize("kv_bits,q_bits", [(16, 16), (4, 16), (4, 4)])
def test_reference_rounds_what_the_configuration_states(kv_bits, q_bits):
    """The cache's int4 rounding of K and V, and of the query only where
    the configuration states it, each moves the logits; none of it runs
    over a 16-bit cache."""
    cell = harness.load_cell("qwen3-1.7b.chat-shared")
    family, cfg = cell.family, shrink_config(cell.config)
    model = family.dims(cfg)
    w = weights.reference_weights(family, model, 7)
    toks = np.random.default_rng(2).integers(0, model["vocab_size"], 48)
    rows = np.arange(40, 48)
    plain = reference.logits_at(family, {**model, "kv_bits": 16,
                                         "q_bits": 16}, w, toks, rows)
    got = reference.logits_at(family, {**model, "kv_bits": kv_bits,
                                       "q_bits": q_bits}, w, toks, rows)
    if kv_bits == 16:
        np.testing.assert_array_equal(got, plain)
        return
    assert not np.allclose(got, plain, atol=1e-4)
    kv_only = reference.logits_at(family, {**model, "kv_bits": 4,
                                           "q_bits": 16}, w, toks, rows)
    assert np.allclose(got, kv_only, atol=1e-6) == (q_bits == 16)
