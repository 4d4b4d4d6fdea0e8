"""Architecture families: the dense family reads exactly what the
single-family code it replaced read, and a new family comes in as a file.

``data/dense_recorded.*`` was written by the benchmark code as it stood
before the dense decoders moved behind the family interface, at the smoke
widths the reference test uses (and, for the counts, at the published
widths): a SHA-256 of every generated weight and parameter leaf, the
reference's logits at four rows, the served-token gaps of one request, and
the counts of a fixed list of ``Rows``.
"""

import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from bench import counts, harness, reference, weights
from bench.tests.smoke import shrink_config

DATA = pathlib.Path(__file__).parent / "data"
RECORDED = json.loads((DATA / "dense_recorded.json").read_text())
CELLS = {"qwen3-1.7b": "qwen3-1.7b.chat-shared",
         "starcoder2-3b": "starcoder2-3b.code-complete"}
SEED = 2**35 + 3
ROWS = [counts.Rows(0, 512, False, "prefill"),
        counts.Rows(512, 32, True, "chunk"),
        counts.Rows(100, 32, False, "chunk"),
        counts.Rows(3071, 1, True, "decode"),
        counts.Rows(40, 1, True, "decode")]


def _sha(a) -> str:
    a = np.asarray(a)
    return hashlib.sha256(a.tobytes()).hexdigest() + f":{a.dtype}:{a.shape}"


def _small(config):
    cell = harness.load_cell(CELLS[config])
    return cell.family, cell.family.dims(shrink_config(cell.config))


@pytest.mark.parametrize("config", sorted(CELLS))
def test_weights_are_the_recorded_draw(config):
    family, model = _small(config)
    w = weights.reference_weights(family, model, SEED)
    assert {k: _sha(v) for k, v in w.items()} == RECORDED["weights"][config]
    p = weights.program_params(family, model, SEED)
    leaves = jax.tree_util.tree_flatten_with_path(p)[0]
    assert ({jax.tree_util.keystr(k): _sha(v) for k, v in leaves}
            == RECORDED["program"][config])


@pytest.mark.parametrize("config", sorted(CELLS))
def test_reference_gives_the_recorded_logits(config):
    family, model = _small(config)
    arrays = np.load(DATA / "dense_recorded.npz")
    w = weights.reference_weights(family, model, SEED)
    toks = np.random.default_rng(2).integers(0, model["vocab_size"], 48)
    rows = np.array([0, 13, 31, 47])
    got = reference.logits_at(family, model, w, toks, rows)
    np.testing.assert_array_equal(got, arrays[f"{config}.logits"])
    vocab = model["vocab_size"]
    prompt = np.random.default_rng(3).integers(0, vocab, 40).astype(np.int32)
    served = np.random.default_rng(4).integers(0, vocab, 8).astype(np.int32)
    gaps, best = reference.served_gaps(family, model, w, prompt, served,
                                       64, 16)
    np.testing.assert_array_equal(gaps, arrays[f"{config}.gaps"])
    np.testing.assert_array_equal(best, arrays[f"{config}.best"])


@pytest.mark.parametrize("size", ["full", "small"])
@pytest.mark.parametrize("config", sorted(CELLS))
def test_counts_are_the_recorded_counts(config, size):
    cell = harness.load_cell(CELLS[config])
    family = cell.family
    model = cell.model if size == "full" else _small(config)[1]
    want = RECORDED["counts"][config][size]
    assert ([family.rows_flops(model, [r]) for r in ROWS]
            + [family.rows_flops(model, ROWS)]) == want["rows_flops"]
    assert ([list(counts.plane_attn_work(model, [r])) for r in ROWS]
            + [list(counts.plane_attn_work(model, ROWS))]
            == want["plane_attn_work"])


#: a family of one embedding and an output head, written only for this test
TOY = '''
import jax.numpy as jnp


def dims(config):
    return {"d_model": config["width"], "vocab_size": config["vocab_size"]}


def shapes(model):
    d, v = model["d_model"], model["vocab_size"]
    return {"embed": ((v, d), jnp.float32), "head": ((d, v), jnp.float32)}


def program_tree(model, w):
    return {"table": w["embed"], "out": w["head"]}


def program_config(config, model):
    return dict(model)


def logits_rows(model, w, tokens, rows):
    return w["embed"][tokens][rows] @ w["head"]


def rows_flops(model, rows):
    return sum(r.n * 2 * model["d_model"] * model["vocab_size"] for r in rows)
'''


def _spec() -> dict:
    with open(harness.ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "toy", "file": "configs/toy.json"}]
    spec["workloads"] = [{"name": "toy.chat", "config": "toy",
                          "traffic": "chat-shared", "chips": 1}]
    return spec


def _write_config(root: pathlib.Path, family: str) -> None:
    (root / "configs").mkdir()
    (root / "configs" / "toy.json").write_text(json.dumps(
        {"family": family, "width": 8, "vocab_size": 16}))


def test_a_family_file_under_another_root_is_all_a_new_architecture_needs(
        tmp_path):
    (tmp_path / "bench" / "families").mkdir(parents=True)
    (tmp_path / "bench" / "families" / "toy.py").write_text(TOY)
    _write_config(tmp_path, "toy")
    cell = harness.cell_from(_spec(), "toy.chat", root=tmp_path)
    assert cell.model == {"d_model": 8, "vocab_size": 16}
    family = cell.family
    w = weights.reference_weights(family, cell.model, 5)
    params = weights.program_params(family, cell.model, 5)
    assert params["out"].shape == (8, 16)
    tokens = np.arange(6)
    got = reference.logits_at(family, cell.model, w, tokens, [2, 5])
    np.testing.assert_allclose(
        got, np.asarray(w["embed"])[[2, 5]] @ np.asarray(w["head"]),
        rtol=1e-5)
    _, best = reference.served_gaps(family, cell.model, w, tokens[:4],
                                    tokens[:1], 8, 2)
    gaps, _ = reference.served_gaps(family, cell.model, w, tokens[:4],
                                    best, 8, 2)
    assert gaps.tolist() == [0.0]
    mfu = harness.load_reader("mfu")
    record = harness.RunRecord(
        cell=cell, seconds=1.0, steps=[(0, 1, [counts.Rows(0, 3, True,
                                                           "prefill")])],
        spans=[], compiles=0, pool_window=None, trace=None, traced_steps=[],
        peaks={"bf16_flops_per_s": 100.0})
    assert mfu(record) == pytest.approx(100 * 3 * 2 * 8 * 16 / 100.0)


def test_a_missing_family_names_the_path_it_looked_for(tmp_path):
    _write_config(tmp_path, "absent")
    want = tmp_path / "bench" / "families" / "absent.py"
    with pytest.raises(FileNotFoundError) as err:
        harness.cell_from(_spec(), "toy.chat", root=tmp_path)
    assert str(want) in str(err.value)
