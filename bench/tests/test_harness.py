"""The harness end to end on the CPU at smoke width.

These runs skip only ``run.py``'s look for a TPU: they drive the rest of a
run (set-up, warm-up, the window, the readers and the reference check) and
read the line it would print.  The device numbers of a CPU run mean
nothing; the keys, the counts and ``correct`` do.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests.smoke import SMOKE_GAP_LIMIT, shrink_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = ["qwen3-1.7b.chat-shared", "starcoder2-3b.code-complete"]
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
         "hbm_bytes_per_s": 819e9}


def _run(name, trace, seed=2**40 + 9, mode=None, monkeypatch=None,
         wide=False):
    if monkeypatch is not None:
        monkeypatch.setattr(harness, "load_peaks", lambda kind: PEAKS)
    cell = shrink_cell(harness.load_cell(name), wide)
    return cell, harness.run(cell, seed, 3.0, trace,
                             t_process=time.perf_counter(),
                             devices=jax.devices(), mode=mode)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_last_line_has_the_contract_keys(name, trace, monkeypatch):
    cell, out = _run(name, bool(trace), monkeypatch=monkeypatch)
    line = json.loads(json.dumps(out.line))
    keys = LINE_KEYS + (["breakdown"] if trace else []) + ["checks"]
    assert list(line) == keys
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    device = {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        device |= {"busy_s", "window_s"}
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert set(line["device"]) == device
    names = [m["name"] for m in (cell.per_layer if trace else cell.end_to_end)]
    # a CPU trace has no device plane, so no kernel time to read
    assert set(line["metrics"]) == set(names) - {"plane_attn_roofline"}
    for m in (cell.per_layer if trace else cell.end_to_end):
        if m["name"] in line["metrics"]:
            assert line["metrics"][m["name"]]["unit"] == m["unit"]
    assert set(line["checks"]) == {"max_logit_gap", "tokens_checked",
                                   "failed_requests"}


def test_altered_tokens_are_not_correct(monkeypatch):
    """A served token altered where it is produced: every third greedy
    token is replaced by the next vocabulary entry."""
    from repro.serve import engine

    def wrong(req, logits_row):
        tok = int(np.argmax(logits_row))
        if len(req.out) % 3 == 2:
            tok = (tok + 1) % len(logits_row)
        return tok

    monkeypatch.setattr(engine.ServeEngine, "_next_token",
                        staticmethod(wrong))
    _, out = _run("qwen3-1.7b.chat-shared", False, wide=True)
    assert out.line["correct"] is False
    assert out.checks["max_logit_gap"]["value"] > SMOKE_GAP_LIMIT["qwen3-1.7b"]


@pytest.mark.parametrize("name", ["qwen3-1.7b.chat-shared",
                                  "starcoder2-3b.code-complete"])
def test_control_is_not_correct(name):
    """The configuration's weights one precision step down (w8a8 → w4a8),
    in the program's place."""
    cell = harness.load_cell(name)
    _, out = _run(name, False, mode=cell.config["control_mode"], wide=True)
    assert out.line["correct"] is False
    limit = SMOKE_GAP_LIMIT[cell.config["program_model"]]
    assert out.checks["max_logit_gap"]["value"] > limit


def _run_py(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-1.7b.chat-shared", "--seed", str(2**33), "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_without_tpu_exits_nonzero():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_finish_for_check_serves_the_sample():
    """After the window the engine runs on, with no new arrivals, until
    the requests finished since the window opened hold enough tokens."""
    cell = shrink_cell(harness.load_cell("starcoder2-3b.code-complete"))
    eng = harness.build_engine(cell.config, 5)
    drv = harness.Sender(eng, time.perf_counter)
    rng = np.random.default_rng(3)
    t0 = time.perf_counter()
    for _ in range(3):
        drv.submit(harness.traffic_lib.RequestSpec(
            prompt=rng.integers(0, 512, 40, dtype=np.int32), max_new=6), t0)
    harness.finish_for_check(eng, drv.sent, t0, min_tokens=10)
    done = [s for s in drv.sent if s.req.state == "done"]
    assert sum(len(s.req.out) for s in done) >= 10
    assert all(len(s.times) == len(s.req.out) for s in drv.sent)
