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
CELLS = ["qwen3-1.7b.chat-shared", "starcoder2-3b.code-complete",
         "qwen3-1.7b.doc-decode"]
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
    checked = {"max_logit_gap", "tokens_checked", "failed_requests"}
    if "checked_requests" in cell.mix:
        checked.add("requests_checked")
        assert (line["checks"]["requests_checked"]["value"]
                >= cell.mix["checked_requests"])
        assert out.info["slots_checked"] == cell.config["stack"]["slots"]
    assert set(line["checks"]) == checked


def _altered_tokens_run(name, monkeypatch):
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
    _, out = _run(name, False, wide=True)
    assert out.line["correct"] is False
    assert out.checks["max_logit_gap"]["value"] > SMOKE_GAP_LIMIT["qwen3-1.7b"]


def test_altered_tokens_are_not_correct(monkeypatch):
    _altered_tokens_run("qwen3-1.7b.chat-shared", monkeypatch)


def test_altered_tokens_in_a_closed_loop_are_not_correct(monkeypatch):
    _altered_tokens_run("qwen3-1.7b.doc-decode", monkeypatch)


@pytest.mark.parametrize("name", ["qwen3-1.7b.chat-shared",
                                  "starcoder2-3b.code-complete",
                                  "qwen3-1.7b.doc-decode"])
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
    eng = harness.build_engine(cell.family, cell.config, 5)
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


def test_finish_for_check_waits_for_the_requests_asked():
    """Enough tokens are not enough where the mix names a number of
    requests to check."""
    cell = shrink_cell(harness.load_cell("starcoder2-3b.code-complete"))
    eng = harness.build_engine(cell.family, cell.config, 5)
    drv = harness.Sender(eng, time.perf_counter)
    rng = np.random.default_rng(4)
    t0 = time.perf_counter()
    for max_new in (12, 3, 3, 3):
        drv.submit(harness.traffic_lib.RequestSpec(
            prompt=rng.integers(0, 512, 40, dtype=np.int32),
            max_new=max_new), t0)
    harness.finish_for_check(eng, drv.sent, t0, min_tokens=10,
                             min_requests=4)
    assert sum(s.req.state == "done" for s in drv.sent) == 4


def _finished_on(slot, n_out, uid):
    req = type("R", (), {"state": "done", "prompt_len": 40, "uid": uid,
                         "out": [0] * n_out, "prompt": [1] * 40})()
    return harness.Sent(spec=None, due=0.0, req=req, times=[1.0] * n_out,
                        slot=slot)


@pytest.mark.parametrize("seed", [3, 2**40 + 9])
def test_sample_covers_the_slots_before_it_repeats_one(seed):
    """With ``min_requests``, the sample takes a request from each slot it
    lacks first, and goes on to the token count after that."""
    sent = [_finished_on(slot=i % 4, n_out=5 + i, uid=i) for i in range(16)]
    pick = harness.sample_finished(sent, 0.0, seed, min_tokens=1,
                                   max_requests=16, min_requests=4)
    assert pick[0] is sent[-1]    # the longest first
    assert len(pick) == 4 and {s.slot for s in pick} == {0, 1, 2, 3}
    more = harness.sample_finished(sent, 0.0, seed, min_tokens=100,
                                   max_requests=16, min_requests=4)
    assert more[:4] == pick and sum(len(s.req.out) for s in more) >= 100
    # without ``min_requests`` the draw is the seed's alone, as before
    one = harness.sample_finished(sent, 0.0, seed, min_tokens=1,
                                  max_requests=16)
    assert one == [sent[-1]]


def test_sent_records_the_slot_of_its_first_token():
    eng = _Engine(3)
    drv = harness.Sender(eng, time.perf_counter)
    sent = [drv.submit(harness.traffic_lib.RequestSpec(
        prompt=np.zeros(4, np.int32), max_new=2), 0.0) for _ in range(5)]
    while harness._busy(eng):
        eng.step()
    assert [s.slot for s in sent] == [0, 1, 2, 0, 1]


class _Request:
    def __init__(self, prompt, max_new, on_token):
        self.prompt, self.max_new, self.on_token = prompt, max_new, on_token
        self.out, self.state = [], "queued"

    @property
    def done(self):
        return self.state == "done"


class _Engine:
    """``slots`` requests at a time, in order of arrival, one token each a
    step; counts the requests admitted and not yet finished."""

    def __init__(self, slots):
        self.queue, self.active = [], [None] * slots
        self.live = self.most_live = 0

    def submit(self, prompt, max_new, on_token=None):
        req = _Request(prompt, max_new, on_token)
        self.queue.append(req)
        self.live += 1
        self.most_live = max(self.most_live, self.live)
        return req

    def step(self):
        for i, r in enumerate(self.active):
            if r is None and self.queue:
                self.active[i] = self.queue.pop(0)
        for i, r in enumerate(self.active):
            if r is not None:
                r.out.append(0)
                r.on_token(r, 0)
                if len(r.out) == r.max_new:
                    r.state, self.active[i] = "done", None
                    self.live -= 1
        time.sleep(0.001)


def _closed_mix(users, output):
    return {**harness.traffic_lib.load_mix("doc-decode"), "users": users,
            "stagger_s": 0.02,
            "prompt": {"median": 40, "sigma": 0.5, "min": 32, "max": 96},
            "output": {"median": output, "sigma": 0.0, "min": output,
                       "max": output}}


@pytest.mark.parametrize("slots", [2, 4])
def test_closed_loop_keeps_at_most_users_in_flight(slots):
    """Each user waits for its request to finish before it sends the next,
    whether the engine has a slot for every user or not."""
    eng = _Engine(slots)
    users = harness.traffic_lib.ClosedUsers(_closed_mix(3, 6), 7, 512)
    drv = harness.Sender(eng, time.perf_counter)
    window, _ = drv.closed(users, 0.3, eng.step)
    assert eng.most_live == 3
    assert len(window) > 3 * 2    # every user sent more than once
    assert all(s.times for s in window)   # the drain's first tokens
    assert all(s.sent >= s.due for s in window)


def test_closed_loop_carries_warm_up_requests_into_the_window():
    """A request in flight when the window opens runs on: its tokens in
    the window count, it is not one of the window's requests, and its user
    sends again only once it has finished."""
    eng = _Engine(4)
    users = harness.traffic_lib.ClosedUsers(_closed_mix(3, 60), 7, 512)
    drv = harness.Sender(eng, time.perf_counter)
    warm, _ = drv.closed(users, 0.15, eng.step, drain_s=0.0)
    carried = [s for s in users.inflight if not s.req.done]
    assert carried and set(map(id, carried)) <= set(map(id, warm))
    window, t0 = drv.closed(users, 0.3, eng.step)
    assert eng.most_live == 3
    assert not set(map(id, carried)) & set(map(id, window))
    assert all(t0 <= s.sent < t0 + 0.3 for s in window)
    for s in carried:
        assert s.req.done and any(t >= t0 for t in s.times)
    e2e = harness.end_to_end(drv.sent, window, t0, 0.3, 0.0)
    in_window = sum(t0 <= t < t0 + 0.3 for s in drv.sent for t in s.times)
    assert e2e["output_tok_s"] == pytest.approx(in_window / 0.3)
    assert in_window > sum(t0 <= t < t0 + 0.3 for s in window
                           for t in s.times)
