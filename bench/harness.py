"""The benchmark harness: one cell, one seed, one measured window.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<mix>.json``).  Everything is found by name, so a later
change adds a configuration, a mix, a per-layer metric
(``bench/metrics/<metric>.py``) or an architecture family
(``bench/families/<family>.py``, named by the configuration's ``family``)
by adding a file.

The system under test is the repository's serving path: random weights from
the seed → ``ServeEngine`` (which converts them to the configuration's
weight residency) → ``submit`` / ``step``.  The harness drives it from the
client side:

* set-up: weights, conversion, every shape the traffic can produce
  (prefill rows 1…slots at the chunk length, decode at 1 and the chunk),
  then ``warmup_s`` seconds of the cell's own traffic, not counted;
* the window: ``seconds`` of the mix's traffic.  The loop submits every
  request whose due time has come, steps, and sleeps only while the engine
  is idle.  Requests are timed from when they were due, not when they were
  sent; in a closed mix a request is due when its user sends it, and the
  users carry on from warm-up into the window;
* afterwards, ``correct``: with no new arrivals the engine runs on until
  the requests finished since the window opened hold enough served tokens
  (and, where the mix names ``checked_requests``, that many requests);
  a sample of them, drawn from the seed with the longest among them and,
  up to ``checked_requests``, one from each slot the sample lacks first, is
  run through the plain float32 reference (:mod:`bench.reference`, the
  family's forward pass) and every served token's gap below the
  reference's best logit is compared with the configuration's limit.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import tempfile
import time
from typing import Callable, Optional

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"

from bench import counts, traffic as traffic_lib  # noqa: E402


# ---------------------------------------------------------------------------
# Cells
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    mix: dict             # bench/traffic/<mix>.json
    end_to_end: list      # BENCHMARK.json metric entries reported here
    per_layer: list
    family: object        # bench/families/<config["family"]>.py

    @property
    def model(self) -> dict:
        return self.family.dims(self.config)


def load_family(name: str, root: pathlib.Path = ROOT):
    """The architecture family ``bench/families/<name>.py`` under ``root``:
    ``dims``, ``shapes``, ``program_tree``, ``program_config``,
    ``logits_rows`` and ``rows_flops`` (see ``bench/families/dense.py``)."""
    path = root / "bench" / "families" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"architecture family {name!r}: no file {path}")
    return _load_module(path, f"bench_family_{name}")


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_from(spec: dict, name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of a benchmark spec (``BENCHMARK.json``'s
    content), with its configuration file, its mix and its metrics."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(root / conf["file"]) as f:
        config = json.load(f)
    return Cell(
        name=name, chips=w["chips"], config=config,
        mix=traffic_lib.load_mix(w["traffic"]),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        family=load_family(config["family"], root),
    )


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    with open(root / "BENCHMARK.json") as f:
        return cell_from(json.load(f), name, root)


# ---------------------------------------------------------------------------
# Compile events (copied from chip_smoke.CompileClock)
# ---------------------------------------------------------------------------


class CompileClock:
    """Compilations (persistent-cache retrievals included) and seconds spent
    on them, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds, self.count, self.hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def enable_compile_cache(path: pathlib.Path = CACHE_DIR) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the path is part of the cache key), keeping every program."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def build_engine(family, config: dict, seed: int, *,
                 mode: Optional[str] = None, trace_sink=None):
    """Weights from ``seed`` → ``ServeEngine`` with the configuration's
    stack (``mode`` replaces the weight residency, for the control)."""
    from bench import weights
    from repro.serve import engine

    stack = config["stack"]
    model = family.dims(config)
    params = weights.program_params(family, model, seed)
    eng = engine.ServeEngine(
        params, family.program_config(config, model), slots=stack["slots"],
        max_len=stack["max_len"], mode=mode or stack["mode"],
        cache_format=stack["cache_format"], scheduler=stack["scheduler"],
        trace=trace_sink if trace_sink is not None else False)
    del params
    return eng


def _busy(eng) -> bool:
    return bool(eng.queue) or any(a is not None for a in eng.active)


def _drive(eng, limit_s: float = 600.0) -> None:
    t_end = time.perf_counter() + limit_s
    while _busy(eng):
        if time.perf_counter() > t_end:
            raise RuntimeError("warm-up did not finish")
        eng.step()


def warm_shapes(eng, config: dict, seed: int) -> None:
    """Compile every shape the traffic can produce: a prefill of each row
    count 1…slots at the chunk length, a chunk step and a one-token step,
    and (with a prefix cache) one attach of a shared prefix."""
    stack = config["stack"]
    chunk, vocab = stack["chunk"], config["vocab_size"]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    for rows in range(1, stack["slots"] + 1):
        for _ in range(rows):
            eng.submit(rng.integers(0, vocab, 2 * chunk, dtype=np.int32), 2)
        _drive(eng)
    shared = rng.integers(0, vocab, 2 * chunk, dtype=np.int32)
    eng.submit(shared, 2)
    _drive(eng)
    tail = rng.integers(0, vocab, chunk, dtype=np.int32)
    eng.submit(np.concatenate([shared[:chunk], tail]), 2)
    _drive(eng)


# ---------------------------------------------------------------------------
# Driving the traffic
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Sent:
    spec: traffic_lib.RequestSpec
    due: float                      # absolute clock time it was due
    sent: float = 0.0
    req: object = None
    times: list = dataclasses.field(default_factory=list)   # token stamps
    slot: Optional[int] = None      # the engine slot of its first token


class StepLog:
    """Per-step accounting for the per-layer metrics (traced runs only):
    the prompt and decode positions each step processed, read from the
    requests' lifecycle fields before and after the step.  A request that
    left the queue holding pages of a cached prefix (the page pool's
    ``prefix_tokens_saved`` grew) processed nothing in that step."""

    def __init__(self, eng, chunk: int):
        self.eng, self.chunk = eng, chunk
        self.steps: list = []   # (t0, t1, [counts.Rows])

    def step(self, clock) -> None:
        eng = self.eng
        pool = eng.page_pool
        saved0 = pool.prefix_tokens_saved if pool is not None else 0
        live = list(eng.queue) + [a for a in eng.active if a is not None]
        before = [(r, r.state, r.prefilled, len(r.out)) for r in live]
        t0 = clock()
        eng.step()
        t1 = clock()
        saved = (pool.prefix_tokens_saved if pool is not None else 0) - saved0
        rows = []
        for r, state, pre, n_out in before:
            emitted = len(r.out) > n_out
            if state == "queued" and r.state != "queued":
                if (saved > 0 and not emitted
                        and r.prefilled != min(r.prompt_len, self.chunk)):
                    saved -= r.prefilled
                    continue
                rows.append(counts.Rows(0, r.prefilled, emitted, "prefill"))
            elif state == "prefilling" and r.prefilled > pre:
                rows.append(counts.Rows(pre, r.prefilled - pre, emitted,
                                        "chunk"))
            elif state == "decoding" and emitted:
                rows.append(counts.Rows(r.prompt_len + n_out - 1, 1, True,
                                        "decode"))
        self.steps.append((t0, t1, rows))


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Sender:
    """Sends the traffic to the engine and stamps every token."""

    def __init__(self, eng, clock):
        self.eng, self.clock = eng, clock
        self.sent: list = []

    def submit(self, spec, due: float) -> Sent:
        s = Sent(spec, due)

        def on_token(req, _tok, s=s, clock=self.clock, eng=self.eng):
            if not s.times:
                s.slot = next((i for i, a in enumerate(eng.active)
                               if a is req), None)
            s.times.append(clock())

        s.sent = self.clock()
        s.req = self.eng.submit(spec.prompt, spec.max_new, on_token=on_token)
        self.sent.append(s)
        return s

    def open(self, specs, seconds: float, step: Callable,
             drain_s: float = 60.0) -> tuple:
        """Serve ``specs`` (due times relative to now) for ``seconds``; then,
        with no new arrivals, step until every request due in the window
        has its first token (at most ``drain_s``).  Returns the window's
        requests and its start."""
        clock, eng = self.clock, self.eng
        t0 = clock()
        end = t0 + seconds
        due = [t0 + s.due_s for s in specs]
        window, i = [], 0
        while True:
            now = clock()
            if now >= end:
                break
            with _annotate("bench.generate"):
                while i < len(specs) and due[i] <= now:
                    window.append(self.submit(specs[i], due[i]))
                    i += 1
            if _busy(eng):
                with _annotate("bench.step"):
                    step()
            else:
                wake = min(due[i] if i < len(specs) else end, end)
                with _annotate("bench.idle"):
                    time.sleep(max(0.0, wake - clock()))
        for j in range(i, len(specs)):   # due in the window, sent late
            window.append(self.submit(specs[j], due[j]))
        self._drain(window, step, drain_s)
        return window, t0

    def closed(self, users: traffic_lib.ClosedUsers, seconds: float,
               step: Callable, drain_s: float = 60.0) -> tuple:
        """Keep each of a closed mix's ``users`` with one request in flight
        for ``seconds``: a user sends its first request at its start time
        and each next one, due when it is sent, once the last has finished.
        Requests in flight when the call starts run on.  Then drain as
        :meth:`open` does.  Returns the requests sent in this call and its
        start."""
        clock, eng = self.clock, self.eng
        t0 = clock()
        end = t0 + seconds
        if users.t_start is None:
            users.t_start = t0
        starts = [users.t_start + users.start_s(u) for u in range(users.count)]
        window = []
        while True:
            now = clock()
            if now >= end:
                break
            with _annotate("bench.generate"):
                for u, s in enumerate(users.inflight):
                    if s.req.done if s is not None else now >= starts[u]:
                        users.inflight[u] = self.submit(
                            users.next_request(u), now)
                        window.append(users.inflight[u])
            if _busy(eng):
                with _annotate("bench.step"):
                    step()
            else:   # users not started yet
                wake = min([starts[u] for u, s in enumerate(users.inflight)
                            if s is None] + [end])
                with _annotate("bench.idle"):
                    time.sleep(max(0.0, wake - clock()))
        self._drain(window, step, drain_s)
        return window, t0

    def _drain(self, window: list, step: Callable, drain_s: float) -> None:
        """With no new arrivals, step until every request of ``window`` has
        its first token (at most ``drain_s``)."""
        deadline = self.clock() + drain_s
        while (any(not s.times for s in window) and _busy(self.eng)
               and self.clock() < deadline):
            step()


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------


def end_to_end(sent: list, window: list, t0: float, seconds: float,
               setup_s: float) -> dict:
    """Over the whole window: every token emitted in it, every gap between
    two tokens of one request inside it, and the first-token time of every
    request due in it, counted from when it was due."""
    end = t0 + seconds
    stamps = [t for s in sent for t in s.times if t0 <= t < end]
    gaps = [b - a for s in sent for a, b in zip(s.times, s.times[1:])
            if t0 <= a and b < end]
    out = {
        "setup_s": setup_s,
        "output_tok_s": len(stamps) / seconds,
        "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3 if gaps else None,
    }
    ttft = [s.times[0] - s.due for s in window if s.times]
    for q in (50, 90, 95):
        out[f"ttft_p{q}_ms"] = (float(np.percentile(ttft, q)) * 1e3
                                if ttft else None)
    return out


def _finished(sent: list, t0: float) -> list:
    return [s for s in sent
            if s.req.state == "done" and s.times and s.times[-1] >= t0]


def finish_for_check(eng, sent: list, t0: float, min_tokens: int,
                     min_requests: int = 1, limit_s: float = 60.0) -> None:
    """After the window, with no new arrivals: step until the requests
    finished since the window opened hold ``min_tokens`` served tokens and
    number ``min_requests``, the engine is idle, or ``limit_s`` has
    passed."""
    deadline = time.perf_counter() + limit_s

    def short():
        done = _finished(sent, t0)
        return (sum(len(s.req.out) for s in done) < min_tokens
                or len(done) < min_requests)

    while short() and _busy(eng) and time.perf_counter() < deadline:
        eng.step()


def sample_finished(sent: list, t0: float, seed: int, min_tokens: int,
                    max_requests: int, min_requests: int = 1) -> list:
    """Of the requests finished after the window opened, the longest, then
    others drawn from the seed, until ``min_tokens`` served tokens and
    ``min_requests`` requests, or ``max_requests`` requests.  Up to
    ``min_requests``, a request on a slot the sample lacks comes first."""
    done = _finished(sent, t0)
    if not done:
        return []
    done.sort(key=lambda s: (-(s.req.prompt_len + len(s.req.out)), s.req.uid))
    pick = [done[0]]
    order = np.random.default_rng(
        np.random.SeedSequence([int(seed), 11])).permutation(len(done) - 1)
    rest = [done[1 + j] for j in order]
    if min_requests > 1:
        slots, first = {pick[0].slot}, []
        for s in rest:
            if len(first) < min_requests - 1 and s.slot not in slots:
                slots.add(s.slot)
                first.append(s)
        rest = first + [s for s in rest if all(s is not f for f in first)]
    for s in rest:
        if ((sum(len(x.req.out) for x in pick) >= min_tokens
             and len(pick) >= min_requests) or len(pick) >= max_requests):
            break
        pick.append(s)
    return pick


def reference_gaps(family, config: dict, seed: int, sample: list,
                   model: Optional[dict] = None) -> np.ndarray:
    """Every served token's gap below the plain reference's best logit
    (``model`` replaces the configuration's sizes and precisions)."""
    from bench import reference, weights

    model = model or family.dims(config)
    w = weights.reference_weights(family, model, seed)
    row_cap = config["stack"]["max_new_cap"]
    gaps = [reference.served_gaps(family, model, w, p, o,
                                  config["stack"]["max_len"], row_cap)[0]
            for p, o in sample]
    del w
    return np.concatenate(gaps) if gaps else np.zeros(0)


def device_info(devices) -> dict:
    d = devices[0]
    stats = [x.memory_stats() or {} for x in devices]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                     for s in stats)}


def load_reader(name: str):
    return _load_module(BENCH / "metrics" / f"{name}.py",
                        f"bench_metric_{name}").read


@dataclasses.dataclass
class RunRecord:
    """What the per-layer readers read (``--trace 1`` runs)."""

    cell: Cell
    seconds: float
    steps: list                 # StepLog.steps inside the window
    spans: list                 # program obs spans inside the window
    compiles: int               # compile events inside the window
    pool_window: Optional[dict]  # page-pool counter deltas over the window
    trace: object               # xplane.TraceSummary of the traced slice
    traced_steps: list          # StepLog.steps inside the traced slice
    peaks: dict


def load_peaks(kind: str) -> dict:
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Outcome:
    line: dict
    checks: dict
    info: dict
    sample: list    # the (prompt, served tokens) pairs that were checked
    gaps: np.ndarray  # every checked token's gap below the reference's best


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, devices, mode: Optional[str] = None,
        trace_slice_s: float = 4.0) -> Outcome:
    """Set up, measure one window, check the served tokens; returns the
    result line (without printing it).  ``mode`` replaces the
    configuration's weight residency (the control)."""
    from repro.obs import trace as obs

    clock = time.perf_counter
    compiles = CompileClock()
    config, mix = cell.config, cell.mix
    ring = obs.RingSink(capacity=1 << 21) if trace else None
    eng = build_engine(cell.family, config, seed, mode=mode, trace_sink=ring)
    warm_shapes(eng, config, seed)
    vocab = config["vocab_size"]
    drv = Sender(eng, clock)
    if mix["kind"] == "closed":   # the same users from warm-up on
        warm = measured = traffic_lib.ClosedUsers(mix, seed, vocab)
        serve = drv.closed
    else:
        sched = traffic_lib.schedule(mix, seed, seconds, vocab)
        warm, measured = sched.warm, sched.window
        serve = drv.open
    log = StepLog(eng, config["stack"]["chunk"]) if trace else None

    def step():
        if log is not None:
            log.step(clock)
        else:
            eng.step()

    # the cell's own traffic for warmup_s, not measured
    serve(warm, mix["warmup_s"], eng.step, drain_s=0.0)
    pool0 = eng.page_pool.stats() if eng.page_pool is not None else None
    n_compiles0 = compiles.count
    setup_compile_s, setup_hits = compiles.seconds, compiles.hits
    if ring is not None:
        ring.clear()
    setup_s = clock() - t_process

    profiler = _Profiler(seconds, trace_slice_s, clock) if trace else None
    stepper = step if profiler is None else profiler.wrap(step)
    window, t0 = serve(measured, seconds, stepper)
    n_compiles = compiles.count - n_compiles0
    if profiler is not None:
        profiler.stop()
    pool1 = eng.page_pool.stats() if eng.page_pool is not None else None

    e2e = end_to_end(drv.sent, window, t0, seconds, setup_s)
    attempted = len(window)
    # a request due in the window fails if it never produced a token
    failed = sum(1 for s in window
                 if s.req.state == "cancelled" or not s.times)
    dev = device_info(devices)
    info = {"compiles_in_window": n_compiles,
            "setup_compile_s": setup_compile_s,
            "setup_cache_hits": setup_hits,
            "generator_lag_max_ms": max(
                [(s.sent - s.due) * 1e3 for s in window] or [0.0]),
            "requests_finished": sum(1 for s in drv.sent
                                     if s.req.state == "done"),
            **{k: v for k, v in e2e.items()}}

    metrics: dict = {}
    breakdown = None
    if trace:
        summary = profiler.summary
        record = RunRecord(
            cell=cell, seconds=seconds,
            steps=[s for s in log.steps if t0 <= s[0] < t0 + seconds],
            spans=[r for r in ring.records() if isinstance(r, obs.SpanRecord)
                   and t0 <= r.ts < t0 + seconds],
            compiles=n_compiles, pool_window=_pool_delta(pool0, pool1),
            trace=summary,
            traced_steps=[s for s in log.steps
                          if profiler.lo <= s[0] and s[1] <= profiler.hi],
            peaks=load_peaks(dev["kind"]))
        for m in cell.per_layer:
            value = load_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops(),
                     "idle_gaps": summary.top_gaps()}
        obs.unregister_sink(ring)
    else:
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    # correctness, once the program's state is freed
    limits = config["limits"]
    n_checked = mix.get("checked_requests", 1)
    finish_for_check(eng, drv.sent, t0, limits["min_tokens"], n_checked)
    picked = sample_finished(drv.sent, t0, seed, limits["min_tokens"],
                             limits["max_requests"], n_checked)
    info["slots_checked"] = len({s.slot for s in picked})
    sample = [(np.asarray(s.req.prompt, np.int32),
               np.asarray(s.req.out, np.int32)) for s in picked]
    del eng, drv, log, window, picked
    gc.collect()
    gaps = reference_gaps(cell.family, config, seed, sample)
    # the widest gap, and the mean where the widest swings too far from
    # seed to seed to separate the program from its control: each is
    # compared where the configuration gives it a limit, else only logged
    stats = {"max_logit_gap": float(gaps.max()) if len(gaps) else np.inf,
             "mean_logit_gap": float(gaps.mean()) if len(gaps) else np.inf}
    compared = {k: v for k, v in stats.items()
                if limits.get(k) is not None}
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in compared.items()}
    checks["tokens_checked"] = {"value": int(len(gaps)),
                                "limit": limits["min_tokens"]}
    if "checked_requests" in mix:
        checks["requests_checked"] = {"value": len(sample),
                                      "limit": n_checked}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    info.update({k: v for k, v in stats.items() if k not in compared})
    correct = (bool(compared)
               and all(v <= limits[k] for k, v in compared.items())
               and failed == 0 and len(gaps) >= limits["min_tokens"]
               and len(sample) >= n_checked)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return Outcome(line=line, checks=checks, info=info, sample=sample,
                   gaps=gaps)


def _pool_delta(a: Optional[dict], b: Optional[dict]) -> Optional[dict]:
    if a is None or b is None:
        return None
    return {k: b[k] - a[k] for k in b if isinstance(b.get(k), (int, float))
            and isinstance(a.get(k), (int, float))}


def profile_options():
    """Device and host events, without the Python tracer (which records
    every Python call and would swamp the trace)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


class _Profiler:
    """Profiles the last ``slice_s`` seconds of the window, so that starting
    the profiler costs the window little and writing the trace nothing.
    The slice is wrapped in a ``bench.window`` annotation and ends with the
    window, before any drain."""

    def __init__(self, seconds: float, slice_s: float, clock):
        self.clock = clock
        self.slice_s = min(slice_s, seconds / 2)
        self.seconds = seconds
        self.t_end: Optional[float] = None
        self.last_step_s = 0.0
        self.dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        self.annotation = None
        self.lo = self.hi = 0.0
        self.summary = None

    def wrap(self, step: Callable) -> Callable:
        import jax

        def traced_step():
            now = self.clock()
            if self.t_end is None:
                self.t_end = now + self.seconds
            # start with the step that will run into the slice, so a step
            # longer than the slice cannot leave it empty
            if (self.annotation is None and not self.hi
                    and now + self.last_step_s >= self.t_end - self.slice_s):
                jax.profiler.start_trace(self.dir.name,
                                         profiler_options=profile_options())
                self.annotation = _annotate("bench.window")
                self.annotation.__enter__()
                self.lo = self.clock()
            step()
            self.last_step_s = self.clock() - now
            if self.annotation is not None and self.clock() >= self.t_end:
                self._close()
        return traced_step

    def _close(self) -> None:
        import jax

        self.hi = self.clock()
        self.annotation.__exit__(None, None, None)
        self.annotation = None
        jax.profiler.stop_trace()

    def stop(self) -> None:
        """Close the slice if the window's last step did not, and reduce
        the trace."""
        from bench import xplane

        if self.annotation is not None:
            self._close()
        if not self.hi:
            raise RuntimeError("the traced slice never started")
        self.summary = xplane.reduce(self.dir.name)
        self.dir.cleanup()
