"""Observability subsystem tests (:mod:`repro.obs`, the fifth registry).

Covers the span/counter core (nesting and exception-safety property
tests, the zero-overhead disabled fast path), the derived-metrics math
(percentile vs the numpy reference), the Chrome-trace exporter + schema
validator, and the load-bearing engine integration: a traced
``bsdp_fused × int4_bp_fused × prefix_cache`` serving run whose timeline
must export valid Chrome JSON with the step-loop spans and the kernels'
trace-time call-site counters, whose event-derived TTFT/TPOT must equal
the engine's Stamp-based stats value-for-value, and whose resident-byte
gauges must be byte-exact against the dry-run analytic twins.  Also: spans
mirrored into ``jax.profiler`` annotations, compiles counted under the
span that caused them, and the ``request.scheduled`` stamp on the attach
and requeue paths.
"""

import io

# imported up front: the first registered sink binds ``jax.profiler`` and
# the compile listeners, and a first import inside a timed property
# example would overrun its deadline
import jax  # noqa: F401
import numpy as np
import pytest

import repro.obs as obs
from repro.obs import trace as trace_mod

from _hypothesis_compat import given, settings, st

#: the all-fused residency policy the acceptance run serves under
MODE = "ffn=bsdp_fused,mixer=w8a16,default=w8a8"
SLOTS, MAX_LEN, MAX_NEW = 2, 32, 4


@pytest.fixture(autouse=True)
def _clean_obs():
    """Sinks and the counter/gauge registry are module-global: reset around
    every test so traces cannot leak across tests (or from the engine
    fixture into unrelated assertions)."""
    obs.clear_sinks()
    obs.reset_metrics()
    yield
    obs.clear_sinks()
    obs.reset_metrics()


class _SpySink(obs.Sink):
    """Counts every sink callback — the disabled path must never call it."""

    def __init__(self):
        self.calls = 0

    def on_span(self, rec):
        self.calls += 1

    def on_point(self, rec):
        self.calls += 1


# ---------------------------------------------------------------------------
# Span core: nesting, exception safety, disabled fast path
# ---------------------------------------------------------------------------


class TestSpans:
    @settings(max_examples=12)
    @given(st.lists(st.integers(min_value=1, max_value=4),
                    min_size=0, max_size=6))
    def test_nesting_depth_restored_and_recorded(self, chain_depths):
        """Any sequence of nested span chains leaves the live depth at 0
        and records one span per level with the depth it was entered at."""
        obs.clear_sinks()
        ring = obs.register_sink(obs.RingSink())

        def nest(d):
            with obs.span(f"level{d}"):
                if d > 1:
                    nest(d - 1)

        for d in chain_depths:
            nest(d)
        assert obs.current_depth() == 0
        spans = [r for r in ring.records()
                 if isinstance(r, obs.SpanRecord)]
        assert len(spans) == sum(chain_depths)
        expected = sorted(lvl for d in chain_depths for lvl in range(d))
        assert sorted(r.depth for r in spans) == expected
        assert all(r.dur >= 0 for r in spans)

    @settings(max_examples=8)
    @given(st.integers(min_value=1, max_value=5))
    def test_exception_safety(self, depth):
        """A raise at any nesting depth still emits every open span (tagged
        with the exception type), restores depth 0, and propagates."""
        obs.clear_sinks()
        ring = obs.register_sink(obs.RingSink())

        class Boom(RuntimeError):
            pass

        def nest(d):
            with obs.span(f"s{d}"):
                if d == 1:
                    raise Boom("bang")
                nest(d - 1)

        with pytest.raises(Boom):
            nest(depth)
        assert obs.current_depth() == 0
        spans = [r for r in ring.records()
                 if isinstance(r, obs.SpanRecord)]
        assert len(spans) == depth
        assert all(r.attrs.get("error") == "Boom" for r in spans)

    def test_no_sink_returns_shared_null_span(self):
        """The disabled path is allocation-free: every span() call returns
        the SAME singleton object."""
        assert not obs.active()
        got = {id(obs.span(f"s{i}", a=i)) for i in range(100)}
        assert got == {id(obs.NULL_SPAN)}

    def test_disabled_context_spy_sees_nothing(self):
        """Inside disabled(): no sink callback fires, no counter/gauge
        accumulates, and span() hands back the null singleton even though a
        sink is registered."""
        spy = obs.register_sink(_SpySink())
        assert obs.active()
        with obs.disabled():
            assert not obs.active()
            assert obs.span("x", a=1) is obs.NULL_SPAN
            with obs.span("y"):
                obs.counter("c.test", 5)
                obs.gauge("g.test", 1.0)
                obs.event("e.test")
        assert spy.calls == 0
        assert obs.counter_value("c.test") == 0
        assert obs.gauge_value("g.test") is None
        # back out of the context, everything records again
        with obs.span("z"):
            obs.counter("c.test")
        assert spy.calls == 2
        assert obs.counter_value("c.test") == 1

    def test_span_attrs_reach_sink(self):
        ring = obs.register_sink(obs.RingSink())
        with obs.span("engine.prefill", slots=2, tokens=17):
            pass
        (rec,) = ring.records()
        assert rec.name == "engine.prefill"
        assert rec.attrs == {"slots": 2, "tokens": 17}


class TestCountersAndGauges:
    def test_counter_accumulates_per_label_set(self):
        ring = obs.register_sink(obs.RingSink())
        obs.counter("k.d", kernel="a")
        obs.counter("k.d", kernel="a")
        obs.counter("k.d", kernel="b")
        obs.counter("k.d", 3, kernel="b")
        assert obs.counter_value("k.d", kernel="a") == 2
        assert obs.counter_value("k.d", kernel="b") == 4
        # records carry the running total at emission time
        totals = [r.value for r in ring.records() if r.labels == {"kernel": "b"}]
        assert totals == [1, 4]

    def test_gauge_last_value_wins(self):
        obs.register_sink(obs.NullSink())
        obs.gauge("occ", 3)
        obs.gauge("occ", 7)
        assert obs.gauge_value("occ") == 7
        assert trace_mod.gauges_snapshot() == {("occ",): 7}

    def test_counter_fires_at_trace_time_under_jit(self):
        """Counters inside jitted code count call sites per compiled
        program: three executions of one compilation = one increment (the
        ``kernel.traced`` semantics documented in kernels/ops.py)."""
        import jax
        import jax.numpy as jnp

        obs.register_sink(obs.NullSink())

        @jax.jit
        def f(x):
            obs.counter("jit.trace.test")
            return x + 1

        for _ in range(3):
            f(jnp.ones(2)).block_until_ready()
        assert obs.counter_value("jit.trace.test") == 1

    def test_pool_telemetry_emits_counters(self):
        from repro.core import paging

        obs.register_sink(obs.NullSink())
        pool = paging.PagePool(4, 2)
        pages = pool.alloc(3)
        pool.release(pages)
        pool.note_cow()
        pool.note_eviction(2)
        pool.note_prefix_hit(16)
        assert obs.counter_value("pages.alloc") == 3
        assert obs.counter_value("pages.free") == 3
        assert obs.counter_value("pages.cow") == 1
        assert obs.counter_value("pages.evict") == 2
        assert obs.counter_value("pages.prefix_hit") == 1
        assert obs.counter_value("pages.prefix_tokens_saved") == 16
        assert obs.gauge_value("pages.occupancy") == 0
        assert obs.gauge_value("pages.high_water") == 3


class TestRingSink:
    def test_capacity_drops_oldest(self):
        ring = obs.RingSink(capacity=4)
        obs.register_sink(ring)
        for i in range(7):
            obs.event("e", i=i)
        assert len(ring.records()) == 4
        assert ring.dropped == 3
        assert [r.labels["i"] for r in ring.records()] == [3, 4, 5, 6]
        ring.clear()
        assert ring.records() == [] and ring.dropped == 0

    def test_register_unregister(self):
        ring = obs.register_sink(obs.RingSink())
        assert obs.active() and ring in obs.sinks()
        obs.unregister_sink(ring)
        assert not obs.active()
        obs.unregister_sink(ring)  # second removal is a no-op


# ---------------------------------------------------------------------------
# Derived metrics
# ---------------------------------------------------------------------------


class TestPercentile:
    @settings(max_examples=30)
    @given(
        st.lists(st.floats(min_value=-1e6, max_value=1e6),
                 min_size=1, max_size=40),
        st.floats(min_value=0.0, max_value=100.0),
    )
    def test_matches_numpy(self, values, q):
        expected = float(np.percentile(np.asarray(values, np.float64), q))
        assert obs.percentile(values, q) == pytest.approx(
            expected, rel=1e-9, abs=1e-6)

    def test_rejects_empty_and_bad_q(self):
        with pytest.raises(ValueError):
            obs.percentile([], 50)
        with pytest.raises(ValueError):
            obs.percentile([1.0], 101)

    def test_summarize_spans(self):
        recs = [obs.SpanRecord("a", 0.0, d, 0, {}) for d in (0.1, 0.3)]
        recs.append(obs.SpanRecord("b", 0.0, 0.2, 1, {}))
        recs.append(obs.PointRecord("counter", "c", 0.0, 1, {}))
        summary = obs.summarize_spans(recs)
        assert set(summary) == {"a", "b"}
        assert summary["a"]["count"] == 2
        assert summary["a"]["total_s"] == pytest.approx(0.4)
        assert summary["a"]["p50_s"] == pytest.approx(0.2)
        assert summary["b"]["max_s"] == pytest.approx(0.2)

    def test_dispatch_table_counts_records(self):
        recs = [
            obs.PointRecord("counter", "kernel.traced", 0.0, t,
                            {"kernel": k})
            for t, k in [(1, "a"), (2, "a"), (1, "b"), (3, "a")]
        ]
        recs.append(obs.PointRecord("gauge", "kernel.traced", 0.0, 9, {}))
        table = obs.traced_kernels(recs)
        assert table == {(("kernel", "a"),): 3, (("kernel", "b"),): 1}


class TestStatsLineSink:
    def test_prints_every_n_steps(self):
        out = io.StringIO()
        sink = obs.StatsLineSink(every=2, stream=out)
        obs.register_sink(sink)
        obs.counter("engine.tokens", 6)
        obs.gauge("pages.occupancy", 3)
        obs.gauge("pages.high_water", 5)
        obs.gauge("bytes.cache", 2e6)
        step = obs.SpanRecord("engine.step", 0.0, 0.01, 0, {})
        sink.on_span(step)
        assert out.getvalue() == ""  # not yet at the period
        sink.on_span(obs.SpanRecord("engine.plan", 0.0, 0.01, 1, {}))
        assert out.getvalue() == ""  # non-step spans don't advance it
        sink.on_span(step)
        line = out.getvalue()
        assert "[obs] step 2" in line
        assert "6 tok (3.0 tok/step)" in line
        assert "pages 3 (hw 5)" in line
        assert "cache 2.00 MB" in line
        with pytest.raises(ValueError):
            obs.StatsLineSink(every=0)


# ---------------------------------------------------------------------------
# Chrome export + schema validation
# ---------------------------------------------------------------------------


class TestChromeExport:
    def test_roundtrip_validates(self, tmp_path):
        ring = obs.register_sink(obs.RingSink())
        with obs.span("outer", a=1):
            with obs.span("inner"):
                obs.counter("k", kernel="x")
            obs.gauge("g", 2.0)
            obs.event("request.arrival", uid=3, state="QUEUED", t=0.0,
                      step=0, work=0, prompt_len=4, new_tokens=0)
        path = tmp_path / "trace.json"
        doc = obs.write_chrome_trace(ring.records(), str(path))
        import json

        with open(path) as f:
            assert json.load(f) == doc
        stats = obs.validate_chrome(doc)
        assert stats["span_names"] == {"inner", "outer"}
        assert stats["counter_names"] == {"k[kernel=x]", "g"}
        assert stats["instants"] == 1
        # ts rebased: earliest event at 0, all non-negative
        assert min(e["ts"] for e in doc["traceEvents"]) == 0

    def test_empty_records(self):
        doc = obs.chrome_trace([])
        assert obs.validate_chrome(doc)["events"] == 0

    @pytest.mark.parametrize("doc", [
        [1, 2],                                            # not an object
        {"foo": []},                                       # no traceEvents
        {"traceEvents": [None]},                           # non-object event
        {"traceEvents": [{"ph": "X", "ts": 0, "pid": 0,
                          "tid": 0, "dur": 1}]},           # missing name
        {"traceEvents": [{"name": "a", "ph": "Q", "ts": 0,
                          "pid": 0, "tid": 0}]},           # unknown phase
        {"traceEvents": [{"name": "a", "ph": "i", "ts": "0",
                          "pid": 0, "tid": 0}]},           # non-numeric ts
        {"traceEvents": [{"name": "a", "ph": "X", "ts": 0,
                          "pid": 0, "tid": 0}]},           # X without dur
        {"traceEvents": [{"name": "a", "ph": "X", "ts": 0,
                          "pid": 0, "tid": 0, "dur": -1}]},  # negative dur
        {"traceEvents": [{"name": "a", "ph": "i", "ts": 0,
                          "pid": 0, "tid": 1.5}]},         # non-int tid
    ])
    def test_validator_rejects(self, doc):
        with pytest.raises(obs.TraceFormatError):
            obs.validate_chrome(doc)

    def test_validate_cli(self, tmp_path, capsys):
        from repro.obs import validate

        good = tmp_path / "good.json"
        good.write_text('{"traceEvents": []}')
        assert validate.main([str(good)]) == 0
        bad = tmp_path / "bad.json"
        bad.write_text('{"traceEvents": [{"ph": "Z"}]}')
        assert validate.main([str(bad)]) == 1
        assert validate.main([]) == 2


# ---------------------------------------------------------------------------
# Engine integration: the acceptance run
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_run():
    """One traced bsdp_fused × int4_bp_fused × prefix_cache serving run;
    everything the tests assert on is captured before the ring sink is
    unregistered (the autouse cleaner wipes registry state per test)."""
    import jax

    from repro.configs import get_smoke_config
    from repro.models import model as model_lib
    from repro.serve import engine
    from repro.sharding import partitioning as P

    obs.clear_sinks()
    obs.reset_metrics()
    cfg = get_smoke_config("qwen3-1.7b").scaled(n_layers=2, vocab_size=64)
    params = P.materialize(model_lib.specs(cfg, 1), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, size=(int(n),)).astype(np.int32)
               for n in (9, 9, 5, 7)]
    eng = engine.ServeEngine(
        params, cfg, slots=SLOTS, max_len=MAX_LEN, mode=MODE,
        cache_format="int4_bp_fused", scheduler="prefix_cache",
        min_dim=16, trace=True,
    )
    reqs = [eng.submit(p, MAX_NEW) for p in prompts]
    eng.run()
    data = {
        "cfg": eng.cfg,
        "timeline": eng.timeline(),
        "stats": eng.stats(),
        "resident": eng.resident_bytes(),
        "bytes_cache_gauge": obs.gauge_value("bytes.cache"),
        "bytes_weights_gauge": obs.gauge_value("bytes.weights"),
        "outs": [list(r.out) for r in reqs],
    }
    obs.unregister_sink(eng._ring)
    obs.reset_metrics()
    return data


class TestEngineTracing:
    def test_run_completed(self, traced_run):
        assert all(len(o) == MAX_NEW for o in traced_run["outs"])
        assert len(traced_run["timeline"]) > 0

    def test_step_loop_spans_present(self, traced_run):
        names = {r.name for r in traced_run["timeline"]
                 if isinstance(r, obs.SpanRecord)}
        assert {"engine.step", "engine.plan", "engine.reserve",
                "engine.prefill", "engine.decode",
                "engine.complete"} <= names

    def test_chrome_export_acceptance(self, traced_run):
        """The timeline exports valid Chrome JSON carrying the step-loop
        spans AND the trace-time kernel call-site counter tracks of the
        all-fused run."""
        doc = obs.chrome_trace(traced_run["timeline"])
        stats = obs.validate_chrome(doc)
        assert {"engine.plan", "engine.prefill",
                "engine.decode"} <= stats["span_names"]
        assert any(n.startswith("kernel.traced")
                   for n in stats["counter_names"])

    def test_dispatch_counters_cover_fused_kernels(self, traced_run):
        table = obs.traced_kernels(traced_run["timeline"])
        kernels = {dict(key).get("kernel") for key in table}
        assert "gemm_fused" in kernels   # the BSDP FFN single-contraction
        assert "plane_attn" in kernels   # the fused decode-attention read

    def test_request_stats_from_events_value_identical(self, traced_run):
        """TTFT/TPOT/E2E derived purely from the trace's lifecycle events
        equal the engine's Stamp-based stats field-for-field."""
        derived = obs.request_stats_from_events(traced_run["timeline"])
        assert derived == traced_run["stats"].requests
        assert all(r.state == "done" for r in derived)

    def test_resident_byte_gauges_exact_vs_dryrun_twins(self, traced_run):
        """The traced bytes.cache / bytes.weights gauges are byte-exact
        against BOTH the engine's registry accounting and the dry-run
        analytic twins (`analytic_cache_bytes`, `abstract_quant` via
        `analytic_weight_bytes`) — observability inherits the registries'
        drift-killed-by-construction property."""
        from repro.launch import dryrun

        cache_twin = dryrun.analytic_cache_bytes(
            traced_run["cfg"], SLOTS, MAX_LEN)
        assert traced_run["bytes_cache_gauge"] == cache_twin
        assert traced_run["resident"]["cache"] == cache_twin
        weight_twin = dryrun.analytic_weight_bytes(
            traced_run["cfg"], MODE, min_dim=16)
        assert traced_run["bytes_weights_gauge"] == weight_twin
        assert traced_run["resident"]["weights"] == weight_twin

    def test_lifecycle_events_per_request(self, traced_run):
        events = [r for r in traced_run["timeline"]
                  if isinstance(r, obs.PointRecord) and r.kind == "event"]
        by_name = {}
        for e in events:
            by_name.setdefault(e.name, set()).add(e.labels["uid"])
        uids = {0, 1, 2, 3}
        assert by_name["request.arrival"] == uids
        assert by_name["request.first_token"] == uids
        assert by_name["request.finished"] == uids

    def test_readback_nests_in_prefill_and_decode(self, traced_run):
        """Each ``engine.readback`` (the wait for the logits) lies inside an
        ``engine.prefill`` or ``engine.decode`` span, one level deeper, and
        both kinds of parent hold one."""
        spans = [r for r in traced_run["timeline"]
                 if isinstance(r, obs.SpanRecord)]
        parents = [r for r in spans
                   if r.name in ("engine.prefill", "engine.decode")]
        seen = set()
        readbacks = [r for r in spans if r.name == "engine.readback"]
        assert readbacks
        for rb in readbacks:
            (parent,) = [p for p in parents if p.ts <= rb.ts
                         and rb.ts + rb.dur <= p.ts + p.dur]
            assert rb.depth == parent.depth + 1
            seen.add(parent.name)
        assert seen == {"engine.prefill", "engine.decode"}

    def test_scheduled_once_per_request(self, traced_run):
        """``request.scheduled`` fires once per uid, between its arrival
        and its first token, and carries the queue wait."""
        events = [r for r in traced_run["timeline"]
                  if isinstance(r, obs.PointRecord) and r.kind == "event"]
        sched = [e for e in events if e.name == "request.scheduled"]
        assert sorted(e.labels["uid"] for e in sched) == [0, 1, 2, 3]
        by = {(e.name, e.labels["uid"]): e.labels for e in events}
        for e in sched:
            uid, t = e.labels["uid"], e.labels["t"]
            arrival = by[("request.arrival", uid)]["t"]
            assert arrival <= t <= by[("request.first_token", uid)]["t"]
            assert e.labels["wait_s"] == t - arrival


# ---------------------------------------------------------------------------
# Spans on the profiler's clock
# ---------------------------------------------------------------------------


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs its life."""

    log: list = []

    def __init__(self, *args, **kwargs):
        self.args, self.kwargs = args, kwargs
        _FakeAnnotation.log.append(("new", args, kwargs))

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.args))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.args))
        return False


class TestProfilerAnnotations:
    @pytest.fixture
    def fake(self, monkeypatch):
        import jax

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
        _FakeAnnotation.log = []
        return _FakeAnnotation.log

    def test_active_span_enters_annotation_with_its_name(self, fake):
        obs.register_sink(obs.RingSink())
        with obs.span("engine.prefill", slots=2, tokens=17):
            with obs.span("engine.readback"):
                pass
        assert fake == [
            ("new", ("engine.prefill",), {}), ("enter", ("engine.prefill",)),
            ("new", ("engine.readback",), {}),
            ("enter", ("engine.readback",)), ("exit", ("engine.readback",)),
            ("exit", ("engine.prefill",)),
        ]

    def test_no_sink_builds_no_annotation(self, fake):
        assert not obs.active()
        for i in range(10):
            s = obs.span(f"s{i}", a=i)
            assert s is obs.NULL_SPAN
            with s:
                pass
        spy = obs.register_sink(_SpySink())
        with obs.disabled():
            with obs.span("off"):
                pass
        assert fake == [] and spy.calls == 0

    def test_annotation_closes_when_the_body_raises(self, fake):
        obs.register_sink(obs.RingSink())
        with pytest.raises(KeyError):
            with obs.span("engine.step"):
                raise KeyError("x")
        assert fake[-1] == ("exit", ("engine.step",))
        assert obs.current_depth() == 0

    def test_spans_share_the_host_line_of_a_profiler_trace(self, tmp_path):
        """In a real profiler trace the spans are host events on the same
        thread line as an annotation the caller opened around them."""
        import jax
        from jax.profiler import ProfileData

        obs.register_sink(obs.RingSink())
        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation("bench.step"):
            with obs.span("engine.step"):
                with obs.span("engine.readback"):
                    pass
        jax.profiler.stop_trace()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        lines = [{e.name for e in line.events}
                 for plane in ProfileData.from_file(str(path)).planes
                 for line in plane.lines]
        assert any({"bench.step", "engine.step", "engine.readback"} <= names
                   for names in lines)


# ---------------------------------------------------------------------------
# Compiles attributed to spans
# ---------------------------------------------------------------------------


class TestCompileCounters:
    def test_jit_compile_counted_under_innermost_span(self):
        import jax
        import jax.numpy as jnp

        x13, x14 = jnp.ones(13), jnp.ones(14)   # made before tracing
        obs.register_sink(obs.NullSink())

        @jax.jit
        def f(x):
            return x * 3 + 1

        with obs.span("engine.step"):
            with obs.span("engine.prefill"):
                f(x13).block_until_ready()
        assert obs.counter_value("jax.compile", span="engine.prefill") == 1
        assert obs.counter_value("jax.compile_s", span="engine.prefill") > 0
        assert obs.counter_value("jax.compile", span="engine.step") == 0
        f(x13).block_until_ready()      # cached: no compile
        f(x14).block_until_ready()      # outside any span
        assert obs.counter_value("jax.compile", span="none") == 1
        assert obs.counter_total("jax.compile") == 2

    def test_no_compile_counted_while_disabled(self):
        import jax
        import jax.numpy as jnp

        x = jnp.ones(11)
        obs.register_sink(obs.NullSink())
        with obs.disabled():
            jax.jit(lambda x: x - 5)(x).block_until_ready()
        assert obs.counter_total("jax.compile") == 0

    def test_cache_hit_counted_under_innermost_span(self):
        import jax

        obs.register_sink(obs.NullSink())
        with obs.span("engine.decode"):
            jax.monitoring.record_event(trace_mod.CACHE_HIT_EVENT)
        jax.monitoring.record_event("/jax/some/other_event")
        assert obs.counter_value("jax.compile_cache_hit",
                                 span="engine.decode") == 1
        assert obs.counter_total("jax.compile_cache_hit") == 1

    def test_stats_line_prints_compiles(self):
        out = io.StringIO()
        sink = obs.register_sink(obs.StatsLineSink(every=1, stream=out))
        obs.counter("jax.compile", 3, span="engine.prefill")
        obs.counter("jax.compile_s", 1.25, span="engine.prefill")
        sink.on_span(obs.SpanRecord("engine.step", 0.0, 0.01, 0, {}))
        assert "3 compiles (1.2s)" in out.getvalue()
        assert "dispatches" not in out.getvalue()


# ---------------------------------------------------------------------------
# request.scheduled on the attach and requeue paths
# ---------------------------------------------------------------------------


def _serve_traced(cache_format, scheduler, prompts, *, slots, pool_pages):
    import jax

    from repro.configs import get_smoke_config
    from repro.models import model as model_lib
    from repro.serve import engine
    from repro.sharding import partitioning as P

    cfg = get_smoke_config("qwen3-1.7b").scaled(n_layers=2, vocab_size=64)
    params = P.materialize(model_lib.specs(cfg, 1), jax.random.PRNGKey(0))
    eng = engine.ServeEngine(params, cfg, slots=slots, max_len=32,
                             cache_format=cache_format, scheduler=scheduler,
                             page_pool_pages=pool_pages, trace=True)
    reqs = [eng.submit(p, 3) for p in prompts]
    eng.run()
    return eng, reqs


def _scheduled(timeline):
    return [r for r in timeline if isinstance(r, obs.PointRecord)
            and r.kind == "event" and r.name == "request.scheduled"]


class TestScheduledEvent:
    def test_attach_path_schedules_once(self):
        """Requests that attach a cached prefix leave the queue holding a
        slot through the attach, and are stamped there once."""
        rng = np.random.default_rng(1)
        prefix = rng.integers(0, 64, 24).astype(np.int32)
        prompts = [np.concatenate([prefix, rng.integers(0, 64, 3)
                                   .astype(np.int32)]) for _ in range(5)]
        eng, reqs = _serve_traced("paged_int4_bp", "prefix_cache", prompts,
                                  slots=2, pool_pages=8)
        timeline = eng.timeline()
        assert eng.stats().pages["prefix_hits"] >= 1
        sched = _scheduled(timeline)
        assert sorted(e.labels["uid"] for e in sched) == [r.uid for r in reqs]
        # an attached request is stamped already prefilling
        assert any(e.labels["state"] == "prefilling" for e in sched)
        assert all(r.scheduled is not None for r in reqs)
        assert obs.request_stats_from_events(timeline) == \
            eng.stats().requests

    def test_requeue_does_not_schedule(self):
        """A request sent back to the queue because the page pool is full
        is stamped only when it finally gets its slot."""
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, 64, 9).astype(np.int32) for _ in range(3)]
        eng, reqs = _serve_traced("paged_bf16", "fcfs", prompts,
                                  slots=2, pool_pages=6)
        timeline = eng.timeline()
        requeues = [r for r in timeline if isinstance(r, obs.PointRecord)
                    and r.name == "sched.requeue"]
        assert requeues
        sched = _scheduled(timeline)
        assert sorted(e.labels["uid"] for e in sched) == [r.uid for r in reqs]
        late = [e for e in sched if e.ts > requeues[0].ts]
        assert late and all(e.labels["wait_s"] > 0 for e in late)
        assert all(r.state == "done" for r in reqs)
        assert obs.request_stats_from_events(timeline) == \
            eng.stats().requests


# ---------------------------------------------------------------------------
# attn.pages_read / attn.pages_mapped per decode call
# ---------------------------------------------------------------------------


class TestPageReadCounters:
    @pytest.mark.parametrize("cache_format,read_share", [
        ("paged_int4_bp_fused", 0.5),  # the live page of two
        ("paged_int4_bp", 1.0),        # the gather reads every page
    ])
    def test_pages_read_and_mapped_per_decode(self, cache_format,
                                              read_share):
        """Each ``engine.decode`` counts the pages one attention layer
        reads and the pages the live block tables map.  Prompts and
        answers stay inside the first 128 positions of a 256-position
        ring: the page-aware kernel reads that one page of a slot's two;
        the block-table gather reads them all."""
        import jax

        from repro.configs import get_smoke_config
        from repro.core import kvcache
        from repro.models import model as model_lib
        from repro.serve import engine
        from repro.sharding import partitioning as P

        cfg = get_smoke_config("qwen3-1.7b").scaled(n_layers=2,
                                                    vocab_size=64)
        params = P.materialize(model_lib.specs(cfg, 1),
                               jax.random.PRNGKey(0))
        eng = engine.ServeEngine(params, cfg, slots=2, max_len=256,
                                 cache_format=cache_format, trace=True)
        rng = np.random.default_rng(3)
        for _ in range(2):
            eng.submit(rng.integers(0, 64, 9).astype(np.int32), 4)
        eng.run()
        timeline = eng.timeline()
        decodes = [r for r in timeline if isinstance(r, obs.SpanRecord)
                   and r.name == "engine.decode"]
        points = {name: [r for r in timeline
                         if isinstance(r, obs.PointRecord)
                         and r.name == name]
                  for name in ("attn.pages_read", "attn.pages_mapped")}
        assert len(decodes) == 3  # answers of 4: the first from prefill
        assert all(len(p) == len(decodes) for p in points.values())
        npp = kvcache.get_cache_format(cache_format).pages_per_slot(256)
        mapped = points["attn.pages_mapped"][-1].value
        assert mapped == len(decodes) * 2 * npp
        assert points["attn.pages_read"][-1].value == read_share * mapped
