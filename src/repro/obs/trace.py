"""Tracing core: spans, a typed counter/gauge registry, pluggable sinks.

The paper's speedups came from looking *below* the SDK — §III–IV's
instruction-level inspection of what the compiler actually emitted — and
the PrIM line of work shows that systematic counters, not guesswork, is
what surfaces software-stack inefficiencies.  This module is that layer
for the serving stack: the **fifth registry concept** after weights,
caches, pages and schedulers.  Observability *sinks* are registered
exactly like formats and schedulers (:func:`register_sink`), and every
instrumented site — the engine step loop, the kernel wrappers,
the page pool, the schedulers — talks to the registry instead of owning
its own logging.

Four primitives, all **zero-overhead when disabled** (no sink registered,
or inside :func:`disabled`):

``span(name, **attrs)``    a ``with``-scoped timed region.  Disabled, it
                           returns one shared no-op singleton — the step
                           loop allocates nothing per call.  Enabled, the
                           span records wall time + nesting depth and
                           emits a :class:`SpanRecord` to every sink at
                           exit (exception-safe: the record is emitted and
                           the depth restored even when the body raises,
                           with the exception type stamped into ``attrs``).
                           An enabled span also enters a
                           ``jax.profiler.TraceAnnotation`` of its name, so
                           a profiler trace shows it on the host thread
                           that ran it, on the device trace's clock.
``counter(name, n, **lb)`` a monotonically accumulating metric, keyed by
                           ``(name, sorted labels)`` in a module registry;
                           each increment also emits a
                           :class:`PointRecord` carrying the running
                           total.  NOTE on jitted code: a ``counter()``
                           call inside a traced function runs at *trace*
                           time, so the ``kernel.traced`` counters count
                           kernel call sites per compiled program, not
                           executions (one compilation of the unrolled
                           BSDP GEMM records 16, the fused kernel 1).
``gauge(name, v, **lb)``   a last-value metric (pool occupancy, resident
                           bytes); same registry, same record stream.

``event(name, **lb)``      an instant (zero-duration) mark — the request
                           lifecycle stream (arrival / scheduled / first
                           token / finished) that :mod:`repro.obs.metrics`
                           turns back into TTFT/TPOT.

Registering the first sink also registers one process-wide
``jax.monitoring`` listener: while tracing is active every compilation
(persistent-cache retrievals included) counts as ``jax.compile`` (its
seconds as ``jax.compile_s``) and every persistent-cache hit as
``jax.compile_cache_hit``, each labelled ``span=`` the innermost open span
(``"none"`` outside any), which names the step phase that compiled.

Shipped sinks: :class:`NullSink` (explicit no-op), :class:`RingSink`
(bounded in-memory ring — powers ``ServeEngine.timeline()``), the
Chrome-trace exporter (:class:`repro.obs.export.ChromeTraceSink`) and the
periodic stats line (:class:`repro.obs.metrics.StatsLineSink`).
Registering a new sink is ~5 lines: subclass :class:`Sink`, override
``on_span``/``on_point``, call :func:`register_sink`.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, NamedTuple, Optional


class SpanRecord(NamedTuple):
    """One closed span: wall-clock start, duration, nesting depth, attrs.

    ``ts``/``dur`` are ``time.perf_counter`` seconds; ``depth`` is the
    span-nesting level at entry (0 = top level), which is what lets the
    Chrome exporter reconstruct the flame graph without parent pointers.
    """

    name: str
    ts: float
    dur: float
    depth: int
    attrs: dict


class PointRecord(NamedTuple):
    """One metric sample: ``kind`` is ``"counter"`` (``value`` = running
    total after the increment), ``"gauge"`` (``value`` = the new value) or
    ``"event"`` (instant mark, ``value`` = 0)."""

    kind: str
    name: str
    ts: float
    value: float
    labels: dict


class Sink:
    """Base sink: override the hooks you care about (both default no-op)."""

    def on_span(self, rec: SpanRecord) -> None:  # noqa: D102 - protocol
        pass

    def on_point(self, rec: PointRecord) -> None:  # noqa: D102 - protocol
        pass


class NullSink(Sink):
    """Explicit no-op sink (keeps tracing *enabled* — spans time and
    counters accumulate — while discarding the record stream; useful for
    measuring instrumentation overhead in isolation)."""


class RingSink(Sink):
    """Bounded in-memory ring of records, in emission order.

    Spans are recorded at *exit* (a parent closes after its children), so
    consumers that need start-ordering sort by ``ts`` — the Chrome
    exporter does.  ``capacity`` bounds memory on long-lived engines;
    the oldest records drop first.
    """

    def __init__(self, capacity: int = 65536):
        if capacity < 1:
            raise ValueError("RingSink capacity must be >= 1")
        self.capacity = int(capacity)
        self._buf: list = []
        self.dropped = 0

    def _push(self, rec) -> None:
        self._buf.append(rec)
        if len(self._buf) > self.capacity:
            del self._buf[0]
            self.dropped += 1

    on_span = _push
    on_point = _push

    def records(self) -> list:
        """All retained records, emission-ordered (oldest first)."""
        return list(self._buf)

    def clear(self) -> None:
        self._buf.clear()
        self.dropped = 0


# ---------------------------------------------------------------------------
# Sink registry + global enable switch
# ---------------------------------------------------------------------------

_SINKS: list[Sink] = []
_ENABLED = True
#: ``jax.profiler``, imported when the first sink is registered
_profiler = None

#: jax.monitoring event names the compile listener counts
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _innermost() -> str:
    return _stack[-1] if _stack else "none"


def _on_compile(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT and _ENABLED and _SINKS:
        where = _innermost()
        counter("jax.compile", span=where)
        counter("jax.compile_s", duration, span=where)


def _on_cache_hit(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT and _ENABLED and _SINKS:
        counter("jax.compile_cache_hit", span=_innermost())


def _bind_jax() -> None:
    """Import ``jax.profiler`` and register the compile listeners, once
    per process."""
    global _profiler
    if _profiler is not None:
        return
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    jax.monitoring.register_event_listener(_on_cache_hit)
    _profiler = jax.profiler


def register_sink(sink: Sink) -> Sink:
    """Register a sink; returns it (so ``ring = register_sink(RingSink())``
    reads naturally).  The first registered sink is what flips the
    module from the zero-overhead disabled path to recording."""
    _bind_jax()
    _SINKS.append(sink)
    return sink


def unregister_sink(sink: Sink) -> None:
    """Remove one registered sink (missing sink is a no-op, so teardown
    paths can call it unconditionally)."""
    try:
        _SINKS.remove(sink)
    except ValueError:
        pass


def clear_sinks() -> None:
    """Drop every sink — back to the zero-overhead path."""
    _SINKS.clear()


def sinks() -> tuple[Sink, ...]:
    return tuple(_SINKS)


def active() -> bool:
    """True when at least one sink is registered and tracing is not
    suppressed by :func:`disabled` — the single branch every primitive
    takes on its fast path."""
    return _ENABLED and bool(_SINKS)


@contextlib.contextmanager
def disabled():
    """Temporarily suppress all tracing (sinks stay registered but see
    nothing; counters do not accumulate).  Nestable."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = False
    try:
        yield
    finally:
        _ENABLED = prev


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

_clock: Callable[[], float] = time.perf_counter
#: names of the open spans, outermost first (its length is the depth)
_stack: list[str] = []


class _NullSpan:
    """Shared disabled-path span: ``span()`` returns THIS singleton when no
    sink is registered, so the step loop performs one branch and zero
    allocations per instrumented region."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "attrs", "_t0", "_depth", "_annotation")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self._depth = len(_stack)
        _stack.append(self.name)
        self._annotation = _profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = _clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = _clock() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        del _stack[self._depth:]
        if exc_type is not None:
            # exception-safe: the span still records, tagged with the error
            self.attrs = dict(self.attrs, error=exc_type.__name__)
        rec = SpanRecord(self.name, self._t0, dur, self._depth, self.attrs)
        for s in _SINKS:
            s.on_span(rec)
        return False


def span(name: str, **attrs):
    """Timed region: ``with span("engine.prefill", slots=2, tokens=17):``.

    Disabled (no sinks / inside :func:`disabled`): returns the shared
    :data:`NULL_SPAN` singleton — no allocation, no clock read, no
    profiler annotation.
    """
    if not (_ENABLED and _SINKS):
        return NULL_SPAN
    return _Span(name, attrs)


# ---------------------------------------------------------------------------
# Typed counter / gauge registry
# ---------------------------------------------------------------------------

_COUNTERS: dict[tuple, float] = {}
_GAUGES: dict[tuple, float] = {}


def _key(name: str, labels: dict) -> tuple:
    return (name,) + tuple(sorted(labels.items()))


def counter(name: str, value: float = 1, **labels) -> None:
    """Accumulate ``value`` into the counter keyed by ``(name, labels)``
    and emit the running total to every sink.  No-op when disabled —
    counters only count what tracing observed."""
    if not (_ENABLED and _SINKS):
        return
    key = _key(name, labels)
    total = _COUNTERS.get(key, 0) + value
    _COUNTERS[key] = total
    rec = PointRecord("counter", name, _clock(), total, labels)
    for s in _SINKS:
        s.on_point(rec)


def gauge(name: str, value: float, **labels) -> None:
    """Set the last-value metric keyed by ``(name, labels)``."""
    if not (_ENABLED and _SINKS):
        return
    _GAUGES[_key(name, labels)] = value
    rec = PointRecord("gauge", name, _clock(), value, labels)
    for s in _SINKS:
        s.on_point(rec)


def event(name: str, **labels) -> None:
    """Instant mark (the request-lifecycle stream)."""
    if not (_ENABLED and _SINKS):
        return
    rec = PointRecord("event", name, _clock(), 0.0, labels)
    for s in _SINKS:
        s.on_point(rec)


def counter_value(name: str, **labels) -> float:
    """Current accumulated total for one counter key (0 if never hit)."""
    return _COUNTERS.get(_key(name, labels), 0)


def gauge_value(name: str, **labels) -> Optional[float]:
    """Last value set for one gauge key (None if never set)."""
    return _GAUGES.get(_key(name, labels))


def counters_snapshot() -> dict[tuple, float]:
    """Copy of the full counter registry (key = (name, *sorted labels))."""
    return dict(_COUNTERS)


def gauges_snapshot() -> dict[tuple, float]:
    return dict(_GAUGES)


def reset_metrics() -> None:
    """Zero the counter/gauge registries (tests; sinks keep their
    records)."""
    _COUNTERS.clear()
    _GAUGES.clear()


def current_depth() -> int:
    """Live span-nesting depth (0 outside any span) — invariant-checked by
    the nesting/exception-safety property tests."""
    return len(_stack)
