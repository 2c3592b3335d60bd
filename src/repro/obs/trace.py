"""Structured tracing for the serving loop and solver.

The serving stack makes layered decisions per epoch — admission ordering,
coflow commit-order search, backfill proofs, portfolio budget splits —
and until now each layer only surfaced aggregate counters on
:class:`~repro.online.metrics.OnlineResult`. This module records the
*structure*: nested wall-time spans (epoch → collect/plan/commit), typed
decision events at every admission/arbitration/backfill branch, per-job
lifecycle marks in simulated time, and a small metrics registry
(counters, gauges, :class:`~repro.online.metrics.StreamingSeries`
histograms) that :mod:`repro.obs.export` renders as a Chrome/Perfetto
trace and a Prometheus-style text exposition.

An enabled :class:`Tracer` also puts its spans on the profiler's clock
and watches the Python runtime:

- each span opens a ``jax.profiler.TraceAnnotation`` of its name, so a
  ``jax.profiler.trace`` shows the program's host structure beside the
  device's operations (outside a profiler trace an annotation is a
  no-op);
- every XLA executable built (``/jax/core/compile/backend_compile_duration``,
  a compile or a load from the persistent cache) while one of its spans is
  open counts in ``xla_compiles`` / ``xla_compile_s`` and leaves a
  ``compile`` event naming the open spans; persistent-cache loads count
  in ``compile_cache_hits`` too;
- every garbage collection while one of its spans is open becomes a
  ``gc`` span (attributes ``generation``, ``collected``) under the
  innermost open span, annotated like any other, and counts in
  ``gc_collections`` / ``gc_pause_s``.

The two runtime hooks (``gc.callbacks`` and a ``jax.monitoring``
listener) are registered once per process, by the first enabled tracer,
and reach only tracers still alive. jax is imported lazily, by that
first tracer.

The default is :data:`NULL_TRACER`, whose every method is a no-op and
whose ``span`` returns a shared reusable context manager, so passing
``tracer=None`` anywhere keeps the hot loop bit-identical at negligible
overhead (the stress lane asserts < 2%) and registers no hook.
Instrumented call sites guard any *extra computation* (not just the
record) behind ``tracer.enabled``.
"""

from __future__ import annotations

import dataclasses
import gc
import time
import typing
import weakref

if typing.TYPE_CHECKING:  # pragma: no cover - type-only import
    from repro.online.metrics import StreamingSeries

__all__ = [
    "Event",
    "JobMark",
    "NullTracer",
    "NULL_TRACER",
    "RUNTIME_COUNTERS",
    "Span",
    "Tracer",
    "as_tracer",
]


@dataclasses.dataclass
class Span:
    """One closed (or still-open) wall-time interval.

    ``t0``/``t1`` are seconds relative to the tracer's epoch
    (``Tracer.t0``); ``t1`` is NaN until the span exits. ``parent`` is
    the index of the enclosing span in ``Tracer.spans`` (-1 at top
    level), so the hierarchy is reconstructible offline.
    """

    name: str
    t0: float
    t1: float
    depth: int
    parent: int
    index: int
    attrs: dict

    @property
    def duration(self) -> float:
        """Wall seconds spent inside the span (NaN while open)."""
        return self.t1 - self.t0


@dataclasses.dataclass(frozen=True)
class Event:
    """One typed point-in-time decision record (wall-clock ``t``)."""

    kind: str
    t: float
    span: int
    attrs: dict


@dataclasses.dataclass(frozen=True)
class JobMark:
    """One job-lifecycle phase transition in *simulated* time.

    ``phase`` is one of ``"arrival"`` / ``"admit"`` / ``"complete"``;
    the exporter renders the marks of one ``job_id`` as an async track.
    """

    job_id: int
    phase: str
    t: float
    attrs: dict


class _SpanCtx:
    """Context manager handed out by :meth:`Tracer.span`.

    Reused objects are cheap but spans nest, so each ``span()`` call
    builds a fresh one; the :class:`NullTracer` instead hands out one
    shared no-op instance forever. The span and its profiler annotation
    open in ``span()`` itself and close on exit.
    """

    __slots__ = ("_tracer", "_span", "_note")

    def __init__(self, tracer: "Tracer", span: Span, note):
        self._tracer = tracer
        self._span = span
        self._note = note

    def __enter__(self) -> "_SpanCtx":
        return self

    def __exit__(self, *exc) -> None:
        tr = self._tracer
        self._span.t1 = time.perf_counter() - tr.t0
        tr._stack.pop()
        self._note.__exit__(None, None, None)

    def set(self, **attrs) -> None:
        """Attach attributes discovered while the span is running."""
        self._span.attrs.update(attrs)

    @property
    def duration(self) -> float:
        """Wall seconds of the span (valid after exit; NaN while open)."""
        return self._span.duration


class _NullSpanCtx:
    """The shared no-op span context (singleton via :class:`NullTracer`)."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpanCtx":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs) -> None:
        return None

    @property
    def duration(self) -> float:
        return 0.0


# Counters of the Python runtime that every enabled tracer reports, from 0.
RUNTIME_COUNTERS = (
    "xla_compiles",
    "xla_compile_s",
    "compile_cache_hits",
    "gc_collections",
    "gc_pause_s",
)


class Tracer:
    """Collects spans, events, job marks, and scalar metrics in memory.

    All timestamps are ``time.perf_counter()`` seconds relative to the
    tracer's construction (``t0``), so exported traces start near zero.
    The metrics registry is deliberately tiny: ``counters`` are plain
    monotonically-growing floats, ``gauges`` hold the last value set,
    and ``series`` maps ``(name, labels)`` to a
    :class:`~repro.online.metrics.StreamingSeries` — the same O(1)
    sketch the serving layer already uses — so histogram state stays
    bounded on 100k-job serves. ``counters`` starts with the runtime
    counters (:data:`RUNTIME_COUNTERS`) at 0.
    """

    enabled: bool = True

    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.t0 = time.perf_counter()
        self._spans: list[Span] = []
        self.events: list[Event] = []
        self.job_marks: list[JobMark] = []
        self.counters: dict[str, float] = dict.fromkeys(RUNTIME_COUNTERS, 0.0)
        self.gauges: dict[tuple[str, tuple], float] = {}
        self.series: dict[tuple[str, tuple], StreamingSeries] = {}
        self._stack: list[int] = []
        # A collection in progress: (t0, parent, depth, annotation).
        self._gc_open: tuple | None = None
        # Finished ``gc`` spans not yet numbered into ``spans``.
        self._gc_done: list[Span] = []
        _watch(self)

    # -- spans / events / job marks ------------------------------------

    @property
    def spans(self) -> "list[Span]":
        """Every span in opening order, ``gc`` spans included."""
        self._take_gc()
        return self._spans

    def _take_gc(self) -> None:
        # A gc callback runs between any two bytecodes, so it never
        # touches ``_spans`` or ``_stack``: the spans it finishes wait in
        # ``_gc_done`` until here, where they get their index.
        done = self._gc_done
        while done:
            sp = done.pop(0)
            sp.index = len(self._spans)
            self._spans.append(sp)

    def span(self, name: str, **attrs) -> _SpanCtx:
        """Open a nested wall-time span; use as a context manager."""
        self._take_gc()
        note = self._annotation(name)
        note.__enter__()
        sp = Span(
            name=name,
            t0=time.perf_counter() - self.t0,
            t1=float("nan"),
            depth=len(self._stack),
            parent=self._stack[-1] if self._stack else -1,
            index=len(self._spans),
            attrs=attrs,
        )
        self._spans.append(sp)
        self._stack.append(sp.index)
        return _SpanCtx(self, sp, note)

    def event(self, kind: str, **attrs) -> None:
        """Record a typed decision event at the current wall time."""
        self.events.append(
            Event(
                kind=kind,
                t=time.perf_counter() - self.t0,
                span=self._stack[-1] if self._stack else -1,
                attrs=attrs,
            )
        )

    def job(self, job_id: int, phase: str, sim_time: float, **attrs) -> None:
        """Record a job lifecycle mark at simulated time ``sim_time``."""
        self.job_marks.append(
            JobMark(job_id=int(job_id), phase=phase, t=float(sim_time), attrs=attrs)
        )

    # -- runtime hooks (called through _watch) -------------------------

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            if self._stack:
                note = self._annotation("gc")
                note.__enter__()
                t0 = time.perf_counter() - self.t0
                self._gc_open = (t0, self._stack[-1], len(self._stack), note)
            return
        if self._gc_open is None:
            return
        t0, parent, depth, note = self._gc_open
        self._gc_open = None
        t1 = time.perf_counter() - self.t0
        note.__exit__(None, None, None)
        attrs = {"generation": info["generation"], "collected": info["collected"]}
        self._gc_done.append(Span("gc", t0, t1, depth, parent, -1, attrs))
        self.counters["gc_collections"] += 1
        self.counters["gc_pause_s"] += t1 - t0

    def _on_compile(self, seconds: float, program: str) -> None:
        if not self._stack:
            return
        self.counters["xla_compiles"] += 1
        self.counters["xla_compile_s"] += seconds
        open_spans = [self._spans[i] for i in self._stack]
        attrs: dict = {}
        for sp in open_spans:
            attrs.update(sp.attrs)
        self.event(
            "compile",
            program=program,
            seconds=seconds,
            within=open_spans[-1].name,
            path="/".join(sp.name for sp in open_spans),
            attrs=attrs,
        )

    def _on_cache_hit(self) -> None:
        if self._stack:
            self.counters["compile_cache_hits"] += 1

    # -- metrics registry ----------------------------------------------

    @staticmethod
    def _key(name: str, labels: dict) -> tuple[str, tuple]:
        return name, tuple(sorted(labels.items()))

    def count(self, name: str, inc: float = 1.0) -> None:
        """Increment a monotone counter."""
        self.counters[name] = self.counters.get(name, 0.0) + inc

    def gauge(self, name: str, value: float, **labels) -> None:
        """Set a gauge to its latest value (labelled)."""
        self.gauges[self._key(name, labels)] = float(value)

    def observe(self, name: str, value: float, **labels) -> None:
        """Push one observation into a labelled histogram series."""
        # Local import: repro.online.service/cluster import this module,
        # so a top-level metrics import would cycle through the package
        # __init__ when repro.obs loads first.
        from repro.online.metrics import StreamingSeries

        key = self._key(name, labels)
        s = self.series.get(key)
        if s is None:
            s = self.series[key] = StreamingSeries()
        s.push(value)

    def adopt_series(self, name: str, series: "StreamingSeries", **labels) -> None:
        """Register an existing series (e.g. a per-tenant sketch) by ref."""
        self.series[self._key(name, labels)] = series

    # -- convenience ---------------------------------------------------

    def spans_named(self, name: str) -> "list[Span]":
        return [s for s in self.spans if s.name == name]

    def events_of(self, kind: str) -> "list[Event]":
        return [e for e in self.events if e.kind == kind]


# The enabled tracers still alive; the runtime hooks reach only these.
_LIVE: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_HOOKED = False
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _gc_hook(phase: str, info: dict) -> None:
    for tr in list(_LIVE):
        tr._on_gc(phase, info)


def _duration_hook(event: str, seconds: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        for tr in list(_LIVE):
            tr._on_compile(seconds, str(kw.get("fun_name", "")))


def _event_hook(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        for tr in list(_LIVE):
            tr._on_cache_hit()


def _watch(tracer: Tracer) -> None:
    """Send the runtime's gc and compile events to ``tracer`` while it
    lives; the first call registers the hooks, once per process."""
    global _HOOKED
    if not _HOOKED:
        from jax import monitoring

        gc.callbacks.append(_gc_hook)
        monitoring.register_event_duration_secs_listener(_duration_hook)
        monitoring.register_event_listener(_event_hook)
        _HOOKED = True
    _LIVE.add(tracer)


class NullTracer:
    """No-op tracer: every method returns immediately.

    ``enabled`` is False so call sites can skip computing span/event
    *arguments* entirely; ``span()`` returns one shared context manager
    whose enter/exit do nothing, keeping per-epoch overhead to a couple
    of attribute lookups.
    """

    enabled: bool = False
    _CTX = _NullSpanCtx()

    def span(self, name: str, **attrs) -> _NullSpanCtx:
        return self._CTX

    def event(self, kind: str, **attrs) -> None:
        return None

    def job(self, job_id: int, phase: str, sim_time: float, **attrs) -> None:
        return None

    def count(self, name: str, inc: float = 1.0) -> None:
        return None

    def gauge(self, name: str, value: float, **labels) -> None:
        return None

    def observe(self, name: str, value: float, **labels) -> None:
        return None

    def adopt_series(self, name: str, series: "StreamingSeries", **labels) -> None:
        return None


NULL_TRACER = NullTracer()


def as_tracer(tracer: "Tracer | NullTracer | None") -> "Tracer | NullTracer":
    """Normalize an optional tracer argument (``None`` → the null tracer)."""
    return NULL_TRACER if tracer is None else tracer
