"""What the benchmark hangs on the program from outside, without changing it.

- :class:`LaunchRecorder` counts every stage-1 and stage-2 device launch and
  keeps the inputs and outputs of the launches it is told to keep, with the
  fleet's instances, so that the reference can answer the same questions
  once the window has closed. It wraps three module attributes of
  ``repro.core.vectorized`` (``_run_fleet``, ``_fleet_lb_device`` and
  ``_compiled_evaluator``) and keeps device arrays by reference: it adds no
  copy and no sync to the timed path.
- :class:`MatchingLog` keeps, for each serve's timeline, every change of its
  usable wireless links (configured by the matching and physically up) with
  the epoch at which it took effect, for the audit of the link guarantees.
  It wraps ``OnlineScheduler._epoch_topology``, the one place where a serve
  moves its link state (outages, then the epoch's re-matching), which runs
  only under a cluster topology: serves without one never reach it.
- :class:`EpochClock` is a tracer that stays disabled, so the program takes
  its untraced path, and keeps the start and end of each ``epoch`` span.
- :class:`AnnotatedTracer` is the program's own tracer that also opens a
  ``jax.profiler.TraceAnnotation`` for each span, so host spans and device
  operations share the profiler's clock.
"""

from __future__ import annotations

import dataclasses
import time
import weakref

import jax
import numpy as np

from repro.core import vectorized as V
from repro.obs.trace import NullTracer, Tracer
from repro.online.service import OnlineScheduler

__all__ = ["Launch", "LaunchRecorder", "MatchingLog", "EpochClock", "AnnotatedTracer"]


@dataclasses.dataclass
class Launch:
    """One kept device launch: the fleet it served and what went in and out."""

    stage: int  # 1 or 2
    instances: list
    racks: jax.Array  # int32[B, n_pad]
    inst_id: jax.Array  # int32[B]
    out: jax.Array  # float32[B]


class LaunchRecorder:
    """Counts launches per stage; keeps those whose index is in ``keep``."""

    def __init__(self):
        self.counts = {1: 0, 2: 0}
        self.keep: dict[int, set[int]] = {1: set(), 2: set()}
        self.kept: list[Launch] = []
        self.shapes: list[tuple] = []  # stage-1 (B, n_pad, n_iters, masked)
        self._fleet: list | None = None
        self._orig = None

    def __enter__(self) -> "LaunchRecorder":
        self._orig = (V._run_fleet, V._fleet_lb_device, V._compiled_evaluator)
        run_fleet, lb_device, compiled_evaluator = self._orig

        def run_fleet_hook(instances, **kw):
            self._fleet = instances
            return run_fleet(instances, **kw)

        def lb_hook(racks, inst_id, *args, **kw):
            out = lb_device(racks, inst_id, *args, **kw)
            masked = len(args) > 8  # pair_ok and uplift follow the 8 tables
            self.shapes.append((racks.shape[0], racks.shape[1], kw["n_iters"], masked))
            self._count(1, racks, inst_id, out)
            return out

        def evaluator_hook(*key):
            fn = compiled_evaluator(*key)

            def evaluate(racks, inst_id, *tables):
                out = fn(racks, inst_id, *tables)
                self._count(2, racks, inst_id, out)
                return out

            return evaluate

        V._run_fleet, V._fleet_lb_device = run_fleet_hook, lb_hook
        V._compiled_evaluator = evaluator_hook
        return self

    def __exit__(self, *exc) -> None:
        V._run_fleet, V._fleet_lb_device, V._compiled_evaluator = self._orig

    def _count(self, stage, racks, inst_id, out):
        k = self.counts[stage]
        self.counts[stage] = k + 1
        if k in self.keep[stage]:
            self.kept.append(Launch(stage, self._fleet, racks, inst_id, out))

    def restart(self, keep1=(), keep2=()) -> None:
        """Forget what was counted and kept; keep the launches of these
        indices next."""
        self.counts = {1: 0, 2: 0}
        self.keep = {1: set(keep1), 2: set(keep2)}
        self.kept, self.shapes = [], []


def _usable(timeline) -> np.ndarray:
    return timeline.matching & timeline.link_state


class MatchingLog:
    """Every change of each serve's usable wireless links, by its timeline."""

    def __init__(self):
        self._logs: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()
        self._orig = None

    def __enter__(self) -> "MatchingLog":
        self._orig = epoch_topology = OnlineScheduler._epoch_topology
        logs = self._logs

        def epoch_topology_hook(svc, t, st):
            log = logs.get(st.cluster)
            if log is None:
                # The links the timeline starts with, in force until now.
                log = logs[st.cluster] = [(-np.inf, _usable(st.cluster))]
            epoch_topology(svc, t, st)
            now = _usable(st.cluster)
            if not np.array_equal(now, log[-1][1]):
                log.append((float(t), now))

        OnlineScheduler._epoch_topology = epoch_topology_hook
        return self

    def __exit__(self, *exc) -> None:
        OnlineScheduler._epoch_topology = self._orig

    def of(self, timeline) -> tuple[np.ndarray, np.ndarray]:
        """``(times float64[L], masks bool[L, n_racks, n_wireless])``: mask
        ``i`` is in force from ``times[i]`` until ``times[i + 1]``, the first
        from ``-inf``; empty for a timeline without a cluster topology."""
        log = self._logs.get(timeline, [])
        times = np.array([t for t, _ in log], np.float64)
        shape = (len(log), timeline.n_racks, timeline.n_wireless)
        return times, np.array([m for _, m in log], bool).reshape(shape)


class _EpochCtx:
    __slots__ = ("clock", "t0")

    def __init__(self, clock: "EpochClock"):
        self.clock = clock
        self.t0 = 0.0

    def __enter__(self) -> "_EpochCtx":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.clock.epochs.append(time.perf_counter() - self.t0)

    def set(self, **attrs) -> None:
        return None

    @property
    def duration(self) -> float:
        return 0.0


class EpochClock(NullTracer):
    """A disabled tracer that times each ``epoch`` span and nothing else."""

    enabled = False

    def __init__(self):
        self.epochs: list[float] = []

    def span(self, name: str, **attrs):
        if name == "epoch":
            return _EpochCtx(self)
        return self._CTX


class _AnnotatedCtx:
    __slots__ = ("inner", "note")

    def __init__(self, inner, note):
        self.inner = inner
        self.note = note

    def __enter__(self):
        self.note.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self.inner.__exit__(*exc)
        self.note.__exit__(*exc)

    def set(self, **attrs) -> None:
        self.inner.set(**attrs)

    @property
    def duration(self) -> float:
        return self.inner.duration


class AnnotatedTracer(Tracer):
    """The program's tracer, with each span also a profiler annotation."""

    def span(self, name: str, **attrs):
        return _AnnotatedCtx(super().span(name, **attrs), jax.profiler.TraceAnnotation(name))
