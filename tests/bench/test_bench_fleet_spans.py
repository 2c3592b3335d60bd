"""The readers of the fleet driver's phase spans, the launch split and the
runtime counters: on a synthetic reduction, with nothing to read, and on
the spans of a real traced serve on the CPU."""

import dataclasses

import pytest

from benchmarks.chip import harness
from benchmarks.chip.trace_reduce import Reduced


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float


def synthetic() -> Reduced:
    """Two epochs; each ``schedule_fleet`` is split into its host phases
    and its launches, which hold a dispatch and a sync."""
    spans = [
        Span("epoch", 0.0, 1.0),
        Span("schedule_fleet", 0.1, 0.9),
        Span("fleet_tables", 0.10, 0.12),
        Span("fleet_enumerate", 0.12, 0.40),
        Span("gc", 0.20, 0.30),
        Span("fleet_rounds", 0.40, 0.45),
        Span("fleet_pack", 0.45, 0.46),
        Span("stage1_launch", 0.46, 0.56),
        Span("stage1_dispatch", 0.46, 0.47),
        Span("stage1_sync", 0.47, 0.56),
        Span("fleet_rounds", 0.56, 0.60),
        Span("fleet_pack", 0.60, 0.62),
        Span("stage2_launch", 0.62, 0.82),
        Span("stage2_dispatch", 0.62, 0.64),
        Span("stage2_sync", 0.64, 0.82),
        Span("fleet_finish", 0.82, 0.9),
        Span("epoch", 1.0, 2.0),
        Span("schedule_fleet", 1.0, 1.5),
        Span("fleet_tables", 1.0, 1.01),
        Span("fleet_enumerate", 1.01, 1.2),
        Span("gc", 1.1, 1.101),
        Span("fleet_pack", 1.2, 1.21),
        Span("stage2_launch", 1.21, 1.41),
        Span("stage2_dispatch", 1.21, 1.22),
        Span("stage2_sync", 1.22, 1.41),
        Span("fleet_rounds", 1.41, 1.45),
        Span("fleet_finish", 1.45, 1.5),
    ]
    return Reduced(
        ops=[],
        modules=[],
        host=[],
        spans=spans,
        counters={"xla_compiles": 0.0, "gc_collections": 2.0, "gc_pause_s": 0.101},
        n_epochs=2,
        window_s=2.0,
        stage1_shapes=[],
        peak={},
    )


EXPECTED = {
    "fleet_tables_ms": 1e3 * (0.02 + 0.01) / 2,
    "fleet_enumerate_ms": 1e3 * (0.28 + 0.19) / 2,
    "fleet_rounds_ms": 1e3 * (0.05 + 0.04 + 0.04) / 2,
    "fleet_pack_ms": 1e3 * (0.01 + 0.02 + 0.01) / 2,
    "fleet_finish_ms": 1e3 * (0.08 + 0.05) / 2,
    "launch_dispatch_ms": 1e3 * (0.01 + 0.02 + 0.01) / 2,
    "gc_pause_ms": 1e3 * (0.1 + 0.001) / 2,
    "window_compiles": 0.0,
}
FLEET_PHASES = [n for n in EXPECTED if n.startswith("fleet_")]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_synthetic_spans(name):
    assert harness.metric_reader(name)(synthetic()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_without_its_span_or_counter(name):
    empty = dataclasses.replace(synthetic(), spans=[], counters={})
    assert harness.metric_reader(name)(empty) is None


def test_gc_pause_reads_zero_when_collections_are_counted_and_none_ran():
    red = dataclasses.replace(
        synthetic(),
        spans=[s for s in synthetic().spans if s.name != "gc"],
        counters={"gc_collections": 0.0},
    )
    assert harness.metric_reader("gc_pause_ms")(red) == 0.0


def test_fleet_phases_sum_to_the_fleet_host_remainder():
    red = synthetic()
    phases = sum(harness.metric_reader(n)(red) for n in FLEET_PHASES)
    assert phases == pytest.approx(harness.metric_reader("fleet_host_ms")(red))


def test_readers_on_a_warm_traced_serve():
    """Serve a stream once to warm every program, then again under the
    program's tracer: every reader reports, the phases account for the
    fleet driver's host time, and nothing compiled."""
    from repro.obs import Tracer
    from repro.online import OnlineScheduler, production_arrivals

    def serve(tracer=None):
        svc = OnlineScheduler(
            6, 2, window=5.0, seed=3, tracer=tracer,
            solver_kwargs=dict(max_enumerate=64, n_samples=64, batch_size=64,
                               refine_rounds=1, refine_pool=32),
        )
        return svc.serve(production_arrivals(3, rate=1 / 10, n_jobs=5,
                                             n_racks=6, n_wireless=2))

    serve()
    tr = Tracer()
    res = serve(tr)
    red = dataclasses.replace(
        synthetic(), spans=tr.spans, counters=dict(tr.counters), n_epochs=res.n_epochs
    )
    values = {n: harness.metric_reader(n)(red) for n in EXPECTED}
    assert all(v is not None for v in values.values()), values
    assert values["window_compiles"] == 0.0
    host = harness.metric_reader("fleet_host_ms")(red)
    phases = sum(values[n] for n in FLEET_PHASES)
    # Collections that fall between two phases belong to none of them.
    fleets = {s.index for s in tr.spans_named("schedule_fleet")}
    between = sum(s.duration for s in tr.spans_named("gc") if s.parent in fleets)
    assert 0.9 * host <= phases + 1e3 * between / res.n_epochs <= host * (1 + 1e-9)
