"""Observability layer: tracer primitives, exporters, the offline
report, and the traced ⇄ untraced serve bit-identity lock.

Four layers:

  1. Tracer units: span nesting/attrs/durations, typed events, job
     marks, the counter/gauge/series registry, and the NullTracer
     contract (shared no-op span context, ``enabled=False``).
  2. Exporters: Chrome ``trace_event`` structure (spans → "X", decisions
     → "i", job lifecycles → async "b"/"n"/"e" on the simulated-time
     pid) with JSON-safe attr coercion, and the Prometheus text
     exposition (counters, labelled gauges, summary quantiles that are
     *omitted* — not zeroed — for empty series).
  3. Serving integration: a traced serve is bit-identical to an
     untraced one on every policy family; the exported commit-stage
     spans reconcile with ``epoch_commit_latency``; decision events
     fire on the admission/arbitration/backfill/compaction branches;
     the solver fleet's spans and counters match ``FleetResult``.
  4. ``StreamingSeries`` edges that the exposition leans on: the
     exact→sketch handoff at ``exact_max``, single-sample quantiles,
     and zero-sample NaN semantics.
  5. The fleet driver's spans (host phases, launches split into dispatch
     and sync) on the tracer and in a profiler trace, and the runtime
     counters: XLA compiles, garbage collections, hooks registered once
     and never by the untraced path.
"""

import gc
import json
import math
import os
import subprocess
import sys
import weakref
from pathlib import Path

import numpy as np
import pytest

from repro.core import ProblemInstance, make_onestage_mapreduce, schedule_fleet
from repro.obs import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    as_tracer,
    chrome_trace_events,
    prometheus_exposition,
    write_chrome_trace,
)
from repro.obs.report import (
    commit_latency_total,
    decision_audit,
    epoch_breakdown,
    job_table,
    load_trace,
    render_report,
)
from repro.online import (
    OnlineScheduler,
    StreamingSeries,
    poisson_arrivals,
    production_arrivals,
    tiered_production_arrivals,
)


def _fingerprint(res):
    return [
        (
            m.job_id, m.admitted, m.completion, m.makespan,
            m.n_racks_granted, m.n_wireless_granted, m.backfilled,
        )
        for m in res.jobs
    ]


# ---------------------------------------------------------------------------
# Layer 1: tracer primitives
# ---------------------------------------------------------------------------


def test_span_nesting_and_attrs():
    tr = Tracer()
    with tr.span("outer", epoch=3) as outer:
        with tr.span("inner") as inner:
            inner.set(rows=7)
    # A garbage collection inside the spans would add a ``gc`` span.
    o, i = [s for s in tr.spans if s.name != "gc"]
    assert [o.name, i.name] == ["outer", "inner"]
    assert (o.depth, o.parent) == (0, -1)
    assert (i.depth, i.parent) == (1, o.index)
    assert i.attrs == {"rows": 7}
    assert o.attrs == {"epoch": 3}
    # Both closed: durations are finite, inner nests inside outer.
    assert 0.0 <= i.duration <= o.duration
    assert outer.duration == o.duration
    assert tr._stack == []
    assert tr.spans_named("inner") == [i]


def test_events_attach_to_enclosing_span():
    tr = Tracer()
    tr.event("orphan", x=1)
    with tr.span("s"):
        tr.event("inside", job_id=5)
    assert tr.events[0].span == -1
    assert tr.events[1].span == tr.spans[0].index
    assert tr.events_of("inside")[0].attrs == {"job_id": 5}


def test_metrics_registry_keys_by_sorted_labels():
    tr = Tracer()
    tr.count("jobs")
    tr.count("jobs", 2.0)
    tr.gauge("slo", 0.5, tier="gold")
    tr.gauge("slo", 0.9, tier="gold")  # latest wins
    tr.observe("lat", 1.0, tenant="a")
    tr.observe("lat", 3.0, tenant="a")
    assert tr.counters["jobs"] == 3.0
    assert tr.gauges[("slo", (("tier", "gold"),))] == 0.9
    s = tr.series[("lat", (("tenant", "a"),))]
    assert (s.count, s.mean) == (2, 2.0)
    adopted = StreamingSeries()
    tr.adopt_series("jct", adopted)
    assert tr.series[("jct", ())] is adopted


def test_null_tracer_is_inert_singleton():
    nt = NullTracer()
    assert not nt.enabled and not NULL_TRACER.enabled
    ctx = nt.span("anything", k=1)
    assert ctx is nt.span("other")  # one shared context, never allocates
    with ctx as c:
        c.set(ignored=True)
        assert c.duration == 0.0
    assert nt.event("e") is None and nt.count("c") is None
    assert nt.job(1, "arrival", 0.0) is None
    assert as_tracer(None) is NULL_TRACER
    tr = Tracer()
    assert as_tracer(tr) is tr


# ---------------------------------------------------------------------------
# Layer 2: exporters
# ---------------------------------------------------------------------------


def test_chrome_trace_structure_and_json_safety():
    tr = Tracer()
    with tr.span("epoch", epoch=0):
        tr.event("fleet_solve", n_candidates=np.int64(12), gain=float("nan"))
    tr.job(7, "arrival", 10.0, family="mapreduce")
    tr.job(7, "admit", 12.5, backfilled=np.bool_(False))
    tr.job(7, "complete", 20.0, makespan=7.5)
    doc = chrome_trace_events(tr)
    json.dumps(doc)  # numpy / NaN attrs must serialize
    by_ph = {}
    for e in doc["traceEvents"]:
        by_ph.setdefault(e["ph"], []).append(e)
    (x,) = [e for e in by_ph["X"] if e["name"] != "gc"]
    assert x["name"] == "epoch" and x["pid"] == 1 and x["tid"] == 0
    assert x["dur"] >= 0.0 and x["ts"] >= 0.0
    (i,) = by_ph["i"]
    assert i["name"] == "fleet_solve" and i["args"]["n_candidates"] == 12
    assert by_ph["b"][0]["ts"] == pytest.approx(10.0 * 1e6)
    assert by_ph["e"][0]["ts"] == pytest.approx(20.0 * 1e6)
    marks = by_ph["b"] + by_ph["n"] + by_ph["e"]
    assert all(m["pid"] == 2 and m["id"] == 7 for m in marks)
    assert {m["args"]["phase"] for m in marks} == {"arrival", "admit", "complete"}


def test_chrome_trace_open_span_gets_zero_duration():
    tr = Tracer()
    tr.span("never_exited")  # deliberately not used as a context manager
    doc = chrome_trace_events(tr)
    (x,) = [e for e in doc["traceEvents"] if e["ph"] == "X" and e["name"] != "gc"]
    assert x["dur"] == 0.0
    json.dumps(doc)


def test_prometheus_exposition_renders_all_kinds():
    tr = Tracer()
    tr.count("serve_epochs", 14)
    tr.gauge("slo_attainment", 0.75, tier="gold")
    tr.gauge("slo_attainment", 1.0, tier="bronze")
    for v in (1.0, 2.0, 3.0, 4.0):
        tr.observe("epoch_latency", v)
    tr.observe("queueing_delay", 9.0, tenant="t0")
    text = prometheus_exposition(tr)
    assert "# TYPE serve_epochs counter\nserve_epochs 14" in text
    assert '# TYPE slo_attainment gauge' in text
    assert 'slo_attainment{tier="gold"} 0.75' in text
    assert 'slo_attainment{tier="bronze"} 1' in text
    assert 'epoch_latency{quantile="0.5"} 2.5' in text
    assert "epoch_latency_count 4" in text
    assert "epoch_latency_sum 10" in text
    assert 'queueing_delay{tenant="t0",quantile="0.99"} 9' in text
    assert 'queueing_delay_sum{tenant="t0"} 9' in text


def test_prometheus_exposition_omits_quantiles_for_empty_series():
    tr = Tracer()
    tr.adopt_series("jct", StreamingSeries())
    text = prometheus_exposition(tr)
    assert "quantile" not in text
    assert "jct_count 0" in text
    assert "jct_sum 0" in text  # sum of nothing is 0, never NaN
    assert "nan" not in text.lower()


# ---------------------------------------------------------------------------
# Layer 3: serving integration
# ---------------------------------------------------------------------------

_CONFIGS = {
    "greedy": dict(policy="greedy_list"),
    "backfill": dict(
        policy="greedy_list", require_full_demand=True, preserve_order=True,
        backfill=True,
    ),
    "edf_search_compact": dict(
        policy="greedy_list", admission="edf", arbitration="search",
        compact_interval=2, admission_control="defer",
    ),
    "fleet": dict(
        solver_kwargs=dict(max_enumerate=64, n_samples=32, batch_size=128,
                           refine_rounds=1, refine_pool=32),
    ),
}


def _stream(name):
    if name == "edf_search_compact":
        return tiered_production_arrivals(3, rate=1 / 6, n_jobs=12,
                                          n_racks=6, n_wireless=2)
    n = 5 if name == "fleet" else 10
    return production_arrivals(3, rate=1 / 10, n_jobs=n, n_racks=6,
                               n_wireless=2)


@pytest.mark.parametrize("name", sorted(_CONFIGS))
def test_traced_serve_is_bit_identical(name):
    kw = _CONFIGS[name]
    base = OnlineScheduler(6, 2, window=5.0, seed=3, **kw).serve(_stream(name))
    tr = Tracer()
    traced = OnlineScheduler(6, 2, window=5.0, seed=3, tracer=tr,
                             **kw).serve(_stream(name))
    assert _fingerprint(traced) == _fingerprint(base)
    assert traced.n_epochs == base.n_epochs
    assert traced.n_backfilled == base.n_backfilled
    # The trace actually recorded the serve.
    assert len(tr.spans_named("epoch")) == base.n_epochs
    arrivals = [m for m in tr.job_marks if m.phase == "arrival"]
    assert len(arrivals) == len(_stream(name))
    completes = [m for m in tr.job_marks if m.phase == "complete"]
    assert len(completes) == base.n_served


def test_explicit_null_tracer_matches_default():
    stream = poisson_arrivals(11, rate=1 / 8, n_jobs=10, n_racks=4,
                              n_wireless=2)
    base = OnlineScheduler(4, 2, window=4.0, policy="greedy_list",
                           seed=11).serve(stream)
    nulled = OnlineScheduler(4, 2, window=4.0, policy="greedy_list",
                             seed=11, tracer=NULL_TRACER).serve(stream)
    assert _fingerprint(nulled) == _fingerprint(base)


def test_traced_serve_decision_events_and_gauges():
    tr = Tracer()
    OnlineScheduler(6, 2, window=5.0, seed=3, tracer=tr,
                    **_CONFIGS["edf_search_compact"]).serve(
        _stream("edf_search_compact"))
    kinds = {e.kind for e in tr.events}
    assert "arbitration_order" in kinds
    assert "timeline_compact" in kinds
    for e in tr.events_of("arbitration_order"):
        assert e.attrs["policy"] == "search"
        assert isinstance(e.attrs["order"], list)
    # End-of-serve metrics landed in the registry.
    assert ("prune_rate", ()) in tr.gauges
    assert tr.counters["serve_epochs"] > 0
    assert any(name == "tenant_queueing_delay"
               for name, _ in tr.series)
    text = prometheus_exposition(tr)
    assert "tenant_queueing_delay_count{tenant=" in text


def test_admission_reorder_event_fires_for_edf():
    tr = Tracer()
    OnlineScheduler(6, 2, window=5.0, seed=3, admission="edf",
                    policy="greedy_list", tracer=tr).serve(
        _stream("edf_search_compact"))
    reorders = tr.events_of("admission_reorder")
    assert reorders and all(e.attrs["policy"] == "edf" for e in reorders)


def test_trace_report_round_trip(tmp_path):
    tr = Tracer()
    res = OnlineScheduler(4, 2, window=4.0, policy="greedy_list", seed=11,
                          track_epoch_latency=True, tracer=tr).serve(
        poisson_arrivals(11, rate=1 / 8, n_jobs=10, n_racks=4, n_wireless=2))
    path = tmp_path / "trace.json"
    write_chrome_trace(tr, path)
    trace = load_trace(path)

    rows = epoch_breakdown(trace)
    assert len(rows) == res.n_epochs
    for r in rows:
        stage_sum = (r["collect_arrivals"] + r["plan_batch"]
                     + r["arbitrate_and_commit"])
        assert stage_sum <= r["total"] + 1e-9

    # Acceptance: span-summed commit latency reconciles with the
    # track_epoch_latency timer within 1% (construction makes it exact
    # up to µs float round-trip).
    tracked = sum(res.epoch_commit_latency)
    assert commit_latency_total(trace) == pytest.approx(tracked, rel=0.01)

    jobs = job_table(trace, top=5)
    assert 0 < len(jobs) <= 5
    jcts = [r["jct"] for r in jobs]
    assert jcts == sorted(jcts, reverse=True)
    for r in jobs:
        assert r["jct"] == pytest.approx(r["complete"] - r["arrival"])
        assert r["queueing_delay"] == pytest.approx(r["admit"] - r["arrival"])
        assert r["channel_queueing"] == pytest.approx(
            r["makespan"] - r["solver_makespan"])

    audit = decision_audit(trace, jobs[0]["job_id"])
    assert [r["kind"] for r in audit][:1] == ["job:arrival"]
    assert {"job:admit", "job:complete"} <= {r["kind"] for r in audit}

    report = render_report(trace, top=3, job=jobs[0]["job_id"])
    assert "per-epoch latency breakdown" in report
    assert "slowest jobs" in report
    assert f"decision audit for job {jobs[0]['job_id']}" in report


def test_trace_report_cli(tmp_path, capsys):
    import sys
    sys.path.insert(0, "tools")
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    tr = Tracer()
    OnlineScheduler(4, 2, window=4.0, policy="greedy_list", seed=11,
                    tracer=tr).serve(
        poisson_arrivals(11, rate=1 / 8, n_jobs=6, n_racks=4, n_wireless=2))
    path = tmp_path / "t.json"
    write_chrome_trace(tr, path)
    assert trace_report.main([str(path), "--top", "2"]) == 0
    out = capsys.readouterr().out
    assert "per-epoch latency breakdown" in out


def test_fleet_solver_spans_match_fleet_result():
    rng = np.random.default_rng(0)
    insts = [
        ProblemInstance(
            job=make_onestage_mapreduce(rng, n_map=3, n_reduce=2, rho=1.0),
            n_racks=2, n_wireless=1,
        )
        for _ in range(3)
    ]
    tr = Tracer()
    fleet = schedule_fleet(insts, max_enumerate=32, n_samples=32,
                           batch_size=64, refine_rounds=1, refine_pool=16,
                           tracer=tr)
    (top,) = tr.spans_named("schedule_fleet")
    assert top.attrs["n_instances"] == 3
    # Tiny instances enumerate exhaustively, so stage 1 may never launch
    # — stage 2 (exact evaluation) always does.
    assert tr.spans_named("stage2_launch")
    assert tr.counters["stage1_launches"] == fleet.n_stage1_launches
    assert tr.counters["stage2_launches"] == fleet.n_stage2_launches
    (ev,) = tr.events_of("fleet_solve")
    assert ev.attrs["n_instances"] == 3
    assert ev.attrs["n_candidates"] == fleet.n_candidates
    assert ev.attrs["n_pruned"] == fleet.n_pruned
    assert ev.attrs["n_evaluated"] == fleet.n_evaluated
    (py,) = tr.events_of("portfolio_yields")
    for name, row in py.attrs["strategies"].items():
        assert set(row) >= {"proposed", "evaluated", "improvement",
                            "yield_per_eval"}


def test_empty_serve_summary_and_exposition():
    tr = Tracer()
    res = OnlineScheduler(4, 2, window=4.0, policy="greedy_list",
                          tracer=tr).serve([])
    assert res.n_served == 0
    assert math.isnan(res.mean_jct) and math.isnan(res.p95_jct)
    text = res.summary()
    assert "n/a" in text and "nan" not in text
    expo = prometheus_exposition(tr)
    assert "nan" not in expo.lower()
    assert "jct_count 0" in expo


def test_all_rejected_serve_renders():
    # Impossible deadlines + reject control: nothing is ever admitted.
    import dataclasses
    stream = [
        dataclasses.replace(ev, deadline=ev.time + 1e-6)
        for ev in production_arrivals(3, rate=1 / 10, n_jobs=4, n_racks=6,
                                      n_wireless=2)
    ]
    tr = Tracer()
    res = OnlineScheduler(6, 2, window=5.0, policy="greedy_list",
                          admission_control="reject", tracer=tr).serve(stream)
    assert res.n_served == 0
    assert len(res.rejected_job_ids) == 4
    assert "n/a" in res.summary()
    assert tr.events_of("deadline_reject") or tr.events_of("deadline_hopeless")
    assert "nan" not in prometheus_exposition(tr).lower()


# ---------------------------------------------------------------------------
# Layer 4: StreamingSeries edges the exposition leans on
# ---------------------------------------------------------------------------


def test_series_exact_to_sketch_boundary():
    rng = np.random.default_rng(7)
    xs = rng.exponential(10.0, size=65)
    s = StreamingSeries(exact_max=64)
    for x in xs[:64]:
        s.push(x)
    # At exactly exact_max the buffer is still alive: quantiles exact.
    assert s._exact is not None
    for p in s.quantiles:
        assert s.quantile(p) == pytest.approx(np.percentile(xs[:64], 100 * p))
    s.push(xs[64])  # 65th observation flips to the P² sketches
    assert s._exact is None and s._sketches is not None
    assert s.count == 65
    for p in s.quantiles:
        exact = np.percentile(xs, 100 * p)
        lo, hi = np.min(xs), np.max(xs)
        est = s.quantile(p)
        assert lo <= est <= hi
        assert abs(est - exact) <= 0.35 * (hi - lo)
    with pytest.raises(KeyError):
        s.quantile(0.123)  # untracked quantile only answerable pre-sketch


def test_series_single_sample_quantiles():
    s = StreamingSeries()
    s.push(42.0)
    assert (s.count, s.mean, s.min, s.max) == (1, 42.0, 42.0, 42.0)
    for p in (0.5, 0.9, 0.99):
        assert s.quantile(p) == 42.0


# ---------------------------------------------------------------------------
# Layer 5: fleet-driver spans and runtime counters
# ---------------------------------------------------------------------------

_FLEET_KW = dict(max_enumerate=64, n_samples=64, batch_size=64,
                 refine_rounds=2, refine_pool=32)
_FLEET_PHASES = ("fleet_tables", "fleet_enumerate", "fleet_rounds",
                 "fleet_pack", "fleet_finish")


def _sampled_fleet():
    """Three instances past ``max_enumerate``: both stages launch."""
    rng = np.random.default_rng(0)
    return [
        ProblemInstance(
            job=make_onestage_mapreduce(rng, n_map=4, n_reduce=3, rho=1.0),
            n_racks=4, n_wireless=2,
        )
        for _ in range(3)
    ]


def _children(tr, span):
    return [s for s in tr.spans if s.parent == span.index and s.name != "gc"]


def test_fleet_launches_split_into_dispatch_then_sync():
    tr = Tracer()
    fleet = schedule_fleet(_sampled_fleet(), tracer=tr, **_FLEET_KW)
    assert fleet.n_stage1_launches > 0 and fleet.n_stage2_launches > 0
    for stage, n in ((1, fleet.n_stage1_launches), (2, fleet.n_stage2_launches)):
        launches = tr.spans_named(f"stage{stage}_launch")
        assert len(launches) == n
        assert len(tr.spans_named(f"stage{stage}_dispatch")) == n
        assert len(tr.spans_named(f"stage{stage}_sync")) == n
        for sp in launches:
            kids = _children(tr, sp)
            assert [k.name for k in kids] == [f"stage{stage}_dispatch",
                                              f"stage{stage}_sync"]
            assert kids[0].t1 <= kids[1].t0
            assert sp.attrs["instances"] == 3
            assert sp.attrs["rows"] == 3 * 64
            assert sp.attrs["n_pad"] >= 7
            if stage == 1:
                assert sp.attrs["n_iters"] == 6


def test_fleet_phases_partition_schedule_fleet():
    tr = Tracer()
    schedule_fleet(_sampled_fleet(), tracer=tr, **_FLEET_KW)
    (top,) = tr.spans_named("schedule_fleet")
    names = [k.name for k in _children(tr, top)]
    for once in ("fleet_tables", "fleet_enumerate", "fleet_finish"):
        assert names.count(once) == 1
    assert names[:2] == ["fleet_tables", "fleet_enumerate"]
    assert names[-1] == "fleet_finish"
    assert set(names) == set(_FLEET_PHASES) | {"stage1_launch", "stage2_launch"}
    # A collection between two phases is a ``gc`` child of its own.
    kids = sorted((s for s in tr.spans if s.parent == top.index), key=lambda s: s.t0)
    for a, b in zip(kids, kids[1:]):
        assert a.t1 <= b.t0  # siblings never overlap
    covered = sum(k.duration for k in kids)
    assert covered <= top.duration
    assert covered >= 0.9 * top.duration


def test_fleet_spans_land_in_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    insts = _sampled_fleet()
    schedule_fleet(insts, **_FLEET_KW)  # compile outside the profile
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        schedule_fleet(insts, tracer=tr, **_FLEET_KW)
    (path,) = Path(tmp_path).rglob("*.xplane.pb")
    host = {
        e.name
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    }
    assert {"schedule_fleet", "fleet_enumerate", "stage2_sync"} <= host


def test_compile_inside_a_span_is_counted_and_named():
    import jax
    import jax.numpy as jnp

    x = jnp.arange(5.0)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    tr = Tracer()
    with tr.span("outer", n_instances=2):
        with tr.span("launch", rows=5):
            f(x).block_until_ready()
    assert tr.counters["xla_compiles"] >= 1
    assert tr.counters["xla_compile_s"] > 0
    ev = tr.events_of("compile")[-1]
    assert ev.attrs["within"] == "launch"
    assert ev.attrs["path"] == "outer/launch"
    assert ev.attrs["attrs"] == {"n_instances": 2, "rows": 5}
    assert ev.span == tr.spans_named("launch")[0].index
    before = dict(tr.counters)
    with tr.span("launch", rows=5):
        f(x).block_until_ready()  # cached: no second executable
    assert tr.counters["xla_compiles"] == before["xla_compiles"]
    assert len(tr.events_of("compile")) == before["xla_compiles"]


def test_gc_inside_a_span_is_a_child_span():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner") as inner:
            gc.collect(2)
    (sp,) = [s for s in tr.spans_named("gc") if s.attrs["generation"] == 2]
    assert sp.parent == inner._span.index and sp.depth == 2
    assert sp.attrs["collected"] >= 0
    assert inner._span.t0 <= sp.t0 <= sp.t1 <= inner._span.t1
    assert tr.counters["gc_collections"] >= 1
    assert tr.counters["gc_pause_s"] >= sp.duration > 0
    # Indices stay positions in the list even with gc spans inserted.
    assert all(s.index == k for k, s in enumerate(tr.spans))
    # Outside every span the tracer records nothing.
    n = tr.counters["gc_collections"]
    gc.collect(2)
    assert tr.counters["gc_collections"] == n


def test_runtime_hooks_registered_once_and_dropped_tracers_forgotten():
    from jax._src import monitoring

    from repro.obs import trace

    a, b = Tracer(), Tracer()
    assert gc.callbacks.count(trace._gc_hook) == 1
    durations = monitoring.get_event_duration_listeners()
    assert durations.count(trace._duration_hook) == 1
    assert monitoring.get_event_listeners().count(trace._event_hook) == 1
    assert a in trace._LIVE and b in trace._LIVE
    ref = weakref.ref(b)
    del b
    assert ref() is None
    assert all(t is not None for t in trace._LIVE)
    assert len([t for t in trace._LIVE if t is a]) == 1


def test_untraced_fleet_serve_registers_no_hook():
    code = (
        "import gc\n"
        "from jax._src import monitoring\n"
        "from repro.obs import trace\n"
        "from repro.online import OnlineScheduler, production_arrivals\n"
        "res = OnlineScheduler(6, 2, window=5.0, seed=3, solver_kwargs=dict("
        "max_enumerate=16, n_samples=16, batch_size=32, refine_rounds=1,"
        " refine_pool=16)).serve(production_arrivals(3, rate=1 / 10,"
        " n_jobs=3, n_racks=6, n_wireless=2))\n"
        "assert res.n_served == 3\n"
        "assert not trace._HOOKED\n"
        "assert trace._gc_hook not in gc.callbacks\n"
        "assert trace._duration_hook not in"
        " monitoring.get_event_duration_listeners()\n"
        "assert trace._event_hook not in monitoring.get_event_listeners()\n"
    )
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root / "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]


def test_exposition_shows_xla_compiles_not_retraces():
    tr = Tracer()
    schedule_fleet(_sampled_fleet(), tracer=tr, **_FLEET_KW)
    text = prometheus_exposition(tr)
    assert "# TYPE xla_compiles counter" in text
    assert "# TYPE gc_collections counter" in text
    assert "compile_cache_misses" not in text
