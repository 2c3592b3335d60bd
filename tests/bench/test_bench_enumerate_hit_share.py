"""The reader of the fleet driver's enumeration memo counters: on synthetic
counters, with nothing to read, and on the counters of a real traced serve
on the CPU."""

import dataclasses

import pytest

from benchmarks.chip import harness
from benchmarks.chip.trace_reduce import Reduced


def reduced(counters: dict) -> Reduced:
    return Reduced(
        ops=[],
        modules=[],
        host=[],
        spans=[],
        counters=counters,
        n_epochs=2,
        window_s=2.0,
        stage1_shapes=[],
        peak={},
    )


def read(counters: dict):
    return harness.metric_reader("enumerate_hit_share")(reduced(counters))


@pytest.mark.parametrize(
    "hits,misses,share",
    [(37.0, 3.0, 100.0 * 37 / (37 + 3)), (0.0, 4.0, 0.0), (4.0, 0.0, 100.0)],
)
def test_share_of_memo_hits(hits, misses, share):
    counters = {"enumerate_cache_hits": hits, "enumerate_cache_misses": misses}
    assert read(counters) == pytest.approx(share)


@pytest.mark.parametrize(
    "counters",
    [
        {},
        {"xla_compiles": 0.0, "gc_collections": 2.0, "gc_pause_s": 0.101},
        {"enumerate_cache_hits": 0.0, "enumerate_cache_misses": 0.0},
    ],
    ids=["no_counters", "other_counters", "no_enumeration"],
)
def test_finds_nothing_without_an_enumeration(counters):
    assert read(counters) is None


def test_a_warm_traced_serve_builds_no_enumeration():
    """Serve a stream once to fill the memo, then again under the program's
    tracer: every enumeration of the second serve comes from the memo."""
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
    serve(tr)
    assert tr.counters["enumerate_cache_hits"] > 0
    assert tr.counters["enumerate_cache_misses"] == 0
    assert read(dict(tr.counters)) == 100.0
