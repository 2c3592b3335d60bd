"""The benchmark's frozen stream generator against the program's."""

import numpy as np
import pytest

from benchmarks.chip import harness, streams
from repro.online import stream_production_arrivals

SEEDS = [0, 1, 7, 2**31 + 5]


def frozen(cfg: dict, seed: int, rate: float, n_jobs: int):
    mix = cfg["jobs"]
    return streams.production_stream(
        seed,
        rate,
        n_jobs,
        family_weights=mix["family_weights"],
        rho_palette=mix["rho_palette"],
        n_tasks=tuple(mix["n_tasks"]),
        rack_demand=tuple(mix["rack_demand"]),
        n_wireless=cfg["cluster"]["n_wireless"],
        wireless_demand=(
            None if mix["wireless_demand"] is None else tuple(mix["wireless_demand"])
        ),
    )


def program(cfg: dict, seed: int, rate: float, n_jobs: int):
    mix, cl = cfg["jobs"], cfg["cluster"]
    wd = mix["wireless_demand"]
    return list(
        stream_production_arrivals(
            seed,
            rate,
            n_jobs,
            n_racks=mix["rack_demand"][1],
            n_wireless=cl["n_wireless"],
            min_rack_demand=mix["rack_demand"][0],
            min_wireless_demand=None if wd is None else wd[0],
            wired_rate=cl["wired_rate"],
            wireless_rate=cl["wireless_rate"],
        )
    )


@pytest.mark.parametrize("name", ["prod8", "topo8"])
@pytest.mark.parametrize("seed", SEEDS)
def test_frozen_stream_is_the_programs_bit_for_bit(name, seed):
    cfg = harness.config(name)
    rate = harness.traffic("backlog")["rate"]
    ours, theirs = frozen(cfg, seed, rate, 60), program(cfg, seed, rate, 60)
    assert len(ours) == len(theirs) == 60
    for a, b in zip(ours, theirs):
        assert a.time == b.time and a.job_id == b.job_id and a.family == b.family
        assert a.n_racks == b.inst.n_racks and a.n_wireless == b.inst.n_wireless
        assert np.array_equal(a.p, b.inst.job.p)
        assert np.array_equal(a.edges, b.inst.job.edges)
        assert np.array_equal(a.d, b.inst.job.d)
        assert b.inst.wired_rate == cfg["cluster"]["wired_rate"]
        assert b.inst.wireless_rate == cfg["cluster"]["wireless_rate"]


def canonical(job):
    """A DAG up to the numbering of its tasks and edges."""
    edges = sorted(
        (float(job.p[u]), float(job.p[v]), float(d)) for (u, v), d in zip(job.edges, job.d)
    )
    return (tuple(sorted(job.p.tolist())), tuple(edges), job.n_racks, job.n_wireless)


def test_numbering_streams_renumber_one_pool():
    cfg, tr = harness.config("prod8"), harness.traffic("backlog")
    a = streams.cell_streams(cfg, tr, 3)
    b = streams.cell_streams(cfg, tr, 3)
    c = streams.cell_streams(cfg, tr, 2**31 + 11)
    pool = frozen(cfg, tr["pool_seed"], tr["rate"], tr["n_jobs"])
    assert len(a) == len(c) == tr["streams"] == 2
    for (ea, ja), (eb, jb) in zip(a, b):
        assert ea == eb
        for x, y in zip(ja, jb):
            assert x.time == y.time and np.array_equal(x.p, y.p)
            assert np.array_equal(x.edges, y.edges) and np.array_equal(x.d, y.d)
    engines = {e for e, _ in a + c}
    assert len(engines) == 4
    # Another stream or seed: the same jobs at the same times, numbered otherwise.
    for _e, jobs in a[1:] + c:
        assert any(not np.array_equal(x.p, y.p) for x, y in zip(a[0][1], jobs))
        for x, z in zip(jobs, pool):
            assert x.time == z.time and x.family == z.family and x.job_id == z.job_id
            assert canonical(x) == canonical(z)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_a_numbering_seed_fixes_the_streams_and_the_seed_orders_them(seed):
    cfg = harness.config("prod8")
    tr = dict(harness.traffic("backlog"), streams=6, numbering_seed=5)
    base = streams.cell_streams(cfg, tr, 0)
    runs = [streams.cell_streams(cfg, tr, s) for s in (seed, seed, seed + 1)]
    assert [e for e, _ in runs[0]] == [e for e, _ in runs[1]]

    def key(stream):
        engine, jobs = stream
        return engine, tuple((j.p.tobytes(), j.edges.tobytes(), j.d.tobytes()) for j in jobs)

    # The same streams for every run seed, in an order drawn from it.
    assert len({key(s) for s in base}) == 6
    assert all(sorted(map(key, r)) == sorted(map(key, base)) for r in runs)
    assert [key(s) for s in runs[0]] != [key(s) for s in runs[2]]
    # Without the key the run seed draws the numberings, as before.
    moved = streams.cell_streams(cfg, dict(tr, numbering_seed=None), seed)
    assert not {key(s) for s in moved} & {key(s) for s in base}


def test_unknown_arrival_process_is_refused():
    with pytest.raises(ValueError):
        streams.cell_streams(
            harness.config("prod8"), dict(harness.traffic("backlog"), arrivals="bursty"), 1
        )
