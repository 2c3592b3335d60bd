"""Arrival streams of the chip benchmark, made from a seed.

A frozen copy of the §V production generator
(``repro.online.workload.stream_production_arrivals``) and of the three DAG
family makers of ``repro.core.dag``, kept here so that a change to the
program's generators cannot move the benchmark's inputs. It draws the same
random numbers in the same order, so for equal parameters it yields the
program's stream bit for bit (``tests/bench/test_bench_streams.py``).

Jobs are plain :class:`Job` records of NumPy arrays; the harness turns them
into the program's own input types.

:func:`cell_streams` makes a run's streams from its seed. Every seed offers
the same jobs, sizes and arrival times, a fixed pool drawn once from the
traffic file's ``pool_seed``: served at the backlog rate, a freshly drawn
stream or a reshuffled arrival order moves the solver's work per serve by a
factor of 3 to 5 from seed to seed, and the benchmark's rates with it. The
seed draws a new numbering of each job's tasks and edges, which changes the
greedy order of every stage-2 score and the rows of both stages, and the
engine's seed. A run serves several such streams so that its work is an
average over them.

The numbering still moves the work by some percent from seed to seed. A
traffic file that names a ``numbering_seed`` draws its streams' numberings
and engine seeds from that instead: every run seed then serves the same
streams, and the run seed only orders them.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Job", "production_stream", "relabel", "cell_streams"]


@dataclasses.dataclass(frozen=True)
class Job:
    """One arrival: a DAG job and the resource shape it asks for."""

    time: float
    job_id: int
    family: str
    p: np.ndarray  # float64[n_tasks] task durations
    edges: np.ndarray  # int64[n_edges, 2] (u, v) dependencies
    d: np.ndarray  # float64[n_edges] data sizes
    n_racks: int  # racks demanded
    n_wireless: int  # wireless subchannels demanded


def _scale_data_sizes(p, d_raw, rho: float, rate: float = 1.0):
    if d_raw.size == 0:
        return d_raw
    mean_transfer = float(np.mean(d_raw)) / rate
    target = rho * float(np.mean(p))
    if mean_transfer <= 0:
        return np.full_like(d_raw, target * rate)
    return d_raw * (target / mean_transfer)


def _simple_mapreduce(rng, n_map: int, rho: float):
    n = n_map + 1
    p = rng.uniform(1.0, 100.0, size=n)
    edges = np.stack([np.arange(n_map), np.full(n_map, n_map)], axis=1)
    d = rng.uniform(0.5, 1.5, size=n_map)
    return p, edges.astype(np.int64), _scale_data_sizes(p, d, rho)


def _onestage_mapreduce(rng, n_map: int, n_reduce: int, rho: float):
    n = n_map + n_reduce
    p = rng.uniform(1.0, 100.0, size=n)
    us, vs = np.meshgrid(np.arange(n_map), np.arange(n_map, n), indexing="ij")
    edges = np.stack([us.ravel(), vs.ravel()], axis=1).astype(np.int64)
    d = rng.uniform(0.5, 1.5, size=edges.shape[0])
    return p, edges, _scale_data_sizes(p, d, rho)


def _random_workflow(rng, n_tasks: int, rho: float, edge_prob: float = 0.3):
    p = rng.uniform(1.0, 100.0, size=n_tasks)
    pairs = [
        (u, v)
        for u in range(n_tasks)
        for v in range(u + 1, n_tasks)
        if rng.uniform() < edge_prob
    ]
    if not pairs and n_tasks > 1:
        pairs = [(0, n_tasks - 1)]
    edges = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
    d = rng.uniform(0.5, 1.5, size=edges.shape[0])
    return p, edges, _scale_data_sizes(p, d, rho)


def _family_job(rng, family: str, n_tasks: int, rho: float):
    if family == "simple_mapreduce":
        return _simple_mapreduce(rng, max(1, n_tasks - 1), rho)
    if family == "onestage_mapreduce":
        n_map = max(1, n_tasks // 2)
        return _onestage_mapreduce(rng, n_map, max(1, n_tasks - n_map), rho)
    if family == "random_workflow":
        return _random_workflow(rng, n_tasks, rho)
    raise ValueError(f"unknown family {family!r}")


def production_stream(
    seed: int,
    rate: float,
    n_jobs: int,
    *,
    family_weights: dict,
    rho_palette,
    n_tasks: tuple[int, int],
    rack_demand: tuple[int, int],
    n_wireless: int,
    wireless_demand: tuple[int, int] | None,
) -> list[Job]:
    """The §V production mix as Poisson arrivals at ``rate``.

    ``n_tasks`` and ``rack_demand`` are inclusive ranges drawn uniformly;
    ``wireless_demand`` is an inclusive range, or ``None`` for the full
    ``n_wireless`` (which draws nothing).
    """
    rng = np.random.default_rng(seed)
    fam_names = tuple(family_weights)
    fam_p = np.asarray([family_weights[f] for f in fam_names], dtype=np.float64)
    fam_p = fam_p / fam_p.sum()
    rho_vals = np.asarray([v for v, _ in rho_palette])
    rho_p = np.asarray([w for _, w in rho_palette], dtype=np.float64)
    rho_p = rho_p / rho_p.sum()
    t = 0.0
    jobs = []
    for j in range(n_jobs):
        t += float(rng.exponential(1.0 / rate))
        family = str(fam_names[int(rng.choice(len(fam_names), p=fam_p))])
        rho = float(rho_vals[int(rng.choice(len(rho_vals), p=rho_p))])
        n = int(rng.integers(n_tasks[0], n_tasks[1] + 1))
        p, edges, d = _family_job(rng, family, n, rho)
        demand = int(rng.integers(rack_demand[0], rack_demand[1] + 1))
        demand_w = (
            n_wireless
            if wireless_demand is None
            else int(rng.integers(wireless_demand[0], wireless_demand[1] + 1))
        )
        jobs.append(Job(t, j, family, p, edges, d, demand, demand_w))
    return jobs


def relabel(job: Job, rng: np.random.Generator) -> Job:
    """The same DAG with its tasks and its edges numbered anew: task ``i``
    becomes ``order[i]``, and the edge list is listed in another order."""
    n, m = job.p.shape[0], job.edges.shape[0]
    order = rng.permutation(n)
    p = np.empty_like(job.p)
    p[order] = job.p
    e_order = rng.permutation(m)
    edges = order[job.edges[e_order]].astype(np.int64).reshape(-1, 2)
    return dataclasses.replace(job, p=p, edges=edges, d=job.d[e_order])


def cell_streams(config: dict, traffic: dict, seed: int) -> list[tuple[int, list[Job]]]:
    """The streams one run of a cell serves, as ``(engine seed, jobs)``.

    Each of the traffic's ``streams`` streams is its fixed pool of jobs at
    their fixed arrival times, each job's tasks and edges numbered in an
    order drawn from ``(seed, k)``, with an engine seed drawn from the same.
    Where the traffic names a ``numbering_seed``, that takes the run seed's
    place there, and the streams come in an order drawn from the run seed.
    """
    if traffic["arrivals"] != "poisson_pool":
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    mix = config["jobs"]
    pool = production_stream(
        traffic["pool_seed"],
        traffic["rate"],
        traffic["n_jobs"],
        family_weights=mix["family_weights"],
        rho_palette=mix["rho_palette"],
        n_tasks=tuple(mix["n_tasks"]),
        rack_demand=tuple(mix["rack_demand"]),
        n_wireless=config["cluster"]["n_wireless"],
        wireless_demand=(
            None if mix["wireless_demand"] is None else tuple(mix["wireless_demand"])
        ),
    )
    fixed = traffic.get("numbering_seed")
    out = []
    for k in range(traffic["streams"]):
        rng = np.random.default_rng([seed if fixed is None else fixed, k])
        engine_seed = int(rng.integers(2**31))
        out.append((engine_seed, [relabel(j, rng) for j in pool]))
    if fixed is not None:
        out = [out[i] for i in np.random.default_rng(seed).permutation(len(out))]
    return out
