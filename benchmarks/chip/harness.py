"""Find a cell's parts by name and build what one run of it serves.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; each
lives in a file of its own (``configs/<name>.json``, ``traffic/<name>.json``),
and each per-layer metric in a reader of its own (``metrics/<name>.py``). A
new cell, mix or metric is a new file and a new entry, never an edit here.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

import numpy as np

from benchmarks.chip import streams

__all__ = [
    "CHIP_DIR",
    "REPO",
    "load_benchmark",
    "cell",
    "config",
    "traffic",
    "metric_reader",
    "arrivals",
    "scheduler",
]

CHIP_DIR = Path(__file__).resolve().parent
REPO = CHIP_DIR.parents[1]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")


def _checked(kind: str, name: str) -> str:
    if not NAME.fullmatch(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return name


def _json(kind: str, folder: str, name: str, root: Path) -> dict:
    path = root / folder / f"{_checked(kind, name)}.json"
    if not path.is_file():
        raise ValueError(f"unknown {kind} {name!r}: no {path.name} in {path.parent}")
    return json.loads(path.read_text())


def load_benchmark(path: Path = REPO / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def cell(name: str, bench: dict) -> dict:
    """The ``workloads`` entry called ``name``."""
    for entry in bench["workloads"]:
        if entry["name"] == name:
            return entry
    raise ValueError(f"unknown workload {name!r}")


def config(name: str, root: Path = CHIP_DIR) -> dict:
    return _json("config", "configs", name, root)


def traffic(name: str, root: Path = CHIP_DIR) -> dict:
    return _json("traffic", "traffic", name, root)


def metric_reader(name: str, root: Path = CHIP_DIR):
    """The ``read(reduced)`` function of ``metrics/<name>.py``."""
    path = root / "metrics" / f"{_checked('metric', name)}.py"
    if not path.is_file():
        raise ValueError(f"unknown metric {name!r}: no {path.name} in {path.parent}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def arrivals(cfg: dict, jobs: list[streams.Job]):
    """The program's arrival events for ``jobs`` on ``cfg``'s cluster."""
    from repro.core.dag import DagJob
    from repro.core.instance import ProblemInstance
    from repro.online.workload import ArrivalEvent

    cl = cfg["cluster"]
    return [
        ArrivalEvent(
            time=job.time,
            inst=ProblemInstance(
                job=DagJob(p=job.p, edges=job.edges, d=job.d, name=job.family),
                n_racks=job.n_racks,
                n_wireless=job.n_wireless,
                wired_rate=cl["wired_rate"],
                wireless_rate=cl["wireless_rate"],
            ),
            job_id=job.job_id,
            family=job.family,
        )
        for job in jobs
    ]


def scheduler(cfg: dict, seed: int, tracer=None):
    """A fresh ``OnlineScheduler`` as ``cfg`` states it."""
    from repro.core.instance import Topology
    from repro.online import OnlineScheduler

    cl, sch, topo = cfg["cluster"], cfg["scheduler"], cfg["topology"]
    kw = {}
    if topo is not None:
        kw = dict(
            topology=topo["policy"],
            cluster_topology=Topology(
                reach=np.asarray(topo["reach"], dtype=bool),
                degree=topo["degree"],
                channel_degree=topo["channel_degree"],
                delta=topo["delta"],
            ),
        )
    return OnlineScheduler(
        cl["n_racks"],
        cl["n_wireless"],
        policy=sch["policy"],
        window=sch["window"],
        admission=sch["admission"],
        arbitration=sch["arbitration"],
        seed=seed,
        solver_kwargs=dict(cfg["solver"]),
        tracer=tracer,
        **kw,
    )
