"""Chip smoke run: drive the online fleet scheduler once on a TPU and check it.

Run (on a machine with a TPU; exits non-zero without one):

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # the sharded stage-2 path only

Phases, all in this one process; each raises on a wrong result:

  (a) stage-1 kernel: ``batched_combined_lb`` at B=4096, n_pad=16, plain and
      masked, on rows built from §V production jobs, against the NumPy
      oracle of ``kernels/ref.py``; it must be lowered to a Mosaic kernel
      (``tpu_custom_call``), not run in interpret mode.
  (b) stage-2 evaluator: device makespans of a 4096-row mixed fleet against
      the host reference ``core.simulator.greedy_makespan``.
  (c) fleet solve: ``schedule_fleet`` over 8 production jobs; each result
      equals a host re-simulation of its assignment and is at least
      ``core.bounds.lower_bound``.
  (d) serve: a stream of production arrivals on the 8-rack / 2-subchannel
      cluster through ``OnlineScheduler(policy="fleet")``; every job served
      and the committed timeline channel-feasible.

``--chips 4`` runs instead the stage-2 launch sharded over every local chip
and a fleet solve on all of them, each against the same work on one device.

The last line of stdout is a JSON object naming the device; it is printed
only after every phase passed on a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import unittest.mock
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import vectorized as V  # noqa: E402
from repro.core.bounds import (  # noqa: E402
    contention_lower_bounds,
    lower_bound,
    min_network_durations,
)
from repro.core.instance import ProblemInstance, Topology  # noqa: E402
from repro.core.simulator import (  # noqa: E402
    build_op_tables,
    greedy_makespan,
    simulate,
)
from repro.kernels import cpm  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels.ref import ref_combined_lb  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.online import (  # noqa: E402
    DEFAULT_SOLVER_KWARGS,
    OnlineScheduler,
    production_arrivals,
    stream_production_arrivals,
)

# The stress cluster of benchmarks/online_serving.py.
CLUSTER = dict(n_racks=8, n_wireless=2)
# Arrival rate of the served stream, in jobs per unit of task time: about
# the cluster's service rate (one job per ~50 units), so the queue builds
# past 8 jobs and the epoch mega-batch reaches its serving width (I >= 8
# jobs x 512 rows) without growing to a hundred distinct widths.
SERVE_RATE = 1 / 50


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def assert_mosaic(lowered_text: str) -> None:
    """Stage 1 must run as a compiled Mosaic kernel, not in interpret mode."""
    check(not kops._interpret(), "Pallas would run in interpret mode here")
    check("tpu_custom_call" in lowered_text, "stage 1 is not a Mosaic kernel")


def production_instances(n_jobs: int, seed: int) -> list[ProblemInstance]:
    """The §V production mix's jobs (5-10 tasks) on the stress cluster."""
    return [ev.inst for ev in production_arrivals(seed, 1.0, n_jobs, **CLUSTER)]


def with_random_topology(inst: ProblemInstance, rng) -> ProblemInstance:
    reach = rng.random((inst.n_racks, inst.n_wireless)) < 0.5
    return ProblemInstance(
        job=inst.job,
        n_racks=inst.n_racks,
        n_wireless=inst.n_wireless,
        topology=Topology(reach=reach),
    )


def stage1_rows(inst: ProblemInstance, racks: np.ndarray, n_pad: int, rng):
    """Kernel inputs (w, p, extra, mask) for one job's candidate rows, built
    on the host as the engine builds them on the device."""
    job, B = inst.job, racks.shape[0]
    e0, e1 = job.edges[:, 0], job.edges[:, 1]
    rows = np.arange(B)[:, None]
    same = racks[:, e0] == racks[:, e1]
    net = min_network_durations(inst)
    w = np.full((B, n_pad, n_pad), -np.inf, np.float32)
    w[rows, e0, e1] = np.where(same, inst.r_local, net) + job.p[e0]
    connected = with_random_topology(inst, rng).topology.pair_connected()
    ok = connected[racks[:, e0], racks[:, e1]]
    mask = np.zeros((B, n_pad, n_pad), np.float32)
    mask[rows, e0, e1] = np.where(same | ok, 0.0, np.asarray(inst.q_wired) - net)
    p = np.zeros((B, n_pad), np.float32)
    p[:, : job.n_tasks] = job.p
    extra = contention_lower_bounds(inst, racks).astype(np.float32)
    return w, p, extra, mask


def phase_stage1(
    n_jobs: int = 8, rows_per_job: int = 512, n_pad: int = 16, seed: int = 0
):
    rng = np.random.default_rng(seed)
    parts = []
    for inst in production_instances(n_jobs, seed):
        racks = rng.integers(0, inst.n_racks, (rows_per_job, inst.job.n_tasks))
        parts.append(stage1_rows(inst, racks, n_pad, rng))
    w, p, extra, mask = (np.concatenate(a) for a in zip(*parts))
    B = w.shape[0]
    print(f"(a) stage 1: B={B} n_pad={n_pad} block_rows={cpm.block_rows(B, n_pad)}")
    for name, args in (("plain", (w, p, extra)), ("masked", (w, p, extra, mask))):
        assert_mosaic(jax.jit(kops.batched_combined_lb).lower(*args).as_text())
        got = np.asarray(kops.batched_combined_lb(*args))
        want = ref_combined_lb(*args)
        err = float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30)))
        check(got.shape == (B,) and np.isfinite(got).all(), f"{name}: bad output")
        check(err <= 1e-4, f"{name}: max relative error {err} vs the oracle")
        print(
            f"(a) stage 1 {name}: tpu_custom_call, "
            f"max rel err vs oracle {err:.3g}: PASS"
        )


def stage2_fleet(n_jobs: int, rows_per_job: int, seed: int):
    """A mixed production fleet (odd jobs with a restricted topology), its
    stacked device tables, and one random candidate block per job."""
    rng = np.random.default_rng(seed)
    insts = production_instances(n_jobs, seed)
    insts = [with_random_topology(x, rng) if i % 2 else x for i, x in enumerate(insts)]
    ops = [build_op_tables(x) for x in insts]
    dims = V._fleet_dims(insts, True, ops)
    tables = V._build_eval_stack(insts, dims, True, ops)
    B = n_jobs * rows_per_job
    rack = np.zeros((B, dims.n_pad), np.int32)
    for i, inst in enumerate(insts):
        lo = i * rows_per_job
        rack[lo : lo + rows_per_job, : inst.job.n_tasks] = rng.integers(
            0, inst.n_racks, (rows_per_job, inst.job.n_tasks)
        )
    iid = np.repeat(np.arange(n_jobs, dtype=np.int32), rows_per_job)
    return insts, dims, tables, rack, iid


def evaluator(n_dev: int, dims):
    return V._compiled_evaluator(n_dev, dims.m_pad, dims.M_pad, dims.n_chan)


def phase_stage2(n_jobs: int = 8, rows_per_job: int = 512, seed: int = 1):
    insts, dims, tables, rack, iid = stage2_fleet(n_jobs, rows_per_job, seed)
    fn = evaluator(jax.local_device_count(), dims)
    got = np.asarray(fn(jnp.asarray(rack), jnp.asarray(iid), *tables))
    want = np.asarray(
        [
            greedy_makespan(insts[i], rack[b, : insts[i].job.n_tasks])
            for b, i in enumerate(iid)
        ],
        np.float32,
    )
    n_diff = int(np.sum(got != want))
    check(n_diff == 0, f"{n_diff}/{got.size} device makespans differ from the host")
    print(f"(b) stage 2: {got.size} rows, device == host reference on all: PASS")


def stage1_call(insts: list[ProblemInstance], rows: int):
    """The fleet's size bucket, and arguments of the engine's stage-1
    program for a ``rows``-row launch."""
    dims = V._fleet_dims(insts, True)
    args = (
        jnp.zeros((rows, dims.n_pad), jnp.int32),
        jnp.zeros(rows, jnp.int32),
        *V._build_lb_arrays(insts, dims),
    )
    return dims, args, dict(M_pad=dims.M_pad, n_iters=dims.n_iters, contention=True)


def phase_fleet(n_jobs: int = 8, seed: int = 2, solver_kwargs=None):
    kw = dict(DEFAULT_SOLVER_KWARGS, **(solver_kwargs or {}))
    insts = production_instances(n_jobs, seed)
    dims, args, static = stage1_call(insts, n_jobs * kw["batch_size"])
    assert_mosaic(V._fleet_lb_device.lower(*args, **static).as_text())
    fleet = V.schedule_fleet(insts, seed=seed, use_kernel=True, **kw)
    for j, (inst, res) in enumerate(zip(insts, fleet.results)):
        sim = simulate(inst, res.best_assignment).makespan
        lb = lower_bound(inst)
        check(res.makespan == sim, f"job {j}: makespan {res.makespan} != host {sim}")
        check(res.makespan >= lb - 1e-9, f"job {j}: makespan {res.makespan} < LB {lb}")
        print(
            f"(c) job {j}: {inst.job.n_tasks} tasks, makespan {res.makespan:.3f} "
            f"(LB {lb:.3f}), pruned {res.n_pruned}/{res.n_candidates}"
        )
    check(fleet.n_stage1_launches > 0, "stage 1 never ran")
    print(
        f"(c) fleet: I={n_jobs} x {kw['batch_size']} rows, n_pad={dims.n_pad}, "
        f"{fleet.n_stage1_launches}+{fleet.n_stage2_launches} launches, "
        f"stage 1 tpu_custom_call: PASS"
    )
    return fleet


def phase_serve(
    n_jobs: int = 100,
    rate: float = SERVE_RATE,
    seed: int = 0,
    solver_kwargs=None,
    min_peak_queue: int = 8,
):
    arrivals = stream_production_arrivals(seed, rate, n_jobs, **CLUSTER)
    svc = OnlineScheduler(
        CLUSTER["n_racks"],
        CLUSTER["n_wireless"],
        policy="fleet",
        seed=seed,
        solver_kwargs=solver_kwargs,
    )
    traces0 = V.TRACE_COUNT + V.LB_TRACE_COUNT
    t0 = time.perf_counter()
    res = svc.serve(arrivals)
    wall = time.perf_counter() - t0
    compiles = V.TRACE_COUNT + V.LB_TRACE_COUNT - traces0
    res.timeline.assert_feasible(full=True)
    served = sorted(j.job_id for j in res.jobs)
    check(served == list(range(n_jobs)), f"served {len(served)}/{n_jobs} jobs")
    check(
        res.peak_queue_depth >= min_peak_queue,
        f"peak queue {res.peak_queue_depth} < {min_peak_queue}: batch too narrow",
    )
    print(
        f"(d) serve: {n_jobs} jobs at rate {rate:.4g}, {res.n_epochs} epochs, "
        f"peak queue {res.peak_queue_depth}; every job served, "
        "timeline feasible: PASS"
    )
    # Information only: one cold run, compiles included.
    print(
        f"(d) info: wall {wall:.3f} s, {n_jobs / wall:.3f} jobs/s, "
        f"JCT p50 {res.p50_jct:.3f} p99 {res.p99_jct:.3f}, "
        f"{compiles} stage-1/stage-2 program traces inside the serve"
    )
    return res


def phase_sharded(
    n_jobs: int = 8, rows_per_job: int = 512, seed: int = 3, solver_kwargs=None
):
    """Stage 2 sharded over every local device, and a fleet solve on all of
    them, against the same work pinned to one device."""
    n_dev = jax.local_device_count()
    check(n_dev > 1, f"the sharded path needs several devices, found {n_dev}")
    insts, dims, tables, rack, iid = stage2_fleet(n_jobs, rows_per_job, seed)
    args = (jnp.asarray(rack), jnp.asarray(iid), *tables)
    sharded = evaluator(n_dev, dims)(*args)
    single = evaluator(1, dims)(*args)
    check(len(sharded.sharding.device_set) == n_dev, "stage 2 was not sharded")
    check(len(single.sharding.device_set) == 1, "one-device reference was sharded")
    check(
        np.array_equal(np.asarray(sharded), np.asarray(single)),
        "sharded stage-2 makespans differ from one device",
    )
    print(
        f"(s) stage 2: {rack.shape[0]} rows sharded over {n_dev} devices "
        "== one device: PASS"
    )

    kw = dict(DEFAULT_SOLVER_KWARGS, **(solver_kwargs or {}))
    fleet_insts = production_instances(n_jobs, seed)
    many = V.schedule_fleet(fleet_insts, seed=seed, **kw)
    # The engine sizes its stage-2 mesh from jax.local_device_count().
    with unittest.mock.patch.object(jax, "local_device_count", return_value=1):
        one = V.schedule_fleet(fleet_insts, seed=seed, **kw)
    check(np.array_equal(many.makespans, one.makespans), "fleet makespans differ")
    for j, (a, b) in enumerate(zip(many.results, one.results)):
        check(
            np.array_equal(a.best_assignment, b.best_assignment),
            f"job {j}: assignment differs",
        )
    print(
        f"(s) fleet: {n_jobs} jobs on {n_dev} devices == one device "
        f"(makespans and assignments, seed {seed}): PASS"
    )
    _, args, static = stage1_call(fleet_insts, n_jobs * kw["batch_size"])
    on = sorted(V._fleet_lb_device(*args, **static).sharding.device_set)
    print(f"(s) note: stage 1 is not sharded; it runs whole on {on}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded stage-2 path against one device",
    )
    args = parser.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU (JAX platform {dev.platform!r}); not run",
            file=sys.stderr,
        )
        return 1
    check(len(devices) >= args.chips, f"{args.chips} chips asked, {len(devices)} found")
    cache = enable_compile_cache()
    print(
        f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache}"
    )
    phases = [phase_sharded] if args.chips == 4 else [
        phase_stage1, phase_stage2, phase_fleet, phase_serve,
    ]
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        print(f"{phase.__name__}: {time.perf_counter() - t0:.3f} s")
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
