"""JAX-vectorized assignment search: score validity, LB soundness, and the
fleet mega-batch contracts (bit-for-bit solo equivalence, prune-rate
regression, one-launch/one-trace compile accounting)."""

import numpy as np
import pytest

from repro.core import ProblemInstance, check_feasible, random_job, solve_bnb
from repro.core.vectorized import (
    batched_lower_bound,
    enumerate_assignments,
    make_batched_evaluator,
    schedule_fleet,
    vectorized_search,
)


def make_instance(seed, n_tasks=5, n_racks=3, n_wireless=1):
    rng = np.random.default_rng(seed)
    job = random_job(rng, None, n_tasks=n_tasks, rho=1.0)
    return ProblemInstance(job=job, n_racks=n_racks, n_wireless=n_wireless)


def test_enumerate_assignments_canonical():
    a = enumerate_assignments(4, 3)
    # Bell-ish count for restricted growth strings capped at 3 racks: 14
    assert a.shape == (14, 4)
    assert (a[:, 0] == 0).all()  # first task always opens rack 0
    # canonical: each new label is at most 1 + max of previous labels
    for row in a:
        mx = 0
        for x in row:
            assert x <= mx + 1
            mx = max(mx, x)


def _restricted_growth_count(n, max_racks):
    """Set partitions of n tasks into at most max_racks blocks (Stirling
    numbers of the second kind, summed)."""
    row = [1]  # S(0, k) for k = 0..
    for _ in range(n):
        row = [0] + [k * (row[k] if k < len(row) else 0) + row[k - 1]
                     for k in range(1, len(row) + 1)]
    return sum(row[1 : max_racks + 1])


ENUMERATION_KEYS = [
    (n, m, limit) for n in range(1, 11) for m in range(1, 9) for limit in (512, 2001)
] + [(n, m, None) for n in range(1, 9) for m in range(1, 9)]


@pytest.mark.parametrize("n,max_racks,limit", ENUMERATION_KEYS)
def test_enumeration_memo_equals_an_uncached_build(n, max_racks, limit):
    got = enumerate_assignments(n, max_racks, limit=limit)
    fresh = enumerate_assignments.__wrapped__(n, max_racks, limit=limit)
    assert got is not fresh
    np.testing.assert_array_equal(got, fresh)
    assert got.dtype == fresh.dtype == np.int32
    assert got.shape == fresh.shape
    count = _restricted_growth_count(n, max_racks)
    assert got.shape == (count if limit is None else min(count, limit), n)
    assert enumerate_assignments(n, max_racks, limit=limit) is got
    with pytest.raises(ValueError):
        got[0, 0] = 1


def test_fleet_is_the_same_with_a_cold_and_a_warm_enumeration_memo():
    """A fleet of one enumerated and one sampled job (with a seed pool)
    solves identically whether the memo is empty or already holds every
    enumeration, and its counters say which it was."""
    from repro.obs import Tracer

    insts = [make_instance(0, n_tasks=5, n_racks=3), make_instance(1, n_tasks=8, n_racks=8)]
    pool = np.random.default_rng(2).integers(0, 8, size=(6, 8))
    kw = dict(max_enumerate=200, n_samples=256, batch_size=128, refine_rounds=2,
              refine_pool=64, strategies="portfolio", seed_pools=[None, pool], seed=[5, 6])

    enumerate_assignments.cache_clear()
    cold_tr, warm_tr = Tracer(), Tracer()
    cold = schedule_fleet(insts, tracer=cold_tr, **kw)
    warm = schedule_fleet(insts, tracer=warm_tr, **kw)
    untraced = schedule_fleet(insts, **kw)
    assert cold.results[1].n_candidates > 200  # the second job is sampled
    for other in (warm, untraced):
        for a, b in zip(cold.results, other.results):
            np.testing.assert_array_equal(a.best_assignment, b.best_assignment)
            assert a.makespan == b.makespan
            assert a.n_candidates == b.n_candidates
            assert a.n_pruned == b.n_pruned
            assert a.strategy_stats == b.strategy_stats
    assert cold_tr.counters["enumerate_cache_misses"] > 0
    assert warm_tr.counters["enumerate_cache_hits"] > 0
    assert warm_tr.counters["enumerate_cache_misses"] == 0


@pytest.mark.parametrize(
    "case", ["wireless", "wired_only", "topology", "edgeless", "nine_tasks"]
)
def test_evaluator_equals_host_greedy_reference(case):
    """Stage-2 device scores equal the plain host restatement of their
    semantics (op-table order, append-only resources, f32) exactly."""
    from repro.core.instance import Topology
    from repro.core.simulator import greedy_makespan

    rng = np.random.default_rng(7)
    n_tasks = {"edgeless": 1, "nine_tasks": 9}.get(case, 7)
    inst = make_instance(3, n_tasks=n_tasks, n_racks=4, n_wireless=2)
    if case == "topology":
        inst = ProblemInstance(
            job=inst.job, n_racks=4, n_wireless=2,
            topology=Topology(reach=np.array([[1, 0], [0, 1], [1, 1], [0, 0]], bool)),
        )
    use_wireless = case != "wired_only"
    racks = rng.integers(0, 4, (64, inst.job.n_tasks)).astype(np.int32)
    dev = np.asarray(make_batched_evaluator(inst, use_wireless=use_wireless)(racks))
    host = [greedy_makespan(inst, r, use_wireless=use_wireless) for r in racks]
    np.testing.assert_array_equal(dev, np.asarray(host, np.float32))


@pytest.mark.parametrize("use_wireless", [True, False], ids=["wireless", "wired_only"])
@pytest.mark.parametrize("seed", range(2))
def test_fleet_launch_equals_host_greedy_reference(seed, use_wireless):
    """One stage-2 launch over a fleet that mixes jobs of the op buckets 16,
    32 and 64, clusters of 3 and 8 racks (M_pad 4 and 8), 1 and 2 wireless
    subchannels and a topology, rows interleaved and padded: every row's
    score equals the plain host restatement exactly."""
    import jax.numpy as jnp

    from repro.core import vectorized as V
    from repro.core.instance import Topology
    from repro.core.simulator import greedy_makespan

    rng = np.random.default_rng(seed)
    jobs = [
        ("simple_mapreduce", 8), ("random_workflow", 9),
        ("onestage_mapreduce", 10), ("random_workflow", 1),
        ("onestage_mapreduce", 6),
    ]
    fleet = []
    for k, (family, n) in enumerate(jobs):
        job = random_job(rng, family, n_tasks=n, rho=1.0)
        n_racks, n_wireless = (3, 1) if k % 2 else (8, 2)
        fleet.append(ProblemInstance(job=job, n_racks=n_racks, n_wireless=n_wireless))
    reach = rng.random((8, 2)) < 0.5
    fleet.append(ProblemInstance(
        job=random_job(rng, "random_workflow", n_tasks=7, rho=1.0),
        n_racks=8, n_wireless=2, topology=Topology(reach=reach),
    ))
    ops = [V.build_op_tables(x) for x in fleet]
    assert {V._bucket(o.n_ops) for o in ops} >= {16, 32, 64}
    dims = V._fleet_dims(fleet, use_wireless, ops)
    tables = V._build_eval_stack(fleet, dims, use_wireless, ops)

    counts = [37, 53, 29, 11, 41, 61]
    inst_id = rng.permutation(np.repeat(np.arange(len(fleet)), counts))
    B = 256  # the rows past sum(counts) are padding: instance 0, racks 0
    iid = np.zeros(B, np.int32)
    iid[: inst_id.size] = inst_id
    racks = np.zeros((B, dims.n_pad), np.int32)
    for b in range(inst_id.size):
        inst = fleet[iid[b]]
        racks[b, : inst.job.n_tasks] = rng.integers(0, inst.n_racks, inst.job.n_tasks)
    fn = V._compiled_evaluator(1, dims.m_pad, dims.M_pad, dims.n_chan)
    dev = np.asarray(fn(jnp.asarray(racks), jnp.asarray(iid), *tables))
    host = [
        greedy_makespan(fleet[i], r[: fleet[i].job.n_tasks], use_wireless=use_wireless)
        for i, r in zip(iid, racks)
    ]
    np.testing.assert_array_equal(dev, np.asarray(host, np.float32))


@pytest.mark.parametrize("seed", range(4))
def test_vectorized_score_upper_bounds_optimum(seed):
    inst = make_instance(seed)
    res = vectorized_search(inst)
    check_feasible(inst, res.schedule)
    opt = solve_bnb(inst, time_limit=30)
    assert res.makespan >= opt.makespan - 0.15
    # the exhaustive-canonical search with greedy sequencing is usually tight
    assert res.makespan <= opt.makespan * 1.5 + 1e-6


@pytest.mark.parametrize("seed", range(4))
def test_batched_lower_bound_sound(seed):
    inst = make_instance(seed)
    cands = enumerate_assignments(inst.job.n_tasks, inst.n_racks)
    lbs = batched_lower_bound(inst, cands)
    evaluate = make_batched_evaluator(inst)
    import jax.numpy as jnp

    scores = np.asarray(evaluate(jnp.asarray(cands)))
    # LB per assignment must not exceed the greedy score of that assignment.
    assert (lbs <= scores + 1e-3).all()


def test_batched_lb_matches_kernel_path(seed=0):
    inst = make_instance(seed)
    cands = enumerate_assignments(inst.job.n_tasks, inst.n_racks)
    a = batched_lower_bound(inst, cands, use_kernel=False)
    b = batched_lower_bound(inst, cands, use_kernel=True)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("seed", range(3))
def test_lb_opt_greedy_sandwich(seed, use_kernel):
    """min LB <= exact optimum <= vectorized greedy score, on both LB paths."""
    inst = make_instance(seed, n_tasks=5, n_racks=3)
    cands = enumerate_assignments(inst.job.n_tasks, inst.n_racks)
    lbs = batched_lower_bound(inst, cands, use_kernel=use_kernel)
    opt = solve_bnb(inst, time_limit=30)
    res = vectorized_search(inst, use_kernel=use_kernel)
    assert float(lbs.min()) <= opt.makespan + 1e-3
    assert opt.makespan <= res.makespan + 0.15
    # per-assignment: LB never exceeds that assignment's greedy score
    evaluate = make_batched_evaluator(inst)
    scores = np.asarray(evaluate(cands))
    assert (lbs <= scores + 1e-3).all()


def test_lb_pruning_is_exact_and_counted():
    """Pruned search returns the same winner as the unpruned sweep, and the
    candidate accounting (evaluated + pruned = considered) holds."""
    inst = make_instance(1, n_tasks=7, n_racks=4)
    pruned = vectorized_search(inst, batch_size=64)
    full = vectorized_search(inst, batch_size=64, lb_prune=False)
    assert pruned.makespan == pytest.approx(full.makespan, abs=1e-6)
    assert pruned.n_evaluated + pruned.n_pruned == pruned.n_candidates
    assert full.n_pruned == 0 and full.n_evaluated == full.n_candidates
    assert pruned.n_evaluated <= full.n_evaluated


def test_size_bucket_shares_compiled_program():
    """Two different instances in the same size bucket must not retrace the
    scan evaluator (the no-per-instance-recompile contract)."""
    from repro.core import vectorized as V
    from repro.core.dag import make_onestage_mapreduce

    insts = [
        ProblemInstance(
            job=make_onestage_mapreduce(
                np.random.default_rng(s), n_map=3, n_reduce=3, rho=1.0
            ),
            n_racks=3,
            n_wireless=1,
        )
        for s in (10, 11)
    ]
    cands = enumerate_assignments(6, 3)
    evaluate0 = make_batched_evaluator(insts[0])
    v0 = np.asarray(evaluate0(cands))
    before = V.TRACE_COUNT
    evaluate1 = make_batched_evaluator(insts[1])
    out = np.asarray(evaluate1(cands))
    assert V.TRACE_COUNT == before, "same-bucket instance retraced the scan"
    assert out.shape == (cands.shape[0],) and (out > 0).all()
    assert not np.allclose(v0, out)  # different durations, same program


def test_refinement_never_hurts_sampled_regime():
    inst = make_instance(3, n_tasks=11, n_racks=6)
    base = vectorized_search(
        inst, max_enumerate=1000, n_samples=512, refine_rounds=0
    )
    refined = vectorized_search(
        inst, max_enumerate=1000, n_samples=512, refine_rounds=4
    )
    assert refined.makespan <= base.makespan + 1e-6
    assert refined.refine_rounds >= 1


def _assert_fleet_matches_solo(insts, fleet, **search_kwargs):
    for i, inst in enumerate(insts):
        solo = vectorized_search(inst, **search_kwargs)
        got = fleet.results[i]
        assert np.array_equal(solo.best_assignment, got.best_assignment)
        assert solo.makespan == got.makespan  # bit-for-bit, both via simulate
        assert solo.n_candidates == got.n_candidates
        assert solo.n_pruned == got.n_pruned
        assert solo.n_evaluated == got.n_evaluated
        assert solo.refine_rounds == got.refine_rounds
        check_feasible(inst, got.schedule)


def test_fleet_matches_single_instance_bit_for_bit():
    """Heterogeneous fleet results == solo solver results, including the
    prune/eval counters (multi-chunk streams so stage-1 pruning is live)."""
    insts = [
        make_instance(s, n_tasks=5 + s % 3, n_racks=3 + s % 2) for s in range(4)
    ]
    fleet = schedule_fleet(insts, batch_size=64)
    _assert_fleet_matches_solo(insts, fleet, batch_size=64)
    assert fleet.n_pruned == sum(r.n_pruned for r in fleet.results)
    assert fleet.n_evaluated + fleet.n_pruned == fleet.n_candidates


def test_dense_prune_rate_regression():
    """Dense shuffle instance where the contention-free critical-path bound
    prunes 0%: the combined §IV-A bound must prune >0% and never discard the
    incumbent-optimal candidate."""
    from repro.core.dag import make_onestage_mapreduce

    job = make_onestage_mapreduce(
        np.random.default_rng(0), n_map=4, n_reduce=3, rho=2.0
    )
    inst = ProblemInstance(job=job, n_racks=4, n_wireless=1)
    old = vectorized_search(inst, batch_size=64, contention=False)
    new = vectorized_search(inst, batch_size=64)
    full = vectorized_search(inst, batch_size=64, lb_prune=False)
    assert old.n_pruned == 0, "seed no longer reproduces the 0%-prune gap"
    assert new.n_pruned > 0
    assert new.makespan == pytest.approx(full.makespan, abs=1e-9)
    assert new.n_evaluated + new.n_pruned == new.n_candidates


def test_fleet_one_sharded_launch_and_compile_count():
    """8 heterogeneous instances: one sharded stage-2 launch when each fits
    a single chunk, and at most one fresh trace per stage; a second fleet in
    the same size bucket must not retrace at all (checked with JAX's
    compilation counters)."""
    from repro.core.dag import make_onestage_mapreduce

    def fleets(base):
        # Heterogeneous shapes across slots (different task/edge/rack
        # counts), but the same shape profile for both fleets so the second
        # one provably lands in the same size bucket.
        return [
            ProblemInstance(
                job=make_onestage_mapreduce(
                    np.random.default_rng(base + s),
                    n_map=2 + s % 3,
                    n_reduce=1 + s % 2,
                    rho=1.0,
                ),
                n_racks=2 + s % 3,
                n_wireless=1 + s % 2,
            )
            for s in range(8)
        ]

    insts = fleets(50)
    fleet = schedule_fleet(insts, batch_size=512)
    # every instance's canonical enumeration fits one 512-chunk -> the whole
    # sweep is one mega-batch dispatch
    assert fleet.n_stage2_launches == 1
    assert fleet.n_stage1_traces <= 1 and fleet.n_stage2_traces <= 1
    assert fleet.n_stage1_traces + fleet.n_stage2_traces <= 2

    # Cross-check with JAX's own compilation counters.
    from jax._src import test_util as jtu

    with jtu.count_jit_tracing_cache_miss() as misses:
        fleet2 = schedule_fleet(fleets(90), batch_size=512)
    assert misses() == 0, "same-bucket fleet retraced a device program"
    assert fleet2.n_stage1_traces == 0 and fleet2.n_stage2_traces == 0


def test_fleet_compile_count_with_pruning():
    """Multi-chunk fleet (stage-1 pruning live): still at most one trace per
    stage across the whole run."""
    insts = [make_instance(s, n_tasks=7, n_racks=4) for s in range(8)]
    fleet = schedule_fleet(insts, batch_size=64)
    assert fleet.n_pruned > 0  # bound is actually engaged
    assert fleet.n_stage1_traces <= 1 and fleet.n_stage2_traces <= 1
    assert fleet.n_stage1_launches > 1 and fleet.n_stage2_launches > 1


def test_fleet_seed_sequence_and_validation():
    insts = [make_instance(s) for s in range(2)]
    fleet = schedule_fleet(insts, batch_size=64, seed=[3, 4])
    for i, inst in enumerate(insts):
        solo = vectorized_search(inst, batch_size=64, seed=3 + i)
        assert solo.makespan == fleet.results[i].makespan
    with pytest.raises(ValueError):
        schedule_fleet([])
    with pytest.raises(ValueError):
        schedule_fleet(insts, seed=[1, 2, 3])


@pytest.mark.slow
def test_sharded_evaluator_matches_single_device():
    """shard_map path on 4 forced host devices agrees with 1-device scores."""
    import subprocess
    import sys

    code = (
        "import numpy as np, jax\n"
        "assert jax.local_device_count() == 4\n"
        "from repro.core.vectorized import make_batched_evaluator, "
        "enumerate_assignments\n"
        "from repro.core import ProblemInstance, random_job\n"
        "rng = np.random.default_rng(0)\n"
        "job = random_job(rng, None, n_tasks=5, rho=1.0)\n"
        "inst = ProblemInstance(job=job, n_racks=3, n_wireless=1)\n"
        "cands = enumerate_assignments(5, 3)\n"
        "print(repr(np.asarray(make_batched_evaluator(inst)(cands)).tolist()))\n"
    )
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # virtual host devices; never the chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True
    )
    assert out.returncode == 0, out.stderr
    sharded = np.asarray(eval(out.stdout.strip().splitlines()[-1]))
    inst = make_instance(0, n_tasks=5, n_racks=3)
    local = np.asarray(make_batched_evaluator(inst)(enumerate_assignments(5, 3)))
    np.testing.assert_allclose(sharded, local, rtol=1e-5, atol=1e-4)
