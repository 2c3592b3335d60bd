"""Property layer locking in the §IV-A bound algebra and the fleet contract.

Two invariants, over randomized instances:

  1. Bound sandwich:  max(cpm_lb, load_lb) <= exact B&B optimum <= greedy
     score — per candidate assignment the combined stage-1 bound never
     exceeds that candidate's greedy score, and across candidates
     min(bound) <= optimum <= min(greedy).

  2. Fleet equivalence: every per-instance result of ``schedule_fleet`` is
     bit-for-bit the result of the single-instance solver (assignment,
     makespan, prune/eval counters).

Runs under Hypothesis when it is installed (CI's ``pip install -e .[test]``
lane); falls back to a fixed seeded sweep of the same checks otherwise
(this container ships without hypothesis by design).
"""

import numpy as np
import pytest

from repro.core import (
    ProblemInstance,
    contention_lower_bounds,
    random_job,
    schedule_fleet,
    solve_bnb,
)
from repro.core.bounds import combined_lower_bounds
from repro.core.vectorized import (
    batched_lower_bound,
    enumerate_assignments,
    make_batched_evaluator,
)

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False


def _instance(seed: int, n_tasks: int, n_racks: int, rho: float, n_wireless: int):
    rng = np.random.default_rng(seed)
    job = random_job(rng, None, n_tasks=n_tasks, rho=rho)
    return ProblemInstance(job=job, n_racks=n_racks, n_wireless=n_wireless)


def _check_bound_sandwich(seed, n_tasks, n_racks, rho, n_wireless):
    inst = _instance(seed, n_tasks, n_racks, rho, n_wireless)
    cands = enumerate_assignments(inst.job.n_tasks, inst.n_racks)
    lbs_kernel = batched_lower_bound(inst, cands)
    lbs_ref = combined_lower_bounds(inst, cands)
    np.testing.assert_allclose(lbs_kernel, lbs_ref, rtol=1e-5, atol=1e-3)

    scores = np.asarray(make_batched_evaluator(inst)(cands))
    # per-candidate admissibility w.r.t. the greedy evaluator
    assert (lbs_kernel <= scores + 1e-3).all()
    # ... including the host-side contention terms on their own
    host = contention_lower_bounds(inst, cands)
    assert (host <= scores + 1e-3).all()

    opt = solve_bnb(inst, time_limit=30)
    assert opt.proved_optimal
    # min over candidates: max(cpm_lb, load_lb) <= optimum <= greedy score
    assert float(lbs_kernel.min()) <= opt.makespan + 1e-3
    assert opt.makespan <= float(scores.min()) + 1e-3


def _check_fleet_equivalence(seeds, n_tasks_list, n_racks, batch_size):
    # Shared with the deterministic fleet tests so both lanes assert the
    # same bit-for-bit contract.
    from test_vectorized import _assert_fleet_matches_solo

    insts = [
        _instance(s, n, n_racks, 1.0, 1) for s, n in zip(seeds, n_tasks_list)
    ]
    fleet = schedule_fleet(insts, batch_size=batch_size)
    _assert_fleet_matches_solo(insts, fleet, batch_size=batch_size)


def test_bnb_assignment_bound_hook_preserves_optimum():
    """An admissible custom bound through solve_bnb's level-1 hook must not
    change the optimum (here: the §IV-A contention bound on complete
    assignments, the same term family the fleet pruner fuses on-device)."""

    def hook(inst, rack):
        rack = np.asarray(rack)
        if (rack < 0).any():
            return 0.0
        return float(contention_lower_bounds(inst, rack[None, :])[0])

    for seed in range(3):
        inst = _instance(seed, n_tasks=5, n_racks=3, rho=1.0, n_wireless=1)
        base = solve_bnb(inst, time_limit=30)
        hooked = solve_bnb(inst, time_limit=30, assignment_bound=hook)
        assert hooked.makespan == pytest.approx(base.makespan, abs=1e-9)
        assert hooked.proved_optimal


if HAVE_HYPOTHESIS:

    @settings(
        max_examples=8,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(
        seed=st.integers(0, 10**6),
        n_tasks=st.integers(3, 6),
        n_racks=st.integers(2, 3),
        rho=st.floats(0.25, 2.0, allow_nan=False),
        n_wireless=st.integers(0, 2),
    )
    def test_bound_sandwich_property(seed, n_tasks, n_racks, rho, n_wireless):
        _check_bound_sandwich(seed, n_tasks, n_racks, rho, n_wireless)

    @settings(
        max_examples=5,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(
        base=st.integers(0, 10**6),
        sizes=st.lists(st.integers(3, 6), min_size=2, max_size=3),
        n_racks=st.integers(2, 3),
    )
    def test_fleet_matches_solo_property(base, sizes, n_racks):
        seeds = [base + i for i in range(len(sizes))]
        _check_fleet_equivalence(seeds, sizes, n_racks, batch_size=32)

else:  # fixed seeded sweep of the same properties

    @pytest.mark.parametrize("case", range(8))
    def test_bound_sandwich_property(case):
        rng = np.random.default_rng(1000 + case)
        _check_bound_sandwich(
            seed=int(rng.integers(10**6)),
            n_tasks=int(rng.integers(3, 7)),
            n_racks=int(rng.integers(2, 4)),
            rho=float(rng.uniform(0.25, 2.0)),
            n_wireless=int(rng.integers(0, 3)),
        )

    @pytest.mark.parametrize("case", range(4))
    def test_fleet_matches_solo_property(case):
        rng = np.random.default_rng(2000 + case)
        k = int(rng.integers(2, 4))
        _check_fleet_equivalence(
            seeds=[int(rng.integers(10**6)) for _ in range(k)],
            n_tasks_list=[int(rng.integers(3, 7)) for _ in range(k)],
            n_racks=int(rng.integers(2, 4)),
            batch_size=32,
        )
