"""Portfolio refinement contracts: fixed-seed determinism, incumbent
safety (no strategy can make the returned result worse), bit-for-bit
reproduction of the pre-portfolio (PR 2) mutation loop by the
single-strategy default, yield-counter accounting, and the acceptance
scenario — on a dense sampled-regime instance where plain local search
stalls, the full portfolio at the same candidate budget is never worse
and strictly better on most seeds."""

import numpy as np
import pytest

from repro.core import ProblemInstance, schedule_fleet, vectorized_search
from repro.core.dag import make_onestage_mapreduce, make_random_workflow
from repro.core.portfolio import (
    DEFAULT_PORTFOLIO,
    AnnealingStrategy,
    CrossoverStrategy,
    ElitePool,
    MutationStrategy,
    Portfolio,
    StrategyStats,
    build_strategies,
    merge_strategy_stats,
    mutate_pool,
    spec_length,
)
from repro.core.vectorized import batched_lower_bound, make_batched_evaluator


def dense_instance(seed, n_map=9, n_reduce=9, n_racks=6, rho=1.0):
    """Full-bipartite shuffle: dense enough that the sampled regime with a
    weak initial sample leaves real work for refinement."""
    job = make_onestage_mapreduce(
        np.random.default_rng(seed), n_map=n_map, n_reduce=n_reduce, rho=rho
    )
    return ProblemInstance(job=job, n_racks=n_racks, n_wireless=1)


SAMPLED = dict(max_enumerate=500, n_samples=64, batch_size=512, refine_pool=256)


# ---------------------------------------------------------------------------
# Unit: elite pool, allocator, spec resolution
# ---------------------------------------------------------------------------

def test_elite_pool_orders_dedupes_and_caps():
    pool = ElitePool(capacity=3)
    a = np.array([0, 1, 2], np.int32)
    pool.add(a, 5.0)
    pool.add(a, 5.0)  # duplicate assignment: dropped
    assert len(pool) == 1
    pool.add(np.array([1, 1, 2], np.int32), 3.0)
    pool.add(np.array([2, 1, 2], np.int32), 4.0)
    pool.add(np.array([0, 0, 0], np.int32), 10.0)  # worse than worst: dropped
    assert pool.vals == [3.0, 4.0, 5.0]
    pool.add(np.array([2, 2, 2], np.int32), 1.0)  # evicts the worst
    assert pool.vals == [1.0, 3.0, 4.0]
    assert len(pool) == 3


def test_elite_pool_add_batch_matches_sequential():
    rng = np.random.default_rng(0)
    racks = rng.integers(0, 3, size=(40, 4)).astype(np.int32)
    vals = rng.uniform(1, 9, size=40)
    a, b = ElitePool(capacity=5), ElitePool(capacity=5)
    a.add_batch(racks, vals)
    for j in np.argsort(vals, kind="stable"):
        b.add(racks[j], float(vals[j]))
    assert a.vals == b.vals


def test_allocator_single_strategy_gets_full_budget():
    inst = dense_instance(0)
    p = Portfolio(
        build_strategies(None), inst, np.random.default_rng(0), pool_size=257
    )
    assert list(p._allocations()) == [257]


def test_allocator_sums_to_budget_and_follows_weights():
    inst = dense_instance(0)
    p = Portfolio(
        build_strategies("portfolio"), inst, np.random.default_rng(0), pool_size=100
    )
    counts = p._allocations()
    assert counts.sum() == 100 and (counts > 0).all()
    p.weights = np.array([8.0, 1.0, 1.0])
    skewed = p._allocations()
    assert skewed.sum() == 100
    assert skewed[0] > counts[0]  # winner gets more
    assert skewed[1] >= 10 and skewed[2] >= 10  # min-share floor holds


def test_spec_resolution_and_errors():
    assert spec_length(None) == 1
    assert spec_length("portfolio") == len(DEFAULT_PORTFOLIO) == 3
    names = [s.name for s in build_strategies("portfolio")]
    assert names == ["mutation", "crossover", "annealing"]
    assert isinstance(build_strategies([AnnealingStrategy])[0], AnnealingStrategy)
    assert build_strategies([MutationStrategy()])[0].name == "mutation"
    with pytest.raises(ValueError):
        build_strategies(["no_such_strategy"])
    with pytest.raises(ValueError):
        build_strategies(["mutation", "mutation"])
    with pytest.raises(TypeError):
        build_strategies([42])


def test_fleet_rejects_live_strategy_objects():
    insts = [dense_instance(s) for s in range(2)]
    with pytest.raises(ValueError):
        schedule_fleet(insts, strategies=[AnnealingStrategy()], **SAMPLED)


def test_fleet_accepts_strategy_classes_as_factories():
    """Classes and zero-arg factories give each instance a private copy."""
    insts = [dense_instance(s) for s in range(2)]
    fleet = schedule_fleet(
        insts, strategies=(MutationStrategy, AnnealingStrategy),
        refine_rounds=2, **SAMPLED,
    )
    assert set(fleet.strategy_stats) == {"mutation", "annealing"}


def test_zero_refine_pool_rounds_are_noops():
    """refine_pool=0 must not crash: every round proposes nothing."""
    inst = dense_instance(0)
    res = vectorized_search(
        inst, seed=0, strategies="portfolio", refine_rounds=3,
        refine_patience=3, max_enumerate=500, n_samples=64, batch_size=512,
        refine_pool=0,
    )
    base = vectorized_search(
        inst, seed=0, refine_rounds=0, **SAMPLED
    )
    assert res.makespan == base.makespan
    assert all(s.proposed == 0 for s in res.strategy_stats.values())


def test_starved_strategy_round_is_rng_silent():
    """refine_pool=2 across 3 strategies starves annealing (allocation 0):
    it must not re-judge a stale candidate or consume RNG, so the run is
    deterministic and annealing proposes nothing."""
    inst = dense_instance(1)
    kw = dict(
        seed=4, strategies="portfolio", refine_rounds=4, refine_patience=4,
        max_enumerate=500, n_samples=64, batch_size=512, refine_pool=2,
    )
    a = vectorized_search(inst, **kw)
    b = vectorized_search(inst, **kw)
    assert a.makespan == b.makespan
    assert np.array_equal(a.best_assignment, b.best_assignment)
    assert a.strategy_stats["annealing"].proposed == 0


def test_strategy_shape_validation():
    class Bad:
        name = "bad"

        def propose(self, view, count):
            return np.zeros((count + 1, view.best_rack.shape[0]), np.int32)

        def observe(self, view, racks, vals):
            pass

        def end_round(self, view):
            pass

    inst = dense_instance(0)
    with pytest.raises(ValueError, match="proposed shape"):
        vectorized_search(
            inst, strategies=[Bad()], refine_rounds=2, **SAMPLED
        )


# ---------------------------------------------------------------------------
# Determinism and bit-for-bit PR 2 reproduction
# ---------------------------------------------------------------------------

def test_portfolio_fixed_seed_is_deterministic():
    inst = dense_instance(2)
    a = vectorized_search(
        inst, seed=7, strategies="portfolio", refine_rounds=6, **SAMPLED
    )
    b = vectorized_search(
        inst, seed=7, strategies="portfolio", refine_rounds=6, **SAMPLED
    )
    assert a.makespan == b.makespan
    assert np.array_equal(a.best_assignment, b.best_assignment)
    assert a.n_evaluated == b.n_evaluated and a.n_pruned == b.n_pruned
    for name in a.strategy_stats:
        sa, sb = a.strategy_stats[name], b.strategy_stats[name]
        assert dataclass_tuple(sa) == dataclass_tuple(sb)


def dataclass_tuple(s: StrategyStats):
    return (s.proposed, s.pruned, s.evaluated, s.improved, s.improvement, s.weight)


def test_portfolio_fleet_matches_solo_bit_for_bit():
    """Fleet packing must not perturb the portfolio's RNG or scores."""
    insts = [dense_instance(s) for s in range(3)]
    fleet = schedule_fleet(
        insts, seed=1, strategies="portfolio", refine_rounds=4, **SAMPLED
    )
    for i, inst in enumerate(insts):
        solo = vectorized_search(
            inst, seed=1, strategies="portfolio", refine_rounds=4, **SAMPLED
        )
        got = fleet.results[i]
        assert solo.makespan == got.makespan
        assert np.array_equal(solo.best_assignment, got.best_assignment)
        assert solo.n_evaluated == got.n_evaluated
        for name in solo.strategy_stats:
            assert dataclass_tuple(solo.strategy_stats[name]) == dataclass_tuple(
                got.strategy_stats[name]
            )
    merged = merge_strategy_stats(r.strategy_stats for r in fleet.results)
    for name, agg in fleet.strategy_stats.items():
        assert dataclass_tuple(agg) == dataclass_tuple(merged[name])


def test_mutation_only_reproduces_pr2_refinement_bit_for_bit():
    """The default (single-mutation-strategy) portfolio must walk exactly
    the pre-portfolio refinement loop: same RNG stream, same pruning
    decisions, same incumbent updates, same counters — verified against a
    host reimplementation of the PR 2 loop built from the public pieces."""
    inst = dense_instance(4)
    R, P = 6, 256
    base = vectorized_search(inst, seed=3, refine_rounds=0, **SAMPLED)
    full = vectorized_search(inst, seed=3, refine_rounds=R, **SAMPLED)

    evaluate = make_batched_evaluator(inst)
    best = base.best_assignment.copy()
    best_val = float(np.asarray(evaluate(best[None, :]))[0])
    rng = np.random.default_rng(3 + 1)  # the driver's refinement stream
    n_eval, n_pruned, rounds = base.n_evaluated, base.n_pruned, 0
    for _ in range(R):
        pool = mutate_pool(rng, best, inst, P)
        lbs = batched_lower_bound(inst, pool)
        keep = lbs < best_val - 1e-6
        n_pruned += int((~keep).sum())
        surv = pool[keep]
        prev = best_val
        if surv.shape[0]:
            vals = np.asarray(evaluate(surv))
            n_eval += vals.shape[0]
            j = int(np.argmin(vals))
            if float(vals[j]) < best_val:
                best_val = float(vals[j])
                best = surv[j].astype(np.int64)
        rounds += 1
        if not (best_val < prev - 1e-9):
            break

    assert full.refine_rounds == rounds
    assert np.array_equal(full.best_assignment, best)
    assert full.n_evaluated == n_eval
    assert full.n_pruned == n_pruned
    assert full.makespan == vectorized_search(inst, seed=3, refine_rounds=R, **SAMPLED).makespan


# ---------------------------------------------------------------------------
# Incumbent safety: no strategy can return a worse result than its input
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "spec", [("crossover",), ("annealing",), ("mutation",), "portfolio"]
)
def test_strategies_never_worsen_incumbent(spec):
    inst = dense_instance(1)
    base = vectorized_search(inst, seed=0, refine_rounds=0, **SAMPLED)
    refined = vectorized_search(
        inst, seed=0, strategies=spec, refine_rounds=6, refine_patience=6, **SAMPLED
    )
    assert refined.makespan <= base.makespan + 1e-6


def test_annealing_walker_accepts_worse_but_incumbent_holds():
    """The SA walker drifts (temperature acceptance) while the driver's
    strict-improvement rule keeps the incumbent monotone."""
    inst = dense_instance(5)
    res = vectorized_search(
        inst,
        seed=2,
        strategies=[AnnealingStrategy(t0_frac=5.0, alpha=1.0)],  # hot walker
        refine_rounds=8,
        refine_patience=8,
        **SAMPLED,
    )
    base = vectorized_search(inst, seed=2, refine_rounds=0, **SAMPLED)
    assert res.makespan <= base.makespan + 1e-6


# ---------------------------------------------------------------------------
# Counter accounting
# ---------------------------------------------------------------------------

def test_strategy_counter_accounting():
    inst = dense_instance(3)
    res = vectorized_search(
        inst, seed=0, strategies="portfolio", refine_rounds=8,
        refine_patience=8, **SAMPLED,
    )
    stats = res.strategy_stats
    assert set(stats) == {"mutation", "crossover", "annealing"}
    for s in stats.values():
        assert s.proposed == s.pruned + s.evaluated
        assert 0 <= s.improved <= s.evaluated
        assert s.improvement >= 0.0 and s.weight > 0.0
    # refinement proposals are part of the global candidate accounting
    refine_proposed = sum(s.proposed for s in stats.values())
    assert refine_proposed == res.refine_rounds * SAMPLED["refine_pool"]
    assert res.n_evaluated + res.n_pruned == res.n_candidates
    # yield property is consistent
    for s in stats.values():
        if s.evaluated:
            assert s.yield_per_eval == pytest.approx(s.improvement / s.evaluated)


def test_fleet_surfaces_aggregated_strategy_stats():
    insts = [dense_instance(s) for s in range(2)]
    fleet = schedule_fleet(
        insts, strategies="portfolio", refine_rounds=4, **SAMPLED
    )
    assert set(fleet.strategy_stats) == {"mutation", "crossover", "annealing"}
    for name, agg in fleet.strategy_stats.items():
        assert agg.proposed == sum(
            r.strategy_stats[name].proposed for r in fleet.results
        )


# ---------------------------------------------------------------------------
# Acceptance: portfolio vs stalled plain local search, same budget
# ---------------------------------------------------------------------------

def test_portfolio_beats_stalled_local_search_same_budget():
    """Dense sampled-regime instances where plain mutation local search
    stalls: at the SAME total candidate budget (same rounds, pool, and
    patience) the full portfolio is never worse on any seed and strictly
    better on at least one, with per-strategy yield counters surfaced."""
    R = 16
    kw = dict(refine_rounds=R, refine_patience=R, **SAMPLED)
    strictly_better = 0
    insts = [dense_instance(s) for s in range(6)]
    plain = schedule_fleet(insts, seed=list(range(6)), strategies=("mutation",), **kw)
    port = schedule_fleet(insts, seed=list(range(6)), strategies="portfolio", **kw)
    for seed in range(6):
        p, q = plain.results[seed], port.results[seed]
        # identical proposal budget per round on both sides
        assert q.refine_rounds * SAMPLED["refine_pool"] == sum(
            s.proposed for s in q.strategy_stats.values()
        )
        assert q.makespan <= p.makespan + 1e-9, f"portfolio worse on seed {seed}"
        strictly_better += q.makespan < p.makespan - 1e-9
    assert strictly_better >= 1
    # the yield counters that justify the win are surfaced on the fleet
    assert sum(s.improved for s in port.strategy_stats.values()) > 0
    assert any(
        s.improvement > 0
        for name, s in port.strategy_stats.items()
        if name != "mutation"
    ), "crossover/annealing contributed nothing — portfolio win is vacuous"
