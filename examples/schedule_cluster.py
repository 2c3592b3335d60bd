"""Periodic multi-job cluster scheduling (the paper's production scenario):
a day's worth of periodic jobs ([15]-style workload) on a hybrid DCN. The
heterogeneous fleet is solved in ONE padded mega-batch (`schedule_fleet`:
shared launches + combined §IV-A LB pruning across all jobs at once),
with the full refinement portfolio (mutation + elite crossover +
simulated annealing under the yield-driven allocator) polishing the
sampled-regime jobs, cross-checked per job against exact B&B under
wired-only vs wireless-augmented operation, plus a straggler re-plan.

Run:  PYTHONPATH=src python examples/schedule_cluster.py
"""

import numpy as np

from repro.core import ProblemInstance, random_job, schedule_fleet, solve_bnb, wired_only
from repro.distribution.plan import LinkSpec, backward_profile, replan
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    n_jobs = 8
    total0, total2, proved = 0.0, 0.0, 0
    print(f"scheduling {n_jobs} periodic jobs (tasks ~ U[5,10], rho=0.5) ...")
    insts = []
    for j in range(n_jobs):
        job = random_job(np.random.default_rng(100 + j), None, rho=0.5)
        insts.append(ProblemInstance(job=job, n_racks=8, n_wireless=2))

    # The whole heterogeneous fleet in one mega-batch search; sampled-regime
    # jobs get the full strategy portfolio for refinement.
    fleet = schedule_fleet(
        insts, max_enumerate=20_000, n_samples=2048, strategies="portfolio"
    )

    for j, (inst, rv) in enumerate(zip(insts, fleet.results)):
        r0 = solve_bnb(wired_only(inst), time_limit=10)
        r2 = solve_bnb(inst, time_limit=10)
        total0 += r0.makespan
        total2 += r2.makespan
        proved += r2.proved_optimal
        print(
            f"  job {j}: |V|={inst.job.n_tasks:2d} wired={r0.makespan:7.1f} "
            f"+wireless={r2.makespan:7.1f} "
            f"gain={100 * (1 - r2.makespan / r0.makespan):5.1f}% "
            f"fleet-search={rv.makespan:7.1f} "
            f"(pruned {rv.n_pruned}/{rv.n_candidates})"
        )
    print(
        f"\nfleet: avg wired JCT={total0 / n_jobs:.1f}, augmented="
        f"{total2 / n_jobs:.1f} ({100 * (1 - total2 / total0):.1f}% reduction, "
        f"{proved}/{n_jobs} proved optimal); mega-batch engine avg JCT="
        f"{float(fleet.makespans.mean()):.1f} with "
        f"{fleet.n_pruned}/{fleet.n_candidates} candidates LB-pruned in "
        f"{fleet.n_stage1_launches}+{fleet.n_stage2_launches} shared launches "
        f"({fleet.n_stage1_traces}+{fleet.n_stage2_traces} program traces)"
    )
    if fleet.strategy_stats:
        counters = "; ".join(
            f"{name}: {s.evaluated} evaluated, {s.improved} improving, "
            f"yield={s.yield_per_eval:.3f}, w={s.weight:.2f}"
            for name, s in sorted(fleet.strategy_stats.items())
        )
        print(f"refinement portfolio: {counters}")

    # Straggler mitigation on the training-integration side.
    cfg = get_config("llama3_2_3b")
    g_secs, g_bytes = backward_profile(cfg, tokens_per_device=4096)
    healthy = replan(g_secs, g_bytes, LinkSpec())
    degraded = replan(g_secs, g_bytes, LinkSpec(), compute_slowdown=1.6, degraded_aux=1)
    print(
        f"\nstraggler re-plan: healthy step {healthy.t_optimal:.3f}s -> "
        f"degraded pod (1.6x compute, 1 aux circuit lost) {degraded.t_optimal:.3f}s; "
        f"schedule re-derived in-flight (fault-tolerance hook)"
    )


if __name__ == "__main__":
    main()
