"""Benchmark harness: one module per paper table/figure plus framework
benches. Prints ``name,us_per_call,derived`` CSV lines; ``--json out.json``
additionally writes the machine-readable ``BENCH`` record (see
``benchmarks/common.py``) so the perf trajectory is tracked across PRs.

  fig4_jct_vs_racks  — paper Fig. 4 (JCT vs racks, baselines ± wireless)
  fig5_gain_vs_factor — paper Fig. 5 (gain vs network factor)
  solver_scaling     — §IV-D decomposition / solver comparison
  online_serving     — arrival-driven serving: JCT/throughput vs rate
  plan_gain          — beyond-paper scheduler->training integration
  kernel_bench       — Pallas kernels (interpret on CPU; see §Roofline for TPU)
  train_bench        — end-to-end smoke train step

REPRO_BENCH_FULL=1 enables the paper-scale sweeps.
"""

from __future__ import annotations

import sys
import time
import traceback


def main(argv=None) -> int:
    """Run every section; returns 1 if any section raised, else 0."""
    from benchmarks import (
        common,
        fig4_jct_vs_racks,
        fig5_gain_vs_factor,
        kernel_bench,
        online_serving,
        plan_gain,
        solver_scaling,
        train_bench,
    )

    from repro.launch.compile_cache import enable_compile_cache

    args = common.bench_arg_parser(__doc__).parse_args(argv)
    enable_compile_cache()
    failed = []
    print("name,us_per_call,derived")
    for mod in (
        fig4_jct_vs_racks,
        fig5_gain_vs_factor,
        solver_scaling,
        online_serving,
        plan_gain,
        kernel_bench,
        train_bench,
    ):
        t0 = time.perf_counter()
        try:
            mod.run()
            common.emit(
                f"_section_{mod.__name__.split('.')[-1]}",
                1e6 * (time.perf_counter() - t0),
                "ok",
            )
        except Exception:  # noqa: BLE001 — run the other sections, then fail
            traceback.print_exc()
            common.emit(f"_section_{mod.__name__.split('.')[-1]}", 0, "FAILED")
            failed.append(mod.__name__)
    if args.json:
        common.write_json(args.json, bench="all")
    if failed:
        print(f"FAILED sections: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
