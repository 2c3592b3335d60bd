"""Readings that the limits of ``limits.json`` are set from; not part of a run.

    python3 benchmarks/chip/control.py --workload prod8.backlog --seeds 1 2 3

For each seed, in one process: build the cell's first stream, serve it once
as a run's window does, keep a sample of its stage-1 and stage-2 launches,
and read each number that decides ``correct`` twice:

- ``program``: the program's answers against the float32 reference (the
  lower reading of each limit);
- ``control``: the reference computed in bfloat16, the next precision below
  the configuration's float32, put in the program's place (the upper
  reading; it has to fail a limit).

One JSON line per seed on stdout. Needs a TPU, as a run does.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import jax  # noqa: E402
import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.chip import harness, reference  # noqa: E402

__all__ = ["control_gaps", "seed_readings"]


def control_gaps(kept) -> dict:
    """Largest relative gap of the bfloat16 reference, put in the program's
    place, from the float32 reference, per stage."""
    gaps = {1: float("-inf"), 2: float("-inf")}
    answer = {1: reference.stage1_bound, 2: reference.stage2_makespan}
    for launch in kept:
        racks = np.asarray(launch.racks)
        iid = np.asarray(launch.inst_id)
        for i, inst in enumerate(launch.instances):
            sel = iid == i
            if not sel.any():
                continue
            q = reference.Question.of(inst)
            low = answer[launch.stage](q, racks[sel], ml_dtypes.bfloat16)
            want = answer[launch.stage](q, racks[sel], np.float32)
            gaps[launch.stage] = max(gaps[launch.stage], reference.rel_gap(low, want))
    return {"stage1_rel_gap": gaps[1], "stage2_rel_gap": gaps[2]}


def seed_readings(workload: str, seed: int, bench: dict, root: Path = harness.CHIP_DIR):
    """Program and control readings of one seed of ``workload``."""
    from benchmarks.chip import run as bench_run
    from benchmarks.chip.probes import LaunchRecorder, MatchingLog

    entry = harness.cell(workload, bench)
    cfg = harness.config(entry["config"], root)
    plans = bench_run.prepare(cfg, harness.traffic(entry["traffic"], root), seed)[:1]
    with LaunchRecorder() as recorder, MatchingLog() as links:
        bench_run.set_up(cfg, plans, recorder)
        out = bench_run.window(cfg, plans, seed, 0.0, recorder)
    program = bench_run.compare_launches(out["kept"])
    rows = program.pop("rows")
    for plan, res in out["serves"]:
        program.update(bench_run.audit(plan["jobs"], cfg, res, links))
    return {
        "workload": workload,
        "seed": seed,
        "rows": rows,
        "program": program,
        "control": control_gaps(out["kept"]),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    args = parser.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU; not run", file=sys.stderr)
        return 1
    from benchmarks.chip import run as bench_run

    bench_run.enable_compile_cache()
    bench = harness.load_benchmark()
    for seed in args.seeds:
        print(json.dumps(seed_readings(args.workload, seed, bench)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
