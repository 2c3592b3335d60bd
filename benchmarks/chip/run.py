"""One run of one cell of the chip benchmark of ``OnlineScheduler(policy="fleet")``.

    python3 benchmarks/chip/run.py --workload prod8.backlog --seed 7 \\
        --seconds 40 --trace 0

Runs on the machine it is started on and needs its chips: without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits 1.

1. Set-up: build the cell's streams from ``--seed`` (``streams.py``), then
   serve each once, untimed, so that every stage-1 and stage-2 program the
   window uses is compiled or loaded from the persistent cache
   (``.jax_cache/`` in this checkout). ``setup_s`` runs from the start of
   the process to the end of those serves.
2. ``--trace 0``: serve the same streams again, each time with a fresh
   scheduler, in rounds of one serve per stream, back to back until
   ``--seconds`` have passed; only whole rounds count. End-to-end metrics
   come from those serves.
   ``--trace 1``: serve the first stream once under the program's tracer
   and the JAX profiler, and reduce both to the per-layer metrics and a
   breakdown.
3. Correctness, once the window has closed: a sample of the window's
   stage-1 and stage-2 launches, drawn from the seed, against the plain
   reference, and every committed serve against its guarantees
   (``reference.py``), under a cluster topology with the matchings that
   ``probes.MatchingLog`` saw it apply. Each number compared is printed
   beside its limit.

The last line of stdout is one JSON object.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.chip import harness, reference, streams  # noqa: E402

LIMITS = json.loads((harness.CHIP_DIR / "limits.json").read_text())["limits"]
PEAKS = json.loads((harness.CHIP_DIR / "peaks.json").read_text())["devices"]
# Launches of each stage kept per serve for the comparison with the reference.
KEEP_PER_SERVE = 32


def device_info(devices) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path in this checkout, keeping the
    sub-second stage programs too."""
    jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def program_traces() -> int:
    from repro.core import vectorized as V

    return V.TRACE_COUNT + V.LB_TRACE_COUNT


def keep_sample(seed: int, serve: int, counts: dict) -> tuple:
    """Launch indices of each stage to keep in one serve, drawn from the seed."""
    rng = np.random.default_rng([seed, serve])
    return tuple(
        rng.choice(counts[s], min(counts[s], KEEP_PER_SERVE), replace=False)
        for s in (1, 2)
    )


def compare_launches(kept) -> dict:
    """Largest relative gap of each stage's kept launches from the float32
    reference (inf where no launch was kept)."""
    gaps = {1: float("-inf"), 2: float("-inf")}
    rows = {1: 0, 2: 0}
    answer = {1: reference.stage1_bound, 2: reference.stage2_makespan}
    for launch in kept:
        racks = np.asarray(launch.racks)
        iid = np.asarray(launch.inst_id)
        out = np.asarray(launch.out)
        for i, inst in enumerate(launch.instances):
            sel = iid == i
            if not sel.any():
                continue
            want = answer[launch.stage](reference.Question.of(inst), racks[sel])
            gaps[launch.stage] = max(gaps[launch.stage], reference.rel_gap(out[sel], want))
            rows[launch.stage] += int(sel.sum())
    return {
        "stage1_rel_gap": gaps[1] if rows[1] else float("inf"),
        "stage2_rel_gap": gaps[2] if rows[2] else float("inf"),
        "rows": rows,
    }


def audit(jobs: list[streams.Job], cfg: dict, res, links) -> dict:
    """Broken guarantees of one committed serve; ``links`` is the run's
    ``probes.MatchingLog``."""
    from repro.online.cluster import RECONFIG_JOB

    cl, topo = cfg["cluster"], cfg["topology"] or {}
    asked = {
        j.job_id: (j.time, j.p, j.edges, j.d, cl["wired_rate"], cl["wireless_rate"])
        for j in jobs
    }
    tl = res.timeline
    return reference.audit_serve(
        asked, res.jobs, tl.rack_intervals, tl.wired_intervals, tl.wireless_intervals,
        links=links.of(tl) if topo else None,
        degree=topo.get("degree"),
        channel_degree=topo.get("channel_degree"),
        reconfig_id=RECONFIG_JOB,
    )


def checks_of(readings: dict) -> dict:
    """Each number compared beside its limit; an infinite reading (no answer
    to compare, or a non-finite one) is printed as the largest float, which
    JSON can hold."""
    return {
        k: {"value": min(readings[k], sys.float_info.max), "limit": LIMITS[k]}
        for k in LIMITS
    }


def prepare(cfg: dict, traffic: dict, seed: int) -> list[dict]:
    """The run's streams: engine seed, jobs and the program's events."""
    return [
        {"engine_seed": e, "jobs": jobs, "events": harness.arrivals(cfg, jobs)}
        for e, jobs in streams.cell_streams(cfg, traffic, seed)
    ]


def window(cfg, plans, seed, seconds, recorder) -> dict:
    """Serve every stream once per round, rounds back to back until
    ``seconds`` have passed."""
    from benchmarks.chip.probes import EpochClock

    serves, epochs, walls, kept = [], [], [], []
    t_start = time.perf_counter()
    while True:
        for plan in plans:
            recorder.restart(*keep_sample(seed, len(serves), plan["counts"]))
            clock = EpochClock()
            svc = harness.scheduler(cfg, plan["engine_seed"], tracer=clock)
            t0 = time.perf_counter()
            res = svc.serve(plan["events"])
            walls.append(time.perf_counter() - t0)
            serves.append((plan, res))
            epochs += clock.epochs
            kept += recorder.kept
        if time.perf_counter() - t_start >= seconds:
            return dict(serves=serves, epochs=epochs, walls=walls, kept=kept)


def traced_serve(cfg, plan, seed, recorder, peak: dict) -> dict:
    """One serve of ``plan`` under the program's tracer and the profiler,
    reduced."""
    from benchmarks.chip import trace_reduce
    from benchmarks.chip.probes import AnnotatedTracer

    recorder.restart(*keep_sample(seed, 0, plan["counts"]))
    tracer = AnnotatedTracer()
    svc = harness.scheduler(cfg, plan["engine_seed"], tracer=tracer)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as logdir:
        jax.profiler.start_trace(logdir, profiler_options=options)
        t0 = time.perf_counter()
        res = svc.serve(plan["events"])
        window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        path = next(Path(logdir).rglob("*.xplane.pb"))
        ops, modules, host = trace_reduce.read_xspace(path)
    print(
        f"traced serve: {window_s:.3f} s; trace written and read in "
        f"{time.perf_counter() - t0 - window_s:.3f} s, {len(ops)} device ops",
        file=sys.stderr,
    )
    red = trace_reduce.Reduced(
        ops=ops,
        modules=modules,
        host=host,
        spans=tracer.spans,
        counters=dict(tracer.counters),
        n_epochs=res.n_epochs,
        window_s=window_s,
        stage1_shapes=list(recorder.shapes),
        peak=peak,
    )
    return dict(serves=[(plan, res)], reduced=red, kept=list(recorder.kept))


def set_up(cfg, plans, recorder) -> None:
    """Serve each stream once, untimed; note its launches per stage."""
    for plan in plans:
        recorder.restart()
        res = harness.scheduler(cfg, plan["engine_seed"]).serve(plan["events"])
        plan["counts"] = dict(recorder.counts)
        print(
            f"set-up serve: {res.n_epochs} epochs, {plan['counts'][1]} stage-1 "
            f"and {plan['counts'][2]} stage-2 launches",
            file=sys.stderr,
        )


def run(
    workload: str,
    seed: int,
    seconds: float,
    trace: int,
    bench: dict,
    root: Path = harness.CHIP_DIR,
    peak: dict | None = None,
) -> dict:
    """One run of ``workload``; returns the result line as a dict. ``root``
    holds the cell's configuration and traffic files; ``peak`` overrides the
    device's entry in ``peaks.json``."""
    from benchmarks.chip import trace_reduce
    from benchmarks.chip.probes import LaunchRecorder, MatchingLog

    entry = harness.cell(workload, bench)
    cfg = harness.config(entry["config"], root)
    plans = prepare(cfg, harness.traffic(entry["traffic"], root), seed)
    devices = jax.devices()
    with LaunchRecorder() as recorder, MatchingLog() as links:
        set_up(cfg, plans, recorder)
        setup_s = time.perf_counter() - PROCESS_T0
        print(f"set-up: {setup_s:.3f} s", file=sys.stderr)
        traces0 = program_traces()
        if trace:
            peak = peak or PEAKS[devices[0].device_kind]
            out = traced_serve(cfg, plans[0], seed, recorder, peak)
        else:
            out = window(cfg, plans, seed, seconds, recorder)
    in_window = program_traces() - traces0
    print(f"stage-1/stage-2 program traces inside the window: {in_window}", file=sys.stderr)
    stats = devices[0].memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    # The window has closed: compare what it produced.
    served, kept = out["serves"], out["kept"]
    readings = compare_launches(kept)
    rows = readings.pop("rows")
    broken: dict[str, int] = {}
    for plan, res in served:
        for k, v in audit(plan["jobs"], cfg, res, links).items():
            broken[k] = broken.get(k, 0) + v
    readings.update(broken)
    checks = checks_of(readings)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    attempted = sum(len(plan["jobs"]) for plan, _res in served)
    failed = attempted - sum(len({r.job_id for r in res.jobs}) for _plan, res in served)
    failed += broken["jobs_off_their_dag"]
    print(
        f"compared {rows[1]} stage-1 and {rows[2]} stage-2 rows of "
        f"{len(kept)} launches; {len(served)} serves audited",
        file=sys.stderr,
    )

    device = dict(device_info(devices), memory_peak_bytes=memory_peak)
    line = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        red = out["reduced"]
        metrics = {}
        for m in bench["per_layer"]:
            if workload in m.get("workloads", [workload]):
                value = harness.metric_reader(m["name"])(red)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = trace_reduce.union_seconds(red.ops)
        device.update(busy_s=busy, window_s=red.window_s)
        line.update(metrics=metrics, device=device, breakdown=trace_reduce.breakdown(red))
    else:
        epochs_ms = 1e3 * np.asarray(out["epochs"])
        jcts = [r.completion - r.arrival for _plan, res in served for r in res.jobs]
        values = {
            "jobs_per_s": len(jcts) / sum(out["walls"]),
            "epoch_p95_ms": float(np.quantile(epochs_ms, 0.95)),
            "jct_mean": float(np.mean(jcts)),
            "setup_s": setup_s,
        }
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
        print(
            f"window: {len(served)} serves, {sum(out['walls']):.3f} s, "
            f"{epochs_ms.size} epochs, {len(jcts)} jobs",
            file=sys.stderr,
        )
        line.update(metrics=metrics, device=device)
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    bench = harness.load_benchmark()
    chips = harness.cell(args.workload, bench)["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"run.py: needs {chips} TPU chip(s), JAX found "
            f"{len(devices)} {devices[0].platform} device(s); not run",
            file=sys.stderr,
        )
        return 1
    enable_compile_cache()
    line = run(args.workload, args.seed, args.seconds, args.trace, bench)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(line))
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
