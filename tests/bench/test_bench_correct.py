"""``correct`` on a test-sized cell: true for the program as it is, false for
the control and for each fault the timed path can have.

The run skips the entry's look for a chip and drives the rest of a run on
this backend: set-up, window, and the comparison with the reference.
"""

import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from benchmarks.chip import control, harness
from benchmarks.chip import run as bench_run
from repro.core import vectorized as V
from repro.online import cluster

CELLS = Path(__file__).resolve().parent / "cells"
SEED = 2**31 + 9


def tiny_bench() -> dict:
    bench = json.loads(json.dumps(harness.load_benchmark()))
    bench["workloads"] = [
        {"name": f"tiny_{c}.few", "config": f"tiny_{c}", "traffic": "few", "chips": 1,
         "why": "test size"}
        for c in ("prod8", "topo8")
    ]
    for m in bench["per_layer"]:
        m["workloads"] = [w["name"] for w in bench["workloads"]]
    return bench


def run_tiny(config: str) -> dict:
    return bench_run.run(f"tiny_{config}.few", SEED, 0.0, 0, tiny_bench(), root=CELLS)


@pytest.mark.parametrize("config", ["prod8", "topo8"])
def test_sound_program_is_correct(config):
    line = run_tiny(config)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 16  # 2 streams of 8 jobs
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"jobs_per_s", "epoch_p95_ms", "jct_mean", "setup_s"}


@pytest.mark.parametrize("config", ["prod8", "topo8"])
def test_control_fails_a_limit(config):
    """The reference in bfloat16, in the program's place, breaks a limit the
    program keeps."""
    got = control.seed_readings(f"tiny_{config}.few", SEED, tiny_bench(), root=CELLS)
    limits = bench_run.LIMITS
    assert all(got["program"][k] <= limits[k] for k in limits)
    assert any(got["control"][k] > limits[k] for k in got["control"])


def alter_stage1(monkeypatch):
    lb = V._fleet_lb_device
    monkeypatch.setattr(V, "_fleet_lb_device", lambda *a, **k: lb(*a, **k).at[0].add(1.0))


def alter_stage2(monkeypatch):
    make = V._compiled_evaluator

    def evaluator(*key):
        fn = make(*key)
        return lambda *a: fn(*a).at[0].add(1.0)

    monkeypatch.setattr(V, "_compiled_evaluator", evaluator)


def half_batch(monkeypatch):
    """Stage 2 scores only the first half of each launch's rows."""
    make = V._compiled_evaluator

    def evaluator(*key):
        fn = make(*key)

        def half(*a):
            out = fn(*a)
            return out.at[out.shape[0] // 2 :].set(jnp.inf)

        return half

    monkeypatch.setattr(V, "_compiled_evaluator", evaluator)


def commit_unchanged(monkeypatch):
    """A commit that reports a completion and leaves the timeline as it was."""
    monkeypatch.setattr(
        cluster.ClusterTimeline,
        "commit",
        lambda self, view, sched, t, job_id=-1, holds_out=None: t + sched.makespan,
    )


@pytest.mark.parametrize(
    "fault", [alter_stage1, alter_stage2, half_batch, commit_unchanged],
    ids=lambda f: f.__name__,
)
def test_fault_makes_the_run_incorrect(fault, monkeypatch):
    fault(monkeypatch)
    line = run_tiny("topo8")
    assert not line["correct"]
    broken = [k for k, c in line["checks"].items() if c["value"] > c["limit"]]
    assert broken, line["checks"]


def test_traced_run_reads_the_span_metrics():
    """On this backend the trace holds no TPU plane: the readers of device
    time find nothing and leave their metrics out; those of spans and
    counters read."""
    peak = {"flops_per_s": 1.97e14, "bytes_per_s": 8.19e11}
    line = bench_run.run("tiny_topo8.few", SEED, 0.0, 1, tiny_bench(), root=CELLS, peak=peak)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 8  # the first stream, once
    assert set(line["metrics"]) == {"commit_ms", "fleet_host_ms", "launches_per_epoch"}
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line)[-1] == "checks"
