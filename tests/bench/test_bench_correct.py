"""``correct`` on a test-sized cell: true for the program as it is, false for
the control and for each fault the timed path can have.

The run skips the entry's look for a chip and drives the rest of a run on
this backend: set-up, window, and the comparison with the reference.
"""

import dataclasses

import jax.numpy as jnp
import pytest

from benchmarks.chip import control
from benchmarks.chip import run as bench_run
from repro.core import vectorized as V
from repro.core.instance import Topology
from repro.online import cluster

LINK_CHECKS = ("transfers_off_their_links", "matchings_over_degree")


@pytest.mark.parametrize("config", ["prod8", "topo8"])
def test_sound_program_is_correct(config, tiny):
    line = tiny.run(config)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 16  # 2 streams of 8 jobs
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"jobs_per_s", "epoch_p95_ms", "jct_mean", "setup_s"}
    assert all(line["checks"][k] == {"value": 0, "limit": 0} for k in LINK_CHECKS)


@pytest.mark.parametrize("config", ["prod8", "topo8"])
def test_control_fails_a_limit(config, tiny):
    """The reference in bfloat16, in the program's place, breaks a limit the
    program keeps."""
    got = control.seed_readings(f"tiny_{config}.few", tiny.seed, tiny.bench(), root=tiny.root)
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


def views_on_every_link(monkeypatch):
    """The views plan on every candidate link; the timeline keeps matching."""
    monkeypatch.setattr(
        cluster.ClusterTimeline,
        "active_reach",
        lambda self: None if self.topology is None else self.topology.reach & self.link_state,
    )


def match_over_degree(monkeypatch):
    """The b-matching ignores the degree limits."""
    match = Topology.match
    monkeypatch.setattr(
        Topology,
        "match",
        lambda self, weight, **kw: match(
            dataclasses.replace(self, degree=None, channel_degree=None), weight, **kw
        ),
    )


@pytest.mark.parametrize(
    "fault, check",
    [
        pytest.param(fault, check, id=fault.__name__)
        for fault, check in [
            (alter_stage1, None),
            (alter_stage2, None),
            (half_batch, None),
            (commit_unchanged, None),
            (views_on_every_link, "transfers_off_their_links"),
            (match_over_degree, "matchings_over_degree"),
        ]
    ],
)
def test_fault_makes_the_run_incorrect(fault, check, monkeypatch, tiny):
    """Each fault breaks some limit; a topology fault breaks its own check."""
    fault(monkeypatch)
    line = tiny.run("topo8")
    assert not line["correct"]
    broken = [k for k, c in line["checks"].items() if c["value"] > c["limit"]]
    assert broken, line["checks"]
    assert check is None or check in broken, line["checks"]


def test_traced_run_reads_the_span_metrics(tiny):
    """On this backend the trace holds no TPU plane: the readers of device
    time find nothing and leave their metrics out; every registered reader
    of the program's spans and counters reads."""
    bench = tiny.bench()
    line = tiny.run("topo8", trace=1, bench=bench)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 8  # the first stream, once
    assert set(line["metrics"]) == tiny.program_metrics(bench)
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line)[-1] == "checks"
