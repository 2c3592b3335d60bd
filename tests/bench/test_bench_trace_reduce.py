"""The trace reduction and the per-layer readers on a small synthetic trace."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import counts, harness, trace_reduce
from benchmarks.chip.trace_reduce import Reduced

PEAK = {"flops_per_s": 1.97e14, "bytes_per_s": 8.19e11}
# The Pallas call as a TPU trace names it.
KERNEL = (
    "%batched_combined_lb.1 = f32[512,1]{1,0:T(8,128)S(1)} custom-call(f32[512,16,16]"
    '{2,1,0:T(8,128)S(1)} %w), custom_call_target="tpu_custom_call"'
)


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float


def synthetic() -> Reduced:
    """Two epochs of 1 s; device busy 0.1-0.3 (stage 1) and 1.2-1.3 (stage 2)."""
    ops = [
        ("fusion.1", 0.10, 0.05),
        (KERNEL, 0.15, 0.10),  # the Pallas call, inside stage 1
        ("fusion.2", 0.20, 0.10),  # overlaps the kernel's tail
        ("while.3", 1.20, 0.10),
    ]
    modules = [
        ("jit__fleet_lb_device(123)", 0.10, 0.20),
        ("jit__unknown(456)", 1.20, 0.10),
    ]
    host = [
        ("epoch", 0.0, 1.0),
        ("plan_batch", 0.06, 0.8),
        ("stage1_launch", 0.08, 0.35),
        ("arbitrate_and_commit", 0.8, 1.0),
        ("epoch", 1.0, 2.0),
        ("stage2_launch", 1.15, 1.35),
        ("arbitrate_and_commit", 1.5, 2.0),
    ]
    spans = [
        Span("epoch", 0.0, 1.0),
        Span("schedule_fleet", 0.05, 0.8),
        Span("stage1_launch", 0.08, 0.35),
        Span("arbitrate_and_commit", 0.8, 1.0),
        Span("epoch", 1.0, 2.0),
        Span("schedule_fleet", 1.1, 1.4),
        Span("stage2_launch", 1.15, 1.35),
        Span("arbitrate_and_commit", 1.5, 2.0),
    ]
    return Reduced(
        ops=ops,
        modules=modules,
        host=host,
        spans=spans,
        counters={"stage1_launches": 1.0, "stage2_launches": 3.0},
        n_epochs=2,
        window_s=2.0,
        stage1_shapes=[(4096, 16, 9, False)],
        peak=PEAK,
    )


def test_busy_union_and_idle_gaps():
    red = synthetic()
    assert trace_reduce.union_seconds(red.ops) == pytest.approx(0.3)
    gaps = trace_reduce.idle_gaps(red.ops, 0.0, 2.0)
    assert [t for g in gaps for t in g] == pytest.approx([0.3, 1.2, 1.3, 2.0, 0.0, 0.1])
    assert trace_reduce.innermost(red.host, 0.05) == "epoch"
    assert trace_reduce.innermost(red.host, 0.5) == "plan_batch"
    assert trace_reduce.innermost(red.host, 1.75) == "arbitrate_and_commit"
    assert trace_reduce.innermost(red.host, 5.0) == "outside_spans"


def test_breakdown_labels_gaps_by_host_span():
    out = trace_reduce.breakdown(synthetic())
    assert out["device_ops"] == [
        ["jit__fleet_lb_device/%batched_combined_lb.1", pytest.approx(0.10)],
        ["jit__fleet_lb_device/fusion.2", pytest.approx(0.10)],
        ["jit__unknown/while.3", pytest.approx(0.10)],
        ["jit__fleet_lb_device/fusion.1", pytest.approx(0.05)],
    ]
    labels = [g[0] for g in out["idle_gaps"]]
    # Midpoints 0.75, 1.65 and 0.05: plan, commit, and the bare epoch.
    assert labels == ["plan_batch", "arbitrate_and_commit", "epoch"]
    assert [g[1] for g in out["idle_gaps"]] == pytest.approx([0.9, 0.7, 0.1])


EXPECTED = {
    "commit_ms": 1e3 * (0.2 + 0.5) / 2,
    "fleet_host_ms": 1e3 * ((0.75 + 0.3) - (0.27 + 0.2)) / 2,
    "launches_per_epoch": 2.0,
    "launch_wait_ms": 1e3 * ((0.27 + 0.2) - (0.2 + 0.1)) / 2,
    "stage1_device_ms": 1e3 * 0.2 / 2,
    "stage2_device_ms": 1e3 * 0.1 / 2,
    "device_idle_pct": 100 * (1 - 0.3 / 2.0),
    "batched_combined_lb_roofline": 100
    * counts.stage1_bytes(4096, 16, False)
    / 8.19e11
    / 0.10,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_synthetic_trace(name):
    value = harness.metric_reader(name)(synthetic())
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_an_empty_trace(name):
    empty = dataclasses.replace(
        synthetic(), ops=[], modules=[], spans=[], counters={}, stage1_shapes=[]
    )
    assert harness.metric_reader(name)(empty) is None


def test_roofline_reads_nothing_when_kernels_and_launches_disagree():
    red = dataclasses.replace(synthetic(), stage1_shapes=[(4096, 16, 9, False)] * 2)
    assert harness.metric_reader("batched_combined_lb_roofline")(red) is None


def test_read_xspace_finds_host_annotations(tmp_path):
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("epoch"):
        with jax.profiler.TraceAnnotation("stage2_launch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    ops, modules, host = trace_reduce.read_xspace(path)
    names = [n for n, _s, _e in host]
    assert names == ["epoch", "stage2_launch"]
    (_, s0, e0), (_, s1, e1) = host
    assert s0 <= s1 < e1 <= e0
    assert ops == [] and modules == []  # no TPU plane on this backend
