"""Reduce a profiler trace and the program's spans to what the metrics read.

:func:`read_xspace` pulls three lists out of a ``.xplane.pb`` file: the
device's operations and its programs (``XLA Ops`` / ``XLA Modules`` lines of
the ``/device:TPU:0`` plane), and the host annotations the benchmark's tracer
opened for each program span. :class:`Reduced` holds them with the
program's own spans and counters; the readers in ``metrics/`` take their
numbers from it. All times are seconds on the profiler's clock.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

__all__ = [
    "Reduced",
    "read_xspace",
    "union_seconds",
    "idle_gaps",
    "innermost",
    "breakdown",
]

SPAN_NAMES = (
    "epoch",
    "collect_arrivals",
    "plan_batch",
    "arbitrate_and_commit",
    "schedule_fleet",
    "stage1_launch",
    "stage2_launch",
)
STAGE1_PROGRAM = "jit__fleet_lb_device"
# The stage-2 evaluator is jitted from a functools.partial, which has no
# name: its programs reach the trace as ``jit__unknown(<hash>)``, the only
# unnamed programs of a serve.
STAGE2_PROGRAM = "jit__unknown"


@dataclasses.dataclass
class Reduced:
    ops: list  # (name, start_s, dur_s) device operations, by start
    modules: list  # (name, start_s, dur_s) device programs, by start
    host: list  # (name, start_s, end_s) host annotations, by start
    spans: list  # the program tracer's spans (name, t0, t1 in its own clock)
    counters: dict  # the program tracer's counters
    n_epochs: int
    window_s: float  # length of the traced window
    stage1_shapes: list  # (B, n_pad, n_iters, masked) per stage-1 launch
    peak: dict  # the device's peaks: flops_per_s, bytes_per_s

    def span_seconds(self, name: str) -> float:
        return sum(s.t1 - s.t0 for s in self.spans if s.name == name)

    def program_seconds(self, prefix: str) -> float:
        return sum(d for n, _s, d in self.modules if n.startswith(prefix))

    def kernel_events(self) -> list:
        """Device operations of the stage-1 Pallas kernel, in launch order."""
        return [o for o in self.ops if is_stage1_kernel(o[0])]


def is_stage1_kernel(name: str) -> bool:
    """The Pallas custom call inside the stage-1 program: the trace shows it
    as ``%batched_combined_lb.<k> = ... custom-call(...),
    custom_call_target="tpu_custom_call"``."""
    return name.startswith("%batched_combined_lb") and "tpu_custom_call" in name


def _events(line):
    return [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events]


def read_xspace(path: str | Path) -> tuple[list, list, list]:
    """(device ops, device programs, host annotations) of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, modules, host = [], [], []
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += _events(line)
                elif line.name == "XLA Modules":
                    modules += _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [
                    (n, s, s + d) for n, s, d in _events(line) if n in SPAN_NAMES
                ]
    key = lambda e: e[1]  # noqa: E731
    return sorted(ops, key=key), sorted(modules, key=key), sorted(host, key=key)


def union_seconds(intervals) -> float:
    """Length of the union of ``(name, start, dur)`` intervals."""
    total, end = 0.0, float("-inf")
    for _n, s, d in sorted(intervals, key=lambda e: e[1]):
        e = s + d
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def idle_gaps(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    """Stretches of ``[t0, t1]`` in which no interval runs, longest first."""
    gaps, cursor = [], t0
    for _n, s, d in sorted(intervals, key=lambda e: e[1]):
        if s > cursor:
            gaps.append((cursor, min(s, t1)))
        cursor = max(cursor, s + d)
        if cursor >= t1:
            break
    if cursor < t1:
        gaps.append((cursor, t1))
    gaps = [(a, b) for a, b in gaps if b > a]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def innermost(host, t: float) -> str:
    """Name of the shortest host annotation covering time ``t``."""
    covering = [(e - s, n) for n, s, e in host if s <= t <= e]
    return min(covering)[1] if covering else "outside_spans"


def op_label(op_name: str, program: str) -> str:
    """``<program>/<op>``: the program's name without its hash and the HLO
    instruction's name without its signature."""
    return f"{program.split('(')[0]}/{op_name.split(' = ')[0]}"


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device operations that took most time, by program and
    instruction, and the longest idle gaps labelled by the host span they
    fell in."""
    by_name: dict[str, float] = {}
    modules, k = red.modules, 0
    for n, s, d in red.ops:
        while k < len(modules) and modules[k][1] + modules[k][2] < s:
            k += 1
        inside = k < len(modules) and modules[k][1] <= s
        label = op_label(n, modules[k][0] if inside else "outside_programs")
        by_name[label] = by_name.get(label, 0.0) + d
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    epochs = [(s, e) for n, s, e in red.host if n == "epoch"]
    gaps = []
    if epochs:
        t0, t1 = min(s for s, _ in epochs), max(e for _, e in epochs)
        for a, b in idle_gaps(red.ops, t0, t1)[:top]:
            gaps.append([innermost(red.host, (a + b) / 2), b - a])
    return {
        "device_ops": [[n, s] for n, s in device_ops],
        "idle_gaps": gaps,
    }
