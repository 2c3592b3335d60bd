"""The test-sized cells under ``cells/``, run on this backend."""

import json
from pathlib import Path

import pytest

from benchmarks.chip import harness
from benchmarks.chip import run as bench_run


class TinyCells:
    """``BENCHMARK.json`` with its cells replaced by ``tiny_<config>.few``
    and every per-layer metric registered for them."""

    root = Path(__file__).resolve().parent / "cells"
    seed = 2**31 + 9
    peak = {"flops_per_s": 1.97e14, "bytes_per_s": 8.19e11}

    @staticmethod
    def bench() -> dict:
        bench = json.loads(json.dumps(harness.load_benchmark()))
        bench["workloads"] = [
            {"name": f"tiny_{c}.few", "config": f"tiny_{c}", "traffic": "few", "chips": 1,
             "why": "test size"}
            for c in ("prod8", "topo8")
        ]
        for m in bench["per_layer"]:
            m["workloads"] = [w["name"] for w in bench["workloads"]]
        return bench

    def run(self, config: str, trace: int = 0, bench: dict | None = None) -> dict:
        """One run: one round of the window, or with ``trace`` the traced serve."""
        return bench_run.run(
            f"tiny_{config}.few", self.seed, 0.0, trace, bench or self.bench(),
            root=self.root, peak=self.peak,
        )

    @staticmethod
    def program_metrics(bench: dict) -> set:
        """The per-layer metrics read from the program's spans and counters."""
        sources = {"program_span", "program_counter"}
        return {m["name"] for m in bench["per_layer"] if m["source"] in sources}


@pytest.fixture
def tiny() -> TinyCells:
    return TinyCells()
