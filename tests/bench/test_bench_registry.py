"""The harness finds configurations, traffic mixes and metrics by name."""

import functools
import json
import shutil

import pytest

from benchmarks.chip import harness

BENCH = harness.load_benchmark()


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves(entry):
    assert harness.cell(entry["name"], BENCH) is entry
    cfg = harness.config(entry["config"])
    assert cfg["name"] == entry["config"]
    assert harness.traffic(entry["traffic"])["arrivals"] == "poisson_pool"


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(harness.metric_reader(metric["name"]))


@pytest.mark.parametrize(
    "find, name",
    [
        (harness.config, "no_such_config"),
        (harness.traffic, "no_such_traffic"),
        (harness.metric_reader, "no_such_metric"),
        (harness.config, "../configs/prod8"),
        (harness.traffic, "a b"),
        (harness.metric_reader, "/etc/passwd"),
    ],
)
def test_unknown_or_bad_names_are_refused(find, name):
    with pytest.raises(ValueError):
        find(name)


def test_unknown_cell_is_refused():
    with pytest.raises(ValueError):
        harness.cell("prod8.nothing", BENCH)


def test_a_new_cell_and_metric_are_files_and_entries_alone(tmp_path, monkeypatch, tiny):
    """A later cell, mix and span metric: new files beside the old ones and
    new entries in BENCHMARK.json, with no edit to any file already there.
    A traced run then reads the new metric with the registered ones."""
    root = tmp_path / "chip"
    shutil.copytree(harness.CHIP_DIR, root, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "traffic" / "sparse.json").write_text(
        json.dumps({"name": "sparse", "arrivals": "poisson_pool", "rate": 0.005,
                    "n_jobs": 60, "pool_seed": 0, "streams": 1})
    )
    (root / "metrics" / "plan_ms.py").write_text(
        "def read(red):\n"
        "    t = red.span_seconds('plan_batch')\n"
        "    return 1e3 * t / red.n_epochs if red.n_epochs and t else None\n"
    )
    bench = tiny.bench()
    bench["workloads"].append(
        {"name": "prod8.sparse", "config": "prod8", "traffic": "sparse", "chips": 1,
         "why": "sparse load"}
    )
    bench["per_layer"].append(
        {"name": "plan_ms", "unit": "ms", "better": "lower", "source": "program_span",
         "layer": "epoch loop", "moves": "epoch_p95_ms"}
    )
    entry = harness.cell("prod8.sparse", bench)
    assert harness.config(entry["config"], root)["cluster"]["n_racks"] == 8
    assert harness.traffic(entry["traffic"], root)["rate"] == 0.005

    # The harness reads its readers from the copy, as a later checkout would.
    reader = functools.partial(harness.metric_reader, root=root)
    monkeypatch.setattr(harness, "metric_reader", reader)
    line = tiny.run("topo8", trace=1, bench=bench)
    assert "plan_ms" in tiny.program_metrics(bench)
    assert set(line["metrics"]) == tiny.program_metrics(bench)
    assert line["metrics"]["plan_ms"]["value"] > 0
    assert all(p.read_bytes() == b for p, b in before.items())
