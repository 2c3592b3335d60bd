"""The harness finds configurations, traffic mixes and metrics by name."""

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


def test_a_new_cell_and_metric_are_files_and_entries_alone(tmp_path):
    """A later cell, mix and metric: new files beside the old ones and new
    entries in BENCHMARK.json, with no edit to any file already there."""
    root = tmp_path / "chip"
    shutil.copytree(harness.CHIP_DIR, root, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "traffic" / "light.json").write_text(
        json.dumps({"name": "light", "arrivals": "poisson_pool", "rate": 0.01,
                    "n_jobs": 60, "pool_seed": 0, "streams": 1})
    )
    (root / "metrics" / "epochs_seen.py").write_text(
        "def read(red):\n    return float(red.n_epochs) or None\n"
    )
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(
        {"name": "prod8.light", "config": "prod8", "traffic": "light", "chips": 1,
         "why": "light load"}
    )
    bench["per_layer"].append(
        {"name": "epochs_seen", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "epoch loop", "moves": "jobs_per_s"}
    )
    entry = harness.cell("prod8.light", bench)
    assert harness.config(entry["config"], root)["cluster"]["n_racks"] == 8
    assert harness.traffic(entry["traffic"], root)["rate"] == 0.01
    reader = harness.metric_reader("epochs_seen", root)
    assert reader(type("R", (), {"n_epochs": 3})()) == 3.0
    assert all(p.read_bytes() == b for p, b in before.items())
