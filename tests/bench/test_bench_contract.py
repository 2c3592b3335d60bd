"""BENCHMARK.json keeps to the benchmark's contract: keys, names, units."""

import re

import pytest

from benchmarks.chip import harness

BENCH = harness.load_benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_./\-]{1,200}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys():
    assert list(BENCH) == [
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    ]
    assert 1 <= len(BENCH["command"]) <= 32 and all(map(one_line, BENCH["command"]))
    assert 1 <= len(BENCH["paths"]) <= 16
    for path in BENCH["paths"]:
        assert PATH.fullmatch(path) and not path.startswith("/") and ".." not in path
        assert (harness.REPO / path).is_dir()
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51


def test_configs():
    assert 1 <= len(BENCH["configs"]) <= 24
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"]) and one_line(c["source"]) and one_line(c["why"])
        assert c["name"] in used and c["file"] not in files
        files.add(c["file"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert (harness.REPO / c["file"]).is_file()
        assert harness.config(c["name"])["name"] == c["name"]
        assert len(c["reduced"]) <= 16 and all(NAME.fullmatch(k) for k in c["reduced"])


def test_workloads():
    cells = BENCH["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.fullmatch(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and one_line(w["why"])
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def test_metrics():
    e2e, layers = BENCH["end_to_end"], BENCH["per_layer"]
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    assert "setup_s" in {m["name"] for m in e2e} and 1 <= len(e2e) <= 16
    assert 1 <= len(layers) <= 128
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in {"host_clock", "device_trace"}
    for m in layers:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in {x["name"] for x in e2e} and m["source"] in SOURCES
        assert one_line(m["layer"]) and set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + layers:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
