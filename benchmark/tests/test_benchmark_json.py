"""BENCHMARK.json against the contract's limits, and every file a cell
names found by name."""

import json
import os
import re

import pytest

from benchmark import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head")


@pytest.fixture(scope="module")
def bench():
    return run.load_json(os.path.join(run.CHECKOUT, "BENCHMARK.json"))


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert len(json.dumps(bench)) < 64 * 1024
    assert all(1 <= len(w) <= 200 for w in bench["command"])


def test_names_units_sources(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
    assert any("mfu" in m["name"] for m in bench["per_layer"])


def test_cells_and_configs(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = bench["workloads"]
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert {c["config"] for c in cells} == set(configs)
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4) and 1 <= len(c["why"]) <= 200
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/") and len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        with open(os.path.join(run.CHECKOUT, c["file"])) as f:
            data = json.load(f)
        assert data["reduced"] == c["reduced"] and data["name"] == c["name"]


def test_every_file_is_found_by_name(bench):
    for cell in bench["workloads"]:
        parts = run.resolve(bench, cell["name"])
        for fn in ("setup", "window", "check"):
            assert callable(getattr(parts["driver"], fn))
        assert os.path.exists(os.path.join(
            parts["config_dir"], parts["config"]["name"] + ".py"))
        assert os.path.exists(os.path.join(
            run.HERE, "generators", parts["traffic"]["generator"] + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        path = os.path.join(run.HERE, "metrics", m["name"] + ".py")
        assert callable(run.load_module(path, "m").read)


def test_harness_names_no_model_cell_or_metric(bench):
    with open(os.path.join(run.HERE, "run.py")) as f:
        text = f.read().lower()
    words = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    words += [c["name"] for c in bench["workloads"] + bench["configs"]]
    words += ["resnet", "yolo", "lenet"]
    assert not [w for w in words if w.lower() in text]
