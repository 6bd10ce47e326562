"""BENCHMARK.json against the files it names: everything resolves by
name, and names, units and lengths keep to the allowed characters."""
import json
import os
import re

import pytest

import run as harness

ROOT = harness.ROOT
HERE = harness.HERE
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(ROOT, "BENCHMARK.json")


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_name_resolves(bench):
    """A cell, configuration, query, mix and metric is found by its
    name alone: adding one is adding files and entries."""
    used = set()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        used.add(w["config"])
        config = cell["config"]
        assert config["name"] == w["config"]
        assert os.path.exists(os.path.join(
            HERE, "datagen", f"{config['schema']}.py"))
        assert set(cell["texts"]) == set(config["queries"])
        for q in config["queries"]:
            base = os.path.join(HERE, "queries", w["config"], q)
            needs = harness.load_json(base + ".json")["tables"]
            assert os.path.exists(base + ".py")
            for table, columns in needs.items():
                have = config["tables"][table]["columns"]
                assert set(columns) <= set(have), (q, table)
        for key in ("wrong_cells", "max_rel_gap"):
            assert key in config["limits"]
        generator = harness.load_generator(cell["mix"])
        assert callable(generator.warm_up) and callable(generator.measure)
        for m in cell["per_layer"]:
            assert callable(harness.metric_reader(m["name"]))
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/")
        config = harness.load_json(ROOT, c["file"])
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])


def test_file_names():
    for d, _dirs, files in os.walk(HERE):
        if any(part.startswith(".") or part == "__pycache__"
               for part in os.path.relpath(d, HERE).split(os.sep)
               if part != "."):
            continue
        for f in files:
            if f == ".gitignore":
                continue
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            assert FILE.match(rel) and len(rel) <= 200, rel


def test_peaks_table():
    peaks = harness.load_json(HERE, "peaks.json")
    for kind, row in peaks.items():
        assert row["hbm_gbps"] > 0 and row["source"], kind
