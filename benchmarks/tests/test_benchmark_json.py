"""BENCHMARK.json against the contract's limits that can be checked here, and
against the files it names: every entry is found by its name."""

import json
import os
import re

import pytest

from benchmarks.harness.byname import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    return load_module(os.path.join(ROOT, "metrics", name + ".py"))


def cells_of(bench, metric):
    return metric.get("workloads", [w["name"] for w in bench["workloads"]])


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json")) <= 64 * 1024
    # the whole check with the full 24 cells fits the contract's budget
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        group_names = [e["name"] for e in bench[group]]
        assert len(set(group_names)) == len(group_names), group
        names += group_names
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for n in names:
        assert NAME.match(n), n
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_entries_have_just_the_contracts_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line(c["source"]) and line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and line(w["why"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert line(m["layer"])


def test_cells_and_configurations(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert c["file"].startswith("benchmarks/")
        with open(os.path.join(CHECKOUT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.isfile(os.path.join(ROOT, "deployments",
                                           cfg["kind"] + ".py"))
    for w in bench["workloads"]:
        with open(os.path.join(CHECKOUT, configs[w["config"]]["file"])) as f:
            assert json.load(f)["chips"] == w["chips"]
        with open(os.path.join(ROOT, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(ROOT, "drivers",
                                           traffic["driver"] + ".py"))
        for st in traffic.get("statements", []):
            assert os.path.isfile(os.path.join(ROOT, st["sql"]))


def test_every_cell_reports_what_the_contract_asks(bench):
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.25
    for w in bench["workloads"]:
        e2e = [m["name"] for m in bench["end_to_end"]
               if w["name"] in cells_of(bench, m)]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert any(w["name"] in cells_of(bench, m) for m in bench["per_layer"])
    cell_names = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(cells_of(bench, m)) <= cell_names, m["name"]


def test_every_moves_names_an_end_to_end_metric_its_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert set(cells_of(bench, m)) <= \
            set(cells_of(bench, e2e[m["moves"]])), m["name"]


def test_every_metric_has_a_reader_that_agrees_with_its_entry(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        mod = reader(m["name"])
        assert mod.UNIT == m["unit"] and mod.SOURCE == m["source"], m["name"]
        assert callable(mod.read)
        if "layer" in m:
            assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"]), m["name"]


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_./-]+$")
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(base, f), CHECKOUT))
