"""BENCHMARK.json against the contract's limits and the files it names."""

import json
import os
import re

import pytest

from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return manifest.benchmark()


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert len(bench["command"]) <= 32 and all(_line(w) for w in bench["command"])
    assert bench["paths"] == ["benchmark"]
    # 2 + 14 runs a cell, 24 cells, inside 43200 s.
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_text(bench):
    entries = (bench["configs"] + bench["workloads"] + bench["end_to_end"]
               + bench["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for group in ("configs", "workloads"):
        assert len({e["name"] for e in bench[group]}) == len(bench[group])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert _line(m["layer"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["why"]) and _line(c["source"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _line(w["why"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["train_fsdp4"]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_reduced_names_no_width(bench):
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|"
                       r"head_size|expansion|experts_per_tok")
    for c in bench["configs"]:
        for key in c["reduced"]:
            assert not width.search(key), key


def test_every_cells_files_exist(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        cell = manifest.cell(w["name"])      # loads config, traffic, workload
        used.add(w["config"])
        cfg = configs[w["config"]]
        assert cfg["file"].startswith("benchmark/")
        assert cell["config"]["source"] == cfg["source"]
        assert set(cfg["reduced"]) == set(cell["config"]["reduced"])
        runner = cell["workload"]["runner"]
        assert os.path.exists(os.path.join(manifest.HERE, "runners", runner + ".py"))
    assert used == set(configs), "a configuration no cell uses"
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for m in bench["per_layer"]:
        spec = manifest.metric_file(m["name"])
        assert os.path.exists(os.path.join(
            manifest.HERE, "readers", spec["reader"] + ".py")), m["name"]


def test_every_cell_reports_what_the_contract_asks(bench):
    for w in bench["workloads"]:
        cell = manifest.cell(w["name"])
        e2e = set(manifest.names(cell["end_to_end"]))
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert m["moves"] in e2e, (w["name"], m["name"], m["moves"])


def test_metric_workloads_name_cells(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]


def test_files_under_paths_have_allowed_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(manifest.HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), manifest.ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel


def test_data_files_are_json():
    for sub in ("configs", "traffic", "workloads", "metrics"):
        for f in os.listdir(os.path.join(manifest.HERE, sub)):
            assert f.endswith(".json"), f
            with open(os.path.join(manifest.HERE, sub, f)) as fh:
                json.load(fh)
