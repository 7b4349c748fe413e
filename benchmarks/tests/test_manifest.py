"""BENCHMARK.json against the contract's limits, and every file a cell or a
metric needs found by its name."""

import json
import os
import re

import pytest

from benchmarks import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert manifest["paths"] == ["benchmarks"]
    assert manifest["command"] == ["python3", "benchmarks/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128


def test_names_units_and_lines(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in {e["name"] for e in manifest["end_to_end"]}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        if "roofline" in m["name"]:
            assert m["unit"] == "%"
    for group in ("configs", "workloads"):
        got = [e["name"] for e in manifest[group]]
        assert len(got) == len(set(got))
        for e in manifest[group]:
            assert NAME.match(e["name"])
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}


def test_cells_configs_and_quota(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    pairs = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    assert used == set(configs)
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/")
        assert len(c["reduced"]) <= 16
        assert 1 <= len(c["source"]) <= 200
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert body["guarantees"], "a deployment states its guarantees"


def test_every_cell_reports_enough_and_finds_its_files(manifest):
    search = [os.path.join(ROOT, p) for p in manifest["paths"]]
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
    for w in manifest["workloads"]:
        with open(run.find(search, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(run.find(search, "runners", mix["runner"] + ".py"))
        e2e = [m["name"] for m in manifest["end_to_end"]
               if run.reports(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layer = run.layer_metrics_of(manifest, w["name"])
        assert layer, w["name"]
        for m in layer:
            # each reports the end-to-end metric that the metric should move
            assert m["moves"] in e2e, (m["name"], w["name"])
            reader = run.load_module(
                run.find(search, "layer_metrics",
                         m["name"].split(".")[0] + ".py"), "reader_under_test")
            assert callable(reader.read)


def test_a_share_of_a_peak_lists_every_cell_that_reports_what_it_moves(manifest):
    """What PR 29 met on the chip: a claim in a cell needs every share of a
    roofline or of a peak that moves the cell's end-to-end metric reported
    there, and one scan read under two names could be reported in no cell at
    once.  So each such metric lists every cell that reports the metric it
    moves; a PR that adds a cell adds its name to these lists."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    shares = [m for m in manifest["per_layer"]
              if "roofline" in m["name"] or "mfu" in m["name"]]
    assert shares
    for m in shares:
        cells = {w["name"] for w in manifest["workloads"]
                 if run.reports(e2e[m["moves"]], w["name"])}
        assert set(m.get("workloads", cells)) == cells, m["name"]
    # the whole step's share stands beside each kernel's roofline
    for m in shares:
        if "roofline" in m["name"]:
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and o.get("workloads") == m.get("workloads")
                       for o in shares), m["name"]


def test_files_under_paths_are_named_from_name_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in manifest["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__")]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel
