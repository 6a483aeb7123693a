"""BENCHMARK.json against the contract's letter and against the files the
harness finds by name: no run is needed to refuse a manifest that a check
would refuse."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def line(text) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(manifest["command"]) <= 32
    assert all(line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    script = manifest["command"][1]
    assert any(script.startswith(p + "/") for p in manifest["paths"])
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_entries(manifest):
    for group, keys in (("configs", {"name", "source", "file", "reduced", "why"}),
                        ("workloads", {"name", "config", "traffic", "chips", "why"})):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names))
        for e in manifest[group]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]) and line(e["why"])
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert line(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"].split("_"):
            assert m["unit"] == "%"


def test_every_file_a_cell_names_exists(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    used = set()
    pairs = set()
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        cfg = configs[w["config"]]
        assert any(cfg["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(ROOT, cfg["file"])) as fh:
            body = json.load(fh)
        assert body["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
        assert line(cfg["source"])
        tpath = os.path.join(ROOT, "benchmarks", "traffic", w["traffic"] + ".json")
        with open(tpath) as fh:
            traffic = json.load(fh)
        for kind, name in (("jobs", traffic["job"] + ".py"),
                           ("limits", traffic["job"] + ".json")):
            assert os.path.isfile(os.path.join(ROOT, "benchmarks", kind, name))
    assert used == set(configs), "a configuration no cell uses"
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_every_metric_has_a_reader_and_moves_what_its_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", "metrics",
                                           m["name"] + ".py")), m["name"]
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in manifest["per_layer"]:
        assert m["moves"] in reports, m
        assert set(m.get("workloads", cells)) <= reports[m["moves"]], m
    for cell in cells:
        e2e = [n for n, ws in reports.items() if cell in ws]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in m.get("workloads", cells) for m in manifest["per_layer"])


def test_files_under_paths_are_named_from_name_characters(manifest):
    for p in manifest["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel
