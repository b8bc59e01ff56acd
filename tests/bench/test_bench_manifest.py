"""BENCHMARK.json against the benchmark's contract, and the files it names."""
import json
import re
import shutil

import pytest

from bench_cells import MANIFEST, ROOT, run, run_tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    cells = len(MANIFEST["workloads"])
    budget = (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200
    assert budget <= 43200 and 1 <= cells <= 24
    for p in MANIFEST["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert len(json.dumps(MANIFEST)) < 64 * 1024


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_are_unique_and_well_formed(section):
    names = [x["name"] for x in MANIFEST[section]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_metric_units_sources_and_direction(section):
    for m in MANIFEST[section]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        if section == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["moves"] in {e["name"] for e in MANIFEST["end_to_end"]}
            assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_every_cell_reports_setup_another_metric_and_a_layer():
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 2)
    for w in MANIFEST["workloads"]:
        e2e = {m["name"] for m in run.metrics_for(MANIFEST, w["name"],
                                                  "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = run.metrics_for(MANIFEST, w["name"], "per_layer")
        assert layers
        for m in layers:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_every_file_is_found_by_name():
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for c in MANIFEST["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert c["file"].startswith("bench/")
        assert any(w["config"] == c["name"] for w in MANIFEST["workloads"])
    for w in MANIFEST["workloads"]:
        entry, workload, config = run.lookup(MANIFEST, w["name"])
        assert workload["config"] == w["config"]
        assert config["chips"] == w["chips"] in (1, 4)
        driver = run.load_driver(workload["driver"])
        for step in ("prepare", "measure", "release", "verify"):
            assert callable(getattr(driver, step))
        assert workload["limits"]
        assert len(w["why"]) <= 200
    for m in MANIFEST["per_layer"]:
        assert callable(run.load_reader(m["name"]))
        assert set(m.get("workloads", cells)) <= cells


def test_every_file_of_a_cell_config_or_metric_is_named_by_the_manifest():
    """No configuration, workload or metric reader lies in the benchmark's
    directories without its manifest entry."""
    names = {"configs": {c["file"] for c in MANIFEST["configs"]},
             "workloads": {f"bench/workloads/{w['name']}.json"
                           for w in MANIFEST["workloads"]},
             "metrics": {f"bench/metrics/{m['name']}.py"
                         for m in MANIFEST["per_layer"]}}
    for kind, named in names.items():
        found = {str(p.relative_to(ROOT))
                 for p in (ROOT / "bench" / kind).iterdir()
                 if p.suffix in (".json", ".py")}
        assert found == named, kind


def test_a_config_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    """A dummy configuration, cell and per-layer metric, added to a copy of
    the manifest and the benchmark's directory, run through the harness
    unchanged."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads(json.dumps(MANIFEST))
    cfg = json.loads((ROOT / "bench/configs/hpl-n28672.json").read_text())
    cfg.update(name="dummy-n192", n=192, nb=64)
    (tmp_path / "bench/configs/dummy-n192.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/workloads/dummy-n192.fresh.json").write_text(
        json.dumps({"config": "dummy-n192", "driver": "batch_solve",
                    "limits": {"hpl_ratio": 16.0}}))
    (tmp_path / "bench/metrics/dummy.solves.py").write_text(
        "def read(cell, trace):\n    return float(cell.attempted)\n")
    manifest["configs"].append(
        {"name": "dummy-n192", "source": "https://example.org",
         "file": "bench/configs/dummy-n192.json", "reduced": ["n"],
         "why": "a test"})
    manifest["workloads"].append(
        {"name": "dummy-n192.fresh", "config": "dummy-n192",
         "traffic": "fresh", "chips": 1, "why": "a test"})
    for m in manifest["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("dummy-n192.fresh")
    manifest["per_layer"].append(
        {"name": "dummy.solves", "unit": "solves", "better": "higher",
         "source": "program_counter", "layer": "direct engine",
         "moves": "solve_s", "workloads": ["dummy-n192.fresh"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    manifest = run.load_manifest(tmp_path)
    entry, workload, config = run.lookup(manifest, "dummy-n192.fresh",
                                         tmp_path)
    common = dict(entry=entry, workload=workload, config=config,
                  manifest=manifest, bench=tmp_path / "bench")
    plain = run_tiny("hpl-n28672.fresh", seconds=0.2, **common)
    assert plain["correct"] and set(plain["metrics"]) == {"setup_s",
                                                          "solve_s"}
    traced = run_tiny("hpl-n28672.fresh", seconds=0.2, trace=True, **common)
    assert traced["metrics"]["dummy.solves"]["value"] == traced["attempted"]
    assert list(traced)[-1] == "checks"
