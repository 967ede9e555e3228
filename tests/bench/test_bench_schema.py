"""BENCHMARK.json against the contract's letter, and against the files."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _entries(spec):
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in spec[section]:
            yield section, e


def test_top_level_keys_and_limits(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51 and isinstance(spec["run_seconds"], int)
    assert 1 <= len(spec["paths"]) <= 16 and len(spec["command"]) <= 32
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for p in spec["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and os.path.isdir(os.path.join(ROOT, p))


def test_every_name_and_unit_has_the_allowed_characters(spec):
    seen = {}
    for section, e in _entries(spec):
        assert NAME.match(e["name"]), (section, e["name"])
        group = "metric" if section in ("end_to_end", "per_layer") else section
        assert e["name"] not in seen.setdefault(group, set()), e["name"]
        seen[group].add(e["name"])
        if "unit" in e:
            assert UNIT.match(e["unit"]), e
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        for key in ("why", "layer", "source"):
            if key in e and section in ("configs", "workloads", "per_layer"):
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
    for w in spec["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for c in spec["configs"]:
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16


def test_entries_have_exactly_the_contract_keys(spec):
    want = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
    }
    for section, e in _entries(spec):
        assert set(e) - {"workloads"} == want[section], (section, e["name"])


def test_bounds_and_end_to_end_sources(spec):
    names = [m["name"] for m in spec["end_to_end"]]
    assert "setup_s" in names
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_setup_another_metric_and_a_layer(spec):
    from benchmark import harness

    bench = harness.Bench(ROOT)
    used_configs = set()
    for w in spec["workloads"]:
        cell = bench.cell(w["name"])
        used_configs.add(w["config"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, w["name"]
    assert used_configs == {c["name"] for c in spec["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(1, len(pairs) // 2)


def test_moves_names_a_metric_every_reporting_cell_reports(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in spec["end_to_end"]}
    layers = {}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s", m["name"]
        reporting = set(m.get("workloads", e2e[m["moves"]]))
        assert reporting and reporting <= cells
        assert reporting <= e2e[m["moves"]], (m["name"], reporting - e2e[m["moves"]])
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_every_file_the_entries_name_exists_and_loads(spec):
    from benchmark import harness

    bench = harness.Bench(ROOT)
    files = set()
    for c in spec["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] and cfg["guarantees"]
        assert "assumed" in cfg
    for w in spec["workloads"]:
        traffic = bench.read_json("traffic", w["traffic"] + ".json")
        driver = bench.module("drivers", traffic["driver"])
        for fn in ("setup", "warmup", "window", "check"):
            assert callable(getattr(driver, fn))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] == "setup_s":
            continue
        metric = bench.read_json("metrics", m["name"] + ".json")
        assert callable(bench.module("reducers", metric["reducer"]).reduce)


def test_files_under_paths_are_named_from_name_characters(spec):
    for p in spec["paths"]:
        for folder, dirs, names in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for n in names:
                if n.endswith(".pyc"):
                    continue
                assert re.fullmatch(r"[A-Za-z0-9_.\-]+", n), os.path.join(folder, n)


def test_peaks_table_refuses_an_unknown_device():
    from benchmark import harness

    bench = harness.Bench(ROOT)
    assert bench.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench.peaks("TPU v9 imaginary")
