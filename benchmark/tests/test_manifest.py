"""BENCHMARK.json (and candidates.json) against the contract's shape and
against the files they name."""

import json
import os
import re

import pytest

from benchmark import plugins

ROOT = plugins.ROOT
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


MANIFEST = load("BENCHMARK.json")
CANDIDATES = load("benchmark/candidates.json")


def test_the_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert MANIFEST["paths"] == ["benchmark"]
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


@pytest.mark.parametrize("doc", [MANIFEST, CANDIDATES], ids=["BENCHMARK", "candidates"])
def test_names_whys_and_files(doc):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in doc[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names), names
    units = [m["unit"] for k in ("end_to_end", "per_layer") for m in doc[k]]
    assert all(UNIT.match(u) for u in units), units
    for e in doc["configs"] + doc["workloads"]:
        assert len(e["why"]) <= 200, (e["name"], len(e["why"]))
    for c in doc["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        held = load(c["file"])
        assert held["name"] == c["name"] and held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert set(held["reduced_why"]) == set(c["reduced"])
        assert c["source"].startswith("https://")
    files = [c["file"] for c in doc["configs"]]
    assert len(files) == len(set(files))


def test_every_cell_resolves_to_files_and_every_config_has_a_cell():
    both = {k: MANIFEST[k] + CANDIDATES[k] for k in ("configs", "workloads")}
    configs = {c["name"] for c in both["configs"]}
    for w in both["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        traffic = plugins.load_json("traffic", w["traffic"])
        assert hasattr(plugins.load("drivers", traffic["driver"]), "run")
    for doc in (MANIFEST, CANDIDATES):
        used = {w["config"] for w in doc["workloads"]}
        assert {c["name"] for c in doc["configs"]} <= used
    pairs = [(w["config"], w["traffic"]) for w in both["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert 2 <= len(MANIFEST["workloads"]) <= 24
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


def test_end_to_end_metrics_and_bounds():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] == 0.1
    for m in MANIFEST["end_to_end"] + CANDIDATES["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("doc", [MANIFEST, CANDIDATES], ids=["BENCHMARK", "candidates"])
def test_every_per_layer_metric_has_its_reader(doc):
    e2e = {m["name"] for m in MANIFEST["end_to_end"] + CANDIDATES["end_to_end"]}
    readers = set(plugins.names("layer_metrics"))
    for m in doc["per_layer"]:
        assert m["name"] in readers, f"no reader file for {m['name']}"
        mod = plugins.load("layer_metrics", m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"],
                                                    m["moves"])
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert m["better"] in ("lower", "higher") and "bound" not in m
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_reader_is_listed_somewhere():
    listed = {m["name"] for m in MANIFEST["per_layer"] + CANDIDATES["per_layer"]}
    assert set(plugins.names("layer_metrics")) == listed


def test_peaks_name_their_source():
    peaks = load("benchmark/peaks.json")
    row = peaks["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in row["source"]
    from benchmark import roofline
    with pytest.raises(SystemExit):
        roofline.peak_row("TPU v9 imaginary")


def test_the_histogram_floor_at_the_cells_shapes():
    from benchmark import roofline
    peak = roofline.peak_row("TPU v5 lite")
    # 11M x 28 int8 bins + node id + three float statistics = 484 MB a level
    s, bound = roofline.hist_build_floor(11_000_000, 28, 1, 1, peak)
    assert bound == "memory" and s == pytest.approx(0.484e9 / 819e9)
    assert roofline.bin_bytes(64) == 1 and roofline.bin_bytes(256) == 2
