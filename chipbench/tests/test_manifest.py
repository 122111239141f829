"""BENCHMARK.json and every file it names load, cross-reference by name
and keep to the contract's limits."""

import importlib
import json
import os

import pytest

from chipbench import harness

from conftest import rehearsal_manifest

REPO = harness.REPO
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# the landed cells alone; with the candidates; and the rehearsal's view
MANIFESTS = {
    "landed": lambda: harness.load_json(os.path.join(REPO, "BENCHMARK.json")),
    "with_candidates": harness.merged_manifest,
    "rehearsal": rehearsal_manifest}


def _manifest(which="landed"):
    return MANIFESTS[which]()


def test_manifest_has_exactly_the_contract_keys():
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["chipbench"]
    assert m["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in m["workloads"])


@pytest.mark.parametrize("which", MANIFESTS)
def test_every_cell_loads_its_files_by_name(which):
    m = _manifest(which)
    for w in m["workloads"]:
        cell = harness.Cell(m, w["name"])
        assert callable(cell.driver.run)
        assert callable(cell.family.build)
        assert cell.traffic["driver"] in ("train", "serve", "data")
        e2e = {x["name"] for x in cell.metrics("end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cell.metrics("per_layer")
        assert layer, f"{w['name']} reports no per-layer metric"
        # a per-layer metric is reported only where the metric it moves is
        assert all(x["moves"] in e2e for x in layer)
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("which", MANIFESTS)
def test_names_units_and_entries_keep_to_the_limits(which):
    m = _manifest(which)
    for groups in (("configs",), ("workloads",), ("end_to_end", "per_layer")):
        names = [entry["name"] for g in groups for entry in m[g]]
        assert all(harness.NAME_RE.match(n) for n in names), names
        assert len(names) == len(set(names)), names
    for w in m["workloads"]:
        # a candidate says why it is not a cell yet; a cell has no such key
        assert set(w) - {"not_yet"} == {"name", "config", "traffic",
                                        "chips", "why"}
        assert ("not_yet" in w) == (which != "landed" and w["name"] not in
                                    {x["name"] for x in
                                     _manifest()["workloads"]})
        assert harness.NAME_RE.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        assert c["file"].startswith(m["paths"][0] + "/")
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert x["source"] in ("host_clock", "device_trace")
        # a candidate's metric has no bound until it has been measured
        assert 0.01 <= x["bound"] <= 0.1 if "bound" in x \
            else which != "landed"
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(x["layer"]) <= 200
    for x in m["end_to_end"] + m["per_layer"]:
        assert harness.UNIT_RE.match(x["unit"]), x["unit"]
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES


def test_every_per_layer_metric_has_a_reader_and_every_reader_a_metric():
    m = _manifest("with_candidates")
    e2e = {x["name"] for x in m["end_to_end"]}
    readers = set()
    for x in m["per_layer"]:
        reader = harness.reader(x["name"])
        readers.add(reader.__name__.rsplit(".", 1)[1])
        assert x["moves"] in e2e
        # a reader that finds nothing to read returns nothing
        empty = {"counters": {"chips": 1}, "trace": {}, "seconds": 1.0}
        assert reader.read(empty) is None
    on_disk = {f[:-3] for f in os.listdir(os.path.join(
        harness.HERE, "layer_metrics")) if f.endswith(".py")} - {"__init__"}
    assert on_disk == readers
    # one reading under a name per kind of cell, read by one file
    assert harness.reader("train_device_idle_share") \
        is harness.reader("data_device_idle_share")
    with pytest.raises(ModuleNotFoundError):
        harness.reader("no_such_metric")


def test_config_files_state_source_and_cuts():
    m = _manifest("with_candidates")
    for c in m["configs"]:
        cfg = harness.load_json(os.path.join(REPO, c["file"]))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] == []
        assert cfg["name"] == c["name"]
        assert "assumed" in cfg and "deployment" in cfg
        importlib.import_module("chipbench.families." + cfg["family"])


def test_files_under_paths_are_named_from_the_allowed_characters():
    for base, _, files in os.walk(harness.HERE):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), REPO)
            assert all(c.isascii() and (c.isalnum() or c in "_.-/")
                       for c in rel), rel


def test_peaks_table_refuses_an_unlisted_kind():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(harness.BenchFailure):
        harness.peaks_for("TPU v9 imaginary")


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert harness.percentile(xs, 50) == 50
    assert harness.percentile(xs, 90) == 90
    assert harness.percentile([3.0], 99) == 3.0
    assert json.dumps(harness.percentile([1, 2], 50)) == "1.0"
