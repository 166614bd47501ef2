"""`manifest` finds every file BENCHMARK.json names and refuses an unknown
name; the names and units keep to the contract's characters."""

import re

import pytest

from chipbench_helpers import BENCH, CELLS, DOC, ROOT, SESSIONS_CELL, SESSIONS_JSON, load_runner
from lib.manifest import Manifest, UnknownName

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_a_cell_names_is_found(cell):
    manifest = Manifest()
    found = manifest.cell(cell)
    assert found["reference"].is_file() and found["reference"].parent == (BENCH / "configs")
    assert set(found["config"]) >= {"source", "deployment", "guarantees", "service_env", "reduced"}
    assert set(found["config"]["reduced"]) <= set(found["config"]), "every cut is a key of the file, with what forced it"
    for spec in manifest.payloads_of(found["traffic"]).values():
        assert spec["text"] and "rel_limit" in spec and "test" in spec
    layer = manifest.metrics("per_layer", cell)
    assert layer
    for entry in layer:
        spec, read = manifest.layer_metric(entry["name"])
        assert callable(read)
        for key in ("layer", "unit", "moves", "source"):
            assert spec[key] == entry[key], (entry["name"], key)


def test_a_tests_own_benchmark_json_brings_its_own_data_files():
    manifest = Manifest(SESSIONS_JSON)
    found = manifest.cell(SESSIONS_CELL)
    assert found["reference"].is_relative_to(SESSIONS_JSON.parent)
    assert found["traffic"]["order"] == "sessions"
    assert list(manifest.payloads_of(found["traffic"])) == [found["traffic"]["session"]["payload"]]
    for entry in manifest.metrics("per_layer", SESSIONS_CELL):  # the readers are the yardstick's
        assert callable(manifest.layer_metric(entry["name"])[1])


@pytest.mark.parametrize(
    "call",
    [
        lambda m: m.cell("no_such.cell"),
        lambda m: m.payload("no_such_payload"),
        lambda m: m.layer_metric("no_such_metric"),
    ],
)
def test_an_unknown_name_is_refused(call):
    with pytest.raises(UnknownName):
        call(Manifest())


def test_names_units_and_sources_keep_to_the_contract():
    names = [e["name"] for s in ("configs", "workloads", "end_to_end", "per_layer") for e in DOC[s]]
    names += [w["traffic"] for w in DOC["workloads"]] + [k for c in DOC["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        section_names = [e["name"] for e in DOC[section]]
        assert len(section_names) == len(set(section_names))
    metric_names = [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for metric in DOC["end_to_end"] + DOC["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
    for config in DOC["configs"]:
        assert 1 <= len(config["source"]) <= 200 and "\n" not in config["source"]
        assert (ROOT / config["file"]).is_file()
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert set(config["reduced"]) <= set(Manifest().cell(
            next(w["name"] for w in DOC["workloads"] if w["config"] == config["name"]))["config"])
    for workload in DOC["workloads"]:
        assert set(workload) == {"name", "config", "traffic", "chips", "why"}
        assert len(workload["why"]) <= 200 and workload["chips"] in (1, 4)
    assert set(DOC) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert DOC["paths"] == ["benchmarks/chip", "tests/chipbench"]


def test_metrics_hang_together():
    end_to_end = {m["name"]: m for m in DOC["end_to_end"]}
    assert "setup_s" in end_to_end
    # an end-to-end number is one the runner takes itself: no reader, no file of its own
    assert set(end_to_end) <= set(load_runner().end_to_end([], 1.0, 1.0))
    for metric in DOC["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1 and metric["source"] in ("host_clock", "device_trace")
    manifest = Manifest()
    for cell in CELLS:
        reported = {m["name"] for m in manifest.metrics("end_to_end", cell)}
        assert "setup_s" in reported and len(reported) >= 2
        for metric in manifest.metrics("per_layer", cell):
            assert metric["moves"] in reported, (cell, metric["name"])
    layers = {}
    for metric in DOC["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        layers.setdefault(metric["layer"], []).append(metric["name"])
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_the_runner_names_no_cell_payload_metric_or_reader():
    text = (BENCH / "run.py").read_text()
    names = set(CELLS) | {p.stem for p in (BENCH / "payloads").glob("*.json")}
    names |= {p.stem for p in (BENCH / "layer_metrics").glob("*.json")}
    names |= {p.stem for p in (BENCH / "readers").glob("*.py")}
    names |= {c["name"] for c in DOC["configs"]}
    for name in names:
        assert not re.search(rf"(?<![A-Za-z0-9_]){re.escape(name)}(?![A-Za-z0-9_])", text), name
    assert "import jax" not in text.replace('"import jax\\n', "")
