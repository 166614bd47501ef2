"""Finds, by the names in BENCHMARK.json, the files that make up a cell: its
configuration and that configuration's plain reference, its traffic mix, the
payloads the mix names, and the per-layer metrics with their readers. An
unknown name is refused; nothing here names a cell, a payload or a metric."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]  # benchmarks/chip
ROOT = BENCH.parents[1]  # the checkout


class UnknownName(Exception):
    pass


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise UnknownName(f"{path} is not there")
    return json.loads(path.read_text())


class Manifest:
    def __init__(self, benchmark_json: Path | None = None) -> None:
        """`benchmark_json` is the repository's unless a test brings its own;
        the data files lie under the first of its `paths`, beside it, and a
        reader that is not there is the yardstick's."""
        path = Path(benchmark_json or ROOT / "BENCHMARK.json").resolve()
        self.doc = load_json(path)
        self.base = path.parent
        self.data = self.base / self.doc["paths"][0]

    def _entry(self, section: str, name: str) -> dict:
        for entry in self.doc[section]:
            if entry["name"] == name:
                return entry
        known = ", ".join(e["name"] for e in self.doc[section])
        raise UnknownName(f"{name!r} is not among BENCHMARK.json's {section}: {known}")

    def cell(self, name: str) -> dict:
        """The cell's entry, its traffic file and its configuration."""
        entry = self._entry("workloads", name)
        traffic = load_json(self.data / "workloads" / f"{name}.json")
        if traffic["config"] != entry["config"] or traffic["chips"] != entry["chips"]:
            raise UnknownName(f"workloads/{name}.json and BENCHMARK.json disagree on config or chips")
        config_entry = self._entry("configs", entry["config"])
        config_path = self.base / config_entry["file"]
        reference = config_path.with_name(config_path.stem + ".reference.py")
        if not reference.is_file():
            raise UnknownName(f"{reference}: the configuration's plain reference is not there")
        return {
            "entry": entry,
            "traffic": traffic,
            "config": load_json(config_path),
            "reference": reference,
        }

    def payload(self, name: str) -> dict:
        spec = load_json(self.data / "payloads" / f"{name}.json")
        source = self.data / "payloads" / f"{name}.py"
        if not source.is_file():
            raise UnknownName(f"payloads/{name}.py is not there")
        spec["name"], spec["text"] = name, source.read_text()
        return spec

    def payloads_of(self, traffic: dict) -> dict[str, dict]:
        """{name: payload} of every payload a traffic mix names."""
        names = set(traffic.get("mix", {}))
        if "session" in traffic:
            names.add(traffic["session"]["payload"])
        return {name: self.payload(name) for name in sorted(names)}

    def metrics(self, section: str, cell: str) -> list[dict]:
        """The metrics of `end_to_end` or `per_layer` that this cell reports:
        those without a `workloads` key and those that list it."""
        return [
            m for m in self.doc[section]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def layer_metric(self, name: str) -> tuple[dict, object]:
        """The metric's own file and its reader's `read(turns, args, ctx)`."""
        spec = load_json(self.data / "layer_metrics" / f"{name}.json")
        path = self.data / "readers" / f"{spec['reader']}.py"
        if not path.is_file():
            path = BENCH / "readers" / f"{spec['reader']}.py"
        if not path.is_file():
            raise UnknownName(f"readers/{spec['reader']}.py (of metric {name}) is not there")
        module_spec = importlib.util.spec_from_file_location(f"chipbench_reader_{spec['reader']}", path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        return spec, module.read
