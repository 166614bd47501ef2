"""Shared by the chipbench tests: where things are, the benchmark's lib on the
path, and what the tests take from the DATA instead of from a name written in
a test file: a cell's manifest, its traffic and its order. Nothing here touches
jax or describes a TPU topology."""

import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
FIXTURES = Path(__file__).resolve().parent / "fixtures"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from lib.manifest import Manifest, UnknownName  # noqa: E402

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
PAYLOADS = sorted(p.stem for p in (BENCH / "payloads").glob("*.json"))
CELLS = [w["name"] for w in DOC["workloads"]]
# A test's own cell in the `sessions` order, which no measured cell uses yet.
SESSIONS_JSON = FIXTURES / "sessions" / "BENCHMARK.json"
SESSIONS_CELL = "sessions.test"
# the yardstick's cells, and the tests' own
ALL_CELLS = CELLS + [SESSIONS_CELL]


def manifest_of(cell: str) -> Manifest:
    return Manifest(SESSIONS_JSON if cell == SESSIONS_CELL else None)


def found_cell(cell: str) -> dict | None:
    """What the manifest finds for the cell; None where a file of it is not
    there: `test_every_file_a_cell_names_is_found` says which."""
    try:
        return manifest_of(cell).cell(cell)
    except UnknownName:
        return None


def order_of(cell: str) -> str | None:
    """The `order` of the cell's traffic file: what decides which generator
    path, which client and which faults a cell has."""
    found = found_cell(cell)
    return found and found["traffic"]["order"]


DECK_CELLS = [c for c in CELLS if order_of(c) == "deck"]


def load_runner():
    """`run.py` as a module: its arithmetic, and `main` without the look for a chip."""
    spec = importlib.util.spec_from_file_location("chipbench_run", BENCH / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
