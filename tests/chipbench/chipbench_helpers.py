"""Shared by the chipbench tests: where things are, and the benchmark's lib
on the path. Nothing here touches jax or describes a TPU topology."""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
FIXTURES = Path(__file__).resolve().parent / "fixtures"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
PAYLOADS = sorted(p.stem for p in (BENCH / "payloads").glob("*.json"))
CELLS = [w["name"] for w in DOC["workloads"]]
# A test's own cell in the `sessions` order, which no measured cell uses yet.
SESSIONS_JSON = FIXTURES / "sessions" / "BENCHMARK.json"
SESSIONS_CELL = "sessions.test"
