"""Plain reference of `npbench-linalg-1chip`: stateless turns of NPBench's
gemm, k3mm and floyd_warshall under stock numpy, in float32 and int32. Every
turn runs under stock python (no shim, no JAX device) in a directory of its
own that holds only the files the turn was given, so nothing of one turn is
visible to the next. Nothing of the program
is imported: a turn's source is the payload's own text, which is NPBench's
numpy kernel, and stock numpy is what it means."""

import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from lib.refrun import run_turn  # noqa: E402


def run(chain: list[dict], scratch: Path) -> list[dict]:
    """`chain` is a list of {"source", "files": {name: bytes}}; each turn is
    independent of the others."""
    results = []
    scratch.mkdir(parents=True, exist_ok=True)
    for turn in chain:
        with tempfile.TemporaryDirectory(prefix="ref-", dir=scratch) as tmp:
            workspace = Path(tmp) / "workspace"
            workspace.mkdir()
            for name, data in turn["files"].items():
                (workspace / name).write_bytes(data)
            results.append(run_turn(turn["source"], workspace, Path(tmp) / "turn.py"))
    return results
