"""Plain reference of `npbench-files-1chip`: stateless turns of NPBench
kernels over the turn's input files, under stock numpy in float32. Every turn
runs under stock python (no shim, no JAX device) in a directory of its own
that holds only the files the turn was given, written there byte for byte, so
the bytes computed on are the bytes uploaded and nothing of one turn is
visible to the next. Nothing of the program is imported: a turn's source is
the payload's own text, `np.fromfile` is stock numpy's, and stock numpy is
what the kernel means."""

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
