"""Plain reference of the test cell `sessions-test`: the turns of one session run in
their order under stock python in ONE directory, so each sees the files the
one before left; a turn's changed files are those whose content differs from
before it ran."""

import tempfile
from pathlib import Path

from lib.refrun import run_turn  # the yardstick's, on the path of whoever loads this


def run(chain: list[dict], scratch: Path) -> list[dict]:
    """`chain` is the session: a list of {"source", "files": {name: bytes}}
    in turn order; a turn's files are written into the shared workspace
    before it runs, as the service's upload does."""
    results = []
    scratch.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory(prefix="ref-", dir=scratch) as tmp:
        workspace = Path(tmp) / "workspace"
        workspace.mkdir()
        for i, turn in enumerate(chain):
            for name, data in turn["files"].items():
                (workspace / name).write_bytes(data)
            results.append(run_turn(turn["source"], workspace, Path(tmp) / f"turn{i}.py"))
    return results
