"""One turn under stock python: what a configuration's plain reference
is made of. No sitecustomize, no shim, JAX_PLATFORMS=cpu; nothing of the
program is imported."""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys
from pathlib import Path

TIMEOUT_S = 1500


def hashes(workspace: Path) -> dict[str, str]:
    return {
        p.relative_to(workspace).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in workspace.rglob("*") if p.is_file()
    }


def run_turn(source: str, workspace: Path, script: Path) -> dict:
    """Run `source` with `workspace` as its directory; stdout, exit code and
    the files it changed there, by content."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "PYTHONSTARTUP", "APP_NUMPY_DISPATCH", "XLA_FLAGS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    # glibc: serve large arrays from the heap and never trim it, so that a
    # freed 4.8 GB array's pages are used again by the next one. A fresh
    # mapping is touched in page by page, and on these machines that alone
    # took most of a pass's time. It changes no result.
    env["MALLOC_MMAP_MAX_"] = "0"
    env["MALLOC_TRIM_THRESHOLD_"] = str(1 << 40)
    before = hashes(workspace)
    script.write_text(source)
    proc = subprocess.run(
        [sys.executable, str(script)],
        cwd=workspace, env=env, capture_output=True, text=True, timeout=TIMEOUT_S,
    )
    changed = {n: h for n, h in hashes(workspace).items() if before.get(n) != h}
    return {"stdout": proc.stdout, "exit_code": proc.returncode, "files": changed,
            "stderr_tail": proc.stderr[-400:]}
