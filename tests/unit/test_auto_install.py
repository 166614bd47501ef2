"""Hermetic test of the dependency auto-install path (the reference's upm
role, SURVEY.md §2.14): APP_AUTO_INSTALL_DEPS=1 makes the executor run
deps.py over the submitted script and pip-install what's missing before
execution. pip is faked via an APP_PYTHON wrapper that 'installs' by writing
the module onto the sandbox's PYTHONPATH — no network, no real pip."""

import json
import os
import re
import stat
import subprocess
import sys
from pathlib import Path

import httpx
import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
BINARY = REPO_ROOT / "executor" / "build" / "executor-server"

FAKE_PYTHON = """#!/usr/bin/env bash
# Pass everything through to the real interpreter EXCEPT `-m pip install ...`,
# which "installs" each requested package by dropping a module into $SITE.
real="{real_python}"
if [ "$1" = "-m" ] && [ "$2" = "pip" ] && [ "$3" = "install" ]; then
  shift 3
  for pkg in "$@"; do
    case "$pkg" in --*) continue ;; esac
    safe=$(printf '%s' "$pkg" | tr - _)
    printf 'INSTALLED = "%s"\\n' "$pkg" > "$SITE/$safe.py"
    echo "$pkg" >> "$SITE/install.log"
  done
  exit 0
fi
exec "$real" "$@"
"""


@pytest.fixture
def auto_install_executor(tmp_path):
    if not BINARY.exists():
        pytest.skip("executor binary not built; run `make -C executor`")
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    site = tmp_path / "site"
    for d in (ws, rp, site):
        d.mkdir()
    # Preinstalled list: deps.py must subtract these (never "install" numpy).
    (rp / "requirements.txt").write_text("numpy\nscipy # comment\n")
    (rp / "requirements-skip.txt").write_text("libtpu\n")
    fake_python = tmp_path / "python"
    fake_python.write_text(FAKE_PYTHON.format(real_python=sys.executable))
    fake_python.chmod(fake_python.stat().st_mode | stat.S_IEXEC)

    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "APP_LISTEN_ADDR": "127.0.0.1:0",
            "APP_WORKSPACE": str(ws),
            "APP_RUNTIME_PACKAGES": str(rp),
            "APP_PYTHON": str(fake_python),
            "APP_WARM_RUNNER": "0",  # cold path: subprocess picks up SITE
            "APP_AUTO_INSTALL_DEPS": "1",
            "SITE": str(site),
            "PYTHONPATH": str(site),
        }
    )
    proc = subprocess.Popen(
        [str(BINARY)], env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL
    )
    line = proc.stdout.readline().decode()
    port = int(re.search(r"port=(\d+)", line).group(1))
    client = httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=60.0)
    yield client, site
    client.close()
    proc.kill()
    proc.wait()


def test_missing_import_is_installed_and_usable(auto_install_executor):
    client, site = auto_install_executor
    resp = client.post(
        "/execute",
        json={
            "source_code": (
                "import some_fake_package\n"
                "print(some_fake_package.INSTALLED)\n"
            )
        },
    )
    body = resp.json()
    assert body["exit_code"] == 0, body["stderr"]
    assert body["stdout"] == "some_fake_package\n"
    assert (site / "install.log").read_text().strip() == "some_fake_package"


def test_preinstalled_and_stdlib_not_reinstalled(auto_install_executor):
    client, site = auto_install_executor
    resp = client.post(
        "/execute",
        json={"source_code": "import json, numpy\nprint('ok')\n"},
    )
    body = resp.json()
    # numpy is in requirements.txt and importable; json is stdlib — the fake
    # pip must never be invoked.
    assert body["exit_code"] == 0, body["stderr"]
    assert not (site / "install.log").exists()


def test_alias_mapping(auto_install_executor):
    """An import whose pip name diverges must install under the ALIASED name
    (IMPORT_TO_PIP), not the import name."""
    import importlib.util

    sys.path.insert(0, str(REPO_ROOT / "executor"))
    try:
        from deps import IMPORT_TO_PIP
    finally:
        sys.path.pop(0)
    candidates = [
        (mod, pip)
        for mod, pip in IMPORT_TO_PIP.items()
        if pip is not None and pip != mod and importlib.util.find_spec(mod) is None
    ]
    if not candidates:
        pytest.skip("every aliased module is importable in this environment")
    mod, pip_name = candidates[0]

    client, site = auto_install_executor
    resp = client.post("/execute", json={"source_code": f"import {mod}\n"})
    body = resp.json()
    log = (site / "install.log").read_text().splitlines()
    assert pip_name in log, (mod, pip_name, log, body["stderr"][-300:])


def test_shipped_stack_covers_reference_parity_packages():
    """The REAL executor/requirements.txt — now pinned, with pandas extras —
    must parse into deps.py's skip list: an agent snippet importing the
    reference-parity packages (pdf2image/pikepdf/pypandoc/yt-dlp, the
    reference's Dockerfile:60-89 additions) takes the fast preinstalled
    path, never auto-install (VERDICT r3 #5)."""
    sys.path.insert(0, str(REPO_ROOT / "executor"))
    try:
        import deps
    finally:
        sys.path.pop(0)
    rp = REPO_ROOT / "executor"
    skip = deps.load_skip_list(rp)
    for pkg in ("pandas", "pdf2image", "pikepdf", "pypandoc", "yt-dlp", "jax"):
        assert pkg in skip, f"{pkg} missing from preinstalled skip list"
    # Pins and extras must not confuse the requirement parser end-to-end.
    source = "import pdf2image, pikepdf, pypandoc\nimport yt_dlp\nimport pandas\n"
    assert deps.missing_packages(source, runtime_packages=rp) == []
