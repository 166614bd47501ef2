"""cgroup-v2 hard-enforcement tests against the real C++ executor binary.

Two groups:

- **Detection & fallback** (run everywhere): the /healthz `cgroup` block
  reports the enforcement verdict honestly — the kill switch forces the
  fallback with its reason, an unusable root falls back cleanly, and the
  fallback mode's rlimits+watchdog enforcement still works (the pre-cgroup
  contract is untouched).
- **Enforcement** (auto-skipped where the host cannot delegate a writable
  cgroup-v2 subtree with memory+pids — v1/hybrid hosts, read-only
  cgroupfs): the runner group and cold children actually live inside a
  kernel-enforced box, and a kernel OOM kill at memory.max surfaces as the
  typed `oom` violation.

The skip is keyed off the SERVER's own /healthz verdict, not host
sniffing: if the binary claims enforcement, the tests hold it to that.
CI re-runs this file under ASan/UBSan and TSan via TEST_EXECUTOR_BINARY.
"""

import os
import re
import subprocess
import time
from pathlib import Path

import httpx
import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
EXECUTOR_DIR = REPO_ROOT / "executor"
BINARY = Path(
    os.environ.get(
        "TEST_EXECUTOR_BINARY", EXECUTOR_DIR / "build" / "executor-server"
    )
)

MB = 1 << 20


def _spawn_server(ws, rp, extra_env=None, wait_warm=True):
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "APP_LISTEN_ADDR": "127.0.0.1:0",
            "APP_WORKSPACE": str(ws),
            "APP_RUNTIME_PACKAGES": str(rp),
            "APP_WARM_IMPORT_JAX": "0",
            "APP_RUNNER_INTERRUPT_GRACE_S": "2",
            "APP_LIMIT_POLL_INTERVAL": "0.05",
        }
    )
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [str(BINARY)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=None,
    )
    line = proc.stdout.readline().decode()
    port = int(re.search(r"port=(\d+)", line).group(1))
    client = httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=60.0)
    if wait_warm:
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            try:
                if client.get("/healthz").json().get("warm"):
                    break
            except httpx.TransportError:
                pass
            time.sleep(0.1)
        else:
            raise AssertionError("executor did not become warm in time")
    return proc, client


@pytest.fixture()
def fresh_dirs(tmp_path):
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    ws.mkdir()
    rp.mkdir()
    return ws, rp


@pytest.fixture(scope="module", autouse=True)
def build_binary():
    if "TEST_EXECUTOR_BINARY" not in os.environ:
        subprocess.run(
            ["make", "-C", str(EXECUTOR_DIR)], check=True, capture_output=True
        )


def _stop(proc, client):
    client.close()
    proc.terminate()
    proc.wait(timeout=10)


def _cgroup_block(client):
    body = client.get("/healthz").json()
    assert "cgroup" in body, body
    return body["cgroup"]


# --------------------------------------------------------- detection/fallback


def test_healthz_reports_cgroup_verdict(fresh_dirs):
    ws, rp = fresh_dirs
    proc, client = _spawn_server(ws, rp, wait_warm=False)
    try:
        cg = _cgroup_block(client)
        assert isinstance(cg["enforced"], bool)
        if cg["enforced"]:
            assert cg["base"]
        else:
            # An honest fallback names its reason.
            assert cg["fallback_reason"]
    finally:
        _stop(proc, client)


def test_kill_switch_forces_fallback(fresh_dirs):
    ws, rp = fresh_dirs
    proc, client = _spawn_server(
        ws, rp, extra_env={"APP_CGROUP_ENFORCE": "0"}, wait_warm=False
    )
    try:
        cg = _cgroup_block(client)
        assert cg["enforced"] is False
        assert "APP_CGROUP_ENFORCE=0" in cg["fallback_reason"]
    finally:
        _stop(proc, client)


def test_unusable_root_falls_back_cleanly(fresh_dirs, tmp_path):
    """Pointing the root at a plain directory (no cgroup.controllers) must
    degrade to the fallback — and the server still serves requests with
    the rlimits+watchdog layers fully functional."""
    ws, rp = fresh_dirs
    bogus = tmp_path / "not-a-cgroupfs"
    bogus.mkdir()
    proc, client = _spawn_server(
        ws, rp, extra_env={"APP_CGROUP_ROOT": str(bogus)}
    )
    try:
        cg = _cgroup_block(client)
        assert cg["enforced"] is False
        assert "cgroup.controllers" in cg["fallback_reason"]
        # The pre-cgroup enforcement contract is untouched: a memory hog
        # still gets its typed in-process oom via the rlimit window.
        resp = client.post(
            "/execute",
            json={
                "source_code": (
                    "b = []\n"
                    "for _ in range(10**4):\n"
                    "    b.append(bytearray(1024 * 1024))\n"
                ),
                "timeout": 30,
                "limits": {"memory_bytes": 64 * MB},
            },
        )
        assert resp.status_code == 200
        assert resp.json().get("violation") == "oom"
    finally:
        _stop(proc, client)


# -------------------------------------------------------------- enforcement


def _enforcing_server(fresh_dirs, extra_env=None):
    """Spawn with caps armed; skip unless the binary reports enforcement
    (the satellite's auto-skip where cgroupfs is read-only / v1-only)."""
    ws, rp = fresh_dirs
    env = {
        "APP_LIMIT_MEMORY_BYTES": str(256 * MB),
        "APP_LIMIT_NPROC": "64",
        # Tiny runner headroom so the enforcement test's hog crosses
        # memory.max quickly (the runner itself is a bare python here).
        "APP_CGROUP_RUNNER_HEADROOM_BYTES": str(128 * MB),
    }
    env.update(extra_env or {})
    proc, client = _spawn_server(ws, rp, extra_env=env)
    cg = _cgroup_block(client)
    if not cg["enforced"]:
        _stop(proc, client)
        pytest.skip(
            "cgroup-v2 enforcement unavailable here: "
            + cg.get("fallback_reason", "unknown")
        )
    return proc, client, cg


def test_runner_lives_inside_the_scope(fresh_dirs):
    proc, client, cg = _enforcing_server(fresh_dirs)
    try:
        assert cg["runner_scope"] is True
        # The warm runner's own view of its cgroup must be the scope the
        # server created — kernel-confirmed membership, not bookkeeping.
        resp = client.post(
            "/execute",
            json={
                "source_code": "print(open('/proc/self/cgroup').read())",
                "timeout": 30,
            },
        )
        body = resp.json()
        assert body["exit_code"] == 0, body
        assert "/runner" in body["stdout"]
    finally:
        _stop(proc, client)


def test_kernel_oom_kill_classified_as_oom_violation(fresh_dirs):
    """A hog that outruns the watchdog's sampling still dies INSIDE the
    box — memory.events oom_kill moves and the response carries the typed
    oom violation, not an anonymous crash."""
    proc, client, _ = _enforcing_server(
        fresh_dirs,
        # Slow the watchdog way down so the KERNEL is provably the killer.
        extra_env={"APP_LIMIT_POLL_INTERVAL": "30"},
    )
    try:
        resp = client.post(
            "/execute",
            json={
                "source_code": (
                    "b = []\n"
                    "while True:\n"
                    "    b.append(bytearray(16 * 1024 * 1024))\n"
                ),
                "timeout": 30,
                "limits": {"memory_bytes": 64 * MB},
            },
        )
        assert resp.status_code == 200
        assert resp.json().get("violation") == "oom"
        # And the sandbox keeps serving (runner restart is backgrounded).
        resp = client.post(
            "/execute", json={"source_code": "print('next')", "timeout": 30}
        )
        assert resp.status_code == 200
        assert resp.json()["exit_code"] == 0
    finally:
        _stop(proc, client)
