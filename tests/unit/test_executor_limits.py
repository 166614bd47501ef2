"""Resource-governance tests against the real C++ executor binary.

Each violation kind (ISSUE 5 acceptance): a memory hog, a CPU spinner, a
bounded fork bomb, a disk filler, and an output flood each end with the
correct typed `violation` in the execute response — and the sandbox server
keeps serving the very next request. Also: request-over-cap clamping, the
streaming-PUT disk quota, and the truncation-flag satellite.

Runs with the warm runner but JAX import disabled (same speed profile as
test_executor_server.py); CI re-runs this file under ASan/UBSan and TSan
via TEST_EXECUTOR_BINARY.
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
    os.environ.get("TEST_EXECUTOR_BINARY", EXECUTOR_DIR / "build" / "executor-server")
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
            # Tight watchdog cadence so kill-path tests resolve in ~100ms
            # instead of the production 100ms-per-tick default drift.
            "APP_LIMIT_POLL_INTERVAL": "0.05",
        }
    )
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [str(BINARY)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=None,  # inherit: sanitizer reports must reach the test log
    )
    line = proc.stdout.readline().decode()
    port = int(re.search(r"port=(\d+)", line).group(1))
    client = httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=60.0)
    if wait_warm:
        _wait_warm(client)
    return proc, client


def _wait_warm(client, seconds=20.0):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        try:
            if client.get("/healthz").json().get("warm"):
                return
        except httpx.TransportError:
            pass
        time.sleep(0.1)
    raise AssertionError("executor did not become warm in time")


@pytest.fixture(scope="module")
def executor(tmp_path_factory):
    if "TEST_EXECUTOR_BINARY" not in os.environ:
        subprocess.run(
            ["make", "-C", str(EXECUTOR_DIR)], check=True, capture_output=True
        )
    root = tmp_path_factory.mktemp("executor-limits")
    ws = root / "ws"
    rp = root / "rp"
    ws.mkdir()
    rp.mkdir()
    proc, client = _spawn_server(ws, rp)
    yield client, ws
    client.close()
    proc.terminate()
    proc.wait(timeout=10)


def _execute(client, code, limits=None, timeout=30):
    body = {"source_code": code, "timeout": timeout}
    if limits:
        body["limits"] = limits
    resp = client.post("/execute", json=body)
    assert resp.status_code == 200
    return resp.json()


# --- in-process guards: the runner survives, violation is typed -------------


def test_memory_hog_gets_oom_violation_runner_survives(executor):
    client, _ = executor
    body = _execute(
        client,
        "b = []\n"
        "import time\n"
        "while True:\n"
        "    b.append(bytearray(8 << 20))\n"
        "    time.sleep(0.002)\n",
        limits={"memory_bytes": 64 * MB},
    )
    assert body["violation"] == "oom"
    assert body["exit_code"] != 0
    assert "Resource limit exceeded: oom" in body["stderr"]
    # The rlimit window caught it in-process: warm state survived.
    assert body["runner_restarted"] is False
    follow = _execute(client, "print('alive')")
    assert follow["stdout"] == "alive\n" and "violation" not in follow


def test_cpu_spinner_gets_cpu_time_violation_runner_survives(executor):
    client, _ = executor
    body = _execute(
        client,
        "while True: pass\n",
        limits={"cpu_seconds": 1},
        timeout=30,
    )
    assert body["violation"] == "cpu_time"
    assert body["exit_code"] != 0
    assert body["runner_restarted"] is False
    follow = _execute(client, "print('alive')")
    assert follow["stdout"] == "alive\n"
    assert follow["warm"] is True  # same warm process, lease intact


# --- watchdog kills: runner group dies, violation still typed ---------------


def test_fork_bomb_killed_with_nproc_violation(executor):
    client, _ = executor
    body = _execute(
        client,
        "import subprocess, time\n"
        "procs = [subprocess.Popen(['sleep', '30']) for _ in range(20)]\n"
        "time.sleep(30)\n",
        limits={"nproc": 5},
        timeout=40,
    )
    assert body["violation"] == "nproc"
    assert body["runner_restarted"] is True  # group kill -> rewarm in flight
    # The immediately following request is still served (cold or rewarmed).
    follow = _execute(client, "print('alive')")
    assert follow["stdout"] == "alive\n"
    _wait_warm(client)


def test_rlimit_dodger_killed_by_watchdog_oom(executor):
    client, _ = executor
    # User code raises its own soft RLIMIT_AS (the documented residual risk
    # of soft-only in-process guards) — the watchdog's group-RSS budget is
    # the layer that still contains it.
    body = _execute(
        client,
        "import resource, time\n"
        "resource.setrlimit(resource.RLIMIT_AS,\n"
        "                   (resource.RLIM_INFINITY, resource.RLIM_INFINITY))\n"
        "b = []\n"
        "while True:\n"
        "    b.append(bytearray(8 << 20))\n"
        "    b[-1][::4096] = b'x' * len(b[-1][::4096])\n"
        "    time.sleep(0.002)\n",
        limits={"memory_bytes": 64 * MB},
    )
    assert body["violation"] == "oom"
    assert body["runner_restarted"] is True
    follow = _execute(client, "print('alive')")
    assert follow["stdout"] == "alive\n"
    _wait_warm(client)


def test_disk_filler_killed_with_disk_quota_violation(executor):
    client, ws = executor
    body = _execute(
        client,
        "import time\n"
        "with open('junk.bin', 'wb') as f:\n"
        "    for _ in range(200):\n"
        "        f.write(b'x' * 262144)\n"
        "        f.flush()\n"
        "        time.sleep(0.01)\n"
        "time.sleep(30)\n",
        limits={"disk_bytes": 1 * MB},
        timeout=40,
    )
    assert body["violation"] == "disk_quota"
    follow = _execute(client, "print('alive')")
    assert follow["stdout"] == "alive\n"
    # Clean the junk so later module tests aren't over any future quota.
    for item in ws.iterdir():
        item.unlink()
    _wait_warm(client)


def test_output_flood_killed_with_output_cap_violation(executor):
    client, _ = executor
    body = _execute(
        client,
        "while True: print('y' * 65536)\n",
        limits={"output_bytes": 1 * MB},
        timeout=30,
    )
    assert body["violation"] == "output_cap"
    assert body["stdout_truncated"] is True
    assert len(body["stdout"]) <= 1 * MB + 64
    follow = _execute(client, "print('alive')")
    assert follow["stdout"] == "alive\n"
    _wait_warm(client)


def test_streaming_execute_reports_violation_in_final_event(executor):
    client, _ = executor
    import json as _json

    events = []
    with client.stream(
        "POST",
        "/execute/stream",
        json={
            "source_code": "while True: pass\n",
            "timeout": 30,
            "limits": {"cpu_seconds": 1},
        },
    ) as resp:
        assert resp.status_code == 200
        for line in resp.iter_lines():
            if line.strip():
                events.append(_json.loads(line))
    final = events[-1]
    assert final["violation"] == "cpu_time"


# --- truncation satellite ---------------------------------------------------


def test_truncation_flags_without_violation(tmp_path):
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    ws.mkdir()
    rp.mkdir()
    proc, client = _spawn_server(ws, rp, {"APP_MAX_OUTPUT_BYTES": "1024"})
    try:
        body = _execute(client, "print('x' * 4096)")
        # The implicit server cap TRUNCATES (historic behavior), now with
        # first-class flags; only an explicit output budget kills.
        assert body["stdout_truncated"] is True
        assert body["stderr_truncated"] is False
        assert "violation" not in body
        assert body["exit_code"] == 0
        assert "[stdout truncated]" in body["stdout"]
    finally:
        client.close()
        proc.terminate()
        proc.wait(timeout=10)


# --- env caps: clamping + PUT quota ----------------------------------------


def test_env_caps_clamp_request_overrides(tmp_path):
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    ws.mkdir()
    rp.mkdir()
    proc, client = _spawn_server(ws, rp, {"APP_LIMIT_NPROC": "4"})
    try:
        # The request asks for a 1000-process allowance; the env cap (4)
        # must win — the bomb still dies with the typed violation.
        body = _execute(
            client,
            "import subprocess, time\n"
            "procs = [subprocess.Popen(['sleep', '30']) for _ in range(20)]\n"
            "time.sleep(30)\n",
            limits={"nproc": 1000},
            timeout=40,
        )
        assert body["violation"] == "nproc"
    finally:
        client.close()
        proc.terminate()
        proc.wait(timeout=10)


def test_put_disk_quota_rejects_with_413(tmp_path):
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    ws.mkdir()
    rp.mkdir()
    proc, client = _spawn_server(
        ws, rp, {"APP_LIMIT_DISK_BYTES": str(2 * MB)}
    )
    try:
        ok = client.put("/workspace/small.bin", content=b"z" * 1024)
        assert ok.status_code == 200
        over = client.put("/workspace/big.bin", content=b"z" * (4 * MB))
        assert over.status_code == 413
        assert over.json()["violation"] == "disk_quota"
        # The refused upload must not have consumed quota: a small PUT
        # still fits afterwards.
        again = client.put("/workspace/small2.bin", content=b"z" * 1024)
        assert again.status_code == 200
        # Overwriting an existing file must count only the NEW bytes — the
        # stale manifest size was freed by O_TRUNC, and double-counting it
        # would 413 the delta-sync's routine changed-file re-uploads.
        first = client.put("/workspace/data.bin", content=b"a" * (1 * MB + 512 * 1024))
        assert first.status_code == 200
        rewrite = client.put("/workspace/data.bin", content=b"b" * (1 * MB + 512 * 1024))
        assert rewrite.status_code == 200
        # Under-quota executes still work with the env cap armed.
        body = _execute(client, "print('fits')")
        assert body["stdout"] == "fits\n" and "violation" not in body
    finally:
        client.close()
        proc.terminate()
        proc.wait(timeout=10)


def test_cold_path_cpu_breach_classified(tmp_path):
    # No warm runner: the spinner runs as a cold subprocess under real
    # RLIMIT_CPU — the kernel's SIGXCPU (soft limit; hard stays put) must
    # come back as the typed cpu_time violation, not a generic 152 crash.
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    ws.mkdir()
    rp.mkdir()
    proc, client = _spawn_server(ws, rp, {"APP_WARM_RUNNER": "0"}, wait_warm=False)
    try:
        body = _execute(
            client,
            "while True: pass\n",
            limits={"cpu_seconds": 1},
            timeout=30,
        )
        assert body["violation"] == "cpu_time"
        assert body["warm"] is False
        follow = _execute(client, "print('alive')")
        assert follow["stdout"] == "alive\n"
    finally:
        client.close()
        proc.terminate()
        proc.wait(timeout=10)
