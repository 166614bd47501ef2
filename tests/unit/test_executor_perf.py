"""Real-binary tests for the executor side of the performance anomaly
plane: the per-request device-memory wire block (/execute, /execute-batch —
present exactly when the request asks), the runner's sampling helpers
against a live JAX, and the strict lease-token mode
(APP_LEASE_REQUIRE_TOKEN=1 → tokenless dispatches 409 once a lease is
recorded; default stays tokenless-compatible)."""

import importlib.util
import os
import re
import subprocess
import sys
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


def _server_env(ws, rp, **extra) -> dict:
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "APP_LISTEN_ADDR": "127.0.0.1:0",
            "APP_WORKSPACE": str(ws),
            "APP_RUNTIME_PACKAGES": str(rp),
            "APP_WARM_IMPORT_JAX": "0",
        }
    )
    env.update(extra)
    return env


def _start(tmp_path_factory, name, **extra_env):
    if "TEST_EXECUTOR_BINARY" not in os.environ:
        subprocess.run(
            ["make", "-C", str(EXECUTOR_DIR)], check=True, capture_output=True
        )
    root = tmp_path_factory.mktemp(name)
    ws = root / "ws"
    rp = root / "rp"
    ws.mkdir()
    rp.mkdir()
    proc = subprocess.Popen(
        [str(BINARY)],
        env=_server_env(ws, rp, **extra_env),
        stdout=subprocess.PIPE,
        stderr=None,
    )
    line = proc.stdout.readline().decode()
    port = int(re.search(r"port=(\d+)", line).group(1))
    client = httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=60.0)
    for _ in range(200):
        try:
            if client.get("/healthz").json().get("warm"):
                break
        except httpx.TransportError:
            pass
        time.sleep(0.1)
    return proc, client, ws


@pytest.fixture(scope="module")
def executor(tmp_path_factory):
    proc, client, ws = _start(tmp_path_factory, "executor-perf")
    yield client, ws
    client.close()
    proc.kill()
    proc.wait()


@pytest.fixture(scope="module")
def strict_executor(tmp_path_factory):
    proc, client, ws = _start(
        tmp_path_factory, "executor-perf-strict", APP_LEASE_REQUIRE_TOKEN="1"
    )
    yield client, ws
    client.close()
    proc.kill()
    proc.wait()


# ------------------------------------------------------ device-memory wire


def test_execute_without_flag_has_no_device_memory_block(executor):
    client, _ws = executor
    body = client.post(
        "/execute", json={"source_code": "print('hi')", "timeout": 30}
    ).json()
    assert body["exit_code"] == 0
    # Byte-for-byte kill-switch contract: no flag on the wire, no block in
    # the reply.
    assert "device_memory" not in body


def test_execute_with_flag_returns_device_memory_block(executor):
    client, _ws = executor
    body = client.post(
        "/execute",
        json={
            "source_code": "print('hi')",
            "timeout": 30,
            "device_memory": True,
        },
    ).json()
    assert body["exit_code"] == 0
    block = body["device_memory"]
    # The warm runner sampled (no jax in this fixture: live/peak report
    # -1 "unavailable"; RSS is real either way).
    assert set(block) == {
        "live_bytes_before",
        "live_bytes_after",
        "peak_bytes_before",
        "peak_bytes_after",
        "rss_bytes",
    }
    assert block["rss_bytes"] > 0


def test_batch_jobs_carry_per_job_device_memory(executor):
    client, _ws = executor
    body = client.post(
        "/execute-batch",
        json={
            "jobs": [
                {"source_code": "print(1)"},
                {"source_code": "print(2)"},
            ],
            "timeout": 30,
            "device_memory": True,
        },
    ).json()
    results = body["results"]
    assert len(results) == 2
    for entry in results:
        assert entry["exit_code"] == 0
        assert entry["device_memory"]["rss_bytes"] > 0
    # Without the flag: no per-job blocks.
    body = client.post(
        "/execute-batch",
        json={
            "jobs": [{"source_code": "print(1)"}],
            "timeout": 30,
        },
    ).json()
    assert "device_memory" not in body["results"][0]


# --------------------------------------------- runner sampling (live jax)


def _load_runner_module():
    spec = importlib.util.spec_from_file_location(
        "perf_runner_under_test", EXECUTOR_DIR / "runner.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_device_memory_probe_sees_live_jax_buffers():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    runner = _load_runner_module()
    sys.modules.setdefault("jax", jax)
    probe = runner._DeviceMemoryProbe()
    keep = jnp.ones((256, 256), dtype=jnp.float32)  # 256KiB live
    keep.block_until_ready()
    block = probe.finish()
    assert block["rss_bytes"] > 0
    # Live bytes measurable (allocator stats on TPU/GPU, live_arrays on
    # CPU) and the new buffer shows up in the bracket's delta.
    assert block["live_bytes_after"] >= 0
    assert (
        block["live_bytes_after"] - max(0, block["live_bytes_before"])
        >= keep.nbytes
    )
    del keep


def test_device_memory_probe_without_jax_reports_unavailable():
    runner = _load_runner_module()
    saved = sys.modules.pop("jax", None)
    try:
        assert runner._device_memory_snapshot() == (-1, -1)
    finally:
        if saved is not None:
            sys.modules["jax"] = saved


# ------------------------------------------------------- strict lease mode


def test_default_mode_accepts_tokenless_after_lease(executor):
    client, _ws = executor
    assert client.post("/lease", json={"token": "lease-compat-1"}).status_code == 200
    # Compatibility contract (PR 13): tokenless dispatches keep working.
    body = client.post(
        "/execute", json={"source_code": "print('ok')", "timeout": 30}
    ).json()
    assert body["exit_code"] == 0


def test_strict_mode_tokenless_passes_before_any_lease(strict_executor):
    client, _ws = strict_executor
    body = client.post(
        "/execute", json={"source_code": "print('pre-lease')", "timeout": 30}
    ).json()
    assert body["exit_code"] == 0


def test_strict_mode_409s_tokenless_once_leased(strict_executor):
    client, _ws = strict_executor
    assert client.post("/lease", json={"token": "lease-strict-1"}).status_code == 200
    resp = client.post(
        "/execute", json={"source_code": "print('no token')", "timeout": 30}
    )
    assert resp.status_code == 409
    body = resp.json()
    assert body["error"] == "lease_token_required"
    # The refusal must NOT disclose the valid token — this response is
    # exactly what tenant code curling localhost from inside the sandbox
    # sees, and echoing the credential would defeat the strict gate.
    assert "held" not in body
    # Strict mode also redacts the token from /device-stats (as reachable
    # from inside the sandbox as /execute).
    assert "lease_token" not in client.get("/device-stats").json()
    # /reset and /execute-batch are fenced the same way.
    assert client.post("/reset").status_code == 409
    assert (
        client.post(
            "/execute-batch",
            json={"jobs": [{"source_code": "print(1)"}], "timeout": 30},
        ).status_code
        == 409
    )
    # The REAL token still serves.
    ok = client.post(
        "/execute",
        json={"source_code": "print('with token')", "timeout": 30},
        headers={"x-lease-token": "lease-strict-1"},
    ).json()
    assert ok["exit_code"] == 0
    # A stale token stays the stale_lease refusal (distinct typed reason).
    stale = client.post(
        "/execute",
        json={"source_code": "print('stale')", "timeout": 30},
        headers={"x-lease-token": "lease-strict-0"},
    )
    assert stale.status_code == 409
    assert stale.json()["error"] == "stale_lease"
