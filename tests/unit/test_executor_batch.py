"""Tests driving the real C++ executor server's POST /execute-batch: N jobs
staged into private workdirs, run as one warm-runner dispatch, per-job
stdout/stderr/exit/files/violations demuxed — plus the trace-id prefix on
runner log lines and generation turnover after a batch.
"""

import importlib.util
import io
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
    os.environ.get("TEST_EXECUTOR_BINARY", EXECUTOR_DIR / "build" / "executor-server")
)

TRACEPARENT = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"


def _server_env(ws, rp) -> dict:
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "APP_LISTEN_ADDR": "127.0.0.1:0",
            "APP_WORKSPACE": str(ws),
            "APP_RUNTIME_PACKAGES": str(rp),
            "APP_WARM_IMPORT_JAX": "0",
            "APP_RUNNER_INTERRUPT_GRACE_S": "2",
        }
    )
    return env


@pytest.fixture(scope="module")
def executor(tmp_path_factory):
    if "TEST_EXECUTOR_BINARY" not in os.environ:
        subprocess.run(
            ["make", "-C", str(EXECUTOR_DIR)], check=True, capture_output=True
        )
    root = tmp_path_factory.mktemp("executor-batch")
    ws = root / "ws"
    rp = root / "rp"
    ws.mkdir()
    rp.mkdir()
    proc = subprocess.Popen(
        [str(BINARY)],
        env=_server_env(ws, rp),
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
    yield client, ws
    client.close()
    proc.kill()
    proc.wait()


def batch(client, jobs, **kwargs):
    payload = {"jobs": jobs, "timeout": 30, **kwargs}
    resp = client.post(
        "/execute-batch", json=payload, headers={"traceparent": TRACEPARENT}
    )
    assert resp.status_code == 200, resp.text
    return resp.json()


def test_batch_demuxes_stdout_stderr_exit_codes(executor):
    client, _ws = executor
    body = batch(
        client,
        [
            {"source_code": "print('job zero')"},
            {"source_code": "import sys\nsys.stderr.write('boom\\n')\nraise SystemExit(3)"},
            {"source_code": "print('job two')"},
        ],
    )
    results = body["results"]
    assert [r["exit_code"] for r in results] == [0, 3, 0]
    assert results[0]["stdout"] == "job zero\n"
    assert results[1]["stderr"] == "boom\n"
    assert results[2]["stdout"] == "job two\n"
    assert body["warm"] is True
    assert body["runner_restarted"] is False


def test_batch_jobs_get_private_workdirs_and_file_demux(executor):
    """Each job's relative-path writes land in ITS workdir (per-thread cwd
    via unshare(CLONE_FS)) and are reported per job with hashes."""
    client, ws = executor
    body = batch(
        client,
        [
            {"source_code": "open('a.txt', 'w').write('from job 0')"},
            {"source_code": "import os\nos.makedirs('sub', exist_ok=True)\nopen('sub/b.txt', 'w').write('from job 1')"},
        ],
    )
    results = body["results"]
    assert [e["path"] for e in results[0]["files"]] == ["a.txt"]
    assert [e["path"] for e in results[1]["files"]] == ["sub/b.txt"]
    assert all(
        re.fullmatch(r"[0-9a-f]{64}", e["sha256"])
        for r in results
        for e in r["files"]
    )
    # The staged files are fetchable at their workdir-prefixed paths.
    resp = client.get(f"/workspace/{results[0]['workdir']}/a.txt")
    assert resp.status_code == 200 and resp.text == "from job 0"
    resp = client.get(f"/workspace/{results[1]['workdir']}/sub/b.txt")
    assert resp.status_code == 200 and resp.text == "from job 1"


def test_batch_jobs_run_concurrently(executor):
    """The whole point: N sleeps overlap instead of serializing."""
    client, _ws = executor
    start = time.monotonic()
    body = batch(
        client,
        [{"source_code": "import time\ntime.sleep(0.8)\nprint('done')"}] * 4,
    )
    elapsed = time.monotonic() - start
    assert all(r["exit_code"] == 0 for r in body["results"])
    assert elapsed < 2.4  # 4 x 0.8s serial would be >= 3.2s


def test_per_job_oom_violation_spares_batchmates(executor):
    """An armed memory budget + one allocation bomb: the bomb's job gets
    the typed oom violation, its batchmates finish clean, and the runner
    (with its device lease) survives."""
    client, _ws = executor
    body = batch(
        client,
        [
            {"source_code": "print('innocent 0')"},
            {"source_code": "x = bytearray(1 << 31)\nprint('never')"},
            {"source_code": "print('innocent 2')"},
        ],
        limits={"memory_bytes": 256 * 1024 * 1024},
    )
    results = body["results"]
    assert results[1]["violation"] == "oom"
    assert results[1]["exit_code"] == 1
    assert "Resource limit exceeded: oom" in results[1]["stderr"]
    assert "violation" not in results[0]
    assert results[0]["stdout"] == "innocent 0\n"
    assert results[2]["stdout"] == "innocent 2\n"
    assert body["runner_restarted"] is False
    assert "violation" not in body  # per-JOB, not batch-level


def test_batch_trace_block_carries_per_job_spans(executor):
    client, _ws = executor
    body = batch(
        client,
        [{"source_code": "print('a')"}, {"source_code": "print('b')"}],
    )
    trace = body["trace"]
    assert trace["traceparent"] == TRACEPARENT
    names = [s["name"] for s in trace["spans"]]
    assert "job-0" in names and "job-1" in names
    assert {"install", "exec", "collect"} <= set(names)


def test_reset_after_batch_recycles_and_wipes_staging(executor):
    """Generation turnover still works after a batch: job threads have
    exited (no surviving-thread refusal) and the staging dirs wipe with
    the workspace."""
    client, ws = executor
    body = batch(client, [{"source_code": "open('x', 'w').write('x')"}] * 2)
    workdir = body["results"][0]["workdir"]
    batch_root = workdir.split("/")[0]
    assert (ws / batch_root).exists()
    resp = client.post("/reset")
    assert resp.status_code == 200, resp.text
    assert not (ws / batch_root).exists()
    # And the sandbox still executes after turnover.
    resp = client.post("/execute", json={"source_code": "print('alive')"})
    assert resp.status_code == 200
    assert resp.json()["stdout"] == "alive\n"


def test_batch_validation_errors(executor):
    client, _ws = executor
    assert client.post("/execute-batch", json={"jobs": []}).status_code == 400
    assert (
        client.post(
            "/execute-batch", json={"jobs": [{"source_code": ""}]}
        ).status_code
        == 400
    )
    assert client.post("/execute-batch", content=b"junk").status_code == 400


def test_runner_log_lines_carry_trace_id():
    """The trace-context-propagation satellite at its source: runner-
    authored log lines are prefixed with the originating request's trace
    id (thread-local, so each batch job logs under its own id)."""
    spec = importlib.util.spec_from_file_location(
        "exec_runner", EXECUTOR_DIR / "runner.py"
    )
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    captured = io.StringIO()
    saved = sys.stderr
    sys.stderr = captured
    try:
        runner._set_trace_id("ab" * 16)
        runner._log("something happened")
        runner._set_trace_id(None)
        runner._log("anonymous line")
    finally:
        sys.stderr = saved
    lines = captured.getvalue().splitlines()
    assert lines[0] == f"[runner trace={'ab' * 16}] something happened"
    assert lines[1] == "[runner] anonymous line"


def test_fd_level_stdout_surfaces_batch_level(executor):
    """fd-level writes (os.write(1, ...) — a stand-in for subprocesses and
    C extensions) bypass the per-thread stream demux and must surface in
    the response's batch_stdout, so the control plane can refuse the demux
    and rerun serially instead of silently dropping output."""
    client, _ws = executor
    body = batch(
        client,
        [
            {"source_code": "print('demuxed fine')"},
            {"source_code": "import os\nos.write(1, b'fd-level escape\\n')"},
        ],
    )
    results = body["results"]
    assert results[0]["stdout"] == "demuxed fine\n"
    assert [r["exit_code"] for r in results] == [0, 0]
    # The fd-level write is NOT in any per-job stream...
    assert "fd-level escape" not in results[1]["stdout"]
    # ...it landed batch-level, where the control plane sees it and falls
    # back to the serial path.
    assert "fd-level escape" in body.get("batch_stdout", "")
