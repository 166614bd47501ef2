"""Tests driving the real C++ executor server binary over HTTP.

The reference had no tests for its executor at all (SURVEY.md §4); these
exercise upload/download with path confinement, /execute (warm-runner mode
with JAX import disabled for speed), timeout kill + runner restart, and
recursive changed-file detection.
"""

import json
import os
import re
import signal
import subprocess
import time
from pathlib import Path

import httpx
import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
EXECUTOR_DIR = REPO_ROOT / "executor"
# CI points this at the ASan/TSan builds to run the same suite under
# sanitizers (SURVEY.md §5: the C++ rebuild earns its safety story in CI).
BINARY = Path(
    os.environ.get("TEST_EXECUTOR_BINARY", EXECUTOR_DIR / "build" / "executor-server")
)


def _server_env(ws, rp) -> dict:
    """Server env based on os.environ so CI's ASAN_OPTIONS/TSAN_OPTIONS
    (halt_on_error etc.) actually reach the sanitized process — a hand-built
    env dict would leave the sanitizer jobs blind."""
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "APP_LISTEN_ADDR": "127.0.0.1:0",
            "APP_WORKSPACE": str(ws),
            "APP_RUNTIME_PACKAGES": str(rp),
            "APP_WARM_IMPORT_JAX": "0",
            # Short cooperative-cancellation grace so the forced-kill tests
            # don't stall the suite waiting out the production default.
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
    root = tmp_path_factory.mktemp("executor")
    ws = root / "ws"
    rp = root / "rp"
    ws.mkdir()
    rp.mkdir()
    proc = subprocess.Popen(
        [str(BINARY)],
        env=_server_env(ws, rp),
        stdout=subprocess.PIPE,
        stderr=None,  # inherit: sanitizer reports must reach the test log
    )
    line = proc.stdout.readline().decode()
    port = int(re.search(r"port=(\d+)", line).group(1))
    client = httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=30.0)
    # The port is announced before warm-up (that's the round-2 design);
    # wait for the background warm thread to finish before tests run.
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


def execute(client, source, **kwargs):
    resp = client.post("/execute", json={"source_code": source, **kwargs})
    assert resp.status_code == 200, resp.text
    return resp.json()


def file_paths(result):
    """Changed-file rel paths from an execute response. Manifest-enabled
    binaries report [{"path", "sha256"}, ...]; legacy mode plain strings."""
    return [
        entry["path"] if isinstance(entry, dict) else entry
        for entry in result["files"]
    ]


def test_healthz_warm(executor):
    client, _ = executor
    health = client.get("/healthz").json()
    assert health["status"] == "ok"
    assert health["warm"] is True
    assert health["warm_state"] == "ready"


def test_readyz_ready(executor):
    client, _ = executor
    resp = client.get("/readyz")
    assert resp.status_code == 200
    assert resp.json()["warm"] is True


def test_warmup_idempotent(executor):
    client, _ = executor
    resp = client.post("/warmup")
    assert resp.status_code == 200
    assert resp.json()["warm_state"] == "ready"


def test_upload_download_roundtrip(executor):
    client, ws = executor
    resp = client.put("/workspace/dir/sub/file.txt", content=b"payload")
    assert resp.status_code == 200
    assert (ws / "dir/sub/file.txt").read_bytes() == b"payload"
    got = client.get("/workspace/dir/sub/file.txt")
    assert got.status_code == 200
    assert got.content == b"payload"


def test_double_prefix_tolerated(executor):
    # The reference control plane produced /workspace//workspace/x URLs
    # (SURVEY.md §0.4); they must land at workspace root, not a nested dir.
    client, ws = executor
    client.put("/workspace//workspace/legacy.txt", content=b"legacy")
    assert (ws / "legacy.txt").read_bytes() == b"legacy"


def test_path_traversal_blocked(executor):
    client, _ = executor
    assert client.put("/workspace/../escape.txt", content=b"x").status_code in (400, 403)
    assert client.get("/workspace/../../etc/passwd").status_code in (400, 403, 404)
    assert client.get("/unknown-prefix/foo").status_code == 404


def test_symlink_escape_blocked(executor):
    client, ws = executor
    (ws / "link").symlink_to("/etc")
    resp = client.get("/workspace/link/passwd")
    assert resp.status_code == 403


def test_execute_stdout_stderr_exit(executor):
    client, _ = executor
    result = execute(client, "import sys\nprint('out')\nprint('err', file=sys.stderr)\nsys.exit(5)")
    assert result["stdout"] == "out\n"
    assert result["stderr"].strip() == "err"
    assert result["exit_code"] == 5
    assert result["warm"] is True


def test_execute_changed_files_recursive(executor):
    client, _ = executor
    result = execute(
        client,
        "import os\nos.makedirs('deep/nested', exist_ok=True)\n"
        "open('deep/nested/new.txt', 'w').write('x')\nopen('top.txt', 'w').write('y')",
    )
    assert result["exit_code"] == 0
    assert "deep/nested/new.txt" in file_paths(result)
    assert "top.txt" in file_paths(result)


def test_execute_timeout_cooperative_cancel(executor):
    """An interruptible runaway (the common case) is cancelled via SIGINT:
    the response carries timeout semantics, but the warm runner SURVIVES —
    no background restart, and the very next request is served warm. On an
    accelerator this is what keeps a timeout from costing a full re-attach
    (12 s on the v5e, chip_smoke reading, PR 21)."""
    client, _ = executor
    result = execute(client, "while True: pass", timeout=1)
    assert result["exit_code"] == -1
    assert "timed out" in result["stderr"]
    assert result["runner_restarted"] is False
    result = execute(client, "print('still warm')")
    assert result["stdout"] == "still warm\n"
    assert result["warm"] is True


def test_execute_timeout_and_recovery(executor):
    """An UNinterruptible runaway (ignores SIGINT outright) exhausts the
    cancellation grace and exercises the forced-kill + background-rewarm
    path."""
    client, _ = executor
    result = execute(
        client,
        "import signal\n"
        "signal.signal(signal.SIGINT, signal.SIG_IGN)\n"
        "while True: pass",
        timeout=1,
    )
    assert result["exit_code"] == -1
    assert "timed out" in result["stderr"]
    # The runner restart happens in the BACKGROUND (VERDICT r1 #9): the very
    # next request must not pay runner re-init on its critical path — it is
    # served by the cold subprocess immediately.
    t0 = time.monotonic()
    result = execute(client, "print('recovered')")
    elapsed = time.monotonic() - t0
    assert result["stdout"] == "recovered\n"
    assert result["exit_code"] == 0
    assert result["warm"] is False
    assert elapsed < 10, f"cold fallback took {elapsed:.1f}s"
    # and the background restart eventually restores warm service
    for _ in range(100):
        if client.get("/healthz").json().get("warm"):
            break
        time.sleep(0.1)
    else:
        pytest.fail("runner did not restart in the background")
    result = execute(client, "print('warm again')")
    assert result["warm"] is True


def test_execute_stream_chunks_arrive_live(executor):
    """POST /execute/stream: NDJSON chunks must arrive while the code is
    still running (not buffered until completion), and the final event must
    be the complete /execute response body."""
    client, _ = executor
    src = (
        "import time\n"
        "for i in range(4):\n"
        "    print('tick', i, flush=True)\n"
        "    time.sleep(0.3)\n"
        "open('streamed.txt', 'w').write('done')\n"
    )
    events = []
    t0 = time.monotonic()
    with client.stream(
        "POST", "/execute/stream", json={"source_code": src}
    ) as resp:
        assert resp.status_code == 200
        buf = ""
        for text in resp.iter_text():
            buf += text
            while "\n" in buf:
                line, buf = buf.split("\n", 1)
                if line.strip():
                    events.append((time.monotonic() - t0, json.loads(line)))
    chunks = [e for _, e in events if "stream" in e]
    assert chunks, "no stream chunks arrived"
    # First chunk must beat the full runtime (~1.2 s) by a wide margin.
    assert events[0][0] < 0.9, f"first chunk too late: {events[0][0]:.2f}s"
    final = events[-1][1]
    assert final["exit_code"] == 0
    assert final["stdout"] == "tick 0\ntick 1\ntick 2\ntick 3\n"
    assert "streamed.txt" in file_paths(final)
    assert final["runner_restarted"] is False
    joined = "".join(c["data"] for c in chunks if c["stream"] == "stdout")
    assert joined == final["stdout"]


def test_execute_stream_utf8_never_split(executor):
    """Multi-byte UTF-8 output streamed in many flushes must decode cleanly
    per event: a chunk boundary through a codepoint would turn both halves
    into U+FFFD. Joined chunks must equal the final stdout exactly."""
    client, _ = executor
    src = (
        "import sys, time\n"
        "for i in range(40):\n"
        "    sys.stdout.write('\\u6f22\\u5b57\\U0001f600' * 50)\n"
        "    sys.stdout.flush()\n"
        "    time.sleep(0.02)\n"
    )
    events = []
    with client.stream(
        "POST", "/execute/stream", json={"source_code": src}
    ) as resp:
        buf = ""
        for text in resp.iter_text():
            buf += text
            while "\n" in buf:
                line, buf = buf.split("\n", 1)
                if line.strip():
                    events.append(json.loads(line))
    chunks = [e for e in events if e.get("stream") == "stdout"]
    final = events[-1]
    assert final["exit_code"] == 0
    joined = "".join(c["data"] for c in chunks)
    assert "�" not in joined
    assert joined == final["stdout"]


def test_execute_stream_timeout(executor):
    """Timeout during a streamed execute: the final event carries the same
    timeout semantics as /execute (exit -1, marker in stderr)."""
    client, _ = executor
    events = []
    with client.stream(
        "POST",
        "/execute/stream",
        json={"source_code": "import time\ntime.sleep(30)", "timeout": 1},
    ) as resp:
        buf = ""
        for text in resp.iter_text():
            buf += text
            while "\n" in buf:
                line, buf = buf.split("\n", 1)
                if line.strip():
                    events.append(json.loads(line))
    final = events[-1]
    assert final["exit_code"] == -1
    assert "timed out" in final["stderr"]
    # time.sleep is SIGINT-interruptible, so cooperative cancellation keeps
    # the runner (and a real deployment's device lease) alive — no restart.
    assert final["runner_restarted"] is False
    assert client.get("/healthz").json().get("warm") is True


def test_execute_mixed_shell_python(executor):
    """Mixed Python/shell snippets (the xonsh role, reference server.rs:
    197-207) execute through the warm runner via the shellfb transform."""
    result = execute(
        client_of(executor),
        "x = 21\necho marker-line > shell_out.txt\n"
        "print(open('shell_out.txt').read().strip())\nprint(x * 2)",
    )
    assert result["exit_code"] == 0
    assert result["stdout"] == "marker-line\n42\n"
    assert "shell_out.txt" in file_paths(result)


def client_of(executor):
    client, _ = executor
    return client


def test_execute_exception_traceback(executor):
    client, _ = executor
    result = execute(client, "1/0")
    assert result["exit_code"] == 1
    assert "ZeroDivisionError" in result["stderr"]


def test_execute_env_passthrough(executor):
    client, _ = executor
    result = execute(
        client, "import os\nprint(os.environ['MY_FLAG'])", env={"MY_FLAG": "tpu"}
    )
    assert result["stdout"] == "tpu\n"


def test_execute_source_file(executor):
    client, _ = executor
    client.put("/workspace/prog.py", content=b"print('from file')")
    resp = client.post("/execute", json={"source_file": "/workspace/prog.py"})
    assert resp.json()["stdout"] == "from file\n"
    # and confinement on source_file
    resp = client.post("/execute", json={"source_file": "/../../etc/passwd"})
    assert resp.status_code == 403


def test_execute_bad_request(executor):
    client, _ = executor
    assert client.post("/execute", content=b"not json").status_code == 400
    assert client.post("/execute", json={}).status_code == 400


def test_unicode_roundtrip(executor):
    client, _ = executor
    result = execute(client, "print('héllo ✓ 日本語')")
    assert result["stdout"] == "héllo ✓ 日本語\n"


def test_reset_scrubs_generation(executor):
    """POST /reset is the generation turnover that lets the control plane
    reuse the warm device process (VERDICT r2 #1): the previous sandbox's
    files, env mutations, workspace module imports, and stray child
    processes must all be gone; the warm runner must stay alive."""
    client, ws = executor
    result = execute(
        client,
        "import os, subprocess, sys\n"
        "open('leftover.txt', 'w').write('secret')\n"
        "open('shadow.py', 'w').write('VALUE = 1')\n"
        "sys.path.insert(0, os.getcwd())\n"
        "import shadow\n"
        "print(shadow.VALUE)\n"
        "os.environ['LEAKED_VAR'] = 'oops'\n"
        "child = subprocess.Popen(['sleep', '600'])\n"
        "print(child.pid)\n",
    )
    assert result["exit_code"] == 0, result["stderr"]
    lines = result["stdout"].split()
    assert lines[0] == "1"
    child_pid = int(lines[1])

    resp = client.post("/reset")
    assert resp.status_code == 200, resp.text
    assert resp.json()["ok"] is True
    assert resp.json()["warm"] is True  # the device process survived

    assert list(ws.iterdir()) == []  # workspace wiped in place
    with pytest.raises(ProcessLookupError):
        os.kill(child_pid, 0)  # stray child reaped

    result = execute(
        client,
        "import os, sys\n"
        "print(sorted(os.listdir('.')))\n"
        "print(os.environ.get('LEAKED_VAR'))\n"
        "open('shadow.py', 'w').write('VALUE = 2')\n"
        "sys.path.insert(0, os.getcwd())\n"
        "import shadow\n"
        "print(shadow.VALUE)\n",
    )
    assert result["exit_code"] == 0, result["stderr"]
    out = result["stdout"].splitlines()
    assert out[0] == "[]"  # fresh workspace
    assert out[1] == "None"  # env restored
    assert out[2] == "2"  # no module-cache shadow from the last generation
    assert result["warm"] is True
    client.post("/reset")  # leave a clean workspace for the next test


def test_reset_refused_when_user_thread_survives(executor):
    """A thread the previous generation started cannot be killed from
    outside — the runner must refuse the reset so the control plane
    disposes the whole process instead of recycling it."""
    client, _ = executor
    result = execute(
        client,
        "import threading, time\n"
        "threading.Thread(target=time.sleep, args=(600,), daemon=True).start()\n"
        "print('spawned')\n",
    )
    assert result["exit_code"] == 0, result["stderr"]
    resp = client.post("/reset")
    assert resp.status_code == 409
    assert resp.json()["ok"] is False
    # The refusal marks the runner failed; restore warm service for the
    # remaining tests the way the control plane would not (it would dispose)
    # — this dev server can just rewarm.
    client.post("/warmup")
    for _ in range(100):
        if client.get("/healthz").json().get("warm"):
            break
        time.sleep(0.1)
    else:
        pytest.fail("runner did not rewarm after refused reset")


def test_reset_wipes_extra_dirs_and_tmpdir(tmp_path):
    """APP_RESET_EXTRA_WIPE_DIRS closes the cross-generation channels
    outside workspace/runtime-packages (sandbox-private tmp, ~/.local)."""
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    extra = tmp_path / "scratch-tmp"
    ws.mkdir()
    rp.mkdir()
    extra.mkdir()
    env = _server_env(ws, rp)
    env["APP_RESET_EXTRA_WIPE_DIRS"] = str(extra) + ":" + str(
        tmp_path / "never-created"
    )
    env["TMPDIR"] = str(extra)
    proc = subprocess.Popen(
        [str(BINARY)], env=env, stdout=subprocess.PIPE, stderr=None
    )
    try:
        line = proc.stdout.readline().decode()
        port = int(re.search(r"port=(\d+)", line).group(1))
        with httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=30.0) as c:
            for _ in range(200):
                if c.get("/healthz").json().get("warm"):
                    break
                time.sleep(0.05)
            result = c.post(
                "/execute",
                json={
                    "source_code": "import tempfile, os\n"
                    "fd, path = tempfile.mkstemp()\n"
                    "os.write(fd, b'stash')\n"
                    "os.close(fd)\n"
                    "print(path)\n"
                },
            ).json()
            assert result["exit_code"] == 0, result["stderr"]
            stash_path = result["stdout"].strip()
            assert stash_path.startswith(str(extra))  # TMPDIR honored
            resp = c.post("/reset")
            assert resp.status_code == 200, resp.text
        assert list(extra.iterdir()) == []  # scratch tmp wiped
    finally:
        proc.kill()
        proc.wait()


def test_runner_dead_at_request_flags_restart(tmp_path):
    """A warm runner that died BETWEEN requests (OOM-kill etc.) must be
    detected at the next /execute: the response reports
    runner_restarted=true (sessions key their state-loss signal off it) and
    a background rewarm starts — without this, the sandbox would serve
    every subsequent request cold forever and sessions would silently lose
    their in-process state. (Detection happens inside the runner protocol —
    the dead/zombie runner's pipe EOFs -> kDied; alive() alone cannot see a
    zombie.)"""
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    ws.mkdir()
    rp.mkdir()
    proc = subprocess.Popen(
        [str(BINARY)], env=_server_env(ws, rp), stdout=subprocess.PIPE, stderr=None
    )
    try:
        line = proc.stdout.readline().decode()
        port = int(re.search(r"port=(\d+)", line).group(1))
        with httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=30.0) as c:
            for _ in range(200):
                if c.get("/healthz").json().get("warm"):
                    break
                time.sleep(0.1)
            else:
                pytest.fail("runner never warmed")
            # Kill the runner out-of-band: it is the server's only child.
            children = [
                int(p)
                for p in os.listdir("/proc")
                if p.isdigit() and _ppid_of(int(p)) == proc.pid
            ]
            assert children, "no runner child found"
            for pid in children:
                os.kill(pid, signal.SIGKILL)
            time.sleep(0.3)

            resp = c.post("/execute", json={"source_code": "print('x')"})
            body = resp.json()
            # The request hits the dead runner: reported honestly (the code
            # never ran) and flagged so the control plane ends any session.
            assert body["exit_code"] == -1
            assert "runner crashed" in body["stderr"].lower()
            assert body["runner_restarted"] is True
            # The background rewarm restores warm service.
            for _ in range(200):
                if c.get("/healthz").json().get("warm"):
                    break
                time.sleep(0.1)
            else:
                pytest.fail("runner did not restart in the background")
            body = c.post(
                "/execute", json={"source_code": "print('warm')"}
            ).json()
            assert body["warm"] is True
            assert body["runner_restarted"] is False
    finally:
        proc.kill()
        proc.wait()


def _ppid_of(pid: int) -> int:
    """Exact ppid (field 2 after the parenthesized comm) — matching the pid
    loosely against all stat fields could hit unrelated processes' counters
    and SIGKILL them."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            stat = f.read()
        return int(stat.rsplit(b") ", 1)[1].split()[1])
    except (OSError, IndexError, ValueError):
        return -1


def test_reset_refused_when_runner_cold(tmp_path):
    """A sandbox whose runner never warmed (or was killed) must not be
    recycled: /reset answers 409 so the control plane disposes it."""
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    ws.mkdir()
    rp.mkdir()
    env = _server_env(ws, rp)
    env["APP_WARM_EAGER"] = "0"  # warm-up waits for /warmup that never comes
    proc = subprocess.Popen(
        [str(BINARY)], env=env, stdout=subprocess.PIPE, stderr=None
    )
    try:
        line = proc.stdout.readline().decode()
        port = int(re.search(r"port=(\d+)", line).group(1))
        with httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=10.0) as c:
            resp = c.post("/reset")
            assert resp.status_code == 409
            assert resp.json()["ok"] is False
    finally:
        proc.kill()
        proc.wait()


def test_reset_without_warm_runner_wipes(tmp_path):
    """Warm mode off (plumbing/dev): /reset still wipes both prefixes."""
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    ws.mkdir()
    rp.mkdir()
    (ws / "old.txt").write_text("x")
    (rp / "pkg").mkdir()
    (rp / "pkg" / "mod.py").write_text("y")
    env = _server_env(ws, rp)
    env["APP_WARM_RUNNER"] = "0"
    proc = subprocess.Popen(
        [str(BINARY)], env=env, stdout=subprocess.PIPE, stderr=None
    )
    try:
        line = proc.stdout.readline().decode()
        port = int(re.search(r"port=(\d+)", line).group(1))
        with httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=10.0) as c:
            resp = c.post("/reset")
            assert resp.status_code == 200
            assert resp.json()["ok"] is True
        assert list(ws.iterdir()) == []
        assert list(rp.iterdir()) == []
    finally:
        proc.kill()
        proc.wait()


def test_deps_scanner():
    out = subprocess.run(
        [
            "python",
            str(EXECUTOR_DIR / "deps.py"),
            "/dev/stdin",
        ],
        input=b"import os\nimport numpy\nimport definitely_not_installed_pkg\nfrom PIL import Image\n",
        capture_output=True,
        check=True,
    )
    missing = out.stdout.decode().split()
    assert "definitely_not_installed_pkg" in missing
    assert "numpy" not in missing  # installed
    assert "os" not in missing  # stdlib


def test_sigterm_reaps_runner_session(tmp_path):
    """SIGTERM to the server must take the warm runner down with it even
    though the runner sits in its own session (kubelet pod stop and the
    local backend's graceful teardown both rely on this; a GIL-wedged
    runner cannot be trusted to notice pipe EOF itself)."""
    import signal

    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    ws.mkdir()
    rp.mkdir()
    proc = subprocess.Popen(
        [str(BINARY)],
        env=_server_env(ws, rp),
        stdout=subprocess.PIPE,
        stderr=None,
        start_new_session=True,
    )
    try:
        assert b"port=" in proc.stdout.readline()
        # the warm runner is forked by a background warm-up thread now —
        # poll for the server's only child to appear
        deadline = time.time() + 10
        children: list[str] = []
        while time.time() < deadline and not children:
            children = subprocess.run(
                ["pgrep", "-P", str(proc.pid)], capture_output=True, text=True
            ).stdout.split()
            time.sleep(0.05)
        assert len(children) == 1, children
        runner_pid = int(children[0])

        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=5)
        deadline = time.time() + 5
        while time.time() < deadline:
            try:
                os.kill(runner_pid, 0)
            except ProcessLookupError:
                break  # runner reaped by the server's handler
            time.sleep(0.05)
        else:
            pytest.fail(f"runner {runner_pid} survived server SIGTERM")
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def test_allocation_bomb_gets_memoryerror_not_host_oom(tmp_path):
    """APP_MAX_USER_MEMORY_BYTES bounds user-code address-space growth with
    a soft RLIMIT_AS window (runner.py:_apply_user_rlimits): an allocation
    bomb gets a clean in-process MemoryError — traceback in its own stderr,
    exit_code 1 — instead of inviting the host OOM killer, and the warm
    runner (limits restored) keeps serving (VERDICT r3 #6; the reference
    delegates this wholesale to the cluster runtime, README.md:56-57)."""
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    ws.mkdir()
    rp.mkdir()
    env = _server_env(ws, rp)
    env["APP_MAX_USER_MEMORY_BYTES"] = str(256 * 1024 * 1024)  # 256 MiB window
    proc = subprocess.Popen(
        [str(BINARY)], env=env, stdout=subprocess.PIPE, stderr=None
    )
    try:
        line = proc.stdout.readline().decode()
        port = int(re.search(r"port=(\d+)", line).group(1))
        with httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=60.0) as c:
            for _ in range(200):
                if c.get("/healthz").json().get("warm"):
                    break
                time.sleep(0.05)
            bomb = c.post(
                "/execute",
                json={
                    "source_code": "chunks = []\n"
                    "while True:\n"
                    "    chunks.append(bytearray(64 * 1024 * 1024))\n"
                },
            ).json()
            assert bomb["exit_code"] == 1, bomb
            assert "MemoryError" in bomb["stderr"], bomb["stderr"][-400:]
            assert not bomb.get("runner_restarted"), bomb
            # Limits were restored: the runner still serves normal requests
            # and can allocate modestly again.
            after = c.post(
                "/execute",
                json={"source_code": "b = bytearray(8 * 1024 * 1024)\nprint(len(b))\n"},
            ).json()
            assert after["exit_code"] == 0, after["stderr"]
            assert after["stdout"].strip() == str(8 * 1024 * 1024)
            # The knob is operator policy: a request-supplied env override
            # must NOT reach the run (else the bomb could disarm the limit).
            override = c.post(
                "/execute",
                json={
                    "source_code": "import os\n"
                    "print(os.environ.get('APP_MAX_USER_MEMORY_BYTES'))\n",
                    "env": {"APP_MAX_USER_MEMORY_BYTES": "0"},
                },
            ).json()
            assert override["stdout"].strip() == str(256 * 1024 * 1024)
    finally:
        proc.kill()
        proc.wait()


TRACEPARENT = "00-" + "a" * 32 + "-" + "b" * 16 + "-01"


def test_execute_trace_block_with_traceparent(executor):
    """A traceparent header makes the response carry a `trace` block: the
    echoed context plus install/exec/collect phase spans with offsets
    relative to the request's own start (ISSUE 4 tentpole — the control
    plane grafts these into the request's trace as child spans)."""
    client, ws = executor
    result = client.post(
        "/execute",
        json={"source_code": "print('traced')"},
        headers={"traceparent": TRACEPARENT},
    ).json()
    assert result["exit_code"] == 0
    trace = result["trace"]
    assert trace["traceparent"] == TRACEPARENT
    spans = {s["name"]: s for s in trace["spans"]}
    # The three phases every control plane reads; their stages ride along
    # (test_execute_trace_block_stages_tile_the_handler).
    assert set(spans) >= {"install", "exec", "collect"}
    for name in ("install", "exec", "collect"):
        assert "parent" not in spans[name]
        assert spans[name]["start_offset_s"] >= 0
        assert spans[name]["duration_s"] >= 0
    # Phases run in order: install, then exec, then collect.
    assert spans["install"]["start_offset_s"] <= spans["exec"]["start_offset_s"]
    assert spans["exec"]["start_offset_s"] <= spans["collect"]["start_offset_s"]
    # The exec span is the duration_s the response already reported.
    assert spans["exec"]["duration_s"] == result["duration_s"]


SERVER_STAGES = {
    # name -> the entry it nests in (None: a top-level phase of the handler)
    "parse": None,
    "install": None,
    "scan_before": "install",
    "exec": None,
    "guard_start": "exec",
    "runner_wait": "exec",
    "guard_stop": "exec",
    "collect": None,
    "scan_after": "collect",
    "outputs": "collect",
    "cache_scan": "collect",
}
RUNNER_STAGES = [
    "runner.pickup",
    "runner.prepare",
    "runner.limits_arm",
    "runner.user_code",
    "runner.limits_restore",
    "runner.finish",
]
EPS = 1e-6  # the runner rounds its offsets to the microsecond


def _ends(span):
    return span["start_offset_s"] + span["duration_s"]


def test_execute_trace_block_stages_tile_the_handler(executor):
    """Every stage between the request's arrival and the reply is named:
    present, ordered, non-negative, inside `total_s`; children inside the
    phase they name as `parent`; the warm runner's own stages inside
    `runner_wait`, each ending where the next begins. Nothing here times
    anything: only order and nesting are asserted."""
    client, ws = executor
    result = client.post(
        "/execute",
        json={"source_code": "print('staged')"},
        headers={"traceparent": TRACEPARENT},
    ).json()
    assert result["exit_code"] == 0 and result["warm"] is True
    trace = result["trace"]
    total = trace["total_s"]
    entries = trace["spans"]
    names = [s["name"] for s in entries]
    assert len(names) == len(set(names))
    spans = {s["name"]: s for s in entries}
    assert set(spans) >= set(SERVER_STAGES) | set(RUNNER_STAGES)
    for span in entries:
        if span["name"] == "runner.gc_after_reset":
            continue  # ran before this request: the one entry that may not nest
        assert span["start_offset_s"] >= 0 and span["duration_s"] >= 0, span
        assert _ends(span) <= total + EPS, span
        parent = span.get("parent")
        if parent is not None:
            # a parent is an EARLIER entry of the same block
            assert names.index(parent) < names.index(span["name"])
            assert spans[parent]["start_offset_s"] <= span["start_offset_s"] + EPS
            assert _ends(span) <= _ends(spans[parent]) + EPS
    for name, parent in SERVER_STAGES.items():
        assert spans[name].get("parent") == parent, name
    # The four phases tile the handler in order, from 0 to total_s.
    assert spans["parse"]["start_offset_s"] == 0
    order = ["parse", "install", "exec", "collect"]
    for earlier, later in zip(order, order[1:]):
        assert abs(_ends(spans[earlier]) - spans[later]["start_offset_s"]) < EPS
    assert abs(_ends(spans["collect"]) - total) < EPS
    # ... and exec's own three, and collect's.
    for parent, children in (
        ("exec", ["guard_start", "runner_wait", "guard_stop"]),
        ("collect", ["scan_after", "outputs", "cache_scan"]),
    ):
        assert abs(spans[children[0]]["start_offset_s"] - spans[parent]["start_offset_s"]) < EPS
        for earlier, later in zip(children, children[1:]):
            assert abs(_ends(spans[earlier]) - spans[later]["start_offset_s"]) < EPS
    # The runner's stages: children of exec in the tree, inside runner_wait
    # in time, tiling it from the pipe write on.
    wait = spans["runner_wait"]
    assert abs(spans["runner.pickup"]["start_offset_s"] - wait["start_offset_s"]) < EPS
    for earlier, later in zip(RUNNER_STAGES, RUNNER_STAGES[1:]):
        assert abs(_ends(spans[earlier]) - spans[later]["start_offset_s"]) < 2 * EPS
    for name in RUNNER_STAGES:
        assert spans[name]["parent"] == "exec"
        assert spans[name]["start_offset_s"] >= wait["start_offset_s"] - EPS
        assert _ends(spans[name]) <= _ends(wait) + EPS
    # An unprofiled turn has no profiler stage.
    assert "runner.profile_start" not in spans and "runner.profile_stop" not in spans


def test_reset_trace_block_and_gc_after_reset(executor):
    """`POST /reset` carries the same kind of block (runner_reset with the
    runner's scrub inside it, then wipe); the runner's full collection
    after the reset's ack is reported by the NEXT request, once."""
    client, ws = executor
    reply = client.post("/reset", headers={"traceparent": TRACEPARENT})
    assert reply.status_code == 200, reply.text
    body = reply.json()
    assert body["ok"] is True
    trace = body["trace"]
    assert trace["traceparent"] == TRACEPARENT
    spans = {s["name"]: s for s in trace["spans"]}
    assert set(spans) == {"runner_reset", "runner.pickup", "runner.scrub", "wipe"}
    for span in spans.values():
        assert span["start_offset_s"] >= 0 and span["duration_s"] >= 0
        assert _ends(span) <= trace["total_s"] + EPS
    assert spans["runner.scrub"]["parent"] == "runner_reset"
    assert _ends(spans["runner.scrub"]) <= _ends(spans["runner_reset"]) + EPS
    assert abs(_ends(spans["runner_reset"]) - spans["wipe"]["start_offset_s"]) < EPS
    # No trace context, no block: the wire is unchanged for an old control plane.
    assert "trace" not in client.post("/reset").json()

    def stages_of_next():
        result = client.post(
            "/execute",
            json={"source_code": "print('next')"},
            headers={"traceparent": TRACEPARENT},
        ).json()
        return {s["name"]: s for s in result["trace"]["spans"]}

    after_reset = stages_of_next()
    assert "runner.gc_after_reset" in after_reset
    assert after_reset["runner.gc_after_reset"]["duration_s"] >= 0
    assert after_reset["runner.gc_after_reset"]["parent"] == "exec"
    assert "runner.gc_after_reset" not in stages_of_next()


def test_execute_no_trace_block_without_traceparent(executor):
    """No trace context, no trace block — the wire format is unchanged for
    untraced callers (and old control planes)."""
    client, ws = executor
    result = execute(client, "print('untraced')")
    assert "trace" not in result


def test_execute_stream_trace_block(executor):
    """The streaming surface's final event carries the same trace block."""
    client, ws = executor
    with client.stream(
        "POST",
        "/execute/stream",
        json={"source_code": "print('streamed')"},
        headers={"traceparent": TRACEPARENT},
    ) as resp:
        assert resp.status_code == 200
        lines = [json.loads(l) for l in resp.iter_lines() if l.strip()]
    final = lines[-1]
    assert final["exit_code"] == 0
    assert final["trace"]["traceparent"] == TRACEPARENT
    assert {s["name"] for s in final["trace"]["spans"]} >= {
        "install",
        "exec",
        "collect",
    }


def test_unwritable_tmpdir_falls_back_to_tmp(tmp_path):
    """ISSUE 4 satellite: a bogus TMPDIR (operator typo, missing mount)
    must not fail every request opaquely at mkdtemp — the server falls back
    to /tmp with a logged warning and keeps serving."""
    ws = tmp_path / "ws"
    rp = tmp_path / "rp"
    ws.mkdir()
    rp.mkdir()
    env = _server_env(ws, rp)
    env["TMPDIR"] = str(tmp_path / "does-not-exist")
    proc = subprocess.Popen(
        [str(BINARY)], env=env, stdout=subprocess.PIPE, stderr=None
    )
    try:
        line = proc.stdout.readline().decode()
        port = int(re.search(r"port=(\d+)", line).group(1))
        with httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=30.0) as c:
            for _ in range(200):
                if c.get("/healthz").json().get("warm"):
                    break
                time.sleep(0.1)
            else:
                pytest.fail("runner never warmed")
            result = c.post(
                "/execute", json={"source_code": "print('fallback ok')"}
            ).json()
            assert result["exit_code"] == 0, result
            assert result["stdout"] == "fallback ok\n"
    finally:
        proc.kill()
        proc.wait()


# ---------------------------------------------------------------------------
# GET /device-stats (the device-health telemetry plane)


def test_device_stats_basic_shape(executor):
    """Warm idle host: the probe-facing signals are all present, ages are
    server-computed, and the op window is closed."""
    client, _ = executor
    execute(client, "print('prime the op counters')")
    stats = client.get("/device-stats").json()
    assert stats["status"] == "ok"
    assert stats["warm"] is True
    assert stats["warm_state"] == "ready"
    assert stats["runner_alive"] is True
    assert stats["runner_pid"] > 0
    assert stats["device_count"] == 0  # APP_WARM_IMPORT_JAX=0 in this suite
    assert stats["op_in_flight"] is False
    assert stats["op_age_s"] == 0
    # The warm-up that made this runner ready was measured.
    assert stats["attach_seconds"] >= 0
    assert stats["attach_pending_s"] == 0
    # A device op just succeeded (the execute above).
    assert 0 <= stats["last_device_op_age_s"] < 30
    # Passive heartbeat: the runner wrote its response moments ago.
    assert 0 <= stats["runner_heartbeat_age_s"] < 30
    # RSS for both processes via /proc.
    assert stats["rss_bytes"] > 0
    assert stats["runner_rss_bytes"] > 0
    assert stats["uptime_s"] > 0


def test_device_stats_answers_during_inflight_op(executor):
    """THE design requirement: while a device op is running (exec_mutex and
    runner_mutex held — exactly the wedged state), /device-stats must still
    answer, report the op in flight with a growing age, and carry the op's
    declared budget so the probe can judge the stall."""
    client, _ = executor
    import threading

    done = threading.Event()

    def run_slow():
        try:
            execute(client, "import time; time.sleep(2)", timeout=30)
        finally:
            done.set()

    thread = threading.Thread(target=run_slow)
    thread.start()
    try:
        probe = httpx.Client(base_url=str(client.base_url), timeout=5.0)
        seen_inflight = None
        for _ in range(100):
            stats = probe.get("/device-stats").json()
            if stats["op_in_flight"]:
                seen_inflight = stats
                break
            time.sleep(0.05)
        assert seen_inflight is not None, "never observed the op in flight"
        assert seen_inflight["op_age_s"] >= 0
        # The budget rides along (timeout 30 + the server's 0.5s pad).
        assert 29 < seen_inflight["op_timeout_s"] < 32
        probe.close()
    finally:
        done.wait(timeout=30)
        thread.join(timeout=30)
    # After completion the window closes and the success stamp moves.
    stats = client.get("/device-stats").json()
    assert stats["op_in_flight"] is False
    assert 0 <= stats["last_device_op_age_s"] < 30


def test_device_stats_runner_identity_after_kill(executor):
    """A forced runner kill flips runner_alive until the background rewarm
    lands — the probe's 'runner died while idle' signal."""
    client, _ = executor
    result = execute(
        client,
        "import signal\n"
        "signal.signal(signal.SIGINT, signal.SIG_IGN)\n"
        "while True: pass",
        timeout=1,
    )
    assert result["exit_code"] == -1
    # Immediately after the kill (before the background rewarm finishes)
    # the mirror may already be re-ready; assert only the eventual state.
    for _ in range(100):
        stats = client.get("/device-stats").json()
        if stats["runner_alive"] and stats["warm_state"] == "ready":
            break
        time.sleep(0.1)
    else:
        pytest.fail("runner never returned to ready after forced kill")
    # The rewarm recorded a fresh attach latency.
    assert stats["attach_seconds"] >= 0


def test_device_stats_detects_silently_dead_runner(executor):
    """A runner OOM-killed BETWEEN requests leaves no trace until the next
    execute — except in /device-stats, whose waitid(WNOWAIT) peek exposes
    the corpse: runner_alive flips false while warm_state still says ready
    (the probe classifies this suspect/runner_dead). The next execute then
    recovers via the normal dead-runner restart path."""
    client, _ = executor
    stats = client.get("/device-stats").json()
    assert stats["runner_alive"] is True
    runner_pid = int(stats["runner_pid"])
    os.kill(runner_pid, signal.SIGKILL)
    for _ in range(100):
        stats = client.get("/device-stats").json()
        if stats["runner_alive"] is False:
            break
        time.sleep(0.05)
    else:
        pytest.fail("silently killed runner still reported alive")
    # The next execute discovers the corpse on the wire (EPIPE -> kDied),
    # reports runner_restarted, and kicks the background rewarm; the one
    # after that is served. Restores warm service for the rest of the
    # module.
    result = execute(client, "print('finds the corpse')")
    assert result["runner_restarted"] is True
    result = execute(client, "print('recovered')")
    assert result["stdout"] == "recovered\n"
    for _ in range(200):
        if client.get("/healthz").json().get("warm"):
            break
        time.sleep(0.1)
    else:
        pytest.fail("runner did not rewarm after silent death")


def test_stale_lease_claim_refused_with_typed_409(executor):
    """Per-chip lease fencing, executor side: once a lease token is
    recorded (POST /lease), an execute dispatch presenting an OLDER token
    is refused with the typed 409 — before the body is processed and
    before exec_mutex, so a stale claim can never even queue behind the
    device plane. Tokenless requests and the current token keep serving
    (old-control-plane compatibility)."""
    client, ws = executor
    # No token recorded yet: any claim passes through.
    r = client.post(
        "/execute",
        json={"source_code": "print('pre')"},
        headers={"x-lease-token": "lane-0:1"},
    )
    assert r.status_code == 200
    # Record generation 2 for this sandbox's chips.
    r = client.post("/lease", json={"token": "lane-0:2"})
    assert r.status_code == 200 and r.json()["ok"] is True
    assert client.get("/device-stats").json()["lease_token"] == "lane-0:2"
    # A stale (generation-1) claim is refused, typed.
    r = client.post(
        "/execute",
        json={"source_code": "print('stale')"},
        headers={"x-lease-token": "lane-0:1"},
    )
    assert r.status_code == 409
    body = r.json()
    assert body["error"] == "stale_lease"
    # The HELD token is log-only: echoing the successor's valid credential
    # to whoever presented a stale one would let any sandbox-internal
    # caller harvest it with a junk claim. The caller's own (stale) token
    # echoes back for diagnostics.
    assert "held" not in body
    assert body["offered"] == "lane-0:1"
    # /execute-batch and /reset refuse the same stale claim (a retry
    # racing a dispose must not wipe the successor's workspace).
    r = client.post(
        "/execute-batch",
        json={"jobs": [{"source_code": "print(1)"}] * 2, "timeout": 10},
        headers={"x-lease-token": "lane-0:1"},
    )
    assert r.status_code == 409 and r.json()["error"] == "stale_lease"
    r = client.post("/reset", headers={"x-lease-token": "lane-0:1"})
    assert r.status_code == 409 and r.json()["error"] == "stale_lease"
    # The CURRENT token serves, as does a tokenless dispatch.
    r = client.post(
        "/execute",
        json={"source_code": "print('current')"},
        headers={"x-lease-token": "lane-0:2"},
    )
    assert r.status_code == 200 and r.json()["stdout"] == "current\n"
    r = client.post("/execute", json={"source_code": "print('bare')"})
    assert r.status_code == 200 and r.json()["stdout"] == "bare\n"
    # Bad /lease bodies are client errors, not token rotations.
    assert client.post("/lease", json={}).status_code == 400
    # First-write-wins: re-pushing the SAME token is an idempotent 200
    # (control-plane push retries), but a ROTATION is refused — tenant
    # code inside the sandbox must not be able to make the control
    # plane's real token read stale.
    assert client.post("/lease", json={"token": "lane-0:2"}).json()["ok"]
    r = client.post("/lease", json={"token": "lane-0:999"})
    assert r.status_code == 409
    assert r.json()["error"] == "lease_already_recorded"
    assert client.get("/device-stats").json()["lease_token"] == "lane-0:2"


def test_snapshot_restore_round_trips_interpreter_state(executor):
    """The session-durability wire protocol against the real binary: a turn
    mutates the interpreter (env var + workspace-module global), /snapshot
    captures it, /reset wipes it, and /restore on a re-uploaded workspace
    brings it back byte-for-byte. This is exactly the hibernate -> evict ->
    lazy-restore path the control plane drives."""
    client, ws = executor
    client.post("/reset")
    assert client.put("/workspace/durmod.py", content=b"counter = 0\n").status_code == 200
    # Workspace-module imports resolve however user code arranges them —
    # here the usual cwd insert (cwd IS the workspace in the warm runner).
    result = execute(
        client,
        "import os, sys\nsys.path.insert(0, os.getcwd())\nimport durmod\n"
        "os.environ['DURABLE_PROBE'] = '42'\ndurmod.counter = 7\n",
    )
    assert result["exit_code"] == 0, result

    snap = client.post("/snapshot", json={})
    assert snap.status_code == 200, snap.text
    body = snap.json()
    assert body["ok"] is True
    state = body["state"]
    assert state["env_set"]["DURABLE_PROBE"] == "42"
    assert "durmod" in [m["name"] for m in state["modules"]]

    # Reset = the hibernate dispose: env gone, workspace gone, modules gone.
    assert client.post("/reset").json()["ok"] is True
    wiped = execute(client, "import os; print(os.environ.get('DURABLE_PROBE'))")
    assert wiped["stdout"] == "None\n"
    assert not (ws / "durmod.py").exists()

    # Restore = what _restore_session does: workspace files first, then the
    # interpreter overlay.
    client.put("/workspace/durmod.py", content=b"counter = 0\n")
    rest = client.post("/restore", json={"state": state})
    assert rest.status_code == 200, rest.text
    assert rest.json()["ok"] is True
    back = execute(
        client,
        "import os, sys\nsys.path.insert(0, os.getcwd())\nimport durmod\n"
        "print(os.environ['DURABLE_PROBE'], durmod.counter)",
    )
    assert back["stdout"] == "42 7\n"
    client.post("/reset")


def test_restore_refusals_leave_runner_untouched(executor):
    """Corrupt or version-skewed state is refused typed BEFORE any mutation
    lands — the never-half-restored invariant at the runner boundary. The
    runner must keep serving normally afterwards."""
    client, _ = executor
    client.post("/reset")
    execute(client, "import os; os.environ['CANARY'] = 'intact'")
    r = client.post("/restore", json={"state": {"version": 99}})
    assert r.status_code == 200
    assert r.json() == {"ok": False, "reason": "bad_state_version"}
    r = client.post(
        "/restore",
        json={
            "state": {
                "version": 1,
                "env_set": {},
                "env_del": [],
                "cwd": ".",
                "modules": [{"name": "x", "values": {"v": "!!!not-base64!!!"}}],
            }
        },
    )
    assert r.status_code == 200
    assert r.json() == {"ok": False, "reason": "corrupt_state"}
    # Neither refusal disturbed the live interpreter.
    result = execute(client, "import os; print(os.environ['CANARY'])")
    assert result["stdout"] == "intact\n"
    client.post("/reset")


def test_snapshot_respects_max_bytes_budget(executor):
    """An oversized interpreter refuses to snapshot (state_too_large) rather
    than shipping an unbounded blob to the control plane; the session then
    just stays resident instead of hibernating."""
    client, _ = executor
    client.post("/reset")
    execute(client, "import os; os.environ['BIG'] = 'x' * 4096")
    r = client.post("/snapshot", json={"max_bytes": 1})
    assert r.status_code == 200
    assert r.json() == {"ok": False, "reason": "state_too_large"}
    # An adequate budget still snapshots the same interpreter.
    assert client.post("/snapshot", json={}).json()["ok"] is True
    client.post("/reset")
