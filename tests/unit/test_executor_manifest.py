"""Workspace-manifest protocol tests against the real C++ executor binary:
stream-hashed uploads, GET /workspace-manifest (lazy rehash), conditional
PUT (If-None-Match -> 304), per-file sha256 + deleted reporting on /execute,
manifest wipe on /reset, and the APP_WORKSPACE_MANIFEST=0 legacy mode that
emulates an old binary for the control plane's fallback path.
"""

import hashlib
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


def _spawn(tmp_root: Path, **env_extra):
    if "TEST_EXECUTOR_BINARY" not in os.environ and not BINARY.exists():
        subprocess.run(
            ["make", "-C", str(EXECUTOR_DIR)], check=True, capture_output=True
        )
    ws = tmp_root / "ws"
    rp = tmp_root / "rp"
    ws.mkdir()
    rp.mkdir()
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
    env.update(env_extra)
    proc = subprocess.Popen(
        [str(BINARY)], env=env, stdout=subprocess.PIPE, stderr=None
    )
    line = proc.stdout.readline().decode()
    port = int(re.search(r"port=(\d+)", line).group(1))
    client = httpx.Client(base_url=f"http://127.0.0.1:{port}", timeout=30.0)
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
    proc, client, ws = _spawn(tmp_path_factory.mktemp("manifest"))
    yield client, ws
    client.close()
    proc.kill()
    proc.wait()


@pytest.fixture(scope="module")
def legacy_executor(tmp_path_factory):
    """The same binary in legacy wire mode — stands in for an old executor
    build when testing the control plane's full-transfer fallback."""
    proc, client, ws = _spawn(
        tmp_path_factory.mktemp("legacy"), APP_WORKSPACE_MANIFEST="0"
    )
    yield client, ws
    client.close()
    proc.kill()
    proc.wait()


def sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def execute(client, source, **kwargs):
    resp = client.post("/execute", json={"source_code": source, **kwargs})
    assert resp.status_code == 200, resp.text
    return resp.json()


def test_upload_returns_streamed_hash(executor):
    client, _ = executor
    body = b"manifest payload"
    resp = client.put("/workspace/m/one.txt", content=body)
    assert resp.status_code == 200
    assert resp.json()["sha256"] == sha(body)


def test_manifest_reflects_uploads(executor):
    client, _ = executor
    body = b"second file"
    client.put("/workspace/m/two.txt", content=body)
    manifest = client.get("/workspace-manifest").json()["files"]
    assert manifest["m/two.txt"] == sha(body)
    assert manifest["m/one.txt"] == sha(b"manifest payload")


def test_conditional_put_304_skips_body(executor):
    client, ws = executor
    body = b"conditional content"
    client.put("/workspace/cond.txt", content=body)
    before_mtime = (ws / "cond.txt").stat().st_mtime_ns
    resp = client.put(
        "/workspace/cond.txt",
        content=body,
        headers={"If-None-Match": sha(body)},
    )
    assert resp.status_code == 304
    assert resp.content == b""
    # The 304 proved no write happened: the file's mtime is untouched.
    assert (ws / "cond.txt").stat().st_mtime_ns == before_mtime


def test_conditional_put_mismatch_writes_normally(executor):
    client, ws = executor
    new_body = b"conditional content v2"
    resp = client.put(
        "/workspace/cond.txt",
        content=new_body,
        headers={"If-None-Match": sha(new_body)},
    )
    # The manifest held v1's sha, so the claim mismatched: a normal write.
    assert resp.status_code == 200
    assert resp.json()["sha256"] == sha(new_body)
    assert (ws / "cond.txt").read_bytes() == new_body


def test_conditional_put_stale_disk_rewrites(executor):
    """A manifest hit alone is not enough: when the file on disk no longer
    matches the cached signature (user code touched it out of band), the
    conditional PUT must fall through to a write, not 304 against bytes the
    workspace lost."""
    client, ws = executor
    body = b"stale-check content"
    client.put("/workspace/stale.txt", content=body)
    (ws / "stale.txt").write_bytes(b"mutated behind the manifest")
    resp = client.put(
        "/workspace/stale.txt", content=body, headers={"If-None-Match": sha(body)}
    )
    assert resp.status_code == 200
    assert (ws / "stale.txt").read_bytes() == body


def test_execute_reports_hashes_and_deletions(executor):
    client, _ = executor
    client.put("/workspace/doomed.txt", content=b"to be deleted")
    result = execute(
        client,
        "import os\nopen('fresh.txt', 'w').write('fresh')\nos.remove('doomed.txt')",
    )
    by_path = {
        entry["path"]: entry.get("sha256") for entry in result["files"]
    }
    assert by_path["fresh.txt"] == sha(b"fresh")
    assert "doomed.txt" in result["deleted"]
    manifest = client.get("/workspace-manifest").json()["files"]
    assert manifest["fresh.txt"] == sha(b"fresh")
    assert "doomed.txt" not in manifest


def test_manifest_lazy_rehash_on_out_of_band_change(executor):
    """GET /workspace-manifest must reconcile with the disk: a file mutated
    without an upload (size/mtime signature changed) rehashes; everything
    else keeps its cached sha without re-reading bytes."""
    client, ws = executor
    client.put("/workspace/lazy.txt", content=b"original")
    (ws / "lazy.txt").write_bytes(b"mutated out of band")
    manifest = client.get("/workspace-manifest").json()["files"]
    assert manifest["lazy.txt"] == sha(b"mutated out of band")


def test_reset_wipes_manifest(executor):
    client, _ = executor
    client.put("/workspace/resetme.txt", content=b"x")
    assert client.post("/reset").status_code == 200
    assert client.get("/workspace-manifest").json()["files"] == {}
    # A conditional PUT against the wiped generation must re-upload.
    resp = client.put(
        "/workspace/resetme.txt", content=b"x", headers={"If-None-Match": sha(b"x")}
    )
    assert resp.status_code == 200


# ------------------------------------------------------------- legacy mode


def test_legacy_mode_plain_files_and_no_manifest_route(legacy_executor):
    client, _ = legacy_executor
    resp = client.put("/workspace/old.txt", content=b"old-school")
    assert resp.status_code == 200
    assert "sha256" not in resp.json()
    assert client.get("/workspace-manifest").status_code == 404
    result = execute(client, "open('made.txt', 'w').write('y')")
    assert result["files"] == ["made.txt"]
    assert "deleted" not in result


def test_legacy_mode_ignores_if_none_match(legacy_executor):
    client, ws = legacy_executor
    body = b"legacy conditional"
    client.put("/workspace/legacy-cond.txt", content=body)
    resp = client.put(
        "/workspace/legacy-cond.txt",
        content=body,
        headers={"If-None-Match": sha(body)},
    )
    # An old binary knows nothing of conditional uploads: plain 200 write.
    assert resp.status_code == 200
    assert (ws / "legacy-cond.txt").read_bytes() == body
