"""Compile-cache protocol tests against the real C++ executor binary:
GET /compile-cache-manifest, hash-negotiated PUT (If-None-Match -> 304) and
GET of entries, the /execute response's compile_cache block, the
APP_COMPILE_CACHE=0 legacy mode, and the regression test for the pod-reuse
cache wipe: /reset wipes APP_RESET_EXTRA_WIPE_DIRS but PRESERVES the
compilation-cache subtree even when the cache dir lives under a wiped dir
(the historic /tmp default put it exactly there).
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
    return proc, client


@pytest.fixture()
def stack(tmp_path):
    """Executor whose cache dir lives UNDER an extra wipe dir — the exact
    pod-reuse layout that used to lose the cache at every turnover."""
    wiped = tmp_path / "wiped-tmp"
    cache = wiped / "deep" / "jax-cache"
    wiped.mkdir()
    proc, client = _spawn(
        tmp_path,
        JAX_COMPILATION_CACHE_DIR=str(cache),
        APP_RESET_EXTRA_WIPE_DIRS=str(wiped),
    )
    yield client, cache, wiped
    client.close()
    proc.kill()
    proc.wait()


def sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def test_cache_dir_created_at_boot(stack):
    _, cache, _ = stack
    assert cache.is_dir()  # mkdir -p at boot, several levels deep


def test_manifest_put_get_roundtrip(stack):
    client, cache, _ = stack
    assert client.get("/compile-cache-manifest").json()["files"] == {}
    body = b"fake-xla-executable"
    resp = client.put("/compile-cache/jit_f-abc-cache", content=body)
    assert resp.status_code == 200
    assert resp.json()["sha256"] == sha(body)
    assert (cache / "jit_f-abc-cache").read_bytes() == body
    manifest = client.get("/compile-cache-manifest").json()["files"]
    assert manifest == {"jit_f-abc-cache": sha(body)}
    assert client.get("/compile-cache/jit_f-abc-cache").content == body


def test_conditional_put_304(stack):
    client, cache, _ = stack
    body = b"conditional-entry"
    client.put("/compile-cache/cond-cache", content=body)
    before = (cache / "cond-cache").stat().st_mtime_ns
    resp = client.put(
        "/compile-cache/cond-cache",
        content=body,
        headers={"If-None-Match": sha(body)},
    )
    assert resp.status_code == 304
    assert (cache / "cond-cache").stat().st_mtime_ns == before


def test_reset_wipes_extra_dir_but_preserves_cache_subtree(stack):
    """THE pod-reuse regression: user residue in the wiped dir goes, the
    compilation cache (and its ancestor chain) survives, and the manifest
    still answers for it afterwards."""
    client, cache, wiped = stack
    entry = b"surviving-kernel"
    client.put("/compile-cache/keeper-cache", content=entry)
    (wiped / "user-residue.txt").write_text("planted by the previous tenant")
    (wiped / "deep" / "sibling.txt").write_text("also residue")
    resp = client.post("/reset")
    assert resp.status_code == 200, resp.text
    assert resp.json()["ok"] is True
    assert not (wiped / "user-residue.txt").exists()
    assert not (wiped / "deep" / "sibling.txt").exists()
    assert (cache / "keeper-cache").read_bytes() == entry
    manifest = client.get("/compile-cache-manifest").json()["files"]
    assert manifest["keeper-cache"] == sha(entry)
    # And the negotiation state survived with it: an If-None-Match re-PUT
    # still 304s after turnover (a wiped cache would have to re-upload).
    resp = client.put(
        "/compile-cache/keeper-cache",
        content=entry,
        headers={"If-None-Match": sha(entry)},
    )
    assert resp.status_code == 304


def test_reset_refuses_symlink_planted_at_preserved_cache_path(stack):
    """The preserve check must not be purely lexical: user code that empties
    the cache dir, rmdirs it, and plants a symlink at the same path must NOT
    get the symlink preserved through /reset (it would redirect the next
    generation's cache writes wherever it points). The impostor is unlinked
    and the wipe reports incomplete, so the sandbox is disposed."""
    client, cache, wiped = stack
    client.put("/compile-cache/doomed-cache", content=b"bytes")
    # The tamper: replace the (real) cache dir with a symlink to a target
    # outside every wiped tree.
    target = wiped.parent / "exfil-target"
    target.mkdir()
    for child in cache.iterdir():
        child.unlink()
    cache.rmdir()
    cache.symlink_to(target)
    resp = client.post("/reset")
    assert resp.status_code == 409, resp.text
    # The planted symlink did not survive, and its target was not entered.
    assert not cache.is_symlink()
    assert not cache.exists()
    assert target.is_dir()


def test_reset_preserves_only_real_dir_not_regular_file(stack):
    """Same tamper with a regular file at the preserved path."""
    client, cache, wiped = stack
    for child in cache.iterdir():
        child.unlink()
    cache.rmdir()
    cache.write_bytes(b"not a directory")
    resp = client.post("/reset")
    assert resp.status_code == 409, resp.text
    assert not cache.exists()


def test_execute_reports_compile_cache_block(stack):
    client, cache, _ = stack
    resp = client.post(
        "/execute",
        json={
            "source_code": (
                "import os\n"
                "d = os.environ['JAX_COMPILATION_CACHE_DIR']\n"
                "open(os.path.join(d, 'jit_new-run-cache'), 'wb')"
                ".write(b'k' * 64)\n"
            )
        },
    )
    assert resp.status_code == 200
    body = resp.json()
    assert body["exit_code"] == 0, body["stderr"]
    block = body["compile_cache"]
    assert block["new_entries"] == 1
    assert block["new_bytes"] == 64
    assert block["entries"] >= 1
    # Cache entries are NOT workspace files: the changed-file scan must not
    # ship them to storage as user outputs.
    assert body["files"] == []


def test_path_confinement_on_cache_routes(stack):
    client, _, _ = stack
    resp = client.put("/compile-cache/../escape", content=b"nope")
    assert resp.status_code in (400, 403)
    resp = client.get("/compile-cache/../../etc/passwd")
    assert resp.status_code in (400, 403, 404)


def test_disabled_mode_emulates_old_binary(tmp_path):
    """APP_COMPILE_CACHE=0 (and a binary without a cache dir) answers 404
    on every compile-cache route — what the control plane's legacy
    fallback keys off."""
    cache = tmp_path / "cc"
    proc, client = _spawn(
        tmp_path,
        JAX_COMPILATION_CACHE_DIR=str(cache),
        APP_COMPILE_CACHE="0",
    )
    try:
        assert client.get("/compile-cache-manifest").status_code == 404
        assert (
            client.put("/compile-cache/x-cache", content=b"y").status_code
            == 404
        )
        body = client.post(
            "/execute", json={"source_code": "print('ok')"}
        ).json()
        assert "compile_cache" not in body
    finally:
        client.close()
        proc.kill()
        proc.wait()


def test_disabled_cache_is_wiped_like_everything_else(tmp_path):
    """Kill switch ⇒ exact pre-cache reset behavior: with APP_COMPILE_CACHE=0
    a cache dir under an extra wipe dir gets wiped at turnover like any
    other tenant residue (a preserved-but-unserved dir would keep the very
    cross-generation channel the switch exists to close)."""
    wiped = tmp_path / "wiped-tmp"
    cache = wiped / "jax-cache"
    wiped.mkdir()
    cache.mkdir()
    (cache / "jit_stale-cache").write_bytes(b"previous tenant's kernel")
    proc, client = _spawn(
        tmp_path,
        JAX_COMPILATION_CACHE_DIR=str(cache),
        APP_RESET_EXTRA_WIPE_DIRS=str(wiped),
        APP_COMPILE_CACHE="0",
    )
    try:
        resp = client.post("/reset")
        assert resp.status_code == 200, resp.text
        assert not cache.exists()
    finally:
        client.close()
        proc.kill()
        proc.wait()


def test_no_cache_dir_means_no_routes(tmp_path):
    env = {k: v for k, v in os.environ.items()}
    proc, client = _spawn(tmp_path)
    try:
        if "JAX_COMPILATION_CACHE_DIR" in env:
            pytest.skip("environment exports a cache dir")
        assert client.get("/compile-cache-manifest").status_code == 404
    finally:
        client.close()
        proc.kill()
        proc.wait()
