"""Copy from storage: where the backend that spawned a sandbox declared that
its server sees the storage directory (the local backend), an input file
reaches the workspace by POST /copy-from-storage/workspace/<rel>: one copy
inside the kernel from the object its sha names, no byte through the control
plane. Against the real local backend and the real C++ server: what the
route does, what it refuses, and that a sandbox whose backend declares
nothing is sent the streamed PUT exactly as before.
"""

import json
import os
from pathlib import Path

import httpx
import pytest
from test_executor_manifest import _spawn, sha
from test_transfer_sync import FakeSandboxHost, TransferBackend, settle

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.local import LocalSandboxBackend
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.errors import LimitExceededError
from bee_code_interpreter_fs_tpu.services.storage import Storage

MB = 1 << 20
ROUTE = "/copy-from-storage/workspace/"


def local_stack(tmp_path, storage_dir=None, **config_kwargs):
    """The real local backend; `storage_dir` is where the objects really
    are, when that is not where the backend's configuration says."""
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        local_sandbox_root=str(tmp_path / "sandboxes"),
        executor_pod_queue_target_length=1,
        jax_compilation_cache_dir="",
        default_execution_timeout=30.0,
        **config_kwargs,
    )
    backend = LocalSandboxBackend(config, warm_import_jax=False)
    return CodeExecutor(
        backend, Storage(storage_dir or config.file_storage_path), config
    )


def server_beside(tmp_path, storage: Path | None, **env):
    """The C++ server alone, spawned beside `storage` (None: beside none)."""
    if storage is not None:
        storage.mkdir(exist_ok=True)
        env["APP_STORAGE_OBJECTS_DIR"] = str(storage)
    (tmp_path / "server").mkdir()
    return _spawn(tmp_path / "server", **env)


def put_object(storage: Path, data: bytes) -> str:
    (storage / sha(data)).write_bytes(data)
    return sha(data)


def copy(client, rel: str, object_id: str) -> httpx.Response:
    return client.post(ROUTE + rel, headers={"X-Storage-Object": object_id})


STAT_INPUTS = (
    "import hashlib, json, os\n"
    "print(json.dumps({f: [os.stat(f).st_nlink, os.stat(f).st_dev, os.stat(f).st_ino,"
    " hashlib.sha256(open(f, 'rb').read()).hexdigest()] for f in sorted(os.listdir('.'))}))"
)


async def test_execute_copies_every_input_as_a_fresh_inode(tmp_path):
    executor = local_stack(tmp_path)
    try:
        blobs = {"a.bin": os.urandom(3 * MB + 17), "deep/b.txt": b"second input"}
        files = {
            f"/workspace/{rel}": await executor.storage.write(data)
            for rel, data in blobs.items()
        }
        total = sum(len(data) for data in blobs.values())
        with executor.tracer.start_trace("test") as root:
            result = await executor.execute(
                "import os; os.chdir('deep'); os.rename('b.txt', '../b.txt'); os.chdir('..'); os.rmdir('deep')\n"
                + STAT_INPUTS,
                files=files,
                executor_id="copy-sess",
            )
        assert result.exit_code == 0, result.stderr
        assert result.phases["upload_bytes"] == float(total)
        assert result.phases["upload_copied_bytes"] == result.phases["upload_bytes"]
        seen = json.loads(result.stdout)
        for rel, data in blobs.items():
            nlink, dev, ino, digest = seen[Path(rel).name]
            stored = os.stat(executor.storage.path / sha(data))
            assert digest == sha(data), "the workspace file is the object, byte for byte"
            assert nlink == 1 and (dev, ino) != (stored.st_dev, stored.st_ino)
        # The untouched input is no changed file of the turn, and the server's
        # manifest holds it under the object's own name, hashed by nobody.
        assert "/workspace/a.bin" not in result.files
        sandbox = executor._sessions["copy-sess"].sandbox
        async with httpx.AsyncClient() as client:
            listed = (await client.get(f"{sandbox.url}/workspace-manifest")).json()
        assert listed["files"]["a.bin"] == files["/workspace/a.bin"]
        [upload] = [
            span
            for span in executor.tracer.ring.trace(root.trace_id)
            if span["name"] == "transfer.upload"
        ]
        assert upload["attributes"]["bytes_copied"] == upload["attributes"]["bytes_moved"] == total
        rendered = executor.metrics.registry.render()
        assert f"code_interpreter_transfer_copied_bytes_total {total}" in rendered
        assert "code_interpreter_transfer_copied_files_total 2" in rendered
    finally:
        await executor.close()


@pytest.mark.parametrize("executor_id", [None, "overwrite-sess"], ids=["stateless", "session"])
async def test_writing_an_input_in_place_never_touches_the_object(tmp_path, executor_id):
    executor = local_stack(tmp_path)
    try:
        payload = b"original bytes " * 4096
        object_id = await executor.storage.write(payload)
        files = {"/workspace/in.bin": object_id}
        first = await executor.execute(
            "with open('in.bin', 'r+b') as f:\n    f.write(b'SCRIBBLED')\n"
            "print(open('in.bin', 'rb').read(9).decode())",
            files=files,
            executor_id=executor_id,
        )
        assert first.exit_code == 0, first.stderr
        assert first.stdout.strip() == "SCRIBBLED"
        stored = (executor.storage.path / object_id).read_bytes()
        assert stored == payload and sha(stored) == object_id
        assert first.files["/workspace/in.bin"] != object_id
        # The next turn names the same object and reads the original bytes.
        second = await executor.execute(
            "print(open('in.bin', 'rb').read(14).decode())",
            files=files,
            executor_id=executor_id,
        )
        assert second.exit_code == 0, second.stderr
        assert second.stdout.strip() == "original bytes"
        assert second.phases["upload_copied_bytes"] == float(len(payload))
    finally:
        await executor.close()


# ------------------------------------------------- refusals and fallbacks


async def _case_bad_object_id(tmp_path):
    storage = tmp_path / "storage"
    proc, client, ws = server_beside(tmp_path, storage)
    try:
        object_id = put_object(storage, b"named by its sha")
        for bad in ("", "abc", object_id.upper(), object_id[:-1] + "g", "../" + object_id[3:]):
            resp = copy(client, "x.bin", bad)
            assert resp.status_code == 400, (bad, resp.text)
        assert not (ws / "x.bin").exists()
    finally:
        client.close()
        proc.kill()
        proc.wait()


async def _case_no_such_object(tmp_path):
    """The server answers 404 for an id it does not see; the control plane
    then streams that file, remembers it for the host, and serves the turn."""
    storage = tmp_path / "storage"
    proc, client, ws = server_beside(tmp_path, storage)
    try:
        assert copy(client, "x.bin", sha(b"never stored")).status_code == 404
        (storage / sha(b"a directory")).mkdir()
        assert copy(client, "x.bin", sha(b"a directory")).status_code == 404
        assert not (ws / "x.bin").exists()
    finally:
        client.close()
        proc.kill()
        proc.wait()
    # The backend's declaration is wrong here: the objects are elsewhere.
    executor = local_stack(tmp_path, storage_dir=tmp_path / "elsewhere")
    try:
        blobs = [b"first " * 1000, b"second " * 1000]
        files = {
            f"/workspace/f{i}.bin": await executor.storage.write(data)
            for i, data in enumerate(blobs)
        }
        result = await executor.execute(
            "print(len(open('f0.bin','rb').read()), len(open('f1.bin','rb').read()))",
            files=files,
            executor_id="undeclared",
        )
        assert result.exit_code == 0, result.stderr
        assert result.stdout.split() == [str(len(b)) for b in blobs]
        assert result.phases["upload_bytes"] == float(sum(map(len, blobs)))
        assert result.phases["upload_copied_bytes"] == 0.0
        sandbox = executor._sessions["undeclared"].sandbox
        assert sandbox.meta["transfer"].host(sandbox.url).copies is False
    finally:
        await executor.close()


async def _case_target_confined(tmp_path):
    """A target outside the workspace, or through a symlink user code
    planted, is answered as the PUT answers it, and nothing is written."""
    storage = tmp_path / "storage"
    proc, client, ws = server_beside(tmp_path, storage)
    try:
        object_id = put_object(storage, b"stay inside")
        outside = tmp_path / "outside"
        outside.mkdir()
        (ws / "link").symlink_to(outside)
        (ws / "file-link").symlink_to(outside / "victim")
        for rel in ("../escape.bin", "link/inside.bin", "file-link", ""):
            put = client.put("/workspace/" + rel, content=b"stay inside")
            resp = copy(client, rel, object_id)
            assert resp.status_code == put.status_code, (rel, resp.status_code, put.status_code)
            assert resp.status_code in (400, 403), rel
        assert list(outside.iterdir()) == [] and not (tmp_path / "escape.bin").exists()
        # Only the workspace is copied into.
        assert client.post("/copy-from-storage/runtime-packages/x", headers={"X-Storage-Object": object_id}).status_code == 404
    finally:
        client.close()
        proc.kill()
        proc.wait()


async def _case_disk_quota(tmp_path):
    """413 before any byte is written, and a typed disk_quota from Execute."""
    storage = tmp_path / "storage"
    proc, client, ws = server_beside(tmp_path, storage, APP_LIMIT_DISK_BYTES=str(2 * MB))
    try:
        small = put_object(storage, b"z" * 1024)
        big = put_object(storage, b"z" * (4 * MB))
        assert copy(client, "small.bin", small).status_code == 200
        over = copy(client, "big.bin", big)
        assert over.status_code == 413 and over.json()["violation"] == "disk_quota"
        assert (ws / "big.bin").stat().st_size == 0
        # The refusal consumed no quota, and a rewrite counts only its new bytes.
        half = put_object(storage, b"a" * (MB + MB // 2))
        other = put_object(storage, b"b" * (MB + MB // 2))
        assert copy(client, "data.bin", half).status_code == 200
        assert copy(client, "data.bin", other).status_code == 200
        assert (ws / "data.bin").read_bytes() == b"b" * (MB + MB // 2)
    finally:
        client.close()
        proc.kill()
        proc.wait()
    executor = local_stack(tmp_path, sandbox_limit_caps={"disk_bytes": MB})
    try:
        object_id = await executor.storage.write(b"q" * (2 * MB))
        with pytest.raises(LimitExceededError) as refused:
            await executor.execute("print('never runs')", files={"/workspace/q.bin": object_id})
        assert refused.value.kind == "disk_quota"
    finally:
        await executor.close()


async def _case_conditional_skip(tmp_path):
    """The manifest holds the path under that sha and the file is untouched:
    304, no write. Touched since: copied again."""
    storage = tmp_path / "storage"
    proc, client, ws = server_beside(tmp_path, storage)
    try:
        object_id = put_object(storage, b"already there")
        first = copy(client, "c.bin", object_id)
        assert first.status_code == 200
        assert first.json() == {"path": "/workspace/c.bin", "size": 13, "sha256": object_id}
        before = (ws / "c.bin").stat()
        again = copy(client, "c.bin", object_id)
        assert again.status_code == 304 and again.content == b""
        after = (ws / "c.bin").stat()
        assert (after.st_mtime_ns, after.st_ino) == (before.st_mtime_ns, before.st_ino)
        # A PUT of the same bytes is skipped on the copy's entry too.
        assert client.put("/workspace/c.bin", content=b"already there", headers={"If-None-Match": object_id}).status_code == 304
        (ws / "c.bin").write_bytes(b"user wrote this")
        assert copy(client, "c.bin", object_id).status_code == 200
        assert (ws / "c.bin").read_bytes() == b"already there"
    finally:
        client.close()
        proc.kill()
        proc.wait()


async def _case_no_storage_directory(tmp_path):
    """A server spawned beside no storage directory, or in the legacy wire
    mode that stands in for an old binary, has no such route: 404."""
    proc, client, ws = server_beside(tmp_path, None)
    try:
        assert copy(client, "x.bin", sha(b"anything")).status_code == 404
        assert list(ws.iterdir()) == []
    finally:
        client.close()
        proc.kill()
        proc.wait()
    legacy = tmp_path / "legacy"
    legacy.mkdir()
    storage = tmp_path / "storage"
    proc, client, ws = server_beside(legacy, storage, APP_WORKSPACE_MANIFEST="0")
    try:
        assert copy(client, "x.bin", put_object(storage, b"anything")).status_code == 404
        assert list(ws.iterdir()) == []
    finally:
        client.close()
        proc.kill()
        proc.wait()


async def _case_other_filesystem(tmp_path):
    """Storage on another filesystem than the workspace: the plain read /
    write loop gives the same bytes."""
    shm = Path("/dev/shm")
    if not shm.is_dir() or not os.access(shm, os.W_OK) or shm.stat().st_dev == tmp_path.stat().st_dev:
        pytest.skip("no second filesystem to keep the objects on")
    import tempfile

    with tempfile.TemporaryDirectory(dir=shm) as far:
        storage = Path(far) / "storage"
        proc, client, ws = server_beside(tmp_path, storage)
        try:
            data = os.urandom(5 * MB + 3)
            resp = copy(client, "far.bin", put_object(storage, data))
            assert resp.status_code == 200 and resp.json()["size"] == len(data)
            assert (ws / "far.bin").read_bytes() == data
            assert copy(client, "empty.bin", put_object(storage, b"")).status_code == 200
            assert (ws / "empty.bin").read_bytes() == b""
        finally:
            client.close()
            proc.kill()
            proc.wait()


class RecordingHost(FakeSandboxHost):
    """The in-memory host, keeping every request it was sent."""

    def __init__(self):
        super().__init__()
        self.requests: list[tuple[str, str, dict, bytes]] = []

    async def handler(self, request: httpx.Request) -> httpx.Response:
        body = await request.aread()
        self.requests.append((request.method, request.url.path, dict(request.headers), body))
        return await super().handler(request)


async def _case_backend_declares_nothing(tmp_path):
    """A sandbox whose backend says nothing about storage is sent what the
    control plane has always sent: one PUT a file, the bytes as its body,
    If-None-Match its sha; no request to the copy route."""
    host = RecordingHost()
    config = Config(file_storage_path=str(tmp_path / "storage"), executor_pod_queue_target_length=1)
    executor = CodeExecutor(TransferBackend(host), Storage(config.file_storage_path), config)
    try:
        data = b"streamed as ever " * 100_000  # over one 1 MiB chunk
        object_id = await executor.storage.write(data)
        result = await executor.execute("pass", files={"/workspace/in/put.bin": object_id})
        assert result.phases["upload_bytes"] == float(len(data))
        assert result.phases["upload_copied_bytes"] == 0.0
        uploads = [r for r in host.requests if r[0] in ("PUT", "POST") and r[1] != "/execute" and r[1] != "/reset"]
        [(method, path, headers, body)] = uploads
        assert (method, path, body) == ("PUT", "/workspace/in/put.bin", data)
        for of_the_library in ("user-agent", "accept-encoding"):
            headers.pop(of_the_library)
        assert headers == {
            "host": "fake",
            "accept": "*/*",
            "connection": "keep-alive",
            "if-none-match": object_id,
            "transfer-encoding": "chunked",
        }
        await settle(executor)
    finally:
        await executor.close()


CASES = {
    "bad_object_id": _case_bad_object_id,
    "no_such_object": _case_no_such_object,
    "target_confined": _case_target_confined,
    "disk_quota": _case_disk_quota,
    "conditional_skip": _case_conditional_skip,
    "no_storage_directory": _case_no_storage_directory,
    "other_filesystem": _case_other_filesystem,
    "backend_declares_nothing": _case_backend_declares_nothing,
}


@pytest.mark.parametrize("case", list(CASES))
async def test_the_copy_route_refuses_and_falls_back(case, tmp_path):
    await CASES[case](tmp_path)


def test_what_a_turn_copied_is_counted_in_what_it_uploaded():
    from bee_code_interpreter_fs_tpu.services.transfer import HostManifest, SandboxTransfer, TransferStats

    stats = TransferStats(upload_bytes=10, upload_files=2, upload_copied_bytes=7, upload_copied_files=1)
    assert stats.as_phases()["upload_bytes"] == 10.0
    assert stats.as_phases()["upload_copied_bytes"] == 7.0
    assert HostManifest().copies is False
    assert SandboxTransfer(shares_storage=True).host("http://h").copies is True
    assert SandboxTransfer().host("http://h").copies is False
