"""End-to-end workspace-sync tests through the real local backend + C++
executor: delta uploads across session turns, hash-negotiated downloads,
and the old-binary fallback (the same binary in APP_WORKSPACE_MANIFEST=0
legacy mode) passing the execute/session flows with full transfers.
"""

# Optional-dep guard: a missing dependency must degrade this module to a
# SKIP at collection, not an ERROR that interrupts the whole run.
import pytest

pytest.importorskip("httpx", reason="optional e2e dependency not installed")

import asyncio

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.local import LocalSandboxBackend
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.storage import Storage


def _make_stack(tmp_path):
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        local_sandbox_root=str(tmp_path / "sandboxes"),
        executor_pod_queue_target_length=1,
        jax_compilation_cache_dir="",
        default_execution_timeout=30.0,
    )
    backend = LocalSandboxBackend(config, warm_import_jax=False)
    return CodeExecutor(backend, Storage(config.file_storage_path), config)


@pytest.fixture
async def stack(tmp_path):
    executor = _make_stack(tmp_path)
    yield executor
    await executor.close()


@pytest.fixture
async def legacy_stack(tmp_path, monkeypatch):
    """The same stack against a sandbox server in legacy wire mode — a
    stand-in for an old executor binary without manifest endpoints."""
    monkeypatch.setenv("APP_WORKSPACE_MANIFEST", "0")
    executor = _make_stack(tmp_path)
    yield executor
    await executor.close()


@pytest.mark.parametrize(
    "num_files, file_bytes", [(1, 4096), (4, 8192), (16, 65536)]
)
async def test_session_unchanged_files_move_no_bytes(
    stack, num_files, file_bytes
):
    executor = stack
    total = num_files * file_bytes
    # Distinct content per file: the skip must come from the manifest
    # protocol, not from accidentally identical payloads.
    files = {
        f"/workspace/input-{i:03d}.bin": await executor.storage.write(
            bytes([i]) * file_bytes
        )
        for i in range(num_files)
    }
    source = (
        "import glob\n"
        "print(sum(len(open(p,'rb').read()) for p in glob.glob('input-*.bin')))"
    )

    async def turn(turn_files):
        result = await executor.execute(
            source, files=turn_files, executor_id="xfer-sess"
        )
        assert result.exit_code == 0, result.stderr
        assert result.stdout.strip() == str(total)
        return result.phases

    # Cold turn: everything moved, nothing skipped.
    cold = await turn(files)
    assert cold["upload_bytes"] == float(total)
    assert cold["upload_skipped_bytes"] == 0.0
    # Unchanged turn: the manifest delta moved nothing.
    unchanged = await turn(files)
    assert unchanged["upload_bytes"] == 0.0
    assert unchanged["upload_skipped_bytes"] == float(total)
    # One file changed: that file moves, the rest are skipped.
    changed = dict(files)
    changed["/workspace/input-000.bin"] = await executor.storage.write(
        b"\xff" * file_bytes
    )
    one_changed = await turn(changed)
    assert one_changed["upload_bytes"] == float(file_bytes)
    assert one_changed["upload_skipped_bytes"] == float(total - file_bytes)


async def test_download_negotiated_away_for_known_content(stack):
    executor = stack
    payload = b"round-trip me"
    object_id = await executor.storage.write(payload)
    result = await executor.execute(
        "open('copy.bin','wb').write(open('orig.bin','rb').read())",
        files={"/workspace/orig.bin": object_id},
        executor_id="xfer-dl",
    )
    assert result.exit_code == 0, result.stderr
    # The new file's bytes equal the input already in content-addressed
    # storage: the sha matched and no bytes came back over the wire.
    assert result.files["/workspace/copy.bin"] == object_id
    assert result.phases["download_bytes"] == 0.0
    assert result.phases["download_skipped_bytes"] == float(len(payload))


async def test_novel_output_still_downloads(stack):
    executor = stack
    result = await executor.execute(
        "open('novel.txt','w').write('fresh output')", executor_id="xfer-novel"
    )
    assert result.exit_code == 0, result.stderr
    object_id = result.files["/workspace/novel.txt"]
    assert await executor.storage.read(object_id) == b"fresh output"
    assert result.phases["download_bytes"] == float(len(b"fresh output"))
    assert result.phases["download_skipped_bytes"] == 0.0


async def test_transfer_metrics_move_on_skip(stack):
    executor = stack
    object_id = await executor.storage.write(b"metrics payload")
    files = {"/workspace/m.bin": object_id}
    await executor.execute("pass", files=files, executor_id="xfer-metrics")
    await executor.execute("pass", files=files, executor_id="xfer-metrics")
    rendered = executor.metrics.registry.render()
    assert (
        'code_interpreter_transfer_skipped_bytes_total{direction="upload"} 15'
        in rendered
    )


# ------------------------------------------------------------ legacy binary


async def test_legacy_binary_execute_and_session_roundtrip(legacy_stack):
    """The full execute/session flow against a manifest-less executor: the
    control plane detects the legacy host from its first response and runs
    the classic full-transfer path — correct results, zero skips."""
    executor = legacy_stack
    payload = b"legacy payload"
    object_id = await executor.storage.write(payload)
    files = {"/workspace/in.txt": object_id}

    first = await executor.execute(
        "open('out.txt','w').write(open('in.txt').read().upper())",
        files=files,
        executor_id="legacy-sess",
    )
    assert first.exit_code == 0, first.stderr
    out_id = first.files["/workspace/out.txt"]
    assert await executor.storage.read(out_id) == b"LEGACY PAYLOAD"

    second = await executor.execute(
        "print(open('in.txt').read())", files=files, executor_id="legacy-sess"
    )
    assert second.exit_code == 0, second.stderr
    assert second.stdout.strip() == "legacy payload"
    assert second.session_seq == 2
    # Fallback = full transfers: nothing is ever skipped.
    assert first.phases["upload_skipped_bytes"] == 0.0
    assert second.phases["upload_skipped_bytes"] == 0.0
    assert first.phases["download_skipped_bytes"] == 0.0


async def test_legacy_binary_stateless_roundtrip(legacy_stack):
    executor = legacy_stack
    result = await executor.execute("open('made.txt','w').write('plain')")
    assert result.exit_code == 0, result.stderr
    object_id = result.files["/workspace/made.txt"]
    assert await executor.storage.read(object_id) == b"plain"
    assert result.phases["download_skipped_bytes"] == 0.0
