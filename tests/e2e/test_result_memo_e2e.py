"""End-to-end result-memo tests through the real local backend + C++
executor: the acceptance criterion verbatim — a repeated pure run serves
from the memo with ZERO sandbox HTTP and zero chip-seconds on the usage
ledger, byte-identical to its live execution (stdout, stderr, exit code,
output files) — plus the real executor's purity echo (the C++
`result_sha256` block verifying against the control plane's own
derivation), tenant isolation, kill-switch parity, and the X-Memo /
`pure` wire surface over the aiohttp server.
"""

# Optional-dep guard: a missing dependency must degrade this module to a
# SKIP at collection, not an ERROR that interrupts the whole run.
import pytest

pytest.importorskip("httpx", reason="optional e2e dependency not installed")
aiohttp = pytest.importorskip(
    "aiohttp", reason="optional e2e dependency not installed"
)

from aiohttp.test_utils import TestClient, TestServer

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.local import (
    LocalSandboxBackend,
)
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.custom_tool_executor import (
    CustomToolExecutor,
)
from bee_code_interpreter_fs_tpu.services.http_server import create_http_app
from bee_code_interpreter_fs_tpu.services.storage import Storage


def _make_stack(tmp_path, **config_kwargs):
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        local_sandbox_root=str(tmp_path / "sandboxes"),
        executor_pod_queue_target_length=1,
        jax_compilation_cache_dir="",
        default_execution_timeout=30.0,
        **config_kwargs,
    )
    backend = LocalSandboxBackend(config, warm_import_jax=False)
    return CodeExecutor(backend, Storage(config.file_storage_path), config)


@pytest.fixture
async def stack(tmp_path):
    executor = _make_stack(tmp_path)
    yield executor
    await executor.close()


def _count_sandbox_http(executor):
    """Arm a request counter on the executor's live sandbox HTTP client —
    every wire round-trip to any sandbox host from now on increments it."""
    count = {"n": 0}

    async def tick(request):
        count["n"] += 1

    executor._http_client().event_hooks["request"].append(tick)
    return count


def _chip_seconds(executor, tenant="shared"):
    row = executor.usage.snapshot()["tenants"].get(tenant)
    return row["chip_seconds"] if row else 0.0


def _requests_billed(executor, tenant="shared"):
    row = executor.usage.snapshot()["tenants"].get(tenant)
    return row["requests"] if row else 0


@pytest.mark.parametrize("repeats", [1, 3, 7])
async def test_repeat_pure_run_zero_sandbox_http_zero_chip_seconds(
    stack, repeats
):
    """The memo's acceptance criterion: a hit costs no sandbox HTTP and no
    chip-seconds and is byte for byte the live run, however often it is
    repeated."""
    executor = stack
    source = "print(sum(range(100)))\nopen('out.txt','w').write('made')"

    live = await executor.execute(source, pure=True)
    assert live.exit_code == 0, live.stderr
    assert live.stdout.strip() == "4950"
    assert live.phases["memo"] == {"state": "miss", "recorded": "admitted"}
    # The real C++ executor echoed the purity block and its hash verified
    # (a record only admits through _verified_pure_echo).
    assert executor.result_memo.entry_count() == 1

    chip_before = _chip_seconds(executor)
    requests_before = _requests_billed(executor)
    wire = _count_sandbox_http(executor)

    for n in range(1, repeats + 1):
        cached = await executor.execute(source, pure=True)
        # Zero sandbox HTTP...
        assert wire["n"] == 0
        # ...zero chip-seconds on the ledger (but the request IS counted)...
        assert _chip_seconds(executor) == chip_before
        assert _requests_billed(executor) == requests_before + n
        assert cached.phases["chip_seconds"] == 0.0
        assert cached.phases["device_op_seconds"] == 0.0
        # ...and byte-identical output, files included.
        assert cached.phases["memo"]["state"] == "hit"
        assert cached.stdout == live.stdout
        assert cached.stderr == live.stderr
        assert cached.exit_code == live.exit_code
        assert cached.files == live.files
        assert (
            await executor.storage.read(cached.files["/workspace/out.txt"])
            == b"made"
        )
    assert executor.result_memo.entry_count() == 1


async def test_stderr_and_nonzero_exit_memoize_too(stack):
    """A deterministic user error is as pure as a success: the memo serves
    the same failure without burning a sandbox on it again."""
    executor = stack
    source = "import sys\nsys.stderr.write('deterministic boom\\n')\nsys.exit(3)"
    live = await executor.execute(source, pure=True)
    assert live.exit_code == 3
    assert "deterministic boom" in live.stderr
    wire = _count_sandbox_http(executor)
    cached = await executor.execute(source, pure=True)
    assert wire["n"] == 0
    assert cached.exit_code == 3
    assert cached.stderr == live.stderr
    assert cached.phases["memo"]["state"] == "hit"


async def test_tenants_never_share_records_e2e(stack):
    executor = stack
    source = "print('isolated')"
    first = await executor.execute(source, pure=True, tenant="tenant-a")
    assert first.phases["memo"]["state"] == "miss"
    other = await executor.execute(source, pure=True, tenant="tenant-b")
    # Identical inputs, different tenant: a real re-execution.
    assert other.phases["memo"]["state"] == "miss"
    same = await executor.execute(source, pure=True, tenant="tenant-a")
    assert same.phases["memo"]["state"] == "hit"


async def test_input_files_key_the_record(stack):
    executor = stack
    a = await executor.storage.write(b"alpha")
    b = await executor.storage.write(b"bravo")
    source = "print(open('in.txt').read())"
    first = await executor.execute(
        source, files={"/workspace/in.txt": a}, pure=True
    )
    assert first.stdout.strip() == "alpha"
    changed = await executor.execute(
        source, files={"/workspace/in.txt": b}, pure=True
    )
    # Different input bytes -> different key -> a live run, not the record.
    assert changed.phases["memo"]["state"] == "miss"
    assert changed.stdout.strip() == "bravo"
    repeat = await executor.execute(
        source, files={"/workspace/in.txt": a}, pure=True
    )
    assert repeat.phases["memo"]["state"] == "hit"
    assert repeat.stdout.strip() == "alpha"


async def test_kill_switch_parity_e2e(tmp_path):
    source = "print('off')\nopen('out.bin','wb').write(bytes(range(16)))"

    async def output_bytes(executor, result):
        return (
            result.stdout,
            result.stderr,
            result.exit_code,
            {
                path: await executor.storage.read(object_id)
                for path, object_id in sorted(result.files.items())
            },
        )

    executor = _make_stack(tmp_path / "disabled", result_memo_enabled=False)
    try:
        for _ in range(2):
            result = await executor.execute(source, pure=True)
            assert result.exit_code == 0, result.stderr
            assert "memo" not in result.phases
        disabled = await output_bytes(executor, result)
        assert executor.result_memo.entry_count() == 0
        assert not (
            tmp_path / "disabled" / "storage" / ".result-memo"
        ).exists()
    finally:
        await executor.close()
    # Byte for byte what the memo plane answers for the same request, live
    # and from the record.
    executor = _make_stack(tmp_path / "enabled")
    try:
        live = await executor.execute(source, pure=True)
        hit = await executor.execute(source, pure=True)
        assert hit.phases["memo"]["state"] == "hit"
        assert (
            disabled
            == await output_bytes(executor, live)
            == await output_bytes(executor, hit)
        )
    finally:
        await executor.close()


# ------------------------------------------------------------ HTTP surface


async def test_http_pure_field_and_x_memo_header(tmp_path):
    executor = _make_stack(tmp_path)
    app = create_http_app(
        executor, CustomToolExecutor(executor), executor.storage
    )
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        body = {"source_code": "print('over http')", "pure": True}
        first = await client.post("/v1/execute", json=body)
        assert first.status == 200
        assert first.headers.get("X-Memo") == "miss"
        first_body = await first.json()

        second = await client.post("/v1/execute", json=body)
        assert second.status == 200
        assert second.headers.get("X-Memo") == "hit"
        second_body = await second.json()
        assert second_body["stdout"] == first_body["stdout"]
        assert second_body["exit_code"] == first_body["exit_code"]

        # Undeclared requests carry no memo surface at all.
        plain = await client.post(
            "/v1/execute", json={"source_code": "print('plain')"}
        )
        assert plain.status == 200
        assert "X-Memo" not in plain.headers
    finally:
        await client.close()
        await executor.close()
