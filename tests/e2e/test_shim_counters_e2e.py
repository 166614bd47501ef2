"""The numpy shim's counters end to end (ISSUE 31): a `/v1/execute` of array
code through the HTTP API, the real C++ executor and a warm runner that has
the shim installed comes back with every `shim_*` key (`SHIM_PHASES`) in
`Result.phases`; the next turn, on the sandbox that `/reset` put back, reads
0 where the shim did nothing; and no histogram observes any of them. A
sandbox without the shim (the no-JAX plumbing mode) stamps none. Nothing
here times anything."""

import numpy as np
import pytest

pytest.importorskip("httpx", reason="optional e2e dependency not installed")
pytest.importorskip("aiohttp", reason="optional e2e dependency not installed")

from aiohttp.test_utils import TestClient, TestServer

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.local import LocalSandboxBackend
from bee_code_interpreter_fs_tpu.services.code_executor import (
    LATENCY_PHASES,
    SHIM_PHASES,
    CodeExecutor,
)
from bee_code_interpreter_fs_tpu.services.custom_tool_executor import CustomToolExecutor
from bee_code_interpreter_fs_tpu.services.http_server import create_http_app
from bee_code_interpreter_fs_tpu.services.storage import Storage

from test_stage_spans_e2e import turnover_traces

# over the shim's shipped dispatch threshold (2**17 elements)
ARRAY_TURN = (
    "import numpy as np\n"
    "a = np.fromfunction(lambda i, j: i * (j + 2) / 512, (512, 512), dtype=np.float32)\n"
    "a[1:-1, 1:-1] = 0.5 * (a[:-2, 1:-1] + a[2:, 1:-1])\n"
    "print(type(a).__name__, float(a.sum(axis=1).sum()))\n"
)


async def make_client(tmp_path, warm_import_jax: bool):
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        local_sandbox_root=str(tmp_path / "sandboxes"),
        executor_pod_queue_target_length=1,
        jax_compilation_cache_dir="",
        default_execution_timeout=60.0,
    )
    backend = LocalSandboxBackend(config, warm_import_jax=warm_import_jax)
    storage = Storage(config.file_storage_path)
    executor = CodeExecutor(backend, storage, config)
    app = create_http_app(executor, CustomToolExecutor(executor), storage)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client, executor


async def test_shim_counters_of_a_served_array_turn_and_of_the_next(tmp_path):
    client, executor = await make_client(tmp_path, warm_import_jax=True)
    try:
        bodies = []
        for source in (ARRAY_TURN, "print(6 * 7)"):
            resp = await client.post("/v1/execute", json={"source_code": source})
            assert resp.status == 200
            bodies.append(await resp.json())
            await turnover_traces(executor, len(bodies))
        array, plain = bodies
        assert array["exit_code"] == 0 and array["stdout"].startswith("TpuArray "), array["stderr"]
        assert array["warm"] and plain["warm"]
        for body in bodies:
            for key in SHIM_PHASES.values():
                assert isinstance(body["phases"][key], float) and body["phases"][key] >= 0.0, key
        phases = array["phases"]
        # creation, the update and the sum: one program, nothing from the host
        assert phases["shim_programs"] == 1.0 and phases["shim_flushes"] == 0.0
        assert phases["shim_nodes"] >= 8 and phases["shim_h2d_bytes"] == 0.0
        assert phases["shim_exec_cache_misses"] == 1.0 and phases["shim_host"] > 0.0
        # the next turn ran on the sandbox /reset put back, in the same warm runner
        assert plain["phases"]["turnover_before"] > 0.0
        assert all(plain["phases"][key] == 0.0 for key in SHIM_PHASES.values())
        # no histogram saw a count, a byte or the shim's seconds
        observed = {labels["phase"] for labels, *_ in executor.metrics.phase_seconds.samples()}
        assert observed <= set(LATENCY_PHASES) and not observed & set(SHIM_PHASES.values())
    finally:
        await client.close()
        await executor.close()


# a file of 2**18 float32, read and used three times: it crosses once (ISSUE 35);
# its histogram is one program of the shim's own (ISSUE 36)
FILE_TURN = (
    "import numpy as np\n"
    "x = np.fromfile('x.bin', dtype=np.float32)\n"
    "y = x * 2.0 + 1.0\n"
    "print(type(x).__name__, type(y).__name__, float(np.max(x)), float(y.sum()))\n"
    "counts, edges = np.histogram(x, 7)\n"
    "print(type(counts).__name__, np.asarray(counts).tolist(), type(edges).__name__, edges.tolist())\n"
)


async def test_a_turn_over_an_uploaded_file_ships_it_once(tmp_path):
    client, executor = await make_client(tmp_path, warm_import_jax=True)
    try:
        data = (np.arange(2**18) % 7).astype(np.float32)
        resp = await client.put("/v1/files", data=data.tobytes())
        object_id = (await resp.json())["hash"]
        resp = await client.post("/v1/execute", json={"source_code": FILE_TURN, "files": {"/workspace/x.bin": object_id}})
        body = await resp.json()
        assert body["exit_code"] == 0, body["stderr"]
        counts, edges = np.histogram(data, 7)
        assert body["stdout"] == (f"TpuArray TpuArray 6.0 {float((data * 2.0 + 1.0).sum())}\n"
                                  f"TpuArray {counts.tolist()} ndarray {edges.tolist()}\n")
        phases = body["phases"]
        assert phases["upload_bytes"] == phases["shim_h2d_bytes"] == float(data.nbytes)
        assert phases["shim_h2d_arrays"] == 1.0 and phases["shim_h2d"] > 0.0 and phases["shim_fallbacks"] == 0.0
        assert phases["shim_histograms"] == 1.0
        # ISSUE 39: what the turn's user code did with its seconds. numpy read the
        # file once, every byte of it; the values it printed came back through
        # `fetch` (two floats, the bins); the six stages lie inside the runner's
        # `user_code` stage, none counted twice; the turn's host CPU beside them.
        assert phases["shim_load_files"] == 1.0 and phases["shim_load_bytes"] == float(data.nbytes)
        assert phases["shim_d2h_arrays"] >= 3.0 and phases["shim_d2h_bytes"] >= 4 + 4 + 7 * 4
        stages = [phases[key] for key in ("shim_load", "shim_h2d", "shim_host", "shim_dispatch", "shim_wait", "shim_d2h")]
        assert all(seconds > 0.0 for seconds in stages), stages
        assert sum(stages) <= phases["runner_user_code"]
        assert 0.0 < phases["runner_user_cpu"] < 60.0
        assert phases["auto_profiled"] == 0.0
        # ... and where the sandbox's attach went, beside `attach_seconds`
        import httpx

        (_lane, sandbox), = executor.live_hosts()
        async with httpx.AsyncClient() as probe:
            stats = (await probe.get(f"{sandbox.url}/device-stats")).json()
        assert list(stats["attach_stages"]) == ["devices", "distributed_init", "first_compile", "import_jax", "interpreter_start"]
        assert all(isinstance(v, float) and v >= 0.0 for v in stats["attach_stages"].values())
        assert sum(stats["attach_stages"].values()) <= stats["attach_seconds"] + 0.01
    finally:
        await client.close()
        await executor.close()


# a product and an all-pairs step over the shipped threshold (ISSUE 37): one
# contraction of 2 * 384 * 512 * 448 operations, three calls of a ufunc's method
LINALG_TURN = (
    "import numpy as np\n"
    "A = np.fromfunction(lambda i, j: (i * j + 1) % 7 / 7, (384, 512), dtype=np.float32)\n"
    "B = np.fromfunction(lambda i, j: (i * j + 2) % 5 / 5, (512, 448), dtype=np.float32)\n"
    "C = np.fromfunction(lambda i, j: (i + j) % 3 / 3, (384, 448), dtype=np.float32)\n"
    "C[:] = 1.5 * A @ B + 1.2 * C\n"
    "path = np.fromfunction(lambda i, j: i * j % 7 + 1, (400, 400), dtype=np.int32)\n"
    "for k in range(3):\n"
    "    path[:] = np.minimum(path[:], np.add.outer(path[:, k], path[k, :]))\n"
    "print(type(C).__name__, float(C[5, 7]), int(path.sum(axis=1, dtype=np.int32)[9]), np.add.nin)\n"
)


async def test_a_turn_of_products_and_ufunc_methods_is_counted(tmp_path):
    client, executor = await make_client(tmp_path, warm_import_jax=True)
    try:
        resp = await client.post("/v1/execute", json={"source_code": LINALG_TURN})
        body = await resp.json()
        assert body["exit_code"] == 0, body["stderr"]
        i, j = np.indices((384, 512)), np.indices((512, 448))
        A, B = ((i[0] * i[1] + 1) % 7 / 7).astype(np.float32), ((j[0] * j[1] + 2) % 5 / 5).astype(np.float32)
        want = 1.5 * float(A[5] @ B[:, 7]) + 1.2 * (12 % 3 / 3)
        path = np.fromfunction(lambda i, j: i * j % 7 + 1, (400, 400), dtype=np.int32)
        for k in range(3):
            path[:] = np.minimum(path[:], np.add.outer(path[:, k], path[k, :]))
        kind, value, row, nin = body["stdout"].split()
        assert (kind, int(row), nin) == ("TpuArray", int(path[9].sum()), "2")
        assert float(value) == pytest.approx(want, rel=1e-5)
        phases = body["phases"]
        assert phases["shim_dots"] == 1.0 and phases["shim_dot_flops"] == 2.0 * 384 * 512 * 448
        assert phases["shim_ufunc_methods"] == 3.0 and phases["shim_fallbacks"] == 0.0
    finally:
        await client.close()
        await executor.close()


async def test_a_sandbox_without_the_shim_stamps_no_shim_phase(tmp_path):
    client, executor = await make_client(tmp_path, warm_import_jax=False)
    try:
        resp = await client.post("/v1/execute", json={"source_code": "print(6 * 7)"})
        body = await resp.json()
        assert body["stdout"] == "42\n"
        assert not set(SHIM_PHASES.values()) & set(body["phases"])
        # the turn's host CPU is the runner's, shim or none; no jax, so no attach to stage
        assert body["phases"]["runner_user_cpu"] >= 0.0 and body["phases"]["auto_profiled"] == 0.0
        import httpx

        (_lane, sandbox), = executor.live_hosts()
        async with httpx.AsyncClient() as probe:
            assert "attach_stages" not in (await probe.get(f"{sandbox.url}/device-stats")).json()
    finally:
        await client.close()
        await executor.close()
