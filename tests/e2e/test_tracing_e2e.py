"""End-to-end tracing (ISSUE 4 acceptance): one `/v1/execute` through the
HTTP API → scheduler → transfer → real C++ executor yields ONE connected
trace spanning both processes — API entry, scheduler wait, transfer upload,
executor call, the sandbox's install/exec/collect (grafted from its trace
block), and transfer download — retrievable via `GET /traces/{trace_id}`
and exported as JSONL.
"""

import pytest

pytest.importorskip("httpx", reason="optional e2e dependency not installed")
pytest.importorskip("aiohttp", reason="optional e2e dependency not installed")

import json

from aiohttp.test_utils import TestClient, TestServer

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.local import LocalSandboxBackend
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.custom_tool_executor import CustomToolExecutor
from bee_code_interpreter_fs_tpu.services.http_server import create_http_app
from bee_code_interpreter_fs_tpu.services.storage import Storage

TRACE_ID = "f" * 32
UPSTREAM_SPAN = "1" * 16
TRACEPARENT = f"00-{TRACE_ID}-{UPSTREAM_SPAN}-01"


async def make_client(tmp_path, **config_overrides):
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        local_sandbox_root=str(tmp_path / "sandboxes"),
        executor_pod_queue_target_length=1,
        jax_compilation_cache_dir="",
        default_execution_timeout=30.0,
        **config_overrides,
    )
    backend = LocalSandboxBackend(config, warm_import_jax=False)
    storage = Storage(config.file_storage_path)
    executor = CodeExecutor(backend, storage, config)
    tools = CustomToolExecutor(executor)
    app = create_http_app(executor, tools, storage)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client, executor


async def test_single_execute_yields_connected_cross_process_trace(tmp_path):
    jsonl_path = tmp_path / "spans.jsonl"
    client, executor = await make_client(
        tmp_path, tracing_jsonl_path=str(jsonl_path)
    )
    try:
        resp = await client.post(
            "/v1/execute",
            json={"source_code": "print(6 * 7)"},
            headers={"traceparent": TRACEPARENT},
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["stdout"] == "42\n"
        # The response correlates to its trace three ways: phases,
        # X-Trace-Id, and the echoed X-Request-Id.
        assert body["phases"]["trace_id"] == TRACE_ID
        assert resp.headers["X-Trace-Id"] == TRACE_ID
        assert resp.headers["X-Request-Id"]

        resp = await client.get(f"/traces/{TRACE_ID}")
        assert resp.status == 200
        spans = (await resp.json())["spans"]
        names = [s["name"] for s in spans]
        # ≥ 8 spans across BOTH processes (the sandbox.* three are measured
        # inside the C++ executor and grafted back).
        assert len(spans) >= 8
        assert set(names) >= {
            "http POST /v1/execute",
            "scheduler.queue_wait",
            "transfer.upload",
            "executor.execute",
            "sandbox.install",
            "sandbox.exec",
            "sandbox.collect",
            "transfer.download",
        }
        # One CONNECTED trace: a single root (parented to the upstream
        # context we sent), every other span reachable from it.
        by_id = {s["span_id"]: s for s in spans}
        roots = [s for s in spans if s["parent_id"] == UPSTREAM_SPAN]
        assert [s["name"] for s in roots] == ["http POST /v1/execute"]
        for span in spans:
            hops = 0
            node = span
            while node["parent_id"] != UPSTREAM_SPAN:
                node = by_id[node["parent_id"]]  # KeyError = orphan
                hops += 1
                assert hops < 10
        # Grafted sandbox spans nest inside their executor.execute parent:
        # the three phases directly, their stages under the phase each
        # names as its parent (tests/e2e/test_stage_spans_e2e.py).
        [host_span] = [s for s in spans if s["name"] == "executor.execute"]
        phase_ids = set()
        for span in spans:
            if span["name"] in ("sandbox.install", "sandbox.exec", "sandbox.collect"):
                assert span["parent_id"] == host_span["span_id"]
                phase_ids.add(span["span_id"])
        for span in spans:
            if span["name"].startswith("sandbox."):
                assert span["parent_id"] in phase_ids | {host_span["span_id"]}

        # Recent-traces debug surface lists it.
        resp = await client.get("/traces")
        assert resp.status == 200
        listing = await resp.json()
        assert listing["enabled"] is True
        assert any(t["trace_id"] == TRACE_ID for t in listing["traces"])

        # JSONL: both the file exporter and the on-demand endpoint.
        exported = [
            json.loads(line)
            for line in jsonl_path.read_text().splitlines()
        ]
        assert {s["trace_id"] for s in exported} == {TRACE_ID}
        assert len(exported) == len(spans)
        resp = await client.get(f"/traces/{TRACE_ID}?format=jsonl")
        assert resp.status == 200
        lines = (await resp.text()).splitlines()
        assert len(lines) == len(spans)

        # Per-stage histograms moved for every span name.
        rendered = executor.metrics.registry.render()
        for stage in ("scheduler.queue_wait", "sandbox.exec"):
            assert f'code_interpreter_span_seconds_count{{span="{stage}"}} 1' in rendered
    finally:
        await client.close()
        await executor.close()


async def test_tracing_disabled_kills_the_subsystem(tmp_path):
    """APP_TRACING_ENABLED=0: no spans, no trace ids anywhere — but request
    ids still correlate responses to logs."""
    client, executor = await make_client(tmp_path, tracing_enabled=False)
    try:
        resp = await client.post(
            "/v1/execute",
            json={"source_code": "print('ok')"},
            headers={"traceparent": TRACEPARENT},
        )
        assert resp.status == 200
        body = await resp.json()
        assert "trace_id" not in body["phases"]
        assert "X-Trace-Id" not in resp.headers
        assert resp.headers["X-Request-Id"]
        assert len(executor.tracer.ring) == 0
        resp = await client.get(f"/traces/{TRACE_ID}")
        assert resp.status == 404
    finally:
        await client.close()
        await executor.close()


async def test_unsampled_trace_propagates_but_records_nothing(tmp_path):
    client, executor = await make_client(tmp_path, tracing_sample_ratio=0.0)
    try:
        resp = await client.post(
            "/v1/execute", json={"source_code": "print('ok')"}
        )
        assert resp.status == 200
        body = await resp.json()
        # Ids exist (downstream propagation) but nothing was recorded.
        trace_id = resp.headers.get("X-Trace-Id")
        assert trace_id
        assert body["phases"]["trace_id"] == trace_id
        assert len(executor.tracer.ring) == 0
        assert (await client.get(f"/traces/{trace_id}")).status == 404
    finally:
        await client.close()
        await executor.close()


async def test_bad_trace_id_rejected(tmp_path):
    client, executor = await make_client(tmp_path)
    try:
        resp = await client.get("/traces/not-hex")
        assert resp.status == 400
    finally:
        await client.close()
        await executor.close()
