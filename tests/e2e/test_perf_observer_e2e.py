"""End-to-end performance-anomaly-plane acceptance (ISSUE 14): HTTP API →
orchestrator → real C++ executors (local backend) with a seeded
``slow_exec`` fault regressing ONE lane.

The acceptance criterion, verbatim: with a seeded slow_exec fault on one
lane, the drift detector flips that (lane, exec) series to ``regressed``
within one window while the healthy lane stays ``normal``;
``perf_regression_total`` fires and the ``perf.regression`` span is
retrievable via /traces at 0% head sampling; the next eligible request on
the flagged lane is auto-profiled, its artifact appears under
``GET /profiles`` cross-linked to its trace id, and the tenant's ledger
shows zero transfer bytes for the harvest; every request's Result.phases
carries ``peak_hbm_bytes``; the ``APP_PERF_OBSERVER_ENABLED=0`` run shows
zero perf surfaces and byte-identical serving behavior.
"""

import pytest

pytest.importorskip("httpx", reason="optional e2e dependency not installed")
pytest.importorskip("aiohttp", reason="optional e2e dependency not installed")

import httpx
from aiohttp.test_utils import TestClient, TestServer

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.faults import (
    FaultInjectingBackend,
    FaultSpec,
)
from bee_code_interpreter_fs_tpu.services.backends.local import (
    LocalSandboxBackend,
)
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.custom_tool_executor import (
    CustomToolExecutor,
)
from bee_code_interpreter_fs_tpu.services.http_server import create_http_app
from bee_code_interpreter_fs_tpu.services.storage import Storage

SLOW_LANE = 2
HEALTHY_LANE = 0
TENANT = "perf-acct"
# What the test injects, and the detector's absolute band as a function of
# it: a lane reads degraded only past baseline * factor + MIN_BAND_S, so the
# healthy lane holds `normal` unless the host's own noise adds half the
# injected delay to a print, and the slowed lane (every exec + SLOW_S) still
# clears baseline * 3 + MIN_BAND_S while its baseline is under SLOW_S / 4.
# (A band of 0.05 s was crossed by the healthy lane under six loaded workers.)
SLOW_S = 1.0
MIN_BAND_S = SLOW_S / 2
# The window must FIT a burst of sequential slow requests (five round trips
# of SLOW_S and a print's own time, which six loaded workers stretch to 0.3 s
# and more: at 6 s the burst spilled over, its samples scattered into
# sub-min_samples slivers the detector rightly ignores, or the regression was
# flagged and its capture spent inside the burst). Windows are stepped over,
# not slept out, so a long one costs the test nothing.
WINDOW_S = 20.0


def _config(tmp_path, **overrides) -> Config:
    defaults = dict(
        file_storage_path=str(tmp_path / "storage"),
        local_sandbox_root=str(tmp_path / "sandboxes"),
        executor_pod_queue_target_length=1,
        jax_compilation_cache_dir="",
        compile_cache_enabled=False,
        batching_enabled=False,
        default_execution_timeout=60.0,
        # 0% HEAD sampling: the perf.regression record_span must still be
        # retrievable (the device-health transition discipline).
        tracing_sample_ratio=0.0,
        tracing_tail_enabled=False,
        executor_fault_spec=(
            f"slow_exec:1.0,slow_exec_lane:{SLOW_LANE},"
            f"slow_exec_seconds:{SLOW_S},seed:7"
        ),
        perf_window_seconds=WINDOW_S,
        perf_min_window_samples=3,
        perf_min_band_seconds=MIN_BAND_S,
        perf_profile_min_interval_seconds=0.0,
    )
    defaults.update(overrides)
    return Config(**defaults)


class SkippingClock:
    """The executor's own clock plus what the test has skipped: a drift
    window is closed by stepping over it, not by sleeping it out."""

    def __init__(self, clock):
        self.clock = clock
        self.skipped = 0.0

    def __call__(self) -> float:
        return self.clock() + self.skipped

    def skip(self, seconds: float) -> None:
        self.skipped += seconds


async def _build_stack(config):
    backend = FaultInjectingBackend(
        LocalSandboxBackend(config, warm_import_jax=False),
        FaultSpec.parse(config.executor_fault_spec),
    )
    storage = Storage(config.file_storage_path)
    executor = CodeExecutor(backend, storage, config)
    # Hold the fault transport so the test can turn the regression ON at a
    # chosen moment (a fault active from the first request would BECOME
    # the baseline — the detector is right to call that normal).
    transport = backend.http_transport()
    transport.rate = 0.0
    executor._client = httpx.AsyncClient(transport=transport, timeout=90.0)
    executor.perf.clock = SkippingClock(executor.perf.clock)
    app = create_http_app(executor, CustomToolExecutor(executor), storage)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client, executor, transport


async def _execute(client, lane: int, tenant: str | None = None) -> dict:
    payload: dict = {"source_code": "print('tick')", "chip_count": lane}
    if tenant is not None:
        payload["tenant"] = tenant
    resp = await client.post("/v1/execute", json=payload)
    assert resp.status == 200, await resp.text()
    body = await resp.json()
    assert body["exit_code"] == 0, body
    return body


async def _window(client, executor, lane: int, tenant=None) -> None:
    for _ in range(5):
        await _execute(client, lane, tenant)
    executor.perf.clock.skip(WINDOW_S + 0.1)


def _perf_state(executor, lane: int) -> str:
    return executor.perf.lane_phase_states().get(f"{lane}/exec", "absent")


async def test_perf_anomaly_plane_end_to_end(tmp_path):
    config = _config(tmp_path)
    client, executor, transport = await _build_stack(config)
    try:
        # ---- baseline: both lanes healthy over two full windows.
        for _ in range(2):
            await _window(client, executor, HEALTHY_LANE)
            await _window(client, executor, SLOW_LANE, tenant=TENANT)
        body = await _execute(client, HEALTHY_LANE)
        # Every request's phases carries the device-memory attribution.
        assert "peak_hbm_bytes" in body["phases"], body["phases"]
        assert "live_buffer_bytes_delta" in body["phases"]
        await _execute(client, SLOW_LANE, tenant=TENANT)
        assert _perf_state(executor, HEALTHY_LANE) == "normal"
        assert _perf_state(executor, SLOW_LANE) == "normal"

        # ---- the regression: the seeded fault lands on the slow lane.
        transport.rate = 1.0
        await _window(client, executor, SLOW_LANE, tenant=TENANT)
        await _window(client, executor, HEALTHY_LANE)
        # The roll-triggering records: one per lane.
        await _execute(client, SLOW_LANE, tenant=TENANT)
        await _execute(client, HEALTHY_LANE)
        # Within ONE window the slowed lane flipped; the healthy one held.
        assert _perf_state(executor, SLOW_LANE) == "regressed"
        assert _perf_state(executor, HEALTHY_LANE) == "normal"
        # perf_regression_total{lane,phase} fired.
        samples = {
            (labels["lane"], labels["phase"]): value
            for labels, value in executor.metrics.perf_regressions.samples()
        }
        assert samples.get((str(SLOW_LANE), "exec"), 0) >= 1
        assert (str(HEALTHY_LANE), "exec") not in samples
        # The perf.regression span is retrievable via /traces at 0% head
        # sampling: find it in the ring, then fetch its trace over HTTP.
        spans = [
            s
            for s in list(executor.tracer.ring._spans)
            if s.get("name") == "perf.regression"
        ]
        assert spans, "perf.regression must bypass head sampling"
        resp = await client.get(f"/traces/{spans[-1]['trace_id']}")
        assert resp.status == 200
        trace_body = await resp.json()
        assert any(
            s["name"] == "perf.regression" for s in trace_body["spans"]
        )

        # ---- auto-profiling: the next eligible request on the flagged
        # lane runs with the JAX profiler armed and its artifact is
        # harvested (not returned to the tenant, not billed).
        ledger_before = executor.usage.tenant_snapshot(TENANT)
        profiled = await _execute(client, SLOW_LANE, tenant=TENANT)
        assert "/workspace/profile.zip" not in profiled["files"], (
            "the auto-captured artifact must be harvested, not returned"
        )
        resp = await client.get("/profiles")
        assert resp.status == 200
        listing = await resp.json()
        assert listing["total"] >= 1
        row = listing["profiles"][0]
        assert row["lane"] == SLOW_LANE
        assert row["tenant"] == TENANT
        assert row["reason"].startswith("regression:")
        # Cross-linked to the triggering request's trace id.
        assert row["trace_id"] == profiled["phases"]["trace_id"]
        resp = await client.get(f"/profiles/{row['id']}")
        assert resp.status == 200
        artifact = await resp.read()
        assert artifact[:2] == b"PK", "profile.zip must be a real zip"
        assert resp.headers["X-Trace-Id"] == row["trace_id"]
        # Zero transfer bytes billed for the harvest: the tenant's
        # download counter did not move (the profile.zip was this
        # workload's only changed file).
        ledger_after = executor.usage.tenant_snapshot(TENANT)
        assert (
            ledger_after["download_bytes"]
            == ledger_before["download_bytes"]
            == 0.0
        )
        # The statusz perf section shows the standing verdict.
        resp = await client.get("/statusz", params={"format": "text"})
        text = await resp.text()
        assert f"!!{SLOW_LANE}/exec: [regressed]" in text
    finally:
        await client.close()
        await executor.close()


async def test_kill_switch_restores_todays_behavior(tmp_path):
    config = _config(
        tmp_path, perf_observer_enabled=False, executor_fault_spec=""
    )
    backend = LocalSandboxBackend(config, warm_import_jax=False)
    storage = Storage(config.file_storage_path)
    executor = CodeExecutor(backend, storage, config)
    app = create_http_app(executor, CustomToolExecutor(executor), storage)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        body = await _execute(client, 0, tenant=TENANT)
        # Zero perf surface: no device-memory keys in phases, no series
        # recorded, 404 on both routes, no perf metric families.
        assert "peak_hbm_bytes" not in body["phases"]
        assert "live_buffer_bytes_delta" not in body["phases"]
        assert executor.perf._series == {}
        assert (await client.get("/perf")).status == 404
        assert (await client.get("/profiles")).status == 404
        metrics_text = (
            await (await client.get("/metrics")).text()
        )
        assert "perf_regression_total" not in metrics_text
        assert "code_interpreter_perf_state" not in metrics_text
        row = executor.usage.tenant_snapshot(TENANT)
        assert row["hbm_byte_seconds"] == 0.0
    finally:
        await client.close()
        await executor.close()
