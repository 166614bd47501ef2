"""Executor-level batched-dispatch tests: eligible small jobs coalesce into
ONE fused sandbox round-trip, per-job results demux back to each caller, and
every batch-level fault falls back to the serial path — the ISSUE's demux
edge cases (a typed violation 422s ITS job while batchmates stay clean; a
batch-partner crash reruns everyone serially; the kill switch restores the
serial path byte-for-byte).
"""

import asyncio

import pytest
from fakes import FakeBackend

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.errors import LimitExceededError
from bee_code_interpreter_fs_tpu.services.storage import Storage

LANE = 4  # a multi-chip, single-host lane (tpu_chips_per_host default 4)


def job_entry(i, **extra):
    return {
        "workdir": f".batch-1/job-{i}",
        "stdout": f"job {i} ok\n",
        "stderr": "",
        "exit_code": 0,
        "files": [],
        "duration_s": 0.01,
        "start_offset_s": 0.001 * i,
        **extra,
    }


def batch_body(n, **extra):
    return {
        "results": [job_entry(i) for i in range(n)],
        "warm": True,
        "runner_restarted": False,
        **extra,
    }


class Harness:
    """CodeExecutor over FakeBackend with both wire hops faked: records
    every serial /execute and every fused /execute-batch the orchestrator
    attempts, so tests can assert exactly which path served a request."""

    def __init__(self, executor: CodeExecutor):
        self.serial_calls = []
        self.batch_calls = []
        self.batch_response = None  # dict, Exception, or callable(payload)

        async def fake_post_execute(client, base, payload, timeout, sandbox):
            self.serial_calls.append(payload)
            return {
                "stdout": "serial ok\n",
                "stderr": "",
                "exit_code": 0,
                "files": [],
                "warm": True,
            }

        async def fake_post_batch(client, base, payload, timeout, sandbox):
            self.batch_calls.append(payload)
            response = self.batch_response
            if callable(response):
                response = response(payload)
            if isinstance(response, Exception):
                raise response
            if response is None:
                response = batch_body(len(payload["jobs"]))
            return response

        executor._post_execute = fake_post_execute
        executor._post_execute_batch = fake_post_batch


def make_executor(tmp_path, **config_kwargs):
    config_kwargs.setdefault("batch_window_ms", 20.0)
    config_kwargs.setdefault("batch_max_jobs", 4)
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        executor_pod_queue_target_length=1,
        **config_kwargs,
    )
    backend = FakeBackend()
    executor = CodeExecutor(backend, Storage(config.file_storage_path), config)
    harness = Harness(executor)
    return executor, harness


async def drain(executor: CodeExecutor) -> None:
    for _ in range(200):
        pending = list(executor._dispose_tasks) + list(executor._fill_tasks)
        if not pending:
            return
        await asyncio.gather(*pending, return_exceptions=True)


# (lane chips, jobs): the default four-chip host, and one job a chip of an
# eight-chip single-host lane.
FULL_BATCHES = [(LANE, 4), (8, 8)]


def make_lane_executor(tmp_path, lane, jobs, **config_kwargs):
    return make_executor(
        tmp_path,
        tpu_chips_per_host=lane,  # keeps the lane on one host
        batch_max_jobs=jobs,
        **config_kwargs,
    )


@pytest.mark.parametrize("lane, jobs", FULL_BATCHES)
async def test_full_batch_one_dispatch_demuxed_results(tmp_path, lane, jobs):
    executor, harness = make_lane_executor(tmp_path, lane, jobs)
    try:
        results = await asyncio.gather(
            *(
                executor.execute(f"print({i})", chip_count=lane)
                for i in range(jobs)
            )
        )
        # ONE fused round-trip served every request, none fell back to the
        # serial path...
        assert len(harness.batch_calls) == 1
        assert len(harness.serial_calls) == 0
        payload = harness.batch_calls[0]
        assert [j["source_code"] for j in payload["jobs"]] == [
            f"print({i})" for i in range(jobs)
        ]
        # ...with the device-axis placement hint per job...
        assert [j["device_index"] for j in payload["jobs"]] == list(range(jobs))
        # ...and each caller got ITS job's demuxed result.
        for i, result in enumerate(results):
            assert result.stdout == f"job {i} ok\n"
            assert result.exit_code == 0
            assert result.phases["batch_index"] == float(i)
            assert result.phases["batch_jobs"] == float(jobs)
        # Occupancy fed the scheduler (full batch = 1.0).
        assert executor.scheduler.batch_occupancies()[lane] == 1.0
        # The batch demux coordinates ride in phases but are NOT latencies:
        # they must never pollute the phase_seconds histogram (found live —
        # batch_jobs=8.0 read as an 8-second sample).
        rendered = executor.metrics.registry.render()
        assert 'phase="batch_jobs"' not in rendered
        assert 'phase="batch_index"' not in rendered
    finally:
        await executor.close()


async def test_job_violation_422s_its_caller_batchmates_stay_clean(tmp_path):
    """One job in the batch hits a typed in-process limit violation: ITS
    caller gets the 422-mapped LimitExceededError, every batchmate gets a
    clean result — a violation inside a batch never corrupts a partner."""
    executor, harness = make_executor(tmp_path)

    def response(payload):
        body = batch_body(len(payload["jobs"]))
        body["results"][1].update(
            {"exit_code": 1, "violation": "oom", "stderr": "MemoryError"}
        )
        return body

    harness.batch_response = response
    try:
        outcomes = await asyncio.gather(
            *(
                executor.execute(f"print({i})", chip_count=LANE)
                for i in range(4)
            ),
            return_exceptions=True,
        )
        assert isinstance(outcomes[1], LimitExceededError)
        assert outcomes[1].kind == "oom"
        assert outcomes[1].continuable  # runner survived: recycle, no strike
        for i in (0, 2, 3):
            assert outcomes[i].stdout == f"job {i} ok\n"
            assert outcomes[i].exit_code == 0
        # The violation was counted on the lane like any serial violation.
        assert (
            executor.metrics.limit_violations._values[(str(LANE), "oom")]
            == 1.0
        )
    finally:
        await executor.close()


async def test_batch_partner_crash_falls_back_to_serial(tmp_path):
    """The warm runner died mid-batch (one partner took the process down):
    every job transparently reruns on the serial path and succeeds — no
    request fails BECAUSE it was batched."""
    executor, harness = make_executor(tmp_path)
    harness.batch_response = batch_body(
        4, runner_restarted=True, timed_out=True
    )
    try:
        results = await asyncio.gather(
            *(
                executor.execute(f"print({i})", chip_count=LANE)
                for i in range(4)
            )
        )
        assert len(harness.batch_calls) == 1
        assert len(harness.serial_calls) == 4  # everyone re-ran serially
        assert all(r.stdout == "serial ok\n" for r in results)
        assert all(r.exit_code == 0 for r in results)
    finally:
        await executor.close()


async def test_batch_level_violation_falls_back_for_individual_verdicts(
    tmp_path,
):
    """A watchdog-attributed BATCH-level violation (one address space —
    unattributable to a job here): the fused dispatch aborts and the serial
    rerun owns each job's individual verdict."""
    executor, harness = make_executor(tmp_path)
    harness.batch_response = batch_body(4, violation="cpu_time")
    try:
        results = await asyncio.gather(
            *(
                executor.execute(f"print({i})", chip_count=LANE)
                for i in range(4)
            )
        )
        assert len(harness.serial_calls) == 4
        assert all(r.exit_code == 0 for r in results)
    finally:
        await executor.close()


async def test_tenants_never_share_a_dispatch(tmp_path):
    executor, harness = make_executor(tmp_path, batch_max_jobs=2)
    try:
        await asyncio.gather(
            executor.execute("print(0)", chip_count=LANE, tenant="alice"),
            executor.execute("print(1)", chip_count=LANE, tenant="alice"),
            executor.execute("print(0)", chip_count=LANE, tenant="bob"),
            executor.execute("print(1)", chip_count=LANE, tenant="bob"),
        )
        assert len(harness.batch_calls) == 2  # one dispatch PER tenant
        assert all(len(p["jobs"]) == 2 for p in harness.batch_calls)
    finally:
        await executor.close()


@pytest.mark.parametrize("lane, jobs", FULL_BATCHES)
async def test_kill_switch_restores_serial_path(tmp_path, lane, jobs):
    executor, harness = make_lane_executor(
        tmp_path, lane, jobs, batching_enabled=False
    )
    try:
        results = await asyncio.gather(
            *(
                executor.execute(f"print({i})", chip_count=lane)
                for i in range(jobs)
            )
        )
        assert executor.batcher is None
        assert len(harness.batch_calls) == 0
        assert len(harness.serial_calls) == jobs
        assert all(r.stdout == "serial ok\n" for r in results)
        # The serial path never touched the batch plane: no demux
        # coordinates in any result.
        assert all("batch_jobs" not in r.phases for r in results)
    finally:
        await executor.close()


async def test_ineligible_requests_take_the_serial_path(tmp_path):
    """Single-chip lanes, file-carrying requests, deadlines, and sessions
    never enter the batching window."""
    executor, harness = make_executor(tmp_path)
    try:
        # Lane 0 (default / single-chip): serial.
        await executor.execute("print('cpu')")
        assert len(harness.batch_calls) == 0
        assert len(harness.serial_calls) == 1
        # A deadline-carrying request: serial (its start-time promise is
        # per-request, not per-batch).
        await executor.execute("print('d')", chip_count=LANE, deadline=60.0)
        assert len(harness.batch_calls) == 0
        assert len(harness.serial_calls) == 2
    finally:
        await executor.close()


async def test_partial_window_still_batches(tmp_path):
    """Two jobs against a max of four: the window expires and they ride one
    under-filled dispatch (occupancy 0.5), not two serial round-trips."""
    executor, harness = make_executor(tmp_path, batch_window_ms=30.0)
    try:
        results = await asyncio.gather(
            executor.execute("print(0)", chip_count=LANE),
            executor.execute("print(1)", chip_count=LANE),
        )
        assert len(harness.batch_calls) == 1
        assert len(harness.batch_calls[0]["jobs"]) == 2
        assert all(r.exit_code == 0 for r in results)
        assert executor.scheduler.batch_occupancies()[LANE] == 0.5
    finally:
        await executor.close()


async def test_single_job_window_takes_serial_path(tmp_path):
    """A lone job whose window expires with no partner: serial semantics,
    exactly as if batching did not exist."""
    executor, harness = make_executor(tmp_path, batch_window_ms=5.0)
    try:
        result = await executor.execute("print('solo')", chip_count=LANE)
        assert len(harness.batch_calls) == 0
        assert len(harness.serial_calls) == 1
        assert result.stdout == "serial ok\n"
    finally:
        await executor.close()


async def test_batch_files_demux_via_hash_negotiation(tmp_path):
    """A batched job's changed files map back to the caller at the paths
    its code wrote (workdir prefix stripped), hash-negotiated against
    storage like any download."""
    executor, harness = make_executor(tmp_path, batch_max_jobs=2)
    async with executor.storage.writer() as writer:
        await writer.write(b"job output bytes")
    sha = writer.hash

    def response(payload):
        body = batch_body(len(payload["jobs"]))
        body["results"][0]["files"] = [{"path": "out/data.bin", "sha256": sha}]
        return body

    harness.batch_response = response
    try:
        results = await asyncio.gather(
            executor.execute("w", chip_count=LANE),
            executor.execute("x", chip_count=LANE),
        )
        assert results[0].files == {"/workspace/out/data.bin": sha}
        assert results[1].files == {}
    finally:
        await executor.close()


async def test_healthz_surfaces_lane_detail_and_batch_occupancy(tmp_path):
    """GET /healthz detail closes the loop on the PR 3 queue-wait EWMA and
    the new batch-occupancy ratio: after a half-filled batched dispatch the
    operator can read, per lane, whether requests queue and whether batches
    run under-filled — without a Prometheus round-trip."""
    pytest.importorskip("aiohttp", reason="optional dependency not installed")
    from aiohttp.test_utils import TestClient, TestServer

    from bee_code_interpreter_fs_tpu.services.custom_tool_executor import (
        CustomToolExecutor,
    )
    from bee_code_interpreter_fs_tpu.services.http_server import create_http_app

    executor, harness = make_executor(tmp_path, batch_max_jobs=4)
    client = TestClient(
        TestServer(create_http_app(executor, CustomToolExecutor(executor), executor.storage))
    )
    await client.start_server()
    try:
        await asyncio.gather(
            *(
                executor.execute(f"print({i})", chip_count=LANE)
                for i in range(2)
            )
        )
        assert len(harness.batch_calls) == 1  # a 2/4 under-filled dispatch
        resp = await client.get("/healthz")
        assert resp.status == 200
        body = await resp.json()
        assert body["status"] == "ok"
        lane = body["lanes"][str(LANE)]
        assert lane["queued"] == 0.0
        assert lane["queue_wait_ewma_s"] >= 0.0
        assert lane["batch_occupancy"] == pytest.approx(0.5)
        assert body["batching"] == {
            "enabled": True,
            "window_ms": 20.0,
            "max_jobs": 4,
        }
    finally:
        await client.close()
        await executor.close()


async def test_different_timeouts_never_share_a_dispatch(tmp_path):
    """The fused run has ONE deadline, so timeout is part of the
    compatibility key: a 5s job must never ride a partner's 300s window
    (found in review — max(timeouts) previously gated the whole batch)."""
    executor, harness = make_executor(tmp_path, batch_window_ms=10.0)
    try:
        results = await asyncio.gather(
            executor.execute("a", chip_count=LANE, timeout=5.0),
            executor.execute("b", chip_count=LANE, timeout=300.0),
            executor.execute("c", chip_count=LANE, timeout=5.0),
            executor.execute("d", chip_count=LANE, timeout=300.0),
        )
        assert len(harness.batch_calls) == 2
        assert sorted(p["timeout"] for p in harness.batch_calls) == [5.0, 300.0]
        for p in harness.batch_calls:
            assert len(p["jobs"]) == 2
        assert all(r.exit_code == 0 for r in results)
    finally:
        await executor.close()


async def test_malformed_batch_entry_is_a_batch_fault_not_one_callers(tmp_path):
    """One corrupt per-job entry reruns EVERYONE serially (with the serial
    path's retries) instead of failing that one caller with a hard infra
    error no serial request would ever see."""
    executor, harness = make_executor(tmp_path, batch_max_jobs=2)

    def response(payload):
        body = batch_body(len(payload["jobs"]))
        body["results"][1] = "not a dict"
        return body

    harness.batch_response = response
    try:
        results = await asyncio.gather(
            executor.execute("a", chip_count=LANE),
            executor.execute("b", chip_count=LANE),
        )
        assert len(harness.serial_calls) == 2
        assert all(r.stdout == "serial ok\n" for r in results)
    finally:
        await executor.close()


async def test_batch_level_stdout_refuses_demux_and_reruns_serially(tmp_path):
    """fd-level stdout (subprocess / C extension) lands batch-level and
    cannot be attributed to a job — the batch reruns serially so no output
    the serial path returns is ever silently dropped."""
    executor, harness = make_executor(tmp_path, batch_max_jobs=2)

    def response(payload):
        body = batch_body(len(payload["jobs"]))
        body["batch_stdout"] = "fd-level write\n"
        return body

    harness.batch_response = response
    try:
        results = await asyncio.gather(
            executor.execute("a", chip_count=LANE),
            executor.execute("b", chip_count=LANE),
        )
        assert len(harness.serial_calls) == 2
        assert all(r.stdout == "serial ok\n" for r in results)
    finally:
        await executor.close()
