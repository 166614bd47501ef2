"""Performance anomaly plane unit tests: the streaming quantile sketch,
the EWMA-banded drift detector (fake-clock windows, transition spans +
counter, baseline-poisoning immunity), auto-profile arming (consume-once,
tenant opt-out, throttle), the bounded content-addressed ProfileStore
(LRU, caps, persisted index), the kill switch, and the executor wiring
(device-memory phases + hbm-byte-second attribution + profile harvest
with the zero-transfer-bill rule)."""

import asyncio
import random
import tempfile

import pytest
from fakes import FakeBackend

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.perf_observer import (
    DEGRADED,
    NORMAL,
    REGRESSED,
    PerfObserver,
    ProfileStore,
    StreamingQuantile,
)
from bee_code_interpreter_fs_tpu.services.storage import Storage
from bee_code_interpreter_fs_tpu.utils.metrics import ExecutorMetrics
from bee_code_interpreter_fs_tpu.utils.tracing import Tracer


class FakeClock:
    def __init__(self, start: float = 1000.0) -> None:
        self.t = start

    def __call__(self) -> float:
        return self.t

    def advance(self, seconds: float) -> None:
        self.t += seconds


def make_observer(clock=None, tracer=None, metrics=None, **overrides):
    tmp = tempfile.mkdtemp(prefix="perf-test-")
    defaults = dict(
        file_storage_path=tmp,
        perf_window_seconds=10.0,
        perf_min_window_samples=3,
        perf_min_band_seconds=0.0,
        perf_profile_min_interval_seconds=0.0,
    )
    defaults.update(overrides)
    config = Config(**defaults)
    observer = PerfObserver(
        config,
        metrics=metrics,
        tracer=tracer,
        clock=clock or FakeClock(),
    )
    if metrics is not None:
        metrics.bind_perf(observer)
    return observer


def feed_window(observer, clock, lane, phase, values):
    """Record `values` into the current window, then advance past the
    window boundary and record one tick so the roll happens (windows roll
    lazily, on the next record)."""
    for value in values:
        observer.record(lane, phase, value)
    clock.advance(observer.window_s + 0.01)


# --------------------------------------------------------------- the sketch


def test_sketch_quantiles_are_close_on_known_distribution():
    sketch = StreamingQuantile()
    rng = random.Random(7)
    values = [rng.uniform(0.01, 1.0) for _ in range(5000)]
    for v in values:
        sketch.add(v)
    values.sort()
    for q in (0.5, 0.95, 0.99):
        exact = values[int(q * len(values)) - 1]
        estimate = sketch.quantile(q)
        # Log-bucket relative error is bounded by the growth factor.
        assert abs(estimate - exact) / exact < 0.15, (q, estimate, exact)
    assert sketch.count == 5000


def test_sketch_is_bounded_and_ignores_garbage():
    sketch = StreamingQuantile(max_buckets=32)
    for i in range(10000):
        sketch.add(float(i))
    assert len(sketch.counts) <= 32
    sketch.add(float("nan"))
    sketch.add(-1.0)
    sketch.add("nope")  # type: ignore[arg-type]
    assert sketch.count == 10000
    assert sketch.quantile(1.0) == sketch.max_value


def test_sketch_empty_reads_zero():
    assert StreamingQuantile().quantile(0.95) == 0.0


# --------------------------------------------------------- drift detection


def test_first_window_establishes_baseline_as_normal():
    clock = FakeClock()
    observer = make_observer(clock)
    feed_window(observer, clock, 0, "exec", [0.1, 0.1, 0.1, 0.1])
    observer.record(0, "exec", 0.1)  # triggers the roll
    states = observer.lane_phase_states()
    assert states["0/exec"] == NORMAL
    series = observer._series[(0, "exec")]
    assert series.baseline is not None
    assert 0.08 < series.baseline < 0.13


def test_regression_flips_within_one_window_and_fires_signals():
    clock = FakeClock()
    tracer = Tracer(enabled=True, sample_ratio=0.0)  # head sampling OFF
    metrics = ExecutorMetrics()
    observer = make_observer(clock, tracer=tracer, metrics=metrics)
    feed_window(observer, clock, 0, "exec", [0.1] * 6)
    feed_window(observer, clock, 0, "exec", [0.5] * 6)  # 5x the baseline
    observer.record(0, "exec", 0.5)
    assert observer.lane_phase_states()["0/exec"] == REGRESSED
    # perf_regression_total{lane,phase} fired.
    samples = metrics.perf_regressions.samples()
    assert any(
        labels == {"lane": "0", "phase": "exec"} and value == 1.0
        for labels, value in samples
    )
    # The perf.regression span is retrievable at 0% head sampling — the
    # record_span path bypasses the sampling coin flip entirely.
    spans = [
        s
        for s in list(tracer.ring._spans)
        if s.get("name") == "perf.regression"
    ]
    assert spans, "perf.regression span must land despite 0% sampling"
    assert spans[-1]["attributes"]["to"] == REGRESSED
    assert spans[-1]["status"] == "error"
    # The regression armed an auto-profile for the lane.
    assert observer.take_profile_arm(0, "someone") == "regression:exec"


def test_degraded_band_sits_between_normal_and_regressed():
    clock = FakeClock()
    # The p99-outlier trigger is parked out of the way (factor 100): this
    # test is about the WINDOW verdict alone.
    observer = make_observer(clock, perf_p99_outlier_factor=100.0)
    feed_window(observer, clock, 0, "exec", [0.1] * 6)
    feed_window(observer, clock, 0, "exec", [0.2] * 6)  # 2x: degraded band
    observer.record(0, "exec", 0.2)
    assert observer.lane_phase_states()["0/exec"] == DEGRADED
    # Degraded does NOT arm a profile — only regressed (and p99 outliers).
    assert observer.take_profile_arm(0, None) is None


def test_regressed_window_does_not_poison_the_baseline():
    clock = FakeClock()
    observer = make_observer(clock)
    feed_window(observer, clock, 0, "exec", [0.1] * 6)
    observer.record(0, "exec", 0.1)
    baseline_before = observer._series[(0, "exec")].baseline
    feed_window(observer, clock, 0, "exec", [0.9] * 6)
    observer.record(0, "exec", 0.9)
    assert observer.lane_phase_states()["0/exec"] == REGRESSED
    # Baseline unchanged: the regression is measured against the healthy
    # past, not slowly becoming the new normal.
    assert observer._series[(0, "exec")].baseline == baseline_before
    # Healthy windows recover the verdict. Two of them: the first still
    # contains the roll-triggering 0.9 straggler, and a 7-sample window's
    # p95 IS its max — tiny-window tail quantiles forgive nothing.
    feed_window(observer, clock, 0, "exec", [0.1] * 6)
    feed_window(observer, clock, 0, "exec", [0.1] * 6)
    observer.record(0, "exec", 0.1)
    assert observer.lane_phase_states()["0/exec"] == NORMAL


def test_thin_window_keeps_the_standing_verdict():
    clock = FakeClock()
    observer = make_observer(clock)
    feed_window(observer, clock, 0, "exec", [0.1] * 6)
    observer.record(0, "exec", 0.1)
    # One slow sample is not a window (min 3): verdict stays normal.
    feed_window(observer, clock, 0, "exec", [5.0])
    observer.record(0, "exec", 0.1)
    assert observer.lane_phase_states()["0/exec"] == NORMAL


def test_lane_isolation_healthy_lane_stays_normal():
    clock = FakeClock()
    observer = make_observer(clock)
    for _ in range(2):
        for value in [0.1] * 6:
            observer.record(0, "exec", value)
            observer.record(4, "exec", value)
        clock.advance(observer.window_s + 0.01)
    observer.record(0, "exec", 0.1)
    observer.record(4, "exec", 0.1)
    # Lane 4 regresses; lane 0 must not.
    feed_window(observer, clock, 4, "exec", [0.8] * 6)
    for value in [0.1] * 6:
        observer.record(0, "exec", value)
    observer.record(4, "exec", 0.8)
    observer.record(0, "exec", 0.1)
    states = observer.lane_phase_states()
    assert states["4/exec"] == REGRESSED
    assert states["0/exec"] == NORMAL


def test_series_cardinality_is_bounded():
    clock = FakeClock()
    observer = make_observer(clock, perf_max_series=10)
    for lane in range(50):
        observer.record(lane, "exec", 0.1)
    assert len(observer._series) <= 10


def test_tenant_series_overflow_discipline():
    clock = FakeClock()
    observer = make_observer(clock, perf_max_tenants=2)
    for i in range(5):
        observer.record_request(
            0, {"exec": 0.1, "queue_wait": 0.01}, tenant=f"t{i}"
        )
    assert set(observer._tenants) <= {"t0", "t1", "_overflow"}
    assert "_overflow" in observer._tenants


# ------------------------------------------------------------ auto-profile


def test_p99_outlier_arms_profile_once():
    clock = FakeClock()
    observer = make_observer(clock)
    for _ in range(20):
        observer.record(0, "exec", 0.1)
    observer.record(0, "exec", 5.0)  # way past p99 * factor
    reason = observer.take_profile_arm(0, "tenant-a")
    assert reason == "p99_outlier:exec"
    # Consumed exactly once.
    assert observer.take_profile_arm(0, "tenant-a") is None


def test_opt_out_tenant_never_consumes_an_arm():
    clock = FakeClock()
    observer = make_observer(
        clock, perf_profile_tenant_opt_out=["private-tenant"]
    )
    observer.arm_profile(0, reason="regression:exec")
    assert observer.take_profile_arm(0, "private-tenant") is None
    # The arm waited for the next consenting request.
    assert observer.take_profile_arm(0, "other") == "regression:exec"


def test_profile_throttle_blocks_rearm_within_interval():
    clock = FakeClock()
    observer = make_observer(clock, perf_profile_min_interval_seconds=60.0)
    observer.arm_profile(0, reason="regression:exec")
    assert observer.take_profile_arm(0, None) is not None
    observer.arm_profile(0, reason="regression:exec")
    assert observer.take_profile_arm(0, None) is None  # throttled
    clock.advance(61.0)
    observer.arm_profile(0, reason="regression:exec")
    assert observer.take_profile_arm(0, None) is not None


# ------------------------------------------------------------ profile store


def test_profile_store_roundtrip_and_content_addressing():
    tmp = tempfile.mkdtemp(prefix="profile-store-")
    store = ProfileStore(tmp)
    pid = store.add(b"zip-bytes", {"lane": 4, "trace_id": "abc"})
    again = store.add(b"zip-bytes", {"lane": 4, "trace_id": "abc"})
    assert pid == again  # identical bytes dedup to one object
    assert store.entry_count() == 1
    data, meta = store.get(pid)
    assert data == b"zip-bytes"
    assert meta["lane"] == 4 and meta["trace_id"] == "abc"
    rows = store.list()
    assert rows[0]["id"] == pid
    assert store.get("0" * 32) is None


def test_profile_store_lru_eviction_under_entry_cap():
    tmp = tempfile.mkdtemp(prefix="profile-store-")
    clock = FakeClock()
    store = ProfileStore(tmp, max_entries=2, walltime=clock)
    a = store.add(b"aaaa", {})
    clock.advance(1)
    b = store.add(b"bbbb", {})
    clock.advance(1)
    store.get(a)  # refresh a's recency: b becomes the LRU victim
    clock.advance(1)
    c = store.add(b"cccc", {})
    assert store.get(b) is None
    assert store.get(a) is not None and store.get(c) is not None
    assert store.evictions == 1


def test_profile_store_byte_cap_and_persisted_index():
    tmp = tempfile.mkdtemp(prefix="profile-store-")
    store = ProfileStore(tmp, max_bytes=1 << 20, max_entries=100)
    # max_bytes floors at 1 MiB; two ~700KB objects exceed it.
    first = store.add(b"x" * 700_000, {"lane": 1})
    second = store.add(b"y" * 700_000, {"lane": 2})
    assert store.entry_count() == 1
    assert store.get(first) is None and store.get(second) is not None
    # The index persists: a fresh instance sees the survivor.
    reopened = ProfileStore(tmp, max_bytes=1 << 20, max_entries=100)
    assert reopened.entry_count() == 1
    assert reopened.get(second) is not None


# -------------------------------------------------------------- kill switch


def test_kill_switch_disables_everything():
    clock = FakeClock()
    metrics = ExecutorMetrics()
    observer = make_observer(clock, metrics=metrics, perf_observer_enabled=False)
    assert not observer.enabled
    assert observer.store is None
    observer.record(0, "exec", 0.1)
    observer.record_request(0, {"exec": 0.1}, tenant="t")
    assert observer._series == {} and observer._tenants == {}
    observer.arm_profile(0, reason="x")
    assert observer.take_profile_arm(0, None) is None
    assert observer.snapshot()["enabled"] is False
    # bind_perf registered NOTHING: /metrics exposition carries zero perf
    # families (the quota-gauge discipline, byte-for-byte).
    assert metrics.perf_regressions is None
    assert "perf_regression_total" not in metrics.registry.render()
    assert "code_interpreter_perf_state" not in metrics.registry.render()


def test_enabled_observer_registers_metric_families():
    metrics = ExecutorMetrics()
    make_observer(FakeClock(), metrics=metrics)
    text = metrics.registry.render()
    assert "perf_regression_total" in text
    assert "code_interpreter_perf_state" in text
    assert "code_interpreter_tenant_usage_hbm_byte_seconds_total" in text


# ---------------------------------------------------------- executor wiring


def _executor(**overrides):
    tmp = tempfile.mkdtemp(prefix="perf-exec-")
    defaults = dict(
        file_storage_path=tmp,
        executor_pod_queue_target_length=1,
        compile_cache_enabled=False,
        device_probe_interval=0.0,
        perf_window_seconds=5.0,
        perf_min_window_samples=3,
    )
    defaults.update(overrides)
    config = Config(**defaults)
    backend = FakeBackend()
    return CodeExecutor(backend, Storage(tmp), config)


DEVICE_MEMORY_BLOCK = {
    "live_bytes_before": 1_000_000,
    "live_bytes_after": 3_000_000,
    "peak_bytes_before": 4_000_000,
    "peak_bytes_after": 9_000_000,
    "rss_bytes": 50_000_000,
}


def _fake_post(captured=None, device_memory=True):
    async def post(client, base, payload, timeout, sandbox):
        if captured is not None:
            captured.append(payload)
        body = {
            "stdout": "ok\n",
            "stderr": "",
            "exit_code": 0,
            "files": [],
            "warm": True,
            "duration_s": 0.5,
            "device_op_seconds": 0.5,
        }
        if device_memory and payload.get("device_memory"):
            body["device_memory"] = dict(DEVICE_MEMORY_BLOCK)
        return body

    return post


def test_execute_carries_device_memory_phases_and_bills_hbm():
    async def run():
        executor = _executor()
        captured = []
        executor._post_execute = _fake_post(captured)
        try:
            result = await executor.execute("print(1)", tenant="acct")
        finally:
            await executor.close()
        assert captured[0]["device_memory"] is True
        # Allocator peak moved during the run → the new high-water is this
        # request's peak.
        assert result.phases["peak_hbm_bytes"] == 9_000_000
        assert result.phases["live_buffer_bytes_delta"] == 2_000_000
        assert result.phases["runner_rss_bytes"] == 50_000_000
        row = executor.usage.tenant_snapshot("acct")
        # peak x device-op wall, to within float rounding.
        assert abs(row["hbm_byte_seconds"] - 9_000_000 * 0.5) < 1.0
        # Latency histogram untouched by the new keys (allowlist).
        phase_labels = {
            labels["phase"]
            for labels, *_ in executor.metrics.phase_seconds.samples()
        }
        assert "peak_hbm_bytes" not in phase_labels
        return result

    asyncio.run(run())


def test_kill_switch_keeps_wire_and_phases_byte_for_byte():
    async def run():
        executor = _executor(perf_observer_enabled=False)
        captured = []
        executor._post_execute = _fake_post(captured)
        try:
            result = await executor.execute("print(1)", tenant="acct")
        finally:
            await executor.close()
        assert "device_memory" not in captured[0]
        assert "peak_hbm_bytes" not in result.phases
        assert "live_buffer_bytes_delta" not in result.phases
        row = executor.usage.tenant_snapshot("acct")
        assert row["hbm_byte_seconds"] == 0.0

    asyncio.run(run())


def test_peak_falls_back_to_live_when_allocator_peak_is_stale():
    block = {
        "live_bytes_before": 500,
        "live_bytes_after": 2000,
        "peak_bytes_before": 9000,
        "peak_bytes_after": 9000,  # unchanged: an OLDER run's high-water
        "rss_bytes": -1,
    }
    assert CodeExecutor._block_peak_bytes(block) == 2000
    no_peak = {
        "live_bytes_before": 100,
        "live_bytes_after": 50,
        "peak_bytes_before": -1,
        "peak_bytes_after": -1,
    }
    assert CodeExecutor._block_peak_bytes(no_peak) == 100


def test_auto_profiled_request_harvests_and_bills_zero_transfer():
    async def run():
        executor = _executor()
        executor._post_execute = _fake_post()
        profile_bytes = b"PK\x03\x04fake-profile-zip"

        async def fake_download(client, hosts, transfer, bodies, stats):
            object_id = await executor.storage.write(profile_bytes)
            stats.download_bytes += len(profile_bytes)
            stats.download_files += 1
            return {"/workspace/profile.zip": object_id}

        executor._download_changed = fake_download
        executor.perf.arm_profile(0, reason="regression:exec")
        try:
            # Inside a real trace context, so the harvested artifact can
            # cross-link to the request's trace id.
            with executor.tracer.start_trace("test-root"):
                result = await executor.execute("print(1)", tenant="acct")
        finally:
            await executor.close()
        # The artifact left the tenant's files and entered the store,
        # cross-linked to the request's trace.
        assert "/workspace/profile.zip" not in result.files
        rows = executor.perf.store.list()
        assert len(rows) == 1
        assert rows[0]["reason"] == "regression:exec"
        assert rows[0]["tenant"] == "acct"
        assert rows[0]["trace_id"] == result.phases.get("trace_id")
        # ... and the turn carries the mark, so that whoever reads a
        # window's phases can tell the service's own captures (ISSUE 39).
        assert result.phases["auto_profiled"] == 1.0
        data, _meta = executor.perf.store.get(rows[0]["id"])
        assert data == profile_bytes
        # Zero transfer bytes billed for the harvest (the PR 9
        # trusted-run rule): the ledger's download_bytes stays 0.
        row = executor.usage.tenant_snapshot("acct")
        assert row["download_bytes"] == 0.0
        # The arm was consumed: the next request runs unprofiled and its
        # downloads bill normally.
        assert executor.perf.take_profile_arm(0, "acct") is None

    asyncio.run(run())


def test_client_requested_profile_is_not_harvested():
    async def run():
        executor = _executor()
        executor._post_execute = _fake_post()
        profile_bytes = b"PK\x03\x04client-profile"

        async def fake_download(client, hosts, transfer, bodies, stats):
            object_id = await executor.storage.write(profile_bytes)
            stats.download_bytes += len(profile_bytes)
            return {"/workspace/profile.zip": object_id}

        executor._download_changed = fake_download
        try:
            result = await executor.execute(
                "print(1)", tenant="acct", profile=True
            )
        finally:
            await executor.close()
        # The tenant profiled itself: the zip stays in its files, the
        # bytes bill normally, nothing enters the store.
        assert "/workspace/profile.zip" in result.files
        assert result.phases["auto_profiled"] == 0.0, "the client's own capture is no auto-profile"
        assert executor.perf.store.entry_count() == 0
        row = executor.usage.tenant_snapshot("acct")
        assert row["download_bytes"] == float(len(profile_bytes))

    asyncio.run(run())


def test_trusted_runs_do_not_feed_baselines():
    async def run():
        executor = _executor()
        executor._post_execute = _fake_post()
        try:
            await executor._execute_trusted("print(1)")
            assert executor.perf._series == {}
            await executor.execute("print(1)")
            assert (0, "exec") in executor.perf._series
        finally:
            await executor.close()

    asyncio.run(run())


def test_statusz_and_perf_snapshot_surface():
    async def run():
        executor = _executor()
        executor._post_execute = _fake_post()
        try:
            await executor.execute("print(1)", tenant="acct")
        finally:
            await executor.close()
        body = executor.statusz()
        assert body["perf"]["enabled"] is True
        assert "0/exec" in body["perf"]["series"]
        snap = executor.perf.snapshot()
        assert snap["status"] in ("normal", "degraded", "regressed")
        assert snap["tenants"]["acct"]["count"] >= 1

    asyncio.run(run())


def test_failed_store_write_keeps_artifact_in_tenant_files():
    """ENOSPC/unwritable profile volume: the harvest must NOT destroy the
    only copy — the artifact stays in the request's files (billed like a
    client-requested profile) and nothing counts as captured."""

    async def run():
        executor = _executor()
        executor._post_execute = _fake_post()
        profile_bytes = b"PK\x03\x04doomed-profile"

        async def fake_download(client, hosts, transfer, bodies, stats):
            object_id = await executor.storage.write(profile_bytes)
            stats.download_bytes += len(profile_bytes)
            return {"/workspace/profile.zip": object_id}

        executor._download_changed = fake_download
        # The store's write path fails (full volume shape).
        executor.perf.store.add = lambda data, meta: None
        executor.perf.arm_profile(0, reason="regression:exec")
        try:
            result = await executor.execute("print(1)", tenant="acct")
        finally:
            await executor.close()
        assert "/workspace/profile.zip" in result.files
        assert executor.perf.profiles_captured == 0
        # Billed normally: the bytes were delivered to the tenant.
        row = executor.usage.tenant_snapshot("acct")
        assert row["download_bytes"] == float(len(profile_bytes))

    asyncio.run(run())


# ------------------------------------------------------- xprof summarization


def _trace_zip(events, member="plugins/profile/run/host.trace.json.gz"):
    import gzip
    import io
    import json
    import zipfile

    payload = json.dumps({"traceEvents": events}).encode()
    if member.endswith(".gz"):
        payload = gzip.compress(payload)
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w") as archive:
        archive.writestr(member, payload)
    return buf.getvalue()


def test_summarize_profile_verdict_top_ops_share_and_gaps():
    from bee_code_interpreter_fs_tpu.services.perf_observer import (
        summarize_profile,
    )

    events = [
        # Process metadata: pid 1 is the device, pid 2 the host runtime.
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "python host"}},
        # Device ops: 0-4000us busy, a 2000us idle gap, 6000-10000us busy.
        {"ph": "X", "pid": 1, "name": "fusion.3", "ts": 0, "dur": 4000},
        {"ph": "X", "pid": 1, "name": "copy.1", "ts": 6000, "dur": 1000},
        {"ph": "X", "pid": 1, "name": "fusion.3", "ts": 7000, "dur": 3000},
        # Host-side event: never counted as device time.
        {"ph": "X", "pid": 2, "name": "python busywork", "ts": 0,
         "dur": 10000},
    ]
    summary = summarize_profile(_trace_zip(events))
    assert summary["span_ms"] == 10.0
    assert summary["device_busy_ms"] == 8.0
    assert summary["device_op_wall_share"] == 0.8
    # Top op by total device time, with its share of op time.
    assert summary["top_ops"][0]["name"] == "fusion.3"
    assert summary["top_ops"][0]["total_ms"] == 7.0
    assert summary["top_ops"][0]["count"] == 2
    assert "python busywork" not in [op["name"] for op in summary["top_ops"]]
    # The idle gap between the two busy stretches: under no annotation.
    assert summary["idle_gaps"] == [
        {"offset_ms": 4.0, "duration_ms": 2.0}
    ]
    assert summary["idle_by"] == {"none": 2.0}
    assert "device busy 80%" in summary["verdict"]
    assert "fusion.3" in summary["verdict"]


def test_summarize_profile_without_a_device_process_claims_no_device_time():
    """A capture with no device process (a CPU run, or a turn that ran no
    device program): host events are not counted as the device's."""
    from bee_code_interpreter_fs_tpu.services.perf_observer import (
        summarize_profile,
    )

    events = [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 7, "name": "python busywork", "ts": 100,
         "dur": 5000},
        {"ph": "X", "pid": 7, "name": "runner.user_code", "ts": 600,
         "dur": 4000},
    ]
    summary = summarize_profile(_trace_zip(events))
    assert summary["device_plane"] is False
    assert summary["span_ms"] == 5.0
    assert "no device plane" in summary["verdict"]
    for key in ("device_busy_ms", "device_op_wall_share", "top_ops", "idle_gaps"):
        assert key not in summary
    assert summary["runner_stages"] == [
        {"name": "runner.user_code", "offset_ms": 0.5, "duration_ms": 4.0}
    ]


def test_summarize_profile_names_idle_gaps_by_the_runner_stage():
    """Where the capture holds the warm runner's `runner.*` annotations and
    no `shim.*` one, the idle time reads by runner stage: each idle stretch
    belongs to the stage that covers it, and one that crosses from a stage
    into the next is split where the stage ends."""
    from bee_code_interpreter_fs_tpu.services.perf_observer import (
        summarize_profile,
    )

    events = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 2, "name": "runner.limits_arm", "ts": 0, "dur": 1000},
        {"ph": "X", "pid": 2, "name": "runner.user_code", "ts": 1000,
         "dur": 8000},
        {"ph": "X", "pid": 2, "name": "runner.limits_restore", "ts": 9000,
         "dur": 1500},
        {"ph": "X", "pid": 1, "name": "fusion.8", "ts": 500, "dur": 1500},
        {"ph": "X", "pid": 1, "name": "fusion.1", "ts": 5000, "dur": 3000},
        {"ph": "X", "pid": 1, "name": "copy.2", "ts": 10000, "dur": 500},
    ]
    summary = summarize_profile(_trace_zip(events))
    assert summary["device_plane"] is True
    assert summary["device_busy_ms"] == 5.0
    assert summary["idle_gaps"] == [
        {"offset_ms": 2.0, "duration_ms": 3.0, "during": "runner.user_code"},
        {"offset_ms": 8.0, "duration_ms": 1.0, "during": "runner.user_code"},
        {"offset_ms": 9.0, "duration_ms": 1.0, "during": "runner.limits_restore"},
        {"offset_ms": 0.0, "duration_ms": 0.5, "during": "runner.limits_arm"},  # before the first device op
    ]
    assert summary["idle_by"] == {
        "runner.user_code": 4.0, "runner.limits_restore": 1.0, "runner.limits_arm": 0.5,
    }
    assert sum(summary["idle_by"].values()) + summary["device_busy_ms"] == summary["span_ms"] == 10.5
    assert "shim_stages" not in summary
    assert [s["name"] for s in summary["runner_stages"]] == [
        "runner.limits_arm", "runner.user_code", "runner.limits_restore",
    ]
    assert "runner.user_code" not in [op["name"] for op in summary["top_ops"]]


def test_summarize_profile_puts_idle_time_down_to_the_innermost_annotation():
    """ISSUE 39: the shim's stages are annotations of the capture's host plane
    beside the runner's. Every idle microsecond belongs to the innermost one
    that covers it; a stretch that crosses a boundary is split there; what
    `idle_by` sums and the device's busy time add up to the capture."""
    from bee_code_interpreter_fs_tpu.services.perf_observer import (
        summarize_profile,
    )

    events = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 2, "name": "runner.limits_arm", "ts": 0, "dur": 1000},
        {"ph": "X", "pid": 2, "name": "runner.user_code", "ts": 1000, "dur": 19000},
        # inside the user's code: a file read, its copy, a program, the wait
        # for its value, the copy back; then python of the user's own
        {"ph": "X", "pid": 2, "name": "shim.load", "ts": 2000, "dur": 6000},
        {"ph": "X", "pid": 2, "name": "shim.h2d", "ts": 8000, "dur": 500},
        {"ph": "X", "pid": 2, "name": "shim.materialize", "ts": 9000, "dur": 1000},
        {"ph": "X", "pid": 2, "name": "shim.wait", "ts": 10000, "dur": 5000},
        {"ph": "X", "pid": 2, "name": "shim.d2h", "ts": 15000, "dur": 200},
        # the device: a copy's tail, then the program, which the wait covers
        {"ph": "X", "pid": 1, "name": "copy.1", "ts": 8200, "dur": 300},
        {"ph": "X", "pid": 1, "name": "fusion.4", "ts": 11000, "dur": 3900},
        {"ph": "X", "pid": 2, "name": "python busywork", "ts": 15200, "dur": 4800},
    ]
    summary = summarize_profile(_trace_zip(events))
    assert summary["span_ms"] == 20.0 and summary["device_busy_ms"] == 4.2
    # ONE stretch, 0 to 8200 us, crosses limits_arm -> user_code -> load -> h2d
    # and is split at each boundary; 8500 to 11000 crosses h2d's end, the
    # dispatch and into the wait.
    assert summary["idle_by"] == {
        "shim.load": 6.0, "runner.user_code": 6.3, "shim.wait": 1.1, "runner.limits_arm": 1.0,
        "shim.materialize": 1.0, "shim.h2d": 0.2, "shim.d2h": 0.2,
    }
    assert sum(summary["idle_by"].values()) + summary["device_busy_ms"] == pytest.approx(summary["span_ms"])
    assert summary["idle_gaps"] == [
        {"offset_ms": 2.0, "duration_ms": 6.0, "during": "shim.load"},
        {"offset_ms": 15.2, "duration_ms": 4.8, "during": "runner.user_code"},
        {"offset_ms": 0.0, "duration_ms": 1.0, "during": "runner.limits_arm"},
        {"offset_ms": 1.0, "duration_ms": 1.0, "during": "runner.user_code"},
        {"offset_ms": 9.0, "duration_ms": 1.0, "during": "shim.materialize"},
    ]
    assert "largest idle gap 6.0ms" in summary["verdict"]
    assert [s["name"] for s in summary["runner_stages"]] == ["runner.limits_arm", "runner.user_code"]
    assert summary["shim_stages"] == {
        "shim.load": {"count": 1, "total_ms": 6.0}, "shim.h2d": {"count": 1, "total_ms": 0.5},
        "shim.materialize": {"count": 1, "total_ms": 1.0}, "shim.wait": {"count": 1, "total_ms": 5.0},
        "shim.d2h": {"count": 1, "total_ms": 0.2},
    }
    assert not {op["name"] for op in summary["top_ops"]} & {"shim.load", "shim.wait", "runner.user_code"}


def test_summarize_profile_degrades_without_a_trace_member():
    import io
    import zipfile

    from bee_code_interpreter_fs_tpu.services.perf_observer import (
        summarize_profile,
    )

    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w") as archive:
        archive.writestr("plugins/profile/run/host.xplane.pb", b"\x00\x01")
    summary = summarize_profile(buf.getvalue())
    assert summary["verdict"] == "unparseable"
    assert "host.xplane.pb" in summary["members"][0]
    # And a corrupt artifact is a verdict, never an exception.
    assert summarize_profile(b"not a zip")["verdict"] == "unparseable"
