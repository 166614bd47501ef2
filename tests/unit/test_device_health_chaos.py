"""Seeded attach-hang chaos for the device-health probe (faults.py ->
device_health.py), CHAOS_SEED-parameterized like the other chaos suites:
CI pins the {7, 23, 1337} matrix; a red leg replays exactly with
``CHAOS_SEED=<n> pytest tests/unit/test_device_health_chaos.py``.

The injected fault is a HANG, not an error: the host's HTTP plane answers
every probe, but its synthesized /device-stats reports an attach that has
been pending since the hang began and keeps aging in (injected) real time —
the wedge of rounds 3 to 5 (a device op that never completes). The probe
must walk that host
healthy -> (busy/suspect) -> wedged while untouched hosts stay healthy.
"""

import os
import random
import tempfile

import httpx
import pytest

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.faults import (
    ATTACH_HANG,
    AttachHangTransport,
    FaultInjectingBackend,
    FaultSpec,
)
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.device_health import (
    BUSY,
    HEALTHY,
    SUSPECT,
    WEDGED,
    DeviceHealthProbe,
)
from bee_code_interpreter_fs_tpu.services.storage import Storage

from fakes import FakeBackend

CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "7"))


def _healthy_stats() -> dict:
    return {
        "status": "ok",
        "warm": True,
        "warm_state": "ready",
        "backend": "cpu",
        "device_kind": "cpu",
        "device_count": 1,
        "attach_pending_s": 0.0,
        "attach_seconds": 1.0,
        "op_in_flight": False,
        "op_age_s": 0.0,
        "op_timeout_s": 0.0,
        "last_device_op_age_s": 1.0,
        "runner_heartbeat_age_s": 0.1,
        "runner_alive": True,
        "rss_bytes": 1,
        "runner_rss_bytes": 1,
    }


def _inner_transport() -> httpx.MockTransport:
    return httpx.MockTransport(
        lambda request: httpx.Response(200, json=_healthy_stats())
    )


def _transport(
    rate: float,
    lane: int,
    host_lanes: dict[str, int],
    clock,
    seed: int = CHAOS_SEED,
    on_fault=None,
) -> AttachHangTransport:
    return AttachHangTransport(
        rate,
        lane,
        random.Random(f"{seed}:{ATTACH_HANG}"),
        host_lanes,
        on_fault,
        inner=_inner_transport(),
        clock=clock,
    )


def test_per_host_draw_is_seeded_and_stable():
    """The wedged subset is a pure function of (seed, first-probe order):
    two transports with the same seed choose the same hosts; a wedge never
    flickers back on a later probe."""
    hosts = [f"h{i}:80" for i in range(8)]
    lanes = {h: 0 for h in hosts}
    clock = lambda: 100.0  # noqa: E731

    def draws(seed):
        transport = _transport(0.5, -1, lanes, clock, seed=seed)
        out = []
        for host in hosts:
            request = httpx.Request("GET", f"http://{host}/device-stats")
            out.append(transport._hang_started(request) is not None)
        return out

    first = draws(CHAOS_SEED)
    assert first == draws(CHAOS_SEED)
    assert any(first), "rate 0.5 over 8 hosts should wedge at least one"
    assert not all(first), "rate 0.5 over 8 hosts should spare at least one"
    # Re-asking the same transport never changes a host's fate.
    transport = _transport(0.5, -1, lanes, clock)
    request = httpx.Request("GET", "http://h0:80/device-stats")
    assert (
        transport._hang_started(request) is transport._hang_started(request)
        or transport._hang_started(request) == transport._hang_started(request)
    )


def test_lane_restriction_spares_other_lanes():
    lanes = {"a:1": 0, "b:2": 2}
    clock = lambda: 5.0  # noqa: E731
    transport = _transport(1.0, 2, lanes, clock)
    assert (
        transport._hang_started(httpx.Request("GET", "http://a:1/device-stats"))
        is None
    )
    assert (
        transport._hang_started(httpx.Request("GET", "http://b:2/device-stats"))
        is not None
    )


async def test_hang_age_grows_in_real_time():
    now = [10.0]
    lanes = {"w:9": 0}
    transport = _transport(1.0, -1, lanes, lambda: now[0])
    async with httpx.AsyncClient(transport=transport) as client:
        first = (await client.get("http://w:9/device-stats")).json()
        assert first["injected"] == ATTACH_HANG
        assert first["warm_state"] == "pending"
        assert first["attach_pending_s"] == pytest.approx(0.0)
        now[0] += 42.0
        later = (await client.get("http://w:9/device-stats")).json()
        assert later["attach_pending_s"] == pytest.approx(42.0)
        # Matching stale heartbeat: the runner has said nothing since.
        assert later["runner_heartbeat_age_s"] == pytest.approx(42.0)


async def test_probe_escalates_wedge_on_hung_host_spares_healthy_one():
    """End-to-end through the probe: two hosts, the fault wedges exactly
    the attach_hang_lane one; the probe walks it to WEDGED while the other
    stays healthy, and the wedge counter/fault counter fire once."""
    tmp = tempfile.mkdtemp(prefix="dh-chaos-")
    config = Config(
        file_storage_path=tmp,
        executor_fault_spec=(
            f"attach_hang:1.0,attach_hang_lane:2,seed:{CHAOS_SEED}"
        ),
        device_probe_attach_budget=10.0,
        device_probe_wedge_after=10.0,
        # Detection-only posture (the actuation kill switch): this suite
        # asserts the PR 8 classification semantics; the fence/drain/
        # replace loop has its own chaos suite (test_recovery_chaos.py).
        device_fence_enabled=False,
    )
    faults = []
    backend = FaultInjectingBackend(
        FakeBackend(distinct_urls=True),
        FaultSpec.parse(config.executor_fault_spec),
        on_fault=faults.append,
    )
    executor = CodeExecutor(backend, Storage(tmp), config)
    try:
        healthy_box = await backend.spawn(0)
        wedged_box = await backend.spawn(2)
        for lane, box in ((0, healthy_box), (2, wedged_box)):
            executor._live_sandboxes[box.id] = (lane, box)
        # The injected clock drives the synthesized hang age.
        now = [0.0]
        hang = _transport(
            1.0, 2, backend._host_lanes, lambda: now[0], on_fault=faults.append
        )
        client = httpx.AsyncClient(transport=hang)
        executor._http_client = lambda: client
        probe = DeviceHealthProbe(executor)
        states = await probe.probe_once()
        assert states[healthy_box.url] == HEALTHY
        # Hang just started: attaching within budget -> busy.
        assert states[wedged_box.url] == BUSY
        now[0] += 15.0  # past the 10s attach budget, not yet wedge_after
        states = await probe.probe_once()
        assert states[wedged_box.url] == SUSPECT
        assert states[healthy_box.url] == HEALTHY
        now[0] += 30.0  # stall >> wedge_after
        states = await probe.probe_once()
        assert states[wedged_box.url] == WEDGED
        assert states[healthy_box.url] == HEALTHY
        assert wedged_box.meta["device_health"] == WEDGED
        assert "device_health" not in healthy_box.meta or (
            healthy_box.meta["device_health"] == HEALTHY
        )
        text = executor.metrics.registry.render()
        assert 'device_wedge_detected_total{chip_count="2"} 1' in text
        assert 'device_wedge_detected_total{chip_count="0"}' not in text
        assert faults.count(ATTACH_HANG) == 1  # one draw, one fault record
        await client.aclose()
    finally:
        await executor.close()


def test_spec_parses_and_counts_as_active():
    spec = FaultSpec.parse(f"attach_hang:0.5,attach_hang_lane:4,seed:{CHAOS_SEED}")
    assert spec.attach_hang == 0.5
    assert spec.attach_hang_lane == 4
    assert spec.active
    # Lane alone (no rate) injects nothing.
    assert not FaultSpec.parse("attach_hang_lane:4").active
    with pytest.raises(ValueError):
        FaultSpec.parse("attach_hang:1.5")


def test_backend_records_host_lanes_at_spawn():
    spec = FaultSpec.parse(f"attach_hang:1.0,seed:{CHAOS_SEED}")
    backend = FaultInjectingBackend(FakeBackend(distinct_urls=True), spec)

    async def run():
        sandbox = await backend.spawn(4)
        parsed = httpx.URL(sandbox.url)
        assert backend._host_lanes[f"{parsed.host}:{parsed.port}"] == 4
        transport = backend.http_transport()
        assert isinstance(transport, AttachHangTransport)

    import asyncio

    asyncio.run(run())
