"""Demand-adaptive warm-pool autoscaling (services/autoscaler.py).

Model dynamics run on a fake clock with zero sleeps (the scheduler-test
discipline): ramp-up is immediate, scale-down waits out the hysteresis
window, the idle reaper disposes only aged excess, and the kill switch
restores the static constant verbatim. Executor-level tests drive the real
pool bookkeeping through a FakeBackend.
"""

import asyncio

import pytest
from fakes import FakeBackend

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.autoscaler import (
    LaneSnapshot,
    PoolAutoscaler,
)
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.scheduler import SandboxScheduler
from bee_code_interpreter_fs_tpu.services.storage import Storage


class FakeClock:
    def __init__(self, start: float = 1000.0):
        self.t = start

    def __call__(self) -> float:
        return self.t

    def advance(self, seconds: float) -> None:
        self.t += seconds


def make_autoscaler(clock: FakeClock | None = None, **config_kwargs):
    config_kwargs.setdefault("executor_pod_queue_target_length", 2)
    config_kwargs.setdefault("pool_min_target", 1)
    config_kwargs.setdefault("pool_max_target", 16)
    config = Config(**config_kwargs)
    return PoolAutoscaler(config, clock=clock or FakeClock())


# --------------------------------------------------------------- pure model


def test_initial_target_is_static_clamped_into_bounds():
    assert make_autoscaler().target(0) == 2
    assert make_autoscaler(executor_pod_queue_target_length=50).target(0) == 16
    assert make_autoscaler(executor_pod_queue_target_length=1, pool_min_target=3).target(0) == 3


def test_static_target_zero_means_no_pool_in_both_modes():
    """Deployments that explicitly disabled pooling (target 0) must not
    gain one because the model started running."""
    for enabled in (True, False):
        scaler = make_autoscaler(
            executor_pod_queue_target_length=0, pool_autoscale_enabled=enabled
        )
        scaler.observe_arrival(0, LaneSnapshot(queued=9, in_use=9), jobs=4)
        assert scaler.evaluate(0, LaneSnapshot(queued=9, in_use=9)) == 0
        assert scaler.target(0) == 0


def test_kill_switch_restores_static_target_verbatim():
    scaler = make_autoscaler(pool_autoscale_enabled=False)
    scaler.observe_arrival(0, LaneSnapshot(queued=12, in_use=4))
    scaler.evaluate(0, LaneSnapshot(queued=12, in_use=4))
    assert scaler.target(0) == 2
    assert not scaler.snapshot()["enabled"]
    assert "lanes" not in scaler.snapshot()


def test_backlog_ramps_target_immediately():
    """Scale-UP applies on the arrival path: a burst's later arrivals see
    the target already raised (no sweep-cadence lag)."""
    clock = FakeClock()
    scaler = make_autoscaler(clock)
    for arriving in range(6):
        clock.advance(0.01)
        scaler.observe_arrival(
            0, LaneSnapshot(queued=arriving, in_use=0), jobs=1
        )
    # 5 queued + the arriving one = 6.
    assert scaler.target(0) == 6


def test_multi_job_ticket_counts_its_jobs():
    scaler = make_autoscaler(FakeClock())
    scaler.observe_arrival(4, LaneSnapshot(), jobs=8)
    assert scaler.target(4) == 8


def test_target_capped_at_max():
    scaler = make_autoscaler(FakeClock(), pool_max_target=4)
    scaler.observe_arrival(0, LaneSnapshot(queued=40, in_use=10))
    assert scaler.target(0) == 4


def test_spawn_ahead_needs_wait_evidence():
    """A fast SEQUENTIAL client (sky-high arrival rate, concurrency one,
    ~zero grant waits) must not inflate the target via rate x spawn-time:
    spawn-ahead only provisions once recent queue waits show supply
    actually lagging."""
    clock = FakeClock()
    scaler = make_autoscaler(clock, pool_target_queue_wait=0.5)
    quiet = LaneSnapshot(spawn_ewma=5.0, queue_wait_ewma=0.001)
    for _ in range(20):
        clock.advance(0.01)  # 100 arrivals/s
        scaler.observe_arrival(0, quiet)
    assert scaler.target(0) == 2  # the initial static clamp, unmoved

    # Same arrival stream WITH wait evidence: rate x spawn-time kicks in.
    pressured = LaneSnapshot(spawn_ewma=0.05, queue_wait_ewma=2.0)
    for _ in range(20):
        clock.advance(0.01)
        scaler.observe_arrival(0, pressured)
    # ~100/s x 0.05s spawn = ~5 spawn-ahead + 1 arriving + wait headroom.
    assert scaler.target(0) >= 6


def test_queue_wait_pressure_adds_headroom():
    """The queue-wait loop: sustained waiting past the acceptable wait
    raises demand even when instantaneous counts look covered."""
    scaler = make_autoscaler(FakeClock(), pool_target_queue_wait=0.5)
    raw = scaler.raw_demand(
        0, LaneSnapshot(queued=2, in_use=2, queue_wait_ewma=2.0)
    )
    assert raw == pytest.approx(4 + 2.0 / 0.5)


def test_scale_down_waits_out_hysteresis_then_steps():
    clock = FakeClock()
    scaler = make_autoscaler(
        clock, pool_scale_down_after=30.0, pool_min_target=1
    )
    scaler.observe_arrival(0, LaneSnapshot(queued=7))
    assert scaler.target(0) == 8
    idle = LaneSnapshot()
    # First evaluation to OBSERVE the drop starts the hysteresis clock.
    assert scaler.evaluate(0, idle) == 8
    # Still inside the window: unchanged.
    clock.advance(29.0)
    assert scaler.evaluate(0, idle) == 8
    # Window expires: ONE step per evaluation, not a cliff.
    clock.advance(2.0)
    assert scaler.evaluate(0, idle) == 7
    assert scaler.evaluate(0, idle) == 6
    for _ in range(10):
        scaler.evaluate(0, idle)
    assert scaler.target(0) == 1  # floor: pool_min_target


def test_demand_resurgence_resets_hysteresis():
    clock = FakeClock()
    scaler = make_autoscaler(clock, pool_scale_down_after=30.0)
    scaler.observe_arrival(0, LaneSnapshot(queued=5))
    assert scaler.target(0) == 6
    assert scaler.evaluate(0, LaneSnapshot()) == 6  # clock starts
    clock.advance(29.0)
    # Demand returns at the target just before the window expires: the
    # below-clock must reset, not carry over.
    assert scaler.evaluate(0, LaneSnapshot(in_use=6)) == 6
    clock.advance(2.0)
    assert scaler.evaluate(0, LaneSnapshot()) == 6  # fresh window


def test_stale_burst_rate_decays_with_idle_time():
    """The arrival-rate EWMA frozen at burst height must not keep
    spawn-ahead demand alive long after traffic stopped: the effective
    rate is bounded by 1 / time-since-last-arrival."""
    clock = FakeClock()
    scaler = make_autoscaler(clock, pool_target_queue_wait=0.5)
    hot = LaneSnapshot(spawn_ewma=2.0, queue_wait_ewma=5.0)
    for _ in range(10):
        clock.advance(0.01)
        scaler.observe_arrival(0, hot)
    burst_raw = scaler.raw_demand(0, hot)
    clock.advance(60.0)
    idle_raw = scaler.raw_demand(0, LaneSnapshot(spawn_ewma=2.0))
    assert idle_raw < 1.0 < burst_raw


def test_snapshot_shape():
    scaler = make_autoscaler(FakeClock())
    scaler.observe_arrival(4, LaneSnapshot(queued=3))
    body = scaler.snapshot()
    assert body["enabled"] and body["static_target"] == 2
    lane = body["lanes"]["4"]
    assert lane["target"] == 4
    assert lane["scale_ups"] == 1
    assert {"raw_demand", "arrival_rate_per_s", "scale_downs", "reaped"} <= set(lane)


# ---------------------------------------------------------- executor glue


class FakeSandboxServer:
    def __init__(self, executor: CodeExecutor):
        async def fake_post_execute(client, base, payload, timeout, sandbox):
            return {
                "stdout": "ok\n",
                "stderr": "",
                "exit_code": 0,
                "files": [],
                "warm": True,
            }

        executor._post_execute = fake_post_execute


def make_executor(backend, tmp_path, clock=None, **config_kwargs):
    config_kwargs.setdefault("executor_pod_queue_target_length", 2)
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        compile_cache_prewarm=False,
        **config_kwargs,
    )
    scheduler = None
    if clock is not None:
        scheduler = SandboxScheduler(config, clock=clock)
    executor = CodeExecutor(
        backend, Storage(config.file_storage_path), config, scheduler=scheduler
    )
    FakeSandboxServer(executor)
    return executor


async def settle(executor: CodeExecutor) -> None:
    for _ in range(200):
        pending = list(executor._dispose_tasks) + list(executor._fill_tasks)
        if not pending:
            return
        await asyncio.gather(*pending, return_exceptions=True)


async def test_sweep_reaps_idle_excess_after_decay(tmp_path):
    """The idle-chip reaper: a burst-inflated pool decays (hysteresis) and
    aged-idle excess sandboxes are disposed down to the shrunken target —
    warm chips stop squatting after the configured window."""
    clock = FakeClock()
    backend = FakeBackend()
    executor = make_executor(
        backend,
        tmp_path,
        clock=clock,
        executor_pod_queue_target_length=1,
        pool_scale_down_after=5.0,
        pool_idle_reap_seconds=10.0,
        pool_min_target=1,
    )
    try:
        # Inflate: a queued burst raises the target, fill to it.
        executor.autoscaler.observe_arrival(
            0, LaneSnapshot(queued=3), jobs=1
        )
        assert executor._lane_target(0) == 4
        await executor.fill_pool(0)
        assert len(executor._pool(0)) == 4
        # Demand gone: the first sweep starts the hysteresis clock, then
        # past the window the target steps down once per sweep.
        await executor.autoscale_sweep()
        clock.advance(6.0)
        for _ in range(3):
            await executor.autoscale_sweep()
        assert executor.autoscaler.target(0) == 1
        # Idle age not reached yet: nothing reaped despite the excess.
        assert len(executor._pool(0)) == 4
        assert backend.deletes == 0
        clock.advance(10.0)
        reaped = await executor.autoscale_sweep()
        await settle(executor)
        assert reaped == 3
        assert len(executor._pool(0)) == 1
        assert backend.deletes == 3
        assert executor.autoscaler.snapshot()["lanes"]["0"]["reaped"] == 3
        events = {
            (labels["chip_count"], labels["direction"]): value
            for labels, value in executor.metrics.pool_scale_events.samples()
        }
        assert events[("0", "reap")] == 3
        assert events[("0", "up")] >= 1
        assert events[("0", "down")] >= 3
    finally:
        await executor.close()


async def test_sweep_spawn_ahead_refills_without_a_waiter(tmp_path):
    """Spawn-ahead actuation: a raised target refills the pool from the
    sweep alone — before any request is waiting on the gap."""
    clock = FakeClock()
    backend = FakeBackend()
    executor = make_executor(
        backend, tmp_path, clock=clock, executor_pod_queue_target_length=1
    )
    try:
        executor.autoscaler.observe_arrival(0, LaneSnapshot(queued=4))
        assert executor._lane_target(0) == 5
        await executor.autoscale_sweep()
        await settle(executor)
        assert len(executor._pool(0)) == 5
    finally:
        await executor.close()


async def test_wedged_hosts_do_not_count_as_supply(tmp_path):
    """The device-health satellite: a pooled sandbox marked wedged stops
    counting toward the lane's supply, so the lane refills past it instead
    of reading 'full' forever — and a healthy pop skips it."""
    backend = FakeBackend()
    executor = make_executor(
        backend, tmp_path, executor_pod_queue_target_length=2
    )
    try:
        await executor.fill_pool(0)
        assert len(executor._pool(0)) == 2
        wedged = executor._pool(0)[0]
        wedged.meta["device_health"] = "wedged"
        assert executor._pool_supply(0) == 1
        await executor.fill_pool(0)
        assert len(executor._pool(0)) == 3  # refilled past the zombie
        assert executor._pool_supply(0) == 2
        popped = executor._pop_pool_sandbox(executor._pool(0))
        assert popped.meta.get("device_health") != "wedged"
        # The reaper never touches the zombie either (fencing actuation is
        # the ROADMAP item, not the autoscaler's job).
        assert wedged in executor._pool(0)
    finally:
        await executor.close()


async def test_pop_falls_back_to_wedged_when_nothing_else(tmp_path):
    backend = FakeBackend()
    executor = make_executor(
        backend, tmp_path, executor_pod_queue_target_length=1
    )
    try:
        await executor.fill_pool(0)
        only = executor._pool(0)[0]
        only.meta["device_health"] = "wedged"
        assert executor._pop_pool_sandbox(executor._pool(0)) is only
    finally:
        await executor.close()


async def test_spawn_burst_cap_paces_large_jumps(tmp_path):
    """APP_POOL_SPAWN_BURST: a big target jump ramps in bounded waves
    instead of stampeding the backend with every missing spawn at once —
    and the capped fill re-arms itself until the target is met."""

    class GaugedBackend(FakeBackend):
        def __init__(self):
            super().__init__()
            self.concurrent = 0
            self.peak = 0

        async def spawn(self, chip_count: int = 0):
            self.concurrent += 1
            self.peak = max(self.peak, self.concurrent)
            try:
                await asyncio.sleep(0)
                return await super().spawn(chip_count)
            finally:
                self.concurrent -= 1

    backend = GaugedBackend()
    executor = make_executor(
        backend,
        tmp_path,
        executor_pod_queue_target_length=9,
        pool_spawn_burst=3,
    )
    try:
        await executor.fill_pool(0)
        await settle(executor)
        assert len(executor._pool(0)) == 9
        assert backend.peak <= 3
    finally:
        await executor.close()


async def test_spawn_burst_cap_zero_is_uncapped(tmp_path):
    backend = FakeBackend()
    executor = make_executor(
        backend,
        tmp_path,
        executor_pod_queue_target_length=6,
        pool_spawn_burst=0,
    )
    try:
        await executor.fill_pool(0)
        assert len(executor._pool(0)) == 6
    finally:
        await executor.close()


@pytest.mark.parametrize("static_target, jobs", [(2, 8), (1, 4), (1, 6)])
async def test_kill_switch_executor_behavior_is_static(
    tmp_path, static_target, jobs
):
    """APP_POOL_AUTOSCALE_ENABLED=0 end to end: targets are the static
    constant, bursts do not move them, surplus sandboxes are disposed back
    down to it, the autoscaler emits nothing, the sweep is a no-op, and
    start_autoscaler refuses to run."""
    backend = FakeBackend()
    executor = make_executor(
        backend,
        tmp_path,
        executor_pod_queue_target_length=static_target,
        pool_autoscale_enabled=False,
    )
    try:
        assert executor._lane_target(0) == static_target
        results = await asyncio.gather(
            *(executor.execute("print('x')") for _ in range(jobs))
        )
        assert all(r.exit_code == 0 for r in results)
        await settle(executor)
        assert executor.autoscaler.target(0) == static_target
        assert executor._lane_target(0) == static_target
        assert len(executor._pool(0)) <= static_target
        assert executor.metrics.pool_scale_events.samples() == []
        assert await executor.autoscale_sweep() == 0
        assert executor.start_autoscaler() is None
        assert executor.statusz()["autoscaler"] == {
            "enabled": False,
            "min_target": 1,
            "max_target": 16,
            "static_target": static_target,
        }
    finally:
        await executor.close()


@pytest.mark.parametrize("jobs", [6, 4])
async def test_burst_retains_recycles_up_to_dynamic_target(tmp_path, jobs):
    """The demand loop end to end: a concurrent burst raises the lane
    target, so released sandboxes recycle into the pool (ready for the
    next wave) instead of being disposed back down to the static 1."""
    backend = FakeBackend()
    executor = make_executor(
        backend, tmp_path, executor_pod_queue_target_length=1
    )
    try:
        results = await asyncio.gather(
            *(executor.execute("print('x')") for _ in range(jobs))
        )
        assert all(r.exit_code == 0 for r in results)
        await settle(executor)
        assert executor._lane_target(0) > 1
        assert len(executor._pool(0)) > 1
        # The next wave pops warm: no new spawns needed for this depth.
        spawns_before = backend.spawns
        warm = min(len(executor._pool(0)), 4)
        again = await asyncio.gather(
            *(executor.execute("print('y')") for _ in range(warm))
        )
        assert all(r.exit_code == 0 for r in again)
        assert backend.spawns == spawns_before
    finally:
        await executor.close()


async def test_healthz_lane_supply_and_statusz_sections(tmp_path):
    backend = FakeBackend()
    executor = make_executor(
        backend, tmp_path, executor_pod_queue_target_length=2
    )
    try:
        await executor.fill_pool(0)
        supply = executor.lane_supply()
        assert supply["0"] == {
            "pool_target": 2,
            "pooled": 2,
            "in_use": 0,
            "spawning": 0,
        }
        body = executor.statusz()
        assert body["autoscaler"]["enabled"] is True
        lane = body["lanes"]["0"]
        assert lane["pool_target"] == 2
        assert lane["pooled"] == 2
    finally:
        await executor.close()


async def test_pool_gauges_sample_target_supply_and_chips(tmp_path):
    backend = FakeBackend()
    executor = make_executor(
        backend, tmp_path, executor_pod_queue_target_length=2
    )
    try:
        await executor.fill_pool(4)
        targets = dict(executor.metrics.pool_target.callback())
        supplies = dict(executor.metrics.pool_supply.callback())
        chips = dict(executor.metrics.pool_desired_chips.callback())
        assert targets[("4",)] == 2.0
        assert supplies[("4",)] == 2.0
        assert chips[("4",)] == 8.0  # target 2 x 4 chips
        rendered = executor.metrics.registry.render()
        assert "code_interpreter_pool_desired_chips" in rendered
    finally:
        await executor.close()


async def test_desired_chips_carries_unclamped_demand(tmp_path):
    """The HPA feed must express demand BEYOND the backend's declared
    capacity — a feed built on the clamped pool_target would read
    desired == current forever and never scale the node pool."""
    backend = FakeBackend(capacity=1)
    executor = make_executor(
        backend, tmp_path, executor_pod_queue_target_length=1
    )
    try:
        executor.autoscaler.observe_arrival(4, LaneSnapshot(queued=5))
        assert executor.autoscaler.target(4) == 6
        assert executor._lane_target(4) == 1  # physical clamp holds
        targets = dict(executor.metrics.pool_target.callback())
        chips = dict(executor.metrics.pool_desired_chips.callback())
        assert targets[("4",)] == 1.0  # operational verdict, clamped
        assert chips[("4",)] == 24.0  # 6 wanted x 4 chips: the HPA signal
    finally:
        await executor.close()


async def test_session_held_lane_visible_on_all_surfaces(tmp_path):
    """One membership rule for known lanes: a lane whose only resident is
    a session-parked sandbox must appear in the sweep, the /healthz
    supply rows, AND the gauges — managed-but-invisible is not a state."""
    backend = FakeBackend()
    executor = make_executor(
        backend, tmp_path, executor_pod_queue_target_length=1
    )
    try:
        executor._session_held[4] = 1
        assert 4 in executor._known_lanes()
        assert "4" in executor.lane_supply()
        assert ("4",) in dict(executor.metrics.pool_target.callback())
    finally:
        await executor.close()
