"""Seeded-chaos coverage for warm-pool autoscaling: spawn faults mid-ramp.

The invariant under fire: the TARGET is a pure function of demand, so spawn
failures (supply-side noise) must never oscillate it — a fault-riddled ramp
converges by retrying spawns toward a steady target, not by flapping the
target itself. Seeds pin the fault pattern (CHAOS_SEED env in CI's matrix,
the PR 2 discipline).
"""

import asyncio
import os

import pytest
from fakes import FakeBackend

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.autoscaler import LaneSnapshot
from bee_code_interpreter_fs_tpu.services.backends.faults import (
    FaultInjectingBackend,
    FaultSpec,
)
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.storage import Storage

SEEDS = [int(s) for s in os.environ.get("CHAOS_SEED", "7 23 1337").split()]
# The draws are seeded, which request takes which draw is not: at 30 % the
# pinned seeds' streams hold runs of up to four failures in a row (seed 23:
# draws 11 to 14), and a request whose whole ladder of three lands inside one
# fails, as it did under six loaded workers. A ladder one longer than the
# longest run is served whatever the interleaving.
SERVED_WHATEVER_THE_ORDER = {"executor_spawn_retry_attempts": 5}


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


def make_executor(backend, tmp_path, **config_kwargs) -> CodeExecutor:
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        executor_pod_queue_target_length=1,
        compile_cache_prewarm=False,
        # The breaker has its own suites; keep it out of the ramp's way.
        breaker_failure_threshold=1000,
        **config_kwargs,
    )
    executor = CodeExecutor(backend, Storage(config.file_storage_path), config)
    FakeSandboxServer(executor)
    return executor


async def settle(executor: CodeExecutor) -> None:
    for _ in range(400):
        pending = list(executor._dispose_tasks) + list(executor._fill_tasks)
        if not pending:
            return
        await asyncio.gather(*pending, return_exceptions=True)


@pytest.mark.parametrize("seed", SEEDS)
async def test_spawn_faults_mid_ramp_do_not_oscillate_target(tmp_path, seed):
    """50% seeded spawn failure while a queued burst ramps the target: the
    target must move monotonically up during the ramp (faults are not
    demand), and the burst-capped refill must still converge the pool to
    the target by retrying."""
    inner = FakeBackend()
    backend = FaultInjectingBackend(inner, FaultSpec(spawn_fail=0.5, seed=seed))
    executor = make_executor(backend, tmp_path, pool_spawn_burst=2)
    try:
        observed: list[int] = []
        original = executor.autoscaler.evaluate

        def spy(lane, snapshot):
            target = original(lane, snapshot)
            observed.append(target)
            return target

        executor.autoscaler.evaluate = spy
        # Demand: a held burst of 5 queued acquisitions' worth.
        executor.autoscaler.observe_arrival(0, LaneSnapshot(queued=4), jobs=1)
        target = executor.autoscaler.target(0)
        assert target == 5
        # Ramp under fire: sweeps keep re-arming fill_pool through faults.
        for _ in range(40):
            await executor.autoscale_sweep()
            await settle(executor)
            if len(executor._pool(0)) >= target:
                break
        assert len(executor._pool(0)) == target, (
            f"pool never converged under seed={seed}"
        )
        # No sweep ever LOWERED the target mid-ramp: hysteresis holds it
        # while spawn failures rage (supply noise is not demand).
        assert observed, "sweep never evaluated the lane"
        assert all(t == target for t in observed), observed
    finally:
        await executor.close()


@pytest.mark.parametrize("seed", SEEDS)
async def test_chaotic_burst_traffic_converges_and_serves(tmp_path, seed):
    """End to end under 30% spawn faults: a concurrent burst is fully
    served, the dynamic target retains recycled supply, and a follow-up
    wave rides warm pops."""
    inner = FakeBackend()
    backend = FaultInjectingBackend(inner, FaultSpec(spawn_fail=0.3, seed=seed))
    executor = make_executor(backend, tmp_path, **SERVED_WHATEVER_THE_ORDER)
    try:
        results = await asyncio.gather(
            *(executor.execute("print('x')") for _ in range(6))
        )
        assert all(r.exit_code == 0 for r in results)
        await settle(executor)
        assert executor._lane_target(0) > 1
        assert len(executor._pool(0)) >= 1
        again = await asyncio.gather(
            *(executor.execute("print('y')") for _ in range(3))
        )
        assert all(r.exit_code == 0 for r in again)
    finally:
        await executor.close()


@pytest.mark.parametrize("seed", SEEDS)
async def test_kill_switch_under_chaos_keeps_static_pool(tmp_path, seed):
    """The kill switch holds under fire too: with autoscaling off, a burst
    through a faulty backend leaves the static-target pool bound intact."""
    inner = FakeBackend()
    backend = FaultInjectingBackend(inner, FaultSpec(spawn_fail=0.3, seed=seed))
    executor = make_executor(
        backend,
        tmp_path,
        pool_autoscale_enabled=False,
        **SERVED_WHATEVER_THE_ORDER,
    )
    try:
        results = await asyncio.gather(
            *(executor.execute("print('x')") for _ in range(5))
        )
        assert all(r.exit_code == 0 for r in results)
        await settle(executor)
        assert executor._lane_target(0) == 1
        assert len(executor._pool(0)) <= 1
    finally:
        await executor.close()
