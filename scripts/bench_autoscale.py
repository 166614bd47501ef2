#!/usr/bin/env python3
"""Warm-pool autoscaling microbench: step-load burst waves against the real
local backend + C++ executor, demand-adaptive lane targets vs the static
pool knob.

Workload: WAVES bursts of JOBS concurrent trivial Executes, one wave per
GAP seconds — the step-load shape that made the static pool's weakness
visible in production traces (a burst queues behind one warm sandbox while
spawns catch up one acquire at a time, then the extra sandboxes are thrown
away and the NEXT wave pays the spawns again).

- ``static``     — APP_POOL_AUTOSCALE_ENABLED=0 with the historic target
  of 1: every wave beyond the warm sandbox pays spawn-scale acquire waits,
  and released surplus is disposed back down to 1 between waves.
- ``autoscaled`` — the demand model raises the lane target with the first
  wave, so its sandboxes are RETAINED at release; later waves pop warm.
  After the burst, hysteresis decays the target and the idle reaper
  disposes the excess — the scale-down half of the gate.

Acceptance (ISSUE verbatim, recorded in ``BENCH_autoscale.json``):
- autoscaled p50 acquire wait over the steady waves (wave 2+) <= 0.5x the
  static pool's (wave 1 is identical cold-start in both legs by design);
- idle-chip reaping observable in metrics within the configured window;
- the kill switch reproduces static-pool behavior exactly (target pinned
  at the constant, surplus disposed, zero scale events).

Usage:
    python scripts/bench_autoscale.py [--waves 4] [--jobs 6]
        [--out BENCH_autoscale.json] [--smoke]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

# Stay off the chip by default: this bench measures the control plane.
os.environ.setdefault("JAX_PLATFORMS", os.environ.get("BENCH_PLATFORM", "cpu"))

from bee_code_interpreter_fs_tpu.config import Config  # noqa: E402
from bee_code_interpreter_fs_tpu.services.backends.local import (  # noqa: E402
    LocalSandboxBackend,
)
from bee_code_interpreter_fs_tpu.services.code_executor import (  # noqa: E402
    CodeExecutor,
)
from bee_code_interpreter_fs_tpu.services.storage import Storage  # noqa: E402

GAP_S = 1.0  # seconds between waves (the step-load cadence)
SOURCE = "print('ok')"

# Autoscale dynamics knobs for the bench: a short sweep so the bench's
# scale-down window is seconds, with the hysteresis LONGER than the whole
# burst so no decay interferes mid-measurement.
SWEEP_INTERVAL = 0.5
SCALE_DOWN_AFTER = 8.0
IDLE_REAP = 2.0


def make_executor(tmp: Path, *, autoscale: bool, max_target: int) -> CodeExecutor:
    config = Config(
        file_storage_path=str(tmp / "storage"),
        local_sandbox_root=str(tmp / "sandboxes"),
        executor_pod_queue_target_length=1,
        pool_autoscale_enabled=autoscale,
        pool_min_target=1,
        pool_max_target=max_target,
        pool_autoscale_interval=SWEEP_INTERVAL,
        pool_scale_down_after=SCALE_DOWN_AFTER,
        pool_idle_reap_seconds=IDLE_REAP,
        compile_cache_prewarm=False,
        default_execution_timeout=120.0,
    )
    backend = LocalSandboxBackend(config, warm_import_jax=True)
    return CodeExecutor(backend, Storage(config.file_storage_path), config)


async def settle(executor: CodeExecutor, skip: set | None = None) -> None:
    """Wait out release/refill tasks. `skip` holds long-running sweeper
    tasks (the autoscaler loop lives in _fill_tasks until close()) that
    must not be awaited — they only finish at shutdown."""
    skip = skip or set()
    for _ in range(400):
        pending = [
            t
            for t in list(executor._dispose_tasks) + list(executor._fill_tasks)
            if t not in skip
        ]
        if not pending:
            return
        await asyncio.gather(*pending, return_exceptions=True)


def scale_events(executor: CodeExecutor) -> dict[str, float]:
    return {
        labels["direction"]: value
        for labels, value in executor.metrics.pool_scale_events.samples()
    }


async def run_waves(
    executor: CodeExecutor, waves: int, jobs: int
) -> list[list[float]]:
    """The step load: per wave, JOBS concurrent Executes; returns each
    wave's per-job acquire waits (the queue_wait phase: scheduler wait +
    any spawn the request had to ride)."""
    per_wave: list[list[float]] = []
    for wave in range(waves):
        results = await asyncio.gather(
            *(executor.execute(SOURCE) for _ in range(jobs))
        )
        for r in results:
            if r.exit_code != 0:
                raise RuntimeError(f"job failed: {r.stderr[:300]}")
        per_wave.append(
            [float(r.phases.get("queue_wait", 0.0)) for r in results]
        )
        if wave < waves - 1:
            await asyncio.sleep(GAP_S)
    return per_wave


def p50(values: list[float]) -> float:
    return round(statistics.median(values), 4)


async def run_bench(waves: int, jobs: int) -> dict:
    tmp = Path(tempfile.mkdtemp(prefix="bench-autoscale-"))
    max_target = jobs + 2

    # ---- static leg (the kill switch IS this leg) -----------------------
    executor = make_executor(tmp / "static", autoscale=False, max_target=max_target)
    kill_switch_ok = True
    try:
        static_waves = await run_waves(executor, waves, jobs)
        await settle(executor)
        # Static behavior reproduced exactly: the target never moved off
        # the constant, surplus warm sandboxes were disposed back down to
        # it, and the autoscaler emitted nothing.
        kill_switch_ok = (
            executor.autoscaler.target(0) == 1
            and executor._lane_target(0) == 1
            and len(executor._pool(0)) <= 1
            and not scale_events(executor)
            and executor.start_autoscaler() is None
        )
        static_pool_depth = len(executor._pool(0))
    finally:
        await executor.close()

    # ---- autoscaled leg -------------------------------------------------
    executor = make_executor(tmp / "auto", autoscale=True, max_target=max_target)
    try:
        sweeper = {executor.start_autoscaler()}
        auto_waves = await run_waves(executor, waves, jobs)
        burst_end = time.perf_counter()
        peak_target = executor._lane_target(0)
        await settle(executor, skip=sweeper)
        retained = len(executor._pool(0))

        # Scale-down: wait out hysteresis + stepped decay + idle age, and
        # watch the reaper reclaim the excess down to the floor.
        reap_window = (
            SCALE_DOWN_AFTER
            + (max_target - 1) * SWEEP_INTERVAL
            + IDLE_REAP
            + 5.0  # scheduling margin on a loaded host
        )
        reclaimed_in = None
        while time.perf_counter() - burst_end < reap_window:
            events = scale_events(executor)
            if len(executor._pool(0)) <= 1 and events.get("reap", 0) > 0:
                reclaimed_in = round(time.perf_counter() - burst_end, 3)
                break
            await asyncio.sleep(0.25)
        await settle(executor, skip=sweeper)
        auto_events = scale_events(executor)
        floor_depth = len(executor._pool(0))
    finally:
        await executor.close()

    # Collect subprocess transports while the loop is alive (spurious
    # "Event loop is closed" __del__ tracebacks otherwise).
    import gc

    gc.collect()
    await asyncio.sleep(0)

    # Wave 1 is identical cold-start work in both legs; the step-load
    # comparison is the steady waves behind it.
    static_steady = [w for wave in static_waves[1:] for w in wave]
    auto_steady = [w for wave in auto_waves[1:] for w in wave]
    static_p50 = p50(static_steady)
    auto_p50 = p50(auto_steady)
    checks = {
        # THE gate: autoscaled p50 acquire wait <= 0.5x static under the
        # step-load burst.
        "autoscaled_p50_halved": auto_p50 <= 0.5 * static_p50,
        # Scale-up actually happened and retained the wave's supply.
        "burst_retained_warm_supply": peak_target > 1 and retained > 1,
        # Idle chips reclaimed, observably (reap events in metrics),
        # within the configured window.
        "reaped_within_window": reclaimed_in is not None and floor_depth <= 1,
        # APP_POOL_AUTOSCALE_ENABLED=0 reproduced the static pool exactly.
        "kill_switch_static": kill_switch_ok,
    }
    return {
        "metric": (
            "p50 acquire wait (queue_wait phase) across steady step-load "
            "burst waves (wave 2+), autoscaled vs static warm pool; plus "
            "idle-chip reclamation and kill-switch equivalence"
        ),
        "config": {
            "waves": waves,
            "jobs_per_wave": jobs,
            "wave_gap_s": GAP_S,
            "platform": os.environ.get("JAX_PLATFORMS", ""),
            "static_target": 1,
            "pool_max_target": max_target,
            "sweep_interval_s": SWEEP_INTERVAL,
            "scale_down_after_s": SCALE_DOWN_AFTER,
            "idle_reap_s": IDLE_REAP,
        },
        "static": {
            "p50_wait_s": static_p50,
            "wave_p50s": [p50(w) for w in static_waves],
            "end_pool_depth": static_pool_depth,
        },
        "autoscaled": {
            "p50_wait_s": auto_p50,
            "wave_p50s": [p50(w) for w in auto_waves],
            "peak_target": peak_target,
            "retained_after_burst": retained,
            "reclaimed_to_floor_in_s": reclaimed_in,
            "reap_window_s": round(
                SCALE_DOWN_AFTER + (max_target - 1) * SWEEP_INTERVAL + IDLE_REAP + 5.0,
                3,
            ),
            "floor_pool_depth": floor_depth,
            "scale_events": auto_events,
        },
        "speedup": round(static_p50 / auto_p50, 2) if auto_p50 else None,
        "checks": checks,
        "ok": all(checks.values()),
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--waves", type=int, default=4)
    parser.add_argument("--jobs", type=int, default=6)
    parser.add_argument("--out", default=str(REPO_ROOT / "BENCH_autoscale.json"))
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="smaller step load + hard-fail on gate breakage (CI leg)",
    )
    args = parser.parse_args()
    if args.smoke:
        args.waves = min(args.waves, 3)
        args.jobs = min(args.jobs, 4)
    blob = asyncio.run(run_bench(max(2, args.waves), max(2, args.jobs)))
    Path(args.out).write_text(json.dumps(blob, indent=2) + "\n")
    print(json.dumps(blob))
    if not blob["ok"]:
        print("AUTOSCALE BENCH GATE FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
